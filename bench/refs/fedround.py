"""Plain FedFog round: the reference the round cells are compared with.

One round, as the paper's Fig. 1 states it and the cell file sizes it:
the Eq. 3 gate over the N-client registry (health ``alpha . (cpu, mem,
batt) > theta_h``; energy level above the client's own threshold; drift
``KL(hist_t || hist_{t-1}) < theta_d``), eligible clients fill the C
slots first; each slot trains E local steps of Nesterov SGD with
momentum from the global model on its own rows, in float32, its
parameters stored in the configuration's dtypes after every step; the
server takes the data-size-weighted mean of the admitted slots' deltas
into FedAvgM momentum and applies it. After the round each eligible
client is billed its round's energy (the section IV.F model: compute
cycles, uplink bytes, a cold start where its container was not kept
warm), and every client's energy threshold moves by the Eq. 10
controller: ``theta_e <- clip(theta_e exp(lam (E_i / E_avg - 1)))``.
Weights and inputs come from the benchmark's generators; nothing is
taken from the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_HIST_EPS = 1e-8


def _normalize(h):
    h = h + _HIST_EPS
    return h / jnp.sum(h, axis=-1, keepdims=True)


class Gate:
    """The scheduler's state over the N-client registry, from a fresh
    start: uniform drift references, every threshold at ``theta_e0``,
    every container cold. ``client_energy_j`` is what one eligible
    client's round costs before any cold start."""

    def __init__(self, n: int, bins: int, sched: dict, client_energy_j: float):
        self.s, self.base = sched, client_energy_j
        self.prev = jnp.full((n, bins), 1.0 / bins, jnp.float32)
        self.theta = jnp.full((n,), sched["theta_e0"], jnp.float32)
        self.warm = jnp.zeros((n,), bool)
        self.last = jnp.full((n,), -1, jnp.int32)
        self.r = 0

    def step(self, batch) -> int:
        """One round's gate; returns the number of eligible clients and
        advances the drift references, the containers and the thresholds."""
        s, e = self.s, self.s["energy"]
        a = s["alpha"]
        health = (a[0] * batch["telemetry_cpu"] + a[1] * batch["telemetry_mem"]
                  + a[2] * batch["telemetry_batt"])
        p, q = _normalize(batch["hist"]), self.prev
        drift = jnp.sum(p * (jnp.log(p + _HIST_EPS) - jnp.log(q + _HIST_EPS)), -1)
        ok = ((health > s["theta_h"]) & (batch["telemetry_energy"] > self.theta)
              & (drift < s["theta_d"]))
        spent = jnp.where(ok, self.base + jnp.where(self.warm, 0.0,
                                                    e["cold_start_j"]), 0.0)
        self.last = jnp.where(ok, self.r, self.last)
        self.warm = ok | (self.warm & (self.last >= 0)
                          & (self.r - self.last < s["keep_alive_rounds"]))
        ratio = spent / (jnp.mean(spent) + 1e-8)
        self.theta = jnp.clip(self.theta * jnp.exp(e["lam"] * (ratio - 1.0)),
                              e["theta_min"], e["theta_max"])
        self.prev = p
        self.r += 1
        return int(jnp.sum(ok))


def client_energy_j(params, sizes: dict, fl: dict, sched: dict) -> float:
    """Section IV.F: one client's round, ``c_cpu`` per compute cycle (the
    model's training cycles per token per non-embedding parameter, times
    its tokens) plus ``c_tx`` per uplink byte of its delta."""
    e = sched["energy"]
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    emb = sizes["vocab_size"] * sizes["d_model"] * (
        1 if sizes["tie_embeddings"] else 2)
    tokens = fl["local_steps"] * fl["batch_per_slot"] * fl["seq_len"]
    cycles = e["cycles_per_param_token"] * (n - emb) * tokens
    return e["c_cpu"] * cycles + e["c_tx"] * e["wire_bytes_per_param"] * n


@jax.jit
def _leaf_norms(t):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in jax.tree.leaves(t)]


@jax.jit
def _change_norms(s, t):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)
                                        - y.astype(jnp.float32))))
            for x, y in zip(jax.tree.leaves(s), jax.tree.leaves(t))]


def leaf_norms(tree) -> list:
    return [float(x) for x in _leaf_norms(tree)]


def change_norms(a, b) -> list:
    return [float(x) for x in _change_norms(a, b)]


def make_local_train(loss_fn, fl: dict, half: bool):
    """Jitted ``(params, rows (E*B, S+1)) -> (params, last step's loss)``.
    ``half``: the fault that leaves out half of each step's batch (half
    its rows, or of a lone row's tokens) and takes the mean over the
    rest."""
    e, b = fl["local_steps"], fl["batch_per_slot"]
    lr, m = fl["inner_lr"], fl["inner_momentum"]

    def train(p, rows):
        steps = rows.reshape((e, b) + rows.shape[1:])
        if half and b > 1:
            steps = steps[:, : b // 2]
        elif half:
            steps = steps[:, :, : steps.shape[2] // 2 + 1]
        f32 = lambda t: jax.tree.map(lambda x: x.astype(jnp.float32), t)  # noqa: E731

        def one(carry, batch):
            p, mu = carry
            loss, g = jax.value_and_grad(loss_fn)(f32(p), batch)
            mu = jax.tree.map(lambda a, c: m * a + c, mu, g)
            p = jax.tree.map(
                lambda x, a, c: (x.astype(jnp.float32) - lr * (m * a + c)
                                 ).astype(x.dtype), p, mu, g)
            return (p, mu), loss

        mu0 = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), p)
        (p, _), losses = jax.lax.scan(one, (p, mu0), steps)
        return p, losses[-1]

    return jax.jit(train)


@jax.jit
def _accumulate(mu, p, p0, w):
    return jax.tree.map(
        lambda a, x, y: a + w * (x.astype(jnp.float32) - y.astype(jnp.float32)),
        mu, p, p0)


@jax.jit
def _decay(mu, m):
    return jax.tree.map(lambda a: m * a, mu)


@jax.jit
def _apply(p0, mu, lr):
    return jax.tree.map(
        lambda x, a: (x.astype(jnp.float32) + lr * a).astype(x.dtype), p0, mu)


def run_rounds(train, params, batches, fl: dict, sched: dict,
               sizes: dict) -> dict:
    """The reference over ``batches`` (one launcher batch per round) from
    ``params`` (consumed), ``train`` from :func:`make_local_train`.
    Returns each round's loss (the mean over slots of the last local
    step's), the server momentum's leaf norms after each round, the slot
    masks, the number of eligible clients, and the final parameters."""
    c = fl["slots"]
    mu = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), params)
    gate = Gate(fl["clients"], fl["hist_bins"], sched,
                client_energy_j(params, sizes, fl, sched))
    losses, mu_norms, masks, eligible = [], [], [], []
    for batch in batches:
        n_ok = gate.step(batch)
        eligible.append(n_ok)
        mask = [s < n_ok for s in range(c)]
        sizes = np.asarray(batch["slot_data_sizes"], np.float64)
        wsum = float(sum(s for s, k in zip(sizes, mask) if k))
        mu = _decay(mu, fl["server_momentum"])
        rows = batch["tokens"].reshape((c, -1) + batch["tokens"].shape[1:])
        slot_losses = []
        for s in range(c):
            p, loss = train(params, rows[s])
            slot_losses.append(float(loss))
            if mask[s]:
                mu = _accumulate(mu, p, params, sizes[s] / wsum)
            del p
        params = _apply(params, mu, fl["server_lr"])
        losses.append(float(np.mean(slot_losses)))
        mu_norms.append(leaf_norms(mu))
        masks.append(mask)
    del mu
    return {"losses": losses, "mu_norms": mu_norms, "masks": masks,
            "eligible": eligible, "params": params}
