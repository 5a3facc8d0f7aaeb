"""The benchmark's traffic generators, read from a cell file's parameters.

Round traffic is a seeded copy of the launcher's federated data
(``data/synthetic.py``: Dirichlet mixtures of unigram domains with a
copy-two-back structure, expected-token histograms, log-normal data
sizes) plus the launcher's device telemetry (``data/telemetry.py``: AR(1)
cpu and memory load, a battery that drains on the rounds a client holds
a slot and recharges otherwise, energy level equal to the battery).
Every seed draws the same amount of work: the round's shapes are fixed
by the cell file, the seed draws the tokens and the telemetry.
"""
from __future__ import annotations

import functools


# --------------------------------------------------------------------- #
# Federated round inputs
# --------------------------------------------------------------------- #
def _domain_logits(key, d: dict, vocab: int):
    import jax

    return jax.random.normal(jax.random.fold_in(key, 1),
                             (d["num_domains"], vocab)) * 2.0


def _client_logits(key, d: dict, vocab: int, cid, r):
    import jax
    import jax.numpy as jnp

    epoch = r // d["drift_period"]
    drifts = jax.random.bernoulli(
        jax.random.fold_in(jax.random.fold_in(key, 2 + 1000 * epoch), cid),
        d["drift_fraction"])
    eff = jnp.where(drifts, epoch, 0)
    k = jax.random.fold_in(jax.random.fold_in(jax.random.fold_in(key, 3), cid),
                           eff)
    mix = jax.random.dirichlet(k, jnp.full((d["num_domains"],),
                                           d["dirichlet_alpha"]))
    probs = jax.nn.softmax(_domain_logits(key, d, vocab), axis=-1)
    return jnp.log(mix @ probs + 1e-9)


def _client_tokens(key, d, vocab, cid, r, kr, batch, seq):
    import jax
    import jax.numpy as jnp

    logits = _client_logits(key, d, vocab, cid, r)
    k1, k2 = jax.random.split(jax.random.fold_in(kr, cid))
    toks = jax.random.categorical(k1, logits, shape=(batch, seq + 1))
    copy = jax.random.bernoulli(k2, 0.5, toks.shape)
    return jnp.where(copy, jnp.roll(toks, 2, axis=1), toks).astype(jnp.int32)


def _histogram(key, d, vocab, cid, r, bins):
    import jax.numpy as jnp

    probs = jnp.exp(_client_logits(key, d, vocab, cid, r))
    pad = (-vocab) % bins
    if pad:
        probs = jnp.concatenate([probs, jnp.zeros((pad,))])
    return probs.reshape(bins, -1).sum(-1)


def _telemetry_init(key, tel: dict, n: int):
    """The launcher's ``data/telemetry.init_telemetry``: cpu, mem and
    battery uniform in ``tel["init"]``; the energy level is the battery."""
    import jax

    ks = jax.random.split(jax.random.fold_in(key, 31), 3)
    lo, hi = tel["init"]
    u = lambda k: jax.random.uniform(k, (n,), minval=lo, maxval=hi)  # noqa: E731
    return {"cpu": u(ks[0]), "mem": u(ks[1]), "batt": u(ks[2])}


def _telemetry_step(key, tel: dict, state: dict, participated):
    """The launcher's ``data/telemetry.step_telemetry`` as its round loop
    calls it: cpu and mem take an AR(1) step, the battery drains on a
    slot's client and trickle-charges elsewhere. The launcher passes zero
    round energy, so the devices' battery capacities drop out."""
    import jax
    import jax.numpy as jnp

    k1, k2 = jax.random.split(key)
    mean = tel["ar_mean"]

    def ar(x, k):
        noise = jax.random.normal(k, x.shape) * tel["ar_noise"]
        return jnp.clip(mean + tel["ar_rho"] * (x - mean) + noise, 0.05, 1.0)

    batt = jnp.clip(state["batt"] - participated * tel["drain_per_round"]
                    + (~participated) * tel["recharge"], 0.0, 1.0)
    return {"cpu": ar(state["cpu"], k1), "mem": ar(state["mem"], k2),
            "batt": batt}


@functools.lru_cache(maxsize=None)
def round_input_fn(tr_json: str, vocab: int, slots: int, bins: int):
    """Jitted ``key -> telemetry`` before round 0, and
    ``(key, r, telemetry) -> (batch, next telemetry)`` for round ``r``,
    in the launcher's batch layout. Slot ``s`` trains on client
    ``(s + r * slots) % N``'s data, and those clients count as having
    taken part when the telemetry steps, as in ``launch/train.py``."""
    import json

    import jax
    import jax.numpy as jnp

    tr = json.loads(tr_json)
    d, tel = tr["data"], tr["telemetry"]
    n = tr["clients"]
    per_slot = tr["batch_per_slot"] * tr["local_steps"]
    seq = tr["seq_len"]

    def make(key, r, state):
        kr = jax.random.fold_in(jax.random.fold_in(key, 4), r)
        slot_ids = (jnp.arange(slots) + r * slots) % n
        toks = jax.vmap(
            lambda cid, k: _client_tokens(key, d, vocab, cid, r, k, per_slot,
                                          seq)
        )(slot_ids, jax.random.split(kr, slots)).reshape(-1, seq + 1)
        sizes = jnp.exp(jax.random.normal(jax.random.fold_in(key, 5), (n,))
                        * 0.5 + jnp.log(300.0)).astype(jnp.float32)
        hist = jax.vmap(lambda c: _histogram(key, d, vocab, c, r, bins))(
            jnp.arange(n))
        batch = {
            "tokens": toks,
            "slot_data_sizes": sizes[slot_ids],
            "telemetry_cpu": state["cpu"],
            "telemetry_mem": state["mem"],
            "telemetry_batt": state["batt"],
            "telemetry_energy": state["batt"] + 0.0,  # a buffer of its own
            "hist": hist.astype(jnp.float32),
        }
        part = jnp.zeros((n,), bool).at[slot_ids].set(True)
        nxt = _telemetry_step(jax.random.fold_in(kr, 6), tel, state, part)
        return batch, nxt

    return jax.jit(lambda key: _telemetry_init(key, tel, n)), jax.jit(make)


class RoundInputs:
    """The rounds' inputs in order from one seed's key: telemetry carries
    from round to round, so round ``r`` follows round ``r - 1``."""

    def __init__(self, tr: dict, vocab: int, slots: int, bins: int, key):
        import json

        self.init, self.make = round_input_fn(json.dumps(tr, sort_keys=True),
                                              vocab, slots, bins)
        self.key = key
        self.r = 0
        self.state = self.init(key)

    def next(self):
        import jax.numpy as jnp

        batch, self.state = self.make(self.key, jnp.asarray(self.r, jnp.int32),
                                      self.state)
        self.r += 1
        return batch
