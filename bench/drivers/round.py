"""Round cells: the FedFog LM round that ``launch/train.py`` builds for one
chip (``--scale full --pallas-agg``), driven round after round with the
benchmark's own inputs.

Set-up builds the compiled round and its state from the seed (weights
from the benchmark's generator), then drives the first ``check_rounds``
rounds through the same call and feed as the window; their readings
are what the reference checks. The window continues from that state.

The traffic is a training job of the ``check_rounds`` rounds from the
seed's state and inputs, run again and again: once a job's last round is
done, the next starts from the job's first state and inputs, made again
from the seed. A job longer than the training stays stable for would leave a
later, faster program more rounds in a window to diverge in: from the
seed's state the loss climbs from about the ninth round at an inner lr
of 0.05, and from about the seventeenth at 0.02, and turns NaN later on
some seeds.
"""
from __future__ import annotations

import collections
import dataclasses
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import common  # noqa: E402
import traffic  # noqa: E402


def launcher_argv(cell: dict) -> list:
    tr, prog = cell["traffic"], cell["cfg"]["program"]
    argv = ["--scale", "full", "--arch", prog["arch"],
            "--clients", str(tr["clients"]), "--slots", str(tr["slots"]),
            "--local-steps", str(tr["local_steps"]),
            "--batch-per-slot", str(tr["batch_per_slot"]),
            "--seq-len", str(tr["seq_len"]),
            "--inner-lr", str(tr["inner_lr"]), "--pallas-agg"]
    if "num_layers" in prog.get("replace", {}):
        argv += ["--layers", str(prog["replace"]["num_layers"])]
    return argv


def program_config(cell: dict):
    """The program's config for the cell: the registry's config with the
    file's ``replace`` applied, checked against every size in the file."""
    from repro.configs import get_config

    prog = cell["cfg"]["program"]
    rep = {k: tuple(v) if isinstance(v, list) else v
           for k, v in prog.get("replace", {}).items()}
    cfg = dataclasses.replace(get_config(prog["arch"]), **rep)
    for k, v in cell["cfg"]["sizes"].items():
        got = getattr(cfg, k)
        if (tuple(v) if isinstance(v, list) else v) != got:
            raise SystemExit(f"bench: {cell['config']}: program's {k} is "
                             f"{got!r}, the file says {v!r}")
    return cfg


def scheduler_config(sched: dict):
    """The program's scheduler with the cell file's gates and energy
    model (the paper's defaults in the shipped cells)."""
    from repro.core.coldstart import ColdStartConfig
    from repro.core.energy import EnergyModelConfig
    from repro.core.scheduler import SchedulerConfig

    e = sched["energy"]
    return SchedulerConfig(
        alpha=tuple(sched["alpha"]), theta_h=sched["theta_h"],
        theta_e=sched["theta_e0"], theta_d=sched["theta_d"],
        cold_start=ColdStartConfig(keep_alive_rounds=sched["keep_alive_rounds"]),
        energy_model=EnergyModelConfig(
            c_cpu=e["c_cpu"], c_tx=e["c_tx"], lam=e["lam"],
            theta_min=e["theta_min"], theta_max=e["theta_max"],
            cold_start_energy_j=e["cold_start_j"]))


class RoundCell:
    def __init__(self, cell: dict, devices):
        from repro.dist import make_rules
        from repro.fl import FLConfig, init_fl_state
        from repro.launch import train
        from repro.models import build_model

        self.cell, self.tr = cell, cell["traffic"]
        args = train.parse_args(launcher_argv(cell))
        cfg = program_config(cell)
        self.cfg, self.model = cfg, build_model(cfg)
        rules = make_rules(None, cfg, device_count=len(devices),
                           devices=list(devices))
        # As launch/train.py: slots fill the client ways.
        ways = rules.client_ways
        args.slots = ways * -(-args.slots // ways)
        args.clients = max(args.clients, 2 * args.slots)
        if (args.slots, args.clients) != (self.tr["slots"], self.tr["clients"]):
            raise SystemExit("bench: the cell's slots and clients are not the "
                             "launcher's for this device count")
        fl_cfg = FLConfig(num_clients=args.clients, slots=args.slots,
                          local_steps=args.local_steps,
                          inner_lr=args.inner_lr, use_pallas_agg=True,
                          scheduler=scheduler_config(self.tr["scheduler"]))
        self.fl_cfg = fl_cfg
        tokens_per_client = args.batch_per_slot * args.seq_len * args.local_steps
        flops_round = self.model.flops_per_token() * tokens_per_client
        self.round_fn = train._sharded_round_fn(
            args, cfg, self.model, fl_cfg, rules, flops_round)
        make_w = common.weights_fn(self.model.param_shapes(),
                                   cell["cfg"]["init"])
        self.make_weights = jax.jit(make_w)
        self._make_state = jax.jit(
            lambda k: dataclasses.replace(
                init_fl_state(self.model, fl_cfg, jax.random.fold_in(k, 1)),
                params=make_w(jax.random.fold_in(k, 2))),
            out_shardings=self.round_fn.input_shardings[0][0])
        self._trainers = {}  # (quant, half) -> the reference's local training
        self.leaf_names = [jax.tree_util.keystr(path) for path, _ in
                           jax.tree_util.tree_flatten_with_path(
                               self.model.param_shapes())[0]]
        self._mu_norms = jax.jit(lambda t: [
            jnp.sqrt(jnp.sum(jnp.square(x))) for x in jax.tree.leaves(t)])

    # -- inputs ------------------------------------------------------- #
    def fl(self) -> dict:
        f = self.fl_cfg
        return {"slots": f.slots, "clients": f.num_clients,
                "local_steps": f.local_steps,
                "batch_per_slot": self.tr["batch_per_slot"],
                "seq_len": self.tr["seq_len"],
                "inner_lr": f.inner_lr, "inner_momentum": f.inner_momentum,
                "server_lr": f.server_lr, "server_momentum": f.server_momentum,
                "hist_bins": f.hist_bins}

    def inputs(self, key) -> traffic.RoundInputs:
        return traffic.RoundInputs(self.tr, self.cfg.vocab_size,
                                   self.fl_cfg.slots, self.fl_cfg.hist_bins,
                                   key)

    # -- the program -------------------------------------------------- #
    def start(self, seed: int):
        """Set-up from ``seed``: the state, then the checked rounds."""
        key = common.seed_key(seed)
        self.key = key
        self._new_job()
        losses, mu_norms, admitted, eligible = [], [], [], []
        for _ in range(self.tr["check_rounds"]):
            self.job_round += 1
            self.state, m = self.round_fn(self.state, self.feed.next())
            losses.append(float(m["loss"]))
            admitted.append(int(m["slot_participation"]))
            eligible.append(int(m["num_selected"]))
            mu_norms.append([float(x) for x in
                             self._mu_norms(self.state.server_mu)])
        params = jax.device_get(self.state.params)
        self.checked_losses = losses
        return {"losses": losses, "mu_norms": mu_norms, "admitted": admitted,
                "eligible": eligible, "params": params}

    def _new_job(self) -> None:
        """The job's first state and its inputs, made anew from the seed
        (the round's own memory leaves no room to keep a copy). The last
        job's state is let go first, to be freed once its rounds are done."""
        self.state = None
        self.state = self._make_state(self.key)
        self.feed = self.inputs(self.key)
        self.job_round = 0

    def window(self, seconds: float, span) -> dict:
        """Rounds back to back, job after job, with up to a job's rounds
        less one sent ahead of the one whose loss is read, so that the chip
        stays fed while the host stands still. Once ``seconds`` have
        passed nothing more is sent; the window closes when every round
        sent has been read, so all of them count, over all of that time.
        A round whose loss is not finite has failed; a round that repeats
        a checked one reads its loss against the checked loss
        (``replay_gap``)."""
        tr = self.tr
        job = tr["check_rounds"]
        ahead = job - 1  # more would hold three jobs' states at once: no room
        pending = collections.deque()  # (round of its job, loss)
        rounds, failed, jobs, replay_gap = 0, 0, 0, 0.0

        def read():
            nonlocal failed, replay_gap
            r, loss = pending.popleft()
            loss = float(loss)
            if r < len(self.checked_losses):
                gap = abs(loss - self.checked_losses[r])
                replay_gap = max(replay_gap, gap if np.isfinite(gap) else np.inf)
            failed += not np.isfinite(loss)

        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            with span("bench.round"):
                if self.job_round == job:
                    self._new_job()
                    jobs += 1
                self.state, m = self.round_fn(self.state, self.feed.next())
                pending.append((self.job_round, m["loss"]))
                self.job_round += 1
                rounds += 1
                while len(pending) > ahead:
                    read()
        while pending:
            read()
        wall = time.perf_counter() - t0
        print(f"[bench] window: {rounds} rounds, {jobs} jobs begun, "
              f"replay gap {replay_gap!r}", file=sys.stderr, flush=True)
        tokens = (tr["slots"] * tr["local_steps"] * tr["batch_per_slot"]
                  * tr["seq_len"])
        params = jax.tree.leaves(self.model.param_shapes())
        return {"attempted": rounds, "failed": failed, "wall_s": wall,
                "round_s": wall / rounds, "rounds": rounds,
                "tokens": tokens * rounds, "slots": tr["slots"],
                "params": sum(x.size for x in params),
                "param_bytes": sum(x.size * x.dtype.itemsize for x in params)}

    def stop(self) -> None:
        """Free the program's device state before the reference runs."""
        self.state = None
        self.round_fn = None

    # -- the reference ------------------------------------------------ #
    def reference(self, seed: int, ref_mod, quant=None, half=False) -> dict:
        import fedround
        from numerics import Numerics

        if (quant, half) not in self._trainers:
            sizes, nm = self.cell["cfg"]["sizes"], Numerics(quant)
            self._trainers[quant, half] = fedround.make_local_train(
                lambda p, b: ref_mod.loss(p, b, sizes, nm), self.fl(), half)
        key = common.seed_key(seed)
        feed = self.inputs(key)
        batches = [jax.device_get(feed.next())
                   for _ in range(self.tr["check_rounds"])]
        params = self.make_weights(jax.random.fold_in(key, 2))
        out = fedround.run_rounds(self._trainers[quant, half], params,
                                  batches, self.fl(), self.tr["scheduler"],
                                  self.cell["cfg"]["sizes"])
        p0 = self.make_weights(jax.random.fold_in(key, 2))
        out["change"] = fedround.change_norms(out.pop("params"), p0)
        return out

    def program_change(self, prog: dict, seed: int) -> list:
        import fedround

        p0 = self.make_weights(jax.random.fold_in(common.seed_key(seed), 2))
        return fedround.change_norms(jax.device_put(prog["params"]), p0)

    def check(self, seed: int, prog: dict) -> dict:
        """The reference's readings of the checked rounds (``readings``)."""
        ref = self.reference(seed, common.reference_module(self.cell))
        prog["change"] = self.program_change(prog, seed)
        return readings(prog, ref, self.leaf_names)


Cell = RoundCell


def readings(prog: dict, ref: dict, names=None) -> dict:
    """The numbers compared: the rounds in which the program's gate let
    another number of the N clients through, or admitted another number
    of slots, than the reference's (exact); the worst
    relative gap of each round's loss; of the server momentum's leaf
    norms after the first round that admitted a slot (the first
    aggregated update as FedAvgM holds it); and of the leaf norms of the
    parameters' change over the checked rounds. A leaf gap is measured
    against the larger of the reference leaf's norm and the median
    leaf's; leaves whose reference update is under a thousandth of the
    median leaf's move by round-off alone and are left out of both. The
    same gaps against each kept leaf's own norm (``*_own``) and the
    leaves left out are reported beside them, not compared."""
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    loss_gap = float(np.max(np.abs(lp - lr) / np.abs(lr)))
    adm_ref = [int(sum(m)) for m in ref["masks"]]
    gate_gap = sum(int(a != b or e != f) for a, b, e, f in zip(
        prog["admitted"], adm_ref, prog["eligible"], ref["eligible"]))
    first = next((i for i, m in enumerate(ref["masks"]) if any(m)), None)
    if first is None:
        raise SystemExit("bench: the reference admitted no slot in the "
                         "checked rounds")
    gr = np.asarray(ref["mu_norms"][first])
    gp = np.asarray(prog["mu_norms"][first])
    med = float(np.median(gr))
    keep = gr >= 1e-3 * med
    names = list(names) if names is not None else [str(i) for i in range(gr.size)]

    def gaps(p, r):
        p, r = np.asarray(p)[keep], np.asarray(r)[keep]
        m = float(np.median(r))
        moved = r > 0  # a kept leaf's bf16 values may still not move
        return (float(np.max(np.abs(p - r) / np.maximum(r, m))),
                float(np.max(np.abs(p - r)[moved] / r[moved])))

    update_gap, update_own = gaps(gp, gr)
    change_gap, change_own = gaps(prog["change"], ref["change"])
    return {"gate_gap": gate_gap, "loss_gap": loss_gap,
            "update_gap": update_gap, "change_gap": change_gap,
            "update_gap_own": update_own, "change_gap_own": change_own,
            "admitted_program": list(prog["admitted"]),
            "admitted_reference": adm_ref,
            "eligible_program": list(prog["eligible"]),
            "eligible_reference": list(ref["eligible"]),
            "leaves_kept": int(keep.sum()), "leaves": int(keep.size),
            "leaves_left_out": {n: float(g) for n, g, k in
                                zip(names, gr, keep) if not k},
            "update_median": med}
