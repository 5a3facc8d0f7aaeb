"""One-process smoke run of FedFog's three engines on a TPU.

    python chip_smoke.py             # one chip: the three phases below
    python chip_smoke.py --chips 4   # a four-chip host: the sharded round only

One chip, three phases, each through the program's own entry points:

1. simulator: the paper's HAR-like and EMNIST-like tasks at the
   ``examples/edge_sim.py`` defaults (48 clients, top-k 16) for a few
   scanned rounds with the Pallas delta-pipeline kernel compiled for the
   chip, against the same run with the kernel off;
2. FedFog LM round: ``repro.launch.train`` on rwkv6-1.6b at its
   published widths, depth cut to what one chip's 16 GB holds, two
   client slots vmapped on the chip, ``--pallas-agg``; then the kernel
   against ``kernels/delta_pipeline/ref.py`` at the round's own (C, P);
3. serving: ``repro.launch.serve --engine continuous --attn paged`` on
   hymba-1.5b at its published 32 layers; then the paged-kernel decode
   against the dense-gather path, on logits.

Four chips: the round on the 2 (client) x 2 (zero) plan, whose compiled
HLO must hold exactly one inter-client all-reduce (asserted by
``repro.launch.train``), against the same round on one device of the
host.

Weights and data are random, made from fixed seeds. Each phase prints its
sizes, compile set-up and checks; the last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU, outside a checkout, or when any check fails, the script
exits non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

# Tolerances, each with its reason.
# Simulator, kernel on vs off: the two runs differ first in the f32
# summation order of Eq. 6, which later local training amplifies. At
# this phase's size (48 clients, top-k 16, 4 rounds) that reads 5e-6 on
# the CPU and up to 7.75e-3 (EMNIST) on a TPU v5e; the kernel leaving
# out one admitted client reads 0.056 (HAR) and 0.068 (EMNIST) on the
# CPU (planted in tests/test_chip_smoke.py). The limit sits near the
# geometric midpoint of the TPU reading and the fault.
SIM_UPDATE_RTOL = 2e-2
# delta_pipeline vs ref.py, both f32 sums (the kernel's MXU dot at
# HIGHEST, the reference under default_matmul_precision("highest")):
# they differ in summation order only, a few f32 ulps (1.2e-7). One bf16
# MXU pass would be off by about 2^-9 = 2e-3.
PIPELINE_MU_RTOL = 1e-5
# The applied params additionally round base + step to f32, which can
# flip one ulp of the base where the two steps differ.
PIPELINE_STEP_RTOL = 1e-4
# Paged kernel vs the float32 attention of its dense-gather reference,
# on one layer of the real pool: the kernel computes in f32 (HIGHEST
# dots) from the bf16 pool and rounds its output to bf16, at most half
# an ulp (2^-8 of an element); bounded here by one ulp (2^-7) of the
# layer's largest output.
ATTN_ATOL_FRAC = 2.0 ** -7
# Whole decode step on logits, paged vs dense-gather path, both with f32
# weights, pool and HIGHEST matmuls: they differ only in the attention's
# summation order (online vs one softmax), ~1e-6 per layer, left room
# here for 32 layers of random-weight amplification. A bf16 step is 3-5%
# from f32 over 32 layers (CPU rehearsal); a misread page is O(1).
LOGITS_RTOL = 1e-3
# Four chips vs one device: the sharded round sums zero-sharded bf16
# gradients and the client psum in another order, and bf16 params round
# every local step, so the two disagree by a few percent of the update
# in norm: 0.0275 in a 2-layer reduced-width rehearsal on four virtual
# CPU devices, where the sharded aggregate leaving out one admitted
# client reads 0.59 (planted in tests/test_chip_smoke.py). The limit is
# near the geometric midpoint of the two. The per-round losses see only
# the forward pass's bf16 rounding (1.7e-3 there, 2.1e-4 on TPU v5e).
SHARDED_UPDATE_RTOL = 0.125
SHARDED_LOSS_RTOL = 1e-2


def _import_repro():
    src = os.path.join(HERE, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SystemExit(
            f"chip_smoke.py: no repro package under {src}; run it from a "
            "checkout of the repository"
        )
    if src not in sys.path:
        sys.path.insert(0, src)


def _report(phase: str, result: dict) -> None:
    print(f"[{phase}] " + json.dumps(result, sort_keys=True), flush=True)


def _require(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(f"chip_smoke check failed: {what}")


def _rel_err(got, want, start, what: str) -> float:
    """``|got - want| / |want - start|`` over every leaf of three param
    trees: how far a run landed from the reference run, relative to how
    far the reference moved. Summed in f32 on the first device, so that a
    full-width model never makes an f64 copy on the host."""
    import jax
    import jax.numpy as jnp

    def sq(a, b):
        return sum(
            jnp.sum(jnp.square(x.astype(jnp.float32) - y.astype(jnp.float32)))
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
        )

    diff, upd = (float(x) for x in jax.jit(
        lambda g, w, s: (sq(g, w), sq(w, s))
    )(got, want, start))
    _require(upd > 0, f"{what}: the reference run moved the model")
    return math.sqrt(diff / upd)


# --------------------------------------------------------------------- #
# phase 1: the paper-scale simulator
# --------------------------------------------------------------------- #
def phase_simulator(*, tasks=("har", "emnist"), clients=48, topk=16,
                    rounds=4) -> dict:
    """Scanned rounds with the delta-pipeline kernel on and off; the
    final models must agree within ``SIM_UPDATE_RTOL`` of the update."""
    import jax
    import numpy as np

    from repro.fl.simulator import FedFogSimulator, SimulatorConfig

    out = {}
    for task in tasks:
        runs = {}
        for kernel in (True, False):
            sim = FedFogSimulator(SimulatorConfig(
                task=task, num_clients=clients, rounds=rounds, top_k=topk,
                policy="fedfog", drift_period=max(rounds // 2, 1),
                attack="label_flip", attack_fraction=0.1, seed=0,
                use_pallas_agg=kernel,
            ))
            p0 = jax.device_get(sim.params)
            t0 = time.perf_counter()
            compiled = sim.aot_scanned(rounds)
            compile_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            h = sim.run_scanned_with(compiled, rounds)
            run_s = time.perf_counter() - t0
            runs[kernel] = dict(
                accuracy_finite=bool(np.isfinite(h["accuracy"]).all()),
                params=jax.device_get(sim.params), p0=p0,
                compile_s=compile_s, run_s=run_s,
                final_accuracy=h["final_accuracy"],
                kernel_in_hlo="tpu_custom_call" in compiled.as_text(),
            )
        on, off = runs[True], runs[False]
        rel = _rel_err(on["params"], off["params"], off["p0"], task)
        out[task] = dict(
            clients=clients, topk=topk, rounds=rounds,
            update_rel_err=rel,
            accuracy_finite=on["accuracy_finite"] and off["accuracy_finite"],
            kernel_in_hlo=on["kernel_in_hlo"],
            compile_s={"kernel": on["compile_s"], "ref": off["compile_s"]},
            run_s={"kernel": on["run_s"], "ref": off["run_s"]},
            final_accuracy={"kernel": on["final_accuracy"],
                            "ref": off["final_accuracy"]},
        )
    _report("simulator", out)
    for task, r in out.items():
        _require(r["accuracy_finite"], f"{task} accuracy finite")
        _require(r["update_rel_err"] <= SIM_UPDATE_RTOL,
                 f"{task}: kernel vs reference update rel err "
                 f"{r['update_rel_err']:.3e}")
    return out


# --------------------------------------------------------------------- #
# phase 2: the FedFog LM round
# --------------------------------------------------------------------- #
def _round_argv(arch, layers, slots, rounds, extra):
    return [
        "--scale", "full", "--arch", arch, "--layers", str(layers),
        "--slots", str(slots), "--rounds", str(rounds), "--pallas-agg",
        *extra,
    ]


def check_pipeline(base, mu, slots: int, *, seed: int = 0,
                   block: int = 1 << 25) -> dict:
    """``delta_pipeline_apply`` on a seeded (slots, P) delta buffer with the
    round's server state (fused ``base`` params, f32 momentum ``mu``) and
    the round's gates (FedAvgM, momentum 0.9, lr 1), against
    ``delta_pipeline_ref`` computed in column blocks so that it fits."""
    import functools

    import jax
    import jax.numpy as jnp

    from repro.kernels.delta_pipeline import (
        delta_pipeline_apply,
        delta_pipeline_ref,
    )

    p = int(base.shape[0])
    upd = 1e-3 * jax.random.normal(
        jax.random.PRNGKey(seed), (slots, p), jnp.float32
    )
    mask = jnp.ones((slots,), bool)
    sizes = 100.0 * jnp.arange(1, slots + 1, dtype=jnp.float32)
    gates = dict(server_optimizer="fedavgm", server_momentum=0.9)
    t0 = time.perf_counter()
    lowered = delta_pipeline_apply.lower(
        upd, base, mask, sizes, 1.0, momentum=mu, **gates
    )
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0
    out_k, mu_k = compiled(upd, base, mask, sizes, 1.0, momentum=mu)

    size = min(block, p)

    @functools.partial(jax.jit, static_argnames="size")
    def block_sq(upd, base, mu, out_k, mu_k, lo, start, *, size):
        def sl(x):
            return jax.lax.dynamic_slice_in_dim(x, start, size, x.ndim - 1)

        out_r, mu_r = delta_pipeline_ref(
            sl(upd), sl(base), mask, sizes, 1.0, momentum=sl(mu), **gates
        )
        keep = (start + jnp.arange(size)) >= lo  # clamped last block

        def sq(x):
            return jnp.sum(jnp.where(keep, jnp.square(x), 0.0))

        return jnp.stack([
            sq(sl(out_k) - out_r), sq(out_r - sl(base)),
            sq(sl(mu_k) - mu_r), sq(mu_r),
        ])

    acc = [0.0] * 4
    with jax.default_matmul_precision("highest"):
        for lo in range(0, p, size):
            start = min(lo, p - size)
            part = block_sq(upd, base, mu, out_k, mu_k, lo, start, size=size)
            acc = [a + float(b) for a, b in zip(acc, part)]
    step_rel = math.sqrt(acc[0] / max(acc[1], 1e-30))
    mu_rel = math.sqrt(acc[2] / max(acc[3], 1e-30))
    res = dict(
        c=slots, p=p, compile_s=compile_s,
        kernel_in_hlo="tpu_custom_call" in compiled.as_text(),
        step_rel_err=step_rel, mu_rel_err=mu_rel,
    )
    _report("fl_round.pipeline", res)
    _require(mu_rel <= PIPELINE_MU_RTOL,
             f"pipeline momentum rel err {mu_rel:.3e}")
    _require(step_rel <= PIPELINE_STEP_RTOL,
             f"pipeline applied-step rel err {step_rel:.3e}")
    return res


def phase_fl_round(*, arch="rwkv6-1.6b", layers=5, slots=2, rounds=3,
                   extra=()) -> dict:
    """``repro.launch.train`` for ``rounds`` rounds; finite losses, then
    the pipeline kernel check on the trained server state."""
    import numpy as np

    from repro.fl.fuse import fuse_vector
    from repro.launch import train

    t0 = time.perf_counter()
    run = train.main(_round_argv(arch, layers, slots, rounds, extra))
    wall = time.perf_counter() - t0
    losses = [h["loss"] for h in run.history]
    res = dict(arch=arch, layers=layers, slots=slots, rounds=rounds,
               losses=losses, wall_s_incl_compile=wall)
    _report("fl_round", res)
    _require(len(losses) == rounds and np.isfinite(losses).all(),
             f"round losses {losses}")
    base, _ = fuse_vector(run.state.params)
    mu, _ = fuse_vector(run.state.server_mu)
    del run
    res["pipeline"] = check_pipeline(base, mu, slots)
    return res


def phase_sharded_round(*, arch="rwkv6-1.6b", layers=5, slots=2, rounds=2,
                        extra=()) -> dict:
    """The round on every local device (the client x zero plan; the
    launcher asserts the one inter-client all-reduce) against the same
    round on the first device alone: the update over all parameters, in
    norm, and the per-round losses. The first round's scheduler admits no
    client, so it takes two rounds for the model to move."""
    import jax
    import numpy as np

    from repro.launch import train

    argv = _round_argv(arch, layers, slots, rounds, extra)
    devs = jax.devices()
    params, losses = {}, {}
    for n in (1, len(devs)):
        run = train.main(argv, devices=devs[:n])
        # To the host: the next round needs the first device's memory.
        params[n] = jax.device_get(run.state.params)
        losses[n] = [h["loss"] for h in run.history]
        model, fl_cfg = run.model, run.fl_cfg
        del run
    # The launcher's own starting state, on the first device.
    p0 = train.init_state(model, fl_cfg, train.parse_args(argv).seed).params

    rel = _rel_err(params[len(devs)], params[1], p0, "one-device round")
    loss_rel = max(
        abs(a - b) / abs(b) for a, b in zip(losses[len(devs)], losses[1])
    )
    res = dict(
        arch=arch, layers=layers, slots=slots, rounds=rounds,
        devices=len(devs), losses=losses, update_rel_err=rel,
        loss_rel_err=loss_rel,
    )
    _report("sharded_round", res)
    _require(all(np.isfinite(v).all() for v in losses.values()),
             f"sharded round losses {losses}")
    _require(rel <= SHARDED_UPDATE_RTOL,
             f"sharded vs one-device update rel err {rel:.3e}")
    _require(loss_rel <= SHARDED_LOSS_RTOL,
             f"sharded vs one-device loss rel err {loss_rel:.3e}")
    return res


# --------------------------------------------------------------------- #
# phase 3: continuous-batching serving
# --------------------------------------------------------------------- #
def check_decode_logits(model, params, *, slots, prompt_len, gen, page,
                        seed=0) -> dict:
    """Admit ``slots`` prompts into a bf16 page pool; check the paged
    kernel on that pool against an f32 reference for each attention
    kind, then one batched decode step at ragged lengths through the
    paged kernel and through the dense-gather path, on f32 logits.
    Takes ownership of ``params`` (cast to f32 on the way)."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.paged_attention import (
        paged_attention,
        paged_attention_ref,
    )
    from repro.models import build_model
    from repro.serve.paged import (
        PagePlan,
        init_pool,
        make_admit_fn,
        make_logits_fn,
    )

    cfg = model.cfg
    plan = PagePlan.build(cfg, prompt_len, gen, page_size=page)
    n_tab = plan.pages_per_slot
    pool = init_pool(cfg, plan, slots, slots * n_tab)
    table = (1 + np.arange(slots * n_tab, dtype=np.int32)).reshape(
        slots, n_tab
    )
    k_prompt, k_q = jax.random.split(jax.random.PRNGKey(seed))
    prompts = jax.random.randint(
        k_prompt, (slots, prompt_len), 0, cfg.vocab_size, jnp.int32
    )
    tokens = jnp.zeros((slots, 1), jnp.int32)
    out_buf = jnp.zeros((slots + 1, gen), jnp.int32)
    admit = jax.jit(make_admit_fn(model, plan), donate_argnums=(1, 2, 3))
    for s in range(slots):
        pool, tokens, out_buf = admit(
            params, pool, tokens, out_buf, prompts[s:s + 1],
            table[s, :plan.prompt_pages], np.int32(s), np.int32(s),
        )
    # Ragged: slot s stands s tokens into its generation.
    positions = (plan.prompt_eff + np.arange(slots) % gen).astype(np.int32)
    active = np.ones((slots,), bool)

    # Kernel vs reference on the served bf16 pool: the first layer of
    # each attention kind (hymba: global and window 1024), bf16 queries;
    # the reference attends in f32.
    f32 = jnp.float32
    windows = cfg.layer_windows()
    attn_err = {}
    lengths = jnp.asarray(positions + 1)
    q = jax.random.normal(
        k_q, (slots, cfg.num_heads, cfg.head_dim)
    ).astype(pool["k"].dtype)
    for w in sorted(set(windows)):
        i = windows.index(w)
        got = paged_attention(q, pool["k"][i], pool["v"][i], table,
                              lengths, w)
        with jax.default_matmul_precision("highest"):
            want = paged_attention_ref(
                q.astype(f32), pool["k"][i].astype(f32),
                pool["v"][i].astype(f32), table, lengths, w,
            )
        got, want = (np.asarray(x, np.float32) for x in (got, want))
        attn_err[str(w)] = float(
            np.max(np.abs(got - want)) / np.max(np.abs(want))
        )

    # The whole decode step, paged vs dense, in f32.
    model32 = build_model(dataclasses.replace(
        cfg, param_dtype="float32", compute_dtype="float32"
    ))
    params = jax.tree.map(lambda x: x.astype(f32), params)
    pool = jax.tree.map(lambda x: x.astype(f32), pool)
    logits = {}
    with jax.default_matmul_precision("highest"):
        for attn in ("paged", "dense"):
            fn = jax.jit(make_logits_fn(model32, plan, attn=attn))
            logits[attn] = np.asarray(
                fn(params, pool, tokens, table, positions, active)[0][:, 0]
            )
    # The real vocabulary only: the padded columns hold the -1e30 mask,
    # whose norm would swamp (and in f32 overflow) any difference.
    paged, dense = (
        logits[a][:, :cfg.vocab_size].astype(np.float64)
        for a in ("paged", "dense")
    )
    per_slot = np.linalg.norm(paged - dense, axis=-1) / np.linalg.norm(
        dense, axis=-1
    )
    top1 = float(np.mean(paged.argmax(-1) == dense.argmax(-1)))
    res = dict(
        slots=slots, lengths=(positions + 1).tolist(),
        logits_rel_err_max=float(per_slot.max()), top1_agree=top1,
        logits_abs_max=float(np.abs(dense).max()),
        attn_err_frac_by_window=attn_err,
    )
    _report("serving.decode_check", res)
    _require(np.isfinite(paged).all() and np.isfinite(dense).all(),
             "paged and dense logits finite")
    _require(float(per_slot.max()) <= LOGITS_RTOL,
             f"paged vs dense logits rel err {per_slot.max():.3e}")
    for w, e in attn_err.items():
        _require(e <= ATTN_ATOL_FRAC,
                 f"paged kernel vs ref, window {w}: {e:.3e}")
    return res


def phase_serving(*, arch="hymba-1.5b", slots=8, requests=16,
                  prompt_len=1536, gen=32, page=16, extra=()) -> dict:
    """``repro.launch.serve --engine continuous --attn paged``: every
    request completes, arrivals are conserved, two executables serve the
    trace; then the logits check on the same model."""
    import jax

    from repro.launch import config_from_args, serve
    from repro.models import build_model

    argv = [
        "--scale", "full", "--arch", arch, "--engine", "continuous",
        "--attn", "paged", "--slots", str(slots),
        "--requests", str(requests), "--prompt-len", str(prompt_len),
        "--gen", str(gen), "--page-size", str(page), "--seed", "0",
        *extra,
    ]
    cfg = config_from_args(serve.parse_args(argv))
    t0 = time.perf_counter()
    rep = serve.main(argv)
    wall = time.perf_counter() - t0
    c = rep.counters
    toks = rep.tokens[: rep.n_requests]
    res = dict(
        arch=arch, slots=slots, requests=requests, prompt_len=prompt_len,
        gen=gen, completed=rep.completed, rejected=rep.rejected,
        decode_steps=rep.decode_steps, tokens=rep.tokens_generated,
        n_compiles=rep.n_compiles, wall_s_incl_compile=wall,
    )
    _report("serving", res)
    _require(rep.completed == requests, f"completed {rep.completed}")
    _require(c["arrived"] == rep.completed + rep.rejected == requests,
             f"conservation {c}")
    _require(rep.n_compiles == {"admit": 1, "decode": 1},
             f"executables {rep.n_compiles}")
    _require(bool(((toks >= 0) & (toks < cfg.vocab_size)).all()),
             "token ids in vocab")
    model = build_model(cfg)
    res["decode_check"] = check_decode_logits(
        model, model.init(jax.random.PRNGKey(0)), slots=slots,
        prompt_len=prompt_len, gen=gen, page=page,
    )
    return res


# --------------------------------------------------------------------- #
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: the three phases on one chip; 4: the "
                         "client-sharded round against one device")
    args = ap.parse_args(argv)
    _import_repro()

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke.py: needs a TPU; JAX found {dev.platform!r}"
        )
    if len(devices) != args.chips:
        raise SystemExit(
            f"chip_smoke.py: --chips {args.chips} but JAX sees "
            f"{len(devices)} {dev.device_kind} devices"
        )
    from repro.launch.compile_cache import use_persistent_cache

    print(f"[setup] device={dev.device_kind} count={len(devices)} "
          f"compile_cache={use_persistent_cache()}", flush=True)

    # Each phase prints its readings, then raises on a failed check.
    if args.chips == 4:
        phase_sharded_round()
    else:
        sim = phase_simulator()
        _require(all(r["kernel_in_hlo"] for r in sim.values()),
                 "simulator kernel compiled for the chip")
        fl = phase_fl_round()
        _require(fl["pipeline"]["kernel_in_hlo"],
                 "delta_pipeline compiled for the chip")
        phase_serving()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
