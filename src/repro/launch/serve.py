"""Serving driver: static batch or continuous batching with any --arch.

    PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-1.6b \
        --scale tiny --batch 4 --prompt-len 32 --gen 16

``--scale tiny`` runs the reduced config on one CPU device. ``--scale
full`` is the distribution-aware path: it builds the ``repro.dist`` mesh
plan for the device pool it runs on, shards params (no FSDP on the decode
path), batch and KV cache via ``ShardingRules`` — batch-parallel when the
batch divides the data axes, sequence-parallel otherwise (the
long-context fallback) — and reports the decode step's collectives via
``analyze_hlo``. A 256-chip pod gets the production mesh, any other pool
a host plan; ``--layers`` cuts the depth in whole periods of the layer
pattern. On CPU back it with fake devices:

    python -m repro.launch.serve --scale full --devices 8 --reduced \
        --batch 8 --prompt-len 32 --gen 8
    python -m repro.launch.serve --scale full --arch hymba-1.5b \
        --engine continuous --attn paged --slots 8 --requests 16  # one chip

Engines:

  * ``--engine static`` (default) — one fixed batch, prefill + N decode
    steps. Greedy tokens accumulate in a device-resident buffer inside
    the compiled step program; the host syncs ONCE at the end. This path
    is the serving oracle.
  * ``--engine continuous`` — the slot-scheduled continuous-batching
    engine (``repro.serve``): Poisson/diurnal arrivals off the DES event
    queue, mid-flight slot eviction/refill on two AOT executables,
    §IV.F latency/energy/cold-start accounting, and optionally the
    Pallas paged flash-decode kernel (``--attn paged``). Reproduces the
    sequential per-request decode token-for-token (``--attn dense``).

``--track jsonl:PATH --track-every K`` streams per-step serving metrics
through the shared ``repro.obs`` tracker stack on either engine.
"""
from __future__ import annotations

import argparse
import os
import time


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--scale", default="tiny", choices=["tiny", "full"])
    ap.add_argument("--engine", default="static",
                    choices=["static", "continuous"])
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--devices", type=int, default=0,
                    help="back the full-scale mesh with N fake CPU devices "
                         "(XLA_FLAGS; set before jax initializes)")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--reduced", action="store_true",
                    help="with --scale full: reduced config on the real "
                         "mesh plan (CPU-executable sharded decode)")
    ap.add_argument("--layers", type=int, default=0,
                    help="with --scale full: cut the published depth to N "
                         "layers (whole periods of the layer pattern; "
                         "widths unchanged). 0 = published depth")
    # Continuous-batching knobs (--engine continuous).
    ap.add_argument("--requests", type=int, default=16,
                    help="trace length for --engine continuous")
    ap.add_argument("--rate", type=float, default=20.0,
                    help="mean request arrival rate (per virtual second)")
    ap.add_argument("--slots", type=int, default=0,
                    help="slot count (default: --batch)")
    ap.add_argument("--slo-ms", type=float, default=4000.0,
                    help="per-request latency SLO (virtual ms)")
    ap.add_argument("--attn", default="dense", choices=["dense", "paged"],
                    help="slot attention: dense gather (oracle-exact) or "
                         "the Pallas paged flash-decode kernel")
    ap.add_argument("--policy", default="fifo", choices=["fifo", "edf"])
    ap.add_argument("--page-size", type=int, default=16)
    # Observability (either engine).
    ap.add_argument("--track", default=None,
                    help="tracker spec, e.g. jsonl:/tmp/serve.jsonl")
    ap.add_argument("--track-every", type=int, default=1)
    return ap.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    if args.scale == "full" and args.devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices} "
            + os.environ.get("XLA_FLAGS", "")
        )
    import jax
    import jax.numpy as jnp

    from repro.launch import config_from_args
    from repro.launch.compile_cache import use_persistent_cache
    from repro.models import Runtime, build_model
    from repro.models.config import Family

    use_persistent_cache()
    full = args.scale == "full"
    cfg = config_from_args(args)
    model = build_model(cfg)
    key = jax.random.PRNGKey(args.seed)

    tap = None
    if args.track:
        from repro.obs import MetricTap, tracker_from_spec

        tap = MetricTap(
            tracker_from_spec(args.track), every=args.track_every,
            const={"arch": cfg.name}, channel="serve",
        )

    rules = None
    runtime = Runtime()
    if full:
        from repro.dist import make_rules
        from repro.launch import mesh as mesh_mod

        pods = 2 if args.multi_pod else 1
        pool = args.devices or jax.device_count()
        if pool == mesh_mod.CHIPS_PER_POD * pods:
            pm = mesh_mod.make_production_mesh(multi_pod=args.multi_pod)
            rules = make_rules(pm, cfg, multi_pod=args.multi_pod)
        else:
            rules = make_rules(None, cfg, multi_pod=args.multi_pod,
                               device_count=pool)
        mesh_shape = rules.mesh.shape
        runtime = Runtime(
            mesh=rules.mesh,
            batch_axes=rules.serve_batch_axes,
            expert_axis="expert" if cfg.num_experts else None,
            tp_axis="tp" if mesh_shape.get("tp", 1) > 1 else None,
            moe_impl="gshard" if cfg.num_experts else "dropless",
            moe_group_axes=rules.serve_batch_axes,
        )
        print(f"[serve] mesh plan: {dict(mesh_shape)} "
              f"layers={cfg.num_layers} params={model.param_count():,}")

    params = model.init(key)
    if rules is not None:
        shapes, laxes = model.param_shapes(), model.param_axes()
        # Decode-path weights: model-parallel only, no ZeRO sharding.
        p_sh = rules.shardings(
            rules.param_specs(shapes, laxes, stacked=False, fsdp=False)
        )
        params = jax.device_put(params, p_sh)

    if args.engine == "continuous":
        return _run_continuous(args, cfg, model, params, rules, runtime, tap)

    cache_len = args.prompt_len + args.gen
    batch = {
        "tokens": jax.random.randint(
            key, (args.batch, args.prompt_len), 0, cfg.vocab_size
        )
    }
    if cfg.family is Family.VLM:
        batch["patch_embeds"] = jax.random.normal(
            key, (args.batch, 8, cfg.d_model)
        ).astype(cfg.compute_dtype)
        cache_len += 8
    if cfg.family is Family.ENCDEC:
        batch["frames"] = jax.random.normal(
            key, (args.batch, args.prompt_len, cfg.d_model)
        ).astype(cfg.compute_dtype)

    def prefill_fn(p, b, buf):
        logits, cache = model.prefill(p, b, cache_len=cache_len,
                                      runtime=runtime)
        toks = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return cache, toks[:, None], buf.at[:, 0].set(toks)

    def step_fn(p, cache, toks, buf, i):
        """One decode step + greedy pick + device-buffer write: tokens
        never leave the device until the single terminal sync."""
        logits, cache = model.decode_step(p, cache, toks, runtime)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return cache, nxt[:, None], buf.at[:, i].set(nxt)

    gen_buf = jnp.zeros((args.batch, args.gen), jnp.int32)
    if rules is not None:
        from jax.sharding import NamedSharding

        b_sh = {
            k: NamedSharding(rules.mesh, v)
            for k, v in rules.serve_batch_specs(batch).items()
        }
        batch = {k: jax.device_put(v, b_sh[k]) for k, v in batch.items()}
        prefill = jax.jit(prefill_fn, in_shardings=(p_sh, b_sh, None))
        decode = jax.jit(step_fn, donate_argnums=(1, 3))
    else:
        prefill = jax.jit(prefill_fn)
        decode = jax.jit(step_fn, donate_argnums=(1, 3))

    t0 = time.time()
    cache, toks, gen_buf = prefill(params, batch, gen_buf)
    toks.block_until_ready()
    t_prefill = time.time() - t0

    if rules is not None:
        # Pin the cache to the rules' layout (batch- or sequence-parallel),
        # AOT-compile ONE decode program against it, and report its
        # collective census — the same executable then serves every step.
        from repro.dist import analyze_hlo

        cache = jax.device_put(
            cache, rules.shardings(rules.cache_specs(cache))
        )
        decode = decode.lower(
            params, cache, toks, gen_buf,
            jax.ShapeDtypeStruct((), jnp.int32),
        ).compile()
        stats = analyze_hlo(decode.as_text()).collectives
        print(f"[serve] decode collectives: {stats.count_by_kind} "
              f"total={stats.total_bytes:.2e} B")

    t0 = time.time()
    for i in range(1, args.gen):
        cache, toks, gen_buf = decode(params, cache, toks, gen_buf,
                                      jnp.int32(i))
        if tap is not None:
            tap.host_log({"step": i, "batch": args.batch}, step=i)
    out = jax.block_until_ready(gen_buf)  # the ONE device->host sync
    t_decode = time.time() - t0

    print(f"arch={cfg.name} prefill={t_prefill*1e3:.1f}ms "
          f"decode={t_decode / max(args.gen - 1, 1) * 1e3:.2f}ms/tok")
    print("generated token ids (first row):", out[0].tolist())
    return out


def _run_continuous(args, cfg, model, params, rules, runtime, tap):
    import jax

    from repro.serve import (
        ContinuousBatchingEngine,
        EngineConfig,
        TraceConfig,
        make_trace,
    )

    slots = args.slots or args.batch
    ecfg = EngineConfig(
        slots=slots,
        page_size=args.page_size,
        prompt_len=args.prompt_len,
        max_gen=args.gen,
        max_requests=max(args.requests, 1),
        attn=args.attn,
        policy=args.policy,
    )
    t0 = time.time()
    engine = ContinuousBatchingEngine(
        model, params, ecfg, runtime=runtime, tap=tap
    )
    print(f"[serve] admit + decode compiled in {time.time() - t0:.1f}s")
    if rules is not None:
        from repro.dist import analyze_hlo

        stats = analyze_hlo(engine.decode_hlo_text()).collectives
        print(f"[serve] decode collectives: {stats.count_by_kind} "
              f"total={stats.total_bytes:.2e} B")

    trace = make_trace(
        jax.random.PRNGKey(args.seed + 1),
        TraceConfig(
            n_requests=args.requests,
            rate_per_s=args.rate,
            slo_ms=args.slo_ms,
            prompt_len=args.prompt_len,
            min_gen=max(args.gen // 2, 1),
            max_gen=args.gen,
        ),
        cfg,
    )
    rep = engine.serve(trace)
    pct = rep.percentiles
    print(
        f"arch={cfg.name} engine=continuous slots={slots} attn={args.attn} "
        f"requests={rep.n_requests} completed={rep.completed} "
        f"rejected={rep.rejected}"
    )
    print(
        f"[serve] latency p50={pct['p50']:.0f}ms p95={pct['p95']:.0f}ms "
        f"p99={pct['p99']:.0f}ms slo_violations={rep.slo_violations} "
        f"goodput={rep.goodput_rps:.2f} req/s"
    )
    print(
        f"[serve] tokens={rep.tokens_generated} "
        f"decode_steps={rep.decode_steps} cold_starts={rep.cold_starts} "
        f"energy_per_token={rep.energy_per_token_j:.2e} J "
        f"throughput={rep.tokens_per_wall_s:.0f} tok/s(wall) "
        f"n_compiles={rep.n_compiles}"
    )
    print("generated token ids (first request):", rep.tokens_for(0))
    return rep


if __name__ == "__main__":
    main()
