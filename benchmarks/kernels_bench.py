"""Kernel microbenchmarks: Pallas (interpret) correctness-path timings and
the XLA-path (jnp oracle) timings that actually execute on this CPU host.

On-TPU wall-times cannot be measured here; us_per_call is the CPU oracle
timing (the kernels' interpret mode is a correctness tool, not a perf
path). Roofline-relevant figures come from benchmarks/roofline.py instead.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from benchmarks.common import Row, fmt
from repro.kernels.delta_pipeline import delta_pipeline_ref
from repro.kernels.fedavg import fedavg_apply, fedavg_apply_ref
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.wkv6.ref import wkv6_ref
from repro.models.layers import attention_xla_chunked


def _time(fn, *args, iters=5) -> float:
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.time()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.time() - t0) / iters * 1e6


def run() -> list[Row]:
    key = jax.random.PRNGKey(0)
    rows = []

    # attention: oracle vs chunked-xla (the dry-run path)
    b, h, s, hd = 1, 4, 1024, 64
    q = jax.random.normal(key, (b, h, s, hd))
    k = jax.random.normal(key, (b, h, s, hd))
    v = jax.random.normal(key, (b, h, s, hd))
    t_ref = _time(jax.jit(lambda q, k, v: flash_attention_ref(q, k, v)), q, k, v)
    qs, ks, vs = (z.swapaxes(1, 2) for z in (q, k, v))
    pos = jnp.arange(s, dtype=jnp.int32)
    t_chunk = _time(
        jax.jit(
            lambda q, k, v: attention_xla_chunked(q, k, v, pos, pos, -1)
        ),
        qs, ks, vs,
    )
    rows.append(
        Row(
            "kernels/attention_1k",
            t_chunk,
            fmt(ref_us=t_ref, chunked_us=t_chunk),
        )
    )

    # wkv6 oracle
    r = jax.random.normal(key, (1, 256, 4, 64, ))
    kk = jax.random.normal(key, (1, 256, 4, 64)) * 0.5
    vv = jax.random.normal(key, (1, 256, 4, 64))
    w = jnp.exp(-jnp.exp(jax.random.uniform(key, (1, 256, 4, 64), minval=-3, maxval=0)))
    u = jax.random.normal(key, (4, 64)) * 0.3
    t_wkv = _time(jax.jit(lambda *a: wkv6_ref(*a)[0]), r, kk, vv, w, u)
    rows.append(Row("kernels/wkv6_256", t_wkv, fmt(ref_us=t_wkv)))

    # fedavg fused kernel (interpret) vs jnp oracle
    upd = jax.random.normal(key, (32, 1 << 16))
    base = jax.random.normal(key, (1 << 16,))
    mask = jnp.ones((32,), bool)
    wts = jnp.ones((32,))
    t_ref = _time(
        jax.jit(lambda *a: fedavg_apply_ref(*a)), upd, base, mask, wts
    )
    rows.append(Row("kernels/fedavg_32x64k", t_ref, fmt(oracle_us=t_ref)))

    # delta pipeline: fused single-buffer pass vs the unfused per-stage
    # per-leaf chain (per-client clip → staleness-discounted Eq. 6
    # aggregate → DP → momentum apply over a 5-leaf tree). Both are the
    # CPU (XLA) oracle implementations — the Pallas kernel itself is a
    # TPU path; its interpret mode is a correctness tool, not perf.
    from repro.core.aggregation import fedavg_stacked
    from repro.optim import clip_by_global_norm

    seg_sizes = (1 << 15, 1 << 14, 1 << 14, 1 << 13, 1 << 13)
    p_total = sum(seg_sizes)
    c = 32
    upd = jax.random.normal(key, (c, p_total))
    base = jax.random.normal(key, (p_total,))
    mu = jnp.zeros((p_total,))
    noise = 0.1 * jax.random.normal(key, (p_total,))
    mask = jnp.ones((c,), bool)
    wts = jnp.ones((c,))
    stal = jnp.arange(c, dtype=jnp.float32) % 4
    kw = dict(
        lr=0.9, dp_noise=noise, momentum=mu, clip_norm=1.0,
        staleness=stal, staleness_exponent=0.5,
        server_optimizer="fedavgm",
    )
    t_fused = _time(
        jax.jit(
            lambda u, b, m, w: delta_pipeline_ref(u, b, m, w, **kw)[0]
        ),
        upd, base, mask, wts,
    )
    offs = [0]
    for s in seg_sizes:
        offs.append(offs[-1] + s)

    def unfused(u, b, m, w):
        tree = {
            f"l{i}": u[:, offs[i]:offs[i + 1]]
            for i in range(len(seg_sizes))
        }
        tree = jax.vmap(lambda d: clip_by_global_norm(d, 1.0)[0])(tree)
        disc = (1.0 + stal) ** -0.5
        agg = fedavg_stacked(tree, m, w * disc)
        sized = m * w
        scale = (jnp.sum(sized * disc) + 1e-12) / (jnp.sum(sized) + 1e-12)
        cat = jnp.concatenate(
            [agg[f"l{i}"] for i in range(len(seg_sizes))]
        ) * scale
        mu2 = 0.9 * mu + (cat + noise)
        return b + 0.9 * mu2

    t_unfused = _time(jax.jit(unfused), upd, base, mask, wts)
    rows.append(
        Row(
            "kernels/delta_pipeline_32x96k",
            t_fused,
            fmt(fused_us=t_fused, unfused_us=t_unfused,
                speedup=t_unfused / max(t_fused, 1e-9)),
        )
    )

    # sharded delta pipeline: shard_map + per-shard partial kernel + one
    # psum on an 8-fake-device mesh, vs the single-device fused kernel on
    # the same (32, 32k) buffer. Subprocess: the fake-device flag must be
    # set before jax initializes (this process is already single-device).
    import json
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    # The child runs on fake CPU devices: pinned to the CPU, so it never
    # reaches for an accelerator this process may hold.
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = (
        os.path.join(repo, "src") + os.pathsep + env.get("PYTHONPATH", "")
    )
    proc = subprocess.run(
        [sys.executable, "-m",
         "repro.kernels.delta_pipeline.sharded_selftest",
         "--json", "--bench", "--devices", "8"],
        capture_output=True, text=True, env=env, cwd=repo, timeout=1200,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"sharded selftest rc={proc.returncode}: {proc.stderr[-500:]}"
        )
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    b = res["bench"]
    rows.append(
        Row(
            "kernels/delta_pipeline_sharded",
            b["sharded_us"],
            fmt(platform="cpu", sharded_us=b["sharded_us"],
                unsharded_us=b["unsharded_us"],
                c=b["c"], p=b["p"], devices=res["devices"],
                gate_matrix_ok=res["ok"]),
        )
    )
    return rows
