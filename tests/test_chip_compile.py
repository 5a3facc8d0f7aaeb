"""Compile-only checks of the main-path Pallas kernels at real widths,
against a described TPU v5e (``v5e:2x2``; no chip is attached).

The TPU compiler refuses tilings and VMEM budgets that interpret mode
accepts, so each kernel the chip runs is compiled here at the shapes the
chip smoke run uses, and each compiled program must hold the kernel
(``tpu_custom_call``). Nothing runs, so no result or time comes from
these tests.

The topology is described only inside the module fixture: loading the TPU
compiler's library takes a process-wide lock, so it must not happen while
any module is imported.
"""
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.delta_pipeline import delta_pipeline_apply
from repro.kernels.paged_attention.ops import paged_attention
from repro.models import build_model


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # no compiler logs on disk
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # no TPU compiler in this environment
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # Programs compiled for a described chip can be written to the
        # persistent cache but never read back: keep them out of it.
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            cc.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_delta_pipeline_compiles_at_the_lm_round_shape(one_chip):
    """FedAvgM gates at the (C, P) of the rwkv6-1.6b round one chip
    holds: 2 slots, depth cut to 5 layers (545.8M parameters). The
    kernel compiles under its derived tile and VMEM limit, in at most
    4,096 grid steps, with no copy of a P-long operand around it."""
    c = 2
    p = build_model(get_config("rwkv6-1.6b").with_depth(5)).param_count()
    f32 = jnp.float32
    shapes = [((c, p), f32), ((p,), f32), ((c,), jnp.bool_), ((c,), f32),
              ((p,), f32)]

    def apply(u, b, m, w, mu):
        return delta_pipeline_apply(u, b, m, w, 1.0, momentum=mu,
                                    server_optimizer="fedavgm",
                                    interpret=False)

    compiled = jax.jit(apply).lower(
        *[_spec(one_chip, a, t) for a, t in shapes]).compile()
    _assert_kernel(compiled)
    big = [m.group(0) for m in re.finditer(
        r"\[([\d,]+)\]\S* (?:pad|copy)\(", compiled.as_text())
        if max(map(int, m.group(1).split(","))) >= p]
    assert big == []
    jaxpr = jax.make_jaxpr(apply)(
        *[jax.ShapeDtypeStruct(a, t) for a, t in shapes])
    (grid,) = [e.params["grid_mapping"].grid
               for e in jaxpr.jaxpr.eqns[0].params["jaxpr"].eqns
               if e.primitive.name == "pallas_call"]
    assert len(grid) == 1 and grid[0] <= 4096


@pytest.mark.parametrize("aggregator", ["median", "trimmed"])
def test_robust_delta_pipeline_compiles(one_chip, aggregator):
    """In-kernel bitonic selection over the simulator's 16-client cohort."""
    c, p = 16, 1 << 20
    f32 = jnp.float32
    compiled = delta_pipeline_apply.lower(
        _spec(one_chip, (c, p), f32), _spec(one_chip, (p,), f32),
        _spec(one_chip, (c,), jnp.bool_), _spec(one_chip, (c,), f32),
        aggregator=aggregator, interpret=False,
    ).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize(
    "window,dtype",
    [(-1, jnp.bfloat16), (1024, jnp.bfloat16), (1024, jnp.float32)],
    ids=["global", "window1024", "window1024-f32"],
)
def test_paged_attention_compiles_at_hymba_widths(one_chip, window, dtype):
    """hymba-1.5b: 25 query heads over 5 KV heads of 64, page 16; the
    serving smoke run's 8 slots of a 1536-token prompt plus 32 tokens."""
    cfg = get_config("hymba-1.5b")
    slots, page = 8, 16
    n = -(-(1536 + 32) // page)
    pool = (slots * n + 1, cfg.num_kv_heads, page, cfg.head_dim)
    compiled = jax.jit(
        lambda q, k, v, t, l: paged_attention(
            q, k, v, t, l, window, interpret=False
        )
    ).lower(
        _spec(one_chip, (slots, cfg.num_heads, cfg.head_dim), dtype),
        _spec(one_chip, pool, dtype), _spec(one_chip, pool, dtype),
        _spec(one_chip, (slots, n), jnp.int32),
        _spec(one_chip, (slots,), jnp.int32),
    ).compile()
    _assert_kernel(compiled)



def _kernel_heads(compiled):
    """Names of the compiled program's Pallas custom calls, suffix cut."""
    return {
        re.sub(r"\.\d+$", "", m.group(1))
        for m in re.finditer(r"^\s*(?:ROOT )?%([\w.\-]+) = .*? custom-call\(.*"
                             r'custom_call_target="tpu_custom_call"',
                             compiled.as_text(), re.M)
    }


def _kernel_programs(one_chip):
    """(kernel name, compile thunk) for each ``pallas_call`` of the
    repository, at small shapes."""
    from repro.kernels.delta_pipeline import delta_pipeline_partial
    from repro.kernels.fedavg.fedavg import fedavg_apply
    from repro.kernels.flash_attention.flash_attention import (
        flash_attention_fwd,
    )

    f32, bf16 = jnp.float32, jnp.bfloat16
    c, p = 2, 1 << 16
    s = lambda shape, dt=f32: _spec(one_chip, shape, dt)  # noqa: E731
    dp = lambda **kw: delta_pipeline_apply.lower(  # noqa: E731
        s((c, p)), s((p,)), s((c,), jnp.bool_), s((c,)), 1.0,
        interpret=False, **kw).compile()
    return {
        # The FedAvgM apply kernel: ``delta_pipeline_roofline`` reads it by
        # this head.
        "delta_pipeline_apply": lambda: dp(
            momentum=s((p,)), server_optimizer="fedavgm"),
        "delta_sq_norms": lambda: dp(clip_norm=1.0),
        "delta_pipeline_partial": lambda: delta_pipeline_partial.lower(
            s((c, p)), s((c,)), interpret=False).compile(),
        "fedavg_apply": lambda: jax.jit(
            lambda u, b, m, w: fedavg_apply(u, b, m, w, interpret=False)
        ).lower(s((c, p)), s((p,)), s((c,), jnp.bool_), s((c,))).compile(),
        "flash_attention": lambda: jax.jit(
            lambda q, k, v: flash_attention_fwd(q, k, v, interpret=False)
        ).lower(*[s((1, 2, 256, 64), bf16)] * 3).compile(),
    }


@pytest.mark.parametrize("kernel", [
    "delta_pipeline_apply", "delta_sq_norms", "delta_pipeline_partial",
    "fedavg_apply", "flash_attention"])
def test_every_kernel_is_named_in_the_compiled_program(one_chip, kernel):
    """A trace names each Pallas kernel after its ``pallas_call`` name."""
    assert kernel in _kernel_heads(_kernel_programs(one_chip)[kernel]())


def test_paged_attention_kernel_is_named(one_chip):
    cfg = get_config("hymba-1.5b")
    pool = (9, cfg.num_kv_heads, 16, cfg.head_dim)
    compiled = jax.jit(
        lambda q, k, v, t, l: paged_attention(q, k, v, t, l, -1,
                                              interpret=False)
    ).lower(
        _spec(one_chip, (2, cfg.num_heads, cfg.head_dim), jnp.bfloat16),
        _spec(one_chip, pool, jnp.bfloat16), _spec(one_chip, pool, jnp.bfloat16),
        _spec(one_chip, (2, 4), jnp.int32), _spec(one_chip, (2,), jnp.int32),
    ).compile()
    assert "paged_attention" in _kernel_heads(compiled)


def test_phase_map_heads_match_the_recorded_chip_trace(one_chip):
    """The benchmark's recorded trace ran ``tanh(x @ x)`` on a v5e. The
    same program, compiled here under a ``fedfog.server`` scope, maps its
    fusion with the head that the trace's event prints, and the
    benchmark's phase reader finds the fusion's time there."""
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "bench")
    sys.path.insert(0, bench)
    try:
        import phases
        import xplane
    finally:
        sys.path.remove(bench)
    from repro.dist import analyze_hlo

    def f(x):
        with jax.named_scope("fedfog.server"):
            return jnp.tanh(x @ x)

    compiled = jax.jit(f).lower(
        _spec(one_chip, (1024, 1024), jnp.bfloat16)).compile()
    hlo = analyze_hlo(compiled.as_text())
    data = os.path.join(bench, "tests", "data", "trace")
    with open(os.path.join(data, "expected.json")) as fh:
        want = json.load(fh)
    fusion = xplane.op_head(want["op"])
    assert hlo.phases[fusion] == "fedfog.server"
    assert phases.signature(want["op"]) == hlo.heads[fusion]
    prog = {"module": hlo.module, "phases": hlo.phases, "heads": hlo.heads}
    got = phases.phase_seconds(xplane.read(data), prog, "fedfog.server")
    assert got == pytest.approx(want["op_seconds"], rel=1e-9)
