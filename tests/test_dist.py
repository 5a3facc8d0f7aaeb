"""Unit tests for the repro.dist distribution layer.

Covers the HLO collective parser (explicit + iota replica groups, loop
warnings, dot flops), the axis-crossing classifier, scaled mesh plans,
and the divisibility fallbacks of the sharding rule table. The
end-to-end fake-device round lives in tests/test_sharded_round.py.
"""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config, get_reduced
from repro.dist import analyze_hlo, count_axis_crossing, make_rules, plan_for
from repro.models import build_model

# --------------------------------------------------------------------- #
# analyze_hlo on synthetic HLO text
# --------------------------------------------------------------------- #
SYNTH_HLO = """\
HloModule jit_round, entry_computation_layout={(f32[8,16]{1,0})->f32[8,16]{1,0}}

%add.clone (x: f32[], y: f32[]) -> f32[] {
  %x = f32[] parameter(0)
  %y = f32[] parameter(1)
  ROOT %add.1 = f32[] add(f32[] %x, f32[] %y)
}

%body (p: (f32[8,16], s32[])) -> (f32[8,16], s32[]) {
  %p = (f32[8,16]{1,0}, s32[]) parameter(0)
  %gte = f32[8,16]{1,0} get-tuple-element((f32[8,16]{1,0}, s32[]) %p), index=0
  %cp = f32[8,16]{1,0} collective-permute(f32[8,16]{1,0} %gte), source_target_pairs={{0,1},{1,0}}
  %i = s32[] get-tuple-element((f32[8,16]{1,0}, s32[]) %p), index=1
  ROOT %tup = (f32[8,16]{1,0}, s32[]) tuple(f32[8,16]{1,0} %cp, s32[] %i)
}

%cond (p: (f32[8,16], s32[])) -> pred[] {
  %p = (f32[8,16]{1,0}, s32[]) parameter(0)
  %i = s32[] get-tuple-element((f32[8,16]{1,0}, s32[]) %p), index=1
  %c = s32[] constant(4)
  ROOT %lt = pred[] compare(s32[] %i, s32[] %c), direction=LT
}

ENTRY %main (arg0: f32[8,16]) -> f32[8,16] {
  %arg0 = f32[8,16]{1,0} parameter(0)
  %w = f32[16,16]{1,0} constant({...})
  %d = f32[8,16]{1,0} dot(f32[8,16]{1,0} %arg0, f32[16,16]{1,0} %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = f32[8,16]{1,0} all-reduce(f32[8,16]{1,0} %d), replica_groups={{0,2},{1,3}}, to_apply=%add.clone
  %ag = f32[16,16]{1,0} all-gather(f32[8,16]{1,0} %ar), replica_groups=[2,2]<=[4], dimensions={0}
  %rs = bf16[4,16]{1,0} reduce-scatter(bf16[4,16]{1,0} %ar), replica_groups={{0,1},{2,3}}, dimensions={0}, to_apply=%add.clone
  %t0 = (f32[8,16]{1,0}, s32[]) tuple(f32[8,16]{1,0} %ar, s32[] %arg0)
  %wh = (f32[8,16]{1,0}, s32[]) while((f32[8,16]{1,0}, s32[]) %t0), condition=%cond, body=%body
  ROOT %out = f32[8,16]{1,0} get-tuple-element((f32[8,16]{1,0}, s32[]) %wh), index=0
}
"""


def test_analyze_hlo_counts_and_bytes():
    a = analyze_hlo(SYNTH_HLO)
    counts = a.collectives.count_by_kind
    assert counts == {
        "all-reduce": 1,
        "all-gather": 1,
        "reduce-scatter": 1,
        "collective-permute": 1,
    }
    by = a.collectives.bytes_by_kind
    assert by["all-reduce"] == 8 * 16 * 4
    assert by["all-gather"] == 16 * 16 * 4
    assert by["reduce-scatter"] == 4 * 16 * 2  # bf16
    # dot: 2 * M*N * K = 2 * 8*16 * 16
    assert a.dot_flops == 2 * 8 * 16 * 16


def test_analyze_hlo_replica_groups():
    a = analyze_hlo(SYNTH_HLO)
    ops = {op.kind: op for op in a.collectives.ops}
    assert ops["all-reduce"].groups == [[0, 2], [1, 3]]
    # iota form [2,2]<=[4] -> [[0,1],[2,3]]
    assert ops["all-gather"].groups == [[0, 1], [2, 3]]
    assert ops["collective-permute"].groups == [[0, 1], [1, 0]]


def test_analyze_hlo_loop_body_warning():
    a = analyze_hlo(SYNTH_HLO)
    warns = a.collectives.trip_count_warnings
    assert len(warns) == 1 and "collective-permute" in warns[0]
    assert "body" in warns[0]


def test_analyze_hlo_iota_transpose():
    text = (
        "ENTRY %main (p0: f32[4]) -> f32[4] {\n"
        "  %p0 = f32[4]{0} parameter(0)\n"
        "  ROOT %ar = f32[4]{0} all-reduce(f32[4]{0} %p0), "
        "replica_groups=[2,2]<=[2,2]T(1,0), to_apply=%add\n"
        "}\n"
    )
    a = analyze_hlo(text)
    (op,) = a.collectives.ops
    # iota over [2,2] transposed: ids [[0,2],[1,3]]
    assert op.groups == [[0, 2], [1, 3]]


# TPU HLO: tiled layouts (":T(8,128)(2,1)") and a tuple-shaped
# all-reduce, as the TPU compiler prints them.
TPU_HLO = """\
HloModule jit_round

%add.2.clone (x: bf16[], y: bf16[]) -> bf16[] {
  %x = bf16[] parameter(0)
  %y = bf16[] parameter(1)
  ROOT %add.3 = bf16[] add(bf16[] %x, bf16[] %y)
}

ENTRY %main.65_spmd (p0: bf16[1,4,128,2048], p1: bf16[1,4,128,2048]) -> (bf16[1,4,128,2048], bf16[1,4,128,2048]) {
  %input_0.13 = bf16[1,4,128,2048]{3,2,1,0:T(8,128)(2,1)} parameter(0)
  %input_1.13 = bf16[1,4,128,2048]{3,2,1,0:T(8,128)(2,1)} parameter(1)
  ROOT %all-reduce.153 = (bf16[1,4,128,2048]{3,2,1,0:T(8,128)(2,1)}, bf16[1,4,128,2048]{3,2,1,0:T(8,128)(2,1)}) all-reduce(%input_0.13, %input_1.13), channel_id=225, replica_groups={{0,1},{2,3}}, use_global_device_ids=true, to_apply=%add.2.clone
}
"""


def test_analyze_hlo_tpu_tiled_layouts():
    a = analyze_hlo(TPU_HLO)
    (op,) = a.collectives.ops
    assert op.kind == "all-reduce"
    assert op.bytes == 2 * (4 * 128 * 2048 * 2)  # both tuple elements
    assert op.groups == [[0, 1], [2, 3]]


def _fake_mesh(shape: dict):
    return types.SimpleNamespace(
        axis_names=tuple(shape), shape=dict(shape)
    )


def test_count_axis_crossing():
    a = analyze_hlo(SYNTH_HLO)
    # mesh (client=2, zero=2), row-major ids: client coord = id // 2.
    mesh = _fake_mesh({"client": 2, "zero": 2})
    # all-reduce groups [[0,2],[1,3]] differ in client coord -> crossing.
    assert count_axis_crossing(a, mesh, axes=("client",)) == 1
    # all-gather groups [[0,1],[2,3]] stay within one client row.
    assert (
        count_axis_crossing(a, mesh, axes=("zero",), kinds=("all-gather",))
        == 1
    )
    assert (
        count_axis_crossing(a, mesh, axes=("client",), kinds=("all-gather",))
        == 0
    )
    # byte filter drops the 512 B all-reduce
    assert (
        count_axis_crossing(a, mesh, axes=("client",), min_bytes=1e6) == 0
    )


def test_analyze_hlo_on_real_compile():
    """The parser handles whatever the current CPU backend emits."""
    f = jax.jit(lambda x, w: jnp.tanh(x @ w).sum())
    x = jnp.ones((8, 16), jnp.float32)
    w = jnp.ones((16, 4), jnp.float32)
    a = analyze_hlo(f.lower(x, w).compile().as_text())
    assert a.num_instructions > 0
    assert a.collectives.total_bytes == 0  # single device
    assert a.dot_flops >= 2 * 8 * 4 * 16


# --------------------------------------------------------------------- #
# Mesh plans
# --------------------------------------------------------------------- #
def test_scaled_plan_arithmetic():
    cfg = get_config("llama3.2-1b")
    plan = plan_for(cfg, device_count=8)
    assert plan.device_count == 8
    assert plan.num_clients * plan.zero == 8
    assert plan.model_split == (1, 1)
    assert plan.client_axes == ("client",)
    assert plan.data_axes == ("client", "zero")

    plan = plan_for(cfg, device_count=8, zero=4)
    assert plan.zero == 4 and plan.num_clients == 2

    with pytest.raises(ValueError):
        plan_for(cfg, device_count=8, zero=3)
    with pytest.raises(ValueError):
        plan_for(cfg, device_count=7, multi_pod=True)


def test_multi_pod_plan_axes():
    cfg = get_config("qwen2.5-14b")
    plan = plan_for(cfg, multi_pod=True)
    assert plan.axis_names[0] == "pod"
    assert plan.shape["pod"] == 2
    assert plan.device_count == 512
    assert plan.client_axes == ("pod", "client")
    # qwen: 40 heads -> tp=8, sp=2
    assert plan.model_axes == ("tp", "sp")
    assert plan.model_split == (8, 2)


def test_moe_plan_expert_axis():
    plan = plan_for(get_config("mixtral-8x7b"))
    assert plan.model_axes == ("expert", "tp")
    assert plan.model_split == (8, 2)
    plan = plan_for(get_config("moonshot-v1-16b-a3b"))
    assert plan.model_split == (16, 1)


# --------------------------------------------------------------------- #
# Sharding rule fallbacks
# --------------------------------------------------------------------- #
def test_rules_divisibility_fallback():
    """GQA kv heads smaller than tp fall back to replication; every spec
    entry's axis product divides its dim by construction."""
    cfg = get_config("yi-9b")  # 32 q heads (tp=16), only 4 kv heads
    plan = plan_for(cfg)
    from repro.dist.sharding import ShardingRules

    rules = ShardingRules.__new__(ShardingRules)
    object.__setattr__(rules, "cfg", cfg)
    object.__setattr__(rules, "plan", plan)
    object.__setattr__(
        rules, "mesh", _fake_mesh({k: v for k, v in plan.shape.items() if v > 1})
    )
    model = build_model(cfg)
    specs = rules.param_specs(model.param_shapes(), model.param_axes())
    flat = jax.tree.flatten(specs, is_leaf=lambda x: isinstance(x, P))[0]
    layer_specs = specs["layers"]
    # q heads sharded over tp; kv heads replicated (4 % 16 != 0)
    assert layer_specs["wq"][2] == "tp"
    assert layer_specs["wk"][2] is None
    # FSDP: embed dims over zero
    assert layer_specs["wq"][1] == "zero"
    # every entry divides
    flat_shapes = jax.tree.leaves(model.param_shapes())
    for sds, spec in zip(flat_shapes, flat):
        for i, entry in enumerate(spec):
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            prod = int(np.prod([plan.shape[a] for a in axes]))
            assert sds.shape[i] % prod == 0


def test_rules_serve_fsdp_off():
    cfg = get_reduced("llama3.2-1b")
    rules = make_rules(None, cfg, device_count=1)
    model = build_model(cfg)
    shapes, laxes = model.param_shapes(), model.param_axes()
    # device_count=1: everything replicated either way
    specs = rules.param_specs(shapes, laxes, fsdp=False)
    for s in jax.tree.flatten(specs, is_leaf=lambda x: isinstance(x, P))[0]:
        assert all(e is None for e in s)


def test_rules_stacked_prepends_client_axis():
    cfg = get_config("llama3.2-1b")
    plan = plan_for(cfg)
    from repro.dist.sharding import ShardingRules

    rules = ShardingRules.__new__(ShardingRules)
    object.__setattr__(rules, "cfg", cfg)
    object.__setattr__(rules, "plan", plan)
    object.__setattr__(
        rules, "mesh", _fake_mesh({k: v for k, v in plan.shape.items() if v > 1})
    )
    model = build_model(cfg)
    specs = rules.param_specs(
        model.param_shapes(), model.param_axes(), stacked=True
    )
    for s in jax.tree.flatten(specs, is_leaf=lambda x: isinstance(x, P))[0]:
        assert s[0] == "client"


def test_host_plans_for_one_and_four_chips():
    cfg = get_config("rwkv6-1.6b")
    assert plan_for(cfg, device_count=1).shape == {
        "client": 1, "zero": 1, "tp": 1, "sp": 1
    }
    assert plan_for(cfg, device_count=4).shape == {
        "client": 2, "zero": 2, "tp": 1, "sp": 1
    }


def test_mesh_axes_are_auto():
    """with_sharding_constraint accepts only Auto axes; jax.make_mesh
    would default to Explicit ones."""
    rules = make_rules(
        None, get_reduced("llama3.2-1b"), device_count=1,
        devices=jax.devices()[:1],
    )
    assert set(rules.mesh.axis_types) == {jax.sharding.AxisType.Auto}


@pytest.mark.parametrize(
    "arch,layers,ok",
    [
        ("rwkv6-1.6b", 5, True),  # uniform stack: any depth
        ("gemma3-12b", 12, True),  # two 5-local:1-global periods
        ("gemma3-12b", 10, False),  # not whole periods
        ("hymba-1.5b", 32, True),  # global at first/middle/last layer
        ("hymba-1.5b", 16, False),  # its pattern spans the whole depth
        ("rwkv6-1.6b", 25, False),  # deeper than published
    ],
)
def test_with_depth_cuts_whole_periods(arch, layers, ok):
    cfg = get_config(arch)
    if not ok:
        with pytest.raises(ValueError):
            cfg.with_depth(layers)
        return
    cut = cfg.with_depth(layers)
    assert cut.num_layers == layers
    assert (cut.d_model, cut.d_ff, cut.vocab_size, cut.num_heads) == (
        cfg.d_model, cfg.d_ff, cfg.vocab_size, cfg.num_heads
    )
    assert cut.layer_windows() == cfg.layer_windows()[:layers]


def test_peak_table_keyed_by_device_kind():
    from repro.launch.mesh import peaks_for

    v5e = peaks_for("TPU v5 lite")
    assert (v5e.flops_bf16, v5e.hbm_bw) == (197e12, 819e9)
    with pytest.raises(KeyError):
        peaks_for("cpu")


def test_persistent_cache_leaves_env_dir_to_jax(monkeypatch):
    from repro.launch import compile_cache

    monkeypatch.setenv(compile_cache.ENV_VAR, "/somewhere/else")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_persistent_cache() == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir == before
