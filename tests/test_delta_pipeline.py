"""Fused delta-pipeline kernel vs per-stage references.

Contracts:
  (a) kernel (interpret) ≡ ``delta_pipeline_ref`` over the FULL gate
      matrix (DP × momentum × compression × clip × staleness) — BITWISE
      at disabled gates, tolerance-bounded at enabled ones;
  (b) the fused ``apply_compression`` path is bitwise-equal to the
      per-leaf reference loop;
  (c) the widened ``use_pallas_agg`` gates — sync simulator round with
      DP, async flush, pod-scale round with momentum/DP/compression —
      reproduce their reference paths to float tolerance.
"""
import dataclasses
import itertools

import jax
import jax.numpy as jnp
from jax.extend import core as jax_core
import numpy as np
import pytest

from repro.fl.compression import apply_compression
from repro.fl.fuse import (
    fuse_clients,
    fuse_vector,
    fused_gaussian_noise,
    leaf_sizes,
    segment_ids,
    stacked_leaf_sizes,
)
from repro.fl.simulator import FedFogSimulator, SimulatorConfig
from repro.kernels.delta_pipeline import (
    delta_pipeline_apply,
    delta_pipeline_partial,
    delta_pipeline_ref,
    delta_sq_norms,
)
from repro.kernels.delta_pipeline.delta_pipeline import (
    _CHUNK,
    _CHUNK_ELEMS,
    _VMEM_BLOCKS,
    chunk_columns,
    tile_columns,
)

KEY = jax.random.PRNGKey(7)

# A P past two chunks and no multiple of the derived tile (two chunks
# here): the default tile then leaves a ragged last block of 333 columns.
RAGGED_P = 2 * _CHUNK + 333

# Shape scales: "quick" forces many small tiles over odd segments,
# "full" is a simulator-sized buffer (the MLP the paper-scale engine
# trains), "derived" takes the default tile over a ragged P.
SCALES = {
    "quick": dict(c=6, seg_sizes=(40, 8, 64, 16), block_d=64),
    "full": dict(c=32, seg_sizes=(784 * 16, 16, 16 * 62, 62), block_d=2048),
    "derived": dict(c=6, seg_sizes=(3000, 8, RAGGED_P - 3024, 16),
                    block_d=None),
}


def _fixture(c, p):
    ks = jax.random.split(KEY, 6)
    return dict(
        upd=jax.random.normal(ks[0], (c, p)),
        base=jax.random.normal(ks[1], (p,)),
        mask=jax.random.bernoulli(ks[2], 0.7, (c,)),
        weights=jnp.abs(jax.random.normal(ks[3], (c,))) * 100,
        noise=0.1 * jax.random.normal(ks[4], (p,)),
        mu=jax.random.normal(ks[5], (p,)),
        staleness=jnp.arange(c, dtype=jnp.float32) % 4,
    )


GATES = list(
    itertools.product(
        [False, True],  # dp
        ["fedavg", "fedavgm", "fedadam"],  # momentum
        ["none", "int8", "topk"],  # compression
        [0.0, 1.5],  # clip
        [False, True],  # staleness
    )
)


@pytest.mark.parametrize("scale", list(SCALES))
@pytest.mark.parametrize("dp,opt,comp,clip,stale", GATES, ids=str)
def test_pipeline_matches_ref_gate_matrix(scale, dp, opt, comp, clip, stale):
    if scale == "full" and (dp, opt, comp, clip, stale) not in [
        # full scale: the all-off corner, the all-on corner, and one
        # mid-point per optimizer — the quick scale covers the matrix.
        (False, "fedavg", "none", 0.0, False),
        (True, "fedadam", "int8", 1.5, True),
        (True, "fedavgm", "topk", 0.0, True),
        (True, "fedavg", "topk", 1.5, False),
    ]:
        pytest.skip("full scale runs a gate subset")
    shp = SCALES[scale]
    c, seg_sizes, block_d = shp["c"], shp["seg_sizes"], shp["block_d"]
    fx = _fixture(c, sum(seg_sizes))
    kw = dict(
        lr=0.7,
        staleness=fx["staleness"] if stale else None,
        staleness_exponent=0.5,
        dp_noise=fx["noise"] if dp else None,
        momentum=fx["mu"] if opt != "fedavg" else None,
        clip_norm=clip,
        compression=comp,
        topk_fraction=0.1,
        seg_sizes=seg_sizes if comp != "none" else None,
        server_optimizer=opt,
        server_momentum=0.9,
    )
    out = delta_pipeline_apply(
        fx["upd"], fx["base"], fx["mask"], fx["weights"],
        block_d=block_d, **kw,
    )
    # jit the oracle too: eager-vs-jit FMA fusion is the only source of
    # 1-ulp noise in the disabled-gate comparison.
    ref = jax.jit(
        lambda u, b, m, w: delta_pipeline_ref(u, b, m, w, **kw)
    )(fx["upd"], fx["base"], fx["mask"], fx["weights"])
    outs = out if isinstance(out, tuple) else (out,)
    refs = ref if isinstance(ref, tuple) else (ref,)
    all_off = not dp and opt == "fedavg" and comp == "none" and clip == 0.0
    for o, r in zip(outs, refs):
        o, r = np.asarray(o), np.asarray(r)
        if all_off and not stale:
            np.testing.assert_array_equal(o, r)  # bitwise at disabled gates
        else:
            # fedadam divides by (|agg| + 1e-3): near-zero aggregates
            # amplify 1-ulp reduction-order noise, hence its wider tol.
            tol = 5e-3 if opt == "fedadam" else 1e-5
            np.testing.assert_allclose(o, r, atol=tol, rtol=1e-4)


def test_pipeline_zero_staleness_is_bitwise_plain():
    """disc(0)=1 and damping=1 exactly: a zero-staleness pipeline equals
    the staleness-free one bitwise (the async engine's sync-recovery
    contract, at kernel level)."""
    fx = _fixture(6, 128)
    a = delta_pipeline_apply(
        fx["upd"], fx["base"], fx["mask"], fx["weights"], lr=0.7,
        staleness=jnp.zeros((6,)), staleness_exponent=0.5, block_d=64,
    )
    b = delta_pipeline_apply(
        fx["upd"], fx["base"], fx["mask"], fx["weights"], lr=0.7,
        block_d=64,
    )
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_delta_sq_norms_matches_jnp():
    fx = _fixture(8, 1000)
    out = delta_sq_norms(fx["upd"], block_d=256)
    ref = jnp.sum(jnp.square(fx["upd"]), axis=1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6)


def test_pipeline_all_masked_is_safe():
    fx = _fixture(4, 64)
    out = delta_pipeline_apply(
        fx["upd"], fx["base"], jnp.zeros((4,), bool), fx["weights"],
        block_d=64,
    )
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(fx["base"]), atol=1e-6
    )


def test_delta_sq_norms_masks_the_ragged_block():
    """The derived tile over a ragged P: the interpreter fills the last
    block past P with NaN, so an unmasked sum would read NaN."""
    fx = _fixture(3, RAGGED_P)
    out = delta_sq_norms(fx["upd"])
    ref = jnp.sum(jnp.square(fx["upd"]), axis=1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6)


@pytest.mark.parametrize("p,block_d", [(1000, 256), (RAGGED_P, None)])
@pytest.mark.parametrize("clip", [0.0, 1.5])
def test_partial_matches_weighted_sum(p, block_d, clip):
    """The sharded and fog tiers' partial sum over forced and derived
    tiles, both with a ragged last block."""
    fx = _fixture(4, p)
    dm = fx["weights"] * fx["mask"]
    out = delta_pipeline_partial(
        fx["upd"], dm, clip_norm=clip, block_d=block_d
    )
    x = fx["upd"]
    if clip:
        norm = jnp.sqrt(jnp.sum(jnp.square(x), axis=1))
        x = x * jnp.minimum(1.0, clip / jnp.maximum(norm, 1e-12))[:, None]
    ref = jnp.einsum("n,nd->d", dm, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize(
    "d,column_bytes,rows",
    [(545_787_904, 24, 2), (1 << 20, 16 * 4 + 8, 16), (RAGGED_P, 24, 2),
     (100, 24, 2), (_CHUNK, 8, 2), (50_000_000, 8, 2),
     (1 << 22, 64 * 4 + 8, 64), (1 << 22, 128 * 4 + 8, 128)],
)
def test_tile_rule(d, column_bytes, rows):
    """The derived tile is whole chunks within the VMEM budget and P —
    one block at P of a chunk or less — the chunk narrows as the client
    rows grow, and the LM round's shape runs a few thousand grid
    steps."""
    bd = tile_columns(d, column_bytes, rows)
    chunk = chunk_columns(rows)
    assert rows * chunk <= _CHUNK_ELEMS or chunk == 1024
    if d <= chunk:
        assert bd == d
        return
    assert bd % chunk == 0 and chunk <= bd <= d
    assert bd == chunk or 2 * bd * column_bytes <= _VMEM_BLOCKS
    if d == 545_787_904:
        assert -(-d // bd) <= 4096
    assert tile_columns(d, column_bytes, rows, block_d=64) == 64


def _walk(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs nested in it, the
    Pallas kernels' bodies left out."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for v in eqn.params.values():
            for j in v if isinstance(v, (tuple, list)) else (v,):
                if isinstance(j, jax_core.ClosedJaxpr):
                    yield from _walk(j.jaxpr)
                elif isinstance(j, jax_core.Jaxpr):
                    yield from _walk(j)


@pytest.mark.parametrize(
    "kw",
    [
        dict(server_optimizer="fedavgm", momentum=True),
        dict(clip_norm=1.5, compression="int8", dp=True),
        dict(compression="topk", server_optimizer="fedadam", momentum=True),
        dict(aggregator="median"),
    ],
    ids=str,
)
def test_pipeline_pads_no_operand(kw):
    """At a P that is no multiple of the tile, the lowered apply passes
    the (C, P) deltas and the (P,) vectors to the kernel as they are:
    the ragged last block is handled inside the kernel, not by a
    ``jnp.pad`` copy of the operands."""
    kw = dict(kw)
    fx = _fixture(4, RAGGED_P)
    momentum = fx["mu"] if kw.pop("momentum", False) else None
    noise = fx["noise"] if kw.pop("dp", False) else None
    if "compression" in kw:
        kw["seg_sizes"] = (RAGGED_P - 100, 100)
    jaxpr = jax.make_jaxpr(
        lambda u, b, m, w: delta_pipeline_apply(
            u, b, m, w, 0.7, None, 0.0, noise, momentum, **kw)
    )(fx["upd"], fx["base"], fx["mask"], fx["weights"])
    eqns = list(_walk(jaxpr.jaxpr))
    pads = [e for e in eqns if e.primitive.name == "pad"
            and RAGGED_P in e.invars[0].aval.shape]
    assert pads == []
    grids = [e.params["grid_mapping"].grid for e in eqns
             if e.primitive.name == "pallas_call"
             and e.params["name"] == "delta_pipeline_apply"]
    assert grids == [(2,)]  # a block of two chunks, then the ragged 333


# --------------------------------------------------------------------- #
# in-kernel robust aggregators (median / trimmed) vs core.aggregation
# --------------------------------------------------------------------- #
def _core_robust(upd, base, mask, lr, frac, agg):
    """Reference: core.aggregation robust aggregate + plain apply."""
    from repro.core.aggregation import median_aggregate, trimmed_mean_aggregate

    a = (
        median_aggregate(upd, mask)
        if agg == "median"
        else trimmed_mean_aggregate(upd, mask, frac)
    )
    return base + lr * a


_MASKS = {
    "random": None,  # the _fixture bernoulli mask
    "all": "all",
    "alternating": "alt",
}


# odd + even client counts; small forced tiles, then the derived tile
# over a ragged P
@pytest.mark.parametrize(
    "c,p,block_d",
    [(5, 192, 64), (6, 192, 64), (5, RAGGED_P, None), (6, RAGGED_P, None)],
    ids=["5", "6", "5-derived", "6-derived"],
)
@pytest.mark.parametrize("mask_kind", list(_MASKS))
@pytest.mark.parametrize("agg,frac", [("median", 0.0), ("trimmed", 0.1),
                                      ("trimmed", 0.25)], ids=str)
def test_robust_kernel_bitwise_matches_core(agg, frac, mask_kind, c, p,
                                            block_d):
    """The in-kernel bitonic-selection median / trimmed mean is BITWISE
    equal to core.aggregation's jnp.sort-based references under masks
    (odd and even live counts)."""
    fx = _fixture(c, p)
    mask = {
        "random": fx["mask"],
        "all": jnp.ones((c,), bool),
        "alternating": jnp.arange(c) % 2 == 0,
    }[mask_kind]
    out = delta_pipeline_apply(
        fx["upd"], fx["base"], mask, fx["weights"], 0.7,
        None, 0.0, None, None, frac,
        aggregator=agg, block_d=block_d,
    )
    # jit the oracle (same FMA-fusion rationale as the gate matrix).
    exp = jax.jit(_core_robust, static_argnames="agg")(
        fx["upd"], fx["base"], mask, 0.7, frac, agg=agg
    )
    np.testing.assert_array_equal(np.asarray(out), np.asarray(exp))


@pytest.mark.parametrize("agg", ["median", "trimmed"])
def test_robust_kernel_random_masks_deterministic(agg):
    """Seeded random-mask sweep (runs without hypothesis): varying client
    counts, live counts and trim fractions, bitwise vs core.aggregation."""
    rng = np.random.default_rng(42)
    for _ in range(8):
        c = int(rng.integers(2, 11))
        p = int(rng.integers(1, 200))
        frac = float(rng.uniform(0.0, 0.45))
        upd = jnp.asarray(rng.normal(size=(c, p)), jnp.float32)
        base = jnp.asarray(rng.normal(size=(p,)), jnp.float32)
        mask = np.asarray(rng.random(c) < 0.6)
        mask[int(rng.integers(c))] = True  # ≥1 live client
        mask = jnp.asarray(mask)
        out = delta_pipeline_apply(
            upd, base, mask, jnp.ones((c,)), 1.0,
            None, 0.0, None, None, frac,
            aggregator=agg, block_d=64,
        )
        exp = jax.jit(_core_robust, static_argnames="agg")(
            upd, base, mask, 1.0, frac, agg=agg
        )
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(exp), err_msg=f"c={c} p={p} f={frac}"
        )


def test_robust_kernel_property_hypothesis():
    """Property form of the bitwise contract (hypothesis is a dev dep —
    skipped when absent; the deterministic sweep above always runs)."""
    pytest.importorskip(
        "hypothesis", reason="dev dependency; see requirements-dev.txt"
    )
    from hypothesis import given, settings
    from hypothesis import strategies as st

    @settings(max_examples=20, deadline=None)
    @given(
        c=st.integers(2, 12),
        p=st.integers(1, 96),
        frac=st.floats(0.0, 0.45),
        seed=st.integers(0, 2**31 - 1),
        agg=st.sampled_from(["median", "trimmed"]),
    )
    def prop(c, p, frac, seed, agg):
        rng = np.random.default_rng(seed)
        upd = jnp.asarray(rng.normal(size=(c, p)), jnp.float32)
        base = jnp.asarray(rng.normal(size=(p,)), jnp.float32)
        mask = np.asarray(rng.random(c) < 0.6)
        mask[int(rng.integers(c))] = True
        mask = jnp.asarray(mask)
        out = delta_pipeline_apply(
            upd, base, mask, jnp.ones((c,)), 1.0,
            None, 0.0, None, None, frac,
            aggregator=agg, block_d=64,
        )
        exp = jax.jit(_core_robust, static_argnames="agg")(
            upd, base, mask, 1.0, frac, agg=agg
        )
        np.testing.assert_array_equal(np.asarray(out), np.asarray(exp))

    prop()


def test_robust_with_dp_noise_matches_ref():
    """DP noise is added to the robust aggregate AFTER selection — the
    same caller-built stream as the fedavg path (gate flips must not
    change the noise; see test_fused_gaussian_noise_matches_reference)."""
    fx = _fixture(6, 192)
    out = delta_pipeline_apply(
        fx["upd"], fx["base"], fx["mask"], fx["weights"], 0.7,
        None, 0.0, fx["noise"], None, 0.1,
        aggregator="trimmed", block_d=64,
    )
    ref = jax.jit(
        lambda u, b, m, w, n: delta_pipeline_ref(
            u, b, m, w, 0.7, None, 0.0, n, None,
            aggregator="trimmed", trim_fraction=0.1,
        )
    )(fx["upd"], fx["base"], fx["mask"], fx["weights"], fx["noise"])
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_robust_rejects_staleness():
    """median/trimmed are unweighted order statistics — staleness
    discounting does not compose with them; the kernel refuses loudly."""
    fx = _fixture(4, 64)
    with pytest.raises(ValueError, match="unweighted"):
        delta_pipeline_apply(
            fx["upd"], fx["base"], fx["mask"], fx["weights"], 1.0,
            fx["staleness"], 0.5, None, None, 0.1,
            aggregator="median", block_d=64,
        )


# --------------------------------------------------------------------- #
# fused buffer helpers + fused compression (satellite)
# --------------------------------------------------------------------- #
def _delta_tree(c=6):
    ks = jax.random.split(KEY, 3)
    return {
        "a": jax.random.normal(ks[0], (c, 13, 7)),
        "b": jax.random.normal(ks[1], (c, 5)),
        "c": jax.random.normal(ks[2], (c, 31)),
    }


def test_fuse_roundtrips():
    tree = _delta_tree()
    cat, unfuse = fuse_clients(tree)
    assert cat.shape == (6, 13 * 7 + 5 + 31)
    back = unfuse(cat)
    for k in tree:
        np.testing.assert_array_equal(np.asarray(back[k]), np.asarray(tree[k]))
    agg = unfuse(cat[0])
    for k in tree:
        np.testing.assert_array_equal(np.asarray(agg[k]), np.asarray(tree[k][0]))
    one = {k: v[0] for k, v in tree.items()}
    vec, unvec = fuse_vector(one)
    back = unvec(vec)
    for k in one:
        np.testing.assert_array_equal(np.asarray(back[k]), np.asarray(one[k]))
    assert stacked_leaf_sizes(tree) == leaf_sizes(one) == (13 * 7, 5, 31)
    seg = np.asarray(segment_ids(stacked_leaf_sizes(tree)))
    assert seg.shape == (13 * 7 + 5 + 31,)
    assert (np.bincount(seg) == [13 * 7, 5, 31]).all()


@pytest.mark.parametrize("kind", ["int8", "topk"])
def test_fused_compression_bitwise_matches_per_leaf(kind):
    tree = _delta_tree()
    fused = apply_compression(tree, kind, 0.1, fused=True)
    ref = apply_compression(tree, kind, 0.1, fused=False)
    for k in tree:
        np.testing.assert_array_equal(
            np.asarray(fused[k]), np.asarray(ref[k]), err_msg=f"{kind}/{k}"
        )


def test_fused_gaussian_noise_matches_reference_mechanism():
    """The fused (P,) noise vector reproduces gaussian_mechanism's
    per-leaf draws exactly — enabling the kernel must not change the DP
    noise stream."""
    from repro.core.privacy import DPConfig, gaussian_mechanism

    tree = {k: v[0] for k, v in _delta_tree().items()}
    key = jax.random.fold_in(KEY, 9)
    cfg = DPConfig(sigma=0.3, sensitivity=1.1)
    zeros = jax.tree.map(jnp.zeros_like, tree)
    ref = gaussian_mechanism(zeros, key, cfg)
    vec = fused_gaussian_noise(
        key, cfg.sigma * cfg.sensitivity, leaf_sizes(tree),
        [x.shape for x in jax.tree.leaves(tree)],
    )
    _, unvec = fuse_vector(zeros)
    back = unvec(vec)
    for k in tree:
        np.testing.assert_array_equal(
            np.asarray(back[k]), np.asarray(ref[k]), err_msg=k
        )


# --------------------------------------------------------------------- #
# widened use_pallas_agg gates, end to end
# --------------------------------------------------------------------- #
def _cfg(**kw) -> SimulatorConfig:
    base = dict(
        task="emnist", num_clients=8, rounds=3, top_k=4, hidden=(16,), seed=0
    )
    base.update(kw)
    return SimulatorConfig(**base)


@pytest.mark.parametrize(
    "extra",
    [
        {"dp_sigma": 0.3, "clip_norm": 1.0},
        {"compression": "int8"},
        {"compression": "topk", "dp_sigma": 0.2, "clip_norm": 0.5},
        {"aggregator": "median", "dp_sigma": 0.3, "clip_norm": 1.0},
        {"aggregator": "trimmed", "trim_fraction": 0.2, "compression": "int8"},
    ],
    ids=str,
)
def test_simulator_pallas_gate_widened(extra):
    """use_pallas_agg now engages WITH DP noise / compression configs in
    the paper-scale simulator and reproduces the reference engine."""
    cfg = _cfg(**extra)
    h_ref = FedFogSimulator(cfg).run_scanned()
    h_pal = FedFogSimulator(
        dataclasses.replace(cfg, use_pallas_agg=True)
    ).run_scanned()
    for name in h_ref:
        np.testing.assert_allclose(
            np.asarray(h_ref[name]), np.asarray(h_pal[name]),
            rtol=1e-5, atol=1e-5, err_msg=f"{extra}/{name}",
        )


@pytest.mark.parametrize(
    "extra",
    [{}, {"dp_sigma": 0.3, "clip_norm": 1.0}, {"aggregator": "median"}],
    ids=str,
)
def test_async_flush_pallas_matches_reference(extra):
    """The async flush path routes through the fused kernel under
    use_pallas_agg — staleness discounting, DP and apply included."""
    from repro.sim.events.engine import AsyncConfig, AsyncFedFogSimulator

    cfg = _cfg(rounds=4, **extra)
    acfg = AsyncConfig.fedbuff(
        2, dispatch_interval_ms=500.0, staleness_exponent=0.5,
        straggler_sigma=0.2,
    )
    h_ref = AsyncFedFogSimulator(cfg, acfg).run()
    h_pal = AsyncFedFogSimulator(
        dataclasses.replace(cfg, use_pallas_agg=True), acfg
    ).run()
    assert h_ref["num_flushes"] == h_pal["num_flushes"]
    assert h_ref["num_dispatches"] == h_pal["num_dispatches"]
    np.testing.assert_allclose(
        h_ref["accuracy"], h_pal["accuracy"], rtol=1e-5, atol=1e-5,
        err_msg=str(extra),
    )
    np.testing.assert_allclose(
        h_ref["mean_staleness"], h_pal["mean_staleness"], atol=1e-6
    )


@pytest.mark.parametrize(
    "kw",
    [
        dict(server_optimizer="fedavgm", dp_sigma=0.05, clip_norm=1.0),
        dict(server_optimizer="fedadam"),
        dict(server_optimizer="fedavg", compression="int8"),
    ],
    ids=str,
)
def test_pod_round_pallas_gate_widened(kw):
    """fl/round.py routes momentum / DP / compression configs through
    the fused pipeline kernel; params and server momentum match the
    reference round to bf16 tolerance (the kernel aggregates in f32
    where the bf16 reference aggregates in bf16 — it is the more
    precise of the two)."""
    from repro.fl import FLConfig, init_fl_state, make_round_fn
    from repro.models import Family, ModelConfig, build_model

    tiny = ModelConfig(
        name="tiny", family=Family.DENSE, num_layers=2, d_model=64,
        num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128,
        vocab_size=128, remat=False, loss_chunk=0,
    )
    model = build_model(tiny)
    fl_ref = FLConfig(num_clients=8, slots=4, **kw)
    fl_pal = dataclasses.replace(fl_ref, use_pallas_agg=True)

    ks = jax.random.split(KEY, 8)
    n = fl_ref.num_clients
    batch = {
        "tokens": jax.random.randint(ks[0], (16, 33), 0, 128),
        "slot_data_sizes": jnp.abs(jax.random.normal(ks[1], (4,))) * 100 + 10,
        "telemetry_cpu": jax.random.uniform(ks[2], (n,), minval=0.5, maxval=1.0),
        "telemetry_mem": jax.random.uniform(ks[3], (n,), minval=0.5, maxval=1.0),
        "telemetry_batt": jax.random.uniform(ks[4], (n,), minval=0.5, maxval=1.0),
        "telemetry_energy": jax.random.uniform(ks[5], (n,), minval=0.55, maxval=1.0),
        "hist": jnp.abs(jax.random.normal(ks[6], (n, fl_ref.hist_bins))) + 1.0,
    }
    s_ref, _ = jax.jit(make_round_fn(model, fl_ref))(
        init_fl_state(model, fl_ref, KEY), batch
    )
    s_pal, _ = jax.jit(make_round_fn(model, fl_pal))(
        init_fl_state(model, fl_pal, KEY), batch
    )
    for a, b in zip(jax.tree.leaves(s_ref.params), jax.tree.leaves(s_pal.params)):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=5e-3, err_msg=str(kw),
        )
    assert (s_ref.server_mu is None) == (s_pal.server_mu is None)
    if s_ref.server_mu is not None:
        for a, b in zip(
            jax.tree.leaves(s_ref.server_mu), jax.tree.leaves(s_pal.server_mu)
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=5e-3, err_msg=f"mu {kw}"
            )
