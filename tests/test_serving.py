"""Continuous-batching serving engine: exactness, kernel oracle, invariants.

Contracts (docs/EXPERIMENTS.md §Serving):
  (a) with ``attn="dense"`` the slot-scheduled engine reproduces the
      sequential per-request oracle TOKEN-FOR-TOKEN on every non-MoE
      family (MoE routing is batch-coupled, so batching legitimately
      changes expert assignment — exempt by design);
  (b) the Pallas paged flash-decode kernel matches the dense-gather
      reference (same ``attention_decode`` the oracle runs) across GQA
      widths, sliding windows, ragged lengths and empty slots;
  (c) slot conservation: arrived == completed + rejected + in-flight +
      waiting at all times, pages return to the free list;
  (d) ONE decode executable serves everything — ``n_compiles`` is frozen
      at construction and stays put as slots churn and rates sweep.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.kernels.paged_attention import paged_attention, paged_attention_ref
from repro.models import build_model
from repro.serve import (
    ContinuousBatchingEngine,
    EngineConfig,
    PageAllocator,
    SequentialOracle,
    TraceConfig,
    make_trace,
    sweep_rates,
)

KEY = jax.random.PRNGKey(11)


def _build(arch):
    cfg = get_reduced(arch, loss_chunk=0)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _small(cfg, **kw):
    base = dict(
        n_requests=8, rate_per_s=400.0, slo_ms=8000.0, prompt_len=8,
        min_gen=1, max_gen=6,
    )
    base.update(kw)
    return make_trace(jax.random.PRNGKey(3), TraceConfig(**base), cfg)


ECFG = EngineConfig(
    slots=3, page_size=4, prompt_len=8, max_gen=6, max_requests=16
)


# --------------------------------------------------------------------- #
# (a) continuous == sequential per-request oracle, token-for-token
# --------------------------------------------------------------------- #
@pytest.mark.parametrize(
    "arch",
    ["llama3.2-1b", "hymba-1.5b", "rwkv6-1.6b", "internvl2-2b"],
)
def test_continuous_matches_oracle_exactly(arch):
    cfg, model, params = _build(arch)
    trace = _small(cfg)
    ref = SequentialOracle(model, params, ECFG).serve(trace)
    rep = ContinuousBatchingEngine(model, params, ECFG).serve(trace)
    assert rep.completed == trace.n_requests == ref.completed
    for req in range(trace.n_requests):
        assert rep.tokens_for(req) == ref.tokens_for(req), (arch, req)
    # Batching must never hurt virtual-time throughput vs one-at-a-time.
    assert rep.virtual_ms <= ref.virtual_ms + 1e-6
    assert np.isfinite(rep.latency_ms).all()


def test_gen_len_one_finishes_at_prefill():
    """Requests whose whole budget is the prefill token still complete,
    still match the oracle, and never occupy a decode slot."""
    cfg, model, params = _build("llama3.2-1b")
    trace = _small(cfg, min_gen=1, max_gen=1)
    ref = SequentialOracle(model, params, ECFG).serve(trace)
    rep = ContinuousBatchingEngine(model, params, ECFG).serve(trace)
    assert rep.completed == trace.n_requests
    assert rep.decode_steps == 0
    for req in range(trace.n_requests):
        assert rep.tokens_for(req) == ref.tokens_for(req)


# --------------------------------------------------------------------- #
# (b) paged kernel vs dense-gather reference
# --------------------------------------------------------------------- #
PAGED_CASES = [
    # (slots, hkv, group, hd, page, pages_per_slot, window)
    (4, 2, 1, 64, 8, 3, -1),
    (4, 2, 4, 64, 8, 3, -1),  # GQA
    (3, 1, 2, 128, 16, 2, -1),  # wide head
    (4, 2, 2, 64, 8, 4, 12),  # sliding window
    (5, 2, 2, 64, 4, 5, 6),  # window < page span
]


@pytest.mark.parametrize("case", PAGED_CASES, ids=str)
def test_paged_kernel_matches_dense_ref(case):
    s, hkv, g, hd, page, n, window = case
    num_pages = s * n + 1
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (s, hkv * g, hd), jnp.float32)
    k_pages = jax.random.normal(ks[1], (num_pages, hkv, page, hd), jnp.float32)
    v_pages = jax.random.normal(ks[2], (num_pages, hkv, page, hd), jnp.float32)
    table = (
        jax.random.permutation(ks[3], num_pages - 1)[: s * n] + 1
    ).reshape(s, n).astype(jnp.int32)
    # Ragged: every fill level from 1 token up to the full span, plus one
    # empty (evicted) slot that must come back as exact zeros.
    lengths = jnp.linspace(1, n * page, s).round().astype(jnp.int32)
    lengths = lengths.at[s // 2].set(0)
    out = paged_attention(
        q, k_pages, v_pages, table, lengths, window, interpret=True
    )
    ref = paged_attention_ref(q, k_pages, v_pages, table, lengths, window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    assert not np.asarray(out[s // 2]).any()


def test_paged_kernel_ignores_dead_pages():
    """Entries past ``lengths`` — stale data from an evicted request —
    must not leak into the output (continuous batching reuses pages
    without zeroing them)."""
    s, hkv, g, hd, page, n = 2, 2, 2, 64, 8, 3
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (s, hkv * g, hd), jnp.float32)
    k = jax.random.normal(ks[1], (s * n + 1, hkv, page, hd), jnp.float32)
    v = jax.random.normal(ks[2], (s * n + 1, hkv, page, hd), jnp.float32)
    table = jnp.arange(1, s * n + 1, dtype=jnp.int32).reshape(s, n)
    lengths = jnp.array([5, page * n], jnp.int32)
    out = paged_attention(q, k, v, table, lengths, interpret=True)
    # Scribble over every position at/after each slot's length.
    mask = jnp.arange(page * n).reshape(n, page)[None] >= lengths[:, None, None]
    mask = mask[:, :, None, :, None]  # -> (S, n, Hkv, page, hd) pool rows
    k2 = k.at[table].set(jnp.where(mask, 1e4, k[table]))
    v2 = v.at[table].set(jnp.where(mask, -1e4, v[table]))
    out2 = paged_attention(q, k2, v2, table, lengths, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))


def test_paged_engine_completes_trace():
    cfg, model, params = _build("llama3.2-1b")
    trace = _small(cfg)
    rep = ContinuousBatchingEngine(
        model, params, dataclasses.replace(ECFG, attn="paged")
    ).serve(trace)
    assert rep.completed == trace.n_requests
    assert rep.counters["arrived"] == rep.completed + rep.rejected
    toks = rep.tokens[: trace.n_requests]
    assert ((toks >= 0) & (toks < cfg.vocab_size)).all()


# --------------------------------------------------------------------- #
# (c) slot conservation + admission control
# --------------------------------------------------------------------- #
def test_slot_conservation_under_rejection():
    cfg, model, params = _build("llama3.2-1b")
    # Burst 12 arrivals into 2 slots with a 1-deep waiting queue: the
    # scheduler must reject the overflow, and every arrival must be
    # accounted for exactly once.
    ecfg = dataclasses.replace(ECFG, slots=2, max_queue=1, policy="edf")
    trace = _small(cfg, n_requests=12, rate_per_s=5000.0)
    rep = ContinuousBatchingEngine(model, params, ecfg).serve(trace)
    assert rep.rejected > 0
    c = rep.counters  # conservation() already asserted inside serve()
    assert c["arrived"] == trace.n_requests
    assert c["arrived"] == rep.completed + rep.rejected
    # Completed requests still carry oracle-exact tokens.
    ref = SequentialOracle(model, params, ecfg).serve(trace)
    done = np.nonzero(~np.isnan(rep.latency_ms))[0]
    assert done.size == rep.completed
    for req in done:
        assert rep.tokens_for(int(req)) == ref.tokens_for(int(req))


def test_page_allocator_roundtrip():
    alloc = PageAllocator(6)
    a = alloc.alloc(4)
    assert a is not None and len(set(a)) == 4 and 0 not in a
    assert alloc.alloc(3) is None  # only 2 left — all-or-nothing
    b = alloc.alloc(2)
    assert b is not None and not (set(a) & set(b))
    alloc.free(a)
    alloc.free(b)
    assert alloc.alloc(6) is not None  # everything came back


# --------------------------------------------------------------------- #
# (d) one-executable contract
# --------------------------------------------------------------------- #
def test_one_decode_executable_across_traces():
    cfg, model, params = _build("llama3.2-1b")
    eng = ContinuousBatchingEngine(model, params, ECFG)
    assert eng.n_compiles == {"admit": 1, "decode": 1}
    for seed in (3, 4):
        trace = make_trace(
            jax.random.PRNGKey(seed),
            TraceConfig(n_requests=6, rate_per_s=300.0, prompt_len=8,
                        min_gen=1, max_gen=6, slo_ms=8000.0),
            cfg,
        )
        rep = eng.serve(trace)
        assert rep.completed == 6
    # Slots churned through two traces on the same two executables.
    assert eng.n_compiles == {"admit": 1, "decode": 1}


def test_sweep_rates_compile_once():
    cfg, model, params = _build("llama3.2-1b")
    eng = ContinuousBatchingEngine(model, params, ECFG)
    res = sweep_rates(
        eng,
        TraceConfig(n_requests=6, prompt_len=8, min_gen=1, max_gen=6,
                    slo_ms=8000.0),
        rates_per_s=[20.0, 2000.0],
    )
    assert eng.n_compiles == {"admit": 1, "decode": 1}
    p95 = res.column("percentiles")  # -> the p95 column
    assert len(p95) == 2 and all(np.isfinite(p95))
    # Saturating arrivals can only raise queueing latency.
    assert p95[1] >= p95[0]


def test_encdec_family_rejected():
    cfg, model, params = _build("seamless-m4t-medium")
    with pytest.raises(NotImplementedError):
        ContinuousBatchingEngine(model, params, ECFG)
