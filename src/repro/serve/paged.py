"""Device-side paged slot state + the compiled serving programs.

Everything here is shape-static so the engine AOT-compiles exactly two
executables per (model, slot-count) structure:

  * ``make_admit_fn``  — prefill one request (batch=1), scatter its
    prompt KV into the physical page pool at host-chosen page ids, seed
    the slot's next-token and the request's output row. One program per
    admission, reused for every request (page ids / slot / request id
    are traced operands).
  * ``make_decode_fn`` — ONE batched decode step over all S slots:
    per-slot positions, per-slot RoPE, KV writes routed through the page
    table (inactive slots write to the reserved trash page 0), ragged
    attention over the paged pool, greedy argmax, and token scatter into
    the device-resident output buffer (inactive slots land in the trash
    row). The output buffer is only synced to host ONCE, after the whole
    trace — the decode loop never materializes tokens host-side.

Attention modes:

  * ``dense`` — gather each slot's pages into a contiguous cache and run
    ``models.layers.attention_decode``. Because the gathered width equals
    the sequential oracle's ``cache_len`` and masked rows contribute
    exact zeros, this path reproduces the per-request decode
    *token-for-token* (the serving correctness contract).
  * ``paged`` — the Pallas paged flash-decode kernel: the page gather
    rides the BlockSpec index_map in the HBM pass, no gathered cache is
    materialized. fp32-tolerance vs. dense (online softmax reassociates).

Family support: DENSE / MOE / VLM / HYBRID route through the paged KV
pool (HYBRID adds slot-indexed SSM/conv states); SSM (rwkv6) has O(1)
recurrent state, so its "pool" is just the slot-indexed state and both
attention modes are no-ops. ENCDEC is rejected (its cross-attention
source cache is per-request ragged in a second axis).

Token-exactness note: MoE routing is batch-coupled (capacity grouping
across the slot batch), so MOE family serves correctly but is excluded
from the token-for-token contract — documented in EXPERIMENTS.md.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.kernels.paged_attention import paged_attention
from repro.kernels.paged_attention.ref import gather_pages
from repro.models import transformer as tf
from repro.models import rwkv6
from repro.models.api import Model
from repro.models.config import Family, ModelConfig
from repro.models.layers import attention_decode, rms_norm
from repro.models.transformer import Runtime, static_layer_meta

Array = jax.Array

ATTN_MODES = ("dense", "paged")


def check_family(cfg: ModelConfig) -> None:
    if cfg.family is Family.ENCDEC:
        raise NotImplementedError(
            "continuous batching does not cover ENCDEC: the cross-attention "
            "source cache is per-request ragged in a second axis"
        )


@dataclasses.dataclass(frozen=True)
class PagePlan:
    """Static paging geometry shared by engine, oracle and tests."""

    page_size: int
    prompt_len: int  # text tokens per request (static prefill shape)
    n_patches: int  # VLM frontend embeddings prepended at prefill
    max_gen: int  # per-request generation cap (sizes the slot span)

    @property
    def prompt_eff(self) -> int:
        """Cached positions after prefill (text + vision tokens)."""
        return self.prompt_len + self.n_patches

    @property
    def span(self) -> int:
        return self.prompt_eff + self.max_gen

    @property
    def pages_per_slot(self) -> int:
        """Page-table width; also fixes the oracle's cache_len (= width *
        page_size) so dense-path reductions match the oracle bitwise."""
        return -(-self.span // self.page_size)

    @property
    def prompt_pages(self) -> int:
        return -(-self.prompt_eff // self.page_size)

    @property
    def cache_len(self) -> int:
        return self.pages_per_slot * self.page_size

    def pages_for_gen(self, gen_len: int) -> int:
        """Physical pages a request with ``gen_len`` decode tokens needs."""
        return -(-(self.prompt_eff + int(gen_len)) // self.page_size)

    @classmethod
    def build(
        cls, cfg: ModelConfig, prompt_len: int, max_gen: int,
        page_size: int = 16, n_patches: int = 8,
    ) -> "PagePlan":
        check_family(cfg)
        return cls(
            page_size=page_size,
            prompt_len=prompt_len,
            n_patches=n_patches if cfg.family is Family.VLM else 0,
            max_gen=max_gen,
        )


# --------------------------------------------------------------------- #
# Pool construction
# --------------------------------------------------------------------- #
def init_pool(
    cfg: ModelConfig, plan: PagePlan, slots: int, num_pages: int, dtype=None
):
    """Fixed-shape device state. Physical page 0 is the trash page, so the
    k/v pools carry ``num_pages + 1`` physical rows, each laid out
    ``(Hkv, page, hd)`` as the paged kernel reads them."""
    check_family(cfg)
    if cfg.family is Family.SSM:
        pool = rwkv6.init_cache(cfg, slots, 0)
        pool.pop("pos")  # per-slot positions are host state in serving
        return pool
    dtype = dtype or jnp.dtype(cfg.compute_dtype)
    L, Hkv, hd = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim
    pool = {
        "k": jnp.zeros((L, num_pages + 1, Hkv, plan.page_size, hd), dtype),
        "v": jnp.zeros((L, num_pages + 1, Hkv, plan.page_size, hd), dtype),
    }
    if cfg.family is Family.HYBRID:
        pool["ssm_state"] = jnp.zeros(
            (L, slots, cfg.d_inner, cfg.ssm_state), jnp.float32
        )
        pool["conv_state"] = jnp.zeros(
            (L, slots, cfg.ssm_conv - 1, cfg.d_inner), jnp.float32
        )
    return pool


# --------------------------------------------------------------------- #
# Admission program: prefill -> page scatter -> slot seed
# --------------------------------------------------------------------- #
def make_admit_fn(model: Model, plan: PagePlan, runtime: Runtime = Runtime()):
    """Returns ``admit(params, pool, tokens, out_buf, prompt, [embeds,]
    pages, slot, req) -> (pool, tokens, out_buf)``.

    ``prompt`` is (1, prompt_len) int32; ``pages`` is (prompt_pages,)
    int32 physical page ids; ``slot``/``req`` are scalars. VLM models
    take the extra ``embeds`` (1, n_patches, d) operand.
    """
    cfg = model.cfg
    check_family(cfg)
    is_vlm = cfg.family is Family.VLM
    # Prefill chunks the prompt KV into whole pages; padding beyond the
    # prompt is zeros, overwritten in place once decode reaches it.
    prefill_len = plan.prompt_pages * plan.page_size

    def admit(params, pool, tokens, out_buf, prompt, *rest):
        if is_vlm:
            embeds, pages, slot, req = rest
            batch = {"tokens": prompt, "patch_embeds": embeds}
        else:
            pages, slot, req = rest
            batch = {"tokens": prompt}
        logits, cache = model.prefill(
            params, batch, cache_len=prefill_len, runtime=runtime
        )
        first = jnp.argmax(logits[0, -1], axis=-1).astype(jnp.int32)
        if cfg.family is Family.SSM:
            pool = dict(pool)
            for key in ("wkv", "tm_x", "cm_x"):
                pool[key] = pool[key].at[:, slot].set(cache[key][:, 0])
        else:
            L = cfg.num_layers
            shape = (L, plan.prompt_pages, plan.page_size) + cache["k"].shape[3:]

            def as_pages(c):  # (L, prefill_len, Hkv, hd) -> (L, n, Hkv, page, hd)
                return jnp.swapaxes(c[:, 0].reshape(shape), 2, 3)

            pool = dict(pool)
            pool["k"] = pool["k"].at[:, pages].set(as_pages(cache["k"]))
            pool["v"] = pool["v"].at[:, pages].set(as_pages(cache["v"]))
            if cfg.family is Family.HYBRID:
                pool["ssm_state"] = (
                    pool["ssm_state"].at[:, slot].set(cache["ssm_state"][:, 0])
                )
                pool["conv_state"] = (
                    pool["conv_state"].at[:, slot].set(cache["conv_state"][:, 0])
                )
        tokens = tokens.at[slot, 0].set(first)
        out_buf = out_buf.at[req, 0].set(first)
        return pool, tokens, out_buf

    return admit


# --------------------------------------------------------------------- #
# The one batched decode step
# --------------------------------------------------------------------- #
def _paged_transformer_step(
    params, cfg: ModelConfig, plan: PagePlan, pool, tokens, page_table,
    positions, active, runtime: Runtime, attn: str, interpret,
):
    """Slot-batched analogue of ``transformer.decode_step``: scalar
    ``cache["pos"]`` becomes per-slot ``positions`` and the contiguous
    cache becomes the page pool. Row-independent ops otherwise identical,
    which is what makes the dense path bitwise-match the oracle."""
    s = tokens.shape[0]
    page = plan.page_size
    x = tf.embed_inputs(params, cfg, tokens=tokens)  # (S, 1, d)
    pos2 = positions[:, None]  # (S, 1) per-slot RoPE positions
    arange = jnp.arange(s)
    # New-token KV target: the slot's current page, or trash page 0.
    tgt = jnp.where(active, page_table[arange, positions // page], 0)
    off = positions % page
    k_pool, v_pool = pool["k"], pool["v"]
    ss_all = pool.get("ssm_state")
    cs_all = pool.get("conv_state")

    for i in range(cfg.num_layers):
        lp = jax.tree.map(lambda p: p[i], params["layers"])
        h = rms_norm(x, lp["attn_norm"], cfg.rms_eps)
        q = jnp.einsum("bsd,dhk->bshk", h, lp["wq"])
        k = jnp.einsum("bsd,dhk->bshk", h, lp["wk"])
        v = jnp.einsum("bsd,dhk->bshk", h, lp["wv"])
        if cfg.qkv_bias:
            q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
        if cfg.qk_norm:
            q = rms_norm(q, lp["q_norm"], cfg.rms_eps)
            k = rms_norm(k, lp["k_norm"], cfg.rms_eps)
        w_i, th_i = static_layer_meta(cfg, i)
        q = tf.apply_rope(q, pos2, th_i)
        k = tf.apply_rope(k, pos2, th_i)
        k_pool = k_pool.at[i, tgt, :, off].set(k[:, 0])
        v_pool = v_pool.at[i, tgt, :, off].set(v[:, 0])
        if attn == "paged":
            lengths = jnp.where(active, positions + 1, 0)
            out = paged_attention(
                q[:, 0], k_pool[i], v_pool[i], page_table, lengths, w_i,
                interpret=interpret,
            )[:, None]
        else:
            kg = gather_pages(k_pool[i], page_table)  # (S, cache_len, ...)
            vg = gather_pages(v_pool[i], page_table)
            out = attention_decode(q, kg, vg, positions, w_i)
        attn_out = jnp.einsum("bshk,hkd->bsd", out, lp["wo"])
        if cfg.family is Family.HYBRID:
            hs = rms_norm(x, lp["ssm_norm"], cfg.rms_eps)
            ssm_out, ss_new, cs_new = tf._ssm_decode_step(
                lp, cfg, hs, ss_all[i], cs_all[i]
            )
            ss_all = ss_all.at[i].set(ss_new)
            cs_all = cs_all.at[i].set(cs_new)
            attn_out = 0.5 * (attn_out + ssm_out)
        x = x + attn_out
        h = rms_norm(x, lp["mlp_norm"], cfg.rms_eps)
        x = x + tf._ffn_block(lp, cfg, h, runtime)

    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    logits = tf._head_logits(params, cfg, x)  # (S, 1, V)
    pool = dict(pool, k=k_pool, v=v_pool)
    if cfg.family is Family.HYBRID:
        pool["ssm_state"], pool["conv_state"] = ss_all, cs_all
    return logits, pool


def make_logits_fn(
    model: Model,
    plan: PagePlan,
    runtime: Runtime = Runtime(),
    attn: str = "dense",
    interpret: bool | None = None,
):
    """Returns ``logits_fn(params, pool, tokens, page_table, positions,
    active) -> (logits (S, 1, V), pool)``: one batched decode step up to
    the logits, before sampling — where the two attention modes are
    compared."""
    cfg = model.cfg
    check_family(cfg)
    if attn not in ATTN_MODES:
        raise ValueError(f"attn must be one of {ATTN_MODES}, got {attn!r}")

    def logits_fn(params, pool, tokens, page_table, positions, active):
        if cfg.family is Family.SSM:
            cache = dict(pool, pos=jnp.zeros((), jnp.int32))
            logits, cache = rwkv6.decode_step(params, cfg, cache, tokens)
            cache.pop("pos")
            return logits, cache
        return _paged_transformer_step(
            params, cfg, plan, pool, tokens, page_table, positions,
            active, runtime, attn, interpret,
        )

    return logits_fn


def make_decode_fn(
    model: Model,
    plan: PagePlan,
    runtime: Runtime = Runtime(),
    attn: str = "dense",
    interpret: bool | None = None,
):
    """Returns ``step(params, pool, tokens, out_buf, page_table, positions,
    active, out_req, out_idx) -> (pool, tokens, out_buf)`` — the single
    executable that serves the whole trace.

    ``out_req``/``out_idx`` route each slot's new token into the device
    output buffer; the host passes the trash row for inactive slots.
    """
    logits_fn = make_logits_fn(model, plan, runtime, attn, interpret)

    def step(params, pool, tokens, out_buf, page_table, positions, active,
             out_req, out_idx):
        logits, pool = logits_fn(
            params, pool, tokens, page_table, positions, active
        )
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)  # (S,)
        tokens = nxt[:, None]
        out_buf = out_buf.at[out_req, out_idx].set(nxt)
        return pool, tokens, out_buf

    return step
