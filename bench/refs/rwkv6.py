"""Plain float32 RWKV6 ("Finch") language-model loss: the reference for
the rwkv6 configurations. It follows the published Finch block (token
shift with a low-rank data-dependent mix, data-dependent decay
``w = exp(-exp(w0 + lora(x)))``, the per-head WKV recurrence with its
bonus ``u``, a normed and gated output, squared-ReLU channel mix), with
RMSNorm ``x * rsqrt(mean(x^2) + eps) * (1 + scale)`` where upstream has
LayerNorm, as the configuration file notes. The recurrence is a plain
``lax.scan`` over time. Weights come in the program's parameter layout
(stacked over layers) from the benchmark's own seeded generator.
"""
from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from numerics import Numerics, rms_norm  # noqa: E402

HEAD = 64
N_MIX = 5  # w, k, v, r, g
MIX_RANK = 32
DECAY_RANK = 64


def matmul_weights(sz: dict) -> int:
    """Weights that multiply each token's activations, output head included
    (the embedding is a lookup)."""
    d, ff, v = sz["d_model"], sz["d_ff"], sz["vocab_size"]
    per_layer = (2 * d * N_MIX * MIX_RANK + 2 * d * DECAY_RANK + 6 * d * d
                 + 2 * d * ff)
    return sz["num_layers"] * per_layer + d * v


def recurrence_flops(sz: dict) -> int:
    """Forward WKV operations per token: per head, the outer product k v^T,
    the bonus u * kv and its sum with the state, the read r . (...), and
    the decayed update w * S + kv: 7 K V."""
    h = sz["d_model"] // HEAD
    return sz["num_layers"] * 7 * h * HEAD * HEAD


def train_flops_per_token(sz: dict) -> float:
    """The operations one training token requires, forward and backward:
    6 per matmul weight, 3 times the recurrence."""
    return 6.0 * matmul_weights(sz) + 3.0 * recurrence_flops(sz)


def _shift(x):
    return jnp.concatenate([jnp.zeros_like(x[:, :1]), x[:, :-1]], axis=1)


def _wkv(r, k, v, w, u):
    """y_t = r_t . (S + u * k_t v_t^T);  S <- w_t * S + k_t v_t^T, per head.
    r, k, v, w: (B, T, H, 64); u: (H, 64)."""
    b, t, h, n = r.shape

    def step(s, x):
        r_t, k_t, v_t, w_t = x
        kv = k_t[..., :, None] * v_t[..., None, :]
        y = jnp.einsum("bhk,bhkv->bhv", r_t, s + u[None, :, :, None] * kv,
                       precision=jax.lax.Precision.HIGHEST)
        return w_t[..., None] * s + kv, y

    xs = tuple(a.swapaxes(0, 1) for a in (r, k, v, w))
    _, ys = jax.lax.scan(step, jnp.zeros((b, h, n, n), jnp.float32), xs)
    return ys.swapaxes(0, 1)


def _block(lp, x, sizes, nm: Numerics):
    b, t, d = x.shape
    h = d // HEAD
    eps = sizes["rms_eps"]
    xn = rms_norm(x, lp["ln_tm"], eps)
    dx = _shift(xn) - xn
    mix = jnp.tanh(nm.mm(xn + dx * lp["maa_x"], lp["maa_w1"]))
    mix = nm.einsum("btnr,nrd->btnd", mix.reshape(b, t, N_MIX, MIX_RANK),
                    lp["maa_w2"])
    xw, xk, xv, xr, xg = (xn + dx * (lp["maa_wkvrg"][i] + mix[:, :, i])
                          for i in range(N_MIX))
    r = nm.mm(xr, lp["wr"]).reshape(b, t, h, HEAD)
    k = nm.mm(xk, lp["wk"]).reshape(b, t, h, HEAD)
    v = nm.mm(xv, lp["wv"]).reshape(b, t, h, HEAD)
    g = jax.nn.silu(nm.mm(xg, lp["wg"]))
    w = jnp.exp(-jnp.exp(lp["decay"] + nm.mm(jnp.tanh(nm.mm(xw, lp["decay_w1"])),
                                             lp["decay_w2"])))
    y = _wkv(r, k, v, w.reshape(b, t, h, HEAD), lp["u"]).reshape(b, t, d)
    x = x + nm.mm(rms_norm(y, lp["ln_x"], eps) * g, lp["wo"])

    xn = rms_norm(x, lp["ln_cm"], eps)
    dx = _shift(xn) - xn
    kk = jnp.square(jax.nn.relu(nm.mm(xn + dx * lp["cm_maa_k"], lp["cm_wk"])))
    rr = jax.nn.sigmoid(nm.mm(xn + dx * lp["cm_maa_r"], lp["cm_wr"]))
    return x + rr * nm.mm(kk, lp["cm_wv"])


def loss(params, tokens, sizes: dict, nm: Numerics):
    """Mean next-token cross-entropy of ``tokens`` (B, S+1), float32."""
    x = params["embed"][tokens[:, :-1]].astype(jnp.float32)
    # Each block is recomputed in the backward pass, so the reference's
    # gradient fits beside the program's memory on one chip.
    block = jax.checkpoint(lambda lp, x: _block(lp, x, sizes, nm))
    for i in range(sizes["num_layers"]):
        lp = jax.tree.map(lambda p: p[i].astype(jnp.float32), params["layers"])
        x = block(lp, x)
    x = rms_norm(x, params["final_norm"].astype(jnp.float32), sizes["rms_eps"])
    logits = nm.mm(x, params["lm_head"].astype(jnp.float32))
    logits = logits[..., : sizes["vocab_size"]]
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)
