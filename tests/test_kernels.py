"""Per-kernel allclose sweeps vs the pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.fedavg import fedavg_apply, fedavg_apply_ref, fedavg_apply_tree
from repro.kernels.flash_attention import flash_attention_ref
from repro.kernels.flash_attention.flash_attention import flash_attention_fwd
from repro.kernels.wkv6 import wkv6_ref
from repro.kernels.wkv6.wkv6 import wkv6_fwd

KEY = jax.random.PRNGKey(7)


# --------------------------------------------------------------------- #
# flash_attention
# --------------------------------------------------------------------- #
FLASH_CASES = [
    # (b, h, hkv, sq, sk, hd, window, bidirectional, dtype)
    (1, 2, 2, 128, 128, 64, 0, False, jnp.float32),
    (2, 4, 2, 256, 256, 64, 0, False, jnp.float32),
    (1, 4, 1, 128, 256, 128, 0, False, jnp.float32),  # tail-aligned q
    (2, 2, 2, 256, 256, 64, 96, False, jnp.float32),  # sliding window
    (1, 8, 4, 128, 128, 128, 64, False, jnp.float32),  # GQA + window
    (1, 2, 1, 128, 128, 64, 0, True, jnp.float32),  # bidirectional
    (1, 2, 2, 256, 256, 64, 0, False, jnp.bfloat16),
    (1, 2, 2, 128, 128, 256, 0, False, jnp.float32),  # gemma3 head_dim
]


@pytest.mark.parametrize("case", FLASH_CASES, ids=str)
def test_flash_attention_matches_ref(case):
    b, h, hkv, sq, sk, hd, window, bidir, dtype = case
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, h, sq, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (b, hkv, sk, hd), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (b, hkv, sk, hd), jnp.float32).astype(dtype)
    out = flash_attention_fwd(
        q, k, v, window=window, bidirectional=bidir,
        block_q=64, block_kv=64, interpret=True,
    )
    ref = flash_attention_ref(q, k, v, window=window, bidirectional=bidir)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=tol
    )


def test_flash_attention_block_shape_independence():
    """Output must not depend on the BlockSpec tiling."""
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 2, 256, 64))
    k = jax.random.normal(ks[1], (1, 2, 256, 64))
    v = jax.random.normal(ks[2], (1, 2, 256, 64))
    outs = [
        flash_attention_fwd(
            q, k, v, window=100, block_q=bq, block_kv=bk, interpret=True
        )
        for bq, bk in [(64, 64), (128, 64), (64, 128), (256, 256)]
    ]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o), atol=1e-5)


# --------------------------------------------------------------------- #
# wkv6
# --------------------------------------------------------------------- #
WKV_CASES = [
    # (b, t, h, dk, dv, chunk, dtype)
    (1, 64, 2, 64, 64, 32, jnp.float32),
    (2, 128, 4, 64, 64, 32, jnp.float32),
    (1, 96, 1, 32, 64, 32, jnp.float32),
    (2, 64, 2, 64, 64, 64, jnp.float32),
    (1, 64, 2, 64, 64, 16, jnp.float32),
    (1, 64, 2, 64, 64, 32, jnp.bfloat16),
]


@pytest.mark.parametrize("case", WKV_CASES, ids=str)
def test_wkv6_matches_ref(case):
    b, t, h, dk, dv, chunk, dtype = case
    ks = jax.random.split(KEY, 5)
    r = jax.random.normal(ks[0], (b, t, h, dk), jnp.float32).astype(dtype)
    k = (jax.random.normal(ks[1], (b, t, h, dk), jnp.float32) * 0.5).astype(dtype)
    v = jax.random.normal(ks[2], (b, t, h, dv), jnp.float32).astype(dtype)
    ww = jax.random.uniform(ks[3], (b, t, h, dk), minval=-4.0, maxval=0.5)
    w = jnp.exp(-jnp.exp(ww)).astype(dtype)
    u = (jax.random.normal(ks[4], (h, dk), jnp.float32) * 0.3).astype(jnp.float32)
    y, s = wkv6_fwd(r, k, v, w, u, chunk=chunk, interpret=True)
    yr, sr = wkv6_ref(r, k, v, w, u)
    tol = 1e-1 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(yr, np.float32), atol=tol
    )
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), atol=tol)


def test_wkv6_state_carry_composes():
    """Running two half-sequences with carried state == one full pass."""
    ks = jax.random.split(KEY, 5)
    b, t, h, dk, dv = 1, 64, 2, 64, 64
    r = jax.random.normal(ks[0], (b, t, h, dk))
    k = jax.random.normal(ks[1], (b, t, h, dk)) * 0.5
    v = jax.random.normal(ks[2], (b, t, h, dv))
    w = jnp.exp(-jnp.exp(jax.random.uniform(ks[3], (b, t, h, dk), minval=-3, maxval=0)))
    u = jax.random.normal(ks[4], (h, dk)) * 0.3
    y_full, s_full = wkv6_ref(r, k, v, w, u)
    y1, s1 = wkv6_ref(r[:, :32], k[:, :32], v[:, :32], w[:, :32], u)
    y2, s2 = wkv6_ref(r[:, 32:], k[:, 32:], v[:, 32:], w[:, 32:], u, initial_state=s1)
    np.testing.assert_allclose(np.asarray(y_full[:, 32:]), np.asarray(y2), atol=1e-5)
    np.testing.assert_allclose(np.asarray(s_full), np.asarray(s2), atol=1e-5)


# --------------------------------------------------------------------- #
# fedavg
# --------------------------------------------------------------------- #
FEDAVG_CASES = [
    (8, 1000, 256, jnp.float32),
    (16, 4096, 2048, jnp.float32),
    (32, 5000, 2048, jnp.bfloat16),
    (64, 333, 128, jnp.float32),
    (4, 2048, 4096, jnp.float32),  # block_d > d
]


@pytest.mark.parametrize("case", FEDAVG_CASES, ids=str)
def test_fedavg_matches_ref(case):
    n, d, bd, dtype = case
    ks = jax.random.split(KEY, 4)
    upd = jax.random.normal(ks[0], (n, d), jnp.float32).astype(dtype)
    base = jax.random.normal(ks[1], (d,), jnp.float32).astype(dtype)
    mask = jax.random.bernoulli(ks[2], 0.7, (n,))
    w = jnp.abs(jax.random.normal(ks[3], (n,))) * 100
    out = fedavg_apply(upd, base, mask, w, lr=0.9, block_d=bd)
    ref = fedavg_apply_ref(upd, base, mask, w, lr=0.9)
    tol = 5e-2 if dtype == jnp.bfloat16 else 2e-6
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=tol
    )


def test_fedavg_tree_matches_paper_example():
    """Kernel path reproduces the paper's §III.G FedAvg numbers."""
    upd = {"w": jnp.array([[0.2, -0.1], [0.0, 0.0], [0.5, 0.0]])}
    base = {"w": jnp.zeros((2,))}
    mask = jnp.array([True, False, True])
    sizes = jnp.array([100.0, 1.0, 300.0])
    out = fedavg_apply_tree(upd, base, mask, sizes)
    np.testing.assert_allclose(np.asarray(out["w"]), [0.425, -0.025], atol=1e-6)


def test_fedavg_all_masked_is_safe():
    upd = jnp.ones((4, 16))
    base = jnp.zeros((16,))
    out = fedavg_apply(upd, base, jnp.zeros(4, bool), jnp.ones(4))
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out), 0.0, atol=1e-6)


def test_wkv6_pallas_call_is_named():
    """The kernel carries its own name into the program (a TPU trace names
    the custom call after it); checked on the traced program, since the
    TPU lowering of this kernel's cumsum is not implemented."""
    x = jnp.zeros((1, 32, 1, 64), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda r, k, v, w, u: wkv6_fwd(r, k, v, w, u, interpret=True)
    )(x, x, x, x, jnp.zeros((1, 64), jnp.float32))

    def names(jx):
        for e in jx.eqns:
            if e.primitive.name == "pallas_call":
                yield e.params["name"]
            for v in e.params.values():
                if hasattr(v, "jaxpr"):  # a nested (closed) jaxpr
                    yield from names(getattr(v.jaxpr, "jaxpr", v.jaxpr))

    assert list(names(jaxpr.jaxpr)) == ["wkv6"]
