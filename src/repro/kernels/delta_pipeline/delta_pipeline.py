"""Pallas TPU kernel family: the fused FedFog delta pipeline.

The server side of a FedFog round (paper §IV, Fig. 1 tail) is a chain of
memory-bound passes over the ``(C, P)`` stacked client-delta buffer:

    clip-by-global-norm → compression emulation (top-k / int8) →
    staleness-discounted Eq. 6 weighting → aggregate → DP noise →
    server momentum (FedAvgM / FedAdam) → apply to the global model

XLA lowers the reference composition as one kernel per stage per leaf —
up to ~6 reads of the C·P delta floats from HBM. This family fuses the
whole chain into at most TWO passes over the delta stack:

  * ``delta_sq_norms`` — the norm reduction (only when clipping is on):
    grid over D-tiles, accumulating per-client Σx² into a (C,) output.
  * ``delta_pipeline_apply`` — everything else in ONE pass: each D-tile
    is read once, transformed in VMEM (clip scale, quant/dequant or
    top-k threshold mask), reduced over clients with C f32
    multiply-adds on the VPU, and combined with the (P,)-sized
    server-state tiles (base, momentum, DP noise) that ride along at
    1/C of the delta traffic.

The D-tile is derived, not fixed: ``tile_columns`` takes the most whole
8,192-column chunks whose streamed blocks (deltas, and whichever of
base, momentum, noise and segment ids the gates stream, inputs and
outputs) fit a 24 MiB double-buffered VMEM budget — about 4 MiB of f32
deltas a grid step at C = 2, 1,042 steps over the rwkv6-1.6b round's
P. The kernels ask the compiler for 32 MiB of scoped VMEM and compute a
step one chunk at a time. P need not be a multiple of the tile: the
last block is ragged, and the kernel handles it (masked out of the
norms, written back only up to P) instead of padding any operand.

Per-client scalars (clip scales, staleness discounts, Eq. 6 weights)
travel in tiny (1, C) vectors; per-(client, leaf) compression scales /
thresholds travel in a (C, L) table plus a (P,) segment-id row — inside
the kernel the table is expanded per tile with a static ``L``-way select
chain (no gather, VPU-friendly). ``lr`` rides as a (1, 1) SMEM-style
scalar input so a sweep-lifted ``server_lr`` stays data.

The top-k threshold and int8 max-abs reductions themselves are computed
by the caller-side wrapper in XLA (``lax.top_k`` needs a sort); they
read the buffer once more when compression is enabled but write only
(C, L) scalars.

Reference oracle: ``ref.py::delta_pipeline_ref`` (same op order on the
fused buffer, built from the repo's per-stage reference semantics).
Bitwise-equal at disabled gates; tolerance-bounded at enabled ones.
Interpret-mode fallback off-TPU, like the other kernels in the package.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.pallas_compat import interpret_default

_EPS = 1e-12  # matches core.aggregation._EPS / sim.events.staleness

# The streaming tile, shared by every kernel of the family. A grid step
# moves a block of ``block_d`` columns of every streamed operand; the
# grid has cdiv(P, block_d) steps, and the last, ragged block is read
# past P (values the kernels never let reach a kept result) and written
# back only up to P, so no operand is padded. Inside a step the block is
# computed a chunk of columns at a time, so the kernel's code and
# temporaries are a chunk's size whatever the block's.
_CHUNK = 8192  # the widest chunk: a multiple of the 1-D f32, int32, bf16 tiles
_CHUNK_ELEMS = 1 << 17  # one (client rows, chunk) f32 temporary: 512 KiB
_VMEM_BLOCKS = 24 << 20  # one step's streamed blocks, double-buffered
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("arbitrary",),
    vmem_limit_bytes=32 << 20,  # the blocks + the chunks' temporaries
)


def chunk_columns(rows: int) -> int:
    """Columns a kernel computes at once over ``rows`` client rows (C,
    or the selection network's power-of-two padded rows): 8,192 up to
    16 rows, halved as the rows double past that, at least 1,024."""
    chunk = _CHUNK
    while chunk > 1024 and rows * chunk > _CHUNK_ELEMS:
        chunk //= 2
    return chunk


def tile_columns(d: int, column_bytes: int, rows: int,
                 block_d: int | None = None) -> int:
    """Columns a grid step streams over a P = ``d`` axis.

    ``column_bytes``: what one column of every streamed operand, inputs
    and outputs, holds — C times the delta itemsize, plus each (P,)
    vector's. The tile is the most whole chunks (``chunk_columns(rows)``)
    whose blocks, double buffered, fit ``_VMEM_BLOCKS`` (FedAvgM at
    C = 2 and f32: 24 B a column, 512Ki columns, 4 MiB of deltas a
    step), and no more whole chunks than P holds; a P of one chunk or
    less is one block. ``block_d`` overrides the rule (tests force many
    tiles at small P).
    """
    if block_d is not None:
        return min(block_d, d)
    chunk = chunk_columns(rows)
    if d <= chunk:
        return d
    fit = _VMEM_BLOCKS // (2 * column_bytes) // chunk * chunk
    return max(chunk, min(fit, d // chunk * chunk))


def _for_each_chunk(block: int, rows: int, on_chunk) -> None:
    """Call ``on_chunk(cols)`` over a ``block``-column grid step, one
    chunk of columns at a time (the whole block when it is no chunk
    multiple, as an explicit small tile is)."""
    chunk = chunk_columns(rows)
    if block % chunk:
        chunk = block

    def body(j, carry):
        on_chunk(pl.ds(pl.multiple_of(j * chunk, chunk), chunk))
        return carry

    jax.lax.fori_loop(0, block // chunk, body, 0)


# --------------------------------------------------------------------- #
# pass 1: per-client squared norms (the clip reduction)
# --------------------------------------------------------------------- #
def _make_sq_norms_kernel(d: int, block: int):
    def kernel(upd_ref, out_ref):
        @pl.when(pl.program_id(0) == 0)
        def _init():
            out_ref[...] = jnp.zeros_like(out_ref)

        start = pl.program_id(0) * block

        def on_chunk(cols):
            x = upd_ref[:, cols].astype(jnp.float32)
            # The ragged last block reads past P: mask those columns.
            col = start + cols.start + jax.lax.broadcasted_iota(
                jnp.int32, x.shape, 1
            )
            x = jnp.where(col < d, x, 0.0)
            out_ref[...] = out_ref[...] + jnp.sum(x * x, axis=1)

        _for_each_chunk(block, upd_ref.shape[0], on_chunk)

    return kernel


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def delta_sq_norms(
    updates: jax.Array,  # (C, P)
    block_d: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Per-client Σx² over the fused delta buffer — one HBM pass."""
    interpret = interpret_default(interpret)
    c, d = updates.shape
    block_d = tile_columns(d, c * updates.dtype.itemsize, c, block_d)
    return pl.pallas_call(
        _make_sq_norms_kernel(d, block_d),
        grid=(pl.cdiv(d, block_d),),
        in_specs=[pl.BlockSpec((c, block_d), lambda i: (0, i))],
        out_specs=pl.BlockSpec((c,), lambda i: (0,)),
        out_shape=jax.ShapeDtypeStruct((c,), jnp.float32),
        compiler_params=_COMPILER_PARAMS,
        name="delta_sq_norms",
        interpret=interpret,
    )(updates)


# --------------------------------------------------------------------- #
# shared tile transform (clip scale + compression expansion)
# --------------------------------------------------------------------- #
def _transform_tile(x, cols, pre_ref, seg_ref, tab_ref, compression,
                    n_leaves):
    """The per-tile pre-aggregation transform of the ``cols`` columns,
    shared by the full pipeline kernel and the sharded partial-sum
    kernel: optional clip pre-scale, then compression emulation via a
    static ``n_leaves``-way select chain over the (C, L) table."""
    if pre_ref is not None:
        x = x * pre_ref[0, :][:, None]
    if compression != "none":
        # Expand the (C, L) per-leaf table to per-column values with
        # a static L-way select chain — no dynamic gather, so the
        # tile stays VPU-only on TPU.
        seg = seg_ref[cols]  # int32 leaf-segment ids
        tab = tab_ref[...].astype(jnp.float32)  # (C, L)
        col = jnp.ones(x.shape, jnp.float32)
        for l in range(n_leaves):
            col = jnp.where((seg == l)[None, :], tab[:, l][:, None], col)
        if compression == "int8":
            q = jnp.clip(jnp.round(x / col), -127.0, 127.0)
            x = q * col
        else:  # topk: col holds the kth-largest |x| per (client, leaf)
            x = x * (jnp.abs(x) >= col).astype(jnp.float32)
    return x


def _weighted_sum(w, x):
    """Eq. 6's client sum of one (C, chunk) tile under the (1, C) weight
    row ``w``: C multiply-adds in f32 on the VPU, in client order (an
    MXU dot at HIGHEST precision takes several passes for its one output
    row and would set the kernel's pace). Written product-first, so that
    a backend that fuses multiply-adds fuses each product into the
    running sum, as an f32 dot does."""
    acc = x[0:1] * w[:, 0:1]
    for k in range(1, x.shape[0]):
        acc = x[k:k + 1] * w[:, k:k + 1] + acc
    return acc[0]


def _network_rows(c: int) -> int:
    """Rows of the selection network over C clients: C padded to a power
    of two."""
    return 1 << max((c - 1).bit_length(), 0)


def _bitonic_sort(rows):
    """Ascending sort of a list of equal-shape rows, elementwise across
    the list, via a static bitonic compare-exchange network (the list
    length must be a power of two; callers pad with +inf rows).
    Produces the exact same sorted VALUES as ``jnp.sort`` over the row
    axis — the sorted sequence of a float multiset is unique — which is
    what makes the in-kernel median/trimmed selection bitwise-equal to
    the ``core.aggregation`` references. Each exchange is a min/max of
    two whole rows with static partners, so the network lowers on TPU
    without ``sort`` or a gather."""
    rows = list(rows)
    n = len(rows)
    k = 2
    while k <= n:
        j = k // 2
        while j >= 1:
            for i in range(n):
                p = i ^ j
                if p < i:
                    continue
                a, b = rows[i], rows[p]
                lo = jnp.where(a <= b, a, b)
                hi = jnp.where(a <= b, b, a)
                rows[i], rows[p] = (lo, hi) if (i & k) == 0 else (hi, lo)
            j //= 2
        k *= 2
    return rows


def _select_aggregate(x, wn, cnt, aggregator):
    """Masked coordinate-wise median / trimmed mean over the client axis
    of one (C, bd) tile — bitwise ``core.aggregation.median_aggregate``/
    ``trimmed_mean_aggregate`` semantics (+inf sentinel sort, identical
    index arithmetic, row sums in row order). ``wn`` is the (1, C) 0/1
    participation row and ``cnt`` the (1, 2) int32 [num_sel, k_trim]
    pair, traced data so participation masks stay dynamic."""
    c = x.shape[0]
    n2 = _network_rows(c)
    inf_row = jnp.full((1,) + x.shape[1:], jnp.inf, x.dtype)
    rows = [
        jnp.where(wn[:, i:i + 1] > 0.0, x[i:i + 1], jnp.inf)
        for i in range(c)
    ] + [inf_row] * (n2 - c)
    s = _bitonic_sort(rows)
    num_sel = cnt[:, 0:1]  # (1, 1): compared as vectors, no scalar reads
    if aggregator == "median":
        lo_idx = jnp.maximum((num_sel - 1) // 2, 0)
        hi_idx = num_sel // 2
        lo = hi = jnp.zeros_like(inf_row)
        for i, r in enumerate(s):
            lo = lo + jnp.where(lo_idx == i, r, 0.0)
            hi = hi + jnp.where(hi_idx == i, r, 0.0)
        return (0.5 * (lo + hi))[0]
    k_trim = cnt[:, 1:2]
    total = jnp.zeros_like(inf_row)
    for i, r in enumerate(s):
        keep = (k_trim <= i) & (num_sel - k_trim > i)
        total = total + jnp.where(keep, r, 0.0)
    n_keep = jnp.maximum(num_sel - 2 * k_trim, 1).astype(jnp.float32)
    return (total / n_keep)[0]


# --------------------------------------------------------------------- #
# pass 2: the fused transform + aggregate + server update
# --------------------------------------------------------------------- #
def _make_pipeline_kernel(
    n_leaves: int,
    has_pre: bool,
    compression: str,
    has_dp: bool,
    has_mu: bool,
    server_optimizer: str,
    server_momentum: float,
    aggregator: str = "fedavg",
):
    robust = aggregator in ("median", "trimmed")

    def kernel(*refs):
        it = iter(refs)
        wn_ref = next(it)  # (1, C): Eq. 6 weights, or the 0/1 mask (robust)
        cnt_ref = next(it) if robust else None  # (1, 2) [num_sel, k_trim]
        lr_ref = next(it)
        upd_ref = next(it)
        base_ref = next(it)
        pre_ref = next(it) if has_pre else None
        seg_ref = next(it) if compression != "none" else None
        tab_ref = next(it) if compression != "none" else None
        noise_ref = next(it) if has_dp else None
        mu_ref = next(it) if has_mu else None
        out_ref = next(it)
        new_mu_ref = next(it) if has_mu else None

        block = base_ref.shape[0]
        lr = lr_ref[0, 0].astype(jnp.float32)

        def on_chunk(cols):
            x = upd_ref[:, cols].astype(jnp.float32)  # (C, chunk)
            x = _transform_tile(x, cols, pre_ref, seg_ref, tab_ref,
                                compression, n_leaves)
            if robust:
                agg = _select_aggregate(x, wn_ref[...], cnt_ref[...],
                                        aggregator)
            else:
                agg = _weighted_sum(wn_ref[...].astype(jnp.float32), x)
            if has_dp:
                agg = agg + noise_ref[cols].astype(jnp.float32)
            if has_mu:
                mu2 = server_momentum * mu_ref[cols].astype(jnp.float32) + agg
                new_mu_ref[cols] = mu2.astype(new_mu_ref.dtype)
                if server_optimizer == "fedadam":
                    step = lr * mu2 / (jnp.sqrt(jnp.square(agg)) + 1e-3)
                else:  # fedavgm
                    step = lr * mu2
            else:
                step = lr * agg
            out_ref[cols] = (
                base_ref[cols].astype(jnp.float32) + step
            ).astype(out_ref.dtype)

        c = upd_ref.shape[0]
        _for_each_chunk(block, _network_rows(c) if robust else c, on_chunk)

    return kernel


def segment_table(updates, compression, topk_fraction, seg_sizes, pre=None):
    """(C, L) compression table: int8 dequant scales or top-k thresholds.

    THE single definition of the per-(client, leaf) reduction — the
    fused ``fl.compression.apply_compression`` path and the Pallas
    pipeline both consume it, so the epsilon / k-rounding rules cannot
    drift apart. The int8 scale is the per-leaf reference
    ``max|x|/127 + 1e-12`` via a segment scatter-max; the top-k
    threshold is the per-leaf kth-largest |x| from static leaf slices
    (``lax.top_k`` needs the static per-leaf ``k``).

    ``pre``: optional (C,) positive clip scales. The table is computed
    on the RAW deltas and rescaled — for a positive per-client scale s,
    ``max|s·x| = s·max|x|`` and the kth largest of ``|s·x|`` is
    ``s·(kth largest |x|)`` bitwise, so this equals computing the table
    on the clipped values without a second elementwise pass (the int8
    epsilon lands after the rescale, within the enabled-gate tolerance).
    """
    c = updates.shape[0]
    n_leaves = len(seg_sizes)
    if compression == "int8":
        seg = jnp.asarray(
            np.repeat(np.arange(n_leaves), seg_sizes), jnp.int32
        )
        tab = (
            jnp.zeros((c, n_leaves), jnp.float32)
            .at[:, seg].max(jnp.abs(updates))
        )
        if pre is not None:
            tab = tab * pre[:, None]
        return tab / 127.0 + 1e-12
    # topk: kth-largest |x| per (client, leaf); k is static per leaf.
    offs = np.concatenate(([0], np.cumsum(seg_sizes)))
    cols = []
    for l, sz in enumerate(seg_sizes):
        k = max(1, int(sz * topk_fraction))
        sl = jnp.abs(updates[:, int(offs[l]):int(offs[l + 1])])
        cols.append(jax.lax.top_k(sl, k)[0][:, -1:])
    tab = jnp.concatenate(cols, axis=1)
    if pre is not None:
        tab = tab * pre[:, None]
    return tab


@functools.partial(
    jax.jit,
    static_argnames=(
        "clip_norm", "compression", "topk_fraction", "seg_sizes",
        "server_optimizer", "server_momentum", "aggregator",
        "block_d", "interpret",
    ),
)
def delta_pipeline_apply(
    updates: jax.Array,  # (C, P) fused client deltas
    base: jax.Array,  # (P,) fused global model
    mask: jax.Array,  # (C,) bool participation
    weights: jax.Array,  # (C,) |D_i| dataset sizes
    lr: jax.Array | float = 1.0,  # server lr (traced-safe)
    staleness: jax.Array | None = None,  # (C,) staleness counts
    staleness_exponent: jax.Array | float = 0.0,  # a in (1+s)^-a
    dp_noise: jax.Array | None = None,  # (P,) pre-scaled Gaussian noise
    momentum: jax.Array | None = None,  # (P,) fused server momentum
    trim_fraction: jax.Array | float = 0.1,  # traced: sweep-liftable
    *,
    clip_norm: float = 0.0,  # static gate: per-client delta clip (0 = off)
    compression: str = "none",  # static: none | int8 | topk
    topk_fraction: float = 0.05,
    seg_sizes: tuple[int, ...] | None = None,  # fused-buffer leaf sizes
    server_optimizer: str = "fedavg",  # fedavg | fedavgm | fedadam
    server_momentum: float = 0.9,
    aggregator: str = "fedavg",  # fedavg | median | trimmed
    block_d: int | None = None,  # None: the tile rule (tile_columns)
    interpret: bool | None = None,
):
    """One-pass fused delta pipeline over the (C, P) buffer.

    Returns the updated (P,) model — or ``(model, new_mu)`` when a
    ``momentum`` buffer is supplied with a momentum server optimizer.

    Gate semantics mirror the per-stage reference paths exactly:
    ``clip_norm > 0`` → ``optim.clip_by_global_norm`` per client;
    ``compression`` → ``fl.compression.apply_compression``;
    ``staleness`` → ``sim.events.staleness.async_aggregate`` weighting
    (discount + global damping); ``dp_noise`` → noise added to the
    aggregate BEFORE the momentum/apply step (``core.privacy``);
    ``momentum`` → ``fl.round._server_update``; ``aggregator`` →
    ``core.aggregation.median_aggregate`` / ``trimmed_mean_aggregate``
    via the in-kernel bitonic selection network (bitwise; ``weights``
    and ``staleness`` do not apply — the robust aggregators are
    unweighted by construction, so staleness raises).
    """
    interpret = interpret_default(interpret)
    c, d = updates.shape
    if compression not in ("none", "int8", "topk"):
        raise ValueError(f"unknown compression {compression!r}")
    if compression != "none" and seg_sizes is None:
        raise ValueError("compression requires seg_sizes (fused leaf sizes)")
    if compression != "none" and int(sum(seg_sizes)) != d:
        raise ValueError(f"seg_sizes sum {sum(seg_sizes)} != P {d}")
    if aggregator not in ("fedavg", "median", "trimmed"):
        raise ValueError(f"unknown aggregator {aggregator!r}")
    robust = aggregator in ("median", "trimmed")
    if robust and staleness is not None:
        raise ValueError(
            f"aggregator={aggregator!r} is unweighted; staleness weighting "
            "does not compose with it"
        )
    has_mu = momentum is not None and server_optimizer in (
        "fedavgm", "fedadam"
    )
    has_dp = dp_noise is not None
    # Streamed bytes a column: the deltas, base in and out, and the
    # segment ids, noise and momentum in and out where their gates run.
    column_bytes = c * updates.dtype.itemsize + 2 * base.dtype.itemsize
    if compression != "none":
        column_bytes += 4
    if has_dp:
        column_bytes += dp_noise.dtype.itemsize
    if has_mu:
        column_bytes += 2 * momentum.dtype.itemsize
    rows = _network_rows(c) if robust else c
    bd = tile_columns(d, column_bytes, rows, block_d)

    # -- per-client scalars: Eq. 6 weights, staleness, clip scales ------ #
    if robust:
        # The wn row carries the raw participation mask; selection counts
        # travel in a (1, 2) int32 [num_sel, k_trim] pair so a lifted
        # ``trim_fraction`` stays traced data.
        wn = mask.astype(jnp.float32)
        num_sel = jnp.sum(mask.astype(jnp.int32))
        k_trim = jnp.floor(
            num_sel.astype(jnp.float32)
            * jnp.asarray(trim_fraction, jnp.float32)
        ).astype(jnp.int32)
        cnt = jnp.stack([num_sel, k_trim]).reshape(1, 2)
    else:
        m = mask.astype(jnp.float32) * weights.astype(jnp.float32)
        if staleness is not None:
            # (1+s)^-a discount + global damping — the async_aggregate
            # rule, bitwise ``fedavg_stacked`` at zero staleness
            # (damping == 1.0).
            s = jnp.maximum(jnp.asarray(staleness, jnp.float32), 0.0)
            disc = (1.0 + s) ** (
                -jnp.asarray(staleness_exponent, jnp.float32)
            )
            dm = m * disc
            wn = dm / (jnp.sum(dm) + _EPS)
            wn = wn * ((jnp.sum(dm) + _EPS) / (jnp.sum(m) + _EPS))
        else:
            wn = m / (jnp.sum(m) + _EPS)

    pre = None
    if clip_norm and clip_norm > 0:
        norm = jnp.sqrt(delta_sq_norms(updates, block_d, interpret))
        pre = jnp.minimum(1.0, clip_norm / jnp.maximum(norm, 1e-12))

    inputs = [wn[None, :]]
    in_specs = [pl.BlockSpec((1, c), lambda i: (0, 0))]
    if robust:
        inputs.append(cnt)
        in_specs.append(pl.BlockSpec((1, 2), lambda i: (0, 0)))
    inputs += [
        jnp.asarray(lr, jnp.float32).reshape(1, 1),
        updates,
        base,
    ]
    in_specs += [
        pl.BlockSpec((1, 1), lambda i: (0, 0)),
        pl.BlockSpec((c, bd), lambda i: (0, i)),
        pl.BlockSpec((bd,), lambda i: (i,)),
    ]
    n_leaves = len(seg_sizes) if seg_sizes else 0
    if pre is not None:
        inputs.append(pre[None, :])
        in_specs.append(pl.BlockSpec((1, c), lambda i: (0, 0)))
    if compression != "none":
        seg = jnp.asarray(
            np.repeat(np.arange(n_leaves), seg_sizes), jnp.int32
        )
        tab = segment_table(
            updates, compression, topk_fraction, seg_sizes, pre=pre
        )
        inputs += [seg, tab]
        in_specs += [
            pl.BlockSpec((bd,), lambda i: (i,)),
            pl.BlockSpec((c, n_leaves), lambda i: (0, 0)),
        ]
    if has_dp:
        inputs.append(dp_noise)
        in_specs.append(pl.BlockSpec((bd,), lambda i: (i,)))
    if has_mu:
        inputs.append(momentum)
        in_specs.append(pl.BlockSpec((bd,), lambda i: (i,)))

    out_shape = [jax.ShapeDtypeStruct((d,), base.dtype)]
    out_specs = [pl.BlockSpec((bd,), lambda i: (i,))]
    if has_mu:
        out_shape.append(jax.ShapeDtypeStruct((d,), momentum.dtype))
        out_specs.append(pl.BlockSpec((bd,), lambda i: (i,)))

    kernel = _make_pipeline_kernel(
        n_leaves, pre is not None, compression, has_dp, has_mu,
        server_optimizer, float(server_momentum), aggregator,
    )
    outs = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(d, bd),),
        in_specs=in_specs,
        out_specs=out_specs if has_mu else out_specs[0],
        out_shape=out_shape if has_mu else out_shape[0],
        compiler_params=_COMPILER_PARAMS,
        name="delta_pipeline_apply",
        interpret=interpret,
    )(*inputs)
    return tuple(outs) if has_mu else outs


# --------------------------------------------------------------------- #
# sharded building block: per-shard partial weighted sums
# --------------------------------------------------------------------- #
def _make_partial_kernel(n_leaves: int, has_pre: bool, compression: str):
    def kernel(*refs):
        it = iter(refs)
        dm_ref = next(it)  # (1, C_local) UNnormalized weights
        upd_ref = next(it)
        pre_ref = next(it) if has_pre else None
        seg_ref = next(it) if compression != "none" else None
        tab_ref = next(it) if compression != "none" else None
        out_ref = next(it)

        def on_chunk(cols):
            x = upd_ref[:, cols].astype(jnp.float32)
            x = _transform_tile(x, cols, pre_ref, seg_ref, tab_ref,
                                compression, n_leaves)
            out_ref[cols] = _weighted_sum(
                dm_ref[...].astype(jnp.float32), x)

        _for_each_chunk(out_ref.shape[0], upd_ref.shape[0], on_chunk)

    return kernel


@functools.partial(
    jax.jit,
    static_argnames=(
        "clip_norm", "compression", "topk_fraction", "seg_sizes",
        "block_d", "interpret",
    ),
)
def delta_pipeline_partial(
    updates: jax.Array,  # (C_local, P) fused client deltas, one shard
    dm: jax.Array,  # (C_local,) UNnormalized Eq. 6 weights (mask·|D|·disc)
    *,
    clip_norm: float = 0.0,
    compression: str = "none",
    topk_fraction: float = 0.05,
    seg_sizes: tuple[int, ...] | None = None,
    block_d: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Per-shard half of the sharded pipeline: clip + compression +
    UNnormalized weighted sum over this shard's clients — one HBM pass
    over the local delta slab. The clip norms are exact (each client's
    full (P,) row lives on one shard) and the compression table is
    shard-local, so the only cross-shard data the caller must combine is
    the (P,) partial plus the Σdm / Σm scalars → exactly one psum."""
    interpret = interpret_default(interpret)
    c, d = updates.shape
    # Streamed bytes a column: the deltas, the f32 partial, segment ids.
    column_bytes = c * updates.dtype.itemsize + 4
    if compression != "none":
        column_bytes += 4
    bd = tile_columns(d, column_bytes, c, block_d)

    pre = None
    if clip_norm and clip_norm > 0:
        norm = jnp.sqrt(delta_sq_norms(updates, block_d, interpret))
        pre = jnp.minimum(1.0, clip_norm / jnp.maximum(norm, 1e-12))

    inputs = [dm[None, :].astype(jnp.float32), updates]
    in_specs = [
        pl.BlockSpec((1, c), lambda i: (0, 0)),
        pl.BlockSpec((c, bd), lambda i: (0, i)),
    ]
    n_leaves = len(seg_sizes) if seg_sizes else 0
    if pre is not None:
        inputs.append(pre[None, :])
        in_specs.append(pl.BlockSpec((1, c), lambda i: (0, 0)))
    if compression != "none":
        seg = jnp.asarray(
            np.repeat(np.arange(n_leaves), seg_sizes), jnp.int32
        )
        tab = segment_table(
            updates, compression, topk_fraction, seg_sizes, pre=pre
        )
        inputs += [seg, tab]
        in_specs += [
            pl.BlockSpec((bd,), lambda i: (i,)),
            pl.BlockSpec((c, n_leaves), lambda i: (0, 0)),
        ]

    kernel = _make_partial_kernel(n_leaves, pre is not None, compression)
    return pl.pallas_call(
        kernel,
        grid=(pl.cdiv(d, bd),),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bd,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((d,), jnp.float32),
        compiler_params=_COMPILER_PARAMS,
        name="delta_pipeline_partial",
        interpret=interpret,
    )(*inputs)
