"""Flash attention for TPU: fused tiled causal attention in Pallas.

The framework's hot-op kernel (the reference's hot ops are its Triton
quantization kernels, torchft/quantization.py:44-430; attention itself it
leaves to torch — on TPU the [T, T] score materialization is the dominant
HBM cost of the transformer, so this is where a Pallas kernel pays).

Standard FlashAttention-2 scheme, fwd + bwd:

- forward: one pass over K/V blocks per Q block with the online-softmax
  running (m, l) statistics in VMEM scratch; writes O and the per-row
  logsumexp L. Never materializes [T, T].
- backward: recomputes p = exp(q·kᵀ·scale − L) per tile from the saved L
  (no stored probabilities), accumulating dK/dV over Q blocks in one
  kernel and dQ over K/V blocks in another.
- causal block skipping: fully-masked tiles are skipped via ``pl.when``
  (half the FLOPs at long T), diagonal tiles masked elementwise.
- a sliding window (``window=w``: query ``i`` sees key ``j`` iff ``0 <= i - j
  < w``): the same three kernels on a grid whose last axis walks only the
  band's ``ceil((w - 1) / blk) + 1`` tiles beside each query (key) tile, so
  tiles older than the window are neither computed nor fetched; the band's
  two edges are masked elementwise.  These calls are named
  ``_fwd_window_kernel`` / ``_bwd_kv_window_kernel`` / ``_bwd_q_window_kernel``
  in the compiled program, so a trace tells them from the global ones.
- dtypes: matmuls run in the input dtype (bf16 on TPU) with f32
  accumulation; softmax statistics and accumulators are f32 scratch.

Layouts follow the guide (/opt/skills/guides/pallas_guide.md): blocks are
(sublane × lane)-aligned, row statistics ride a 128-lane minor dim.  Off
TPU every kernel runs in interpreter mode so the CPU test suite covers
the same code path.

Wired into the model as ``TransformerConfig(attn_impl="flash")``
(torchft_tpu/models/transformer.py); requires T % 128 == 0.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
_LANE = 128

# The names ``_flash_fwd`` gives the forward kernel's two results.  A
# ``jax.checkpoint`` whose policy saves these names (``models/transformer.py``
# ``_remat`` under ``"full"``) keeps ``o`` and ``lse`` from the forward pass, so
# its backward does not run the forward kernel a second time.  Without such a
# policy a name is the identity.
FLASH_OUT_NAME = "flash_attn_out"
FLASH_LSE_NAME = "flash_attn_lse"


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _block_size(t: int, d: int, at_most: int = 1024) -> int:
    """Largest tile that divides ``t`` — bigger tiles amortize the
    per-block softmax bookkeeping.  1024 engages only at head_dim <= 256
    (measured +3% whole-step at the d256 flagship; beyond d256 the
    q/k/v/acc tiles alone would crowd VMEM).  ``at_most`` (128 or more)
    caps it."""
    sizes = (1024, 512, 256, 128) if d <= 256 else (512, 256, 128)
    for blk in sizes:
        if t % blk == 0 and blk <= at_most:
            return blk
    raise ValueError(f"flash attention requires seq len % 128 == 0, got {t}")


# A windowed call's tile is at most this share of the window.  A query tile
# computes ``window + blk`` keys' worth of tiles for ``window`` live keys, so
# a smaller tile wastes less, but costs more a pair: on a v5e at ``T`` 8192,
# window 2048, heads of 128 (forward | forward + backward of one layer, ms)
# tiles of 1024 read 9.0 | 25.5, of 512 13.3 | 29.4, of 256 23.1 | 54.4
# against the causal call's 14.7 | 45.0.
_WINDOW_TILES = 2


def _tiles(tq: int, tk: int, width: int, window: "Optional[int]"):
    """``(blk_q, blk_k, band)`` of a call.  Under a window: square tiles of at
    most the window's ``_WINDOW_TILES``-th, and ``band``, how many key tiles a
    query tile sees (and how many query tiles see a key tile): the diagonal
    one and those before it down to the tile of key ``i - window + 1`` for
    the tile's first query ``i``.  ``band`` is None without a window."""
    if window is None:
        return _block_size(tq, width), _block_size(tk, width), None
    blk = _block_size(tq, width, at_most=max(window // _WINDOW_TILES, 128))
    return blk, blk, min(tk // blk, -(-(window - 1) // blk) + 1)


def _key_tile(i, step, band):
    """The key tile the inner grid axis is at beside query tile ``i``:
    ``step`` itself, or the band's, its last the diagonal one; a tile before
    the sequence's start repeats tile 0, which is fetched once."""
    return step if band is None else jnp.maximum(i - (band - 1) + step, 0)


def _query_tile(j, step, band, n):
    """The query tile the inner grid axis is at beside key tile ``j``:
    ``step`` itself, or the band's, its first the diagonal one; a tile past
    the last of the ``n`` repeats it."""
    return step if band is None else jnp.minimum(j + step, n - 1)


def _visible(rq, rk, window):
    """The causal mask of query rows ``rq`` on key rows ``rk``, and under a
    window its older edge."""
    if window is None:
        return rq >= rk
    return jnp.logical_and(rq >= rk, rq - rk < window)


def _on_live_tiles(needed, window, ahead, blk, tile):
    """Runs ``tile(masked)`` where the tile is ``needed``.  Under a window a
    tile ``ahead()`` tiles before the diagonal that lies wholly inside the
    band (every ``i - j`` in ``[1, window)``) runs without the mask's compares
    and selects: of the band's ``w / blk + 1`` tiles only the two at its
    edges need them."""
    if window is None:
        pl.when(needed)(lambda: tile(True))
        return
    inside = jnp.logical_and(ahead() >= 1, (ahead() + 1) * blk <= window)
    pl.when(jnp.logical_and(needed, inside))(lambda: tile(False))
    pl.when(jnp.logical_and(needed, jnp.logical_not(inside)))(lambda: tile(True))


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------


def _fwd_kernel(
    offs_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_s, l_s,
    *, scale, causal, blk_q, blk_k, window=None
):
    """offs_ref: SMEM int32 [2] = (q_offset, k_offset) GLOBAL positions of
    this call's first query/key row — the ring composition runs the kernel
    on local chunks whose causal relation depends on the shard offsets.

    ``window``: the last grid axis walks the band's tiles only (square
    tiles, no offsets), the last of them the diagonal tile."""
    i = pl.program_id(1)
    step = pl.program_id(2)
    nj = pl.num_programs(2)
    j = step if window is None else i - (nj - 1) + step
    q_off, k_off = offs_ref[0], offs_ref[1]

    @pl.when(step == 0)
    def _():
        acc[:] = jnp.zeros_like(acc)
        m_s[:] = jnp.full_like(m_s, _NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)

    if window is None:
        # causal: this tile is live unless every key position exceeds every
        # query position in the block
        needed = jnp.logical_or(
            not causal, k_off + j * blk_k <= q_off + i * blk_q + blk_q - 1
        )
    else:
        needed = j >= 0  # a tile before the sequence's start

    def tile(masked):
        q = q_ref[0]
        s = jax.lax.dot_general(
            q,
            k_ref[0],
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [blk_q, blk_k]
        if causal and masked:
            rq = q_off + i * blk_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0
            )
            rk = k_off + j * blk_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1
            )
            s = jnp.where(_visible(rq, rk, window), s, _NEG_INF)
        m_prev = m_s[:, :1]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        # A query row with zero live keys so far has m_new == _NEG_INF, so
        # s - m_new == 0 for every MASKED entry and p would be 1 — O would
        # become a garbage mean of V.  Zero p for such rows instead: l
        # stays 0, O resolves to 0 and lse to ~-inf, so callers passing
        # offsets (ring chunks where q precedes every k) get an exact
        # zero-weight chunk rather than relying on the combiner's
        # exp-underflow to hide it.
        p = jnp.where(m_new > _NEG_INF / 2, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_s[:] = jnp.broadcast_to(
            l_s[:, :1] * corr + p.sum(axis=1, keepdims=True), l_s.shape
        )
        m_s[:] = jnp.broadcast_to(m_new, m_s.shape)
        acc[:] = acc[:] * corr + jax.lax.dot_general(
            p.astype(q.dtype),
            v_ref[0],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    _on_live_tiles(needed, window, lambda: nj - 1 - step, blk_k, tile)

    @pl.when(step == nj - 1)
    def _():
        l = jnp.maximum(l_s[:, :1], 1e-30)
        o_ref[0] = (acc[:] / l).astype(o_ref.dtype)
        # [blk, 1] column -> [1, blk] lane vector (Mosaic relayout)
        lse_ref[0] = (m_s[:, :1] + jnp.log(l)).reshape(1, -1)


def _fwd(
    q3: jax.Array,
    k3: jax.Array,
    v3: jax.Array,
    scale: float,
    causal: bool,
    offsets: "Optional[jax.Array]" = None,
    window: "Optional[int]" = None,
) -> "Tuple[jax.Array, jax.Array]":
    bh, tq, d = q3.shape
    tk, dv = k3.shape[1], v3.shape[2]  # values may be narrower than q/k
    if offsets is None:
        offsets = jnp.zeros((2,), jnp.int32)
    blk_q, blk_k, band = _tiles(tq, tk, max(d, dv), window)
    grid = (bh, tq // blk_q, band or tk // blk_k)

    def kv(b, i, step):
        return (b, _key_tile(i, step, band), 0)

    o, lse = pl.pallas_call(
        functools.partial(
            _fwd_kernel, scale=scale, causal=causal, blk_q=blk_q, blk_k=blk_k,
            window=window,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, blk_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, blk_k, d), kv),
            pl.BlockSpec((1, blk_k, dv), kv),
        ],
        out_specs=(
            pl.BlockSpec((1, blk_q, dv), lambda b, i, j: (b, i, 0)),
            # row stats as [bh, 1, t]: a (1, 1, blk) block keeps the
            # sublane dim equal to the array's (TPU block-shape rule) and
            # the per-row scalars on lanes — 128x less HBM than
            # broadcasting to a [bh, t, 128] stat plane
            pl.BlockSpec((1, 1, blk_q), lambda b, i, j: (b, 0, i)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((bh, tq, dv), q3.dtype),
            jax.ShapeDtypeStruct((bh, 1, tq), jnp.float32),
        ),
        scratch_shapes=[
            pltpu.VMEM((blk_q, dv), jnp.float32),
            pltpu.VMEM((blk_q, _LANE), jnp.float32),
            pltpu.VMEM((blk_q, _LANE), jnp.float32),
        ],
        interpret=_interpret(),
        # the benchmark finds the three kernels in a trace by these names
        # (benchmarks/families/*.py FLASH_KERNELS)
        name="_fwd_kernel" if window is None else "_fwd_window_kernel",
    )(offsets.astype(jnp.int32), q3, k3, v3)
    return o, lse[:, 0]


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------


def _recompute_p(q, k, lse_row, scale, causal, q_pos0, k_pos0, window=None):
    """exp(q·kᵀ·scale − L) with the causal mask — shared by both bwd
    kernels.  lse_row: [1, blk_q] f32 lane vector (reshaped to a column
    here; Mosaic relayout).  q_pos0/k_pos0: GLOBAL position of the first
    row of each block."""
    lse_col = lse_row.reshape(-1, 1)  # lane vector -> column
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    p = jnp.exp(s - lse_col)
    if causal:
        rq = q_pos0 + jax.lax.broadcasted_iota(jnp.int32, p.shape, 0)
        rk = k_pos0 + jax.lax.broadcasted_iota(jnp.int32, p.shape, 1)
        p = jnp.where(_visible(rq, rk, window), p, 0.0)
    return p


def _bwd_kv_kernel(
    offs_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal, blk_q, blk_k,
    window=None,
):
    j = pl.program_id(1)  # K/V block (outer)
    step = pl.program_id(2)  # Q block (inner, accumulated)
    ni = pl.num_programs(2)
    # under a window: the band's query tiles, the first the diagonal one
    i = step if window is None else j + step
    q_off, k_off = offs_ref[0], offs_ref[1]

    @pl.when(step == 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    if window is None:
        needed = jnp.logical_or(
            not causal, q_off + i * blk_q + blk_q - 1 >= k_off + j * blk_k
        )
    else:
        needed = i < pl.num_programs(1)  # a tile past the sequence's end

    def tile(masked):
        q = q_ref[0]
        do = do_ref[0]
        p = _recompute_p(
            q, k_ref[0], lse_ref[0], scale, causal and masked,
            q_off + i * blk_q, k_off + j * blk_k, window,
        )
        pt = p.astype(q.dtype)
        dv_acc[:] = dv_acc[:] + jax.lax.dot_general(
            pt, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dp = jax.lax.dot_general(
            do, v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_ref[0].reshape(-1, 1)) * scale
        dk_acc[:] = dk_acc[:] + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    _on_live_tiles(needed, window, lambda: step, blk_k, tile)

    @pl.when(step == ni - 1)
    def _():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_q_kernel(
    offs_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dq_ref, dq_acc, *, scale, causal, blk_q, blk_k, window=None,
):
    i = pl.program_id(1)  # Q block (outer)
    step = pl.program_id(2)  # K/V block (inner, accumulated)
    nj = pl.num_programs(2)
    # under a window: the band's key tiles, the last the diagonal one
    j = step if window is None else i - (nj - 1) + step
    q_off, k_off = offs_ref[0], offs_ref[1]

    @pl.when(step == 0)
    def _():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    if window is None:
        needed = jnp.logical_or(
            not causal, k_off + j * blk_k <= q_off + i * blk_q + blk_q - 1
        )
    else:
        needed = j >= 0

    def tile(masked):
        q = q_ref[0]
        p = _recompute_p(
            q, k_ref[0], lse_ref[0], scale, causal and masked,
            q_off + i * blk_q, k_off + j * blk_k, window,
        )
        dp = jax.lax.dot_general(
            do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_ref[0].reshape(-1, 1)) * scale
        dq_acc[:] = dq_acc[:] + jax.lax.dot_general(
            ds.astype(q.dtype), k_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    _on_live_tiles(needed, window, lambda: nj - 1 - step, blk_k, tile)

    @pl.when(step == nj - 1)
    def _():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd(
    q3, k3, v3, o3, lse, do3, scale: float, causal: bool,
    offsets: "Optional[jax.Array]" = None,
    delta: "Optional[jax.Array]" = None,
    window: "Optional[int]" = None,
) -> "Tuple[jax.Array, jax.Array, jax.Array]":
    bh, tq, d = q3.shape
    tk, d_v = k3.shape[1], v3.shape[2]
    blk, blk_kk, band = _tiles(tq, tk, max(d, d_v), window)
    n = tq // blk
    nk = tk // blk_kk

    def q_of(jj, step):
        return _query_tile(jj, step, band, n)

    def kv_of(ii, step):
        return _key_tile(ii, step, band)

    if offsets is None:
        offsets = jnp.zeros((2,), jnp.int32)
    offsets = offsets.astype(jnp.int32)
    if delta is None:
        # delta_i = rowsum(dO * O): tiny elementwise pass, plain XLA
        delta = jnp.sum(
            do3.astype(jnp.float32) * o3.astype(jnp.float32), axis=-1
        )
    delta = delta[:, None, :]  # [bh, 1, t]
    lse3 = lse[:, None, :]

    # kv kernel grid = (b, j, i): index maps receive (b, kv_block, q_block)
    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_kv_kernel, scale=scale, causal=causal, blk_q=blk,
            blk_k=blk_kk, window=window,
        ),
        grid=(bh, nk, band or n),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, blk, d), lambda b, jj, ii: (b, q_of(jj, ii), 0)),     # q
            pl.BlockSpec((1, blk_kk, d), lambda b, jj, ii: (b, jj, 0)),  # k
            pl.BlockSpec((1, blk_kk, d_v), lambda b, jj, ii: (b, jj, 0)),  # v
            pl.BlockSpec((1, blk, d_v), lambda b, jj, ii: (b, q_of(jj, ii), 0)),     # do
            pl.BlockSpec((1, 1, blk), lambda b, jj, ii: (b, 0, q_of(jj, ii))),  # lse
            pl.BlockSpec((1, 1, blk), lambda b, jj, ii: (b, 0, q_of(jj, ii))),  # delta
        ],
        out_specs=(
            pl.BlockSpec((1, blk_kk, d), lambda b, jj, ii: (b, jj, 0)),
            pl.BlockSpec((1, blk_kk, d_v), lambda b, jj, ii: (b, jj, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((bh, tk, d), q3.dtype),
            jax.ShapeDtypeStruct((bh, tk, d_v), q3.dtype),
        ),
        scratch_shapes=[
            pltpu.VMEM((blk_kk, d), jnp.float32),
            pltpu.VMEM((blk_kk, d_v), jnp.float32),
        ],
        interpret=_interpret(),
        name="_bwd_kv_kernel" if window is None else "_bwd_kv_window_kernel",
    )(offsets, q3, k3, v3, do3, lse3, delta)

    # q kernel grid = (b, i, j): index maps receive (b, q_block, kv_block)
    dq = pl.pallas_call(
        functools.partial(
            _bwd_q_kernel, scale=scale, causal=causal, blk_q=blk,
            blk_k=blk_kk, window=window,
        ),
        grid=(bh, n, band or nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, blk, d), lambda b, ii, jj: (b, ii, 0)),     # q
            pl.BlockSpec((1, blk_kk, d), lambda b, ii, jj: (b, kv_of(ii, jj), 0)),  # k
            pl.BlockSpec((1, blk_kk, d_v), lambda b, ii, jj: (b, kv_of(ii, jj), 0)),  # v
            pl.BlockSpec((1, blk, d_v), lambda b, ii, jj: (b, ii, 0)),     # do
            pl.BlockSpec((1, 1, blk), lambda b, ii, jj: (b, 0, ii)),  # lse
            pl.BlockSpec((1, 1, blk), lambda b, ii, jj: (b, 0, ii)),  # delta
        ],
        out_specs=pl.BlockSpec((1, blk, d), lambda b, ii, jj: (b, ii, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, tq, d), q3.dtype),
        scratch_shapes=[pltpu.VMEM((blk, d), jnp.float32)],
        interpret=_interpret(),
        name="_bwd_q_kernel" if window is None else "_bwd_q_window_kernel",
    )(offsets, q3, k3, v3, do3, lse3, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# differentiable wrapper
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, scale, causal, window):
    """``[B, T, H, D]`` x 2, ``[B, T, H, Dv]`` -> ``[B, T, H, Dv]``; ``k`` and
    ``v`` carry the query's heads."""
    b, _, h, _ = q.shape
    o3, _ = _fwd(_to3(q), _to3(k), _to3(v), scale, causal, window=window)
    return _from3(o3, b, h)


def _flash_fwd(q, k, v, scale, causal, window):
    b, t, h, _ = q.shape
    o3, lse = _fwd(_to3(q), _to3(k), _to3(v), scale, causal, window=window)
    # ``o`` is named as the rows a block reads it in, ``[B, T, H Dv]``: the
    # forward pass lays those out anyway, and a stack of the kernel's own
    # ``[bh, T, 64]`` would be padded to 128 lanes and hold twice its bytes.
    # The primal result is read from the named rows, so that a checkpoint
    # which saves the name needs no second ``o``.
    o = checkpoint_name(_from3(o3, b, h).reshape(b, t, -1), FLASH_OUT_NAME)
    lse = checkpoint_name(lse, FLASH_LSE_NAME)
    return o.reshape(b, t, h, -1), (q, k, v, o, lse)


def _flash_bwd(scale, causal, window, res, do):
    q, k, v, o, lse = res
    b, t, h, _ = q.shape
    # delta_i = rowsum(dO * O), from the rows as they were kept
    delta = jnp.sum(
        do.astype(jnp.float32) * o.reshape(do.shape).astype(jnp.float32), axis=-1
    ).transpose(0, 2, 1).reshape(b * h, t)
    dq, dk, dv = _bwd(
        _to3(q), _to3(k), _to3(v), None, lse, _to3(do), scale, causal,
        delta=delta, window=window,
    )
    return _from3(dq, b, h), _from3(dk, b, h), _from3(dv, b, h)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, causal: bool = True,
    window: "Optional[int]" = None,
) -> jax.Array:
    """Tiled fused causal attention, ``[B, T, H, D] -> [B, T, H, Dv]``.

    Drop-in for :func:`~torchft_tpu.ops.ring_attention.dense_attention`
    with O(T) memory instead of the O(T^2) score matrix.  GQA K/V with
    fewer heads are broadcast up (the kernel is per-head).  Requires
    ``T % 128 == 0``; other shapes should use ``dense_attention``.
    ``v`` may have a head width ``Dv`` of its own (latent attention: queries
    and keys of 192 against values of 128); the scale is ``D ** -0.5``.
    ``window``: query ``i`` sees key ``j`` iff ``0 <= i - j < window`` (causal
    self-attention only); the kernels then walk the band's tiles alone.
    """
    b, t, h, d = q.shape
    if window is not None:
        if not causal or window < 1 or k.shape[1] != t:
            raise ValueError(
                "a window needs causal self-attention and at least one key"
            )
        if window >= t:
            window = None  # no causal query looks further back than t - 1
    if h % k.shape[2] != 0:
        raise ValueError(
            f"query heads {h} not a multiple of kv heads {k.shape[2]}"
        )
    k, v = _expand_gqa(k, v, h)
    scale = 1.0 / math.sqrt(d)
    return _flash(q, k, v, scale, causal, window)


__all__ = ["flash_attention"]


# ---------------------------------------------------------------------------
# ring composition: flash tiles inside sequence-parallel ring attention
# ---------------------------------------------------------------------------


def _to3(x: jax.Array) -> jax.Array:
    b, t, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)


def _from3(x3: jax.Array, b: int, h: int) -> jax.Array:
    bh, t, d = x3.shape
    return x3.reshape(b, h, t, d).transpose(0, 2, 1, 3)


def _expand_gqa(k: jax.Array, v: jax.Array, h: int):
    rep = h // k.shape[2]
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    return k, v


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def ring_flash_local(
    q: jax.Array, k: jax.Array, v: jax.Array, axis_name: str, causal: bool = True
) -> jax.Array:
    """Per-shard ring attention with FLASH tiles: the K/V chunks rotate
    around the ``axis_name`` ring exactly like
    :func:`~torchft_tpu.ops.ring_attention.ring_attention_local`, but each
    (local-Q x visiting-KV) tile runs the fused Pallas kernel with global
    position offsets instead of materializing [T_local, T_local] scores —
    the single-chip flash memory/speed profile composed with cp sharding.

    Same contract as ring_attention_local: must run inside shard_map over
    ``axis_name``; q/k/v are local chunks [B, T_local, H, D] rotary-
    embedded with GLOBAL positions; GQA K/V rotate unexpanded.  Requires
    T_local % 128 == 0.  The backward pass re-rotates K/V and runs the
    flash bwd kernels per tile against the globally-combined logsumexp
    (the standard ring-attention backward), so [T, T] is never built in
    either direction.
    """
    o, _ = _ring_flash_fwd_impl(q, k, v, axis_name, causal)
    return o


def _ring_flash_fwd_impl(q, k, v, axis_name, causal):
    idx = jax.lax.axis_index(axis_name)
    size = jax.lax.axis_size(axis_name)
    b, tq, h, d = q.shape
    tk = k.shape[1]
    scale = 1.0 / math.sqrt(d)
    q3 = _to3(q)

    def step(carry, s):
        o3, lse, kc, vc = carry
        kv_idx = (idx - s) % size
        ke, ve = _expand_gqa(kc, vc, h)
        offs = jnp.stack([idx * tq, kv_idx * tk]).astype(jnp.int32)
        o_s, lse_s = _fwd(q3, _to3(ke), _to3(ve), scale, causal, offs)
        # blockwise softmax combination over chunks (f32)
        m = jnp.maximum(lse, lse_s)
        w1 = jnp.exp(lse - m)
        w2 = jnp.exp(lse_s - m)
        denom = jnp.maximum(w1 + w2, 1e-30)
        o3 = (
            o3.astype(jnp.float32) * (w1 / denom)[..., None]
            + o_s.astype(jnp.float32) * (w2 / denom)[..., None]
        )
        lse = m + jnp.log(denom)
        perm = [(r, (r + 1) % size) for r in range(size)]
        kc = jax.lax.ppermute(kc, axis_name, perm)
        vc = jax.lax.ppermute(vc, axis_name, perm)
        return (o3, lse, kc, vc), None

    # zeros derived from q carry its device-varying axis set (vma rule)
    o0 = jnp.zeros_like(q3, dtype=jnp.float32)
    lse0 = jnp.zeros((b * h, tq), jnp.float32) + (
        jnp.zeros_like(q3[:, :, 0]) + _NEG_INF
    )
    (o3, lse, _, _), _ = jax.lax.scan(
        step, (o0, lse0, k, v), jnp.arange(size)
    )
    return _from3(o3.astype(q.dtype), b, h), lse


def _ring_flash_fwd(q, k, v, axis_name, causal):
    o, lse = _ring_flash_fwd_impl(q, k, v, axis_name, causal)
    return o, (q, k, v, o, lse)


def _ring_flash_bwd(axis_name, causal, res, do):
    q, k, v, o, lse = res
    idx = jax.lax.axis_index(axis_name)
    size = jax.lax.axis_size(axis_name)
    b, tq, h, d = q.shape
    tk = k.shape[1]
    hkv = k.shape[2]
    rep = h // hkv
    scale = 1.0 / math.sqrt(d)
    q3, o3, do3 = _to3(q), _to3(o), _to3(do)
    # loop-invariant: rowsum(dO * O), computed once for all ring steps
    delta = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32), axis=-1)

    def step(carry, s):
        dq3, kc, vc, dkc, dvc = carry
        kv_idx = (idx - s) % size
        ke, ve = _expand_gqa(kc, vc, h)
        offs = jnp.stack([idx * tq, kv_idx * tk]).astype(jnp.int32)
        dq_s, dk_s, dv_s = _bwd(
            q3, _to3(ke), _to3(ve), o3, lse, do3, scale, causal, offs,
            delta=delta,
        )
        dq3 = dq3 + dq_s.astype(jnp.float32)
        # fold expanded-head grads back onto the unexpanded K/V heads
        dk4 = _from3(dk_s, b, h).reshape(b, tk, hkv, rep, d).sum(3)
        dv4 = _from3(dv_s, b, h).reshape(b, tk, hkv, rep, d).sum(3)
        dkc = dkc + dk4.astype(jnp.float32)
        dvc = dvc + dv4.astype(jnp.float32)
        # K/V and their grad accumulators rotate together: after the full
        # cycle each chunk (and its accumulated grad) is home again
        perm = [(r, (r + 1) % size) for r in range(size)]
        kc = jax.lax.ppermute(kc, axis_name, perm)
        vc = jax.lax.ppermute(vc, axis_name, perm)
        dkc = jax.lax.ppermute(dkc, axis_name, perm)
        dvc = jax.lax.ppermute(dvc, axis_name, perm)
        return (dq3, kc, vc, dkc, dvc), None

    dq0 = jnp.zeros_like(q3, dtype=jnp.float32)
    dk0 = jnp.zeros_like(k, dtype=jnp.float32)
    dv0 = jnp.zeros_like(v, dtype=jnp.float32)
    (dq3, _, _, dk_acc, dv_acc), _ = jax.lax.scan(
        step, (dq0, k, v, dk0, dv0), jnp.arange(size)
    )
    return (
        _from3(dq3, b, h).astype(q.dtype),
        dk_acc.astype(k.dtype),
        dv_acc.astype(v.dtype),
    )


ring_flash_local.defvjp(_ring_flash_fwd, _ring_flash_bwd)

__all__.append("ring_flash_local")
