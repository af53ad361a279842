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
- a causal tile does only the work the mask and the row leave it.  In a
  call without offsets on square tiles (every model's) that follows from the
  tile's place in the grid (:func:`_by_place`; :func:`tile_kinds` counts a
  head's tiles by kind, the gauge ``torchft_flash_tiles{kind}`` a grad
  step's).  A tile *under* the diagonal runs without the mask's iotas,
  compare and select and without the forward's empty-row guard (its rows
  have seen key 0).  A tile the mask *cuts* (the diagonal one; under a
  window also the band's older edge) is computed in blocks of rows (of keys
  in the key-value backward; :func:`_sub_block` sizes them by the head's
  widths), each against the stretch of keys that holds a live pair of it,
  under a mask of local indices: the sub-blocks wholly above the diagonal
  are never formed; the guard stays only on a piece in which a row can have
  no live key (a window's older edge).  A tile *above* the diagonal is
  skipped via ``pl.when`` and its index maps repeat the diagonal tile, so
  nothing is fetched for it.  Where the scale is a power of two (heads of 64
  and 256) it multiplies the query rows and the query gradient's
  accumulator in place of the pairs, which changes no bit.  A call with
  offsets (the ring composition) or ``causal=False`` is decided at run time:
  the mask by positions on every tile its gate lets through and, in the
  forward, the guard.
- a sliding window (``window=w``: query ``i`` sees key ``j`` iff ``0 <= i - j
  < w``): the same three kernels on a grid whose last axis walks only the
  band's ``ceil((w - 1) / blk) + 1`` tiles beside each query (key) tile, so
  tiles older than the window are neither computed nor fetched; the band's
  two edges are cut as above, the tiles between them run unmasked.  These
  calls are named
  ``_fwd_window_kernel`` / ``_bwd_kv_window_kernel`` / ``_bwd_q_window_kernel``
  in the compiled program, so a trace tells them from the global ones.
- a mask at block granularity (``block=b``: query ``i`` sees key ``j`` iff
  ``i // b >= j // b``; ``strict=True``: ``>``): the causal call's tile walk
  as it is, since a tile's and a sub-block's rows are whole blocks, with the
  cut tiles' mask reading blocks (:func:`_visible`).  Under the strict form
  the rows of block 0 see no key at all: the forward's empty-row guard, until
  then a window's older edge's, stands on the diagonal tile's first piece
  (``o`` 0, ``lse`` ~ -inf, no NaN forward or backward).  Named
  ``_<stem>_block_kernel`` / ``_<stem>_block_strict_kernel``
  (:func:`_kernel_name`).  ``block=None`` traces to the program it always
  did.
- block diffusion (:func:`flash_block_diffusion`): a row run twice, noised
  beside clean, ``2T`` positions under a three-part mask of which a quarter
  of the ``[2T, 2T]`` plane is live.  The plane is never formed: the clean
  copy is one block-causal call, the noised copy one strictly block-causal
  call on the clean keys whose partial result is merged by log-sum-exp
  (:func:`merge_partials`, the helper the ring's shards merge through too)
  with the dense ``[T / b, b, b]`` scores of its own block, and one backward
  runs both parts under the merged ``lse`` and ``delta``
  (:func:`_flash_own_block`), as the ring's does across shards.
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
# ``_flash_fwd`` also writes the call's shapes into the traced program, as a
# name that no policy saves: ``<this>:bh:tq:tk:d:dv:window``, which
# :func:`call_tiles` reads back (``models/transformer.py`` ``_grad_step`` sums
# them into the gauge ``torchft_flash_tiles{kind}``).
FLASH_CALL_NAME = "flash_attn_call"


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


def _key_tile(i, step, band, by_place=False):
    """The key tile the inner grid axis is at beside query tile ``i``:
    ``step`` itself, or the band's, its last the diagonal one; a tile before
    the sequence's start repeats tile 0, which is fetched once.  ``by_place``
    (no band): a step above the diagonal repeats the diagonal tile, so
    nothing is fetched for it."""
    if band is None:
        return jnp.minimum(step, i) if by_place else step
    return jnp.maximum(i - (band - 1) + step, 0)


def _query_tile(j, step, band, n, by_place=False):
    """The query tile the inner grid axis is at beside key tile ``j``:
    ``step`` itself, or the band's, its first the diagonal one; a tile past
    the last of the ``n`` repeats it.  ``by_place`` (no band): a step above
    the diagonal fetches the diagonal tile ahead of its turn (the last tile
    where the keys outnumber the queries)."""
    if band is None:
        return jnp.minimum(jnp.maximum(step, j), n - 1) if by_place else step
    return jnp.minimum(j + step, n - 1)


def _visible(rq, rk, window, block=None):
    """The causal mask of query rows ``rq`` on key rows ``rk``, and under a
    window its older edge.  ``block`` = ``(size, strict)``: the mask at block
    granularity, ``rq // size >= rk // size`` (a query sees its own block
    whole and every block before it) or, ``strict``, ``>`` (the blocks before
    its own alone): the key lies before the end, or the start, of the query's
    block."""
    if block is not None:
        size, strict = block
        start = rq - jax.lax.rem(rq, jnp.int32(size))  # the query's block's first row
        return rk < (start if strict else start + size)
    if window is None:
        return rq >= rk
    return jnp.logical_and(rq >= rk, rq - rk < window)


def _live(shape, ahead, window, by_key=False, block=None):
    """The mask of a piece of scores whose first query lies ``ahead`` rows
    after its first key (under ``block`` a multiple of the block's size);
    ``by_key``: keys down the rows, queries along the lanes."""
    rq = ahead + jax.lax.broadcasted_iota(jnp.int32, shape, 1 if by_key else 0)
    rk = jax.lax.broadcasted_iota(jnp.int32, shape, 0 if by_key else 1)
    return _visible(rq, rk, window, block)


def _exact_scale(scale: float) -> bool:
    """Whether a product with ``scale`` rounds nothing in any float type: a
    power of two (heads of 64 and 256).  Then ``(q * scale) @ k`` is
    ``(q @ k) * scale`` bit for bit, and the scale leaves the pairs."""
    return math.frexp(scale)[0] == 0.5


def _kernel_name(stem: str, window, block) -> str:
    """What a call is named in the compiled program, so that a trace tells the
    calls apart (the benchmark finds them by these names,
    ``benchmarks/families/*.py``): ``_fwd_kernel`` / ``_bwd_kv_kernel`` /
    ``_bwd_q_kernel`` of a global call, ``_<stem>_window_kernel`` of a windowed
    one, ``_<stem>_block_kernel`` under a block mask and
    ``_<stem>_block_strict_kernel`` under its strict form."""
    if block is not None:
        return f"_{stem}_block_strict_kernel" if block[1] else f"_{stem}_block_kernel"
    return f"_{stem}_kernel" if window is None else f"_{stem}_window_kernel"


# ---------------------------------------------------------------------------
# what a tile computes
# ---------------------------------------------------------------------------

# A piece of a tile is ``(rows, cols, ahead)``: slices of the tile's queries
# and keys and, where the mask cuts the piece, how many rows its first query
# lies after its first key (None where every pair is live).
_WHOLE = ((slice(None), slice(None), None),)


def _by_place(causal, offsets_given, blk_q, blk_k) -> bool:
    """Whether what a tile needs follows from its place in the grid alone:
    causal attention on square tiles and no offsets, windowed or not (every
    model's call; the ring composition passes offsets and is decided at run
    time)."""
    return bool(causal) and not offsets_given and blk_q == blk_k


def _cut_tiles(band, blk, window):
    """How many tiles before the diagonal the tiles lie that the mask cuts:
    the diagonal one (0) and, under a window, those on the band's older
    edge.  Every other live tile holds live pairs alone."""
    if window is None:
        return (0,)
    return tuple(
        a for a in range(band) if not (a >= 1 and (a + 1) * blk <= window)
    )


def _sub_block(kernel: str, blk: int, d: int, dv: int) -> int:
    """The rows (keys, in the key-value backward) of the blocks a tile that
    the mask cuts is computed in; ``blk`` leaves it whole under its mask.
    Blocks of ``blk / n`` spare ``(n - 1) / 2n`` of the tile's pairs.

    The two backward kernels gain what the pairs say at every width, and a
    quarter tile reads best or within 2 % of it.  The forward pays for each
    block a row maximum, a row sum and a rescale of its own, which do not
    shrink with the keys: at heads of 64 and 128 that is more than the pairs
    spare and the tile stays whole; wider heads put more matrix work on a
    pair and halves win.  On a v5e (PR 40; ms a call of one layer, bfloat16,
    the kernel alone with ~0.5 ms of dispatch in each reading; ``parent`` =
    PR 38's kernels, the backward's two in one reading):

      heads, T, batch x heads   kernel  parent   whole    512     256     128
      64 / 64, 2048, 120        fwd      2.788   2.226   2.453   2.509     -
                                bwd_kv   5.519   3.050   2.726   2.578   2.529
                                bwd_q      "     2.403   2.163   2.039   2.034
      192 / 128, 4096, 128      fwd     11.054   9.022   8.605   8.683     -
                                bwd_kv  26.785  13.726  12.689  12.165     -
                                bwd_q      "    12.680  11.807  11.427     -
      192 / 128, 8192, 64       fwd     18.429  14.532  14.124  14.194     -
                                bwd_kv  45.463  22.636  21.590  21.061     -
                                bwd_q      "    20.367  19.498  19.087     -
      128 / 128, 8192, 64       fwd     13.365   9.863  10.316  10.430     -
                                bwd_kv  29.470  13.923  13.222  12.940     -
                                bwd_q      "    11.303  10.790  10.530     -
      the same, window 2048     fwd      7.640   6.242   6.717   6.731     -
                                bwd_kv  15.598   8.240   7.006   6.524     -
                                bwd_q      "     6.044   5.124   4.703     -

    Two thirds of the live tiles are cut ones at ``T`` 2048, four of ten at
    4096, eight of thirty-six at 8192, two of three in a band: the share
    sizes the gain and never turned its sign, so the rule reads the widths
    and the tile alone."""
    if kernel == "fwd":
        return max(blk // 2, _LANE) if max(d, dv) > 128 else blk
    return max(blk // 4, _LANE)


def _pieces(blk, sub, ahead, window, by_key=False):
    """The pieces of a ``blk`` x ``blk`` tile whose first query lies ``ahead``
    rows after its first key (the diagonal tile: 0), cut into blocks of
    ``sub`` queries (keys if ``by_key``): each block against the stretch of
    keys (queries) that holds a live pair of it, under the mask where the
    stretch holds a dead one too.  Sub-blocks of ``sub`` x ``sub`` wholly
    above the diagonal or older than the window fall outside every stretch:
    of a diagonal tile's ``n`` x ``n`` they are ``n (n - 1) / 2``."""
    n = blk // sub
    top = math.inf if window is None else window

    def dead_masked(r, c):  # of query block r on key block c
        first = ahead + (r - c) * sub  # its first query after its first key
        lo, hi = first - (sub - 1), first + (sub - 1)
        return (hi < 0 or lo >= top), not (lo >= 0 and hi < top)

    pieces = []
    for s in range(n):
        kinds = [dead_masked(o, s) if by_key else dead_masked(s, o) for o in range(n)]
        live = [o for o, (dead, _) in enumerate(kinds) if not dead]
        if not live:
            continue
        block, stretch = (s * sub, (s + 1) * sub), (live[0] * sub, (live[-1] + 1) * sub)
        rows, cols = (stretch, block) if by_key else (block, stretch)
        masked = any(kinds[o][1] for o in live)
        pieces.append((
            slice(*rows), slice(*cols), ahead + rows[0] - cols[0] if masked else None,
        ))
    return tuple(pieces)


def _can_be_empty(piece, window, block=None) -> bool:
    """Whether a query row of a piece of :func:`_pieces` may have no live key
    in it: its first row's newest key or its last row's oldest lies outside;
    under a strict block mask the piece's first block of queries, where no
    whole block of the piece's keys lies before it."""
    rows, cols, ahead = piece
    if ahead is None:
        return False
    if block is not None:
        return block[1] and ahead < block[0]
    last_row, last_col = rows.stop - rows.start - 1, cols.stop - cols.start - 1
    return ahead < 0 or (window is not None and ahead + last_row - window >= last_col)


def _on_live_tiles(i, j, in_grid, offs_ref, causal, blk, cut, pieces_of, piece):
    """Runs ``piece(rows, cols, ahead)`` on the pieces of query tile ``i``
    against key tile ``j`` (``blk = (blk_q, blk_k)``) that hold a live pair.
    By place (``cut`` given: how many tiles before the diagonal the tiles lie
    that the mask cuts): those in ``pieces_of(a)``, every other tile under the
    diagonal whole and unmasked, nothing above it or outside the grid
    (``in_grid``: a band's step before the sequence's start or past its end).
    Else, at run time from the call's offsets: the whole tile, masked by
    positions if causal, unless every key lies after every query."""

    def run(pieces):
        for p in pieces:
            piece(*p)

    if cut is None:
        # GLOBAL positions of the tile's first query and key
        first_q, first_k = offs_ref[0] + i * blk[0], offs_ref[1] + j * blk[1]
        live = jnp.logical_or(not causal, first_k <= first_q + blk[0] - 1)
        ahead = first_q - first_k if causal else None
        pl.when(live)(lambda: run(((slice(None), slice(None), ahead),)))
        return
    whole = jnp.logical_and(in_grid, i >= j)
    for a in cut:
        here = jnp.logical_and(in_grid, i - j == a)
        pl.when(here)(lambda a=a: run(pieces_of(a)))
        whole = jnp.logical_and(whole, i - j != a)
    pl.when(whole)(lambda: run(_WHOLE))


TILE_KINDS = ("under", "diagonal", "above", "general", "sub_computed", "sub_skipped")


def tile_kinds(tq, tk, d, dv, window=None, offsets_given=False):
    """A head's tiles by kind in a causal call: ``under`` the diagonal (every
    pair live: no mask, no guard), ``diagonal`` (cut by the mask: the
    diagonal tile and, under a window, the band's older edge), ``above``
    (grid steps with no live pair: skipped, nothing fetched), ``general``
    (decided at run time: offsets, or tiles that are not square); and of
    the cut tiles' sub-blocks, summed over the three kernels (each cuts by
    its own :func:`_sub_block`), those a kernel computes and those it skips."""
    blk_q, blk_k, band = _tiles(tq, tk, max(d, dv), window)
    nq, nk = tq // blk_q, tk // blk_k
    kinds = dict.fromkeys(TILE_KINDS, 0)
    if not _by_place(True, offsets_given, blk_q, blk_k):
        kinds["general"] = nq * nk
        return kinds
    def sub_blocks(a):  # of a cut tile, the three kernels': (computed, all)
        done = every = 0
        for kernel in ("fwd", "bwd_kv", "bwd_q"):
            sub = _sub_block(kernel, blk_q, d, dv)
            done += sum(
                (rows.stop - rows.start) * (cols.stop - cols.start)
                for rows, cols, _ in _pieces(blk_q, sub, a * blk_q, window)
            ) // (sub * sub)
            every += (blk_q // sub) ** 2
        return done, every

    cut = {a: sub_blocks(a) for a in _cut_tiles(band, blk_q, window)}
    for i in range(nq):
        for a in range(i - nk + 1, i + 1) if band is None else range(band):
            if a < 0 or i - a < 0:
                kinds["above"] += 1
            elif a in cut:
                kinds["diagonal"] += 1
                kinds["sub_computed"] += cut[a][0]
                kinds["sub_skipped"] += cut[a][1] - cut[a][0]
            else:
                kinds["under"] += 1
    return kinds


def call_tiles(name: str) -> "Optional[dict]":
    """The tiles by kind (:func:`tile_kinds` times the heads) of the call whose
    shapes ``_flash_fwd`` wrote into ``name``; None for any other name."""
    tag, *shapes = name.split(":")
    if tag != FLASH_CALL_NAME:
        return None
    bh, tq, tk, d, dv, window = map(int, shapes)
    kinds = tile_kinds(tq, tk, d, dv, window or None)
    return {kind: bh * n for kind, n in kinds.items()}


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------


def _fwd_kernel(
    offs_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_s, l_s,
    *, scale, causal, blk_q, blk_k, window=None, cut=None, sub=None, fold=False,
    block=None,
):
    """offs_ref: SMEM int32 [2] = (q_offset, k_offset) GLOBAL positions of
    this call's first query/key row — the ring composition runs the kernel
    on local chunks whose causal relation depends on the shard offsets.

    ``window``: the last grid axis walks the band's tiles only (square
    tiles, no offsets), the last of them the diagonal tile.

    ``cut`` (:func:`_cut_tiles`; None: not :func:`_by_place`): what a tile
    needs follows from ``i`` and ``j``; ``sub``: the blocks a cut tile is
    computed in.  ``fold``: the scale
    is exact and goes onto the query rows, not the scores.  ``block``
    (:func:`_visible`): the mask reads blocks; the tiles' places are the
    causal ones."""
    i = pl.program_id(1)
    step = pl.program_id(2)
    nj = pl.num_programs(2)
    j = step if window is None else i - (nj - 1) + step

    @pl.when(step == 0)
    def _():
        acc[:] = jnp.zeros_like(acc)
        m_s[:] = jnp.full_like(m_s, _NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)

    def update(rows, cols, ahead):
        """One online-softmax step of the tile's query ``rows`` over its key
        ``cols``."""
        # whether a row may have seen no key yet: decided at run time (offsets),
        # or a piece of a window's older edge
        guard = ahead is not None and (
            cut is None or _can_be_empty((rows, cols, ahead), window, block))
        q = q_ref[0, rows]
        if fold:
            q = q * scale
        s = jax.lax.dot_general(
            q,
            k_ref[0, cols],
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [rows, cols]
        if not fold:
            s = s * scale
        if ahead is not None:
            s = jnp.where(_live(s.shape, ahead, window, block=block), s, _NEG_INF)
        m_prev = m_s[rows, :1]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        if guard:
            # A query row with zero live keys so far has m_new == _NEG_INF, so
            # s - m_new == 0 for every MASKED entry and p would be 1 — O would
            # become a garbage mean of V.  Zero p for such rows instead: l
            # stays 0, O resolves to 0 and lse to ~-inf, so callers passing
            # offsets (ring chunks where q precedes every k) get an exact
            # zero-weight chunk rather than relying on the combiner's
            # exp-underflow to hide it.  By place only a window's older edge
            # and a strict block mask's first block can hold such a row:
            # elsewhere a row has seen key 0 or its own.
            p = jnp.where(m_new > _NEG_INF / 2, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        lanes = (p.shape[0], _LANE)
        l_s[rows] = jnp.broadcast_to(
            l_s[rows, :1] * corr + p.sum(axis=1, keepdims=True), lanes
        )
        m_s[rows] = jnp.broadcast_to(m_new, lanes)
        acc[rows] = acc[rows] * corr + jax.lax.dot_general(
            p.astype(q.dtype),
            v_ref[0, cols],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    _on_live_tiles(
        i, j, j >= 0, offs_ref, causal, (blk_q, blk_k), cut,
        lambda a: _pieces(blk_q, sub, a * blk_q, window), update,
    )

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
    block: "Optional[Tuple[int, bool]]" = None,
) -> "Tuple[jax.Array, jax.Array]":
    bh, tq, d = q3.shape
    tk, dv = k3.shape[1], v3.shape[2]  # values may be narrower than q/k
    blk_q, blk_k, band = _tiles(tq, tk, max(d, dv), window)
    by_place = _by_place(causal, offsets is not None, blk_q, blk_k)
    if offsets is None:
        offsets = jnp.zeros((2,), jnp.int32)
    grid = (bh, tq // blk_q, band or tk // blk_k)

    def kv(b, i, step):
        return (b, _key_tile(i, step, band, by_place), 0)

    o, lse = pl.pallas_call(
        functools.partial(
            _fwd_kernel, scale=scale, causal=causal, blk_q=blk_q, blk_k=blk_k,
            window=window, cut=_cut_tiles(band, blk_q, window) if by_place else None,
            sub=_sub_block("fwd", blk_q, d, dv), fold=_exact_scale(scale),
            block=block,
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
        name=_kernel_name("fwd", window, block),
    )(offsets.astype(jnp.int32), q3, k3, v3)
    return o, lse[:, 0]


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------


def _bwd_kv_kernel(
    offs_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal, blk_q, blk_k,
    window=None, cut=None, sub=None, fold=False, block=None,
):
    """Keys down the rows, queries along the lanes: the scores are formed as
    ``k qᵀ``, so ``pᵀ`` and ``dsᵀ`` stand as the two products into ``dv`` and
    ``dk`` take them, and the rows' ``lse`` and ``delta`` are used as the lane
    vectors they arrive as."""
    j = pl.program_id(1)  # K/V block (outer)
    step = pl.program_id(2)  # Q block (inner, accumulated)
    ni = pl.num_programs(2)
    # under a window: the band's query tiles, the first the diagonal one
    i = step if window is None else j + step

    @pl.when(step == 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def piece(rows, cols, ahead):
        """The tile's key ``cols`` against its query ``rows``."""
        q = q_ref[0, rows]
        if fold:
            q = q * scale  # exact: the scores' scale and ds's in one
        do = do_ref[0, rows]
        st = jax.lax.dot_general(
            k_ref[0, cols], q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [cols, rows]
        if not fold:
            st = st * scale
        pt = jnp.exp(st - lse_ref[0, :, rows])
        if ahead is not None:
            pt = jnp.where(_live(pt.shape, ahead, window, by_key=True, block=block), pt, 0.0)
        dv_acc[cols] = dv_acc[cols] + jax.lax.dot_general(
            pt.astype(q.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dpt = jax.lax.dot_general(
            v_ref[0, cols], do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dst = pt * (dpt - delta_ref[0, :, rows])
        if not fold:
            dst = dst * scale
        dk_acc[cols] = dk_acc[cols] + jax.lax.dot_general(
            dst.astype(q.dtype), q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    # under a window a band's step may lie past the sequence's end
    in_grid = window is None or i < pl.num_programs(1)
    _on_live_tiles(
        i, j, in_grid, offs_ref, causal, (blk_q, blk_k), cut,
        lambda a: _pieces(blk_k, sub, a * blk_k, window, by_key=True), piece,
    )

    @pl.when(step == ni - 1)
    def _():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_q_kernel(
    offs_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dq_ref, dq_acc, *, scale, causal, blk_q, blk_k, window=None,
    cut=None, sub=None, fold=False, block=None,
):
    i = pl.program_id(1)  # Q block (outer)
    step = pl.program_id(2)  # K/V block (inner, accumulated)
    nj = pl.num_programs(2)
    # under a window: the band's key tiles, the last the diagonal one
    j = step if window is None else i - (nj - 1) + step

    @pl.when(step == 0)
    def _():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def piece(rows, cols, ahead):
        """The tile's query ``rows`` against its key ``cols``; with ``fold``
        ``ds`` goes without the scale, which ``dq_acc`` takes at write-out."""
        q = q_ref[0, rows]
        if fold:
            q = q * scale
        s = jax.lax.dot_general(
            q, k_ref[0, cols], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if not fold:
            s = s * scale
        # lse, delta: [1, rows] lane vectors -> columns (Mosaic relayout)
        p = jnp.exp(s - lse_ref[0, :, rows].reshape(-1, 1))
        if ahead is not None:
            p = jnp.where(_live(p.shape, ahead, window, block=block), p, 0.0)
        dp = jax.lax.dot_general(
            do_ref[0, rows], v_ref[0, cols], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_ref[0, :, rows].reshape(-1, 1))
        if not fold:
            ds = ds * scale
        dq_acc[rows] = dq_acc[rows] + jax.lax.dot_general(
            ds.astype(q.dtype), k_ref[0, cols], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    _on_live_tiles(
        i, j, j >= 0, offs_ref, causal, (blk_q, blk_k), cut,
        lambda a: _pieces(blk_q, sub, a * blk_q, window), piece,
    )

    @pl.when(step == nj - 1)
    def _():
        dq = dq_acc[:] * scale if fold else dq_acc[:]
        dq_ref[0] = dq.astype(dq_ref.dtype)


def _bwd(
    q3, k3, v3, o3, lse, do3, scale: float, causal: bool,
    offsets: "Optional[jax.Array]" = None,
    delta: "Optional[jax.Array]" = None,
    window: "Optional[int]" = None,
    block: "Optional[Tuple[int, bool]]" = None,
) -> "Tuple[jax.Array, jax.Array, jax.Array]":
    if delta is None:
        # delta_i = rowsum(dO * O): tiny elementwise pass, plain XLA
        delta = jnp.sum(
            do3.astype(jnp.float32) * o3.astype(jnp.float32), axis=-1
        )
    args = (q3, k3, v3, lse, do3, delta, scale, causal, offsets, window, block)
    dk, dv = _bwd_kv(*args)
    return _bwd_q(*args), dk, dv


def _bwd_operands(q3, k3, v3, lse, do3, delta, scale, causal, offsets, window, block):
    """What the two backward calls share: the tiles, the kernels' static
    arguments and their operands (row statistics as ``[bh, 1, t]``)."""
    d, d_v = q3.shape[2], v3.shape[2]
    blk, blk_kk, band = _tiles(q3.shape[1], k3.shape[1], max(d, d_v), window)
    by_place = _by_place(causal, offsets is not None, blk, blk_kk)
    static = dict(
        scale=scale, causal=causal, blk_q=blk, blk_k=blk_kk, window=window,
        cut=_cut_tiles(band, blk, window) if by_place else None,
        fold=_exact_scale(scale), block=block,
    )
    if offsets is None:
        offsets = jnp.zeros((2,), jnp.int32)
    operands = (
        offsets.astype(jnp.int32), q3, k3, v3, do3, lse[:, None, :], delta[:, None, :],
    )
    return blk, blk_kk, band, static, operands


def _bwd_kv(q3, k3, v3, lse, do3, delta, scale, causal, offsets, window, block):
    bh, tq, d = q3.shape
    tk, d_v = k3.shape[1], v3.shape[2]
    blk, blk_kk, band, static, operands = _bwd_operands(
        q3, k3, v3, lse, do3, delta, scale, causal, offsets, window, block
    )
    n = tq // blk

    def q_of(jj, step):
        return _query_tile(jj, step, band, n, static["cut"] is not None)

    # grid = (b, j, i): index maps receive (b, kv_block, q_block)
    return pl.pallas_call(
        functools.partial(
            _bwd_kv_kernel, sub=_sub_block("bwd_kv", blk_kk, d, d_v), **static
        ),
        grid=(bh, tk // blk_kk, band or n),
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
        name=_kernel_name("bwd_kv", window, block),
    )(*operands)


def _bwd_q(q3, k3, v3, lse, do3, delta, scale, causal, offsets, window, block):
    bh, tq, d = q3.shape
    tk, d_v = k3.shape[1], v3.shape[2]
    blk, blk_kk, band, static, operands = _bwd_operands(
        q3, k3, v3, lse, do3, delta, scale, causal, offsets, window, block
    )

    def kv_of(ii, step):
        return _key_tile(ii, step, band, static["cut"] is not None)

    # grid = (b, i, j): index maps receive (b, q_block, kv_block)
    return pl.pallas_call(
        functools.partial(
            _bwd_q_kernel, sub=_sub_block("bwd_q", blk, d, d_v), **static
        ),
        grid=(bh, tq // blk, band or tk // blk_kk),
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
        name=_kernel_name("bwd_q", window, block),
    )(*operands)


# ---------------------------------------------------------------------------
# differentiable wrapper
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, scale, causal, window, block):
    """``[B, T, H, D]`` x 2, ``[B, T, H, Dv]`` -> ``[B, T, H, Dv]``; ``k`` and
    ``v`` carry the query's heads."""
    b, _, h, _ = q.shape
    o3, _ = _fwd(_to3(q), _to3(k), _to3(v), scale, causal, window=window, block=block)
    return _from3(o3, b, h)


def _name_results(o3, lse, b, h, tk, d, window):
    """Names a forward call's two results for a checkpoint to keep, and writes
    the call's shapes beside them (``FLASH_CALL_NAME``).  ``o`` is named as the
    rows a block reads it in, ``[B, T, H Dv]``: the forward pass lays those out
    anyway, and a stack of the kernel's own ``[bh, T, 64]`` would be padded to
    128 lanes and hold twice its bytes.  The primal result is read from the
    named rows, so that a checkpoint which saves the name needs no second
    ``o``."""
    t, dv = o3.shape[1], o3.shape[2]
    o = checkpoint_name(_from3(o3, b, h).reshape(b, t, -1), FLASH_OUT_NAME)
    shapes = (b * h, t, tk, d, dv, window or 0)
    lse = checkpoint_name(lse, ":".join(map(str, (FLASH_CALL_NAME, *shapes))))
    return o, checkpoint_name(lse, FLASH_LSE_NAME)


def _delta(do, o):
    """``delta_i = rowsum(dO * O)`` as ``[bh, T]``, from the rows as they were
    kept (``[B, T, H Dv]``)."""
    b, t, h, _ = do.shape
    return jnp.sum(
        do.astype(jnp.float32) * o.reshape(do.shape).astype(jnp.float32), axis=-1
    ).transpose(0, 2, 1).reshape(b * h, t)


def _flash_fwd(q, k, v, scale, causal, window, block):
    b, t, h, _ = q.shape
    o3, lse = _fwd(_to3(q), _to3(k), _to3(v), scale, causal, window=window, block=block)
    o, lse = _name_results(o3, lse, b, h, k.shape[1], q.shape[3], window)
    return o.reshape(b, t, h, -1), (q, k, v, o, lse)


def _flash_bwd(scale, causal, window, block, res, do):
    q, k, v, o, lse = res
    b, _, h, _ = q.shape
    delta = _delta(do, o)
    dq, dk, dv = _bwd(
        _to3(q), _to3(k), _to3(v), None, lse, _to3(do), scale, causal,
        delta=delta, window=window, block=block,
    )
    return _from3(dq, b, h), _from3(dk, b, h), _from3(dv, b, h)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, causal: bool = True,
    window: "Optional[int]" = None, block: "Optional[int]" = None,
    strict: bool = False,
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
    ``block``: the causal mask at block granularity, query ``i`` sees key
    ``j`` iff ``i // block >= j // block`` (``strict``: ``>``; the rows of
    block 0 then see no key and come back as zeros); the same tile walk as
    the causal call's, under kernel names of its own (:func:`_kernel_name`).
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
    return _flash(q, k, v, scale, causal, window, _block_mask(block, strict, causal, window, t, k.shape[1]))


def _block_mask(block, strict, causal, window, tq, tk) -> "Optional[Tuple[int, bool]]":
    """``(size, strict)`` as the kernels take a block mask, or None.  A block
    divides a sub-block's 128 rows, so that the mask cuts the tiles the causal
    mask cuts; queries and keys are as many, and there is no window."""
    if block is None:
        if strict:
            raise ValueError("strict is the strict form of a block mask: give block")
        return None
    if not causal or window is not None or tq != tk or block < 1 or _LANE % block:
        raise ValueError(
            f"a block mask needs causal attention without a window, as many keys as "
            f"queries and a block that divides {_LANE}, got block {block}"
        )
    return (int(block), bool(strict))


# ---------------------------------------------------------------------------
# block diffusion: a row run twice, noised beside clean
# ---------------------------------------------------------------------------


def merge_partials(o1, lse1, o2, lse2):
    """Two partial attention results over disjoint key sets (``o`` ``[..., T,
    Dv]`` normalised over its own keys, ``lse`` ``[..., T]`` its log-sum-exp)
    as one softmax over both sets: ``(o, lse)`` in float32.  A side that saw
    no key (``o`` 0, ``lse`` ~ -inf) gets weight exactly 0.  The ring's shards
    (:func:`_ring_flash_fwd_impl`) and a block's own keys beside the blocks
    before it (:func:`_flash_own_block`) merge through this."""
    m = jnp.maximum(lse1, lse2)
    w1 = jnp.exp(lse1 - m)
    w2 = jnp.exp(lse2 - m)
    denom = jnp.maximum(w1 + w2, 1e-30)
    o = (
        o1.astype(jnp.float32) * (w1 / denom)[..., None]
        + o2.astype(jnp.float32) * (w2 / denom)[..., None]
    )
    return o, m + jnp.log(denom)


def _own_blocks(x3, size, by_key):
    """``[bh, T, D] -> [bh, T / size, size, 1, D]`` as queries, ``[bh, T /
    size, 1, size, D]`` as keys (``by_key``), in float32: a block's rows
    against its own."""
    bh, t, d = x3.shape
    shape = (bh, t // size, 1, size, d) if by_key else (bh, t // size, size, 1, d)
    return x3.astype(jnp.float32).reshape(shape)


def _own_scores(q3, k3, scale, size):
    """Scores of every query on the keys of its own block: float32 ``[bh, T /
    size, size (query), size (key)]``, a sum over the head's width on the
    vector unit (products of ``size`` x ``size`` are no work for the matrix
    unit)."""
    return jnp.sum(_own_blocks(q3, size, False) * _own_blocks(k3, size, True), axis=-1) * scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _flash_own_block(q, k, v, k_own, v_own, scale, size):
    """Queries ``[B, T, H, D]`` on two key sets under one softmax: ``k`` /
    ``v`` (``[B, T, H, D | Dv]``, another copy of the row) under the strict
    block mask, through the kernels; ``k_own`` / ``v_own`` (the queries' own
    copy) inside the query's block, both directions, as dense ``[T / size,
    size, size]`` scores a head.  The two partial results are merged by their
    log-sum-exp (:func:`merge_partials`), and the backward runs both parts
    under the merged ``lse`` and ``delta``, as the ring's does across shards.
    The rows of block 0 see no key of the first set (the kernel gives them
    ``o`` 0 and ``lse`` ~ -inf): the merge weighs that side 0."""
    return _flash_own_block_fwd(q, k, v, k_own, v_own, scale, size)[0]


def _flash_own_block_fwd(q, k, v, k_own, v_own, scale, size):
    b, t, h, d = q.shape
    q3 = _to3(q)
    o_before, lse_before = _fwd(q3, _to3(k), _to3(v), scale, True, block=(size, True))
    s = _own_scores(q3, _to3(k_own), scale, size)
    lse_own = jax.nn.logsumexp(s, axis=-1)
    p = jnp.exp(s - lse_own[..., None])
    o_own = jnp.sum(p[..., None] * _own_blocks(_to3(v_own), size, True), axis=3)
    o3, lse = merge_partials(
        o_before, lse_before, o_own.reshape(b * h, t, -1), lse_own.reshape(b * h, t))
    o, lse = _name_results(o3.astype(q.dtype), lse, b, h, t, d, None)
    return o.reshape(b, t, h, -1), (q, k, v, k_own, v_own, o, lse)


def _flash_own_block_bwd(scale, size, res, do):
    q, k, v, k_own, v_own, o, lse = res
    b, t, h, _ = q.shape
    q3, do3, delta = _to3(q), _to3(do), _delta(do, o)
    dq, dk, dv = _bwd(
        q3, _to3(k), _to3(v), None, lse, do3, scale, True, delta=delta, block=(size, True))
    # the own block's part, dense, under the same merged statistics
    k_own3, v_own3 = _to3(k_own), _to3(v_own)
    by_block = (b * h, t // size, size, 1)
    p = jnp.exp(_own_scores(q3, k_own3, scale, size) - lse.reshape(by_block))
    do5 = _own_blocks(do3, size, False)
    dp = jnp.sum(do5 * _own_blocks(v_own3, size, True), axis=-1)
    ds = (p * (dp - delta.reshape(by_block)) * scale)[..., None]
    dq_own = jnp.sum(ds * _own_blocks(k_own3, size, True), axis=3).reshape(q3.shape)
    dk_own = jnp.sum(ds * _own_blocks(q3, size, False), axis=2).reshape(k_own3.shape)
    dv_own = jnp.sum(p[..., None] * do5, axis=2).reshape(v_own3.shape)
    dq = (dq.astype(jnp.float32) + dq_own).astype(q.dtype)
    return (_from3(dq, b, h), _from3(dk, b, h), _from3(dv, b, h),
            _from3(dk_own.astype(k_own.dtype), b, h), _from3(dv_own.astype(v_own.dtype), b, h))


_flash_own_block.defvjp(_flash_own_block_fwd, _flash_own_block_bwd)


def flash_block_diffusion(q: jax.Array, k: jax.Array, v: jax.Array, block: int) -> jax.Array:
    """Attention of a block-diffusion training step: ``k`` and ``v`` ``[B, 2T,
    Hkv, D | Dv]`` hold a row twice, positions ``0..T-1`` its noised copy,
    ``T..2T-1`` its clean copy, token ``i`` at ``i`` and ``T + i``; with
    ``blk(p) = (p mod T) // block`` a query sees

    - noised on noised: the keys of its own block, both directions;
    - noised on clean: the blocks before its own (``blk(q) > blk(k)``);
    - clean on clean: block-causal (``blk(q) >= blk(k)``);
    - clean on noised: nothing.

    ``q`` holds both copies' queries (``[B, 2T, H, D] -> [B, 2T, H, Dv]``) or
    the noised copy's alone (``[B, T, H, D] -> [B, T, H, Dv]``: a last layer,
    whose clean copy feeds keys and values only).  Of the ``[2T, 2T]`` plane a
    quarter is live and the plane is never formed: the clean copy is one
    block-causal call (the causal tile walk, the diagonal tiles' mask reading
    blocks), the noised copy one strictly block-causal call on the clean keys
    merged with its own block's dense ``[T / block, block, block]`` scores
    (:func:`_flash_own_block`).  No dead tile is computed or fetched.  GQA K/V
    are broadcast up; ``T % 128 == 0``."""
    b, tq, h, d = q.shape
    t = k.shape[1] // 2
    if k.shape[1] % 2 or tq not in (t, 2 * t) or h % k.shape[2]:
        raise ValueError("k and v hold a row twice (2T positions), q its noised copy's queries or "
                         "both copies', kv heads dividing the query's")
    mask = _block_mask(block, False, True, None, t, t)
    k, v = _expand_gqa(k, v, h)
    scale = 1.0 / math.sqrt(d)
    noised = _flash_own_block(q[:, :t], k[:, t:], v[:, t:], k[:, :t], v[:, :t], scale, mask[0])
    if tq == t:
        return noised
    clean = _flash(q[:, t:], k[:, t:], v[:, t:], scale, True, None, mask)
    return jnp.concatenate([noised, clean], axis=1)


__all__ = ["flash_attention", "flash_block_diffusion", "merge_partials", "tile_kinds"]


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
        o3, lse = merge_partials(o3, lse, o_s, lse_s)
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
