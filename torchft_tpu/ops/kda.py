"""Kimi Delta Attention (KDA): a gated delta-rule linear attention with a
decay of its own for every key channel, in the chunkwise form.

Per head, with a state ``S`` of ``[dk, dv]``, a decay ``a_t = exp(g_t)`` per
key channel and a write strength ``b_t``::

    S'_t = Diag(a_t) S_{t-1}
    S_t  = S'_t + b_t k_t (v_t - S'_t^T k_t)^T
    o_t  = S_t^T q_t

``kda_recurrent`` is that recurrence as a scan of ``T`` rank-one steps: the
yardstick of the tests, never the model's path.  ``kda_chunked`` computes the
same in chunks of ``chunk`` steps (64, as the published kernels): inside a
chunk everything is a matrix product, and only the ``T / chunk`` chunk states
are sequential.  With ``G`` the running sum of ``g`` inside a chunk and
``S_0`` the state the chunk starts from::

    A_sr = sum_c k_sc k_rc exp(G_sc - G_rc)            r < s   (keys on keys)
    P_sr = sum_c q_sc k_rc exp(G_sc - G_rc)            r <= s  (queries on keys)
    T    = (I + Diag(b) A)^-1                          unit lower triangular
    W    = T Diag(b) (K * exp(G)),   U = T Diag(b) V
    D    = U - W S_0                                   what each step writes
    O    = (Q * exp(G)) S_0 + P D
    S_C  = Diag(exp(G_C)) S_0 + (K * exp(G_C - G))^T D

``exp(G_s - G_r)`` never leaves [0, 1], but neither factor of a matrix
product can carry it whole: ``exp(-G_r)`` alone overflows after a few dozen
steps of strong decay.  So the rows of ``A`` and ``P`` are formed in blocks of
``sub`` (16) steps, each against an anchor ``m`` in its own middle (halfway
between the eighth step's ``G`` and the ninth's):
``(k_s exp(G_s - m)) . (k_r exp(m - G_r))``.  For every ``r`` in an earlier
block the second exponent is negative; inside the block either is at most
half a block of decay, and float32 (bfloat16 has the same exponents) carries
a factor only up to ``exp(+-87)``.  Past that the two factors no longer
multiply to ``exp(G_s - G_r)``: the diagonal of ``P`` reads 0 where it is 1,
and the gradient of ``g`` gains a term that is not there (at published widths
the later layers have channels that decay by ``exp(-166)`` within eight
steps).  So a step's log decay is held at ``-_FLOOR`` on entry: a channel the
model wipes by ``exp(-36)`` in a step is wiped by ``exp(-10)`` (4.5e-5 of one
channel of 128, far under the compute type's resolution), its gradient is
zero where the true one is ``exp(-36)`` of something, and half a block stays
within ``exp(+-75)``, where the product of the factors is what it stands
for.  ``_CAP`` only keeps the factors of the pairs that are masked out
(``r > s``) finite.  The triangular inverse is block forward
substitution by doubling (``log2(chunk)`` levels of small matrix products),
in float32 at the highest precision, with its own backward (``-T^T dT T^T``)
so that the levels are not kept.  Everything
else is differentiated by JAX, which makes the backward chunkwise too.

Matrix products take their operands in the dtype of ``q`` (bfloat16 in the
model) and accumulate in float32; decays, the inverse and the carried state
are float32.

``kda_chunked`` is plain ``jax.numpy`` through XLA: what runs off the TPU and
what the kernels are tested against.  On a TPU the model's call (``kda``) goes
through two Pallas kernels of the same mathematics (``kda_kernels``, a
``custom_vjp``).  The forward walks a grid of (row, block of heads, chunk),
the chunks in order: it reads a chunk's ``q, k, v, g, beta`` once (as the
projections leave them, ``[B, T, H d]``: a block of heads is a stretch of
lanes), forms ``G``, the anchors' factors, ``A``, ``P``, the inverse, ``W``
and ``U`` in VMEM, carries the heads' states in a float32 scratch from chunk
to chunk, and writes ``o`` and the state each chunk started from.  Those
states and the inputs are all the backward is handed: it walks the chunks in
reverse with the state's gradient carried the same way, forms a chunk's
insides again and writes the five gradients.  The anchors take no gradient
there: nothing depends on where they stand (differentiating through them, as
JAX does for the XLA form, adds the rounding of two sums that cancel).  The
inverse is the same doubling with every level at full size under a mask (no
slices at 1, 2, 4 lanes); ``G`` and the gradient of ``g`` are products with a
triangle of ones, the float32 operand split in three bfloat16 pieces that add
up to it, so three passes carry float32's last bits.  Each ``pallas_call``
stands behind a ``jit`` of its own, called from the ``custom_vjp``'s rules: it
is traced once a shape, so a program's calls (every KDA layer body's forward,
its forward again under remat, its backward) are one equation each and the
lowering turns each kernel into a Mosaic module once, not once a call.  (That
work is Python's and no compile cache holds it: it is paid at every start.)
"""

from __future__ import annotations

import functools
import math
import types

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from torchft_tpu.ops import flash_attention

_SUB = 16        # rows of a block that share an anchor
_FLOOR = 10.0    # half a block of decay at the floor is exp(-80): inside float32's exponents
_CAP = 80.0
_HIGHEST = jax.lax.Precision.HIGHEST


def kda_recurrent(
    q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array
) -> jax.Array:
    """The recurrence, one step at a time, in float32.  ``q``, ``k``, ``g``
    ``[B, T, H, dk]``, ``v`` ``[B, T, H, dv]``, ``beta`` ``[B, T, H]``; ``g``
    is the log of the decay (``<= 0``).  Returns ``[B, T, H, dv]``."""
    f32 = jnp.float32
    b, _, h, dk = q.shape
    dv = v.shape[-1]

    def step(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = jnp.exp(g_t)[..., None] * s
        read = jnp.einsum("bhk,bhkv->bhv", k_t, s, precision=_HIGHEST)
        s = s + jnp.einsum("bhk,bhv->bhkv", k_t * b_t[..., None], v_t - read, precision=_HIGHEST)
        return s, jnp.einsum("bhk,bhkv->bhv", q_t, s, precision=_HIGHEST)

    xs = tuple(jnp.moveaxis(x.astype(f32), 1, 0) for x in (q, k, v, g, beta))
    _, o = jax.lax.scan(step, jnp.zeros((b, h, dk, dv), f32), xs)
    return jnp.moveaxis(o, 0, 1).astype(v.dtype)


@jax.custom_vjp
def _unit_lower_inverse(n: jax.Array) -> jax.Array:
    """``(I + n)^-1`` for strictly lower triangular ``n`` ``[..., C, C]``
    (float32, ``C`` a power of two), by block forward substitution: the
    inverses of the diagonal blocks of size ``m`` give those of size ``2 m``,
    ``[[A, 0], [c, B]]^-1 = [[A^-1, 0], [-B^-1 c A^-1, B^-1]]``, from ``m = 1``
    (where they are 1) up.  As stable as substituting row by row, which a
    series in powers of ``n`` is not: with keys that resemble each other and
    ``beta`` near one the powers reach 1e17 and their alternating sum keeps
    no digit."""
    size = n.shape[-1]
    lead = n.shape[:-2]
    mm = functools.partial(jnp.matmul, precision=_HIGHEST)
    inv = jnp.ones(lead + (size, 1, 1), n.dtype)
    m = 1
    while m < size:
        pairs = size // (2 * m)
        # the lower left block of each diagonal pair of blocks: [..., pair, m, m]
        low = jnp.stack([n[..., (2 * p + 1) * m:(2 * p + 2) * m, 2 * p * m:(2 * p + 1) * m]
                         for p in range(pairs)], axis=-3)
        first, second = inv[..., 0::2, :, :], inv[..., 1::2, :, :]
        off = -mm(mm(second, low), first)
        inv = jnp.concatenate([
            jnp.concatenate([first, jnp.zeros_like(first)], axis=-1),
            jnp.concatenate([off, second], axis=-1)], axis=-2)
        m *= 2
    return inv[..., 0, :, :]


def _unit_lower_inverse_fwd(n):
    inv = _unit_lower_inverse(n)
    return inv, inv


def _unit_lower_inverse_bwd(inv, ct):
    inv_t = jnp.swapaxes(inv, -1, -2)
    return (-jnp.matmul(jnp.matmul(inv_t, ct, precision=_HIGHEST), inv_t, precision=_HIGHEST),)


_unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def _chunks(x: jax.Array, n: int, chunk: int) -> jax.Array:
    """``[B, n * chunk, H, ...] -> [B, H, n, chunk, ...]``."""
    b, _, h = x.shape[:3]
    x = x.reshape((b, n, chunk, h) + x.shape[3:])
    return jnp.moveaxis(x, 3, 1)


def kda_chunked(
    q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array,
    chunk: int = 64,
) -> jax.Array:
    """The same result as :func:`kda_recurrent`, in chunks (see the module's
    text), with a step's log decay held at ``-_FLOOR``.  Any ``T``: the last
    chunk is filled with steps that write nothing.  ``chunk`` is a power of
    two and a multiple of the block of 16."""
    sub = _SUB
    if chunk % sub or chunk & (chunk - 1):
        raise ValueError(f"chunk {chunk} must be a power of two and a multiple of {sub}")
    f32, act = jnp.float32, q.dtype
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    g = jnp.maximum(g.astype(f32), -_FLOOR)
    pad = -t % chunk
    if pad:
        q, k, v, g = (jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0))) for x in (q, k, v, g))
        beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
    n, ns = (t + pad) // chunk, chunk // sub
    qc, kc, vc = (_chunks(x, n, chunk) for x in (q, k, v))    # [B, H, n, C, d]
    bc = _chunks(beta.astype(f32), n, chunk)                  # [B, H, n, C]
    # G, [B, H, n, C, dk]: the running sum as a product with a triangle of
    # ones (a cumsum over 64 steps is a slow windowed reduction on a TPU)
    run = jnp.einsum("sr,...rd->...sd", jnp.tril(jnp.ones((chunk, chunk), f32)),
                     _chunks(g.astype(f32), n, chunk), precision=_HIGHEST)
    last = run[..., -1:, :]                                   # G_C

    def mm(eq, x, y):
        return jnp.einsum(eq, x.astype(act), y.astype(act), preferred_element_type=f32)

    # rows in blocks of `sub`, each against the anchor in its own middle
    lead = run.shape[:3]
    blocks = run.reshape(lead + (ns, sub, dk))
    anchor = 0.5 * (blocks[..., sub // 2 - 1, :] + blocks[..., sub // 2, :])   # [.., ns, dk]
    to_anchor = jnp.exp(jnp.minimum(blocks - anchor[..., None, :], _CAP))      # [.., ns, sub, dk]
    from_anchor = jnp.exp(jnp.minimum(
        anchor[..., None, :] - run[..., None, :, :], _CAP))                   # [.., ns, C, dk]
    keys_from = kc.astype(f32)[..., None, :, :] * from_anchor

    def on_keys(x):
        rows = x.astype(f32).reshape(lead + (ns, sub, dk)) * to_anchor
        return mm("...isd,...ird->...isr", rows, keys_from).reshape(lead + (chunk, chunk))

    steps = jnp.arange(chunk)
    earlier = steps[:, None] > steps[None, :]
    a = jnp.where(earlier, on_keys(kc), 0.0) * bc[..., None]
    p = jnp.where(earlier | (steps[:, None] == steps[None, :]), on_keys(qc), 0.0)
    inv = _unit_lower_inverse(a)

    grown = jnp.exp(run)
    w = mm("...sr,...rd->...sd", inv, kc.astype(f32) * grown * bc[..., None])  # [.., C, dk]
    u = mm("...sr,...rd->...sd", inv, vc.astype(f32) * bc[..., None])          # [.., C, dv]
    k_to_end = kc.astype(f32) * jnp.exp(last - run)
    decay = jnp.exp(last[..., 0, :])                                          # [B, H, n, dk]

    def step(state, x):
        w_n, u_n, k_n, decay_n = x
        wrote = u_n - mm("bhsk,bhkv->bhsv", w_n, state)
        new = decay_n[..., None] * state + mm("bhsk,bhsv->bhkv", k_n, wrote)
        return new, (state.astype(act), wrote.astype(act))

    xs = tuple(jnp.moveaxis(x, 2, 0) for x in (w, u, k_to_end, decay))
    _, (starts, wrote) = jax.lax.scan(step, jnp.zeros((b, h, dk, dv), f32), xs)
    starts, wrote = jnp.moveaxis(starts, 0, 2), jnp.moveaxis(wrote, 0, 2)  # [B, H, n, ...]
    o = mm("...sk,...kv->...sv", qc.astype(f32) * grown, starts) + mm("...sr,...rv->...sv", p, wrote)
    o = jnp.moveaxis(o, 1, 3).reshape(b, t + pad, h, dv)[:, :t]
    return o.astype(v.dtype)


# ---------------------------------------------------------------------------
# the same in Pallas: a chunk's insides stay in VMEM
# ---------------------------------------------------------------------------

_LANE = 128
# heads a grid step takes, as a batch dimension of its products.  A layer's
# forward | backward at 4 x 4096 x 32 x 128 on a v5e (PR 50): 11.0 | 13.2 ms at
# 8, 12.0 | 15.0 at 4, 14.9 | 18.2 at 2, 24.0 | 29.9 at 1
_HEADS_A_STEP = 8
_NN = (((2,), (1,)), ((0,), (0,)))   # [h, s, c] [h, c, r] -> [h, s, r]
_NT = (((2,), (2,)), ((0,), (0,)))   # [h, s, c] [h, r, c] -> [h, s, r]
_TN = (((1,), (1,)), ((0,), (0,)))   # [h, s, a] [h, s, b] -> [h, a, b]


def _dot(dims, x, y, act):
    """A batch of matrix products, operands in ``act``, accumulated in float32."""
    return jax.lax.dot_general(x.astype(act), y.astype(act), dims,
                               preferred_element_type=jnp.float32)


def _dot32(dims, x, y):
    return jax.lax.dot_general(x, y, dims, precision=_HIGHEST, preferred_element_type=jnp.float32)


def _ones_dot(ones, x):
    """``ones`` ``[C, C]`` of zeros and ones (bfloat16 holds them exactly)
    times ``x`` ``[C, n]`` float32, to float32's last bits: ``x`` goes in as
    three bfloat16 pieces that add up to it, half the passes the highest
    precision takes for two float32 operands."""
    f32, bf = jnp.float32, jnp.bfloat16
    top = x.astype(bf)
    rest = x - top.astype(f32)
    mid = rest.astype(bf)
    low = (rest - mid.astype(f32)).astype(bf)
    dot = functools.partial(jnp.dot, ones, preferred_element_type=f32)
    return dot(top) + dot(mid) + dot(low)


def _split(x, hb):
    """``[C, hb * d] -> [hb, C, d]``: the heads of a block, off the lanes."""
    d = x.shape[-1] // hb
    return jnp.stack([x[:, i * d:(i + 1) * d] for i in range(hb)])


def _merge(x):
    """``[hb, C, d] -> [C, hb * d]``."""
    return jnp.concatenate([x[i] for i in range(x.shape[0])], axis=-1)


def _unit_lower_inverse_tiles(a, row, col):
    """:func:`_unit_lower_inverse` for ``a`` ``[hb, C, C]`` inside a kernel:
    the same doubling with every level at full size.  With ``X_m`` the
    inverses of the diagonal blocks of size ``m`` (``X_1 = I``) and ``L_m``
    the lower left blocks of the diagonal blocks of size ``2 m``, ``X_2m =
    X_m - X_m L_m X_m``: no slices at 1, 2, 4 .. lanes, a mask a level."""
    inv = (row == col).astype(a.dtype) - jnp.where((row ^ col) == 1, a, 0.0)   # a is strictly lower: L_1

    def level(l, inv):
        low = ((row >> (l + 1)) == (col >> (l + 1))) & (((row >> l) & 1) == 1) & (((col >> l) & 1) == 0)
        return inv - _dot32(_NN, _dot32(_NN, inv, jnp.where(low, a, 0.0)), inv)

    return jax.lax.fori_loop(1, int(math.log2(a.shape[-1])), level, inv)


def _insides(q_ref, k_ref, v_ref, g_ref, b_ref, hb):
    """What both kernels form of a chunk from its inputs (``[hb, C, ..]``,
    float32 unless it says otherwise; the module's text has the names)."""
    f32 = jnp.float32
    x = types.SimpleNamespace()
    x.act = act = q_ref.dtype
    chunk = q_ref.shape[1]
    x.row = row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    x.col = col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    x.q, x.k, x.v = (_split(ref[0], hb).astype(f32) for ref in (q_ref, k_ref, v_ref))
    # beta [C, hb] -> [hb, C, 1]: a head's lane by a masked sum, no slice of one lane
    lane = jax.lax.broadcasted_iota(jnp.int32, (chunk, hb), 1)
    x.beta = beta = jnp.stack([jnp.sum(jnp.where(lane == i, b_ref[0, 0], 0.0), axis=1, keepdims=True)
                               for i in range(hb)])
    g = jnp.maximum(g_ref[0], -_FLOOR)
    x.run = run = _split(_ones_dot((row >= col).astype(jnp.bfloat16), g), hb)      # G
    x.last = run[:, chunk - 1:chunk]                                               # G_C, [hb, 1, dk]
    # rows in blocks of `_SUB`, each against the anchor in its own middle
    half = _SUB // 2
    x.to_anchor, x.from_anchor, x.rows_to, x.keys_from, scores = [], [], [], [], []
    for i in range(chunk // _SUB):
        rows = slice(i * _SUB, (i + 1) * _SUB)
        mid = i * _SUB + half
        anchor = 0.5 * (run[:, mid - 1:mid] + run[:, mid:mid + 1])
        to = jnp.exp(jnp.minimum(run[:, rows] - anchor, _CAP))                     # [hb, sub, dk]
        frm = jnp.exp(jnp.minimum(anchor - run, _CAP))                             # [hb, C, dk]
        # as the product takes them (in `act`): the backward multiplies by
        # these same values, so that the two sides of a pair's gradient of G
        # (+ at its row, - at its key) cancel under the running sum to the bit
        rows_to = jnp.concatenate([x.k[:, rows] * to, x.q[:, rows] * to], axis=1).astype(act)  # keys, queries
        keys_from = (x.k * frm).astype(act)
        scores.append(_dot(_NT, rows_to, keys_from, act))                          # [hb, 2 sub, C]
        x.to_anchor.append(to)
        x.from_anchor.append(frm)
        x.rows_to.append(rows_to)
        x.keys_from.append(keys_from)
    x.strict, x.through = row > col, row >= col
    x.a_keys = jnp.where(x.strict, jnp.concatenate([s[:, :_SUB] for s in scores], axis=1), 0.0)
    x.p = jnp.where(x.through, jnp.concatenate([s[:, _SUB:] for s in scores], axis=1), 0.0)
    x.inv = _unit_lower_inverse_tiles(x.a_keys * beta, row, col)
    x.grown, x.to_end, x.decay = jnp.exp(run), jnp.exp(x.last - run), jnp.exp(x.last)
    x.q_grown, x.k_grown, x.k_to_end = x.q * x.grown, x.k * x.grown, x.k * x.to_end
    x.rhs = jnp.concatenate([x.k_grown * beta, x.v * beta], axis=-1)               # [hb, C, dk + dv]
    x.wu = _dot(_NN, x.inv, x.rhs, act)                                            # W beside U
    return x


def _wrote(x, state):
    """``D = U - W S_0`` with the state held transposed, ``[hb, dv, dk]``."""
    dk = x.k.shape[-1]
    return x.wu[..., dk:] - _dot(_NT, x.wu[..., :dk], state, x.act)


def _kda_fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, s_ref, state, *, hb):
    """One chunk of ``hb`` heads.  ``state`` ``[hb, dv, dk]`` float32 is the
    heads' state transposed (a channel's decay then scales a lane), carried
    over the grid's last axis; ``s_ref`` takes it as the chunk found it."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    x = _insides(q_ref, k_ref, v_ref, g_ref, b_ref, hb)
    start = state[...]
    s_ref[0, :, 0] = start.astype(s_ref.dtype)
    wrote = _wrote(x, start)
    o = _dot(_NT, x.q_grown, start, x.act) + _dot(_NN, x.p, wrote, x.act)
    state[...] = x.decay * start + _dot(_TN, wrote, x.k_to_end, x.act)
    dv = o.shape[-1]
    for i in range(hb):
        o_ref[0, :, i * dv:(i + 1) * dv] = o[i].astype(o_ref.dtype)


def _kda_bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, s_ref, do_ref,
                    dq_ref, dk_ref, dv_ref, dg_ref, db_ref, d_state, *, hb):
    """The chunks in reverse: a chunk's insides formed again from its inputs
    and the state it started from, ``d_state`` ``[hb, dv, dk]`` the gradient
    of the state the chunk left, carried like the forward's.  The anchors
    take no gradient: nothing depends on where they stand."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        d_state[...] = jnp.zeros_like(d_state)

    f32 = jnp.float32
    x = _insides(q_ref, k_ref, v_ref, g_ref, b_ref, hb)
    act, dk = x.act, x.k.shape[-1]
    chunk = x.k.shape[1]
    start = s_ref[0, :, 0]
    d_o = _split(do_ref[0], hb)
    d_end = d_state[...]
    w = x.wu[..., :dk]
    wrote = _wrote(x, start)

    d_wrote = _dot(_TN, x.p, d_o, act) + _dot(_NT, x.k_to_end, d_end, act)
    d_p = jnp.where(x.through, _dot(_NT, d_o, wrote, act), 0.0)
    d_q_grown = _dot(_NN, d_o, start, act)
    d_k_to_end = _dot(_NN, wrote, d_end, act)
    d_state[...] = _dot(_TN, d_o, x.q_grown, act) + x.decay * d_end - _dot(_TN, d_wrote, w, act)
    d_last = (jnp.sum(d_end * start.astype(f32), axis=1, keepdims=True) * x.decay
              + jnp.sum(d_k_to_end * x.k_to_end, axis=1, keepdims=True))
    d_wu = jnp.concatenate([-_dot(_NN, d_wrote, start, act), d_wrote], axis=-1)
    d_rhs = _dot(_TN, x.inv, d_wu, act)
    # the inverse's own backward, -T^T dT T^T, in float32 as the inverse
    d_a = jnp.where(x.strict, -_dot32(_NT, _dot32(_TN, x.inv, _dot(_NT, d_wu, x.rhs, act)), x.inv), 0.0)
    d_k_grown = d_rhs[..., :dk] * x.beta
    d_beta = (jnp.sum(d_a * x.a_keys, axis=-1, keepdims=True)
              + jnp.sum(d_rhs[..., :dk] * x.k_grown, axis=-1, keepdims=True)
              + jnp.sum(d_rhs[..., dk:] * x.v, axis=-1, keepdims=True))
    d_a_keys = d_a * x.beta

    d_q_rows, d_k_rows, d_run_rows = [], [], []
    d_k = d_k_grown * x.grown + d_k_to_end * x.to_end
    d_run = d_q_grown * x.q_grown + d_k_grown * x.k_grown - d_k_to_end * x.k_to_end
    for i, (to, frm, rows_to, keys_from) in enumerate(
            zip(x.to_anchor, x.from_anchor, x.rows_to, x.keys_from)):
        rows = slice(i * _SUB, (i + 1) * _SUB)
        d_scores = jnp.concatenate([d_a_keys[:, rows], d_p[:, rows]], axis=1)     # [hb, 2 sub, C]
        d_rows_to = _dot(_NN, d_scores, keys_from, act)                            # [hb, 2 sub, dk]
        d_keys_from = _dot(_TN, d_scores, rows_to, act)                            # [hb, C, dk]
        d_k = d_k + d_keys_from * frm
        d_run = d_run - d_keys_from * keys_from.astype(f32)
        d_k_rows.append(d_rows_to[:, :_SUB] * to)
        d_q_rows.append(d_rows_to[:, _SUB:] * to)
        d_rows_run = d_rows_to * rows_to.astype(f32)
        d_run_rows.append(d_rows_run[:, :_SUB] + d_rows_run[:, _SUB:])
    d_q = d_q_grown * x.grown + jnp.concatenate(d_q_rows, axis=1)
    d_k = d_k + jnp.concatenate(d_k_rows, axis=1)
    is_last = jax.lax.broadcasted_iota(jnp.int32, (1, chunk, 1), 1) == chunk - 1
    d_run = d_run + jnp.concatenate(d_run_rows, axis=1) + jnp.where(is_last, d_last, 0.0)
    d_v = d_rhs[..., dk:] * x.beta

    dv = d_v.shape[-1]
    for i in range(hb):
        dq_ref[0, :, i * dk:(i + 1) * dk] = d_q[i].astype(dq_ref.dtype)
        dk_ref[0, :, i * dk:(i + 1) * dk] = d_k[i].astype(dk_ref.dtype)
        dv_ref[0, :, i * dv:(i + 1) * dv] = d_v[i].astype(dv_ref.dtype)
    # dg = the sum of dG over the later steps, zero where the floor held the step
    d_g = _ones_dot((x.row <= x.col).astype(jnp.bfloat16), _merge(d_run))
    dg_ref[0] = jnp.where(g_ref[0] > -_FLOOR, d_g, 0.0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (chunk, hb), 1)
    d_b = jnp.zeros((chunk, hb), f32)
    for i in range(hb):
        d_b = jnp.where(lane == i, d_beta[i], d_b)
    db_ref[0, 0] = d_b


def _laid_out(q, k, v, g, beta, chunk):
    """The op's arguments as the kernels read them: heads side by side on the
    lanes (``[B, T, H d]``, no copy), ``beta`` as ``[B, H / hb, T, hb]``,
    ``T`` filled to whole chunks with steps that write nothing."""
    b, t, h, _ = q.shape
    hb = math.gcd(h, _HEADS_A_STEP)
    pad = -t % chunk
    beta = _rows(beta.astype(jnp.float32), pad).reshape(b, t + pad, h // hb, hb).transpose(0, 2, 1, 3)
    return (*(_rows(x, pad) for x in (q, k, v, g.astype(jnp.float32))), beta)


def _rows(x, pad):
    """``[B, T, H, ..] -> [B, T + pad, H ..]``, the new steps zeros."""
    x = x.reshape(x.shape[0], x.shape[1], -1)
    return jnp.pad(x, ((0, 0), (0, pad), (0, 0))) if pad else x


def _specs(hb, chunk, dk, dv, at):
    """Block specs over the grid ``(row, head block, chunk)``; ``at`` maps the
    grid's last index to the chunk (the backward walks them in reverse)."""
    def wide(d):
        return pl.BlockSpec((1, chunk, hb * d), lambda i, j, c: (i, at(c), j))

    narrow = pl.BlockSpec((1, 1, chunk, hb), lambda i, j, c: (i, j, at(c), 0))
    states = pl.BlockSpec((1, hb, 1, dv, dk), lambda i, j, c: (i, j, at(c), 0, 0))
    return wide, narrow, states


_SEMANTICS = pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary"))


@functools.partial(jax.jit, static_argnames=("heads", "chunk", "interpret"))
def _kda_fwd_kernel_call(q, k, v, g, beta, *, heads, chunk, interpret):
    """The forward kernel on arguments as :func:`_laid_out` hands them: ``o``
    ``[B, T, H dv]`` and the states the chunks start from (``[B, H, n, dv,
    dk]``: a state is kept transposed).  A ``jit`` of its own around the
    ``pallas_call`` and nothing else, called from the ``custom_vjp``'s rules:
    traced once a shape, so every call of a program (each layer body's
    forward, its forward again under remat) is the same equation and the
    lowering makes the kernel's Mosaic module once, not once a call."""
    b, rows, _ = q.shape
    dk, dv, hb, n = q.shape[-1] // heads, v.shape[-1] // heads, beta.shape[-1], rows // chunk
    wide, narrow, states = _specs(hb, chunk, dk, dv, lambda c: c)
    return pl.pallas_call(
        functools.partial(_kda_fwd_kernel, hb=hb),
        grid=(b, heads // hb, n),
        in_specs=[wide(dk), wide(dk), wide(dv), wide(dk), narrow],
        out_specs=(wide(dv), states),
        out_shape=(jax.ShapeDtypeStruct((b, rows, heads * dv), v.dtype),
                   jax.ShapeDtypeStruct((b, heads, n, dv, dk), q.dtype)),
        scratch_shapes=[pltpu.VMEM((hb, dv, dk), jnp.float32)],
        compiler_params=_SEMANTICS,
        interpret=interpret,
        # the benchmark's trace shows the kernels by these names
        name="_kda_fwd_kernel",
    )(q, k, v, g, beta)


@functools.partial(jax.jit, static_argnames=("heads", "chunk", "interpret"))
def _kda_bwd_kernel_call(q, k, v, g, beta, starts, d_o, *, heads, chunk, interpret):
    """The backward kernel, laid out and lowered as the forward: the
    gradients of ``q, k, v, g`` as ``[B, T, H d]``, of ``beta`` as it came."""
    b, rows, _ = q.shape
    dk, dv, hb, n = q.shape[-1] // heads, v.shape[-1] // heads, beta.shape[-1], rows // chunk
    wide, narrow, states = _specs(hb, chunk, dk, dv, lambda c: n - 1 - c)
    f32 = jnp.float32
    return pl.pallas_call(
        functools.partial(_kda_bwd_kernel, hb=hb),
        grid=(b, heads // hb, n),
        in_specs=[wide(dk), wide(dk), wide(dv), wide(dk), narrow, states, wide(dv)],
        out_specs=(wide(dk), wide(dk), wide(dv), wide(dk), narrow),
        out_shape=(jax.ShapeDtypeStruct(q.shape, q.dtype), jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype), jax.ShapeDtypeStruct(g.shape, f32),
                   jax.ShapeDtypeStruct(beta.shape, f32)),
        scratch_shapes=[pltpu.VMEM((hb, dv, dk), f32)],
        compiler_params=_SEMANTICS,
        interpret=interpret,
        name="_kda_bwd_kernel",
    )(q, k, v, g, beta, starts, d_o)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def kda_kernels(q, k, v, g, beta, chunk=64, interpret=False):
    """:func:`kda_chunked` as two Pallas kernels (see the module's text).
    ``interpret`` runs them in the interpreter (the tests, off the TPU)."""
    return _kda_kernels_fwd(q, k, v, g, beta, chunk, interpret)[0]


def _kda_kernels_fwd(q, k, v, g, beta, chunk, interpret):
    b, t, h, _ = q.shape
    o, starts = _kda_fwd_kernel_call(
        *_laid_out(q, k, v, g, beta, chunk), heads=h, chunk=chunk, interpret=interpret)
    return o[:, :t].reshape(b, t, h, -1), (q, k, v, g, beta, starts)


def _kda_kernels_bwd(chunk, interpret, kept, d_o):
    q, k, v, g, beta, starts = kept
    b, t, h, _ = q.shape
    d_q, d_k, d_v, d_g, d_beta = _kda_bwd_kernel_call(
        *_laid_out(q, k, v, g, beta, chunk), starts, _rows(d_o, -t % chunk),
        heads=h, chunk=chunk, interpret=interpret)
    d_beta = d_beta.transpose(0, 2, 1, 3).reshape(b, -1, h)
    return (d_q[:, :t].reshape(q.shape), d_k[:, :t].reshape(k.shape), d_v[:, :t].reshape(v.shape),
            d_g[:, :t].reshape(g.shape).astype(g.dtype), d_beta[:, :t].astype(beta.dtype))


kda_kernels.defvjp(_kda_kernels_fwd, _kda_kernels_bwd)


def kernels_take(q_shape, v_shape, chunk: int) -> bool:
    """Whether the kernels take these shapes on a chip: a head's keys and
    values fill whole lanes (widths that are multiples of 128), so that a
    block's heads come apart without a shuffle.  Any ``T``, any number of
    heads, any ``chunk`` that :func:`kda_chunked` takes."""
    return q_shape[-1] % _LANE == 0 and v_shape[-1] % _LANE == 0 and chunk % _SUB == 0 \
        and not chunk & (chunk - 1)


def kda(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array,
        chunk: int = 64) -> jax.Array:
    """The chunked delta rule as the model runs it: through the kernels on a
    TPU where they take the shapes (:func:`kernels_take`), else through
    :func:`kda_chunked`, a row of the batch at a time, each under its own
    checkpoint (the XLA form's intermediates, several times ``q, k, v, g`` in
    float32, then live for one row, not for the batch).  Which of the two is
    asked of what the flash kernels ask (``flash_attention._interpret``), so a
    compile for a described chip gets the kernels.  Counts the call, as
    traced, in ``torchft_kda_calls_total{path}``."""
    from torchft_tpu.utils import metrics

    kernels = kernels_take(q.shape, v.shape, chunk) and not flash_attention._interpret()
    metrics.KDA_CALLS.labels(path="kernels" if kernels else "chunked").inc()
    if kernels:
        return kda_kernels(q, k, v, g, beta, chunk, False)

    def one_row(x):
        return kda_chunked(*(leaf[None] for leaf in x), chunk=chunk)[0]

    return jax.lax.map(jax.checkpoint(one_row), (q, k, v, g, beta))


__all__ = ["kda", "kda_chunked", "kda_kernels", "kda_recurrent", "kernels_take"]

