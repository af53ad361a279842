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
are float32.  Plain ``jax.numpy`` through XLA: no kernel of its own.
"""

from __future__ import annotations

import functools
import jax
import jax.numpy as jnp

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


__all__ = ["kda_chunked", "kda_recurrent"]
