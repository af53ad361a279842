"""Latent attention (MLA), the one function the families that have it call
(``models/kimi_linear.py``: no rotary, a full-rank query; ``models/joyai.py``:
a rotary part on every head and the query through a latent of its own).

Keys and values come from a latent of ``kv_lora_rank`` with its own RMS norm,
``[k_nope; v] = W_kvb c`` per head; beside it ``W_kva`` gives one key head of
``qk_rope_head_dim`` that all the query heads share, ``k = [k_nope; k_pe]``.
The query is ``W_q x`` (``q_lora_rank`` ``None``) or ``W_qb rms(W_qa x)``
through a latent of ``q_lora_rank``, in heads of ``nope + rope``.  Where the
configuration rotates (``rope_theta`` given), the ``rope`` dimensions of every
query head and of the shared key head are turned by the position; the other
``nope`` carry none.  Causal softmax at ``(nope + rope) ** -0.5`` through the
flash kernels (queries and keys of ``nope + rope`` against values of
``v_head_dim``), then ``W_o``.  No bias anywhere.

**The rotary on interleaved pairs** (``rope_interleave``): pair ``j`` is the
dimensions ``(2j, 2j + 1)``, turned by ``pos * theta ** (-2j / rope)``.
``models/transformer.py`` ``_rope`` turns the pair ``(j, j + rope / 2)`` by
the same angle, so the columns of ``W_qb`` and ``W_kva`` that give the rotary
dimensions are taken in the order evens, then odds, and ``_rope`` does the
rest: a score is a sum over the dimensions, which the same reordering of
query and key leaves as it was, and nothing else reads them.  Reordering the
weights' columns moves 10 M numbers a layer that lie well for the chip; a
``[..., 32, 2]`` view of the activations would not.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from torchft_tpu.models.transformer import _rms_norm, _rope
from torchft_tpu.ops.ring_attention import dense_attention

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """What one latent-attention layer reads of its model's configuration."""

    d_model: int
    n_heads: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    # the query's latent; None: one full-rank projection ``wq``
    q_lora_rank: Optional[int] = None
    # None: the ``rope`` dimensions carry no position either (NoPE)
    rope_theta: Optional[float] = None
    # pairs (2j, 2j + 1) if true, (j, j + rope / 2) if not
    rope_interleave: bool = False
    rms_norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    # "flash" (ops/flash_attention.py; T % 128 == 0) or "dense"
    attn_impl: str = "flash"

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


def init_mla_params(rng: jax.Array, cfg: MLAConfig, n_layers: int) -> Params:
    """The attention norm and the projections, a leading ``[n_layers]`` dim
    for stacked blocks; the query's leaves by ``q_lora_rank``."""
    e, pd, nh = cfg.d_model, cfg.param_dtype, cfg.n_heads
    keys = iter(jax.random.split(rng, 5))

    def dense(*shape):
        return (jax.random.normal(next(keys), (n_layers,) + shape, pd) / np.sqrt(shape[-2])).astype(pd)

    if cfg.q_lora_rank is None:
        query = {"wq": dense(e, nh * cfg.qk_head_dim)}
    else:
        query = {"q_a": dense(e, cfg.q_lora_rank), "q_norm": jnp.ones((n_layers, cfg.q_lora_rank), pd),
                 "q_b": dense(cfg.q_lora_rank, nh * cfg.qk_head_dim)}
    return {
        "attn_norm": jnp.ones((n_layers, e), pd), **query,
        "kv_a": dense(e, cfg.kv_lora_rank + cfg.qk_rope_head_dim),
        "kv_norm": jnp.ones((n_layers, cfg.kv_lora_rank), pd),
        "kv_b": dense(cfg.kv_lora_rank, nh * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
        "wo": dense(nh * cfg.v_head_dim, e),
    }


def pairs_as_halves(rope: int) -> np.ndarray:
    """``[0, 2, .., rope - 2, 1, 3, .., rope - 1]``: where each of the
    dimensions ``_rope`` pairs as ``(j, j + rope / 2)`` lies among the
    interleaved pairs ``(2j, 2j + 1)``."""
    return np.concatenate([np.arange(0, rope, 2), np.arange(1, rope, 2)])


def _rotary_columns(w: jax.Array, heads: int, nope: int, rope: int) -> jax.Array:
    """``w [in, heads * (nope + rope)]`` with each head's last ``rope``
    columns in the order ``pairs_as_halves`` gives."""
    head = np.concatenate([np.arange(nope), nope + pairs_as_halves(rope)])
    return w[:, (np.arange(heads)[:, None] * (nope + rope) + head[None, :]).reshape(-1)]


def mla_attention(h: jax.Array, p: Params, cfg: MLAConfig) -> jax.Array:
    """``h [B, T, E]`` (normed) -> the attention output ``[B, T, E]``; ``p``:
    one layer's leaves, without their layer dimension."""
    b, t, _ = h.shape
    nh, act = cfg.n_heads, cfg.dtype
    nope, rope, dv, rank = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
                            cfg.kv_lora_rank)
    turned = cfg.rope_theta is not None and cfg.rope_interleave
    with jax.named_scope("mla"):
        if cfg.q_lora_rank is None:
            q_in, q_w = h, p["wq"].astype(act)
        else:
            q_in = _rms_norm(h @ p["q_a"].astype(act), p["q_norm"], cfg.rms_norm_eps)
            q_w = p["q_b"].astype(act)
        q = (q_in @ (_rotary_columns(q_w, nh, nope, rope) if turned else q_w)).reshape(
            b, t, nh, nope + rope)
        kv_a_w = p["kv_a"].astype(act)
        kv_a = h @ (_rotary_columns(kv_a_w, 1, rank, rope) if turned else kv_a_w)
        latent = _rms_norm(kv_a[..., :rank], p["kv_norm"], cfg.rms_norm_eps)
        k_pe = kv_a[..., None, rank:]
        if cfg.rope_theta is not None:
            with jax.named_scope("mla.rope"):
                positions = jnp.arange(t)
                q = jnp.concatenate(
                    [q[..., :nope], _rope(q[..., nope:], positions, cfg.rope_theta)], axis=-1)
                k_pe = _rope(k_pe, positions, cfg.rope_theta)
        k_pe = jnp.broadcast_to(k_pe, (b, t, nh, rope))
        kv = (latent @ p["kv_b"].astype(act)).reshape(b, t, nh, nope + dv)
        k = jnp.concatenate([kv[..., :nope], k_pe], axis=-1)
        v = kv[..., nope:]
        if cfg.attn_impl == "flash":
            from torchft_tpu.ops.flash_attention import flash_attention

            o = flash_attention(q, k, v, causal=True)
        elif cfg.attn_impl == "dense":
            o = dense_attention(q, k, v, causal=True)
        else:
            raise ValueError(f"unknown attn_impl {cfg.attn_impl!r}; expected 'flash' or 'dense'")
        return o.reshape(b, t, nh * dv) @ p["wo"].astype(act)


__all__ = ["MLAConfig", "init_mla_params", "mla_attention", "pairs_as_halves"]
