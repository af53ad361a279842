"""A sparse decoder trained as a block-diffusion model (``model_type``
``sdar_moe``: SDAR-30B-A3B-Chat): the Qwen3-MoE block (grouped-query heads of
a width of their own with per-head q / k norms, rotary over the whole head,
softmax-routed experts in every layer renormalised over the chosen, no shared
expert, an untied head) under a training step that runs every row twice.

**The step** on a row ``x0`` of ``T`` tokens (block-diffusion training as
BD3-LM, arXiv:2503.09573, writes it, with the masking forward process of
LLaDA, arXiv:2502.09992, which SDAR, arXiv:2510.06303, trains under):

- **the noise** (:func:`corrupt`): a level ``t ~ U(0, 1)`` a row, ``p = (1 -
  t_eps) t + t_eps``, every position masked independently with probability
  ``p``; a masked position's token becomes ``mask_token_id``.  The draw is a
  pure function of the row and ``noise_seed`` (:func:`noise_key`: the seed's
  key folded with a checksum of the row's own tokens), so a step replays bit
  for bit on a healed replica and the healed tree carries no generator state;
- **the input**: ``[noised, clean]`` along positions, ``2T`` a row; position
  ``i`` and ``T + i`` hold token ``i`` and turn by the same rotary angle;
- **attention** (:func:`diffusion_mask`; blocks of ``block_length`` tokens):
  a noised query sees the noised keys of its own block, both directions, and
  the clean keys of the blocks before it; a clean query sees the clean keys
  of its own block and of those before it; nobody sees a noised key of
  another block.  Through ``ops/flash_attention.py``
  ``flash_block_diffusion``: two calls on the causal tile walk and the own
  block's dense ``[T / block, block, block]`` scores, merged by their
  log-sum-exp; the ``[2T, 2T]`` plane is never formed (``attn_impl="dense"``
  forms it, for small sizes);
- **the loss** (:func:`loss_fn`): logits of the noised half only, ``1 / (B
  T) sum_b sum_{i masked} (1 / p_b) (-log softmax(logits[b, i])[x0[b, i]])``:
  position ``i`` predicts token ``i``, no shift.

The expert layer is ``models/moe.py`` ``held_moe_ffn`` routed by
``route_softmax``, as ``models/mellum.py`` runs it: ``2T`` positions a row go
through the router, and the masked positions of the noised copy enter layer 0
as one and the same embedding, so that layer's router sends them one way.

The parameters: ``attn`` (the attention weights with ``input_norm``) and
``moe`` (the expert layer's with ``post_attention_norm``), each stacked by
layer, beside ``embed``, ``head`` and ``final_norm``.  Every layer is alike,
so the walk (``models/kimi_linear.py`` ``_run_layers``) is one ``lax.scan``
over all layers but the last, which is walked once on its own: its clean
copy feeds keys and values only (no query, no output projection and no
expert of it reaches a logit), and what leaves it is the noised copy's ``T``
positions.  Norm, the rotation, remat and the embedding are
``models/transformer.py``'s.

Single device: the replica dimension lives above jit in the Manager, and the
chips that hold the other experts and layers are not this program's.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from torchft_tpu.models import moe
from torchft_tpu.models.kimi_linear import _logits, _run_layers
from torchft_tpu.models.moe import HeldMoEConfig, held_moe_ffn, init_held_moe_params
from torchft_tpu.models.transformer import _embed, _grad_step, _remat, _rms_norm, _rotate

Params = Dict[str, Any]
GROUPS = ("attn", "moe")
# a layer's kind names the groups its weights come from; the last layer's
# carries a third name, of a group without leaves, so that the walk cuts it
# from the scan's run
_KIND, _LAST = ("attn", "moe"), ("attn", "moe", "last")


@dataclasses.dataclass(frozen=True)
class SDARConfig:
    vocab_size: int = 151936
    d_model: int = 2048
    n_layers: int = 48
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1000000.0
    # the expert layer
    d_expert: int = 768
    n_routed_experts: int = 128
    experts_per_token: int = 8
    held_experts: Tuple[int, ...] = tuple(range(16))
    expert_slack: float = 2.0
    rms_norm_eps: float = 1e-6
    # the block-diffusion step
    block_length: int = 4
    # None: the last row of the vocabulary held here
    mask_token_id: Optional[int] = None
    noise_seed: int = 0
    t_eps: float = 1e-3
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    # as ``TransformerConfig.remat_policy``
    remat_policy: str = "full"
    # "flash" (ops/flash_attention.py; T % 128 == 0) or "dense"
    attn_impl: str = "flash"

    def moe(self) -> HeldMoEConfig:
        return HeldMoEConfig(
            d_model=self.d_model, d_expert=self.d_expert, n_routed=self.n_routed_experts,
            top_k=self.experts_per_token, held=tuple(self.held_experts),
            slack=self.expert_slack, shared=False, renorm_eps=0.0, score="softmax",
            dtype=self.dtype, param_dtype=self.param_dtype)

    def mask_id(self) -> int:
        return self.vocab_size - 1 if self.mask_token_id is None else self.mask_token_id


def init_params(rng: jax.Array, cfg: SDARConfig) -> Params:
    """The parameter tree (see the module's text)."""
    n, e, pd = cfg.n_layers, cfg.d_model, cfg.param_dtype
    dq, dkv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    keys = iter(jax.random.split(rng, 8))

    def dense(*shape):
        return (jax.random.normal(next(keys), shape, pd) / np.sqrt(shape[-2])).astype(pd)

    return {
        "embed": jax.random.normal(next(keys), (cfg.vocab_size, e), pd) * 0.02,
        "head": dense(e, cfg.vocab_size),
        "final_norm": jnp.ones((e,), pd),
        "attn": {
            "input_norm": jnp.ones((n, e), pd), "wq": dense(n, e, dq), "wk": dense(n, e, dkv),
            "wv": dense(n, e, dkv), "q_norm": jnp.ones((n, cfg.head_dim), pd),
            "k_norm": jnp.ones((n, cfg.head_dim), pd), "wo": dense(n, dq, e)},
        "moe": dict(init_held_moe_params(next(keys), cfg.moe(), n),
                    post_attention_norm=jnp.ones((n, e), pd)),
    }


# ---------------------------------------------------------------------------
# the noise
# ---------------------------------------------------------------------------


def noise_key(row: jax.Array, noise_seed: int) -> jax.Array:
    """The key a row's noise is drawn from: ``PRNGKey(noise_seed)`` folded
    with the row's checksum ``sum_i (token_i + 1) (i + 1) mod 2^32`` (integer
    sums wrap alike in any order and on any device)."""
    row = row.astype(jnp.uint32)
    checksum = jnp.sum((row + 1) * (jnp.arange(row.shape[0], dtype=jnp.uint32) + 1), dtype=jnp.uint32)
    return jax.random.fold_in(jax.random.PRNGKey(noise_seed), checksum)


def corrupt(tokens: jax.Array, cfg: SDARConfig) -> "Tuple[jax.Array, jax.Array, jax.Array]":
    """tokens ``[B, T]`` -> ``(noised [B, T], masked [B, T] bool, p [B])``: a
    row's level ``t`` is the first uniform of its key's first half, ``p = (1 -
    t_eps) t + t_eps``, position ``i`` is masked where the ``i``-th uniform
    of the key's second half lies under ``p``, and a masked position holds
    ``mask_token_id``."""

    def one(row):
        k_level, k_mask = jax.random.split(noise_key(row, cfg.noise_seed))
        p = (1.0 - cfg.t_eps) * jax.random.uniform(k_level, (), jnp.float32) + cfg.t_eps
        return jax.random.uniform(k_mask, row.shape, jnp.float32) < p, p

    with jax.named_scope("sdar.corrupt"):
        masked, p = jax.vmap(one)(tokens)
        noised = jnp.where(masked, jnp.asarray(cfg.mask_id(), tokens.dtype), tokens)
    return noised, masked, p


# ---------------------------------------------------------------------------
# the layers
# ---------------------------------------------------------------------------


def diffusion_mask(t: int, block: int) -> np.ndarray:
    """The ``[2T, 2T]`` plane as booleans (query down, key along; its first
    ``T`` rows are the noised copy's queries): the three live parts of the
    module's text.  For the dense path and for tests; the flash path never
    forms it."""
    pos = np.arange(2 * t)
    noised, blk = pos < t, (pos % t) // block
    nq, nk, bq, bk = noised[:, None], noised[None, :], blk[:, None], blk[None, :]
    return (nq & nk & (bq == bk)) | (nq & ~nk & (bq > bk)) | (~nq & ~nk & (bq >= bk))


def _dense_diffusion(q: jax.Array, k: jax.Array, v: jax.Array, block: int) -> jax.Array:
    """``flash_block_diffusion`` with the plane formed: float32 softmax over
    the keys :func:`diffusion_mask` leaves (every query has its own); ``q``
    holds both copies' queries or the noised copy's alone."""
    rep = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    seen = jnp.asarray(diffusion_mask(k.shape[1] // 2, block)[:q.shape[1]])
    probs = jax.nn.softmax(jnp.where(seen, scores * q.shape[-1] ** -0.5, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(q.dtype), v)


def _attention(h: jax.Array, p: Params, cfg: SDARConfig, clean_queries: bool) -> jax.Array:
    """``h`` ``[B, 2T, E]`` -> ``[B, 2T, E]``, or without ``clean_queries``
    ``[B, T, E]``: the noised copy's rows alone, the clean copy giving keys
    and values."""
    b, t2, _ = h.shape
    tq = t2 if clean_queries else t2 // 2
    nh, nkv, dh, act = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.dtype
    with jax.named_scope("attn.proj"):
        q = (h[:, :tq] @ p["wq"].astype(act)).reshape(b, tq, nh, dh)
        k = (h @ p["wk"].astype(act)).reshape(b, t2, nkv, dh)
        v = (h @ p["wv"].astype(act)).reshape(b, t2, nkv, dh)
    with jax.named_scope("attn.diffusion"):
        q = _rms_norm(q, p["q_norm"], cfg.rms_norm_eps)
        k = _rms_norm(k, p["k_norm"], cfg.rms_norm_eps)
        with jax.named_scope("attn.rope"):
            # a position turns by its token's index: 0..T-1 twice
            index = jnp.tile(jnp.arange(t2 // 2, dtype=jnp.float32), 2)
            inv_freq = cfg.rope_theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
            angles = index[:, None] * inv_freq[None, :]
            q, k = _rotate(q, angles[:tq]), _rotate(k, angles)
        if cfg.attn_impl == "flash":
            from torchft_tpu.ops.flash_attention import flash_block_diffusion

            o = flash_block_diffusion(q, k, v, cfg.block_length)
        elif cfg.attn_impl == "dense":
            o = _dense_diffusion(q, k, v, cfg.block_length)
        else:
            raise ValueError(f"unknown attn_impl {cfg.attn_impl!r}; expected 'flash' or 'dense'")
    with jax.named_scope("attn.proj"):
        return o.reshape(b, tq, nh * dh) @ p["wo"].astype(act)


def _make_layer(kind: "Tuple[str, ...]", cfg: SDARConfig):
    """``layer(x, attention params, expert params, ...) -> (x, routing
    stats)`` on ``x`` ``[B, 2T, E]``, every leaf without its layer dimension.
    The last layer gives back the noised copy's ``[B, T, E]`` alone: its
    attention has no clean query, and its experts see ``T`` positions a row."""
    eps, whole = cfg.rms_norm_eps, kind != _LAST

    def layer(x, pa, pf, *_):
        residual = x if whole else x[:, :x.shape[1] // 2]
        x = residual + _attention(_rms_norm(x, pa["input_norm"], eps), pa, cfg, clean_queries=whole)
        y, stats = held_moe_ffn(_rms_norm(x, pf["post_attention_norm"], eps), pf, cfg.moe())
        return x + y, stats

    return _remat(layer, cfg) if cfg.remat else layer


def forward_hidden(
    params: Params, both: jax.Array, cfg: SDARConfig,
) -> "Tuple[jax.Array, Dict[str, jax.Array]]":
    """``both`` ``[B, 2T]`` (a row's noised copy, then its clean copy) -> the
    last layer's output for the noised copy ``[B, T, E]`` and the routing
    stats of the layers (``assignments`` ``[layers, held]``, ``unrouted``
    ``[layers]``, counted over the ``2T`` positions a layer runs, ``T`` in the
    last)."""
    if both.shape[1] % (2 * cfg.block_length):
        raise ValueError(f"a row of {both.shape[1] // 2} tokens is no whole number of blocks "
                         f"of {cfg.block_length}")
    with jax.named_scope("embed"):
        x = _embed(params, both, cfg, sharded=False)
    groups = dict({g: params[g] for g in GROUPS}, last={})
    return _run_layers(x, groups, [_KIND] * (cfg.n_layers - 1) + [_LAST], lambda kind: _make_layer(kind, cfg))


def forward(params: Params, tokens: jax.Array, cfg: SDARConfig) -> jax.Array:
    """tokens ``[B, T]`` -> the logits of the noised copy ``[B, T, vocab]``
    (float32) under the row's own noise."""
    noised, _, _ = corrupt(tokens, cfg)
    x, _ = forward_hidden(params, jnp.concatenate([noised, tokens], axis=1), cfg)
    with jax.named_scope("head"):
        return _logits(params, x, cfg)


def _masked_nll(params: Params, x: jax.Array, tokens: jax.Array, weight: jax.Array,
                cfg: SDARConfig) -> jax.Array:
    """``sum_b sum_i weight[b, i] (-log softmax(head(x[b, i]))[tokens[b,
    i]])``, a row of the batch at a time under ``jax.checkpoint`` (the float32
    logits of one row live at once).  No shift: position ``i`` is scored on
    token ``i``."""

    def row(acc, xs):
        x_row, tok_row, w_row = xs
        with jax.named_scope("head"):
            logits = _logits(params, x_row[None], cfg)[0]
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, tok_row[:, None], axis=-1)[:, 0]
        return acc + jnp.sum((lse - picked) * w_row), None

    with jax.named_scope("sdar.loss"):
        total, _ = jax.lax.scan(jax.checkpoint(row), jnp.zeros((), jnp.float32), (x, tokens, weight))
    return total


def loss_fn(params: Params, tokens: jax.Array, cfg: SDARConfig) -> jax.Array:
    """The block-diffusion loss of the module's text: the masked positions'
    cross-entropy on their own tokens, weighted ``1 / p_b``, over ``B T``.
    No auxiliary loss: the published configuration has no coefficient."""
    b, t = tokens.shape
    noised, masked, p = corrupt(tokens, cfg)
    x, _ = forward_hidden(params, jnp.concatenate([noised, tokens], axis=1), cfg)
    weight = masked.astype(jnp.float32) / p[:, None]
    return _masked_nll(params, x, tokens, weight, cfg) / (b * t)


def make_grad_step(cfg: SDARConfig):
    """A jitted ``(params, tokens) -> (loss, grads)`` step, the FT-DDP shape
    of ``models/transformer.py`` ``make_grad_step``; the noise is inside."""

    return jax.jit(_grad_step(lambda p, t: loss_fn(p, t, cfg), cfg))


def make_routing_stats(cfg: SDARConfig):
    """A jitted ``routing_stats(params, tokens)`` (as ``models/mellum.py``'s),
    over the positions the step runs (``2T`` a row, ``T`` in the last layer):
    per layer the assignments that landed on each held expert and the
    positions that found none of theirs here; beside them the batch's noise: ``masked_share`` (of the ``B T``
    positions) and each row's ``p``.  A forward pass of its own: never inside
    a timed step."""

    def routing_stats(params, tokens):
        noised, masked, p = corrupt(tokens, cfg)
        stats = forward_hidden(params, jnp.concatenate([noised, tokens], axis=1), cfg)[1]
        return dict(stats, masked_share=masked.mean(dtype=jnp.float32), p=p)

    return jax.jit(routing_stats)


def record_routing_stats(stats: "Dict[str, Any]", cfg: SDARConfig) -> None:
    """Feeds one batch's ``routing_stats`` to the counters (``models/moe.py``
    ``record_routing_stats``; the rows are the layers' numbers from 0) and
    the batch's noise to ``torchft_diffusion_masked_share`` and
    ``torchft_diffusion_noise_level{row}``."""
    from torchft_tpu.utils import metrics

    moe.record_routing_stats(stats, range(cfg.n_layers), cfg.held_experts)
    metrics.DIFFUSION_MASKED_SHARE.set(float(stats["masked_share"]))
    for row, p in enumerate(np.asarray(stats["p"])):
        metrics.DIFFUSION_NOISE_LEVEL.labels(row=str(row)).set(float(p))


__all__ = [
    "SDARConfig",
    "init_params",
    "noise_key",
    "corrupt",
    "diffusion_mask",
    "forward_hidden",
    "forward",
    "loss_fn",
    "make_grad_step",
    "make_routing_stats",
    "record_routing_stats",
]
