"""A sparse decoder in the JoyAI-LLM-Flash architecture (``model_type``
``joyai_llm_flash``; its keys are those of the DeepSeek-V3 modelling code):
latent attention in every layer, the query through a latent of its own and a
rotary part on every head, a leading dense SwiGLU FFN and then sigmoid-routed
experts with a shared expert, an untied output head, and a multi-token
prediction module behind the trunk that shares embedding and head with it.

Every layer is ``x + Attn(RMSNorm(x))`` then ``x + FFN(RMSNorm(x))``.

- **Attention**: ``models/mla.py`` ``mla_attention``, the function
  ``models/kimi_linear.py`` runs, as this model's configuration has it:
  ``q = W_qb rms(W_qa x)`` through a latent of ``q_lora_rank``, heads of
  ``nope + rope``; ``[c; k_pe] = W_kva x``, ``[k_nope; v] = W_kvb rms(c)``;
  the ``rope`` dimensions of every query head and of the one key head all
  heads share turned by the position (interleaved pairs, ``rope_theta``).
- **Experts**: ``models/moe.py`` ``held_moe_ffn``, the layer
  ``models/kimi_linear.py`` and ``models/afmoe.py`` run: the chip's share of
  a sigmoid-routed, dropless layer, told which published experts it holds.
- **The module** (``num_nextn_predict_layers`` 1): with ``x_L`` the trunk's
  last hidden state before the final norm, ``h'[i] = [rms(embed[t_{i+1}]) |
  rms(x_L[i])] W_eh``, one more layer of the expert kind with weights of its
  own over ``h'``, a final norm of its own, and the trunk's head: position
  ``i`` predicts token ``i + 2``.  ``loss = L_main + mtp_loss_weight L_mtp``,
  each a mean over the positions that have a target (``T - 1`` and ``T - 2``
  a row).  The module runs over all ``T`` positions, so that the flash
  kernels keep their tiles: position ``T - 1`` is fed the row's first token's
  embedding and is left out of the loss with position ``T - 2``; causality
  keeps it from every position that counts.  The embedding and the head are
  each used twice in a step: their gradients are the sums of both paths.

The parameters are grouped as ``models/kimi_linear.py`` groups them, each
group stacked by layer: ``mla`` (with the attention norm), ``dense`` and
``moe`` (with the FFN norm), beside ``embed``, ``head``, ``final_norm`` and
``mtp``: the module's layer (the leaves of an ``mla`` and a ``moe`` layer
under their names) with ``e_norm``, ``h_norm``, ``w_eh`` and ``out_norm``,
each stacked ``[1, ...]`` as a group of one layer.  The walk over the trunk
(a run of repeats is one ``lax.scan``), the head's loss by rows, norm,
remat and the SwiGLU are the other models'.

Single device: the replica dimension lives above jit in the Manager, and the
chips that hold the other experts and layers are not this program's.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from torchft_tpu.models import moe
from torchft_tpu.models.kimi_linear import Kind, _logits, _run_layers
from torchft_tpu.models.mla import MLAConfig, init_mla_params, mla_attention
from torchft_tpu.models.moe import HeldMoEConfig, held_moe_ffn, init_held_moe_params
from torchft_tpu.models.transformer import (
    _embed,
    _grad_step,
    _next_token_nll,
    _remat,
    _rms_norm,
    _swiglu,
)

Params = Dict[str, Any]
GROUPS = ("mla", "dense", "moe")
_EXPERT_LAYER: Kind = ("mla", "moe")


@dataclasses.dataclass(frozen=True)
class JoyAIConfig:
    vocab_size: int = 129280
    d_model: int = 2048
    n_layers: int = 40
    first_k_dense: int = 1
    n_heads: int = 32
    # latent attention
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 32_000_000.0
    rope_interleave: bool = True
    # FFNs
    d_ff: int = 7168
    d_expert: int = 768
    n_routed_experts: int = 256
    experts_per_token: int = 8
    held_experts: Tuple[int, ...] = tuple(range(8))
    routed_scaling_factor: float = 2.5
    expert_slack: float = 8.0
    # the module: 0 or 1 further prediction depth, and its loss's weight
    n_predict_layers: int = 1
    mtp_loss_weight: float = 0.3
    rms_norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    # as ``TransformerConfig.remat_policy``: "full" keeps a layer's input and,
    # of a layer through the flash kernels, the forward kernel's two results
    # (B*T*H*Dv activations + B*H*T float32 a layer) so that the backward
    # does not run that kernel again; "dots" keeps matrix products
    remat_policy: str = "full"
    # "flash" (ops/flash_attention.py; T % 128 == 0) or "dense"
    attn_impl: str = "flash"

    def mla(self) -> MLAConfig:
        return MLAConfig(
            d_model=self.d_model, n_heads=self.n_heads, kv_lora_rank=self.kv_lora_rank,
            qk_nope_head_dim=self.qk_nope_head_dim, qk_rope_head_dim=self.qk_rope_head_dim,
            v_head_dim=self.v_head_dim, q_lora_rank=self.q_lora_rank, rope_theta=self.rope_theta,
            rope_interleave=self.rope_interleave, rms_norm_eps=self.rms_norm_eps,
            dtype=self.dtype, param_dtype=self.param_dtype, attn_impl=self.attn_impl)

    def moe(self) -> HeldMoEConfig:
        return HeldMoEConfig(
            d_model=self.d_model, d_expert=self.d_expert, n_routed=self.n_routed_experts,
            top_k=self.experts_per_token, held=tuple(self.held_experts),
            routed_scale=self.routed_scaling_factor, slack=self.expert_slack,
            dtype=self.dtype, param_dtype=self.param_dtype)


def layer_kinds(cfg: JoyAIConfig) -> "List[Kind]":
    """``(attention, ffn)`` of every trunk layer, numbered from 0 as
    published: the first ``first_k_dense`` with the dense FFN."""
    return [("mla", "dense" if i < cfg.first_k_dense else "moe") for i in range(cfg.n_layers)]


def init_params(rng: jax.Array, cfg: JoyAIConfig) -> Params:
    """The parameter tree (see the module's text).  The router's correction
    bias is no parameter: the forward pass takes it as a buffer."""
    if cfg.n_predict_layers not in (0, 1):
        raise ValueError(f"n_predict_layers {cfg.n_predict_layers}: the module is one layer or none")
    ld = min(cfg.first_k_dense, cfg.n_layers)
    lx = cfg.n_layers - ld
    e, pd = cfg.d_model, cfg.param_dtype
    keys = iter(jax.random.split(rng, 10))

    def dense(n, *shape):
        return (jax.random.normal(next(keys), (n,) + shape, pd) / np.sqrt(shape[-2])).astype(pd)

    def expert_layers(n):
        return dict(init_held_moe_params(next(keys), cfg.moe(), n), mlp_norm=jnp.ones((n, e), pd))

    params = {
        "embed": jax.random.normal(next(keys), (cfg.vocab_size, e), pd) * 0.02,
        "head": dense(1, e, cfg.vocab_size)[0],
        "final_norm": jnp.ones((e,), pd),
        "mla": init_mla_params(next(keys), cfg.mla(), cfg.n_layers),
        "dense": {"mlp_norm": jnp.ones((ld, e), pd), "w_gate": dense(ld, e, cfg.d_ff),
                  "w_up": dense(ld, e, cfg.d_ff), "w_down": dense(ld, cfg.d_ff, e)},
        "moe": expert_layers(lx),
    }
    if cfg.n_predict_layers:
        params["mtp"] = {
            "e_norm": jnp.ones((1, e), pd), "h_norm": jnp.ones((1, e), pd),
            "w_eh": dense(1, 2 * e, e), "out_norm": jnp.ones((1, e), pd),
            **init_mla_params(next(keys), cfg.mla(), 1), **expert_layers(1)}
    return params


def _make_layer(kind: Kind, cfg: JoyAIConfig):
    """``layer(x, attention params, ffn params) -> (x, routing stats)`` for
    one layer of this kind, every leaf without its layer dimension."""
    eps = cfg.rms_norm_eps

    def layer(x, pa, pf):
        x = x + mla_attention(_rms_norm(x, pa["attn_norm"], eps), pa, cfg.mla())
        h = _rms_norm(x, pf["mlp_norm"], eps)
        if kind[1] == "moe":
            y, stats = held_moe_ffn(h, pf, cfg.moe(), router_bias=pf.get("router_bias"))
            return x + y, stats
        with jax.named_scope("ffn.dense"):
            return x + _swiglu(h, pf["w_gate"], pf["w_up"], pf["w_down"]), None

    return _remat(layer, cfg) if cfg.remat else layer


def _bias_rows(cfg: JoyAIConfig, router_bias: "Optional[jax.Array]"):
    """``router_bias [expert layers of the trunk + the module's, n_routed]``
    -> (the trunk's rows, the module's row); a buffer, never differentiated."""
    if router_bias is None:
        return None, None
    rows = jax.lax.stop_gradient(router_bias)
    trunk = sum(1 for kind in layer_kinds(cfg) if kind[1] == "moe")
    return rows[:trunk], (rows[trunk] if cfg.n_predict_layers else None)


def forward_hidden(
    params: Params, tokens: jax.Array, cfg: JoyAIConfig,
    router_bias: "Optional[jax.Array]" = None,
) -> "Tuple[jax.Array, Dict[str, jax.Array]]":
    """tokens ``[B, T]`` -> the trunk's last hidden state ``[B, T, E]``
    (before the final norm) and the routing stats of its expert layers
    (``assignments`` ``[layers, held]``, ``unrouted`` ``[layers]``)."""
    with jax.named_scope("embed"):
        x = _embed(params, tokens, cfg, sharded=False)
    groups = {g: params[g] for g in GROUPS}
    bias, _ = _bias_rows(cfg, router_bias)
    if bias is not None:
        groups["moe"] = dict(groups["moe"], router_bias=bias)
    return _run_layers(x, groups, layer_kinds(cfg), lambda kind: _make_layer(kind, cfg))


def _depth_nll(head: Params, x: jax.Array, targets: jax.Array, cfg: JoyAIConfig,
               tail: int) -> jax.Array:
    """The summed loss of one prediction depth, a row of the batch at a time
    under ``jax.checkpoint`` (as ``models/kimi_linear.py`` ``_head_nll``:
    one row's float32 logits live at once): position ``i`` of ``x`` against
    ``targets[i + 1]``, the last ``tail`` of those pairs left out.  ``head``
    holds this depth's ``final_norm`` and the shared ``head``."""

    def row(acc, xs):
        x_row, tgt_row = xs
        nll = _next_token_nll(_logits(head, x_row[None], cfg), tgt_row[None])
        return acc + nll[:, :nll.shape[1] - tail].sum(), None

    with jax.named_scope("head"):
        total, _ = jax.lax.scan(jax.checkpoint(row), jnp.zeros((), jnp.float32), (x, targets))
    return total


def _module(
    params: Params, x_last: jax.Array, tokens: jax.Array, cfg: JoyAIConfig,
    router_bias: "Optional[jax.Array]",
) -> "Tuple[jax.Array, Dict[str, jax.Array]]":
    """The multi-token-prediction module over all ``T`` positions: its summed
    loss (position ``i`` against token ``i + 2``, ``i <= T - 3``) and its
    layer's routing stats."""
    eps, act = cfg.rms_norm_eps, cfg.dtype
    p = {name: leaf[0] for name, leaf in params["mtp"].items()}
    if router_bias is not None:
        p["router_bias"] = router_bias
    with jax.named_scope("mtp"):
        # position i is given token i + 1; the last position, which has none,
        # the row's first: it is masked out of the loss below
        following = jnp.roll(tokens, -1, axis=1)
        with jax.named_scope("embed"):
            e = _embed(params, following, cfg, sharded=False)

        @jax.checkpoint
        def merge(e, x_last, e_norm, h_norm, w_eh):
            both = jnp.concatenate([_rms_norm(e, e_norm, eps), _rms_norm(x_last, h_norm, eps)], axis=-1)
            return both @ w_eh.astype(act)

        with jax.named_scope("mtp.merge"):
            h = merge(e, x_last, p["e_norm"], p["h_norm"], p["w_eh"])
        y, stats = _make_layer(_EXPERT_LAYER, cfg)(h, p, p)
        total = _depth_nll({"final_norm": p["out_norm"], "head": params["head"]}, y, following, cfg, 1)
    return total, stats


def loss_parts(
    params: Params, tokens: jax.Array, cfg: JoyAIConfig,
    router_bias: "Optional[jax.Array]" = None,
) -> "Tuple[jax.Array, jax.Array, Dict[str, jax.Array]]":
    """``(L_main, L_mtp, routing stats)``: the two prediction depths' mean
    cross-entropies (``L_mtp`` 0 without the module) and the stats of every
    expert layer, the module's last."""
    x, stats = forward_hidden(params, tokens, cfg, router_bias)
    b, t = tokens.shape
    main = _depth_nll(params, x, tokens, cfg, 0) / (b * (t - 1))
    if not cfg.n_predict_layers:
        return main, jnp.zeros((), jnp.float32), stats
    total, own = _module(params, x, tokens, cfg, _bias_rows(cfg, router_bias)[1])
    stats = jax.tree_util.tree_map(lambda s, o: jnp.concatenate([s, o[None]]), stats, own) if stats else \
        jax.tree_util.tree_map(lambda o: o[None], own)
    return main, total / (b * (t - 2)), stats


def forward(
    params: Params, tokens: jax.Array, cfg: JoyAIConfig,
    router_bias: "Optional[jax.Array]" = None,
) -> jax.Array:
    """tokens ``[B, T]`` -> the trunk's logits ``[B, T, vocab]`` (float32)."""
    x, _ = forward_hidden(params, tokens, cfg, router_bias)
    with jax.named_scope("head"):
        return _logits(params, x, cfg)


def loss_fn(
    params: Params, tokens: jax.Array, cfg: JoyAIConfig,
    router_bias: "Optional[jax.Array]" = None,
) -> jax.Array:
    """``L_main + mtp_loss_weight L_mtp``.  No auxiliary loss: the balancing
    rule moves the router's bias instead."""
    main, mtp, _ = loss_parts(params, tokens, cfg, router_bias)
    return main + cfg.mtp_loss_weight * mtp if cfg.n_predict_layers else main


def make_grad_step(cfg: JoyAIConfig, router_bias: "Optional[jax.Array]" = None):
    """A jitted ``(params, tokens) -> (loss, grads)`` step, the FT-DDP shape
    of ``models/transformer.py`` ``make_grad_step``."""

    return jax.jit(_grad_step(lambda p, t: loss_fn(p, t, cfg, router_bias), cfg))


def make_routing_stats(cfg: JoyAIConfig, router_bias: "Optional[jax.Array]" = None):
    """A jitted ``routing_stats(params, tokens)`` (as
    ``models/kimi_linear.py``'s): per expert layer, the module's last, the
    assignments that landed on each held expert and the tokens that found
    none of theirs here.  A forward pass of its own: never inside a timed
    step."""

    def routing_stats(params, tokens):
        return loss_parts(params, tokens, cfg, router_bias)[2]

    return jax.jit(routing_stats)


def record_routing_stats(stats: "Dict[str, Any]", cfg: JoyAIConfig,
                         module_layer: "Optional[int]" = None) -> None:
    """Feeds one batch's ``routing_stats`` to the counters
    (``models/moe.py`` ``record_routing_stats``): layers by their number in
    the model, from 0 as published; the module's layer under
    ``module_layer``, the number it has behind the whole trunk (the
    published ``num_hidden_layers``: a model cut in depth passes it), else
    behind this trunk."""
    layers = [i for i, kind in enumerate(layer_kinds(cfg)) if kind[1] == "moe"]
    if cfg.n_predict_layers:
        layers.append(cfg.n_layers if module_layer is None else module_layer)
    moe.record_routing_stats(stats, layers, cfg.held_experts)


def make_loss_parts(cfg: JoyAIConfig, router_bias: "Optional[jax.Array]" = None):
    """A jitted ``(params, tokens) -> (L_main, L_mtp)``: the two depths'
    losses apart.  A forward pass of its own, for ``record_loss_parts``:
    never inside a training step."""

    def parts(params, tokens):
        return loss_parts(params, tokens, cfg, router_bias)[:2]

    return jax.jit(parts)


def record_loss_parts(parts: "Tuple[Any, Any]", replica_id: str) -> None:
    """Sets the gauge ``torchft_loss_depth{replica_id,depth}`` from one
    batch's ``make_loss_parts`` result: depth ``0`` the next token's loss,
    ``1`` the module's.  ``replica_id``: the Manager's stable id (one value
    per process for the life of the job)."""
    from torchft_tpu.utils import metrics

    for depth, value in enumerate(parts):
        metrics.LOSS_DEPTH.labels(  # tft-lint: allow(metrics-cardinality)
            replica_id=replica_id, depth=str(depth)).set(float(value))


__all__ = [
    "JoyAIConfig",
    "init_params",
    "layer_kinds",
    "forward",
    "loss_fn",
    "loss_parts",
    "make_grad_step",
    "make_routing_stats",
    "make_loss_parts",
    "record_routing_stats",
    "record_loss_parts",
]
