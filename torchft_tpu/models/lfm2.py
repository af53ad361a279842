"""A sparse decoder in the LFM2-MoE architecture (``model_type``
``lfm2_moe``: LFM2-8B-A1B): gated short convolutions as the token mixer of
three layers in four, grouped-query attention in the fourth, leading dense
SwiGLU FFNs and then sigmoid-routed experts with no shared expert, the output
head tied to the embedding.

Every layer is ``x + Op(RMSNorm(x))`` then ``x + FFN(RMSNorm(x))``.  Which
operator a layer has comes from the configuration's ``layer_types`` (numbered
from 0, as published); the first ``num_dense_layers`` have the dense FFN.

- **conv**: ``[B | C | u] = h W_in`` (``d -> 3 d``, split in that order);
  ``z = B * u``; ``c_t = sum_j w[:, j] z_{t - (L - 1) + j}`` with ``z_{<0} =
  0`` (depthwise, ``conv_L_cache`` taps, causal: the last tap is the current
  position); ``y = (C * c) W_out``.  No activation inside, no bias.
- **full_attention**: ``q, k, v = W h`` in ``n_heads`` / ``n_kv_heads`` heads
  of ``head_dim`` (query head ``i`` reads key-value head ``i // (n_heads /
  n_kv_heads)``); ``q`` and ``k`` RMS-normalised per head (one weight of
  ``head_dim`` each); both rotated (halves of all of ``head_dim``,
  ``rope_theta``); causal softmax at ``head_dim ** -0.5`` through the flash
  kernels; ``W_o``.  No gate.
- **Experts**: ``models/moe.py`` ``held_moe_ffn``, the layer the other sparse
  families run, without its shared expert and with this model's epsilon in
  the renormalisation: a token none of whose experts lives here gets nothing
  from the FFN.

The parameters are grouped by kind of layer, each group stacked by layer in
the order the layers come: ``conv`` and ``attn`` (the operator's weights with
``operator_norm``), ``dense`` and ``moe`` (the FFN's weights with
``ffn_norm``), beside ``embed`` and ``embedding_norm`` (the final norm; the
head is ``embed`` transposed).  The walk over the layers (runs of a repeating
pattern, a run of repeats one ``lax.scan``), the head's loss by rows and the
depthwise convolution are ``models/kimi_linear.py``'s; norm, rotary, remat
and the SwiGLU are ``models/transformer.py``'s.

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
from torchft_tpu.models.kimi_linear import Kind, _causal_conv, _head_nll, _run_layers
from torchft_tpu.models.moe import HeldMoEConfig, held_moe_ffn, init_held_moe_params
from torchft_tpu.models.transformer import _embed, _grad_step, _remat, _rms_norm, _rope, _swiglu
from torchft_tpu.ops.ring_attention import dense_attention

Params = Dict[str, Any]
GROUPS = ("conv", "attn", "dense", "moe")
_OPERATOR = {"conv": "conv", "full_attention": "attn"}
# added to the chosen scores' sum before they are renormalised (the modelling
# code's constant; ``config.json`` has no key for it)
_ROUTER_EPS = 1e-6
_PUBLISHED_LAYERS = ("conv", "conv", "full_attention") + ("conv", "conv", "conv", "full_attention") * 4 + (
    "conv", "conv", "full_attention", "conv", "conv")


@dataclasses.dataclass(frozen=True)
class Lfm2Config:
    vocab_size: int = 65536
    d_model: int = 2048
    n_layers: int = 24
    layer_types: Tuple[str, ...] = _PUBLISHED_LAYERS
    num_dense_layers: int = 2
    # the convolution operator
    conv_taps: int = 3
    # the attention operator
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: int = 64
    rope_theta: float = 1_000_000.0
    # FFNs
    d_ff: int = 7168
    d_expert: int = 1792
    n_routed_experts: int = 32
    experts_per_token: int = 4
    held_experts: Tuple[int, ...] = tuple(range(8))
    routed_scaling_factor: float = 1.0
    expert_slack: float = 2.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    # as ``TransformerConfig.remat_policy``: "full" keeps a layer's input and,
    # of a layer through the flash kernels, the forward kernel's two results;
    # "dots" keeps matrix products
    remat_policy: str = "full"
    # "flash" (ops/flash_attention.py; T % 128 == 0) or "dense"
    attn_impl: str = "flash"

    def moe(self) -> HeldMoEConfig:
        return HeldMoEConfig(
            d_model=self.d_model, d_expert=self.d_expert, n_routed=self.n_routed_experts,
            top_k=self.experts_per_token, held=tuple(self.held_experts),
            routed_scale=self.routed_scaling_factor, slack=self.expert_slack, shared=False,
            renorm_eps=_ROUTER_EPS, dtype=self.dtype, param_dtype=self.param_dtype)


def layer_kinds(cfg: Lfm2Config) -> "List[Kind]":
    """``(operator, ffn)`` of every layer: ``layer_types`` read cyclically (a
    whole published list holds one entry a layer), the first
    ``num_dense_layers`` with the dense FFN."""
    return [(_OPERATOR[cfg.layer_types[i % len(cfg.layer_types)]],
             "dense" if i < cfg.num_dense_layers else "moe") for i in range(cfg.n_layers)]


def init_params(rng: jax.Array, cfg: Lfm2Config) -> Params:
    """The parameter tree (see the module's text).  The router's expert bias
    is no parameter: ``forward_hidden`` takes it as a buffer."""
    kinds = layer_kinds(cfg)
    count = {g: sum(1 for kind in kinds if g in kind) for g in GROUPS}
    e, pd = cfg.d_model, cfg.param_dtype
    dq, dkv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    keys = iter(jax.random.split(rng, 16))

    def dense(n, *shape, fan_in=None):
        return (jax.random.normal(next(keys), (n,) + shape, pd) / np.sqrt(fan_in or shape[-2])).astype(pd)

    lc, la, ld = count["conv"], count["attn"], count["dense"]
    return {
        "embed": jax.random.normal(next(keys), (cfg.vocab_size, e), pd) * 0.02,
        "embedding_norm": jnp.ones((e,), pd),
        "conv": {
            "operator_norm": jnp.ones((lc, e), pd), "w_in": dense(lc, e, 3 * e),
            "conv": dense(lc, e, cfg.conv_taps, fan_in=cfg.conv_taps), "w_out": dense(lc, e, e)},
        "attn": {
            "operator_norm": jnp.ones((la, e), pd), "wq": dense(la, e, dq), "wk": dense(la, e, dkv),
            "wv": dense(la, e, dkv), "q_layernorm": jnp.ones((la, cfg.head_dim), pd),
            "k_layernorm": jnp.ones((la, cfg.head_dim), pd), "wo": dense(la, dq, e)},
        "dense": {"ffn_norm": jnp.ones((ld, e), pd), "w_gate": dense(ld, e, cfg.d_ff),
                  "w_up": dense(ld, e, cfg.d_ff), "w_down": dense(ld, cfg.d_ff, e)},
        "moe": dict(init_held_moe_params(next(keys), cfg.moe(), count["moe"]),
                    ffn_norm=jnp.ones((count["moe"], e), pd)),
    }


def short_conv_mixer(h: jax.Array, p: Params, cfg: Lfm2Config) -> jax.Array:
    """The gated short convolution, ``[B, T, d] -> [B, T, d]``: two matrix
    products (``shortconv.proj``) around the chain gate, taps, gate
    (``shortconv.mix``).  The chain is under ``jax.checkpoint``: its float32
    insides are recomputed in the backward from the first product's output in
    the compute type, which is all a layer's backward then holds of them."""
    act = cfg.dtype

    @jax.checkpoint
    def mix(bcu, w):
        b_gate, c_gate, u = jnp.split(bcu, 3, axis=-1)
        return (c_gate.astype(jnp.float32) * _causal_conv(b_gate * u, w)).astype(act)

    with jax.named_scope("shortconv"):
        with jax.named_scope("shortconv.proj"):
            bcu = h @ p["w_in"].astype(act)
        with jax.named_scope("shortconv.mix"):
            y = mix(bcu, p["conv"])
        with jax.named_scope("shortconv.proj"):
            return y @ p["w_out"].astype(act)


def _attention(h: jax.Array, p: Params, cfg: Lfm2Config) -> jax.Array:
    b, t, _ = h.shape
    nh, nkv, dh, act = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.dtype
    with jax.named_scope("attn.proj"):
        q = (h @ p["wq"].astype(act)).reshape(b, t, nh, dh)
        k = (h @ p["wk"].astype(act)).reshape(b, t, nkv, dh)
        v = (h @ p["wv"].astype(act)).reshape(b, t, nkv, dh)
    with jax.named_scope("attn"):
        positions = jnp.arange(t)
        q = _rope(_rms_norm(q, p["q_layernorm"], cfg.norm_eps), positions, cfg.rope_theta)
        k = _rope(_rms_norm(k, p["k_layernorm"], cfg.norm_eps), positions, cfg.rope_theta)
        if cfg.attn_impl == "flash":
            from torchft_tpu.ops.flash_attention import flash_attention

            o = flash_attention(q, k, v, causal=True)
        elif cfg.attn_impl == "dense":
            o = dense_attention(q, k, v, causal=True)
        else:
            raise ValueError(f"unknown attn_impl {cfg.attn_impl!r}; expected 'flash' or 'dense'")
    with jax.named_scope("attn.proj"):
        return o.reshape(b, t, nh * dh) @ p["wo"].astype(act)


def _make_layer(kind: Kind, cfg: Lfm2Config):
    """``layer(x, operator params, ffn params) -> (x, routing stats)`` for one
    layer of this kind, every leaf without its layer dimension."""
    operator = {"conv": short_conv_mixer, "attn": _attention}[kind[0]]
    eps = cfg.norm_eps

    def layer(x, po, pf):
        x = x + operator(_rms_norm(x, po["operator_norm"], eps), po, cfg)
        h = _rms_norm(x, pf["ffn_norm"], eps)
        if kind[1] == "moe":
            y, stats = held_moe_ffn(h, pf, cfg.moe(), router_bias=pf.get("router_bias"))
            return x + y, stats
        with jax.named_scope("ffn.dense"):
            return x + _swiglu(h, pf["w_gate"], pf["w_up"], pf["w_down"]), None

    return _remat(layer, cfg) if cfg.remat else layer


def forward_hidden(
    params: Params, tokens: jax.Array, cfg: Lfm2Config,
    router_bias: "Optional[jax.Array]" = None,
) -> "Tuple[jax.Array, Dict[str, jax.Array]]":
    """tokens ``[B, T]`` -> the last layer's output ``[B, T, E]`` and the
    routing stats of the expert layers (``assignments`` ``[layers, held]``,
    ``unrouted`` ``[layers]``).  ``router_bias`` ``[expert layers,
    n_routed]``: the expert bias (``use_expert_bias``), a buffer the
    balancing rule moves and never the gradient (zeros if not given)."""
    with jax.named_scope("embed"):
        x = _embed(params, tokens, cfg, sharded=False)
    groups = {g: params[g] for g in GROUPS}
    if router_bias is not None:
        groups["moe"] = dict(groups["moe"], router_bias=jax.lax.stop_gradient(router_bias))
    return _run_layers(x, groups, layer_kinds(cfg), lambda kind: _make_layer(kind, cfg))


def _logits(params: Params, x: jax.Array, cfg: Lfm2Config) -> jax.Array:
    """Final norm and the tied head: ``[B, T, E] -> [B, T, V]`` float32, the
    product in the compute type (as ``models/transformer.py`` ``_head``)."""
    h = _rms_norm(x, params["embedding_norm"], cfg.norm_eps)
    return jnp.einsum("bte,ve->btv", h.astype(cfg.dtype), params["embed"].astype(cfg.dtype),
                      preferred_element_type=jnp.float32)


def forward(
    params: Params, tokens: jax.Array, cfg: Lfm2Config,
    router_bias: "Optional[jax.Array]" = None,
) -> jax.Array:
    """tokens ``[B, T]`` -> logits ``[B, T, vocab]`` (float32)."""
    x, _ = forward_hidden(params, tokens, cfg, router_bias)
    with jax.named_scope("head"):
        return _logits(params, x, cfg)


def loss_fn(
    params: Params, tokens: jax.Array, cfg: Lfm2Config,
    router_bias: "Optional[jax.Array]" = None,
) -> jax.Array:
    """Next-token cross-entropy, mean over all positions but the last.  No
    auxiliary loss: the balancing rule moves the expert bias instead."""
    x, _ = forward_hidden(params, tokens, cfg, router_bias)
    b, t = tokens.shape
    return _head_nll(params, x, tokens, cfg, _logits) / (b * (t - 1))


def make_grad_step(cfg: Lfm2Config, router_bias: "Optional[jax.Array]" = None):
    """A jitted ``(params, tokens) -> (loss, grads)`` step, the FT-DDP shape
    of ``models/transformer.py`` ``make_grad_step``."""

    return jax.jit(_grad_step(lambda p, t: loss_fn(p, t, cfg, router_bias), cfg))


def make_routing_stats(cfg: Lfm2Config, router_bias: "Optional[jax.Array]" = None):
    """A jitted ``routing_stats(params, tokens)`` (as
    ``models/kimi_linear.py``'s): per expert layer the assignments that landed
    on each held expert and the tokens that found none of theirs here, whose
    FFN output is zero in this model.  A forward pass of its own: never inside
    a timed step."""

    def routing_stats(params, tokens):
        return forward_hidden(params, tokens, cfg, router_bias)[1]

    return jax.jit(routing_stats)


def record_routing_stats(stats: "Dict[str, Any]", cfg: Lfm2Config) -> None:
    """Feeds one batch's ``routing_stats`` to the counters
    (``models/moe.py`` ``record_routing_stats``): layers by their number in
    the model, from 0 as ``layer_types`` counts them."""
    expert_layers = [i for i, kind in enumerate(layer_kinds(cfg)) if kind[1] == "moe"]
    moe.record_routing_stats(stats, expert_layers, cfg.held_experts)


__all__ = [
    "Lfm2Config",
    "init_params",
    "layer_kinds",
    "short_conv_mixer",
    "forward",
    "loss_fn",
    "make_grad_step",
    "make_routing_stats",
    "record_routing_stats",
]
