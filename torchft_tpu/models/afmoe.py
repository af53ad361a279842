"""A sparse decoder in the AFMoE architecture (``model_type`` ``afmoe``:
Trinity): sliding-window attention layers beside global ones in a published
pattern, a gate on the attention output, norms on both sides of each
sub-block, leading dense SwiGLU FFNs and then sigmoid-routed experts with a
shared expert, an untied output head.

Every layer is ``x + RMSNorm(Attn(RMSNorm(x)))`` then ``x + RMSNorm(FFN(
RMSNorm(x)))``: four norms a layer.  Which attention a layer has comes from
the configuration's ``layer_types`` (numbered from 0, as published); the
first ``num_dense_layers`` have the dense FFN.

- **Attention**: ``q, k, v = W x`` in ``n_heads`` / ``n_kv_heads`` heads of
  ``head_dim`` (query head ``h`` reads key-value head ``h // (n_heads /
  n_kv_heads)``); ``q`` and ``k`` RMS-normalised per head (one weight of
  ``head_dim`` each); a *window* layer (``sliding_attention``) rotates ``q``
  and ``k`` (rotary over all of ``head_dim``) and lets query ``i`` see key
  ``j`` iff ``0 <= i - j < sliding_window``; a *global* layer
  (``full_attention``) carries no position and is causal; softmax at
  ``head_dim ** -0.5`` through the flash kernels (``ops/flash_attention.py``:
  a window walks the band's tiles alone); the output times ``sigmoid(W_g x)``
  elementwise, then ``W_o``.
- **Experts**: ``models/moe.py`` ``held_moe_ffn``, the layer
  ``models/kimi_linear.py`` runs: the chip's share of a sigmoid-routed,
  dropless layer, told which published experts it holds.
- The embedding is scaled by ``sqrt(d_model)`` (``mup_enabled``).

The parameters are grouped by kind of layer, each group stacked by layer in
the order the layers come: ``local`` and ``global`` (the attention weights
with the two norms around attention), ``dense`` and ``moe`` (the FFN weights
with the two norms around the FFN), beside ``embed``, ``head`` and
``final_norm``.  The walk over the layers (runs of a repeating pattern, a run
of repeats one ``lax.scan``) is ``models/kimi_linear.py``'s, as are the
head's loss by rows and the layout of an expert group; norm, rotary, remat
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
from torchft_tpu.models.kimi_linear import Kind, _head_nll, _logits, _run_layers
from torchft_tpu.models.moe import HeldMoEConfig, held_moe_ffn, init_held_moe_params
from torchft_tpu.models.transformer import _grad_step, _remat, _rms_norm, _rope, _swiglu
from torchft_tpu.ops.ring_attention import dense_attention

Params = Dict[str, Any]
GROUPS = ("local", "global", "dense", "moe")
_ATTENTION = {"sliding_attention": "local", "full_attention": "global"}


@dataclasses.dataclass(frozen=True)
class AfmoeConfig:
    vocab_size: int = 200192
    d_model: int = 2048
    n_layers: int = 32
    layer_types: Tuple[str, ...] = ("sliding_attention",) * 3 + ("full_attention",)
    num_dense_layers: int = 2
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    sliding_window: int = 2048
    rope_theta: float = 10000.0
    mup_enabled: bool = True
    # FFNs
    d_ff: int = 6144
    d_expert: int = 1024
    n_routed_experts: int = 128
    experts_per_token: int = 8
    held_experts: Tuple[int, ...] = tuple(range(8))
    route_scale: float = 2.826
    expert_slack: float = 8.0
    rms_norm_eps: float = 1e-5
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

    def moe(self) -> HeldMoEConfig:
        return HeldMoEConfig(
            d_model=self.d_model, d_expert=self.d_expert, n_routed=self.n_routed_experts,
            top_k=self.experts_per_token, held=tuple(self.held_experts),
            routed_scale=self.route_scale, slack=self.expert_slack,
            dtype=self.dtype, param_dtype=self.param_dtype)


def layer_kinds(cfg: AfmoeConfig) -> "List[Kind]":
    """``(attention, ffn)`` of every layer: ``layer_types`` read cyclically
    (a whole published list holds one entry a layer), the first
    ``num_dense_layers`` with the dense FFN."""
    return [(_ATTENTION[cfg.layer_types[i % len(cfg.layer_types)]],
             "dense" if i < cfg.num_dense_layers else "moe") for i in range(cfg.n_layers)]


def init_params(rng: jax.Array, cfg: AfmoeConfig) -> Params:
    """The parameter tree (see the module's text).  The router's balancing
    bias is no parameter: ``forward_hidden`` takes it as a buffer."""
    kinds = layer_kinds(cfg)
    count = {g: sum(1 for kind in kinds if g in kind) for g in GROUPS}
    e, pd = cfg.d_model, cfg.param_dtype
    dq, dkv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    keys = iter(jax.random.split(rng, 16))

    def dense(n, *shape):
        return (jax.random.normal(next(keys), (n,) + shape, pd) / np.sqrt(shape[-2])).astype(pd)

    def attention(n):
        return {
            "input_norm": jnp.ones((n, e), pd), "post_attn_norm": jnp.ones((n, e), pd),
            "wq": dense(n, e, dq), "wk": dense(n, e, dkv), "wv": dense(n, e, dkv),
            "q_norm": jnp.ones((n, cfg.head_dim), pd), "k_norm": jnp.ones((n, cfg.head_dim), pd),
            "wg": dense(n, e, dq), "wo": dense(n, dq, e),
        }

    def norms(n):
        return {"pre_mlp_norm": jnp.ones((n, e), pd), "post_mlp_norm": jnp.ones((n, e), pd)}

    ld = count["dense"]
    return {
        "embed": jax.random.normal(next(keys), (cfg.vocab_size, e), pd) * 0.02,
        "head": dense(1, e, cfg.vocab_size)[0],
        "final_norm": jnp.ones((e,), pd),
        "local": attention(count["local"]), "global": attention(count["global"]),
        "dense": dict(norms(ld), w_gate=dense(ld, e, cfg.d_ff), w_up=dense(ld, e, cfg.d_ff),
                      w_down=dense(ld, cfg.d_ff, e)),
        "moe": dict(init_held_moe_params(next(keys), cfg.moe(), count["moe"]),
                    **norms(count["moe"])),
    }


def _attention(h: jax.Array, p: Params, cfg: AfmoeConfig, local: bool) -> jax.Array:
    b, t, _ = h.shape
    nh, nkv, dh, act = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.dtype
    window = cfg.sliding_window if local else None
    with jax.named_scope("attn.proj"):
        q = (h @ p["wq"].astype(act)).reshape(b, t, nh, dh)
        k = (h @ p["wk"].astype(act)).reshape(b, t, nkv, dh)
        v = (h @ p["wv"].astype(act)).reshape(b, t, nkv, dh)
        gate = h @ p["wg"].astype(act)
    with jax.named_scope("attn.local" if local else "attn.global"):
        q = _rms_norm(q, p["q_norm"], cfg.rms_norm_eps)
        k = _rms_norm(k, p["k_norm"], cfg.rms_norm_eps)
        if local:  # a global layer carries no position
            positions = jnp.arange(t)
            q, k = _rope(q, positions, cfg.rope_theta), _rope(k, positions, cfg.rope_theta)
        if cfg.attn_impl == "flash":
            from torchft_tpu.ops.flash_attention import flash_attention

            o = flash_attention(q, k, v, causal=True, window=window)
        elif cfg.attn_impl == "dense":
            o = dense_attention(q, k, v, causal=True, window=window)
        else:
            raise ValueError(f"unknown attn_impl {cfg.attn_impl!r}; expected 'flash' or 'dense'")
        o = o.reshape(b, t, nh * dh) * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(act)
    with jax.named_scope("attn.proj"):
        return o @ p["wo"].astype(act)


def _make_layer(kind: Kind, cfg: AfmoeConfig):
    """``layer(x, attention params, ffn params) -> (x, routing stats)`` for
    one layer of this kind, every leaf without its layer dimension."""
    eps = cfg.rms_norm_eps

    def layer(x, pa, pf):
        a = _attention(_rms_norm(x, pa["input_norm"], eps), pa, cfg, kind[0] == "local")
        x = x + _rms_norm(a, pa["post_attn_norm"], eps)
        h = _rms_norm(x, pf["pre_mlp_norm"], eps)
        if kind[1] == "moe":
            f, stats = held_moe_ffn(h, pf, cfg.moe(), router_bias=pf.get("router_bias"))
        else:
            with jax.named_scope("ffn.dense"):
                f, stats = _swiglu(h, pf["w_gate"], pf["w_up"], pf["w_down"]), None
        return x + _rms_norm(f, pf["post_mlp_norm"], eps), stats

    return _remat(layer, cfg) if cfg.remat else layer


def forward_hidden(
    params: Params, tokens: jax.Array, cfg: AfmoeConfig,
    router_bias: "Optional[jax.Array]" = None,
) -> "Tuple[jax.Array, Dict[str, jax.Array]]":
    """tokens ``[B, T]`` -> the last layer's output ``[B, T, E]`` and the
    routing stats of the expert layers (``assignments`` ``[layers, held]``,
    ``unrouted`` ``[layers]``).  ``router_bias`` ``[expert layers,
    n_routed]``: the balancing rule's bias, a buffer (zeros if not given)."""
    with jax.named_scope("embed"):
        # the rows in the parameters' type, scaled, then one rounding
        x = params["embed"][tokens]
        if cfg.mup_enabled:
            x = x * np.sqrt(cfg.d_model).astype(x.dtype)
        x = x.astype(cfg.dtype)
    groups = {g: params[g] for g in GROUPS}
    if router_bias is not None:
        groups["moe"] = dict(groups["moe"], router_bias=jax.lax.stop_gradient(router_bias))
    return _run_layers(x, groups, layer_kinds(cfg), lambda kind: _make_layer(kind, cfg))


def forward(
    params: Params, tokens: jax.Array, cfg: AfmoeConfig,
    router_bias: "Optional[jax.Array]" = None,
) -> jax.Array:
    """tokens ``[B, T]`` -> logits ``[B, T, vocab]`` (float32)."""
    x, _ = forward_hidden(params, tokens, cfg, router_bias)
    with jax.named_scope("head"):
        return _logits(params, x, cfg)


def loss_fn(
    params: Params, tokens: jax.Array, cfg: AfmoeConfig,
    router_bias: "Optional[jax.Array]" = None,
) -> jax.Array:
    """Next-token cross-entropy, mean over all positions but the last.  No
    auxiliary loss: the balancing rule moves the router's bias instead."""
    x, _ = forward_hidden(params, tokens, cfg, router_bias)
    b, t = tokens.shape
    return _head_nll(params, x, tokens, cfg) / (b * (t - 1))


def make_grad_step(cfg: AfmoeConfig, router_bias: "Optional[jax.Array]" = None):
    """A jitted ``(params, tokens) -> (loss, grads)`` step, the FT-DDP shape
    of ``models/transformer.py`` ``make_grad_step``."""

    return jax.jit(_grad_step(lambda p, t: loss_fn(p, t, cfg, router_bias), cfg))


def make_routing_stats(cfg: AfmoeConfig, router_bias: "Optional[jax.Array]" = None):
    """A jitted ``routing_stats(params, tokens)`` (as
    ``models/kimi_linear.py``'s): per expert layer the assignments that landed
    on each held expert and the tokens that found none of theirs here.  A
    forward pass of its own: never inside a timed step."""

    def routing_stats(params, tokens):
        return forward_hidden(params, tokens, cfg, router_bias)[1]

    return jax.jit(routing_stats)


def record_routing_stats(stats: "Dict[str, Any]", cfg: AfmoeConfig) -> None:
    """Feeds one batch's ``routing_stats`` to the counters
    (``models/moe.py`` ``record_routing_stats``): layers by their number in
    the model, from 0 as ``layer_types`` counts them."""
    expert_layers = [i for i, kind in enumerate(layer_kinds(cfg)) if kind[1] == "moe"]
    moe.record_routing_stats(stats, expert_layers, cfg.held_experts)


__all__ = [
    "AfmoeConfig",
    "init_params",
    "layer_kinds",
    "forward",
    "loss_fn",
    "make_grad_step",
    "make_routing_stats",
    "record_routing_stats",
]
