"""A sparse decoder in the Mellum architecture (``model_type`` ``mellum``:
Mellum2-12B-A2.5B): sliding-window attention layers beside global ones in a
published pattern, each kind with a rotary table of its own (plain in the
window layers, YaRN-scaled in the global ones), softmax-routed experts in
every layer with no shared expert and no dense FFN anywhere, an untied output
head.

Every layer is ``x + Attn(RMSNorm(x))`` then ``x + MoE(RMSNorm(x))``: two
norms a layer.  Which attention a layer has comes from the configuration's
``layer_types`` (numbered from 0, as published).

- **Attention**: ``q, k, v = W x`` in ``n_heads`` / ``n_kv_heads`` heads of
  ``head_dim`` (a size of its own: ``d_model / n_heads`` is not it; query head
  ``h`` reads key-value head ``h // (n_heads / n_kv_heads)``); ``q`` and ``k``
  RMS-normalised per head (one weight of ``head_dim`` each); both rotated
  (halves of all of ``head_dim``) by the table of the layer's kind
  (``rope_tables``); a *window* layer (``sliding_attention``) lets query ``i``
  see key ``j`` iff ``0 <= i - j < sliding_window``, a *global* layer
  (``full_attention``) is causal; softmax at ``head_dim ** -0.5`` through the
  flash kernels (``ops/flash_attention.py``: a window walks the band's tiles
  alone); ``W_o``.  No gate.
- **The two tables** (``RopeRule``): ``default`` turns pair ``i`` by ``pos *
  theta ** (-2 i / head_dim)``.  ``yarn`` slows the pairs that turn less than
  ``beta_slow`` times over ``original_length`` positions by ``factor``, leaves
  those that turn more than ``beta_fast`` times as they are, ramps linearly
  between (over the pairs ``low .. high``, the floor and the ceiling of
  ``head_dim ln(original_length / (2 pi beta)) / (2 ln theta)``), and
  multiplies cos and sin by ``attention_factor``, on ``q`` and on ``k`` (the
  logits by its square).  The table does not depend on the row's length.
- **Experts**: ``models/moe.py`` ``held_moe_ffn``, the layer the other sparse
  families run, routed by a softmax over all published experts
  (``score="softmax"``), without a shared expert and with no epsilon in the
  renormalisation: a token none of whose experts lives here gets nothing from
  the FFN.  No auxiliary loss.

The parameters are grouped by kind of layer, each group stacked by layer in
the order the layers come: ``local`` and ``global`` (the attention weights
with ``input_norm``) and ``moe`` (the expert layer's weights with
``post_attention_norm``), beside ``embed``, ``head`` and ``final_norm``.  The
walk over the layers (runs of a repeating pattern, a run of repeats one
``lax.scan``), the head's loss by rows and the untied head are
``models/kimi_linear.py``'s; norm, the rotation, remat and the embedding are
``models/transformer.py``'s.

Single device: the replica dimension lives above jit in the Manager, and the
chips that hold the other experts and layers are not this program's.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from torchft_tpu.models import moe
from torchft_tpu.models.kimi_linear import Kind, _head_nll, _logits, _run_layers
from torchft_tpu.models.moe import HeldMoEConfig, held_moe_ffn, init_held_moe_params
from torchft_tpu.models.transformer import _embed, _grad_step, _remat, _rms_norm, _rotate
from torchft_tpu.ops.ring_attention import dense_attention

Params = Dict[str, Any]
GROUPS = ("local", "global", "moe")
_ATTENTION = {"sliding_attention": "local", "full_attention": "global"}


@dataclasses.dataclass(frozen=True)
class RopeRule:
    """One entry of the published ``rope_parameters``: ``default`` reads
    ``theta`` alone, ``yarn`` the rest too."""

    rope_type: str = "default"
    theta: float = 500000.0
    factor: float = 1.0
    original_length: int = 8192
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0


@dataclasses.dataclass(frozen=True)
class MellumConfig:
    vocab_size: int = 98304
    d_model: int = 2304
    n_layers: int = 28
    layer_types: Tuple[str, ...] = ("sliding_attention",) * 3 + ("full_attention",)
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    sliding_window: int = 1024
    rope_local: RopeRule = RopeRule()
    rope_global: RopeRule = RopeRule("yarn", factor=16.0, attention_factor=1.2772588722239782)
    # the expert layer
    d_expert: int = 896
    n_routed_experts: int = 64
    experts_per_token: int = 8
    held_experts: Tuple[int, ...] = tuple(range(16))
    expert_slack: float = 2.0
    rms_norm_eps: float = 1e-6
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
            slack=self.expert_slack, shared=False, renorm_eps=0.0, score="softmax",
            dtype=self.dtype, param_dtype=self.param_dtype)


def layer_kinds(cfg: MellumConfig) -> "List[Kind]":
    """``(attention, "moe")`` of every layer: ``layer_types`` read cyclically
    (a whole published list holds one entry a layer)."""
    return [(_ATTENTION[cfg.layer_types[i % len(cfg.layer_types)]], "moe") for i in range(cfg.n_layers)]


def rope_tables(cfg: MellumConfig) -> "Dict[str, Tuple[np.ndarray, float]]":
    """``{kind: (inv_freq [head_dim / 2] float32, scale)}``, made in numpy
    when the step is traced: what a position is multiplied by for each pair of
    a window layer's and of a global layer's heads, and what multiplies cos
    and sin there (the module's text has the equations)."""
    d = cfg.head_dim
    pair = np.arange(d // 2, dtype=np.float64)

    def table(rule: RopeRule) -> "Tuple[np.ndarray, float]":
        inv_freq = rule.theta ** (-2.0 * pair / d)
        if rule.rope_type == "default":
            return inv_freq.astype(np.float32), 1.0
        if rule.rope_type != "yarn":
            raise ValueError(f"unknown rope_type {rule.rope_type!r}; expected 'default' or 'yarn'")

        def turns(beta: float) -> float:  # the pair that turns ``beta`` times over the original length
            return d * math.log(rule.original_length / (2 * math.pi * beta)) / (2 * math.log(rule.theta))

        low = max(math.floor(turns(rule.beta_fast)), 0)
        high = min(math.ceil(turns(rule.beta_slow)), d - 1)
        ramp = np.clip((pair - low) / max(high - low, 1e-3), 0.0, 1.0)
        slowed = inv_freq * ((1.0 - ramp) + ramp / rule.factor)
        return slowed.astype(np.float32), float(rule.attention_factor)

    return {"local": table(cfg.rope_local), "global": table(cfg.rope_global)}


def init_params(rng: jax.Array, cfg: MellumConfig) -> Params:
    """The parameter tree (see the module's text)."""
    kinds = layer_kinds(cfg)
    count = {g: sum(1 for kind in kinds if g in kind) for g in GROUPS}
    e, pd = cfg.d_model, cfg.param_dtype
    dq, dkv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    keys = iter(jax.random.split(rng, 12))

    def dense(n, *shape):
        return (jax.random.normal(next(keys), (n,) + shape, pd) / np.sqrt(shape[-2])).astype(pd)

    def attention(n):
        return {
            "input_norm": jnp.ones((n, e), pd), "wq": dense(n, e, dq), "wk": dense(n, e, dkv),
            "wv": dense(n, e, dkv), "q_norm": jnp.ones((n, cfg.head_dim), pd),
            "k_norm": jnp.ones((n, cfg.head_dim), pd), "wo": dense(n, dq, e)}

    return {
        "embed": jax.random.normal(next(keys), (cfg.vocab_size, e), pd) * 0.02,
        "head": dense(1, e, cfg.vocab_size)[0],
        "final_norm": jnp.ones((e,), pd),
        "local": attention(count["local"]), "global": attention(count["global"]),
        "moe": dict(init_held_moe_params(next(keys), cfg.moe(), count["moe"]),
                    post_attention_norm=jnp.ones((count["moe"], e), pd)),
    }


def _attention(h: jax.Array, p: Params, cfg: MellumConfig, kind: str) -> jax.Array:
    b, t, _ = h.shape
    nh, nkv, dh, act = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.dtype
    window = cfg.sliding_window if kind == "local" else None
    inv_freq, scale = rope_tables(cfg)[kind]
    with jax.named_scope("attn.proj"):
        q = (h @ p["wq"].astype(act)).reshape(b, t, nh, dh)
        k = (h @ p["wk"].astype(act)).reshape(b, t, nkv, dh)
        v = (h @ p["wv"].astype(act)).reshape(b, t, nkv, dh)
    with jax.named_scope("attn." + kind):
        q = _rms_norm(q, p["q_norm"], cfg.rms_norm_eps)
        k = _rms_norm(k, p["k_norm"], cfg.rms_norm_eps)
        with jax.named_scope("attn.rope"):
            angles = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq)[None, :]
            q, k = _rotate(q, angles, scale), _rotate(k, angles, scale)
        if cfg.attn_impl == "flash":
            from torchft_tpu.ops.flash_attention import flash_attention

            o = flash_attention(q, k, v, causal=True, window=window)
        elif cfg.attn_impl == "dense":
            o = dense_attention(q, k, v, causal=True, window=window)
        else:
            raise ValueError(f"unknown attn_impl {cfg.attn_impl!r}; expected 'flash' or 'dense'")
    with jax.named_scope("attn.proj"):
        return o.reshape(b, t, nh * dh) @ p["wo"].astype(act)


def _make_layer(kind: Kind, cfg: MellumConfig):
    """``layer(x, attention params, expert params) -> (x, routing stats)`` for
    one layer of this kind, every leaf without its layer dimension."""
    eps = cfg.rms_norm_eps

    def layer(x, pa, pf):
        x = x + _attention(_rms_norm(x, pa["input_norm"], eps), pa, cfg, kind[0])
        y, stats = held_moe_ffn(_rms_norm(x, pf["post_attention_norm"], eps), pf, cfg.moe())
        return x + y, stats

    return _remat(layer, cfg) if cfg.remat else layer


def forward_hidden(
    params: Params, tokens: jax.Array, cfg: MellumConfig,
) -> "Tuple[jax.Array, Dict[str, jax.Array]]":
    """tokens ``[B, T]`` -> the last layer's output ``[B, T, E]`` and the
    routing stats of the layers (``assignments`` ``[layers, held]``,
    ``unrouted`` ``[layers]``)."""
    with jax.named_scope("embed"):
        x = _embed(params, tokens, cfg, sharded=False)
    return _run_layers(x, {g: params[g] for g in GROUPS}, layer_kinds(cfg),
                       lambda kind: _make_layer(kind, cfg))


def forward(params: Params, tokens: jax.Array, cfg: MellumConfig) -> jax.Array:
    """tokens ``[B, T]`` -> logits ``[B, T, vocab]`` (float32)."""
    x, _ = forward_hidden(params, tokens, cfg)
    with jax.named_scope("head"):
        return _logits(params, x, cfg)


def loss_fn(params: Params, tokens: jax.Array, cfg: MellumConfig) -> jax.Array:
    """Next-token cross-entropy, mean over all positions but the last.  No
    auxiliary loss: the published configuration has no coefficient for one."""
    x, _ = forward_hidden(params, tokens, cfg)
    b, t = tokens.shape
    return _head_nll(params, x, tokens, cfg) / (b * (t - 1))


def make_grad_step(cfg: MellumConfig):
    """A jitted ``(params, tokens) -> (loss, grads)`` step, the FT-DDP shape
    of ``models/transformer.py`` ``make_grad_step``."""

    return jax.jit(_grad_step(lambda p, t: loss_fn(p, t, cfg), cfg))


def make_routing_stats(cfg: MellumConfig):
    """A jitted ``routing_stats(params, tokens)`` (as
    ``models/kimi_linear.py``'s): per layer the assignments that landed on
    each held expert (a token up to ``experts_per_token`` times where that
    many of its experts are held) and the tokens that found none of theirs
    here, whose FFN output is zero in this model.  A forward pass of its own:
    never inside a timed step."""

    def routing_stats(params, tokens):
        return forward_hidden(params, tokens, cfg)[1]

    return jax.jit(routing_stats)


def record_routing_stats(stats: "Dict[str, Any]", cfg: MellumConfig) -> None:
    """Feeds one batch's ``routing_stats`` to the counters
    (``models/moe.py`` ``record_routing_stats``): every layer has experts, so
    the rows are the layers' numbers from 0."""
    moe.record_routing_stats(stats, range(cfg.n_layers), cfg.held_experts)


__all__ = [
    "MellumConfig",
    "RopeRule",
    "init_params",
    "layer_kinds",
    "rope_tables",
    "forward",
    "loss_fn",
    "make_grad_step",
    "make_routing_stats",
    "record_routing_stats",
]
