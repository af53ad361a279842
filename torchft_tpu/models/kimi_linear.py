"""A hybrid decoder in the Kimi Linear architecture: Kimi Delta Attention
(KDA) layers beside latent attention without rotary (MLA, NoPE), a leading
dense SwiGLU FFN and then sigmoid-routed experts with a shared expert, an
untied output head.

Every layer is ``x + Attn(RMSNorm(x))`` then ``x + FFN(RMSNorm(x))``.  Which
attention and which FFN a layer has comes from the configuration's lists
(``kda_layers``, ``full_attn_layers``, numbered from 1; the first
``first_k_dense`` layers have the dense FFN) and from nothing in this file.

- **KDA**: ``q, k, v = SiLU(conv4(W x))`` (depthwise, causal), ``q`` and ``k``
  L2-normalised per head, ``q`` scaled by ``head_dim ** -0.5``; a decay per
  head and key channel ``g = -exp(A_log) softplus(W_fb W_fa x + dt_bias)``; a
  write strength ``beta = sigmoid(W_b x)`` per head; the delta-rule state in
  chunks (``ops/kda.py``); output ``W_o (RMSNorm_head(o) * sigmoid(W_gb W_ga
  x))``.
- **MLA**: ``models/mla.py`` ``mla_attention``, the latent attention
  ``models/joyai.py`` runs too, as this model's configuration has it:
  ``q = W_q x`` with heads of ``nope + rope`` (``q_lora_rank`` null: no
  latent under the query; ``mla_use_nope``: no rotary is applied);
  ``[c; k_pe] = W_kva x``, ``c = RMSNorm(c)``, ``[k_nope; v] = W_kvb c`` per
  head, ``k = [k_nope; k_pe]`` with ``k_pe`` shared by the heads; causal
  softmax at ``(nope + rope) ** -0.5`` through the flash kernels (queries and
  keys of 192 against values of 128).
- **Experts**: ``models/moe.py`` ``held_moe_ffn``: the chip's share of a
  sigmoid-routed, dropless layer, told which published experts it holds.

The parameters are grouped by kind, each group stacked by layer in the order
the layers come (``kda``, ``mla``, ``dense``, ``moe``: the attention groups
hold the attention norm, the FFN groups the FFN norm), beside ``embed``,
``head`` and ``final_norm``.  The stack of layers is cut into runs of a
repeating pattern (``layer_plan``): a run of several repeats is one
``lax.scan`` over the repeats whose body holds one layer of each position of
the pattern, so the published depth of 27 (a dense layer, six times ``KDA KDA
MLA KDA``, then ``KDA MLA``) traces seven layer bodies and not 27.

Norm, embedding, loss, remat and the SwiGLU are ``models/transformer.py``'s.
Single device: the replica dimension lives above jit in the Manager, and the
chips that hold the other experts and layers are not this program's.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from torchft_tpu.models import moe
from torchft_tpu.models.mla import MLAConfig, mla_attention
from torchft_tpu.models.moe import HeldMoEConfig, held_moe_ffn, init_held_moe_params
from torchft_tpu.models.transformer import (
    _embed,
    _grad_step,
    _next_token_nll,
    _remat,
    _rms_norm,
    _swiglu,
)
from torchft_tpu.ops.kda import kda

Params = Dict[str, Any]
Kind = Tuple[str, str]  # (attention, ffn) of one layer
GROUPS = ("kda", "mla", "dense", "moe")


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig:
    vocab_size: int = 163840
    d_model: int = 2304
    n_layers: int = 27
    kda_layers: Tuple[int, ...] = (1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                                   21, 22, 23, 25, 26)
    full_attn_layers: Tuple[int, ...] = (4, 8, 12, 16, 20, 24, 27)
    first_k_dense: int = 1
    n_heads: int = 32
    # KDA
    kda_heads: int = 32
    kda_head_dim: int = 128
    conv_kernel: int = 4
    kda_gate_rank: int = 128   # the width between W_fa / W_fb and W_ga / W_gb
    kda_chunk: int = 64
    # MLA; as published: no latent under the query, no rotary on its 64
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    q_lora_rank: Optional[int] = None
    mla_use_nope: bool = True
    rope_theta: float = 10000.0
    # FFNs
    d_ff: int = 9216
    d_expert: int = 1024
    n_routed_experts: int = 256
    experts_per_token: int = 8
    held_experts: Tuple[int, ...] = tuple(range(8))
    routed_scaling_factor: float = 2.446
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

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    def mla(self) -> MLAConfig:
        return MLAConfig(
            d_model=self.d_model, n_heads=self.n_heads, kv_lora_rank=self.kv_lora_rank,
            qk_nope_head_dim=self.qk_nope_head_dim, qk_rope_head_dim=self.qk_rope_head_dim,
            v_head_dim=self.v_head_dim, q_lora_rank=self.q_lora_rank,
            rope_theta=None if self.mla_use_nope else self.rope_theta,
            rms_norm_eps=self.rms_norm_eps, dtype=self.dtype, param_dtype=self.param_dtype,
            attn_impl=self.attn_impl)

    def moe(self) -> HeldMoEConfig:
        return HeldMoEConfig(
            d_model=self.d_model, d_expert=self.d_expert, n_routed=self.n_routed_experts,
            top_k=self.experts_per_token, held=tuple(self.held_experts),
            routed_scale=self.routed_scaling_factor, slack=self.expert_slack,
            dtype=self.dtype, param_dtype=self.param_dtype)


# ---------------------------------------------------------------------------
# the pattern of layers
# ---------------------------------------------------------------------------


def layer_kinds(cfg: KimiLinearConfig) -> "List[Kind]":
    """``(attention, ffn)`` of every layer, from the configuration's lists."""
    kinds = []
    for layer in range(1, cfg.n_layers + 1):
        in_kda, in_mla = layer in cfg.kda_layers, layer in cfg.full_attn_layers
        if in_kda == in_mla:
            raise ValueError(f"layer {layer} must be in exactly one of kda_layers, full_attn_layers")
        kinds.append(("kda" if in_kda else "mla", "dense" if layer <= cfg.first_k_dense else "moe"))
    return kinds


def layer_plan(kinds: "Sequence[Kind]") -> "List[Tuple[Tuple[Kind, ...], int]]":
    """Cuts the layers into runs ``(pattern, repeats)``: at each point the
    pattern whose repeats (two or more) cover the most layers, the shorter
    pattern on a tie; layers no repeat covers join into one run that is
    walked once."""
    plan: "List[Tuple[Tuple[Kind, ...], int]]" = []
    loose: "List[Kind]" = []
    at, n = 0, len(kinds)
    while at < n:
        best = (0, 0, 0)  # covered, -period, repeats
        for period in range(1, (n - at) // 2 + 1):
            pattern = tuple(kinds[at:at + period])
            repeats = 1
            while tuple(kinds[at + repeats * period:at + (repeats + 1) * period]) == pattern:
                repeats += 1
            if repeats >= 2:
                best = max(best, (repeats * period, -period, repeats))
        if best[0]:
            if loose:
                plan.append((tuple(loose), 1))
                loose = []
            plan.append((tuple(kinds[at:at - best[1]]), best[2]))
            at += best[0]
        else:
            loose.append(kinds[at])
            at += 1
    if loose:
        plan.append((tuple(loose), 1))
    return plan


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init_params(rng: jax.Array, cfg: KimiLinearConfig) -> Params:
    """The parameter tree (see the module's text).  The router's correction
    bias is no parameter: ``forward`` takes it as a buffer."""
    kinds = layer_kinds(cfg)
    count = {g: sum(1 for kind in kinds if g in kind) for g in GROUPS}
    e, pd = cfg.d_model, cfg.param_dtype
    h, dh, r = cfg.kda_heads, cfg.kda_head_dim, cfg.kda_gate_rank
    d = h * dh
    keys = iter(jax.random.split(rng, 32))

    def dense(n, *shape):
        return (jax.random.normal(next(keys), (n,) + shape, pd) / np.sqrt(shape[-2])).astype(pd)

    lk, lm, ld = count["kda"], count["mla"], count["dense"]
    # decays as the published layer starts them: A in [1, 16], a step of 0.001 to 0.1
    a = jax.random.uniform(next(keys), (lk, h), pd, 1.0, 16.0)
    dt = jnp.exp(jax.random.uniform(next(keys), (lk, d), pd, np.log(1e-3), np.log(1e-1)))
    kda = {
        "attn_norm": jnp.ones((lk, e), pd),
        "wq": dense(lk, e, d), "wk": dense(lk, e, d), "wv": dense(lk, e, d),
        "conv_q": dense(lk, d, cfg.conv_kernel) * np.sqrt(d / cfg.conv_kernel),
        "conv_k": dense(lk, d, cfg.conv_kernel) * np.sqrt(d / cfg.conv_kernel),
        "conv_v": dense(lk, d, cfg.conv_kernel) * np.sqrt(d / cfg.conv_kernel),
        "f_a": dense(lk, e, r), "f_b": dense(lk, r, d),
        "a_log": jnp.log(a), "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "b_proj": dense(lk, e, h),
        "g_a": dense(lk, e, r), "g_b": dense(lk, r, d),
        "o_norm": jnp.ones((lk, dh), pd),
        "wo": dense(lk, d, e),
    }
    if cfg.q_lora_rank is None:
        query = {"wq": dense(lm, e, cfg.n_heads * cfg.qk_head_dim)}
    else:
        query = {"q_a": dense(lm, e, cfg.q_lora_rank), "q_norm": jnp.ones((lm, cfg.q_lora_rank), pd),
                 "q_b": dense(lm, cfg.q_lora_rank, cfg.n_heads * cfg.qk_head_dim)}
    mla = {
        "attn_norm": jnp.ones((lm, e), pd), **query,
        "kv_a": dense(lm, e, cfg.kv_lora_rank + cfg.qk_rope_head_dim),
        "kv_norm": jnp.ones((lm, cfg.kv_lora_rank), pd),
        "kv_b": dense(lm, cfg.kv_lora_rank, cfg.n_heads * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
        "wo": dense(lm, cfg.n_heads * cfg.v_head_dim, e),
    }
    ffn = {
        "mlp_norm": jnp.ones((ld, e), pd),
        "w_gate": dense(ld, e, cfg.d_ff), "w_up": dense(ld, e, cfg.d_ff),
        "w_down": dense(ld, cfg.d_ff, e),
    }
    moe = dict(init_held_moe_params(next(keys), cfg.moe(), count["moe"]),
               mlp_norm=jnp.ones((count["moe"], e), pd))
    return {
        "embed": jax.random.normal(next(keys), (cfg.vocab_size, e), pd) * 0.02,
        "head": dense(1, e, cfg.vocab_size)[0],
        "final_norm": jnp.ones((e,), pd),
        "kda": kda, "mla": mla, "dense": ffn, "moe": moe,
    }


# ---------------------------------------------------------------------------
# layers
# ---------------------------------------------------------------------------


def _causal_conv(x: jax.Array, w: jax.Array) -> jax.Array:
    """Depthwise causal convolution: ``x [B, T, D]``, ``w [D, K]``, ``y_t =
    sum_i w[:, i] x_{t - K + 1 + i}`` with ``x_{<0} = 0``, the taps as shifted
    multiply-adds accumulated in float32 (the result's type): no ``[B, T, D,
    K]`` array.  (``models/lfm2.py``'s mixer runs this too.)"""
    taps = w.shape[-1]
    t = x.shape[1]
    padded = jnp.pad(x.astype(jnp.float32), ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(padded[:, i:i + t] * w[:, i].astype(jnp.float32) for i in range(taps))


def _short_conv(x: jax.Array, w: jax.Array) -> jax.Array:
    """``_causal_conv`` then SiLU, in the type of ``x``."""
    return jax.nn.silu(_causal_conv(x, w)).astype(x.dtype)


def _kda_attention(h: jax.Array, p: Params, cfg: KimiLinearConfig) -> jax.Array:
    """The elementwise stages (convolution, normalisation, decay, output gate)
    are each under ``jax.checkpoint``: their float32 insides are recomputed in
    the backward from the projections' outputs in the compute type, which is
    all a layer's backward then holds of them."""
    b, t, _ = h.shape
    nh, dh = cfg.kda_heads, cfg.kda_head_dim
    act, f32 = cfg.dtype, jnp.float32

    def heads(x):
        return x.reshape(b, t, nh, dh)

    @jax.checkpoint
    def conv_unit(x, w, scale):
        x32 = heads(_short_conv(x, w)).astype(f32)
        unit = x32 * jax.lax.rsqrt(jnp.sum(x32 * x32, axis=-1, keepdims=True) + 1e-6)
        return (unit * scale).astype(act)

    @jax.checkpoint
    def log_decay(step, a_log, dt_bias):
        return -jnp.exp(a_log.astype(f32))[:, None] * heads(
            jax.nn.softplus(step.astype(f32) + dt_bias.astype(f32)))

    @jax.checkpoint
    def gated_norm(o, gate, w):
        return _rms_norm(o, w, cfg.rms_norm_eps) * jax.nn.sigmoid(heads(gate).astype(f32)).astype(act)

    with jax.named_scope("kda.proj"):
        q = conv_unit(h @ p["wq"].astype(act), p["conv_q"], dh ** -0.5)
        k = conv_unit(h @ p["wk"].astype(act), p["conv_k"], 1.0)
        v = heads(jax.checkpoint(_short_conv)(h @ p["wv"].astype(act), p["conv_v"]))
        g = log_decay((h @ p["f_a"].astype(act)) @ p["f_b"].astype(act), p["a_log"], p["dt_bias"])
        beta = jax.nn.sigmoid((h @ p["b_proj"].astype(act)).astype(f32))
    with jax.named_scope("kda"):
        # the whole batch: the kernels keep a chunk's insides in VMEM (where
        # the op falls back to the XLA form it walks the rows itself)
        o = kda(q, k, v, g, beta, chunk=cfg.kda_chunk)
    with jax.named_scope("kda.proj"):
        o = gated_norm(o, (h @ p["g_a"].astype(act)) @ p["g_b"].astype(act), p["o_norm"])
        return o.reshape(b, t, nh * dh) @ p["wo"].astype(act)


def _mla_attention(h: jax.Array, p: Params, cfg: KimiLinearConfig) -> jax.Array:
    return mla_attention(h, p, cfg.mla())


def _make_layer(kind: Kind, cfg: KimiLinearConfig):
    """``layer(x, attention params, ffn params) -> (x, routing stats)`` for
    one layer of this kind, every leaf without its layer dimension."""
    attention = {"kda": _kda_attention, "mla": _mla_attention}[kind[0]]
    eps = cfg.rms_norm_eps

    def layer(x, pa, pf):
        x = x + attention(_rms_norm(x, pa["attn_norm"], eps), pa, cfg)
        h = _rms_norm(x, pf["mlp_norm"], eps)
        if kind[1] == "moe":
            y, stats = held_moe_ffn(h, pf, cfg.moe(), router_bias=pf.get("router_bias"))
            return x + y, stats
        with jax.named_scope("ffn.dense"):
            return x + _swiglu(h, pf["w_gate"], pf["w_up"], pf["w_down"]), None

    return _remat(layer, cfg) if cfg.remat else layer


def _cut(leaf: jax.Array, bounds: "Tuple[int, ...]") -> "Tuple[jax.Array, ...]":
    """A stacked leaf cut at ``bounds`` into the runs of ``layer_plan``.  Its
    own backward concatenates the runs' gradients once; differentiating the
    slices would pad each run's gradient to the whole stack and add them."""

    @jax.custom_vjp
    def cut(x):
        return tuple(x[lo:hi] for lo, hi in zip(bounds, bounds[1:]))

    cut.defvjp(lambda x: (cut(x), None), lambda _, cts: (jnp.concatenate(cts),))
    return cut(leaf)


@jax.custom_vjp
def _unstack(leaf: jax.Array) -> "Tuple[jax.Array, ...]":
    """``[c, ...] -> c`` arrays, the gradients stacked once on the way back
    (as ``_cut``: no padding of each to the whole and adding)."""
    return tuple(leaf[i] for i in range(leaf.shape[0]))


_unstack.defvjp(lambda leaf: (_unstack(leaf), None), lambda _, cts: (jnp.stack(cts),))


def _run_layers(
    x: jax.Array, groups: "Dict[str, Params]", kinds: "Sequence[Kind]",
    make_layer: "Callable[[Kind], Any]",
) -> "Tuple[jax.Array, Dict[str, jax.Array]]":
    """Walks ``layer_plan(kinds)``.  ``groups`` are the stacked parameter
    groups by the names the kinds use (an expert group may carry the
    ``router_bias`` buffer); ``make_layer(kind)`` gives ``layer(x, attention
    params, ffn params) -> (x, routing stats or None)``.  Returns the routing
    stats of the expert layers stacked in layer order.  (``models/afmoe.py``
    walks its layers through this too.)"""
    names = tuple(groups)
    stats: "List[Dict[str, jax.Array]]" = []

    def walk(x, pattern, taken):
        """One pass over ``pattern``; ``taken[g]`` holds this pass's layers
        of group ``g`` stacked on the first dimension."""
        seen = {g: 0 for g in names}
        found = []
        layers = {g: {name: _unstack(leaf) for name, leaf in taken[g].items() if leaf.shape[0]}
                  for g in names}
        for kind in pattern:
            pick = [{name: ls[seen[g]] for name, ls in layers[g].items()} for g in kind]
            for g in kind:
                seen[g] += 1
            x, st = make_layer(kind)(x, *pick)
            if st is not None:
                found.append(st)
        stacked = jax.tree_util.tree_map(lambda *s: jnp.stack(s), *found) if found else None
        return x, stacked

    plan = layer_plan(kinds)
    bounds = {g: (0,) for g in names}
    for pattern, repeats in plan:
        for g in names:
            bounds[g] += (bounds[g][-1] + repeats * sum(1 for kind in pattern if g in kind),)
    runs = {g: jax.tree_util.tree_map(lambda leaf, g=g: _cut(leaf, bounds[g]), groups[g])
            for g in names}
    for at, (pattern, repeats) in enumerate(plan):
        taken = {
            g: jax.tree_util.tree_map(
                lambda run, c=sum(1 for kind in pattern if g in kind): run[at].reshape(
                    (repeats, c) + run[at].shape[1:]),
                runs[g], is_leaf=lambda x: isinstance(x, tuple))
            for g in names}
        if repeats == 1:
            x, st = walk(x, pattern, jax.tree_util.tree_map(lambda leaf: leaf[0], taken))
        else:
            x, st = jax.lax.scan(lambda x, xs: walk(x, pattern, xs), x, taken)
            if st is not None:
                st = jax.tree_util.tree_map(lambda s: s.reshape((-1,) + s.shape[2:]), st)
        if st is not None:
            stats.append(st)
    merged = jax.tree_util.tree_map(lambda *s: jnp.concatenate(s), *stats) if stats else {}
    return x, merged


def _groups(params: Params, router_bias: "Optional[jax.Array]") -> "Dict[str, Params]":
    groups = {g: params[g] for g in GROUPS}
    if router_bias is not None:
        groups["moe"] = dict(groups["moe"], router_bias=jax.lax.stop_gradient(router_bias))
    return groups


def _logits(params: Params, x: jax.Array, cfg: KimiLinearConfig) -> jax.Array:
    """Final norm and the untied head: ``[B, T, E] -> [B, T, V]`` float32, the
    product in the compute type (as ``models/transformer.py`` ``_head``)."""
    h = _rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    return jnp.einsum("bte,ev->btv", h.astype(cfg.dtype), params["head"].astype(cfg.dtype),
                      preferred_element_type=jnp.float32)


def _head_nll(params: Params, x: jax.Array, tokens: jax.Array, cfg: Any,
              logits: "Callable[..., jax.Array]" = _logits) -> jax.Array:
    """The summed next-token loss, a row of the batch at a time under
    ``jax.checkpoint``: the float32 logits of one row live at once, not the
    batch's.  ``logits(params, x, cfg)``: the model's final norm and head (a
    tied head is ``models/lfm2.py``'s)."""

    def row(acc, xs):
        x_row, tok_row = xs
        return acc + _next_token_nll(logits(params, x_row[None], cfg), tok_row[None]).sum(), None

    with jax.named_scope("head"):
        total, _ = jax.lax.scan(jax.checkpoint(row), jnp.zeros((), jnp.float32), (x, tokens))
    return total


def forward_hidden(
    params: Params, tokens: jax.Array, cfg: KimiLinearConfig,
    router_bias: "Optional[jax.Array]" = None,
) -> "Tuple[jax.Array, Dict[str, jax.Array]]":
    """tokens ``[B, T]`` -> the last layer's output ``[B, T, E]`` and the
    routing stats of the expert layers (``assignments`` ``[layers, held]``,
    ``unrouted`` ``[layers]``).  ``router_bias`` ``[expert layers,
    n_routed]``: the router's correction bias, a buffer (zeros if not
    given)."""
    with jax.named_scope("embed"):
        x = _embed(params, tokens, cfg, sharded=False)
    return _run_layers(x, _groups(params, router_bias), layer_kinds(cfg),
                       lambda kind: _make_layer(kind, cfg))


def forward(
    params: Params, tokens: jax.Array, cfg: KimiLinearConfig,
    router_bias: "Optional[jax.Array]" = None,
) -> jax.Array:
    """tokens ``[B, T]`` -> logits ``[B, T, vocab]`` (float32)."""
    x, _ = forward_hidden(params, tokens, cfg, router_bias)
    with jax.named_scope("head"):
        return _logits(params, x, cfg)


def loss_fn(
    params: Params, tokens: jax.Array, cfg: KimiLinearConfig,
    router_bias: "Optional[jax.Array]" = None,
) -> jax.Array:
    """Next-token cross-entropy, mean over all positions but the last.  No
    auxiliary loss: the balancing rule moves the router's bias instead."""
    x, _ = forward_hidden(params, tokens, cfg, router_bias)
    b, t = tokens.shape
    return _head_nll(params, x, tokens, cfg) / (b * (t - 1))


def make_grad_step(cfg: KimiLinearConfig, router_bias: "Optional[jax.Array]" = None):
    """A jitted ``(params, tokens) -> (loss, grads)`` step, the FT-DDP shape
    of ``models/transformer.py`` ``make_grad_step``."""

    return jax.jit(_grad_step(lambda p, t: loss_fn(p, t, cfg, router_bias), cfg))


def make_routing_stats(cfg: KimiLinearConfig, router_bias: "Optional[jax.Array]" = None):
    """A jitted ``routing_stats(params, tokens)``: per expert layer, how many
    of the batch's ``N k`` assignments landed on each held expert
    (``assignments`` ``[layers, held]``) and how many tokens found none of
    their experts here (``unrouted`` ``[layers]``).  A forward pass of its
    own: call it beside the training step, never inside a timed one."""

    def routing_stats(params, tokens):
        return forward_hidden(params, tokens, cfg, router_bias)[1]

    return jax.jit(routing_stats)


def record_routing_stats(stats: "Dict[str, Any]", cfg: KimiLinearConfig) -> None:
    """Feeds one batch's ``routing_stats`` to the ``utils/metrics`` counters
    ``torchft_moe_assignments_total{layer,expert}`` and
    ``torchft_moe_tokens_unrouted_total{layer}`` (layers by their number in
    the model, experts by their published id)."""
    expert_layers = [i + 1 for i, kind in enumerate(layer_kinds(cfg)) if kind[1] == "moe"]
    moe.record_routing_stats(stats, expert_layers, cfg.held_experts)


__all__ = [
    "KimiLinearConfig",
    "init_params",
    "layer_kinds",
    "layer_plan",
    "forward",
    "loss_fn",
    "make_grad_step",
    "make_routing_stats",
    "record_routing_stats",
]
