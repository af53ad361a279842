"""Plain reference for the SmolLM2 decoder (Llama architecture, as its
published ``config.json`` and the Hugging Face model code describe it):
forward pass and next-token loss (the gradient is ``jax.grad`` of it) in
straightforward ``jax.numpy``, float32, every matrix product at
``Precision.HIGHEST``.  No kernels, no fused loss, no donated buffers.

It imports nothing from ``torchft_tpu`` and takes nothing the program made.
Weights come from the benchmark (``families/llama_dense.py``) in the layout the
program's loop is handed too: a dict ``{"embed": [V, E], "final_norm": [E],
"blocks": {name: [L, ...]}}`` with ``wq/wk/wv/wo/w_gate/w_up/w_down`` stored
``[in, out]`` and ``attn_norm/mlp_norm`` per layer.

Published facts used: RMSNorm with ``rms_norm_eps`` inside the square root,
rotary embedding in the rotate-half convention with ``rope_theta``, grouped
query attention where query head ``i`` reads key/value head ``i // (heads /
kv_heads)``, SwiGLU ``down(silu(gate(x)) * up(x))``, no biases, the output
head tied to the embedding, loss = mean cross-entropy of position ``t``
predicting token ``t + 1``.

``operand_dtype`` is the knob of the lower-precision control: ``None`` keeps
float32 throughout; ``"bfloat16"`` or ``"float8_e4m3fn"`` rounds both operands
and the result of every matrix product (weights and activations, attention's
included) to that type, accumulating in float32, and rounds the cotangents the
same way in the backward pass — what a run whose compute type is that type
does; norms, softmax and the loss stay float32, as they do in the program.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
_FP8_MAX = 448.0  # largest finite float8_e4m3fn


def _rounder(operand_dtype: Optional[str]) -> Any:
    """``x -> x`` rounded to ``operand_dtype`` and back to float32; the
    cotangent is rounded the same way on the way back.  float8 is scaled per
    tensor to its range, as fp8 training recipes do."""
    if operand_dtype is None:
        return lambda x: x
    lo = jnp.dtype(operand_dtype)

    def rnd(x: jax.Array) -> jax.Array:
        if lo == jnp.dtype(jnp.float8_e4m3fn):
            scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / _FP8_MAX
            # saturate: this type has no infinity, a hair over its range is NaN
            scaled = jnp.clip(x / scale, -_FP8_MAX, _FP8_MAX)
            return scaled.astype(lo).astype(jnp.float32) * scale
        return x.astype(lo).astype(jnp.float32)

    rounded = jax.custom_vjp(rnd)
    rounded.defvjp(lambda x: (rnd(x), None), lambda _, ct: (rnd(ct),))
    return rounded


def _rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x: jax.Array, theta: float) -> jax.Array:
    """x [B, T, H, D]; rotate-half convention."""
    t, d = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, axis=-1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, axis=-1)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def forward(params: Any, tokens: jax.Array, sizes: Dict[str, Any],
            operand_dtype: Optional[str] = None) -> jax.Array:
    """tokens [B, T] int32 -> logits [B, T, V] float32."""
    nh, nkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    hd = sizes["head_dim"]
    eps, theta = sizes["rms_norm_eps"], float(sizes["rope_theta"])
    b, t = tokens.shape

    rnd = _rounder(operand_dtype)

    def mm(eq: str, x: jax.Array, y: jax.Array) -> jax.Array:
        return rnd(jnp.einsum(eq, rnd(x), rnd(y), precision=HIGHEST))

    causal = jnp.tril(jnp.ones((t, t), bool))

    def layer(x: jax.Array, p: Dict[str, jax.Array]) -> Tuple[jax.Array, None]:
        h = _rms_norm(x, p["attn_norm"], eps)
        q = _rope(mm("bte,ef->btf", h, p["wq"]).reshape(b, t, nh, hd), theta)
        k = _rope(mm("bte,ef->btf", h, p["wk"]).reshape(b, t, nkv, hd), theta)
        v = mm("bte,ef->btf", h, p["wv"]).reshape(b, t, nkv, hd)
        k = jnp.repeat(k, nh // nkv, axis=2)
        v = jnp.repeat(v, nh // nkv, axis=2)
        scores = mm("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(hd))
        scores = jnp.where(causal[None, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        attn = mm("bhqk,bkhd->bqhd", probs, v).reshape(b, t, nh * hd)
        x = x + mm("btf,fe->bte", attn, p["wo"])
        h = _rms_norm(x, p["mlp_norm"], eps)
        gated = jax.nn.silu(mm("bte,ef->btf", h, p["w_gate"])) * mm(
            "bte,ef->btf", h, p["w_up"])
        return x + mm("btf,fe->bte", gated, p["w_down"]), None

    x = params["embed"][tokens]
    # checkpoint per layer: the float32 score matrices of 30+ layers would
    # not fit beside the weights otherwise; the arithmetic is unchanged
    x, _ = jax.lax.scan(jax.checkpoint(layer), x, params["blocks"])
    x = _rms_norm(x, params["final_norm"], eps)
    return mm("bte,ve->btv", x, params["embed"])


def loss_fn(params: Any, tokens: jax.Array, sizes: Dict[str, Any],
            operand_dtype: Optional[str] = None) -> jax.Array:
    logits = forward(params, tokens, sizes, operand_dtype)[:, :-1]
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return -picked.mean()
