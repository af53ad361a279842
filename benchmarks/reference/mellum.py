"""Plain reference for the Mellum decoder (``model_type`` ``mellum``:
Mellum2-12B-A2.5B, as its published ``config.json`` describes it and, where
that has no key, as the Qwen3-MoE family whose key set it carries has it; the
configuration file lists each such size under ``assumed``): forward pass and
next-token loss in straightforward ``jax.numpy``, float32, every matrix
product at ``Precision.HIGHEST``.  No kernels, no band of tiles, no gathering
of tokens by expert.

It imports nothing from ``torchft_tpu`` and takes nothing the program made.
Weights come from the benchmark (``families/mellum.py``) in the layout the
program's loop is handed too: ``embed [V, E]``, ``head [E, V]``, ``final_norm
[E]`` and three groups stacked by layer in the order the layers come:
``local`` and ``global`` (attention, each with ``input_norm``) and ``moe``
(the expert layer with ``post_attention_norm``); matrices are stored ``[in,
out]``.

**The layers**, numbered from 0 as ``layer_types`` numbers them (those beyond
``num_hidden_layers`` lie on other chips).  RMSNorm has a weight and
``rms_norm_eps`` (1e-6) inside the square root; no bias anywhere
(``attention_bias`` false); ``hidden_act`` silu.

    x0 = embed[tokens]                                          (no scale)
    a  = rms(x; input_norm)
    q  = rms_head(a Wq -> [32 heads, head_dim 128]; q_norm)     (2304 -> 4096: head_dim is a
    k  = rms_head(a Wk -> [4 kv heads, 128]; k_norm)   v = a Wv  key of its own, 2304 / 32 is not it)
    q, k = q cos + rotate_half(q) sin, k cos + rotate_half(k) sin, over all 128,
           cos, sin of pos * inv_freq by the table of the layer's kind:
      sliding_attention (rope_type default):
           inv_freq_i = rope_theta^(-2 i / 128), i = 0..63;
           key j is seen by query t iff 0 <= t - j < sliding_window (1024)
      full_attention (rope_type yarn; factor 16, original_max_position_embeddings
           8192, beta_fast 32, beta_slow 1, attention_factor 1.2772588722239782
           = 0.1 ln 16 + 1, truncate true):
           c(n)   = 128 ln(8192 / (2 pi n)) / (2 ln rope_theta)
           low    = max(floor(c(beta_fast)), 0) = 18,  high = min(ceil(c(beta_slow)), 127) = 35
           ramp_i = clip((i - low) / (high - low), 0, 1)
           inv_freq_i = rope_theta^(-2 i / 128) / 16 * ramp_i + rope_theta^(-2 i / 128) * (1 - ramp_i)
           cos and sin are multiplied by attention_factor, on q and on k (so the
           logits by its square, 1.63); key j is seen by query t iff j <= t.
           The table does not depend on the row's length ("yarn", not "dynamic").
    o  = softmax(q k^T / sqrt(128)) v      (key-value head h serves query heads
                                            8 h .. 8 h + 7); no gate
    x  = x + o Wo                                               (4096 -> 2304)
    m  = rms(x; post_attention_norm)
    p  = softmax(m Wr) over all ``router_outputs`` (64) published experts;
         chosen = the ``num_experts_per_tok`` (8) largest of p;
         g = p[chosen] / sum p[chosen]          (norm_topk_prob; no epsilon, no bias, no scale)
    x  = x + sum over the chosen experts that live here (``held_expert_ids``)
             of g_e SwiGLU_e(m)                 (experts of 896; no shared expert)

then a final RMSNorm, an untied head, and the mean cross-entropy of position
``t`` predicting token ``t + 1`` over the rows of the vocabulary held here.
What the absent experts would add is left out: a token none of whose eight
experts lives here gets nothing from that FFN.  No capacity, no drop, no
auxiliary loss (``config.json`` has no coefficient for one).

**Departures**: the three cuts the configuration file lists (layers, experts
held, vocabulary rows), and no multi-token-prediction head: the catalog's
summary names one, ``config.json`` has no key for one and the published
parameter count (12,149,923,072 by these layers, 2,439,060,736 active) has no
room for one.  ``intermediate_size``, ``max_window_layers``,
``use_sliding_window`` and ``max_position_embeddings`` are read by no layer.

**To fit one row beside 24 bytes a parameter** a layer is under
``jax.checkpoint`` with its weights cut from their stacks inside, the score
matrix (``[T, T]`` with an explicit mask) is formed a head at a time under
its own checkpoint, an expert's part is under its own checkpoint and the
head's loss is taken in blocks of positions.  None changes a number.

``operand_dtype`` is the knob of the lower-precision control, as in
``smollm2.py``: both operands and the result of every matrix product but the
router's are rounded to that type (and the cotangents on the way back);
norms, softmax, the rotation and the loss stay float32.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.smollm2 import HIGHEST, _rms_norm, _rounder

_LOSS_BLOCK = 1024
_KIND = {"sliding_attention": "local", "full_attention": "global"}


def rope_table(rule: Dict[str, Any], head_dim: int) -> Tuple[np.ndarray, float]:
    """``(inv_freq [head_dim / 2], what multiplies cos and sin)`` of one entry
    of ``rope_parameters``, as the text above writes it out."""
    pairs = head_dim // 2
    plain = np.array([float(rule["rope_theta"]) ** (-2.0 * i / head_dim) for i in range(pairs)])
    if rule["rope_type"] == "default":
        return plain, 1.0
    assert rule["rope_type"] == "yarn", rule["rope_type"]

    def c(turns: float) -> float:
        return head_dim * math.log(rule["original_max_position_embeddings"] / (2 * math.pi * turns)) / (
            2 * math.log(rule["rope_theta"]))

    low = max(math.floor(c(rule["beta_fast"])), 0)
    high = min(math.ceil(c(rule["beta_slow"])), head_dim - 1)
    ramp = np.array([min(max((i - low) / (high - low), 0.0), 1.0) for i in range(pairs)])
    return plain / rule["factor"] * ramp + plain * (1.0 - ramp), float(rule["attention_factor"])


def _rope(x: jax.Array, inv_freq: np.ndarray, factor: float) -> jax.Array:
    """x [B, T, H, D]; rotate-half convention, cos and sin times ``factor``."""
    t, d = x.shape[1], x.shape[-1]
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq, jnp.float32)[None, :]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, axis=-1)[None, :, None, :] * factor
    sin = jnp.concatenate([jnp.sin(angles)] * 2, axis=-1)[None, :, None, :] * factor
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def loss_fn(params: Any, tokens: jax.Array, sizes: Dict[str, Any],
            operand_dtype: Optional[str] = None) -> jax.Array:
    eps = sizes["rms_norm_eps"]
    nh, nkv, hd = sizes["num_attention_heads"], sizes["num_key_value_heads"], sizes["head_dim"]
    top_k, held = sizes["num_experts_per_tok"], sizes["held_expert_ids"]
    b, t = tokens.shape
    rnd = _rounder(operand_dtype)
    tables = {_KIND[name]: rope_table(rule, hd) for name, rule in sizes["rope_parameters"].items()}

    def mm(x: jax.Array, w: jax.Array) -> jax.Array:
        return rnd(jnp.matmul(rnd(x), rnd(w), precision=HIGHEST))

    def glu(h, gate, up, down):
        return mm(jax.nn.silu(mm(h, gate)) * mm(h, up), down)

    ahead = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]   # t - j
    seen = {"local": (ahead >= 0) & (ahead < sizes["sliding_window"]), "global": ahead >= 0}

    def attention(h, p, kind):
        q = _rope(_rms_norm(mm(h, p["wq"]).reshape(b, t, nh, hd), p["q_norm"], eps), *tables[kind])
        k = _rope(_rms_norm(mm(h, p["wk"]).reshape(b, t, nkv, hd), p["k_norm"], eps), *tables[kind])
        v = mm(h, p["wv"]).reshape(b, t, nkv, hd)

        def one_head(_, x):
            q_h, head = x  # [B, T, head_dim]; the key-value head is cut inside
            k_h, v_h = k[:, :, head // (nh // nkv)], v[:, :, head // (nh // nkv)]
            scores = rnd(jnp.einsum("bqd,bkd->bqk", rnd(q_h), rnd(k_h), precision=HIGHEST))
            scores = jnp.where(seen[kind][None], scores * hd ** -0.5, -jnp.inf)
            probs = jax.nn.softmax(scores, axis=-1)
            return None, rnd(jnp.einsum("bqk,bkd->bqd", rnd(probs), rnd(v_h), precision=HIGHEST))

        _, o = jax.lax.scan(jax.checkpoint(one_head), None, (jnp.moveaxis(q, 2, 0), jnp.arange(nh)))
        return mm(jnp.moveaxis(o, 0, 2).reshape(b, t, nh * hd), p["wo"])

    def experts(h, p):
        probs = jax.nn.softmax(jnp.matmul(h, p["router"], precision=HIGHEST), axis=-1)  # over all 64
        picked, chosen = jax.lax.top_k(probs, top_k)
        weight = picked / picked.sum(axis=-1, keepdims=True)

        @jax.checkpoint
        def part(expert, gate, up, down):
            """One held expert on all tokens, its weights as a mask."""
            w_e = jnp.sum(jnp.where(chosen == expert, weight, 0.0), axis=-1, keepdims=True)
            return w_e * glu(h, gate, up, down)

        out, _ = jax.lax.scan(
            lambda out, e: (out + part(*e), None), jnp.zeros_like(h),
            (jnp.asarray(held, jnp.int32), p["w_gate"], p["w_up"], p["w_down"]))
        return out

    def layer(x, pa, pf, kind):
        x = x + attention(_rms_norm(x, pa["input_norm"], eps), pa, kind)
        return x + experts(_rms_norm(x, pf["post_attention_norm"], eps), pf)

    x = params["embed"][tokens]
    used = {"local": 0, "global": 0}
    for number in range(sizes["num_hidden_layers"]):
        kind = _KIND[sizes["layer_types"][number]]
        ia = used[kind]
        used[kind] += 1
        # the layer's weights are cut from their stacks inside its checkpoint
        x = jax.checkpoint(
            lambda x, ga, gf, kind=kind, ia=ia, jf=number: layer(
                x, {n: w[ia] for n, w in ga.items()}, {n: w[jf] for n, w in gf.items()}, kind)
        )(x, params[kind], params["moe"])

    # the head's loss in blocks of positions; the last block is filled with
    # positions of weight zero
    n = b * (t - 1)
    blocks = -(-n // _LOSS_BLOCK)
    fill = blocks * _LOSS_BLOCK - n
    x = _rms_norm(x, params["final_norm"], eps)[:, :-1].reshape(n, -1)
    x = jnp.pad(x, ((0, fill), (0, 0))).reshape(blocks, _LOSS_BLOCK, -1)
    targets = jnp.pad(tokens[:, 1:].reshape(n), (0, fill)).reshape(blocks, _LOSS_BLOCK)
    counts = (jnp.arange(blocks * _LOSS_BLOCK) < n).astype(jnp.float32).reshape(blocks, _LOSS_BLOCK)

    def block_loss(total, blk):
        x_blk, tgt_blk, counts_blk = blk
        logp = jax.nn.log_softmax(mm(x_blk, params["head"]), axis=-1)
        picked = jnp.take_along_axis(logp, tgt_blk[:, None], axis=-1)[:, 0]
        return total - jnp.sum(picked * counts_blk), None

    total, _ = jax.lax.scan(jax.checkpoint(block_loss), jnp.zeros((), jnp.float32),
                            (x, targets, counts))
    return total / n
