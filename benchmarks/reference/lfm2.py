"""Plain reference for the LFM2-MoE decoder (``model_type`` ``lfm2_moe``:
LFM2-8B-A1B, as its published ``config.json`` and the modelling code of these
keys in ``transformers`` (``models/lfm2_moe/modeling_lfm2_moe.py``:
``Lfm2MoeShortConv``, ``Lfm2MoeAttention``, ``Lfm2MoeSparseMoeBlock``)
describe it): forward pass and next-token loss in straightforward
``jax.numpy``, float32, every matrix product at ``Precision.HIGHEST``.  No
kernels, no padded convolution, no gathering of tokens by expert.

It imports nothing from ``torchft_tpu`` and takes nothing the program made.
Weights come from the benchmark (``families/lfm2.py``) in the layout the
program's loop is handed too: ``embed [V, E]``, ``embedding_norm [E]`` and four
groups stacked by layer in the order the layers come: ``conv`` and ``attn``
(the operator, each with ``operator_norm``), ``dense`` and ``moe`` (the FFN,
each with ``ffn_norm``); matrices are stored ``[in, out]``, the convolution's
taps ``[E, L]``.

**The layers**, numbered from 0 as ``layer_types`` numbers them (those beyond
``num_hidden_layers`` lie on other chips).  RMSNorm has a weight and
``norm_eps`` inside the square root.  No bias anywhere (``conv_bias`` false).

    x0 = embed[tokens]
    h  = rms(x; operator_norm)
    conv:            [B | C | u] = h W_in            (E -> 3 E, split in that order)
                     z = B * u
                     c_t = sum_{j=0..L-1} w[:, j] z_{t-(L-1)+j},  z_{<0} = 0
                                                     (depthwise, L = conv_L_cache taps,
                                                      causal: tap L-1 is the current position)
                     y = (C * c) W_out               (no activation inside)
    full_attention:  q = rms_head(h Wq -> [heads, head_dim]; q_layernorm)
                     k = rms_head(h Wk -> [kv_heads, head_dim]; k_layernorm)    v = h Wv
                     q, k = rope(q, k; rope_theta, rotate-half over all of head_dim)
                     y = softmax(q k^T / sqrt(head_dim)) v Wo, key j seen by query i iff j <= i
                                                     (key-value head h serves query heads
                                                      h g .. h g + g - 1, g = heads / kv_heads; no gate)
    x  = x + y
    m  = rms(x; ffn_norm)
    layers < num_dense_layers:  f = w2(silu(w1 m) * w3 m)      (intermediate_size;
                                                                stored w_gate, w_up, w_down)
    other layers:  s = sigmoid(m Wr) over all ``router_outputs`` published experts;
                   the ``num_experts_per_tok`` largest of s + b are chosen (b the
                   expert bias, ``use_expert_bias``: zeros, a buffer, not in the tree);
                   g = s[chosen] / (sum s[chosen] + 1e-6) * routed_scaling_factor
                                                     (``norm_topk_prob``)
                   f = sum over the chosen experts that live here
                       (``held_expert_ids``) of g_e SwiGLU_e(m)      (no shared expert)
    x  = x + f
    logits = rms(x_L; embedding_norm) embed^T        (the head is tied)

and the mean cross-entropy of position ``t`` predicting token ``t + 1`` over
the rows of the vocabulary held here.  What the absent experts would add is
left out: a token none of whose experts lives here gets nothing from the FFN.
No capacity, no drop, no auxiliary loss.

**Departures from the published description**: none in the mathematics.
``config.json`` has no key for the tie (assumed tied: the parameter count is
the published 8.3 B only so), the head's width (hidden / heads), the rotary's
form (rotate-half, as the modelling code), the expert bias being a buffer, the
epsilon of the q / k norms (``norm_eps``): the configuration file lists each
under ``assumed``.

**To fit one row beside 24 bytes a parameter** a layer is under
``jax.checkpoint`` with its weights cut from their stacks inside, the score
matrix (``[T, T]`` with an explicit mask) is formed a head at a time under
its own checkpoint, an expert's part is under its own checkpoint and the
head's loss is taken in blocks of positions.  None changes a number.

``operand_dtype`` is the knob of the lower-precision control, as in
``smollm2.py``: both operands and the result of every matrix product but the
router's are rounded to that type (and the cotangents on the way back);
norms, the rotary, the convolution's taps and gates, softmax, the routing
weights and the loss stay float32.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from benchmarks.reference.smollm2 import HIGHEST, _rms_norm, _rope, _rounder

_LOSS_BLOCK = 1024
_KIND = {"conv": "conv", "full_attention": "attn"}
_ROUTER_EPS = 1e-6


def loss_fn(params: Any, tokens: jax.Array, sizes: Dict[str, Any],
            operand_dtype: Optional[str] = None) -> jax.Array:
    eps, theta = sizes["norm_eps"], float(sizes["rope_theta"])
    nh, nkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    hd = sizes["hidden_size"] // nh
    top_k, held = sizes["num_experts_per_tok"], sizes["held_expert_ids"]
    b, t = tokens.shape
    rnd = _rounder(operand_dtype)

    def mm(x: jax.Array, w: jax.Array) -> jax.Array:
        return rnd(jnp.matmul(rnd(x), rnd(w), precision=HIGHEST))

    def glu(h, gate, up, down):
        return mm(jax.nn.silu(mm(h, gate)) * mm(h, up), down)

    def conv(h, p):
        b_gate, c_gate, u = jnp.split(mm(h, p["w_in"]), 3, axis=-1)
        z = b_gate * u
        taps = p["conv"].shape[-1]
        # three explicit shifted products: tap j reads position t - (taps - 1) + j
        c = jnp.zeros_like(z)
        for j in range(taps):
            back = taps - 1 - j
            shifted = z if back == 0 else jnp.concatenate(
                [jnp.zeros_like(z[:, :back]), z[:, :t - back]], axis=1)
            c = c + shifted * p["conv"][:, j]
        return mm(c_gate * c, p["w_out"])

    seen = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]

    def attn(h, p):
        q = _rope(_rms_norm(mm(h, p["wq"]).reshape(b, t, nh, hd), p["q_layernorm"], eps), theta)
        k = _rope(_rms_norm(mm(h, p["wk"]).reshape(b, t, nkv, hd), p["k_layernorm"], eps), theta)
        v = mm(h, p["wv"]).reshape(b, t, nkv, hd)

        def one_head(_, x):
            q_h, head = x  # [B, T, head_dim]; the key-value head is cut inside
            k_h, v_h = k[:, :, head // (nh // nkv)], v[:, :, head // (nh // nkv)]
            scores = rnd(jnp.einsum("bqd,bkd->bqk", rnd(q_h), rnd(k_h), precision=HIGHEST))
            scores = jnp.where(seen[None], scores * hd ** -0.5, -jnp.inf)
            probs = jax.nn.softmax(scores, axis=-1)
            return None, rnd(jnp.einsum("bqk,bkd->bqd", rnd(probs), rnd(v_h), precision=HIGHEST))

        _, o = jax.lax.scan(jax.checkpoint(one_head), None, (jnp.moveaxis(q, 2, 0), jnp.arange(nh)))
        return mm(jnp.moveaxis(o, 0, 2).reshape(b, t, nh * hd), p["wo"])

    def dense(h, p):
        return glu(h, p["w_gate"], p["w_up"], p["w_down"])

    def experts(h, p):
        scores = jax.nn.sigmoid(jnp.matmul(h, p["router"], precision=HIGHEST))
        _, chosen = jax.lax.top_k(scores, top_k)  # the expert bias is zeros
        picked = jnp.take_along_axis(scores, chosen, axis=-1)
        weight = picked / (picked.sum(axis=-1, keepdims=True) + _ROUTER_EPS) * sizes["routed_scaling_factor"]

        @jax.checkpoint
        def part(expert, gate, up, down):
            """One held expert on all tokens, its weights as a mask."""
            w_e = jnp.sum(jnp.where(chosen == expert, weight, 0.0), axis=-1, keepdims=True)
            return w_e * glu(h, gate, up, down)

        out, _ = jax.lax.scan(
            lambda out, e: (out + part(*e), None), jnp.zeros_like(h),
            (jnp.asarray(held, jnp.int32), p["w_gate"], p["w_up"], p["w_down"]))
        return out

    def layer(x, po, pf, operator, ffn):
        x = x + operator(_rms_norm(x, po["operator_norm"], eps), po)
        return x + ffn(_rms_norm(x, pf["ffn_norm"], eps), pf)

    x = params["embed"][tokens]
    used = {"conv": 0, "attn": 0, "dense": 0, "moe": 0}
    for number in range(sizes["num_hidden_layers"]):
        o_kind = _KIND[sizes["layer_types"][number]]
        f_kind = "dense" if number < sizes["num_dense_layers"] else "moe"
        io, jf = used[o_kind], used[f_kind]
        used[o_kind] += 1
        used[f_kind] += 1
        # the layer's weights are cut from their stacks inside its checkpoint
        x = jax.checkpoint(
            lambda x, go, gf, o=o_kind, f=f_kind, io=io, jf=jf: layer(
                x, {n: w[io] for n, w in go.items()}, {n: w[jf] for n, w in gf.items()},
                {"conv": conv, "attn": attn}[o], {"dense": dense, "moe": experts}[f])
        )(x, params[o_kind], params[f_kind])

    # the tied head's loss in blocks of positions; the last block is filled
    # with positions of weight zero
    n = b * (t - 1)
    blocks = -(-n // _LOSS_BLOCK)
    fill = blocks * _LOSS_BLOCK - n
    x = _rms_norm(x, params["embedding_norm"], eps)[:, :-1].reshape(n, -1)
    x = jnp.pad(x, ((0, fill), (0, 0))).reshape(blocks, _LOSS_BLOCK, -1)
    targets = jnp.pad(tokens[:, 1:].reshape(n), (0, fill)).reshape(blocks, _LOSS_BLOCK)
    counts = (jnp.arange(blocks * _LOSS_BLOCK) < n).astype(jnp.float32).reshape(blocks, _LOSS_BLOCK)

    def block_loss(total, blk):
        x_blk, tgt_blk, counts_blk = blk
        logp = jax.nn.log_softmax(mm(x_blk, params["embed"].T), axis=-1)
        picked = jnp.take_along_axis(logp, tgt_blk[:, None], axis=-1)[:, 0]
        return total - jnp.sum(picked * counts_blk), None

    total, _ = jax.lax.scan(jax.checkpoint(block_loss), jnp.zeros((), jnp.float32),
                            (x, targets, counts))
    return total / n
