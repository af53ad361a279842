"""Plain reference for the AFMoE decoder (``model_type`` ``afmoe``: Trinity, as
its published ``config.json`` and the family's modelling code in
``transformers`` (``models/afmoe``) describe it): forward pass and next-token
loss in straightforward ``jax.numpy``, float32, every matrix product at
``Precision.HIGHEST``.  No kernels, no band of tiles, no gathering of tokens
by expert.

It imports nothing from ``torchft_tpu`` and takes nothing the program made.
Weights come from the benchmark (``families/afmoe.py``) in the layout the
program's loop is handed too: ``embed [V, E]``, ``head [E, V]``, ``final_norm
[E]`` and four groups stacked by layer in the order the layers come:
``local`` and ``global`` (attention, each with ``input_norm`` and
``post_attn_norm``), ``dense`` and ``moe`` (FFN, each with ``pre_mlp_norm``
and ``post_mlp_norm``); matrices are stored ``[in, out]``.

**The layers**, numbered from 0 as ``layer_types`` numbers them (those beyond
``num_hidden_layers`` lie on other chips).  RMSNorm has a weight and
``rms_norm_eps`` inside the square root.

    x0 = embed[tokens] * sqrt(hidden_size)                      (mup_enabled)
    a  = rms(x; input_norm)
    q  = rms_head(a Wq -> [heads, head_dim]; q_norm)
    k  = rms_head(a Wk -> [kv_heads, head_dim]; k_norm)     v = a Wv
    sliding_attention:  q, k = rope(q, k; rope_theta, rotate-half over all of
                        head_dim); key j is seen by query i iff 0 <= i - j < sliding_window
    full_attention:     no rotary; key j is seen by query i iff j <= i
    o  = softmax(q k^T / sqrt(head_dim)) v      (key-value head h serves query
                                                 heads h g .. h g + g - 1, g = heads / kv_heads)
    x  = x + rms((o * sigmoid(a Wg)) Wo; post_attn_norm)
    m  = rms(x; pre_mlp_norm)
    layers < num_dense_layers:  f = SwiGLU(m; intermediate_size)
    other layers:  s = sigmoid(m Wr) over all ``router_outputs`` published experts;
                   the ``num_experts_per_tok`` largest of s + b are chosen (b the
                   balancing rule's bias, zeros: a buffer, not in the tree);
                   w = s[chosen] / (sum s[chosen] + 1e-20) * route_scale;
                   f = SwiGLU_shared(m) + sum over the chosen experts that live
                   here (``held_expert_ids``) of w_e SwiGLU_e(m)
    x  = x + rms(f; post_mlp_norm)

then a final RMSNorm, an untied head, and the mean cross-entropy of position
``t`` predicting token ``t + 1`` over the rows of the vocabulary held here.
What the absent experts would add is left out.  No capacity, no drop, no
auxiliary loss, no bias anywhere.

**To fit one row beside 24 bytes a parameter** a layer is under
``jax.checkpoint`` with its weights cut from their stacks inside, the score
matrix (``[T, T]`` with an explicit mask) is formed a head at a time under
its own checkpoint, an expert's part is under its own checkpoint and the
head's loss is taken in blocks of positions.  None changes a number.

``operand_dtype`` is the knob of the lower-precision control, as in
``smollm2.py``: both operands and the result of every matrix product but the
router's are rounded to that type (and the cotangents on the way back);
norms, softmax, gates and the loss stay float32.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from benchmarks.reference.smollm2 import HIGHEST, _rms_norm, _rope, _rounder

_LOSS_BLOCK = 1024
_KIND = {"sliding_attention": "local", "full_attention": "global"}


def loss_fn(params: Any, tokens: jax.Array, sizes: Dict[str, Any],
            operand_dtype: Optional[str] = None) -> jax.Array:
    eps, theta = sizes["rms_norm_eps"], float(sizes["rope_theta"])
    nh, nkv, hd = sizes["num_attention_heads"], sizes["num_key_value_heads"], sizes["head_dim"]
    window = sizes["sliding_window"]
    top_k, held = sizes["num_experts_per_tok"], sizes["held_expert_ids"]
    b, t = tokens.shape
    rnd = _rounder(operand_dtype)

    def mm(x: jax.Array, w: jax.Array) -> jax.Array:
        return rnd(jnp.matmul(rnd(x), rnd(w), precision=HIGHEST))

    def glu(h, gate, up, down):
        return mm(jax.nn.silu(mm(h, gate)) * mm(h, up), down)

    ahead = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]   # i - j
    seen = {"local": (ahead >= 0) & (ahead < window), "global": ahead >= 0}

    def attention(h, p, kind):
        q = _rms_norm(mm(h, p["wq"]).reshape(b, t, nh, hd), p["q_norm"], eps)
        k = _rms_norm(mm(h, p["wk"]).reshape(b, t, nkv, hd), p["k_norm"], eps)
        v = mm(h, p["wv"]).reshape(b, t, nkv, hd)
        if kind == "local":
            q, k = _rope(q, theta), _rope(k, theta)

        def one_head(_, x):
            q_h, head = x  # [B, T, head_dim]; the key-value head is cut inside
            k_h, v_h = k[:, :, head // (nh // nkv)], v[:, :, head // (nh // nkv)]
            scores = rnd(jnp.einsum("bqd,bkd->bqk", rnd(q_h), rnd(k_h), precision=HIGHEST))
            scores = jnp.where(seen[kind][None], scores * hd ** -0.5, -jnp.inf)
            probs = jax.nn.softmax(scores, axis=-1)
            return None, rnd(jnp.einsum("bqk,bkd->bqd", rnd(probs), rnd(v_h), precision=HIGHEST))

        _, o = jax.lax.scan(jax.checkpoint(one_head), None, (jnp.moveaxis(q, 2, 0), jnp.arange(nh)))
        o = jnp.moveaxis(o, 0, 2).reshape(b, t, nh * hd)
        return mm(o * jax.nn.sigmoid(mm(h, p["wg"])), p["wo"])

    def dense(h, p):
        return glu(h, p["w_gate"], p["w_up"], p["w_down"])

    def experts(h, p):
        scores = jax.nn.sigmoid(jnp.matmul(h, p["router"], precision=HIGHEST))
        _, chosen = jax.lax.top_k(scores, top_k)  # the balancing bias is zeros
        picked = jnp.take_along_axis(scores, chosen, axis=-1)
        weight = picked / (picked.sum(axis=-1, keepdims=True) + 1e-20) * sizes["route_scale"]

        @jax.checkpoint
        def part(expert, gate, up, down):
            """One held expert on all tokens, its weights as a mask."""
            w_e = jnp.sum(jnp.where(chosen == expert, weight, 0.0), axis=-1, keepdims=True)
            return w_e * glu(h, gate, up, down)

        out, _ = jax.lax.scan(
            lambda out, e: (out + part(*e), None),
            glu(h, p["shared_gate"], p["shared_up"], p["shared_down"]),
            (jnp.asarray(held, jnp.int32), p["w_gate"], p["w_up"], p["w_down"]))
        return out

    def layer(x, pa, pf, a_kind, ffn):
        x = x + _rms_norm(attention(_rms_norm(x, pa["input_norm"], eps), pa, a_kind),
                          pa["post_attn_norm"], eps)
        return x + _rms_norm(ffn(_rms_norm(x, pf["pre_mlp_norm"], eps), pf), pf["post_mlp_norm"], eps)

    x = params["embed"][tokens]
    if sizes["mup_enabled"]:
        x = x * jnp.sqrt(jnp.float32(sizes["hidden_size"]))
    used = {"local": 0, "global": 0, "dense": 0, "moe": 0}
    for number in range(sizes["num_hidden_layers"]):
        a_kind = _KIND[sizes["layer_types"][number]]
        f_kind = "dense" if number < sizes["num_dense_layers"] else "moe"
        ia, jf = used[a_kind], used[f_kind]
        used[a_kind] += 1
        used[f_kind] += 1
        # the layer's weights are cut from their stacks inside its checkpoint
        x = jax.checkpoint(
            lambda x, ga, gf, a=a_kind, f=f_kind, ia=ia, jf=jf: layer(
                x, {n: w[ia] for n, w in ga.items()}, {n: w[jf] for n, w in gf.items()},
                a, {"dense": dense, "moe": experts}[f])
        )(x, params[a_kind], params[f_kind])

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
