"""Plain reference for the JoyAI-LLM-Flash decoder (``model_type``
``joyai_llm_flash``; its published ``config.json`` has the keys of the
DeepSeek-V3 modelling code in ``transformers``
(``models/deepseek_v3/modeling_deepseek_v3.py``), whose multi-token-prediction
module is the one of the DeepSeek-V3 report, section 2.2): forward pass and
the loss over two prediction depths in straightforward ``jax.numpy``, float32,
every matrix product at ``Precision.HIGHEST``.  No kernels, no reordered
weights, no gathering of tokens by expert, no padded tail.

It imports nothing from ``torchft_tpu`` and takes nothing the program made.
Weights come from the benchmark (``families/joyai.py``) in the layout the
program's loop is handed too: ``embed [V, E]``, ``head [E, V]``, ``final_norm
[E]``, three groups stacked by layer in the order the layers come (``mla``
with ``attn_norm``; ``dense`` and ``moe`` with ``mlp_norm``) and ``mtp``, the
module's leaves stacked ``[1, ...]``; matrices are stored ``[in, out]``.

**The layers**, numbered from 0 (those beyond ``num_hidden_layers`` lie on
other chips).  RMSNorm has a weight and ``rms_norm_eps`` inside the root.

    x0 = embed[t]                                          t = t_0 .. t_{T-1}
    a  = rms(x; attn_norm)
    cq = rms(a Wqa; q_norm)      [q_lora_rank]    q = cq Wqb -> [heads, nope + rope] = [q_n | q_r]
    ck = a Wkva                  [kv_lora_rank + rope]
    c  = rms(ck[:kv_lora_rank]; kv_norm)          k_r = ck[kv_lora_rank:]      (one head)
    kv = c Wkvb -> [heads, nope + v] = [k_n | v]
    q_r, k_r = rope(q_r), rope(k_r): the pair (2j, 2j + 1), read as a complex
               number, times exp(i pos rope_theta^(-2j / rope))     (rope_interleave)
    k  = [k_n | k_r, the same for every head]
    o  = softmax(q k^T / sqrt(nope + rope)) v, key j seen by query i iff j <= i
    x  = x + o Wo                                          (no bias anywhere)
    m  = rms(x; mlp_norm)
    layers < first_k_dense_replace:  f = SwiGLU(m; intermediate_size)
    other layers:  s = sigmoid(m Wr) over all ``router_outputs`` published experts;
                   the ``num_experts_per_tok`` largest of s + b are chosen (b the
                   balancing rule's bias, zeros: a buffer, not in the tree);
                   w = s[chosen] / (sum s[chosen] + 1e-20) * routed_scaling_factor;
                   f = SwiGLU_shared(m) + sum over the chosen experts that live
                   here (``held_expert_ids``) of w_e SwiGLU_e(m)
    x  = x + f
    L_main = mean over i = 0 .. T-2 of CE(rms(x_L[i]; final_norm) W_head, t_{i+1})

    the module (``num_nextn_predict_layers`` 1), over the T - 1 positions that
    have a next token:
    h'[i] = [rms(embed[t_{i+1}]; e_norm) | rms(x_L[i]; h_norm)] W_eh      i = 0 .. T-2
    y     = one layer of the "other layers" kind over h', weights of its own,
            causal over i, positions i
    L_mtp = mean over i = 0 .. T-3 of CE(rms(y[i]; out_norm) W_head, t_{i+2})

    loss  = L_main + mtp_loss_weight L_mtp

over the rows of the vocabulary held here.  What the absent experts would add
is left out, in the module's layer too.  No capacity, no drop, no auxiliary
loss.

**To fit one row beside 24 bytes a parameter** a layer is under
``jax.checkpoint`` with its weights cut from their stacks inside, the score
matrix (``[T, T]`` with an explicit mask) is formed a head at a time under
its own checkpoint, an expert's part is under its own checkpoint and each
depth's loss is taken in blocks of positions.  None changes a number.

``operand_dtype`` is the knob of the lower-precision control, as in
``smollm2.py``: both operands and the result of every matrix product but the
router's are rounded to that type (and the cotangents on the way back);
norms, the rotary, softmax and the loss stay float32.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from benchmarks.reference.smollm2 import HIGHEST, _rms_norm, _rounder

_LOSS_BLOCK = 1024


def loss_fn(params: Any, tokens: jax.Array, sizes: Dict[str, Any],
            operand_dtype: Optional[str] = None) -> jax.Array:
    eps, theta = sizes["rms_norm_eps"], float(sizes["rope_theta"])
    nh, rank = sizes["num_attention_heads"], sizes["kv_lora_rank"]
    nope, rope, dv = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"], sizes["v_head_dim"]
    top_k, held = sizes["num_experts_per_tok"], sizes["held_expert_ids"]
    b, t = tokens.shape
    rnd = _rounder(operand_dtype)

    def mm(x: jax.Array, w: jax.Array) -> jax.Array:
        return rnd(jnp.matmul(rnd(x), rnd(w), precision=HIGHEST))

    def glu(h, gate, up, down):
        return mm(jax.nn.silu(mm(h, gate)) * mm(h, up), down)

    def rotate(x):
        """``x [B, n, ..., rope]``, position = index on axis 1: each pair
        ``(2j, 2j + 1)`` as a complex number times ``exp(i pos theta^(-2j / rope))``."""
        n = x.shape[1]
        angle = jnp.arange(n, dtype=jnp.float32)[:, None] * theta ** (
            -jnp.arange(0, rope, 2, dtype=jnp.float32) / rope)[None, :]
        turn = jax.lax.complex(jnp.cos(angle), jnp.sin(angle))
        turn = turn.reshape((1, n) + (1,) * (x.ndim - 3) + (rope // 2,))
        pairs = x.reshape(x.shape[:-1] + (rope // 2, 2))
        turned = jax.lax.complex(pairs[..., 0], pairs[..., 1]) * turn
        return jnp.stack([jnp.real(turned), jnp.imag(turned)], axis=-1).reshape(x.shape)

    def attention(h, p):
        n = h.shape[1]
        q = mm(_rms_norm(mm(h, p["q_a"]), p["q_norm"], eps), p["q_b"]).reshape(b, n, nh, nope + rope)
        q = jnp.concatenate([q[..., :nope], rotate(q[..., nope:])], axis=-1)
        ck = mm(h, p["kv_a"])
        k_r = rotate(ck[..., rank:])                                   # [B, n, rope]: one head
        kv = mm(_rms_norm(ck[..., :rank], p["kv_norm"], eps), p["kv_b"]).reshape(b, n, nh, nope + dv)
        seen = jnp.arange(n)[:, None] >= jnp.arange(n)[None, :]

        def one_head(_, x):
            q_h, kv_h = x  # [B, n, nope + rope], [B, n, nope + v]
            k_h = jnp.concatenate([kv_h[..., :nope], k_r], axis=-1)
            scores = rnd(jnp.einsum("bqd,bkd->bqk", rnd(q_h), rnd(k_h), precision=HIGHEST))
            scores = jnp.where(seen[None], scores * (nope + rope) ** -0.5, -jnp.inf)
            probs = jax.nn.softmax(scores, axis=-1)
            return None, rnd(jnp.einsum("bqk,bkd->bqd", rnd(probs), rnd(kv_h[..., nope:]),
                                        precision=HIGHEST))

        _, o = jax.lax.scan(jax.checkpoint(one_head), None,
                            (jnp.moveaxis(q, 2, 0), jnp.moveaxis(kv, 2, 0)))
        return mm(jnp.moveaxis(o, 0, 2).reshape(b, n, nh * dv), p["wo"])

    def dense(h, p):
        return glu(h, p["w_gate"], p["w_up"], p["w_down"])

    def experts(h, p):
        scores = jax.nn.sigmoid(jnp.matmul(h, p["router"], precision=HIGHEST))
        _, chosen = jax.lax.top_k(scores, top_k)  # the correction bias is zeros
        picked = jnp.take_along_axis(scores, chosen, axis=-1)
        weight = picked / (picked.sum(axis=-1, keepdims=True) + 1e-20) * sizes["routed_scaling_factor"]

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

    def layer(x, pa, pf, ffn):
        x = x + attention(_rms_norm(x, pa["attn_norm"], eps), pa)
        return x + ffn(_rms_norm(x, pf["mlp_norm"], eps), pf)

    def depth_loss(x, final_norm, targets):
        """Mean cross-entropy of ``x [B, n, E]`` against ``targets [B, n]``,
        in blocks of positions; the last block is filled with positions of
        weight zero."""
        n = targets.size
        blocks = -(-n // _LOSS_BLOCK)
        fill = blocks * _LOSS_BLOCK - n
        x = _rms_norm(x, final_norm, eps).reshape(n, -1)
        x = jnp.pad(x, ((0, fill), (0, 0))).reshape(blocks, _LOSS_BLOCK, -1)
        tgt = jnp.pad(targets.reshape(n), (0, fill)).reshape(blocks, _LOSS_BLOCK)
        counts = (jnp.arange(blocks * _LOSS_BLOCK) < n).astype(jnp.float32).reshape(blocks, _LOSS_BLOCK)

        def block_loss(total, blk):
            x_blk, tgt_blk, counts_blk = blk
            logp = jax.nn.log_softmax(mm(x_blk, params["head"]), axis=-1)
            picked = jnp.take_along_axis(logp, tgt_blk[:, None], axis=-1)[:, 0]
            return total - jnp.sum(picked * counts_blk), None

        total, _ = jax.lax.scan(jax.checkpoint(block_loss), jnp.zeros((), jnp.float32),
                                (x, tgt, counts))
        return total / n

    x = params["embed"][tokens]
    used = {"dense": 0, "moe": 0}
    for number in range(sizes["num_hidden_layers"]):
        f_kind = "dense" if number < sizes["first_k_dense_replace"] else "moe"
        jf = used[f_kind]
        used[f_kind] += 1
        # the layer's weights are cut from their stacks inside its checkpoint
        x = jax.checkpoint(
            lambda x, ga, gf, f=f_kind, ia=number, jf=jf: layer(
                x, {n: w[ia] for n, w in ga.items()}, {n: w[jf] for n, w in gf.items()},
                {"dense": dense, "moe": experts}[f])
        )(x, params["mla"], params[f_kind])
    loss = depth_loss(x[:, :-1], params["final_norm"], tokens[:, 1:])

    if sizes["num_nextn_predict_layers"]:
        def module(x_last, embed, g):
            p = {n: w[0] for n, w in g.items()}
            merged = jnp.concatenate([_rms_norm(embed[tokens[:, 1:]], p["e_norm"], eps),
                                      _rms_norm(x_last[:, :-1], p["h_norm"], eps)], axis=-1)
            return layer(mm(merged, p["w_eh"]), p, p, experts)

        y = jax.checkpoint(module)(x, params["embed"], params["mtp"])        # [B, T - 1, E]
        loss = loss + sizes["mtp_loss_weight"] * depth_loss(
            y[:, :-1], params["mtp"]["out_norm"][0], tokens[:, 2:])
    return loss
