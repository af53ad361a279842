"""Plain reference for the Kimi Linear decoder (``model_type`` ``kimi_linear``,
as its published ``config.json``, the Kimi Linear report and the released
model code describe it): forward pass and next-token loss in straightforward
``jax.numpy``, float32, every matrix product at ``Precision.HIGHEST``.  No
kernels, no chunking of the delta rule, no gathering of tokens by expert.

It imports nothing from ``torchft_tpu`` and takes nothing the program made.
Weights come from the benchmark (``families/kimi_linear.py``) in the layout
the program's loop is handed too: ``embed [V, E]``, ``head [E, V]``,
``final_norm [E]`` and four groups stacked by layer in the order the layers
come: ``kda``, ``mla`` (attention, each with its ``attn_norm``), ``dense``,
``moe`` (FFN, each with its ``mlp_norm``); matrices are stored ``[in, out]``.

**The layers.**  Layers are numbered from 1.  Every layer is ``x = x +
Attn(RMSNorm(x))``, ``x = x + FFN(RMSNorm(x))``; RMSNorm has a weight and
``rms_norm_eps`` inside the square root.  ``linear_attn_config.kda_layers``
and ``full_attn_layers`` say which attention a layer has (those beyond
``num_hidden_layers`` lie on other chips); the first
``first_k_dense_replace`` layers have the dense SwiGLU FFN of
``intermediate_size``, every later one the expert FFN.  Then a final RMSNorm,
an untied head, and the mean cross-entropy of position ``t`` predicting token
``t + 1`` over the rows of the vocabulary held here.  No auxiliary loss.

*KDA* (``linear_attn_config``: ``num_heads`` heads of ``head_dim``, kernel
``short_conv_kernel_size``): ``q, k, v = SiLU(conv(W x))``, the convolution
depthwise and causal (``y_t = sum_i w_i x_{t-K+1+i}``); ``q`` and ``k``
L2-normalised per head (``x / sqrt(sum x^2 + 1e-6)``), ``q`` scaled by
``head_dim ** -0.5``.  Decay per head and key channel ``g_t = -exp(A_log_h)
softplus(W_fb W_fa x_t + dt_bias)``, ``a_t = exp(g_t)``; ``b_t = sigmoid(W_b
x_t)`` per head.  State ``S`` of ``[head_dim, head_dim]`` per head, from
zero: ``S'_t = Diag(a_t) S_{t-1}``; ``S_t = S'_t + b_t k_t (v_t - S'_t^T
k_t)^T``; ``o_t = S_t^T q_t``.  Output ``W_o (RMSNorm_head(o_t) *
sigmoid(W_gb W_ga x_t))``, the norm over a head with one weight of
``head_dim`` for all heads.

*MLA, NoPE* (``mla_use_nope``: no rotary anywhere): ``q = W_q x`` in heads of
``qk_nope_head_dim + qk_rope_head_dim``; ``[c; k_pe] = W_kva x``
(``kv_lora_rank + qk_rope_head_dim``), ``c = RMSNorm(c)``; ``[k_nope_h; v_h] =
W_kvb c`` per head; ``k_h = [k_nope_h; k_pe]``, ``k_pe`` shared by all heads;
causal softmax of ``q k^T (nope + rope) ** -0.5``; ``W_o``.

*Expert FFN*: ``s = sigmoid(W_r x)`` over all ``router_outputs`` published
experts; the ``num_experts_per_token`` largest of ``s + b`` are chosen (``b``
the correction bias, zeros: a buffer, not in the tree); weights ``w =
s[chosen] / (sum s[chosen] + 1e-20) * routed_scaling_factor``; ``y = sum over
the chosen experts that live here (``held_expert_ids``) of w_e SwiGLU_e(x) +
SwiGLU_shared(x)``.  What the absent experts would add is left out.  No
capacity, no drop.

**To fit one row beside 24 bytes a parameter** a layer is under
``jax.checkpoint`` and so are the stages inside a KDA layer, the recurrence is a
scan of checkpointed blocks of steps over a few heads at a time,
the score matrix is formed a head at a time, an expert's part is under its own
checkpoint and the head's loss is taken in blocks of positions.  None changes a number.

``operand_dtype`` is the knob of the lower-precision control, as in
``smollm2.py``: both operands and the result of every matrix product but the
router's are rounded to that type (and the cotangents on the way back);
norms, softmax, the recurrence's state and the loss stay float32.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from benchmarks.reference.smollm2 import HIGHEST, _rms_norm, _rounder

_RECURRENCE_BLOCK = 64
_HEAD_GROUPS = 4
_LOSS_BLOCK = 1024


def loss_fn(params: Any, tokens: jax.Array, sizes: Dict[str, Any],
            operand_dtype: Optional[str] = None) -> jax.Array:
    eps = sizes["rms_norm_eps"]
    lin = sizes["linear_attn_config"]
    kh, kd = lin["num_heads"], lin["head_dim"]
    nh = sizes["num_attention_heads"]
    nope, rope, dv = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"], sizes["v_head_dim"]
    rank = sizes["kv_lora_rank"]
    top_k, held = sizes["num_experts_per_token"], sizes["held_expert_ids"]
    b, t = tokens.shape
    rnd = _rounder(operand_dtype)

    def mm(x: jax.Array, w: jax.Array) -> jax.Array:
        return rnd(jnp.matmul(rnd(x), rnd(w), precision=HIGHEST))

    def glu(h, gate, up, down):
        return mm(jax.nn.silu(mm(h, gate)) * mm(h, up), down)

    def conv_silu(x, w):
        taps = w.shape[-1]
        padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
        return jax.nn.silu(sum(padded[:, i:i + t] * w[:, i] for i in range(taps)))

    def l2(x):
        return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)

    def delta_rule(q, k, v, g, beta):
        """[T, B, H, ...] each; the recurrence over t, from a zero state."""

        def step(s, x):
            q_t, k_t, v_t, g_t, b_t = x
            s = jnp.exp(g_t)[..., None] * s
            read = jnp.einsum("bhk,bhkv->bhv", k_t, s, precision=HIGHEST)
            s = s + b_t[..., None, None] * jnp.einsum(
                "bhk,bhv->bhkv", k_t, v_t - read, precision=HIGHEST)
            return s, jnp.einsum("bhk,bhkv->bhv", q_t, s, precision=HIGHEST)

        blk = _RECURRENCE_BLOCK if t % _RECURRENCE_BLOCK == 0 else 1
        xs = jax.tree_util.tree_map(
            lambda x: x.reshape((t // blk, blk) + x.shape[1:]), (q, k, v, g, beta))
        _, o = jax.lax.scan(jax.checkpoint(lambda s, x: jax.lax.scan(step, s, x)),
                            jnp.zeros(q.shape[1:3] + (kd, kd), jnp.float32), xs)
        return o.reshape((t,) + o.shape[2:])

    def kda(h, p):
        def heads(x):
            return x.reshape(b, t, kh, kd)

        # each stage under its own checkpoint, the recurrence a few heads at a
        # time: a layer's backward then holds one stage's insides at once
        @jax.checkpoint
        def unit(w, taps, scale):
            return l2(heads(conv_silu(mm(h, w), taps))) * scale

        q = unit(p["wq"], p["conv_q"], kd ** -0.5)
        k = unit(p["wk"], p["conv_k"], 1.0)
        v = jax.checkpoint(lambda w, taps: heads(conv_silu(mm(h, w), taps)))(p["wv"], p["conv_v"])
        g = jax.checkpoint(lambda a_log, f_a, f_b, dt_bias: -jnp.exp(a_log)[:, None] * heads(
            jax.nn.softplus(mm(mm(h, f_a), f_b) + dt_bias)))(p["a_log"], p["f_a"], p["f_b"], p["dt_bias"])
        beta = jax.nn.sigmoid(mm(h, p["b_proj"]))
        groups = _HEAD_GROUPS if kh % _HEAD_GROUPS == 0 else 1

        def grouped(x):  # [B, T, H, ...] -> [groups, T, B, H / groups, ...]
            x = jnp.moveaxis(x, 1, 0)
            x = x.reshape(x.shape[:2] + (groups, kh // groups) + x.shape[3:])
            return jnp.moveaxis(x, 2, 0)

        o = jax.lax.map(jax.checkpoint(lambda x: delta_rule(*x)),
                        tuple(grouped(x) for x in (rnd(q), rnd(k), rnd(v), g, beta)))
        o = jnp.moveaxis(o, 0, 2).reshape(t, b, kh, kd)   # [groups, T, B, H/groups, d] -> [T, B, H, d]

        @jax.checkpoint
        def out(o, o_norm, g_a, g_b):
            gate = jax.nn.sigmoid(mm(mm(h, g_a), g_b))
            return (_rms_norm(jnp.moveaxis(o, 0, 1), o_norm, eps) * heads(gate)).reshape(b, t, kh * kd)

        return mm(out(o, p["o_norm"], p["g_a"], p["g_b"]), p["wo"])

    def mla(h, p):
        q = mm(h, p["wq"]).reshape(b, t, nh, nope + rope)
        kv_a = mm(h, p["kv_a"])
        latent = _rms_norm(kv_a[..., :rank], p["kv_norm"], eps)
        k_pe = kv_a[..., rank:]
        kv = mm(latent, p["kv_b"]).reshape(b, t, nh, nope + dv)

        def one_head(_, x):
            q_h, kv_h = x  # [B, T, nope + rope], [B, T, nope + dv]
            k_h = jnp.concatenate([kv_h[..., :nope], k_pe], axis=-1)
            scores = rnd(jnp.einsum("bqd,bkd->bqk", rnd(q_h), rnd(k_h), precision=HIGHEST))
            causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
            scores = jnp.where(causal[None], scores * (nope + rope) ** -0.5, -jnp.inf)
            probs = jax.nn.softmax(scores, axis=-1)
            return None, rnd(jnp.einsum("bqk,bkd->bqd", rnd(probs), rnd(kv_h[..., nope:]),
                                        precision=HIGHEST))

        _, o = jax.lax.scan(jax.checkpoint(one_head), None,
                            (jnp.moveaxis(q, 2, 0), jnp.moveaxis(kv, 2, 0)))
        return mm(jnp.moveaxis(o, 0, 2).reshape(b, t, nh * dv), p["wo"])

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

    def layer(x, pa, pf, attention, ffn):
        x = x + attention(_rms_norm(x, pa["attn_norm"], eps), pa)
        return x + ffn(_rms_norm(x, pf["mlp_norm"], eps), pf)

    def dense(h, p):
        return glu(h, p["w_gate"], p["w_up"], p["w_down"])

    x = params["embed"][tokens]
    used = {"kda": 0, "mla": 0, "dense": 0, "moe": 0}
    for number in range(1, sizes["num_hidden_layers"] + 1):
        a_kind = "kda" if number in lin["kda_layers"] else "mla"
        assert (number in lin["kda_layers"]) != (number in lin["full_attn_layers"]), number
        f_kind = "dense" if number <= sizes["first_k_dense_replace"] else "moe"
        ia, jf = used[a_kind], used[f_kind]
        used[a_kind] += 1
        used[f_kind] += 1
        # the layer's weights are cut from their stacks inside its checkpoint
        x = jax.checkpoint(
            lambda x, ga, gf, a=a_kind, f=f_kind, ia=ia, jf=jf: layer(
                x, {n: w[ia] for n, w in ga.items()}, {n: w[jf] for n, w in gf.items()},
                {"kda": kda, "mla": mla}[a], {"dense": dense, "moe": experts}[f])
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
