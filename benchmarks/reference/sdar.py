"""Plain reference for the SDAR decoder's block-diffusion training step
(``model_type`` ``sdar_moe``: SDAR-30B-A3B-Chat, as its published
``config.json`` describes the block, whose key set is the Qwen3-MoE family's,
and as the papers it cites describe the step; the configuration file lists
every size ``config.json`` lacks under ``assumed`` with its source): forward
pass and loss in straightforward ``jax.numpy``, float32, every matrix product
at ``Precision.HIGHEST``.  No kernels, no tile walk, no merging of partial
softmaxes, no gathering of tokens by expert: the ``[2T, 2T]`` mask is formed
and applied as it is written below.

It imports nothing from ``torchft_tpu`` and takes nothing the program made.
Weights come from the benchmark (``families/sdar.py``) in the layout the
program's loop is handed too: ``embed [V, E]``, ``head [E, V]``, ``final_norm
[E]`` and two groups stacked by layer: ``attn`` (attention with
``input_norm``) and ``moe`` (the expert layer with ``post_attention_norm``);
matrices are stored ``[in, out]``.

**The block**, per layer, on ``x`` of ``[B, P, 2048]`` (those beyond
``num_hidden_layers`` lie on other chips).  RMSNorm has a weight and
``rms_norm_eps`` (1e-6) inside the square root; no bias anywhere
(``attention_bias`` false); ``hidden_act`` silu.

    a = rms(x; input_norm)
    q = rms_head(a Wq -> [32 heads, head_dim 128]; q_norm)
    k = rms_head(a Wk -> [4 kv heads, 128]; k_norm)        v = a Wv -> [4, 128]
    q, k = q cos + rotate_half(q) sin, k cos + rotate_half(k) sin over all 128,
           cos, sin of index * inv_freq, inv_freq_i = rope_theta^(-2 i / 128),
           i = 0..63, rope_theta 1e6 (rope_scaling null), index the position's
           *token index* (below)
    o = softmax(q k^T / sqrt(128) where seen, else -inf) v   (key-value head h
                                            serves query heads 8 h .. 8 h + 7)
    x = x + o Wo
    m = rms(x; post_attention_norm)
    p = softmax(m Wr) over all ``router_outputs`` (128) published experts;
        chosen = the ``num_experts_per_tok`` (8) largest of p;
        g = p[chosen] / sum p[chosen]           (norm_topk_prob; no epsilon, no bias)
    x = x + sum over the chosen experts that live here (``held_expert_ids``)
            of g_e * (silu(m Wgate_e) * (m Wup_e)) Wdown_e      (experts of 768)

then a final RMSNorm and an untied head.  No shared expert, no dense layer
(``mlp_only_layers`` empty, ``decoder_sparse_step`` 1), no auxiliary loss
(``config.json`` has no coefficient).  What the absent experts would add is
left out: a position none of whose eight experts lives here gets nothing from
that FFN.

**The training step** on a row ``x0`` of ``T`` tokens (block-diffusion
training as BD3-LM, arXiv:2503.09573, sections 3-4, writes it; the masking
forward process is LLaDA's, arXiv:2502.09992, which SDAR, arXiv:2510.06303,
trains under):

    t_b ~ U(0, 1) per row;  p_b = (1 - t_eps) t_b + t_eps  (t_eps 1e-3);
    masked[b, i] ~ Bernoulli(p_b) independently
    xt[b, i] = mask_token_id where masked else x0[b, i]
    input = concat(xt, x0) along positions: P = 2T; positions 0..T-1 are the
            noised copy, T..2T-1 the clean copy; token index = position mod T
    with block(i) = (i mod T) // block_length and noised(i) = i < T,
    query i sees key j iff
        noised(i) and  noised(j) and block(i) == block(j)      (M_BD: inside its own noised block, both directions)
     or noised(i) and !noised(j) and block(i) >  block(j)      (M_OBC: the clean blocks before it)
     or !noised(i) and !noised(j) and block(i) >= block(j)     (M_BC: block-causal over the clean copy)
    logits = head(rms(x_L[:, :T]))                             (the noised half only)
    loss = 1 / (B T) sum_b sum_{i masked} (1 / p_b) (-log softmax(logits[b, i])[x0[b, i]])
                                                               (position i scores token i: no shift)

**The noise** is a pure function of the row and ``noise_seed`` (``row_noise``
below writes the configuration file's rule out on its own): the key is
``jax.random.PRNGKey(noise_seed)`` folded (``fold_in``) with the row's checksum
``sum_i (x0[i] + 1) (i + 1) mod 2^32``, split in two; ``t_b`` is one uniform
of the first half, position ``i`` is masked where the ``i``-th of ``T``
uniforms of the second half lies under ``p_b``.

**Departures**: the three cuts the configuration file lists (layers, experts
held, vocabulary rows); ``mask_token_id`` is the last row of the vocabulary
held here (the published id lies outside an eighth of the rows, and a sliced
vocabulary is a smaller vocabulary); one noise level a row, not one a block
(BD3-LM samples a level for every block of a row; SDAR's and LLaDA's forward
process, which this follows, draw one a sequence).  ``intermediate_size``,
``max_window_layers``, ``use_sliding_window``, ``sliding_window`` and
``max_position_embeddings`` are read by no layer.

**To fit one row beside 24 bytes a parameter** a layer is under
``jax.checkpoint`` with its weights cut from their stacks inside, the score
matrix (``[2T, 2T]`` with the explicit mask) is formed a head at a time under
its own checkpoint, an expert's part is under its own checkpoint and the
head's loss is taken in blocks of positions.  None changes a number.

``operand_dtype`` is the knob of the lower-precision control, as in
``smollm2.py``: both operands and the result of every matrix product but the
router's are rounded to that type (and the cotangents on the way back);
norms, softmax, the rotation, the noise and the loss stay float32.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from benchmarks.reference.smollm2 import HIGHEST, _rms_norm, _rounder

_LOSS_BLOCK = 1024


def row_noise(row: jax.Array, sizes: Dict[str, Any]) -> Tuple[jax.Array, jax.Array]:
    """``(masked [T] bool, p)`` of one row ``[T]``, by the rule above."""
    t = row.shape[0]
    weights = jnp.arange(1, t + 1, dtype=jnp.uint32)
    checksum = jnp.sum((row.astype(jnp.uint32) + jnp.uint32(1)) * weights, dtype=jnp.uint32)
    key = jax.random.fold_in(jax.random.PRNGKey(sizes["noise_seed"]), checksum)
    first, second = jax.random.split(key)
    level = jax.random.uniform(first, (), jnp.float32)
    p = (1.0 - sizes["t_eps"]) * level + sizes["t_eps"]
    return jax.random.uniform(second, (t,), jnp.float32) < p, p


def seen_plane(t: int, block_length: int) -> jax.Array:
    """The ``[2T, 2T]`` mask (query down, key along), the three lines above."""
    i = jnp.arange(2 * t)
    noised, block = i < t, (i % t) // block_length
    ni, nj, bi, bj = noised[:, None], noised[None, :], block[:, None], block[None, :]
    m_bd = ni & nj & (bi == bj)
    m_obc = ni & ~nj & (bi > bj)
    m_bc = ~ni & ~nj & (bi >= bj)
    return m_bd | m_obc | m_bc


def _rope(x: jax.Array, index: jax.Array, theta: float) -> jax.Array:
    """x [B, P, H, D], index [P] the positions' token indices; rotate-half."""
    d = x.shape[-1]
    inv_freq = jnp.asarray([float(theta) ** (-2.0 * i / d) for i in range(d // 2)], jnp.float32)
    angles = index.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, axis=-1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, axis=-1)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def loss_fn(params: Any, tokens: jax.Array, sizes: Dict[str, Any],
            operand_dtype: Optional[str] = None) -> jax.Array:
    eps = sizes["rms_norm_eps"]
    nh, nkv, hd = sizes["num_attention_heads"], sizes["num_key_value_heads"], sizes["head_dim"]
    top_k, held = sizes["num_experts_per_tok"], sizes["held_expert_ids"]
    b, t = tokens.shape
    rnd = _rounder(operand_dtype)

    def mm(x: jax.Array, w: jax.Array) -> jax.Array:
        return rnd(jnp.matmul(rnd(x), rnd(w), precision=HIGHEST))

    def glu(h, gate, up, down):
        return mm(jax.nn.silu(mm(h, gate)) * mm(h, up), down)

    # the noise, a row at a time; the row run twice
    masked, p = zip(*(row_noise(tokens[r], sizes) for r in range(b)))
    masked, p = jnp.stack(masked), jnp.stack(p)
    xt = jnp.where(masked, sizes["mask_token_id"], tokens)
    both = jnp.concatenate([xt, tokens], axis=1)                 # [B, 2T]
    index = jnp.arange(2 * t) % t
    seen = seen_plane(t, sizes["block_length"])

    def attention(h, pa):
        q = _rope(_rms_norm(mm(h, pa["wq"]).reshape(b, 2 * t, nh, hd), pa["q_norm"], eps),
                  index, sizes["rope_theta"])
        k = _rope(_rms_norm(mm(h, pa["wk"]).reshape(b, 2 * t, nkv, hd), pa["k_norm"], eps),
                  index, sizes["rope_theta"])
        v = mm(h, pa["wv"]).reshape(b, 2 * t, nkv, hd)

        def one_head(_, x):
            q_h, head = x  # [B, 2T, head_dim]; the key-value head is cut inside
            k_h, v_h = k[:, :, head // (nh // nkv)], v[:, :, head // (nh // nkv)]
            scores = rnd(jnp.einsum("bqd,bkd->bqk", rnd(q_h), rnd(k_h), precision=HIGHEST))
            scores = jnp.where(seen[None], scores * hd ** -0.5, -jnp.inf)
            probs = jax.nn.softmax(scores, axis=-1)
            return None, rnd(jnp.einsum("bqk,bkd->bqd", rnd(probs), rnd(v_h), precision=HIGHEST))

        _, o = jax.lax.scan(jax.checkpoint(one_head), None, (jnp.moveaxis(q, 2, 0), jnp.arange(nh)))
        return mm(jnp.moveaxis(o, 0, 2).reshape(b, 2 * t, nh * hd), pa["wo"])

    def experts(h, pf):
        probs = jax.nn.softmax(jnp.matmul(h, pf["router"], precision=HIGHEST), axis=-1)  # over all 128
        picked, chosen = jax.lax.top_k(probs, top_k)
        weight = picked / picked.sum(axis=-1, keepdims=True)

        @jax.checkpoint
        def part(expert, gate, up, down):
            """One held expert on all positions, its weights as a mask."""
            w_e = jnp.sum(jnp.where(chosen == expert, weight, 0.0), axis=-1, keepdims=True)
            return w_e * glu(h, gate, up, down)

        out, _ = jax.lax.scan(
            lambda out, e: (out + part(*e), None), jnp.zeros_like(h),
            (jnp.asarray(held, jnp.int32), pf["w_gate"], pf["w_up"], pf["w_down"]))
        return out

    def layer(x, pa, pf):
        x = x + attention(_rms_norm(x, pa["input_norm"], eps), pa)
        return x + experts(_rms_norm(x, pf["post_attention_norm"], eps), pf)

    x = params["embed"][both]
    for number in range(sizes["num_hidden_layers"]):
        # the layer's weights are cut from their stacks inside its checkpoint
        x = jax.checkpoint(
            lambda x, ga, gf, number=number: layer(
                x, {n: w[number] for n, w in ga.items()}, {n: w[number] for n, w in gf.items()})
        )(x, params["attn"], params["moe"])

    # the head's loss over the noised half, in blocks of positions; a position
    # weighs 1 / p_b where it was masked and nothing where it was not
    n = b * t
    blocks = -(-n // _LOSS_BLOCK)
    fill = blocks * _LOSS_BLOCK - n
    x = _rms_norm(x[:, :t], params["final_norm"], eps).reshape(n, -1)
    x = jnp.pad(x, ((0, fill), (0, 0))).reshape(blocks, _LOSS_BLOCK, -1)
    targets = jnp.pad(tokens.reshape(n), (0, fill)).reshape(blocks, _LOSS_BLOCK)
    weights = jnp.where(masked, 1.0 / p[:, None], 0.0).reshape(n)
    weights = jnp.pad(weights, (0, fill)).reshape(blocks, _LOSS_BLOCK)

    def block_loss(total, blk):
        x_blk, tgt_blk, w_blk = blk
        logp = jax.nn.log_softmax(mm(x_blk, params["head"]), axis=-1)
        picked = jnp.take_along_axis(logp, tgt_blk[:, None], axis=-1)[:, 0]
        return total - jnp.sum(picked * w_blk), None

    total, _ = jax.lax.scan(jax.checkpoint(block_loss), jnp.zeros((), jnp.float32),
                            (x, targets, weights))
    return total / n
