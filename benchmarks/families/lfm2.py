"""The LFM2-MoE family (``model_type`` ``lfm2_moe``: LFM2-8B-A1B): gated
short convolutions as the token mixer of three layers in four, grouped-query
attention with per-head q / k norms and a rotary in the fourth, leading dense
SwiGLU FFNs then sigmoid-routed experts of which the chip holds a share, no
shared expert, a head tied to the embedding of which the chip holds a slice of
rows; ``torchft_tpu/models/lfm2.py`` trains it.  The members are those
``families/llama_dense.py`` lists; the plain reference is
``reference/lfm2.py``, whose text holds the layers' equations.

A configuration keeps ``layer_types`` whole as published: the layers up to
``num_hidden_layers`` are run.  ``num_experts`` counts the experts held here
(their published ids are ``held_expert_ids``) and ``router_outputs`` the
experts the router scores, which is never cut.

The compiled step that lets go of the chip's memory before the reference
runs and the device trace by the program's scopes (``scope_ms`` /
``scope_rows``) are ``families/kimi_linear.py``'s; ``flash_attn_work`` gives
the operations and bytes of the three causal flash kernels at heads of 64,
``shortconv_work`` those of the convolution operator."""

from __future__ import annotations

import importlib.util
from typing import Any, Dict, Optional

import numpy as np

from benchmarks.families import kimi_linear as _shared
from benchmarks.reference import lfm2 as _reference

STACKED = ("conv", "attn", "dense", "moe")
CUT_KEYS = {"layers": "num_hidden_layers", "experts": "num_experts", "vocab": "vocab_size"}
# heads, experts per token, the router's outputs and the convolution's taps
# are widths here: the router scores every published expert whichever of them
# live on this chip
WIDTH_KEYS = ("num_attention_heads", "num_key_value_heads", "num_experts_per_tok", "router_outputs",
              "conv_L_cache")
ASSUMED_KEYS = ("remat", "remat_policy", "attn_impl", "held_expert_ids", "expert_slack",
                "tie_word_embeddings")
_KIND = {"conv": "conv", "full_attention": "attn"}

scope_rows, scope_ms = _shared.scope_rows, _shared.scope_ms
aot_prepare = _shared.aot_prepare
FLASH_KERNELS = _shared.FLASH_KERNELS


def _layers(sizes: Dict[str, Any]) -> "list[str]":
    """``conv`` or ``attn`` for each layer run."""
    return [_KIND[kind] for kind in sizes["layer_types"][:sizes["num_hidden_layers"]]]


def layer_pattern(sizes: Dict[str, Any]) -> Dict[str, int]:
    """The leading dense layers, then the published ratio: an attention layer
    every ``period`` layers (the widest spacing of ``full_attention`` in
    ``layer_types``: three convolutions to one attention)."""
    full = [i for i, kind in enumerate(sizes["layer_types"]) if kind == "full_attention"]
    gaps = [b - a for a, b in zip(full, full[1:])]
    return {"leading_dense": sizes["num_dense_layers"], "period": max(gaps) if gaps else 1}


def check(sizes: Dict[str, Any]) -> None:
    if importlib.util.find_spec("torchft_tpu.models.lfm2") is None:
        raise ValueError("this checkout's program has no models/lfm2.py")
    types = sizes["layer_types"]
    if len(types) < sizes["num_hidden_layers"] or set(types) - set(_KIND):
        raise ValueError("layer_types names conv or full_attention for every layer run")
    fixed = {"conv_bias": False, "norm_topk_prob": True, "use_expert_bias": True,
             "tie_word_embeddings": True}
    wrong = {k: sizes[k] for k, v in fixed.items() if sizes[k] != v}
    if wrong:
        raise ValueError(f"models/lfm2.py expresses {fixed} only; the sizes have {wrong}")
    if sizes["hidden_size"] % sizes["num_attention_heads"]:
        raise ValueError("the head's width is hidden_size over num_attention_heads")
    if sizes["num_attention_heads"] % sizes["num_key_value_heads"]:
        raise ValueError("query heads are a multiple of key-value heads")
    held = sizes["held_expert_ids"]
    if len(held) != sizes["num_experts"] or len(set(held)) != len(held) or not all(
            0 <= e < sizes["router_outputs"] for e in held):
        raise ValueError("held_expert_ids names num_experts distinct experts of the router's outputs")
    if sizes["num_experts_per_tok"] > sizes["router_outputs"]:
        raise ValueError("more experts a token than the router scores")


def _program_config(sizes: Dict[str, Any]) -> Any:
    import jax.numpy as jnp

    from torchft_tpu.models import lfm2

    return lfm2.Lfm2Config(
        vocab_size=sizes["vocab_size"], d_model=sizes["hidden_size"],
        n_layers=sizes["num_hidden_layers"], layer_types=tuple(sizes["layer_types"]),
        num_dense_layers=sizes["num_dense_layers"], conv_taps=sizes["conv_L_cache"],
        n_heads=sizes["num_attention_heads"], n_kv_heads=sizes["num_key_value_heads"],
        head_dim=sizes["hidden_size"] // sizes["num_attention_heads"],
        rope_theta=float(sizes["rope_theta"]), d_ff=sizes["intermediate_size"],
        d_expert=sizes["moe_intermediate_size"], n_routed_experts=sizes["router_outputs"],
        experts_per_token=sizes["num_experts_per_tok"],
        held_experts=tuple(sizes["held_expert_ids"]),
        routed_scaling_factor=float(sizes["routed_scaling_factor"]),
        expert_slack=sizes["expert_slack"], norm_eps=sizes["norm_eps"],
        dtype=jnp.dtype(sizes["compute_dtype"]), param_dtype=jnp.dtype(sizes["param_dtype"]),
        remat=sizes["remat"], remat_policy=sizes["remat_policy"], attn_impl=sizes["attn_impl"])


def make_grad_step(sizes: Dict[str, Any], seq_len: int) -> Any:
    """The program's step, whose compiled form can be released before the
    reference runs (``families/kimi_linear.py`` says why)."""
    from torchft_tpu.models import lfm2

    return _shared._GradStep(lfm2.make_grad_step(_program_config(sizes)))


def reference_loss(params: Any, tokens: Any, sizes: Dict[str, Any],
                   operand_dtype: Optional[str] = None) -> Any:
    """The plain reference's loss (``reference/lfm2.py``).  Tracing it
    releases the program's compiled steps: the window is over by then."""
    _shared._release_compiled(of_ended_threads_only=False)
    return _reference.loss_fn(params, tokens, sizes, operand_dtype)


def make_routing_stats(sizes: Dict[str, Any]) -> Any:
    """The program's jitted ``routing_stats(params, tokens)``: how far a batch
    is from the uniform routing ``flops_per_step`` counts on, and how many
    tokens found no expert here (their FFN output is zero: no shared expert)."""
    from torchft_tpu.models import lfm2

    return lfm2.make_routing_stats(_program_config(sizes))


def program_init_shapes(sizes: Dict[str, Any]) -> Any:
    import jax

    from torchft_tpu.models import lfm2

    cfg = _program_config(sizes)
    return jax.eval_shape(lambda k: lfm2.init_params(k, cfg), jax.random.PRNGKey(0))


def weight_shapes(sizes: Dict[str, Any]) -> Dict[str, Any]:
    e, v = sizes["hidden_size"], sizes["vocab_size"]
    hd = e // sizes["num_attention_heads"]
    dq, dkv = sizes["num_attention_heads"] * hd, sizes["num_key_value_heads"] * hd
    f, fx = sizes["intermediate_size"], sizes["moe_intermediate_size"]
    held, outs, taps = sizes["num_experts"], sizes["router_outputs"], sizes["conv_L_cache"]
    layers = _layers(sizes)
    lc, la = layers.count("conv"), layers.count("attn")
    ld = min(sizes["num_dense_layers"], len(layers))
    lx = len(layers) - ld
    return {
        "embed": (v, e), "embedding_norm": (e,),
        "conv": {"operator_norm": (lc, e), "w_in": (lc, e, 3 * e), "conv": (lc, e, taps),
                 "w_out": (lc, e, e)},
        "attn": {"operator_norm": (la, e), "wq": (la, e, dq), "wk": (la, e, dkv), "wv": (la, e, dkv),
                 "q_layernorm": (la, hd), "k_layernorm": (la, hd), "wo": (la, dq, e)},
        "dense": {"ffn_norm": (ld, e), "w_gate": (ld, e, f), "w_up": (ld, e, f), "w_down": (ld, f, e)},
        "moe": {"ffn_norm": (lx, e), "router": (lx, e, outs), "w_gate": (lx, held, e, fx),
                "w_up": (lx, held, e, fx), "w_down": (lx, held, fx, e)},
    }


def n_params(sizes: Dict[str, Any]) -> int:
    """Trained parameters by the shapes; the tied head is the embedding and
    counted once.  The router's expert bias (``router_outputs`` a layer) is a
    buffer and not counted."""
    return sum(int(np.prod(s)) for s in _shared._leaves(weight_shapes(sizes)))


def make_weights_fn(sizes: Dict[str, Any]) -> Any:
    """``key -> weights``, the benchmark's own: matrices normal over the
    square root of the fan-in (a convolution's is its taps), norms ones, the
    embedding 0.02 normal."""
    import jax
    import jax.numpy as jnp

    _shared._release_compiled(of_ended_threads_only=True)
    shapes = weight_shapes(sizes)
    pd = jnp.dtype(sizes["param_dtype"])

    def make(key):
        flat, tree = jax.tree_util.tree_flatten_with_path(
            shapes, is_leaf=lambda s: isinstance(s, tuple))
        out = []
        for i, (path, shape) in enumerate(flat):
            name = str(getattr(path[-1], "key", path[-1]))
            k = jax.random.fold_in(key, i)
            if name.endswith("norm"):
                leaf = jnp.ones(shape, pd)
            elif name == "embed":
                leaf = jax.random.normal(k, shape, pd) * 0.02
            else:
                fan_in = shape[-1] if name == "conv" else shape[-2]
                leaf = jax.random.normal(k, shape, pd) / np.sqrt(fan_in)
            out.append(leaf)
        return jax.tree_util.tree_unflatten(tree, out)

    return make


# ---- operations and bytes ---------------------------------------------------

def flash_attn_work(sizes: Dict[str, Any], batch: int, seq: int) -> Dict[str, Dict[str, float]]:
    """Operations and bytes of one call of each flash-attention kernel: one
    attention layer, ``batch`` rows, the causal half only, matrix products
    only (as ``families/llama_dense.py`` counts them: 2 / 4 / 3 products a
    pair in forward / key-value backward / query backward, of the head's
    width each).  Bytes: every operand read once and every result written
    once in the compute type (the kernels see K and V repeated up to the
    query heads), row statistics in float32."""
    import jax.numpy as jnp

    heads = batch * sizes["num_attention_heads"]
    d = sizes["hidden_size"] // sizes["num_attention_heads"]
    pairs = heads * seq * (seq + 1) // 2
    tile = heads * seq * d * jnp.dtype(sizes["compute_dtype"]).itemsize  # one [T, d] operand of every head
    stat = heads * seq * 4
    return {
        "_fwd_kernel": {"flops": 2.0 * 2 * pairs * d, "bytes": 4.0 * tile + stat},
        "_bwd_kv_kernel": {"flops": 2.0 * 4 * pairs * d, "bytes": 6.0 * tile + 2 * stat},
        "_bwd_q_kernel": {"flops": 2.0 * 3 * pairs * d, "bytes": 5.0 * tile + 2 * stat},
    }


def shortconv_work(sizes: Dict[str, Any], batch: int, seq: int) -> Dict[str, Dict[str, float]]:
    """Operations and bytes of the gated short convolution of one layer on
    ``batch`` rows: ``{"forward": ..., "backward": ...}``, the least any
    implementation needs.

    Operations, 2 a multiply-add: the two projections (``E -> 3 E`` and ``E ->
    E``: ``8 N E^2``) and the taps (``2 N E L``); the two gates' products and
    every other elementwise pass are not counted.  The backward forms each
    projection's input gradient and weight gradient and walks the taps both
    ways (towards ``z`` and towards the taps' weights): twice the forward.

    Bytes, each array once and no intermediate (a fused operator keeps ``[B |
    C | u]``, ``z`` and ``c`` on the chip): forward reads ``h`` and writes
    ``y`` in the compute type and reads the three weights once in the compute
    type; the backward reads ``h``, ``dy`` and the weights, writes ``dh`` and
    writes the three weight gradients in the parameters' type."""
    import jax.numpy as jnp

    e, taps = sizes["hidden_size"], sizes["conv_L_cache"]
    n = batch * seq
    item = jnp.dtype(sizes["compute_dtype"]).itemsize
    weights = 3 * e * e + e * taps + e * e
    flops = float(2 * n * (4 * e * e + e * taps))
    forward = 2 * n * e * item + weights * item
    backward = 3 * n * e * item + weights * item + weights * jnp.dtype(sizes["param_dtype"]).itemsize
    return {"forward": {"flops": flops, "bytes": float(forward)},
            "backward": {"flops": 2 * flops, "bytes": float(backward)}}


def flops_per_step(sizes: Dict[str, Any], batch: int, seq: int) -> float:
    """Model FLOPs of forward + backward (= 3x forward) for ``batch`` rows;
    recomputation under remat is not counted.

    Six a token for every matmul parameter the token meets: a convolution
    layer's two projections and its taps, an attention layer's four
    projections, the dense FFN, the router, the tied head; of the routed
    experts held here a token meets, **under uniform routing**, ``experts per
    token x held / router outputs`` (one at 4 x 8 / 32): the program's
    ``routing_stats`` says how far a batch is from that.  No shared expert.
    Beside them causal attention over the causal half (``flash_attn_work``'s
    products, forward x 3) in the attention layers."""
    e = sizes["hidden_size"]
    hd = e // sizes["num_attention_heads"]
    dq, dkv = sizes["num_attention_heads"] * hd, sizes["num_key_value_heads"] * hd
    layers = _layers(sizes)
    ld = min(sizes["num_dense_layers"], len(layers))
    lx = len(layers) - ld
    met = sizes["num_experts_per_tok"] * sizes["num_experts"] / sizes["router_outputs"]
    per_token = (layers.count("conv") * (4 * e * e + e * sizes["conv_L_cache"])
                 + layers.count("attn") * (2 * e * dq + 2 * e * dkv)
                 + ld * 3 * e * sizes["intermediate_size"]
                 + lx * (e * sizes["router_outputs"] + met * 3 * e * sizes["moe_intermediate_size"])
                 + e * sizes["vocab_size"])
    attn = 3 * flash_attn_work(sizes, batch, seq)["_fwd_kernel"]["flops"] * layers.count("attn")
    return float(6 * per_token * batch * seq + attn)
