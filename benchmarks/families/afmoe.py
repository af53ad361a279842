"""The AFMoE family (``model_type`` ``afmoe``: Trinity): sliding-window
attention layers beside global ones in a published pattern, gated heads,
norms on both sides of each sub-block, leading dense SwiGLU FFNs then
sigmoid-routed experts of which the chip holds a share, a shared expert, an
untied head of which the chip holds a slice of rows;
``torchft_tpu/models/afmoe.py`` trains it.  The members are those
``families/llama_dense.py`` lists; the plain reference is
``reference/afmoe.py``, whose text holds the layers' equations.

A configuration keeps ``layer_types`` whole as published: the layers up to
``num_hidden_layers`` are run.  ``num_experts`` counts the experts held here
(their published ids are ``held_expert_ids``) and ``router_outputs`` the
experts the router scores, which is never cut.

The compiled step that lets go of the chip's memory before the reference
runs and the device trace by the program's scopes (``scope_ms`` /
``scope_rows``) are ``families/kimi_linear.py``'s; ``flash_attn_work`` gives
the operations and bytes of the flash kernels, the windowed ones by the band."""

from __future__ import annotations

import importlib.util
from typing import Any, Dict, Optional

import numpy as np

from benchmarks.families import kimi_linear as _shared
from benchmarks.reference import afmoe as _reference

STACKED = ("local", "global", "dense", "moe")
CUT_KEYS = {"layers": "num_hidden_layers", "experts": "num_experts", "vocab": "vocab_size"}
# heads, experts per token and the router's outputs are widths here: the
# router scores every published expert whichever of them live on this chip
WIDTH_KEYS = ("num_attention_heads", "num_key_value_heads", "head_dim", "num_experts_per_tok",
              "router_outputs", "num_shared_experts")
ASSUMED_KEYS = ("remat", "remat_policy", "attn_impl", "held_expert_ids", "expert_slack")
_KIND = {"sliding_attention": "local", "full_attention": "global"}

scope_rows, scope_ms = _shared.scope_rows, _shared.scope_ms


def _layers(sizes: Dict[str, Any]) -> "list[str]":
    """``local`` or ``global`` for each layer run."""
    return [_KIND[kind] for kind in sizes["layer_types"][:sizes["num_hidden_layers"]]]


def layer_pattern(sizes: Dict[str, Any]) -> Dict[str, int]:
    """The leading dense layers, then the published ratio: a global layer
    every ``global_attn_every_n_layers`` layers."""
    return {"leading_dense": sizes["num_dense_layers"], "period": sizes["global_attn_every_n_layers"]}


def check(sizes: Dict[str, Any]) -> None:
    if importlib.util.find_spec("torchft_tpu.models.afmoe") is None:
        raise ValueError("this checkout's program has no models/afmoe.py")
    types = sizes["layer_types"]
    if len(types) < sizes["num_hidden_layers"] or set(types) - set(_KIND):
        raise ValueError("layer_types names sliding_attention or full_attention for every layer run")
    every = sizes["global_attn_every_n_layers"]
    if any((kind == "full_attention") != ((i + 1) % every == 0) for i, kind in enumerate(types)):
        raise ValueError("layer_types is not a global layer every global_attn_every_n_layers")
    fixed = {"tie_word_embeddings": False, "score_func": "sigmoid", "route_norm": True,
             "num_shared_experts": 1, "n_group": 1, "topk_group": 1, "num_expert_groups": 1,
             "num_limited_groups": 1, "hidden_act": "silu", "rope_scaling": None}
    wrong = {k: sizes[k] for k, v in fixed.items() if sizes[k] != v}
    if wrong:
        raise ValueError(f"models/afmoe.py expresses {fixed} only; the sizes have {wrong}")
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

    from torchft_tpu.models import afmoe

    return afmoe.AfmoeConfig(
        vocab_size=sizes["vocab_size"], d_model=sizes["hidden_size"],
        n_layers=sizes["num_hidden_layers"], layer_types=tuple(sizes["layer_types"]),
        num_dense_layers=sizes["num_dense_layers"], n_heads=sizes["num_attention_heads"],
        n_kv_heads=sizes["num_key_value_heads"], head_dim=sizes["head_dim"],
        sliding_window=sizes["sliding_window"], rope_theta=float(sizes["rope_theta"]),
        mup_enabled=sizes["mup_enabled"], d_ff=sizes["intermediate_size"],
        d_expert=sizes["moe_intermediate_size"], n_routed_experts=sizes["router_outputs"],
        experts_per_token=sizes["num_experts_per_tok"],
        held_experts=tuple(sizes["held_expert_ids"]), route_scale=sizes["route_scale"],
        expert_slack=sizes["expert_slack"], rms_norm_eps=sizes["rms_norm_eps"],
        dtype=jnp.dtype(sizes["compute_dtype"]), param_dtype=jnp.dtype(sizes["param_dtype"]),
        remat=sizes["remat"], remat_policy=sizes["remat_policy"], attn_impl=sizes["attn_impl"])


def make_grad_step(sizes: Dict[str, Any], seq_len: int) -> Any:
    """The program's step, whose compiled form can be released before the
    reference runs (``families/kimi_linear.py`` says why)."""
    from torchft_tpu.models import afmoe

    return _shared._GradStep(afmoe.make_grad_step(_program_config(sizes)))


def reference_loss(params: Any, tokens: Any, sizes: Dict[str, Any],
                   operand_dtype: Optional[str] = None) -> Any:
    """The plain reference's loss (``reference/afmoe.py``).  Tracing it
    releases the program's compiled steps: the window is over by then."""
    _shared._release_compiled(of_ended_threads_only=False)
    return _reference.loss_fn(params, tokens, sizes, operand_dtype)


def make_routing_stats(sizes: Dict[str, Any]) -> Any:
    """The program's jitted ``routing_stats(params, tokens)``: how far a batch
    is from the uniform routing ``flops_per_step`` counts on."""
    from torchft_tpu.models import afmoe

    return afmoe.make_routing_stats(_program_config(sizes))


def program_init_shapes(sizes: Dict[str, Any]) -> Any:
    import jax

    from torchft_tpu.models import afmoe

    cfg = _program_config(sizes)
    return jax.eval_shape(lambda k: afmoe.init_params(k, cfg), jax.random.PRNGKey(0))


aot_prepare = _shared.aot_prepare


def weight_shapes(sizes: Dict[str, Any]) -> Dict[str, Any]:
    e, v, hd = sizes["hidden_size"], sizes["vocab_size"], sizes["head_dim"]
    dq, dkv = sizes["num_attention_heads"] * hd, sizes["num_key_value_heads"] * hd
    f, fx = sizes["intermediate_size"], sizes["moe_intermediate_size"]
    held, outs = sizes["num_experts"], sizes["router_outputs"]
    layers = _layers(sizes)
    ld = min(sizes["num_dense_layers"], len(layers))
    lx = len(layers) - ld

    def attention(n):
        return {"input_norm": (n, e), "post_attn_norm": (n, e), "wq": (n, e, dq), "wk": (n, e, dkv),
                "wv": (n, e, dkv), "q_norm": (n, hd), "k_norm": (n, hd), "wg": (n, e, dq),
                "wo": (n, dq, e)}

    return {
        "embed": (v, e), "head": (e, v), "final_norm": (e,),
        "local": attention(layers.count("local")), "global": attention(layers.count("global")),
        "dense": {"pre_mlp_norm": (ld, e), "post_mlp_norm": (ld, e), "w_gate": (ld, e, f),
                  "w_up": (ld, e, f), "w_down": (ld, f, e)},
        "moe": {
            "pre_mlp_norm": (lx, e), "post_mlp_norm": (lx, e), "router": (lx, e, outs),
            "w_gate": (lx, held, e, fx), "w_up": (lx, held, e, fx), "w_down": (lx, held, fx, e),
            "shared_gate": (lx, e, fx), "shared_up": (lx, e, fx), "shared_down": (lx, fx, e)},
    }


def n_params(sizes: Dict[str, Any]) -> int:
    """Trained parameters by the shapes.  The router's balancing bias
    (``router_outputs`` a layer) is a buffer and not counted."""
    return sum(int(np.prod(s)) for s in _shared._leaves(weight_shapes(sizes)))


def make_weights_fn(sizes: Dict[str, Any]) -> Any:
    """``key -> weights``, the benchmark's own: matrices normal over the
    square root of the fan-in, norms ones, the embedding 0.02 normal."""
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
                leaf = jax.random.normal(k, shape, pd) / np.sqrt(shape[-2])
            out.append(leaf)
        return jax.tree_util.tree_unflatten(tree, out)

    return make


# ---- operations and bytes ---------------------------------------------------

# what `ops/flash_attention.py` names its kernels: a global layer's calls, and
# a window layer's, which walk the band's tiles alone
FLASH_KERNELS = ("_fwd_kernel", "_bwd_kv_kernel", "_bwd_q_kernel")
FLASH_WINDOW_KERNELS = ("_fwd_window_kernel", "_bwd_kv_window_kernel", "_bwd_q_window_kernel")


def _pairs(seq: int, window: Optional[int] = None) -> int:
    """Query-key pairs a head computes: the causal half, or under a window
    the band inside it (query ``i`` sees ``min(i + 1, window)`` keys)."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def flash_attn_work(sizes: Dict[str, Any], batch: int, seq: int) -> Dict[str, Dict[str, float]]:
    """Operations and bytes of one call of each flash-attention kernel: one
    layer, ``batch`` rows, matrix products only (as ``families/llama_dense.py``
    counts them: 2 / 4 / 3 products a pair in forward / key-value backward /
    query backward, of the head's width each).  The global layer's three
    kernels over the causal half; the window layer's three over the band's
    live pairs only: a tile the window skips is neither work done nor work
    counted, so skipping cannot lift a share.  Bytes: every operand read once
    and every result written once in the compute type (the kernels see K and
    V repeated up to the query heads), row statistics in float32: the least
    any tiling moves (the kernels read a key tile once for every query tile
    that sees it; at a head width of 128 the roofline is bound by the
    operations under either count)."""
    import jax.numpy as jnp

    d = sizes["head_dim"]
    heads = batch * sizes["num_attention_heads"]
    tile = heads * seq * d * jnp.dtype(sizes["compute_dtype"]).itemsize  # one [T, d] operand of every head
    stat = heads * seq * 4
    work = {}
    for names, window in ((FLASH_KERNELS, None), (FLASH_WINDOW_KERNELS, sizes["sliding_window"])):
        pairs = heads * _pairs(seq, window)
        fwd, bwd_kv, bwd_q = names
        work[fwd] = {"flops": 2.0 * 2 * pairs * d, "bytes": 4.0 * tile + stat}
        work[bwd_kv] = {"flops": 2.0 * 4 * pairs * d, "bytes": 6.0 * tile + 2 * stat}
        work[bwd_q] = {"flops": 2.0 * 3 * pairs * d, "bytes": 5.0 * tile + 2 * stat}
    return work


def flops_per_step(sizes: Dict[str, Any], batch: int, seq: int) -> float:
    """Model FLOPs of forward + backward (= 3x forward) for ``batch`` rows;
    recomputation under remat is not counted.

    Six a token for every matmul parameter the token meets: the five
    projections of every attention layer, the dense FFN, the router, the
    shared expert, the head; of the routed experts held here a token meets,
    **under uniform routing**, ``experts per token x held / router outputs``
    (a half of one at 8 x 8 / 128): the program's ``routing_stats`` says how
    far a batch is from that.  Beside them attention (``flash_attn_work``'s
    products, forward x 3): a global layer over the causal half, a window
    layer over the pairs inside its band only."""
    e, hd = sizes["hidden_size"], sizes["head_dim"]
    dq, dkv = sizes["num_attention_heads"] * hd, sizes["num_key_value_heads"] * hd
    layers = _layers(sizes)
    ld = min(sizes["num_dense_layers"], len(layers))
    lx = len(layers) - ld
    met = sizes["num_experts_per_tok"] * sizes["num_experts"] / sizes["router_outputs"]
    per_token = (len(layers) * (3 * e * dq + 2 * e * dkv) + ld * 3 * e * sizes["intermediate_size"]
                 + lx * (e * sizes["router_outputs"] + (1 + met) * 3 * e * sizes["moe_intermediate_size"])
                 + e * sizes["vocab_size"])
    work = flash_attn_work(sizes, batch, seq)
    attn = 3 * (work["_fwd_kernel"]["flops"] * layers.count("global")
                + work["_fwd_window_kernel"]["flops"] * layers.count("local"))
    return float(6 * per_token * batch * seq + attn)
