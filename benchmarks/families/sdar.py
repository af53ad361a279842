"""The SDAR family (``model_type`` ``sdar_moe``: SDAR-30B-A3B-Chat): the
Qwen3-MoE block (grouped-query heads of a width of their own, per-head q / k
norms, softmax-routed experts in every layer of which the chip holds a share,
no shared expert, an untied head of which the chip holds a slice of rows)
trained as a block-diffusion model: a row run twice, noised beside clean,
under a three-part mask at block granularity, a loss over the masked positions
without a shift; ``torchft_tpu/models/sdar.py`` trains it.  The members are
those ``families/llama_dense.py`` lists; the plain reference is
``reference/sdar.py``, whose text holds the equations.

``num_experts`` counts the experts held here (their published ids are
``held_expert_ids``) and ``router_outputs`` the experts the router scores,
which is never cut.  ``intermediate_size``, ``max_window_layers``,
``sliding_window``, ``use_sliding_window`` and ``max_position_embeddings`` are
read by no layer.  What ``config.json`` has no key for (``block_length``,
``mask_token_id``, ``noise_seed``, ``t_eps``) is under ``assumed``.

The compiled step that lets go of the chip's memory before the reference
runs and the device trace by the program's scopes (``scope_ms`` /
``scope_rows``) are ``families/kimi_linear.py``'s.  ``flash_block_work`` gives
the operations and bytes of the flash kernels' calls under the block masks
(``FLASH_BLOCK_KERNELS``), by the masks' live pairs."""

from __future__ import annotations

import importlib.util
from typing import Any, Dict, Optional

import numpy as np

from benchmarks.families import kimi_linear as _shared
from benchmarks.reference import sdar as _reference

STACKED = ("attn", "moe")
CUT_KEYS = {"layers": "num_hidden_layers", "experts": "num_experts", "vocab": "vocab_size"}
# heads, their width, experts per token and the router's outputs are widths
# here: the router scores every published expert whichever of them live on
# this chip
WIDTH_KEYS = ("num_attention_heads", "num_key_value_heads", "head_dim", "num_experts_per_tok",
              "router_outputs")
ASSUMED_KEYS = ("remat", "remat_policy", "attn_impl", "held_expert_ids", "expert_slack",
                "block_length", "mask_token_id", "noise_seed", "t_eps")

scope_rows, scope_ms = _shared.scope_rows, _shared.scope_ms
aot_prepare = _shared.aot_prepare


def layer_pattern(sizes: Dict[str, Any]) -> Dict[str, int]:
    """No leading dense layer and every layer alike: a period of one."""
    return {"leading_dense": 0, "period": 1}


def check(sizes: Dict[str, Any]) -> None:
    if importlib.util.find_spec("torchft_tpu.models.sdar") is None:
        raise ValueError("this checkout's program has no models/sdar.py")
    fixed = {"tie_word_embeddings": False, "norm_topk_prob": True, "attention_bias": False,
             "hidden_act": "silu", "rope_scaling": None, "mlp_only_layers": [],
             "decoder_sparse_step": 1, "use_sliding_window": False}
    wrong = {k: sizes[k] for k, v in fixed.items() if sizes[k] != v}
    if wrong:
        raise ValueError(f"models/sdar.py expresses {fixed} only; the sizes have {wrong}")
    if sizes["num_attention_heads"] % sizes["num_key_value_heads"]:
        raise ValueError("query heads are a multiple of key-value heads")
    held = sizes["held_expert_ids"]
    if len(held) != sizes["num_experts"] or len(set(held)) != len(held) or not all(
            0 <= e < sizes["router_outputs"] for e in held):
        raise ValueError("held_expert_ids names num_experts distinct experts of the router's outputs")
    if sizes["num_experts_per_tok"] > sizes["router_outputs"]:
        raise ValueError("more experts a token than the router scores")
    if not 0 <= sizes["mask_token_id"] < sizes["vocab_size"]:
        raise ValueError("mask_token_id is a row of the vocabulary held here")
    if sizes["block_length"] < 1 or 128 % sizes["block_length"]:
        raise ValueError("block_length divides the flash kernels' 128 rows")
    if not 0.0 < sizes["t_eps"] < 1.0:
        raise ValueError("t_eps lies between 0 and 1")


def _program_config(sizes: Dict[str, Any]) -> Any:
    import jax.numpy as jnp

    from torchft_tpu.models import sdar

    return sdar.SDARConfig(
        vocab_size=sizes["vocab_size"], d_model=sizes["hidden_size"],
        n_layers=sizes["num_hidden_layers"], n_heads=sizes["num_attention_heads"],
        n_kv_heads=sizes["num_key_value_heads"], head_dim=sizes["head_dim"],
        rope_theta=float(sizes["rope_theta"]), d_expert=sizes["moe_intermediate_size"],
        n_routed_experts=sizes["router_outputs"], experts_per_token=sizes["num_experts_per_tok"],
        held_experts=tuple(sizes["held_expert_ids"]), expert_slack=sizes["expert_slack"],
        rms_norm_eps=sizes["rms_norm_eps"], block_length=sizes["block_length"],
        mask_token_id=sizes["mask_token_id"], noise_seed=sizes["noise_seed"], t_eps=sizes["t_eps"],
        dtype=jnp.dtype(sizes["compute_dtype"]), param_dtype=jnp.dtype(sizes["param_dtype"]),
        remat=sizes["remat"], remat_policy=sizes["remat_policy"], attn_impl=sizes["attn_impl"])


def make_grad_step(sizes: Dict[str, Any], seq_len: int) -> Any:
    """The program's step, whose compiled form can be released before the
    reference runs (``families/kimi_linear.py`` says why)."""
    from torchft_tpu.models import sdar

    return _shared._GradStep(sdar.make_grad_step(_program_config(sizes)))


def reference_loss(params: Any, tokens: Any, sizes: Dict[str, Any],
                   operand_dtype: Optional[str] = None) -> Any:
    """The plain reference's loss (``reference/sdar.py``).  Tracing it
    releases the program's compiled steps: the window is over by then."""
    _shared._release_compiled(of_ended_threads_only=False)
    return _reference.loss_fn(params, tokens, sizes, operand_dtype)


def make_routing_stats(sizes: Dict[str, Any]) -> Any:
    """The program's jitted ``routing_stats(params, tokens)`` over the ``2T``
    positions a step runs: how far a batch is from the uniform routing
    ``flops_per_step`` counts on, how many positions found no expert here, and
    the batch's noise (``masked_share``, each row's ``p``)."""
    from torchft_tpu.models import sdar

    return sdar.make_routing_stats(_program_config(sizes))


def program_init_shapes(sizes: Dict[str, Any]) -> Any:
    import jax

    from torchft_tpu.models import sdar

    cfg = _program_config(sizes)
    return jax.eval_shape(lambda k: sdar.init_params(k, cfg), jax.random.PRNGKey(0))


def weight_shapes(sizes: Dict[str, Any]) -> Dict[str, Any]:
    e, v, hd = sizes["hidden_size"], sizes["vocab_size"], sizes["head_dim"]
    dq, dkv = sizes["num_attention_heads"] * hd, sizes["num_key_value_heads"] * hd
    fx, held, outs = sizes["moe_intermediate_size"], sizes["num_experts"], sizes["router_outputs"]
    n = sizes["num_hidden_layers"]
    return {
        "embed": (v, e), "head": (e, v), "final_norm": (e,),
        "attn": {"input_norm": (n, e), "wq": (n, e, dq), "wk": (n, e, dkv), "wv": (n, e, dkv),
                 "q_norm": (n, hd), "k_norm": (n, hd), "wo": (n, dq, e)},
        "moe": {"post_attention_norm": (n, e), "router": (n, e, outs), "w_gate": (n, held, e, fx),
                "w_up": (n, held, e, fx), "w_down": (n, held, fx, e)},
    }


def n_params(sizes: Dict[str, Any]) -> int:
    """Trained parameters by the shapes: at the published sizes
    30,532,122,624, the published 30B; the router has no bias and no buffer."""
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

# what `ops/flash_attention.py` names its kernels under a block mask: the clean
# copy's block-causal call, and the noised copy's strictly block-causal call
# on the clean keys
FLASH_BLOCK_KERNELS = ("_fwd_block_kernel", "_bwd_kv_block_kernel", "_bwd_q_block_kernel")
FLASH_STRICT_KERNELS = ("_fwd_block_strict_kernel", "_bwd_kv_block_strict_kernel",
                        "_bwd_q_block_strict_kernel")


def live_pairs(seq: int, block: int) -> Dict[str, int]:
    """Query-key pairs a head computes for one row of ``seq`` tokens under
    each of the three masks (``seq / block = n`` blocks): ``clean`` on clean,
    block-causal (``block^2 n (n + 1) / 2``); ``before``, noised on the clean
    blocks before its own (``block^2 n (n - 1) / 2``); ``own``, noised on its
    own noised block (``seq block``).  Together ``seq^2 + seq block`` of the
    ``4 seq^2`` of the plane."""
    n = seq // block
    return {"clean": block * block * n * (n + 1) // 2, "before": block * block * n * (n - 1) // 2,
            "own": seq * block}


def flash_block_work(sizes: Dict[str, Any], batch: int, seq: int) -> Dict[str, Dict[str, float]]:
    """Operations and bytes of one call of each flash kernel under a block
    mask: one layer, ``batch`` rows of ``seq`` tokens (``2 seq`` positions),
    matrix products only (2 / 4 / 3 products a pair in forward / key-value
    backward / query backward, of the head's width each, as
    ``families/afmoe.py`` counts the causal and the windowed calls), over the
    **live** pairs of the call's mask alone (``live_pairs``: a skipped tile is
    neither work done nor work counted).  Bytes: every operand read once and
    every result written once in the compute type (K and V repeated up to the
    query heads), row statistics in float32.  The noised copy's own block
    (``seq block`` pairs a head, a thousandth of the rest) runs outside the
    kernels, as fusions under ``attn.diffusion``, and is not counted here."""
    import jax.numpy as jnp

    d = sizes["head_dim"]
    heads = batch * sizes["num_attention_heads"]
    tile = heads * seq * d * jnp.dtype(sizes["compute_dtype"]).itemsize  # one [T, d] operand of every head
    stat = heads * seq * 4
    pairs = live_pairs(seq, sizes["block_length"])
    work = {}
    for names, mask in ((FLASH_BLOCK_KERNELS, "clean"), (FLASH_STRICT_KERNELS, "before")):
        live = heads * pairs[mask]
        fwd, bwd_kv, bwd_q = names
        work[fwd] = {"flops": 2.0 * 2 * live * d, "bytes": 4.0 * tile + stat}
        work[bwd_kv] = {"flops": 2.0 * 4 * live * d, "bytes": 6.0 * tile + 2 * stat}
        work[bwd_q] = {"flops": 2.0 * 3 * live * d, "bytes": 5.0 * tile + 2 * stat}
    return work


def flops_per_step(sizes: Dict[str, Any], batch: int, seq: int) -> float:
    """Model FLOPs of forward + backward (= 3x forward) for ``batch`` rows of
    ``seq`` tokens, which a step runs as ``2 seq`` positions; recomputation
    under remat is not counted, and what the loss does not need is not
    counted either (nor does the program compute it).

    Six a position for every matmul parameter the position meets.  In every
    layer but the last, both copies meet the four projections, the router and,
    **under uniform routing**, ``experts per token x held / router outputs``
    of an expert (one at 8 x 16 / 128; the program's ``routing_stats`` says
    how far a batch is from that).  In the last layer the noised copy meets
    the same and the clean copy the key and value projections alone: its
    queries, its output projection and its experts reach no logit.  The head
    runs over the noised copy (``seq`` positions).  Beside them attention
    (two products a live pair of the head's width, forward x 3): the three
    masks' ``live_pairs`` in every layer but the last, where the clean copy
    has no query."""
    e, hd = sizes["hidden_size"], sizes["head_dim"]
    dq, dkv = sizes["num_attention_heads"] * hd, sizes["num_key_value_heads"] * hd
    layers = sizes["num_hidden_layers"]
    met = sizes["num_experts_per_tok"] * sizes["num_experts"] / sizes["router_outputs"]
    whole = 2 * e * dq + 2 * e * dkv + e * sizes["router_outputs"] + met * 3 * e * sizes["moe_intermediate_size"]
    per_token = (2 * layers - 1) * whole + 2 * e * dkv + e * sizes["vocab_size"]
    pairs = live_pairs(seq, sizes["block_length"])
    live = layers * (pairs["before"] + pairs["own"]) + (layers - 1) * pairs["clean"]
    attn = 3 * 2.0 * 2 * batch * sizes["num_attention_heads"] * live * hd
    return float(6 * per_token * batch * seq + attn)
