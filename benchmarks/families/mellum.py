"""The Mellum family (``model_type`` ``mellum``: Mellum2-12B-A2.5B):
sliding-window attention layers beside global ones in a published pattern,
each kind with a rotary table of its own (plain | YaRN-scaled), per-head q / k
norms, two norms a layer, softmax-routed experts in every layer of which the
chip holds a share, no shared expert, no dense FFN, an untied head of which
the chip holds a slice of rows; ``torchft_tpu/models/mellum.py`` trains it.
The members are those ``families/llama_dense.py`` lists; the plain reference
is ``reference/mellum.py``, whose text holds the layers' equations.

A configuration keeps ``layer_types``, ``mlp_layer_types`` and both entries of
``rope_parameters`` whole as published: the layers up to ``num_hidden_layers``
are run.  ``num_experts`` counts the experts held here (their published ids
are ``held_expert_ids``) and ``router_outputs`` the experts the router scores,
which is never cut.  ``intermediate_size``, ``max_window_layers``,
``use_sliding_window`` and ``max_position_embeddings`` are read by no layer
(every FFN is the expert layer; ``layer_types`` says which layers have the
window; a row is no longer than the scaling rule's original length).

The compiled step that lets go of the chip's memory before the reference
runs and the device trace by the program's scopes (``scope_ms`` /
``scope_rows``) are ``families/kimi_linear.py``'s; the flash kernels'
operations and bytes, the windowed ones by the band's live pairs, are
``families/afmoe.py``'s (the same kernels at the same head width)."""

from __future__ import annotations

import importlib.util
from typing import Any, Dict, Optional

import numpy as np

from benchmarks.families import afmoe as _windowed
from benchmarks.families import kimi_linear as _shared
from benchmarks.reference import mellum as _reference

STACKED = ("local", "global", "moe")
CUT_KEYS = {"layers": "num_hidden_layers", "experts": "num_experts", "vocab": "vocab_size"}
# heads, their width, experts per token and the router's outputs are widths
# here: the router scores every published expert whichever of them live on
# this chip
WIDTH_KEYS = ("num_attention_heads", "num_key_value_heads", "head_dim", "num_experts_per_tok",
              "router_outputs")
ASSUMED_KEYS = ("remat", "remat_policy", "attn_impl", "held_expert_ids", "expert_slack")

scope_rows, scope_ms = _shared.scope_rows, _shared.scope_ms
aot_prepare = _shared.aot_prepare
FLASH_KERNELS, FLASH_WINDOW_KERNELS = _windowed.FLASH_KERNELS, _windowed.FLASH_WINDOW_KERNELS
flash_attn_work = _windowed.flash_attn_work
# ``layer_types``' two names, and ``local`` or ``global`` for each layer run
_KIND, _layers = _windowed._KIND, _windowed._layers


def layer_pattern(sizes: Dict[str, Any]) -> Dict[str, int]:
    """No leading dense layer; the published ratio is read from
    ``layer_types``: a global layer every ``period`` layers (the widest
    spacing of ``full_attention``: three window layers to one global)."""
    full = [i for i, kind in enumerate(sizes["layer_types"]) if kind == "full_attention"]
    gaps = [b - a for a, b in zip(full, full[1:])]
    return {"leading_dense": 0, "period": max(gaps) if gaps else len(sizes["layer_types"])}


def check(sizes: Dict[str, Any]) -> None:
    if importlib.util.find_spec("torchft_tpu.models.mellum") is None:
        raise ValueError("this checkout's program has no models/mellum.py")
    types = sizes["layer_types"]
    if len(types) < sizes["num_hidden_layers"] or set(types) - set(_KIND):
        raise ValueError("layer_types names sliding_attention or full_attention for every layer run")
    if set(sizes["mlp_layer_types"]) != {"sparse"} or len(sizes["mlp_layer_types"]) != len(types):
        raise ValueError("mlp_layer_types is sparse for every layer: models/mellum.py has no dense FFN")
    fixed = {"tie_word_embeddings": False, "norm_topk_prob": True, "attention_bias": False,
             "hidden_act": "silu"}
    wrong = {k: sizes[k] for k, v in fixed.items() if sizes[k] != v}
    if wrong:
        raise ValueError(f"models/mellum.py expresses {fixed} only; the sizes have {wrong}")
    rules = sizes["rope_parameters"]
    if set(rules) != set(_KIND) or any(rule["rope_type"] not in ("default", "yarn") for rule in rules.values()):
        raise ValueError("rope_parameters has a default or a yarn rule for sliding_attention and for full_attention")
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

    from torchft_tpu.models import mellum

    def rule(published: Dict[str, Any]) -> Any:
        if published["rope_type"] == "default":
            return mellum.RopeRule("default", theta=float(published["rope_theta"]))
        return mellum.RopeRule(
            "yarn", theta=float(published["rope_theta"]), factor=float(published["factor"]),
            original_length=published["original_max_position_embeddings"],
            beta_fast=float(published["beta_fast"]), beta_slow=float(published["beta_slow"]),
            attention_factor=float(published["attention_factor"]))

    return mellum.MellumConfig(
        vocab_size=sizes["vocab_size"], d_model=sizes["hidden_size"],
        n_layers=sizes["num_hidden_layers"], layer_types=tuple(sizes["layer_types"]),
        n_heads=sizes["num_attention_heads"], n_kv_heads=sizes["num_key_value_heads"],
        head_dim=sizes["head_dim"], sliding_window=sizes["sliding_window"],
        rope_local=rule(sizes["rope_parameters"]["sliding_attention"]),
        rope_global=rule(sizes["rope_parameters"]["full_attention"]),
        d_expert=sizes["moe_intermediate_size"], n_routed_experts=sizes["router_outputs"],
        experts_per_token=sizes["num_experts_per_tok"],
        held_experts=tuple(sizes["held_expert_ids"]), expert_slack=sizes["expert_slack"],
        rms_norm_eps=sizes["rms_norm_eps"], dtype=jnp.dtype(sizes["compute_dtype"]),
        param_dtype=jnp.dtype(sizes["param_dtype"]), remat=sizes["remat"],
        remat_policy=sizes["remat_policy"], attn_impl=sizes["attn_impl"])


def make_grad_step(sizes: Dict[str, Any], seq_len: int) -> Any:
    """The program's step, whose compiled form can be released before the
    reference runs (``families/kimi_linear.py`` says why)."""
    from torchft_tpu.models import mellum

    return _shared._GradStep(mellum.make_grad_step(_program_config(sizes)))


def reference_loss(params: Any, tokens: Any, sizes: Dict[str, Any],
                   operand_dtype: Optional[str] = None) -> Any:
    """The plain reference's loss (``reference/mellum.py``).  Tracing it
    releases the program's compiled steps: the window is over by then."""
    _shared._release_compiled(of_ended_threads_only=False)
    return _reference.loss_fn(params, tokens, sizes, operand_dtype)


def make_routing_stats(sizes: Dict[str, Any]) -> Any:
    """The program's jitted ``routing_stats(params, tokens)``: how far a batch
    is from the uniform routing ``flops_per_step`` counts on, and how many
    tokens found no expert here (their FFN output is zero: no shared expert)."""
    from torchft_tpu.models import mellum

    return mellum.make_routing_stats(_program_config(sizes))


def program_init_shapes(sizes: Dict[str, Any]) -> Any:
    import jax

    from torchft_tpu.models import mellum

    cfg = _program_config(sizes)
    return jax.eval_shape(lambda k: mellum.init_params(k, cfg), jax.random.PRNGKey(0))


def weight_shapes(sizes: Dict[str, Any]) -> Dict[str, Any]:
    e, v, hd = sizes["hidden_size"], sizes["vocab_size"], sizes["head_dim"]
    dq, dkv = sizes["num_attention_heads"] * hd, sizes["num_key_value_heads"] * hd
    fx, held, outs = sizes["moe_intermediate_size"], sizes["num_experts"], sizes["router_outputs"]
    layers = _layers(sizes)
    lx = len(layers)

    def attention(n):
        return {"input_norm": (n, e), "wq": (n, e, dq), "wk": (n, e, dkv), "wv": (n, e, dkv),
                "q_norm": (n, hd), "k_norm": (n, hd), "wo": (n, dq, e)}

    return {
        "embed": (v, e), "head": (e, v), "final_norm": (e,),
        "local": attention(layers.count("local")), "global": attention(layers.count("global")),
        "moe": {"post_attention_norm": (lx, e), "router": (lx, e, outs), "w_gate": (lx, held, e, fx),
                "w_up": (lx, held, e, fx), "w_down": (lx, held, fx, e)},
    }


def n_params(sizes: Dict[str, Any]) -> int:
    """Trained parameters by the shapes: at the published sizes 12,149,923,072,
    the published 12B; the router has no bias and no buffer."""
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


def flops_per_step(sizes: Dict[str, Any], batch: int, seq: int) -> float:
    """Model FLOPs of forward + backward (= 3x forward) for ``batch`` rows;
    recomputation under remat is not counted.

    Six a token for every matmul parameter the token meets: the four
    projections of every attention layer, the router, the head; of the routed
    experts held here a token meets, **under uniform routing**, ``experts per
    token x held / router outputs`` (two at 8 x 16 / 64): the program's
    ``routing_stats`` says how far a batch is from that.  No shared expert, no
    dense FFN.  Beside them attention (``flash_attn_work``'s products, forward
    x 3): a global layer over the causal half, a window layer over the pairs
    inside its band only."""
    e, hd = sizes["hidden_size"], sizes["head_dim"]
    dq, dkv = sizes["num_attention_heads"] * hd, sizes["num_key_value_heads"] * hd
    layers = _layers(sizes)
    met = sizes["num_experts_per_tok"] * sizes["num_experts"] / sizes["router_outputs"]
    per_token = (len(layers) * (2 * e * dq + 2 * e * dkv + e * sizes["router_outputs"]
                                + met * 3 * e * sizes["moe_intermediate_size"])
                 + e * sizes["vocab_size"])
    work = flash_attn_work(sizes, batch, seq)
    attn = 3 * (work["_fwd_kernel"]["flops"] * layers.count("global")
                + work["_fwd_window_kernel"]["flops"] * layers.count("local"))
    return float(6 * per_token * batch * seq + attn)
