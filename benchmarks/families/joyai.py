"""The JoyAI-LLM-Flash family (``model_type`` ``joyai_llm_flash``, the keys of
the DeepSeek-V3 modelling code): latent attention in every layer with the
query through a latent of its own and a rotary part on every head, a leading
dense SwiGLU FFN then sigmoid-routed experts of which the chip holds a share,
a shared expert, an untied head of which the chip holds a slice of rows, and a
multi-token-prediction module behind the trunk that shares embedding and head
with it; ``torchft_tpu/models/joyai.py`` trains it.  The members are those
``families/llama_dense.py`` lists; the plain reference is
``reference/joyai.py``, whose text holds the layers' equations.

``n_routed_experts`` counts the experts held here (their published ids are
``held_expert_ids``) and ``router_outputs`` the experts the router scores,
which is never cut; the module (``num_nextn_predict_layers``) is never cut
either.  ``head_dim`` and ``qk_head_dim`` are published and used by nothing:
attention has the latent layer's widths.

The compiled step that lets go of the chip's memory before the reference
runs and the device trace by the program's scopes (``scope_ms`` /
``scope_rows``) are ``families/kimi_linear.py``'s; ``flash_attn_work`` gives
the operations and bytes of the three causal flash kernels at 192 / 128."""

from __future__ import annotations

import importlib.util
from typing import Any, Dict, Optional

import numpy as np

from benchmarks.families import kimi_linear as _shared
from benchmarks.reference import joyai as _reference

STACKED = ("mla", "dense", "moe", "mtp")
CUT_KEYS = {"layers": "num_hidden_layers", "experts": "n_routed_experts", "vocab": "vocab_size"}
# heads, experts per token, the router's outputs and the module are widths
# here: the router scores every published expert whichever of them live on
# this chip, and a model without its module is another model
WIDTH_KEYS = ("num_attention_heads", "num_key_value_heads", "head_dim", "num_experts_per_tok",
              "router_outputs", "n_shared_experts", "num_nextn_predict_layers")
ASSUMED_KEYS = ("remat", "remat_policy", "attn_impl", "held_expert_ids", "expert_slack",
                "mtp_loss_weight")

scope_rows, scope_ms = _shared.scope_rows, _shared.scope_ms
aot_prepare = _shared.aot_prepare
flash_attn_work = _shared.flash_attn_work  # queries and keys of nope + rope, values of v_head_dim
FLASH_KERNELS = _shared.FLASH_KERNELS


def layer_pattern(sizes: Dict[str, Any]) -> Dict[str, int]:
    """The leading dense layers, then one kind of layer: a period of one."""
    return {"leading_dense": sizes["first_k_dense_replace"], "period": 1}


def check(sizes: Dict[str, Any]) -> None:
    if importlib.util.find_spec("torchft_tpu.models.joyai") is None:
        raise ValueError("this checkout's program has no models/joyai.py")
    fixed = {"tie_word_embeddings": False, "attention_bias": False, "scoring_func": "sigmoid",
             "topk_method": "noaux_tc", "norm_topk_prob": True, "n_shared_experts": 1, "n_group": 1,
             "topk_group": 1, "moe_layer_freq": 1, "num_nextn_predict_layers": 1, "hidden_act": "silu",
             "rope_scaling": None, "rope_interleave": True}
    wrong = {k: sizes[k] for k, v in fixed.items() if sizes[k] != v}
    if wrong:
        raise ValueError(f"models/joyai.py expresses {fixed} only; the sizes have {wrong}")
    if sizes["num_key_value_heads"] != sizes["num_attention_heads"]:
        raise ValueError("latent attention has a key-value head a query head")
    if sizes["qk_rope_head_dim"] % 2:
        raise ValueError("the rotary turns pairs: qk_rope_head_dim is even")
    held = sizes["held_expert_ids"]
    if len(held) != sizes["n_routed_experts"] or len(set(held)) != len(held) or not all(
            0 <= e < sizes["router_outputs"] for e in held):
        raise ValueError("held_expert_ids names n_routed_experts distinct experts of the router's outputs")
    if sizes["num_experts_per_tok"] > sizes["router_outputs"]:
        raise ValueError("more experts a token than the router scores")


def _program_config(sizes: Dict[str, Any]) -> Any:
    import jax.numpy as jnp

    from torchft_tpu.models import joyai

    return joyai.JoyAIConfig(
        vocab_size=sizes["vocab_size"], d_model=sizes["hidden_size"],
        n_layers=sizes["num_hidden_layers"], first_k_dense=sizes["first_k_dense_replace"],
        n_heads=sizes["num_attention_heads"], q_lora_rank=sizes["q_lora_rank"],
        kv_lora_rank=sizes["kv_lora_rank"], qk_nope_head_dim=sizes["qk_nope_head_dim"],
        qk_rope_head_dim=sizes["qk_rope_head_dim"], v_head_dim=sizes["v_head_dim"],
        rope_theta=float(sizes["rope_theta"]), rope_interleave=sizes["rope_interleave"],
        d_ff=sizes["intermediate_size"], d_expert=sizes["moe_intermediate_size"],
        n_routed_experts=sizes["router_outputs"], experts_per_token=sizes["num_experts_per_tok"],
        held_experts=tuple(sizes["held_expert_ids"]),
        routed_scaling_factor=sizes["routed_scaling_factor"], expert_slack=sizes["expert_slack"],
        n_predict_layers=sizes["num_nextn_predict_layers"], mtp_loss_weight=sizes["mtp_loss_weight"],
        rms_norm_eps=sizes["rms_norm_eps"], dtype=jnp.dtype(sizes["compute_dtype"]),
        param_dtype=jnp.dtype(sizes["param_dtype"]), remat=sizes["remat"],
        remat_policy=sizes["remat_policy"], attn_impl=sizes["attn_impl"])


def make_grad_step(sizes: Dict[str, Any], seq_len: int) -> Any:
    """The program's step, whose compiled form can be released before the
    reference runs (``families/kimi_linear.py`` says why)."""
    from torchft_tpu.models import joyai

    return _shared._GradStep(joyai.make_grad_step(_program_config(sizes)))


def reference_loss(params: Any, tokens: Any, sizes: Dict[str, Any],
                   operand_dtype: Optional[str] = None) -> Any:
    """The plain reference's loss (``reference/joyai.py``).  Tracing it
    releases the program's compiled steps: the window is over by then."""
    _shared._release_compiled(of_ended_threads_only=False)
    return _reference.loss_fn(params, tokens, sizes, operand_dtype)


def make_routing_stats(sizes: Dict[str, Any]) -> Any:
    """The program's jitted ``routing_stats(params, tokens)``, the module's
    layer last: how far a batch is from the uniform routing
    ``flops_per_step`` counts on."""
    from torchft_tpu.models import joyai

    return joyai.make_routing_stats(_program_config(sizes))


def program_init_shapes(sizes: Dict[str, Any]) -> Any:
    import jax

    from torchft_tpu.models import joyai

    cfg = _program_config(sizes)
    return jax.eval_shape(lambda k: joyai.init_params(k, cfg), jax.random.PRNGKey(0))


def weight_shapes(sizes: Dict[str, Any]) -> Dict[str, Any]:
    e, v, nh = sizes["hidden_size"], sizes["vocab_size"], sizes["num_attention_heads"]
    qr, rank = sizes["q_lora_rank"], sizes["kv_lora_rank"]
    nope, rope, dv = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"], sizes["v_head_dim"]
    f, fx = sizes["intermediate_size"], sizes["moe_intermediate_size"]
    held, outs = sizes["n_routed_experts"], sizes["router_outputs"]
    layers = sizes["num_hidden_layers"]
    ld = min(sizes["first_k_dense_replace"], layers)

    def attention(n):
        return {"attn_norm": (n, e), "q_a": (n, e, qr), "q_norm": (n, qr), "q_b": (n, qr, nh * (nope + rope)),
                "kv_a": (n, e, rank + rope), "kv_norm": (n, rank), "kv_b": (n, rank, nh * (nope + dv)),
                "wo": (n, nh * dv, e)}

    def experts(n):
        return {"mlp_norm": (n, e), "router": (n, e, outs),
                "w_gate": (n, held, e, fx), "w_up": (n, held, e, fx), "w_down": (n, held, fx, e),
                "shared_gate": (n, e, fx), "shared_up": (n, e, fx), "shared_down": (n, fx, e)}

    return {
        "embed": (v, e), "head": (e, v), "final_norm": (e,),
        "mla": attention(layers),
        "dense": {"mlp_norm": (ld, e), "w_gate": (ld, e, f), "w_up": (ld, e, f), "w_down": (ld, f, e)},
        "moe": experts(layers - ld),
        "mtp": {"e_norm": (1, e), "h_norm": (1, e), "w_eh": (1, 2 * e, e), "out_norm": (1, e),
                **attention(1), **experts(1)},
    }


def n_params(sizes: Dict[str, Any]) -> int:
    """Trained parameters by the shapes, the module's among them.  The
    router's correction bias (``router_outputs`` a layer) is a buffer and not
    counted."""
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


# ---- operations ---------------------------------------------------------------

def flops_per_step(sizes: Dict[str, Any], batch: int, seq: int) -> float:
    """Model FLOPs of forward + backward (= 3x forward) for ``batch`` rows;
    recomputation under remat is not counted.

    Six a token for every matmul parameter the token meets: the five
    projections of latent attention in every block (the trunk's and the
    module's), the dense FFN, the router, the shared expert, the module's
    ``W_eh``, and the head **twice** (both prediction depths go through it);
    of the routed experts held here a token meets, **under uniform routing**,
    ``experts per token x held / router outputs`` (a quarter of one at 8 x 8 /
    256): the program's ``routing_stats`` says how far a batch is from that.
    Beside them causal attention over the causal half in every block
    (``flash_attn_work``'s products, forward x 3).  The module is counted
    over all ``seq`` positions, as the program runs it; the one position a
    row that has no target is 1 / ``seq`` of its work."""
    e, nh = sizes["hidden_size"], sizes["num_attention_heads"]
    qr, rank = sizes["q_lora_rank"], sizes["kv_lora_rank"]
    nope, rope, dv = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"], sizes["v_head_dim"]
    fx = sizes["moe_intermediate_size"]
    layers = sizes["num_hidden_layers"]
    ld = min(sizes["first_k_dense_replace"], layers)
    mtp = sizes["num_nextn_predict_layers"]
    met = sizes["num_experts_per_tok"] * sizes["n_routed_experts"] / sizes["router_outputs"]
    per_mla = e * qr + qr * nh * (nope + rope) + e * (rank + rope) + rank * nh * (nope + dv) + nh * dv * e
    per_moe = e * sizes["router_outputs"] + (1 + met) * 3 * e * fx
    per_token = ((layers + mtp) * per_mla + ld * 3 * e * sizes["intermediate_size"]
                 + (layers - ld + mtp) * per_moe + mtp * 2 * e * e + (1 + mtp) * e * sizes["vocab_size"])
    attn = 3 * flash_attn_work(sizes, batch, seq)["_fwd_kernel"]["flops"] * (layers + mtp)
    return float(6 * per_token * batch * seq + attn)
