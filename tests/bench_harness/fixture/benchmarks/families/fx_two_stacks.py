"""A fixture family, in no model's name: what a second architecture brings to
the benchmark by files alone.  Two kinds of layer in two stacks (leading
dense layers, then expert layers), routed experts of which this chip holds a
share, a shared expert, an untied output head.  The program under test is the
plain ``jax.numpy`` model in this file, in the configuration's compute type;
its plain float32 reference is ``reference/fx_two_stacks.py`` and imports
nothing from here.  The members are those ``families/llama_dense.py`` lists."""

from __future__ import annotations

from typing import Any, Dict

import numpy as np

from benchmarks.reference.fx_two_stacks import loss_fn as reference_loss  # noqa: F401

STACKED = ("dense_blocks", "expert_blocks")
CUT_KEYS = {"layers": "n_layers", "experts": "n_routed_experts", "vocab": "vocab_rows"}
# experts per token and the router's outputs are widths: the router scores
# every published expert whichever of them live here
WIDTH_KEYS = ("experts_per_token", "router_width", "n_shared_experts")
ASSUMED_KEYS = ()


def layer_pattern(sizes: Dict[str, Any]) -> Dict[str, int]:
    return {"leading_dense": sizes["n_leading_dense"], "period": 1}


def check(sizes: Dict[str, Any]) -> None:
    if not sizes["n_leading_dense"] < sizes["n_layers"]:
        raise ValueError("no expert layer is left after the leading dense ones")
    if not sizes["experts_per_token"] <= sizes["n_routed_experts"] <= sizes["router_width"]:
        raise ValueError("the router scores router_width experts, of which n_routed_experts live here")
    if sizes["tie_head"] or sizes["n_shared_experts"] != 1:
        raise ValueError("the program has an untied head and one shared expert")


def weight_shapes(sizes: Dict[str, Any]) -> Dict[str, Any]:
    e, f, fx = sizes["model_width"], sizes["ffn_width"], sizes["expert_width"]
    ld = sizes["n_leading_dense"]
    lx = sizes["n_layers"] - ld
    x, r, v = sizes["n_routed_experts"], sizes["router_width"], sizes["vocab_rows"]
    return {
        "embed": (v, e), "head": (e, v), "final_norm": (e,),
        "dense_blocks": {"norm": (ld, e), "w_gate": (ld, e, f), "w_up": (ld, e, f),
                         "w_down": (ld, f, e)},
        "expert_blocks": {"norm": (lx, e), "router": (lx, e, r),
                          "x_gate": (lx, x, e, fx), "x_up": (lx, x, e, fx), "x_down": (lx, x, fx, e),
                          "s_gate": (lx, e, fx), "s_up": (lx, e, fx), "s_down": (lx, fx, e)},
    }


def _leaves(shapes: Any) -> Any:
    import jax

    return jax.tree_util.tree_leaves(shapes, is_leaf=lambda s: isinstance(s, tuple))


def n_params(sizes: Dict[str, Any]) -> int:
    return sum(int(np.prod(s)) for s in _leaves(weight_shapes(sizes)))


def make_weights_fn(sizes: Dict[str, Any]) -> Any:
    """``key -> weights``, the benchmark's own: norms are ones, the rest
    normal over the square root of the fan-in."""
    import jax
    import jax.numpy as jnp

    shapes = weight_shapes(sizes)
    pd = jnp.dtype(sizes["param_dtype"])

    def make(key):
        flat, tree = jax.tree_util.tree_flatten_with_path(
            shapes, is_leaf=lambda s: isinstance(s, tuple))
        out = []
        for i, (path, shape) in enumerate(flat):
            name = str(getattr(path[-1], "key", path[-1]))
            if name.endswith("norm"):
                out.append(jnp.ones(shape, pd))
            else:
                fan_in = shape[-2] if name != "embed" else 2500
                out.append(jax.random.normal(jax.random.fold_in(key, i), shape, pd)
                           / np.sqrt(fan_in))
        return jax.tree_util.tree_unflatten(tree, out)

    return make


# ---- the program under test: this fixture's own model ----------------------

def program_init(key: Any, sizes: Dict[str, Any]) -> Any:
    """The program's own initialiser (zeros; the layout is what counts)."""
    import jax.numpy as jnp

    pd = jnp.dtype(sizes["param_dtype"])
    e, f, fx = sizes["model_width"], sizes["ffn_width"], sizes["expert_width"]
    ld, lx = sizes["n_leading_dense"], sizes["n_layers"] - sizes["n_leading_dense"]
    x, r, v = sizes["n_routed_experts"], sizes["router_width"], sizes["vocab_rows"]
    z = lambda *shape: jnp.zeros(shape, pd)  # noqa: E731
    return {
        "embed": z(v, e), "head": z(e, v), "final_norm": z(e),
        "dense_blocks": {"norm": z(ld, e), "w_gate": z(ld, e, f), "w_up": z(ld, e, f),
                         "w_down": z(ld, f, e)},
        "expert_blocks": {"norm": z(lx, e), "router": z(lx, e, r),
                          "x_gate": z(lx, x, e, fx), "x_up": z(lx, x, e, fx),
                          "x_down": z(lx, x, fx, e),
                          "s_gate": z(lx, e, fx), "s_up": z(lx, e, fx), "s_down": z(lx, fx, e)},
    }


def program_init_shapes(sizes: Dict[str, Any]) -> Any:
    import jax

    return jax.eval_shape(lambda k: program_init(k, sizes), jax.random.PRNGKey(0))


def _program_loss(params: Any, tokens: Any, sizes: Dict[str, Any]) -> Any:
    import jax
    import jax.numpy as jnp

    cd = jnp.dtype(sizes["compute_dtype"])
    eps, k = sizes["norm_eps"], sizes["experts_per_token"]
    held = sizes["n_routed_experts"]

    def norm(x, w):
        x32 = x.astype(jnp.float32)
        return (x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps) * w).astype(cd)

    def glu(h, gate, up, down):
        return (jax.nn.silu(h @ gate.astype(cd)) * (h @ up.astype(cd))) @ down.astype(cd)

    def dense_layer(x, p):
        with jax.named_scope("fx_dense"):
            return x + glu(norm(x, p["norm"]), p["w_gate"], p["w_up"], p["w_down"]), None

    def expert_layer(x, p):
        h = norm(x, p["norm"])
        with jax.named_scope("fx_router"):
            scores = h.astype(jnp.float32) @ p["router"]  # over every published expert
            top, where = jax.lax.top_k(scores, k)
            weight = jax.nn.softmax(top, axis=-1)
            # the part of the result the experts held here give: the first `held`
            gates = jnp.sum(jax.nn.one_hot(where, held, dtype=jnp.float32) * weight[..., None], -2)
        with jax.named_scope("fx_experts"):
            each = jax.vmap(lambda g, u, d: glu(h, g, u, d))(p["x_gate"], p["x_up"], p["x_down"])
            routed = jnp.einsum("xbte,btx->bte", each.astype(jnp.float32), gates).astype(cd)
        with jax.named_scope("fx_shared"):
            shared = glu(h, p["s_gate"], p["s_up"], p["s_down"])
        return x + routed + shared, None

    x = params["embed"].astype(cd)[tokens]
    x, _ = jax.lax.scan(dense_layer, x, params["dense_blocks"])
    x, _ = jax.lax.scan(expert_layer, x, params["expert_blocks"])
    logits = (norm(x, params["final_norm"]) @ params["head"].astype(cd)).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    return -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1).mean()


def make_grad_step(sizes: Dict[str, Any], seq_len: int) -> Any:
    import jax

    def step(params, tokens):
        return jax.value_and_grad(_program_loss)(params, tokens, sizes)

    return jax.jit(step)


def flops_per_step(sizes: Dict[str, Any], batch: int, seq: int) -> float:
    """Model FLOPs of forward + backward: 6 per token and matmul parameter a
    token meets.  Every token meets the dense layers, the router, the shared
    expert and the head; of the experts held here it meets, on average, the
    share of the router's choices that fall on them."""
    e, f, fx = sizes["model_width"], sizes["ffn_width"], sizes["expert_width"]
    ld, lx = sizes["n_leading_dense"], sizes["n_layers"] - sizes["n_leading_dense"]
    met = sizes["experts_per_token"] * sizes["n_routed_experts"] / sizes["router_width"]
    per_token = (ld * 3 * e * f + lx * (e * sizes["router_width"] + (1 + met) * 3 * e * fx)
                 + e * sizes["vocab_rows"])
    return float(6 * per_token * batch * seq)
