"""Plain reference for the fixture family ``fx_two_stacks``: float32, every
matrix product at ``Precision.HIGHEST``, one layer after the other in Python
loops, the experts one by one.  Imports nothing of the program.

The model: token embedding; ``n_leading_dense`` layers ``x + down(silu(gate
h) * up h)`` with ``h = rmsnorm(x)``; then expert layers ``x + shared(h) +
sum over the experts held here of gate_e(h) * expert_e(h)``, where the router
scores all ``router_width`` published experts, keeps the ``experts_per_token``
best, softmaxes their scores, and the chip adds the part its own experts (the
first ``n_routed_experts``) give; a final norm and an untied head; mean
cross-entropy of position ``t`` predicting token ``t + 1`` over the rows of
the vocabulary held here.  ``operand_dtype`` rounds the operands of every
matrix product but the router's, for the lower-precision control."""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def loss_fn(params: Any, tokens: jax.Array, sizes: Dict[str, Any],
            operand_dtype: Optional[str] = None) -> jax.Array:
    eps, k, held = sizes["norm_eps"], sizes["experts_per_token"], sizes["n_routed_experts"]

    def rnd(x):
        return x if operand_dtype is None else x.astype(operand_dtype).astype(jnp.float32)

    def mm(x, w):
        return jnp.matmul(rnd(x), rnd(w), precision=HIGHEST)

    def norm(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w

    def glu(h, gate, up, down):
        return mm(jax.nn.silu(mm(h, gate)) * mm(h, up), down)

    x = params["embed"][tokens]
    d = params["dense_blocks"]
    for i in range(d["norm"].shape[0]):
        x = x + glu(norm(x, d["norm"][i]), d["w_gate"][i], d["w_up"][i], d["w_down"][i])
    m = params["expert_blocks"]
    for i in range(m["norm"].shape[0]):
        h = norm(x, m["norm"][i])
        scores = jnp.matmul(h, m["router"][i], precision=HIGHEST)
        top, where = jax.lax.top_k(scores, k)
        weight = jax.nn.softmax(top, axis=-1)
        out = glu(h, m["s_gate"][i], m["s_up"][i], m["s_down"][i])
        for e in range(held):
            gate_e = jnp.sum(jnp.where(where == e, weight, 0.0), axis=-1, keepdims=True)
            out = out + gate_e * glu(h, m["x_gate"][i, e], m["x_up"][i, e], m["x_down"][i, e])
        x = x + out
    logits = mm(norm(x, params["final_norm"]), params["head"])[:, :-1]
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0].mean()
