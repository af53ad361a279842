"""Drives the plain reference through the first training steps of a cell:
the mean gradient over every group's rows (computed in blocks of rows so the
float32 activations fit), the AdamW steps, and the per-leaf norms the
comparison reads.  The model is the family's plain ``loss_fn(params, tokens,
sizes, operand_dtype)`` (``smollm2.py``); ``operand_dtype`` selects the
lower-precision control.  Imports nothing of the program."""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def leaf_norms(tree: Any, stacked: Sequence[str] = ("blocks",)) -> Dict[str, jax.Array]:
    """L2 norm of every leaf; the leaves of the top-level groups in
    ``stacked`` (the family's ``STACKED``) are stacked by layer ([L, ...]) and
    read layer by layer: ``{"blocks/wq": [L], "embed": [1], ...}``.  Runs
    under jit."""
    out = {}
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        x = x.astype(jnp.float32)
        if name.split("/", 1)[0] in stacked:
            out[name] = jnp.sqrt(jnp.sum(x * x, axis=tuple(range(1, x.ndim))))
        else:
            out[name] = jnp.sqrt(jnp.sum(x * x))[None]
    return out


def delta_norms(new: Any, old: Any, stacked: Sequence[str] = ("blocks",)) -> Dict[str, jax.Array]:
    return leaf_norms(jax.tree_util.tree_map(lambda a, b: a - b, new, old), stacked)


def adamw_step(params: Any, grads: Any, mu: Any, nu: Any, count: int,
               hp: Dict[str, float]) -> Tuple[Any, Any, Any]:
    """One AdamW step (Loshchilov & Hutter, decoupled weight decay; bias-
    corrected moments).  ``count`` is the number of steps already taken."""
    b1, b2, eps = hp["adam_b1"], hp["adam_b2"], hp["adam_eps"]
    lr, wd = hp["learning_rate"], hp["weight_decay"]
    n = count + 1
    tm = jax.tree_util.tree_map
    mu = tm(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = tm(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
    params = tm(
        lambda p, m, v: p - lr * (
            (m / (1 - b1 ** n)) / (jnp.sqrt(v / (1 - b2 ** n)) + eps) + wd * p),
        params, mu, nu)
    return params, mu, nu


def run(
    loss_fn: "Callable[..., jax.Array]",
    weights: Any,
    batches: "List[List[np.ndarray]]",
    sizes: Dict[str, Any],
    hp: Dict[str, float],
    devices: Sequence[Any],
    operand_dtype: Optional[str] = None,
    stacked: Sequence[str] = ("blocks",),
) -> Dict[str, Any]:
    """``batches[step][group]`` is that group's [B, T] token array.  Returns
    per-step per-group losses, the norms of step 0's mean gradient and the
    norms of the parameters' change after the last step, as numpy."""
    mesh = Mesh(np.array(list(devices)), ("rows",))
    replicated = NamedSharding(mesh, P())
    by_rows = NamedSharding(mesh, P("rows", None))
    chunk = len(devices)  # one row a chip: float32 logits of a row are 0.4 GB

    def block(params, rows, acc):
        loss, g = jax.value_and_grad(loss_fn)(
            params, rows, sizes, operand_dtype)
        return loss, jax.tree_util.tree_map(jnp.add, acc, g)

    block = jax.jit(block, donate_argnums=(2,), out_shardings=(replicated, replicated))
    step = jax.jit(
        lambda p, g, mu, nu, count, scale: adamw_step(
            p, jax.tree_util.tree_map(lambda x: x * scale, g), mu, nu, count, hp),
        static_argnums=(4,), donate_argnums=(0, 2, 3))
    norms = jax.jit(lambda t: leaf_norms(t, stacked))
    dnorms = jax.jit(lambda new, old: delta_norms(new, old, stacked))
    zeros = jax.jit(lambda t: jax.tree_util.tree_map(jnp.zeros_like, t),
                    out_shardings=replicated)

    params = jax.device_put(weights, replicated)
    start = jax.tree_util.tree_map(jnp.copy, params)
    mu, nu = zeros(params), zeros(params)
    losses: "List[List[float]]" = []
    grad0 = None
    for count, groups in enumerate(batches):
        acc, n_blocks, step_losses = zeros(params), 0, []
        for toks in groups:
            if toks.shape[0] % chunk:
                raise ValueError(
                    f"{toks.shape[0]} rows do not split into blocks of {chunk}")
            group_losses = []
            for lo in range(0, toks.shape[0], chunk):
                rows = jax.device_put(toks[lo:lo + chunk], by_rows)
                loss, acc = block(params, rows, acc)
                group_losses.append(loss)
                n_blocks += 1
            step_losses.append(float(np.mean([float(x) for x in group_losses])))
        losses.append(step_losses)
        scale = jnp.float32(1.0 / n_blocks)
        if count == 0:
            grad0 = jax.tree_util.tree_map(
                lambda v: np.asarray(v) * (1.0 / n_blocks), norms(acc))
        params, mu, nu = step(params, acc, mu, nu, count, scale)
        del acc
    out = {
        "losses": losses,
        "grad0_norms": grad0,
        "delta_norms": jax.tree_util.tree_map(np.asarray, dnorms(params, start)),
    }
    del params, start, mu, nu
    return out
