"""Mixture-of-Experts FFN with expert parallelism (``ep`` mesh axis).

A TPU-first capability beyond the reference (which has no expert
parallelism — SURVEY §2.3): GShard-style capacity-based top-k routing
expressed entirely as dense one-hot einsums, so the whole layer is static-
shaped, jit-friendly, and MXU-resident. Experts are sharded over the ``ep``
mesh axis via sharding constraints on the ``[E, C, d]`` dispatch tensor —
XLA inserts the token all-to-alls; no hand-written collectives.

Routing: top-k (default 2) experts per token, probabilities renormalized
over the chosen k; per-expert capacity ``C = ceil(capacity_factor * N * k /
E)``; tokens past capacity are dropped (their combine weight is zero, so
the residual connection passes them through unchanged — standard GShard
semantics). The load-balance auxiliary loss (Switch/GShard ``E * Σ_e
fraction_tokens_e * mean_prob_e``) is returned for the trainer to add.

Beside it, a chip's share of a dropless layer, sigmoid- or softmax-routed,
with or without a shared expert (``HeldMoEConfig``, ``held_moe_ffn``): the
router scores every published expert, the layer is told which of them it
holds and computes their part of the result.  No capacity, no drop, no
auxiliary loss, no ``ep`` axis: the chips that hold the other experts and
their exchange are not in it.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    d_model: int
    d_ff: int
    n_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    ep_axis: str = "ep"
    fsdp_axis: str = "fsdp"
    tp_axis: str = "tp"
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32


def init_moe_params(rng: jax.Array, cfg: MoEConfig, n_layers: int = 0) -> Params:
    """Expert + router weights; with ``n_layers`` > 0 a leading stacked
    layer dim is added (for `lax.scan` blocks)."""
    e, f, ne = cfg.d_model, cfg.d_ff, cfg.n_experts
    pd = cfg.param_dtype
    lead = (n_layers,) if n_layers else ()
    keys = jax.random.split(rng, 4)

    def dense(key, *shape):
        fan_in = shape[-2]
        return (jax.random.normal(key, shape, pd) / np.sqrt(fan_in)).astype(pd)

    return {
        "router": dense(keys[0], *lead, e, ne),
        "w_gate": dense(keys[1], *lead, ne, e, f),
        "w_up": dense(keys[2], *lead, ne, e, f),
        "w_down": dense(keys[3], *lead, ne, f, e),
    }


def moe_param_specs(cfg: MoEConfig, stacked: bool = False) -> Params:
    """PartitionSpecs: experts sharded over ep, inner dims over fsdp/tp."""
    lead = (None,) if stacked else ()
    ep, fs, tp = cfg.ep_axis, cfg.fsdp_axis, cfg.tp_axis
    return {
        "router": P(*lead, None, None),
        "w_gate": P(*lead, ep, fs, tp),
        "w_up": P(*lead, ep, fs, tp),
        "w_down": P(*lead, ep, tp, fs),
    }


def _capacity(n_tokens: int, cfg: MoEConfig) -> int:
    return max(
        1, math.ceil(cfg.capacity_factor * n_tokens * cfg.top_k / cfg.n_experts)
    )


def moe_ffn(
    x: jax.Array,
    params: Params,
    cfg: MoEConfig,
    mesh: "Optional[Mesh]" = None,
) -> "Tuple[jax.Array, jax.Array]":
    """MoE feed-forward: ``x [B, T, d] -> (y [B, T, d], aux_loss scalar)``.

    With a mesh, the ``[E, C, d]`` expert buffers get ``P(ep, ...)``
    sharding constraints so XLA dispatches tokens to expert shards over the
    ep axis (all-to-all on ICI).  ``mesh="manual"`` applies the constraint
    with a bare PartitionSpec — the form required inside a partial-manual
    shard_map (e.g. the pipeline), where ep stays automatic but a
    NamedSharding over the full mesh is rejected for mentioning manual
    axes.
    """
    b, t, d = x.shape
    n = b * t
    ne, k = cfg.n_experts, cfg.top_k
    cap = _capacity(n, cfg)
    act = cfg.dtype

    flat = x.reshape(n, d)
    logits = (
        flat.astype(jnp.float32) @ params["router"].astype(jnp.float32)
    )  # [N, E] — routing in f32 always
    probs = jax.nn.softmax(logits, axis=-1)

    # top-k assignment (distinct experts per token)
    _, top_idx = jax.lax.top_k(logits, k)  # [N, k]
    expert_masks = [
        jax.nn.one_hot(top_idx[:, kk], ne, dtype=jnp.float32) for kk in range(k)
    ]

    # renormalize gates over the chosen k
    gates = jnp.stack(
        [(probs * m).sum(axis=-1) for m in expert_masks], axis=0
    )  # [k, N]
    gates = gates / jnp.maximum(gates.sum(axis=0, keepdims=True), 1e-9)

    # position of each (token, choice) within its expert: earlier choices
    # get priority, then token order (GShard scheme). Counts in int32 —
    # f32 cumsum would collide capacity slots past 2^24 assignments.
    prev_per_expert = jnp.zeros((ne,), jnp.int32)
    dispatch = jnp.zeros((n, ne, cap), jnp.float32)
    combine = jnp.zeros((n, ne, cap), jnp.float32)
    for kk in range(k):
        mask = expert_masks[kk]  # [N, E]
        imask = mask.astype(jnp.int32)
        pos = jnp.cumsum(imask, axis=0) - 1 + prev_per_expert[None, :]
        prev_per_expert = prev_per_expert + imask.sum(axis=0)
        within = (pos < cap) & (imask > 0)
        pos_oh = jax.nn.one_hot(pos, cap, dtype=jnp.float32)
        sel = jnp.where(within[..., None], pos_oh, 0.0)  # [N, E, C]
        dispatch = dispatch + sel
        combine = combine + sel * gates[kk][:, None, None]

    # dispatch tokens into per-expert buffers on the MXU
    expert_in = jnp.einsum(
        "nec,nd->ecd", dispatch, flat.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    ).astype(act)
    if mesh is not None:
        spec = (
            P(cfg.ep_axis, None, None)
            if isinstance(mesh, str)
            else NamedSharding(mesh, P(cfg.ep_axis, None, None))
        )
        expert_in = jax.lax.with_sharding_constraint(expert_in, spec)

    wg = params["w_gate"].astype(act)
    wu = params["w_up"].astype(act)
    wd = params["w_down"].astype(act)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", expert_in, wg)) * jnp.einsum(
        "ecd,edf->ecf", expert_in, wu
    )
    expert_out = jnp.einsum("ecf,efd->ecd", h, wd)
    if mesh is not None:
        expert_out = jax.lax.with_sharding_constraint(expert_out, spec)

    y = jnp.einsum(
        "nec,ecd->nd", combine, expert_out.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )

    # load-balance auxiliary loss (Switch eq. 4): E * sum_e f_e * p_e over
    # the FIRST choice (standard), where f_e = fraction of tokens routed
    fraction = expert_masks[0].mean(axis=0)
    mean_prob = probs.mean(axis=0)
    aux = ne * jnp.sum(fraction * mean_prob)

    return y.reshape(b, t, d).astype(x.dtype), aux


def moe_ffn_reference(
    x: jax.Array, params: Params, cfg: MoEConfig
) -> jax.Array:
    """Brute-force per-token reference (no capacity drops): for tests."""
    b, t, d = x.shape
    flat = x.reshape(b * t, d).astype(jnp.float32)
    logits = flat @ params["router"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    _, top_idx = jax.lax.top_k(logits, cfg.top_k)
    out = jnp.zeros_like(flat)
    gates = jnp.take_along_axis(probs, top_idx, axis=-1)
    gates = gates / gates.sum(axis=-1, keepdims=True)

    def one_expert(e):
        wg = params["w_gate"][e].astype(jnp.float32)
        wu = params["w_up"][e].astype(jnp.float32)
        wd = params["w_down"][e].astype(jnp.float32)
        h = jax.nn.silu(flat @ wg) * (flat @ wu)
        return h @ wd

    all_out = jnp.stack([one_expert(e) for e in range(cfg.n_experts)])  # [E, N, d]
    for kk in range(cfg.top_k):
        idx = top_idx[:, kk]
        out = out + gates[:, kk:kk + 1] * jnp.take_along_axis(
            all_out, idx[None, :, None], axis=0
        )[0]
    return out.reshape(b, t, d).astype(x.dtype)


# ---------------------------------------------------------------------------
# a chip's share of a dropless expert layer
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class HeldMoEConfig:
    """An expert layer as one chip of an expert-parallel deployment holds it:
    the router scores all ``n_routed`` published experts and keeps ``top_k``
    a token, the layer is told which of them live here (``held``, their
    published ids) and computes their part of the result, plus the shared
    expert that every chip computes alike (``shared``: a model without one
    has no ``shared_*`` leaves, and a token none of whose experts lives here
    gets nothing from the layer).  ``score`` is how the router's logits become
    scores: ``"sigmoid"`` (each expert alone) or ``"softmax"`` (all
    ``n_routed`` against each other)."""

    d_model: int
    d_expert: int
    n_routed: int
    top_k: int
    held: "Tuple[int, ...]"
    routed_scale: float = 1.0
    # rows of the pool the landed assignments are gathered into, over the mean
    # load of the held experts together; a batch that overflows it takes the
    # masked path instead: nothing is ever dropped
    slack: float = 8.0
    shared: bool = True
    # added to the chosen scores' sum before they are renormalised to one
    renorm_eps: float = 1e-20
    score: str = "sigmoid"
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32


def init_held_moe_params(rng: jax.Array, cfg: HeldMoEConfig, n_layers: int) -> Params:
    """Router over all published experts, the held experts, the shared one
    where the model has one; a leading ``[n_layers]`` dim for stacked blocks."""
    e, f, pd = cfg.d_model, cfg.d_expert, cfg.param_dtype
    held = len(cfg.held)
    keys = jax.random.split(rng, 7)

    def dense(key, *shape):
        return (jax.random.normal(key, (n_layers,) + shape, pd) / np.sqrt(shape[-2])).astype(pd)

    params = {
        "router": dense(keys[0], e, cfg.n_routed),
        "w_gate": dense(keys[1], held, e, f),
        "w_up": dense(keys[2], held, e, f),
        "w_down": dense(keys[3], held, f, e),
    }
    if cfg.shared:
        params.update(shared_gate=dense(keys[4], e, f), shared_up=dense(keys[5], e, f),
                      shared_down=dense(keys[6], f, e))
    return params


def _router_logits(flat: jax.Array, router: jax.Array) -> jax.Array:
    """``[N, d] -> [N, n_routed]`` in float32 at the highest precision: a
    near-tie decides where a token goes."""
    return jnp.matmul(flat.astype(jnp.float32), router.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)


def _choose(scores: jax.Array, ranked: jax.Array, cfg: HeldMoEConfig) -> "Tuple[jax.Array, jax.Array]":
    """The ``top_k`` largest of ``ranked``, and their ``scores`` renormalised
    to one (their sum + ``renorm_eps``) and scaled."""
    _, chosen = jax.lax.top_k(ranked, cfg.top_k)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = picked / (picked.sum(axis=-1, keepdims=True) + cfg.renorm_eps) * cfg.routed_scale
    return chosen, weights


def route_sigmoid(
    flat: jax.Array, router: jax.Array, cfg: HeldMoEConfig,
    router_bias: "Optional[jax.Array]" = None,
) -> "Tuple[jax.Array, jax.Array]":
    """``[N, d] -> (chosen [N, k] published ids, weights [N, k])``: sigmoid
    scores in float32 at the highest precision (a near-tie decides where a
    token goes), the ``top_k`` largest of score + ``router_bias`` (a buffer
    the balancing rule moves, never the gradient), the chosen scores
    renormalised to one (their sum + ``renorm_eps``) and scaled."""
    scores = jax.nn.sigmoid(_router_logits(flat, router))
    ranked = scores if router_bias is None else scores + jax.lax.stop_gradient(router_bias)
    return _choose(scores, ranked, cfg)


def route_softmax(
    flat: jax.Array, router: jax.Array, cfg: HeldMoEConfig,
) -> "Tuple[jax.Array, jax.Array]":
    """As ``route_sigmoid`` with the scores a softmax over all ``n_routed``
    published experts, those that live elsewhere too: the ``top_k`` largest
    probabilities, renormalised to one over the chosen (which is a softmax
    over the chosen logits alone) and scaled.  No bias."""
    scores = jax.nn.softmax(_router_logits(flat, router), axis=-1)
    return _choose(scores, scores, cfg)


def pool_rows(cfg: HeldMoEConfig, n: int) -> int:
    """Rows of the pool ``n`` tokens' landed assignments are gathered into:
    ``slack x`` the mean load of the held experts together (``n k held /
    n_routed``), up to the next multiple of 8, and never more than can land:
    a token's choices are distinct, so it lands here at most ``min(k, held)``
    times.  Where more experts are held than a token chooses that cap is the
    batch's every assignment, ``n k``."""
    k, held = cfg.top_k, len(cfg.held)
    return min(n * min(k, held), -(-int(np.ceil(cfg.slack * n * k * held / cfg.n_routed)) // 8) * 8)


def place_assignments(
    chosen: jax.Array, weights: jax.Array, cfg: HeldMoEConfig,
) -> "Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]":
    """Where each of the ``N k`` assignments goes: ``(local [N k] the held
    slot or -1, assignments [held], unrouted, rows_token [pool], rows_weight
    [pool])``.  The pool's rows are in expert order (the order held), an
    expert's by token then choice; a row no assignment fills names token ``N``
    (a row of zeros) at weight 0; an assignment past the pool's end is in no
    row (the layer then takes the masked path)."""
    n, k = chosen.shape
    held, pool = len(cfg.held), pool_rows(cfg, n)
    slot_of = np.full((cfg.n_routed,), -1, np.int32)
    slot_of[list(cfg.held)] = np.arange(held, dtype=np.int32)
    local = jnp.asarray(slot_of)[chosen].reshape(n * k)          # -1: lives elsewhere
    landed = (local[:, None] == jnp.arange(held)[None, :]).astype(jnp.int32)  # [N k, held]
    assignments = landed.sum(axis=0)
    unrouted = n - jnp.any(local.reshape(n, k) >= 0, axis=-1).sum()
    # the row of each assignment: its expert's first row (experts in the
    # order held), then its place among that expert's, by token then choice
    first = jnp.cumsum(assignments) - assignments
    place = ((jnp.cumsum(landed, axis=0) - 1 + first[None, :]) * landed).sum(axis=-1)
    row = jnp.where((local >= 0) & (place < pool), place, pool)
    token = jnp.arange(n * k, dtype=jnp.int32) // k
    rows_token = jnp.full((pool,), n, jnp.int32).at[row].set(token, mode="drop")
    rows_weight = jnp.zeros((pool,), jnp.float32).at[row].set(
        weights.reshape(n * k), mode="drop")
    return local, assignments, unrouted, rows_token, rows_weight


def held_moe_ffn(
    x: jax.Array, params: Params, cfg: HeldMoEConfig,
    router_bias: "Optional[jax.Array]" = None,
) -> "Tuple[jax.Array, Dict[str, jax.Array]]":
    """``x [B, T, d] -> (y, stats)``: ``y = sum over the chosen experts that
    live here of w_e SwiGLU_e(x), plus SwiGLU_shared(x)``; a token none of
    whose experts is here gets the shared expert alone, and exactly zero
    where the model has none (``cfg.shared`` false).  No capacity, no
    drop, no auxiliary loss.  ``stats``: ``assignments`` ``[held]`` (how many
    of the ``N k`` assignments landed on each held expert) and ``unrouted``
    (tokens that found none of their experts here).  ``cfg.score`` picks the
    router (``route_sigmoid`` | ``route_softmax``; a softmax takes no
    ``router_bias``).

    Static shapes with the work going by what landed: the assignments that
    landed here are laid out by expert in a pool (``pool_rows``: ``slack x``
    the mean load, never more than can land; ``place_assignments``), the
    three products of the SwiGLU run as ``lax.ragged_dot`` over the pool's
    groups (on a TPU a grouped-matmul kernel that visits the rows in use) and
    the results are added back by token.  A batch so skewed that more lands
    here than the pool holds takes, under ``lax.cond``, a path that runs every
    held expert over every token with the weights as a mask."""
    from torchft_tpu.models.transformer import _swiglu

    b, t, d = x.shape
    n, k, held = b * t, cfg.top_k, len(cfg.held)
    act = cfg.dtype
    flat = x.reshape(n, d)
    if cfg.score not in ("sigmoid", "softmax"):
        raise ValueError(f"unknown score {cfg.score!r}; expected 'sigmoid' or 'softmax'")
    if cfg.score == "softmax" and router_bias is not None:
        raise ValueError("a softmax router takes no router_bias")
    with jax.named_scope("moe.route"):
        with jax.named_scope("moe.route.score"):
            if cfg.score == "softmax":
                chosen, weights = route_softmax(flat, params["router"], cfg)
            else:
                chosen, weights = route_sigmoid(flat, params["router"], cfg, router_bias)
        with jax.named_scope("moe.route.place"):
            local, assignments, unrouted, rows_token, rows_weight = place_assignments(chosen, weights, cfg)
    pool = rows_token.shape[0]

    with jax.named_scope("moe.experts"):
        wg, wu, wd = (params[name].astype(act) for name in ("w_gate", "w_up", "w_down"))

        def gathered(_):
            rows = jnp.concatenate([flat, jnp.zeros((1, d), flat.dtype)])[rows_token]
            # a TPU's grouped matmul leaves the rows past the last group as
            # it found them: nothing of them may reach the sum or a gradient
            used = (jnp.arange(pool) < assignments.sum())[:, None]

            def grouped(lhs, rhs, out_type):
                return jnp.where(used, jax.lax.ragged_dot(
                    lhs, rhs, assignments, preferred_element_type=out_type), 0)

            hidden = jax.nn.silu(grouped(rows, wg, act)) * grouped(rows, wu, act)
            out = grouped(hidden, wd, jnp.float32) * rows_weight[:, None]
            return jnp.zeros((n + 1, d), jnp.float32).at[rows_token].add(out)[:n]

        def masked(_):
            gate = jnp.where(
                local.reshape(n, k, 1) == jnp.arange(held), weights[..., None], 0.0).sum(axis=1)

            @jax.checkpoint
            def expert(g, u, dn, gate_e):
                return _swiglu(flat, g, u, dn).astype(jnp.float32) * gate_e[:, None]

            def one(acc, e):
                return acc + expert(*e), None

            acc, _ = jax.lax.scan(one, jnp.zeros((n, d), jnp.float32), (wg, wu, wd, gate.T))
            return acc

        # each path under its own checkpoint: what `cond` keeps for the
        # backward is then the paths' common inputs, not both paths' insides;
        # and under its own scope: a device trace says which of them ran
        routed = jax.lax.cond(
            assignments.sum() <= pool,
            jax.checkpoint(jax.named_scope("moe.gathered")(gathered)),
            jax.checkpoint(jax.named_scope("moe.masked")(masked)), None)

    if cfg.shared:
        with jax.named_scope("moe.shared"):
            shared = _swiglu(flat, params["shared_gate"], params["shared_up"], params["shared_down"])
        routed = routed + shared.astype(jnp.float32)
    y = routed.astype(x.dtype).reshape(b, t, d)
    return y, {"assignments": assignments, "unrouted": unrouted}


def record_routing_stats(
    stats: "Dict[str, Any]", expert_layers: "Sequence[int]", held: "Sequence[int]",
) -> None:
    """Feeds one batch's routing stats (``held_moe_ffn``'s, stacked over a
    model's expert layers) to the ``utils/metrics`` counters
    ``torchft_moe_assignments_total{layer,expert}`` and
    ``torchft_moe_tokens_unrouted_total{layer}``: ``expert_layers`` are the
    stacked rows' numbers in the model, ``held`` the published ids of the
    experts held here."""
    from torchft_tpu.utils import metrics

    assignments, unrouted = np.asarray(stats["assignments"]), np.asarray(stats["unrouted"])
    for row, layer in enumerate(expert_layers):
        for slot, expert in enumerate(held):
            metrics.MOE_ASSIGNMENTS.labels(layer=str(layer), expert=str(expert)).inc(
                int(assignments[row, slot]))
        metrics.MOE_TOKENS_UNROUTED.labels(layer=str(layer)).inc(int(unrouted[row]))


__all__ = [
    "HeldMoEConfig",
    "init_held_moe_params",
    "route_sigmoid",
    "route_softmax",
    "pool_rows",
    "place_assignments",
    "held_moe_ffn",
    "record_routing_stats",
    "MoEConfig",
    "init_moe_params",
    "moe_param_specs",
    "moe_ffn",
    "moe_ffn_reference",
]
