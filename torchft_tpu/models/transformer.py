"""Flagship model: a llama-style decoder-only transformer, TPU-first.

The reference's examples train a toy CNN/MLP (train_ddp.py:84-102,
train_diloco.py:76-120) and its reference-scale config is Llama3-8B via
torchtitan (torchft/examples/slurm/runner.py:16-49).  This module is that
model family built natively: pure-functional JAX (params are a pytree),
bfloat16 compute with fp32 master params, RMSNorm + rotary embeddings + GQA
+ SwiGLU, layers stacked and iterated with `lax.scan` (one trace per block,
fast compiles at depth), optional `jax.checkpoint` rematerialization, and a
multi-axis parallelism story expressed as `PartitionSpec`s:

- ``dp``   data-parallel replicas *within* a slice (pure batch dim),
- ``fsdp`` fully-sharded data parallel (params sharded over it, batch too),
- ``tp``   tensor parallel (attention heads / MLP hidden),
- ``cp``   context parallel (sequence; ring or Ulysses attention),
- ``ep``   expert parallel (MoE experts; rides the batch dims elsewhere).

Pipeline parallelism is a separate composition primitive
(torchft_tpu/parallel/pipeline.py) for stacked-layer stacks.

The elastic FT replica dimension deliberately does NOT appear here: it lives
above jit in the Manager (zero-fill + divide-by-participants keeps compiled
shapes static across quorum changes — SURVEY §7, reference manager.py:416).

Weights layout keeps matmuls [*, E] x [E, F] shaped for the MXU; all
reductions accumulate in fp32 (`preferred_element_type`).
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from torchft_tpu.ops.ring_attention import dense_attention, ring_attention_local
from torchft_tpu.ops.ulysses import ulysses_attention_local

logger = logging.getLogger(__name__)
_warned_replicated: set = set()

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_heads: int = 8
    n_kv_heads: int = 4
    d_ff: int = 1408
    n_layers: int = 6
    max_seq_len: int = 2048
    rope_theta: float = 10000.0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    # remat granularity: "full" recomputes the block in the backward except
    # the flash kernel's forward call, whose two results it keeps from the
    # forward pass: a layer's B*T*H*Dv activations (2 B each in bfloat16)
    # and B*H*T float32 of logsumexp on top of the block's input (0.03 GB a
    # layer at 360m B8), so the backward does not run the forward kernel a
    # second time; a job at its memory limit should reckon that.  A block
    # without the flash kernel (dense, ring, ulysses) keeps its input alone.
    # "dots" saves matmul outputs and recomputes only the cheap elementwise
    # ops (jax.checkpoint_policies.dots_saveable — trades ~260 MB/layer of
    # bf16 activations for skipping the FLOP-heavy recompute; measured
    # faster whenever it fits in HBM).
    remat_policy: str = "full"
    # "auto"    = TPU-first resolution per call site: flash when the
    #             sequence is lane-aligned (T % 128 == 0) and unsharded,
    #             ring on cp meshes / manual-cp contexts, dense otherwise
    #             (one log line on fallback);
    # "dense"   = single-pass attention (cp must be 1 / unsharded seq);
    # "flash"   = fused Pallas tiles (ops/flash_attention.py); needs
    #             T % 128 == 0, sequence unsharded;
    # "ring"    = ring attention, sequence sharded over `cp_axis`
    #             (K/V ppermute ring; memory stays local-T, best for
    #             extreme sequence lengths);
    # "ulysses" = all-to-all head-scatter/seq-gather attention over
    #             `cp_axis`. Needs the PER-TP-SHARD head counts divisible
    #             by cp: (n_heads/tp) % cp == 0 and (n_kv_heads/tp) % cp
    #             == 0. One dense attention per head group; best MXU
    #             utilization at moderate T.
    attn_impl: str = "auto"
    # n_experts > 0 replaces the dense FFN with a MoE layer (top-k routed,
    # experts sharded over `ep_axis`; see torchft_tpu/models/moe.py).
    n_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01
    dp_axis: str = "dp"
    fsdp_axis: str = "fsdp"
    tp_axis: str = "tp"
    cp_axis: str = "cp"
    ep_axis: str = "ep"

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init_params(rng: jax.Array, cfg: TransformerConfig) -> Params:
    """Initialize the parameter pytree. Per-layer weights are stacked on a
    leading [n_layers] dim so the forward can `lax.scan` over blocks."""
    e, f, l = cfg.d_model, cfg.d_ff, cfg.n_layers
    hd, nh, nkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    pd = cfg.param_dtype
    keys = jax.random.split(rng, 8)

    def dense(key, *shape):
        fan_in = shape[-2]
        return (jax.random.normal(key, shape, pd) / np.sqrt(fan_in)).astype(pd)

    blocks = {
        "attn_norm": jnp.ones((l, e), pd),
        "wq": dense(keys[1], l, e, nh * hd),
        "wk": dense(keys[2], l, e, nkv * hd),
        "wv": dense(keys[3], l, e, nkv * hd),
        "wo": dense(keys[4], l, nh * hd, e),
        "mlp_norm": jnp.ones((l, e), pd),
    }
    if cfg.n_experts:
        from torchft_tpu.models.moe import init_moe_params

        blocks.update(init_moe_params(keys[5], _moe_cfg(cfg), n_layers=l))
    else:
        blocks.update(
            {
                "w_gate": dense(keys[5], l, e, f),
                "w_up": dense(keys[6], l, e, f),
                "w_down": dense(keys[7], l, f, e),
            }
        )
    return {
        "embed": jax.random.normal(keys[0], (cfg.vocab_size, e), pd) * 0.02,
        "blocks": blocks,
        "final_norm": jnp.ones((e,), pd),
    }


def _moe_cfg(cfg: TransformerConfig):
    from torchft_tpu.models.moe import MoEConfig

    return MoEConfig(
        d_model=cfg.d_model,
        d_ff=cfg.d_ff,
        n_experts=cfg.n_experts,
        top_k=cfg.moe_top_k,
        capacity_factor=cfg.moe_capacity_factor,
        ep_axis=cfg.ep_axis,
        fsdp_axis=cfg.fsdp_axis,
        tp_axis=cfg.tp_axis,
        dtype=cfg.dtype,
        param_dtype=cfg.param_dtype,
    )


def _filter_spec(spec: P, mesh: "Optional[Mesh]") -> P:
    """Drop axes the mesh doesn't have (partial meshes, e.g. cp-only or
    fsdp/tp-only inner HSDP meshes)."""
    if mesh is None:
        return spec

    def keep(entry):
        if entry is None:
            return None
        if isinstance(entry, tuple):
            kept = tuple(a for a in entry if a in mesh.axis_names)
            return kept or None
        return entry if entry in mesh.axis_names else None

    return P(*(keep(e) for e in spec))


def param_specs(cfg: TransformerConfig, mesh: "Optional[Mesh]" = None) -> Params:
    """PartitionSpecs matching init_params' tree: 2-D weights sharded
    (fsdp x tp); the stacked layer dim stays unsharded so `lax.scan` slices
    locally. With a mesh, axes absent from it are dropped."""
    fs, tp = cfg.fsdp_axis, cfg.tp_axis
    blocks = {
        "attn_norm": P(None, None),
        "wq": P(None, fs, tp),
        "wk": P(None, fs, tp),
        "wv": P(None, fs, tp),
        "wo": P(None, tp, fs),
        "mlp_norm": P(None, None),
    }
    if cfg.n_experts:
        from torchft_tpu.models.moe import moe_param_specs

        blocks.update(moe_param_specs(_moe_cfg(cfg), stacked=True))
    else:
        blocks.update(
            {
                "w_gate": P(None, fs, tp),
                "w_up": P(None, fs, tp),
                "w_down": P(None, tp, fs),
            }
        )
    specs = {
        "embed": P(tp, fs),
        "blocks": blocks,
        "final_norm": P(None),
    }
    if mesh is not None and fs not in mesh.axis_names and tp not in mesh.axis_names:
        # legitimate for e.g. a cp-only inner mesh (weights replicated by
        # design), but also the symptom of a cfg/mesh axis-name mismatch —
        # which would otherwise silently train unsharded. Warn once per
        # combination (param_specs sits in training-loop paths).
        key = (tuple(mesh.axis_names), fs, tp)
        if key not in _warned_replicated:
            _warned_replicated.add(key)
            logger.warning(
                "mesh %s has neither the fsdp (%r) nor tp (%r) axis: "
                "parameters will be fully replicated. If this is "
                "unintended, align the TransformerConfig *_axis names "
                "with the mesh.",
                mesh.axis_names, fs, tp,
            )
    return jax.tree_util.tree_map(
        lambda s: _filter_spec(s, mesh), specs,
        is_leaf=lambda s: isinstance(s, P),
    )


def _batch_axes(cfg: TransformerConfig, mesh: "Optional[Mesh]") -> tuple:
    """Mesh axes the batch dim shards over: (dp, fsdp) plus ep when it
    exists — ep rides the batch dims so non-MoE compute is data-parallel
    over ep shards instead of replicated; inside the MoE layer the
    [E, C, d] constraint re-shards tokens expert-wise (the GShard
    ep-borrowed-from-dp layout).

    With a mesh, axes are filtered to those present and deduped, so
    partial meshes (e.g. an inner HSDP mesh with only fsdp/tp) and axis
    aliasing (dp_axis == fsdp_axis) both work.
    """
    axes = [cfg.dp_axis, cfg.fsdp_axis]
    if (mesh is not None and cfg.ep_axis in mesh.axis_names) or (
        mesh is None and cfg.n_experts
    ):
        axes.append(cfg.ep_axis)
    if mesh is not None:
        axes = [a for a in axes if a in mesh.axis_names]
    return tuple(dict.fromkeys(axes))  # dedupe, order-preserving


def _seq_axis(cfg: TransformerConfig, mesh: "Optional[Mesh]") -> "Optional[str]":
    if mesh is not None and cfg.cp_axis not in mesh.axis_names:
        return None
    return cfg.cp_axis


def batch_spec(cfg: TransformerConfig, mesh: "Optional[Mesh]" = None) -> P:
    """Tokens [B, T]: batch over (dp, fsdp[, ep]), sequence over cp."""
    return P(_batch_axes(cfg, mesh), _seq_axis(cfg, mesh))


def shard_params(params: Params, mesh: Mesh, cfg: TransformerConfig) -> Params:
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        params,
        param_specs(cfg, mesh),
    )


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w.astype(x.dtype)


def _swiglu(h: jax.Array, w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array) -> jax.Array:
    """``down(silu(gate h) * up h)`` in the dtype of ``h``: the dense FFN of
    this model and the dense, shared and routed ones of ``kimi_linear``."""
    act = h.dtype
    gate = jax.nn.silu(h @ w_gate.astype(act))
    up = h @ w_up.astype(act)
    return (gate * up) @ w_down.astype(act)


def _rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding; x [B, T, H, D], positions [T] (global)."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    return _rotate(x, positions[:, None].astype(jnp.float32) * freqs[None, :])


def _rotate(x: jax.Array, angles: jax.Array, scale: float = 1.0) -> jax.Array:
    """The halves of ``x`` [B, T, H, D] turned by ``angles`` [T, D/2]; a
    scaling rule's factor (``models/mellum.py``) multiplies cos and sin."""
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def _expand_kv_for_tp(cfg: TransformerConfig, mesh: Mesh, nh: int, k, v):
    """K/V normally cross shard_map unexpanded (nkv heads of ppermute /
    all-to-all / kernel bytes); when tp doesn't divide nkv that layout
    isn't shardable, so pre-expand to nh heads."""
    tp_size = dict(zip(mesh.axis_names, mesh.devices.shape)).get(
        cfg.tp_axis, 1
    )
    if k.shape[2] % tp_size != 0:
        rep = nh // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    return k, v


_warned_attn_fallback: set = set()


def _resolve_attn_impl(
    cfg: TransformerConfig, mesh: Any, manual_cp: bool, seq_len: int
) -> str:
    """Resolve ``attn_impl='auto'`` at trace time, TPU-first: the fused
    Pallas flash tiles whenever the shapes allow (they remove the [T, T]
    score materialization — the dominant HBM cost of dense attention),
    ring attention when the sequence is cp-sharded, dense only as the
    lane-unaligned fallback (logged once per shape)."""
    if cfg.attn_impl != "auto":
        return cfg.attn_impl
    if manual_cp:
        return "ring"
    if isinstance(mesh, Mesh):
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        if sizes.get(cfg.cp_axis, 1) > 1:
            return "ring"
    # The Pallas kernel only pays on real TPU hardware; off-TPU it would
    # run in interpreter mode (orders of magnitude slower than XLA dense),
    # so "auto" means dense there — CPU debugging / virtual-mesh dryruns
    # keep their speed, and the flash path itself is covered off-TPU by
    # its interpret-mode kernel tests.
    if seq_len % 128 == 0 and jax.default_backend() == "tpu":
        return "flash"
    key = (seq_len, jax.default_backend())
    if key not in _warned_attn_fallback:
        _warned_attn_fallback.add(key)
        logger.info(
            "attn_impl='auto': %s; using dense attention",
            f"T={seq_len} is not 128-lane-aligned"
            if seq_len % 128
            else f"backend={jax.default_backend()} runs pallas interpreted",
        )
    return "dense"


def _make_block(
    cfg: TransformerConfig, mesh: "Optional[Mesh]", manual_cp: bool = False
):
    """Returns block(x, layer_params, positions) -> x for one decoder layer.

    ``manual_cp``: the block runs inside an existing manual shard_map
    context over ``cp_axis`` (e.g. the pipeline's) — attention calls the
    local ring body directly instead of opening its own shard_map, and
    ``positions=None`` makes the block derive global rotary positions from
    its cp shard index.
    """
    nh, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    act = cfg.dtype

    def attention(q, k, v):
        impl = _resolve_attn_impl(cfg, mesh, manual_cp, q.shape[1])
        if manual_cp:
            if impl == "ring":
                # the pipeline's shard_map is partial-auto, which rejects
                # pallas lowering — keep the jnp tile body there
                return ring_attention_local(
                    q, k, v, axis_name=cfg.cp_axis, causal=True,
                    use_flash=False,
                )
            if impl == "ulysses":
                # same partial-auto shard_map constraint as ring above:
                # no pallas lowering inside the pipeline's blocks
                return ulysses_attention_local(
                    q, k, v, axis_name=cfg.cp_axis, causal=True,
                    use_flash=False,
                )
            raise ValueError(
                "manual-cp blocks support ring or ulysses attention only"
            )
        if impl in ("ring", "ulysses"):
            if mesh is None:
                raise ValueError(f"{impl} attention requires a mesh")
            if cfg.cp_axis not in mesh.axis_names:
                raise ValueError(
                    f"{impl} attention requires a {cfg.cp_axis!r} "
                    f"mesh axis; this mesh has {mesh.axis_names} "
                    "(use attn_impl='dense' on cp-less meshes)"
                )
            local_fn = (
                ring_attention_local
                if impl == "ring"
                else ulysses_attention_local
            )
            k, v = _expand_kv_for_tp(cfg, mesh, nh, k, v)
            spec = _filter_spec(
                P(_batch_axes(cfg, mesh), cfg.cp_axis, cfg.tp_axis, None), mesh
            )
            fn = jax.shard_map(
                lambda q_, k_, v_: local_fn(
                    q_, k_, v_, axis_name=cfg.cp_axis, causal=True
                ),
                mesh=mesh,
                in_specs=(spec, spec, spec),
                out_specs=spec,
                # the ring body may lower to pallas_call (flash tiles)
                check_vma=False,
            )
            return fn(q, k, v)
        if impl == "flash":
            from torchft_tpu.ops.flash_attention import flash_attention

            if mesh is None:
                return flash_attention(q, k, v, causal=True)
            if isinstance(mesh, str):
                raise ValueError(
                    "attn_impl='flash' does not nest in manual shard_map "
                    "contexts; use 'ring'/'ulysses' there"
                )
            # batch/head-parallel over the mesh: each shard holds the FULL
            # sequence (flash is not sequence-parallel — use ring/ulysses
            # for cp) and runs the kernel on its [B/dp.., T, H/tp, D] shard
            sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
            if sizes.get(cfg.cp_axis, 1) > 1:
                raise ValueError(
                    "attn_impl='flash' needs the sequence unsharded; on a "
                    f"{cfg.cp_axis!r} mesh use 'ring' or 'ulysses'"
                )
            k, v = _expand_kv_for_tp(cfg, mesh, nh, k, v)
            spec = _filter_spec(
                P(_batch_axes(cfg, mesh), None, cfg.tp_axis, None), mesh
            )
            fn = jax.shard_map(
                lambda q_, k_, v_: flash_attention(q_, k_, v_, causal=True),
                mesh=mesh,
                in_specs=(spec, spec, spec),
                out_specs=spec,
                # pallas_call's out_shape carries no vma annotation; the
                # kernel is per-shard elementwise in the mesh sense
                check_vma=False,
            )
            return fn(q, k, v)
        if impl != "dense":
            raise ValueError(
                f"unknown attn_impl {impl!r}; "
                "expected 'dense', 'flash', 'ring', or 'ulysses'"
            )
        return dense_attention(q, k, v, causal=True)

    def block(x: jax.Array, p: Params, positions: "Optional[jax.Array]"):
        b, t, e = x.shape
        if positions is None:
            # manual-cp context: x is the local sequence chunk; rotary
            # embeddings need GLOBAL positions, derived from the shard index
            offset = jax.lax.axis_index(cfg.cp_axis) * t
            positions = offset + jnp.arange(t)
        h = _rms_norm(x, p["attn_norm"])
        q = (h @ p["wq"].astype(act)).reshape(b, t, nh, hd)
        k = (h @ p["wk"].astype(act)).reshape(b, t, nkv, hd)
        v = (h @ p["wv"].astype(act)).reshape(b, t, nkv, hd)
        q = _rope(q, positions, cfg.rope_theta)
        k = _rope(k, positions, cfg.rope_theta)
        # GQA kv heads stay unexpanded: each attention impl broadcasts them
        # up AFTER any cross-device transfer (ring ppermute / ulysses
        # all-to-all move nkv, not nh, heads of K/V)
        attn = attention(q, k, v).reshape(b, t, nh * hd)
        x = x + attn @ p["wo"].astype(act)

        h = _rms_norm(x, p["mlp_norm"])
        if cfg.n_experts:
            from torchft_tpu.models.moe import moe_ffn

            y, aux = moe_ffn(h, p, _moe_cfg(cfg), mesh=mesh)
            return x + y, aux
        x = x + _swiglu(h, p["w_gate"], p["w_up"], p["w_down"])
        return x, jnp.zeros((), jnp.float32)

    return block


def _remat(fn, cfg: TransformerConfig):
    """Apply cfg's rematerialization policy to a block function.

    ``"full"`` recomputes the block in the backward except the flash kernel's
    forward call: its two results, which ``ops/flash_attention.py`` names, are
    kept from the forward pass (a layer's ``B T H Dv`` x 2 B + ``B H T`` x 4 B).
    A block without that kernel has no such name and keeps nothing."""
    if cfg.remat_policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_saveable
        )
    if cfg.remat_policy != "full":
        raise ValueError(
            f"unknown remat_policy {cfg.remat_policy!r}; expected 'full' or 'dots'"
        )
    from torchft_tpu.ops.flash_attention import FLASH_LSE_NAME, FLASH_OUT_NAME

    return jax.checkpoint(
        fn,
        policy=jax.checkpoint_policies.save_only_these_names(
            FLASH_OUT_NAME, FLASH_LSE_NAME
        ),
    )


def _named(jaxpr, layers: int = 1):
    """``(name, value's aval, times a step)`` of every ``checkpoint_name`` in a
    gradient's jaxpr outside its rematerialized parts, a scanned one counted
    once a layer."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "name":
            yield eqn.params["name"], eqn.outvars[0].aval, layers
        elif eqn.primitive.name != "checkpoint":
            inner = layers * eqn.params.get("length", 1)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from _named(sub, inner)


def _kept_bytes(jaxpr) -> int:
    """Bytes of the flash kernel's named results in a gradient's jaxpr: what
    the ``"full"`` policy of :func:`_remat` keeps from the forward pass."""
    from torchft_tpu.ops.flash_attention import FLASH_LSE_NAME, FLASH_OUT_NAME

    return sum(
        times * aval.size * aval.dtype.itemsize
        for name, aval, times in _named(jaxpr)
        if name in (FLASH_OUT_NAME, FLASH_LSE_NAME)
    )


def _flash_tiles(jaxpr) -> "Dict[str, int]":
    """A grad step's flash tiles by kind (``ops/flash_attention.py``
    ``tile_kinds``), summed over its calls, heads and layers."""
    from torchft_tpu.ops.flash_attention import TILE_KINDS, call_tiles

    total = dict.fromkeys(TILE_KINDS, 0)
    for name, _, times in _named(jaxpr):
        for kind, n in (call_tiles(name) or {}).items():
            total[kind] += times * n
    return total


def _grad_step(loss, cfg):
    """The body of a family's jitted ``step(params, tokens) -> (loss, grads)``.
    Tracing it sets the gauges ``torchft_remat_kept_bytes`` and
    ``torchft_flash_tiles{kind}`` from the one trace the step is built from
    (the inner ``jit`` is inlined into the caller's: it is there to hand out
    its jaxpr).  A name outside a checkpoint is kept only where ``"full"``
    asked for it, so the other settings read 0 kept bytes."""
    from torchft_tpu.utils import metrics

    grad = jax.jit(jax.value_and_grad(loss), inline=True)

    def step(params, tokens):
        jaxpr = grad.trace(params, tokens).jaxpr.jaxpr
        full = cfg.remat and cfg.remat_policy == "full"
        metrics.REMAT_KEPT_BYTES.set(_kept_bytes(jaxpr) if full else 0)
        for kind, n in _flash_tiles(jaxpr).items():
            metrics.FLASH_TILES.labels(kind=kind).set(n)
        return grad(params, tokens)

    return step


def forward(
    params: Params,
    tokens: jax.Array,
    cfg: TransformerConfig,
    mesh: "Optional[Mesh]" = None,
    return_aux: bool = False,
) -> jax.Array:
    """tokens [B, T] int32 -> logits [B, T, vocab] (fp32).

    With a mesh, activations get sharding constraints so XLA places the tp
    collectives; without one it is a plain single-device program (the
    `entry()` compile-check path). ``return_aux`` additionally returns the
    summed MoE load-balance loss (0 for dense FFN configs).
    """
    b, t = tokens.shape
    x = _embed(params, tokens, cfg, sharded=mesh is not None)
    positions = jnp.arange(t)

    if mesh is not None:
        act_spec = NamedSharding(
            mesh, P(_batch_axes(cfg, mesh), _seq_axis(cfg, mesh), None)
        )
        x = jax.lax.with_sharding_constraint(x, act_spec)

    block = _make_block(cfg, mesh)
    if cfg.remat:
        block = _remat(block, cfg)

    def scan_body(carry, layer_params):
        x, aux_sum = carry
        x, aux = block(x, layer_params, positions)
        if mesh is not None:
            x = jax.lax.with_sharding_constraint(x, act_spec)
        return (x, aux_sum + aux), None

    (x, aux_sum), _ = jax.lax.scan(
        scan_body, (x, jnp.zeros((), jnp.float32)), params["blocks"]
    )
    logits = _head(params, x, cfg)
    if return_aux:
        return logits, aux_sum
    return logits


def _embed(
    params: Params, tokens: jax.Array, cfg: TransformerConfig, sharded: bool
) -> jax.Array:
    """Token embedding [B, T] -> [B, T, E].

    Sharded path: one-hot matmul instead of gather — runs on the MXU and
    partitions cleanly when embed is sharded (tp, fsdp); XLA's SPMD
    partitioner fully rematerializes a sharded gather.
    """
    act = cfg.dtype
    if sharded:
        return jnp.einsum(
            "btv,ve->bte",
            jax.nn.one_hot(tokens, cfg.vocab_size, dtype=act),
            params["embed"].astype(act),
        )
    return params["embed"].astype(act)[tokens]


def _head(params: Params, x: jax.Array, cfg: TransformerConfig) -> jax.Array:
    """Final norm + tied output head: [B,T,E] x [E,V] on the MXU.

    The matmul runs in the ACTIVATION dtype (bf16 on TPU) with f32
    accumulation — at V=32k this is the largest single matmul in the
    model, and running it f32 costs the MXU's 3-pass f32 emulation on the
    ~10% of model FLOPs it represents (measured +1.5 MFU points on the
    flagship from this cast alone).  Logits come out f32 from the
    accumulator."""
    x = _rms_norm(x, params["final_norm"])
    return jnp.einsum(
        "bte,ve->btv",
        x.astype(cfg.dtype),
        params["embed"].astype(cfg.dtype),
        preferred_element_type=jnp.float32,
    )


def forward_pipelined(
    params: Params,
    tokens: jax.Array,
    cfg: TransformerConfig,
    mesh: Mesh,
    microbatches: int = 4,
    pp_axis: str = "pp",
    return_aux: bool = False,
) -> "jax.Array | tuple":
    """Pipeline-parallel forward: decoder blocks GPipe-scheduled over the
    ``pp`` mesh axis (torchft_tpu/parallel/pipeline.py), embedding/head
    outside the pipe.

    Each stage holds ``n_layers / pp`` consecutive blocks (the stacked
    layer dim is sharded over pp). Composes with the other parallelism
    axes:

    - ``attn_impl='ring'`` / ``'ulysses'`` with a ``cp`` mesh axis: the
      pipeline shard_map goes manual over (pp, cp) and each stage runs the
      local sequence-parallel body (K/V ppermute ring / head all-to-all);
    - ``n_experts > 0`` (MoE / ep): expert FFNs run inside the stage; the
      load-balance aux loss rides the pipe as a side stream of the
      activation pytree and is returned with ``return_aux=True``. Aux is
      computed per microbatch (batch statistics over each microbatch
      rather than the full batch — an equally valid estimator).
    """
    if cfg.attn_impl == "auto":
        # inside the pipe, flash never applies (the pipeline's
        # partial-auto shard_map rejects pallas lowering): auto means
        # ring when the sequence is cp-sharded, dense otherwise
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        cfg = dataclasses.replace(
            cfg,
            attn_impl="ring" if sizes.get(cfg.cp_axis, 1) > 1 else "dense",
        )
    manual_cp = cfg.attn_impl in ("ring", "ulysses")
    if cfg.attn_impl not in ("dense", "ring", "ulysses"):
        raise ValueError(
            f"forward_pipelined does not support attn_impl "
            f"{cfg.attn_impl!r}; expected 'dense', 'ring', or 'ulysses' "
            "('flash' does not compose with the pipeline's manual "
            "shard_map — use ring/ulysses for sequence parallelism "
            "inside the pipe)"
        )
    if manual_cp and cfg.cp_axis not in mesh.axis_names:
        raise ValueError(
            f"{cfg.attn_impl} attention requires a {cfg.cp_axis!r} mesh "
            f"axis; this mesh has {mesh.axis_names}"
        )
    from torchft_tpu.parallel.pipeline import pipeline_apply

    b, t = tokens.shape
    x = _embed(params, tokens, cfg, sharded=True)
    positions = None if manual_cp else jnp.arange(t)
    # MoE blocks pin their [E, C, d] expert buffers to the ep axis inside
    # the pipeline's partial-manual shard_map — via a bare-PartitionSpec
    # constraint ("manual" sentinel), since ep stays automatic in there
    moe_mesh = (
        "manual" if cfg.n_experts and cfg.ep_axis in mesh.axis_names else None
    )
    block = _make_block(cfg, moe_mesh, manual_cp=manual_cp)

    moe = bool(cfg.n_experts)

    def layer_fn(h, layer_params):
        # non-MoE: plain array activations — no dead aux stream riding the
        # pipe (it would cost a ppermute + scatter per tick for zeros)
        if not moe:
            return block(h, layer_params, positions)[0]
        y, aux = block(h["x"], layer_params, positions)
        if manual_cp:
            # aux is computed from this cp shard's local tokens: average
            # over cp for the global-batch statistic (also makes the value
            # cp-invariant, which the pipe's carry signature requires)
            aux = jax.lax.pmean(aux, cfg.cp_axis)
        return {"x": y, "aux": h["aux"] + aux}

    if cfg.remat:
        layer_fn = _remat(layer_fn, cfg)

    # pipeline_apply is partial-manual over pp (+cp for ring/ulysses):
    # batch (dp/fsdp/ep) and weight (fsdp/tp) shardings flow automatically
    # from input shardings; MoE adds a per-example aux side stream
    out = pipeline_apply(
        params["blocks"],
        {"x": x, "aux": jnp.zeros((b,), jnp.float32)} if moe else x,
        layer_fn,
        mesh,
        axis_name=pp_axis,
        microbatches=microbatches,
        seq_axis=cfg.cp_axis if manual_cp else None,
    )
    logits = _head(params, out["x"] if moe else out, cfg)
    if return_aux:
        return logits, out["aux"].mean() if moe else jnp.zeros((), jnp.float32)
    return logits


def _next_token_nll(logits: jax.Array, tokens: jax.Array) -> jax.Array:
    """``[B, T, V]`` logits, ``[B, T]`` tokens -> ``[B, T - 1]``: the negative
    log-likelihood of token ``t + 1`` at position ``t``."""
    logits = logits[:, :-1]
    targets = tokens[:, 1:]
    # fused NLL: logsumexp(logits) - logit[target] == -log_softmax[target]
    # without materializing the full [B, T, V] log-probability tensor (at
    # flagship scale that tensor is ~1 GB of f32 HBM write+read per step)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return lse - picked


def loss_fn(
    params: Params,
    tokens: jax.Array,
    cfg: TransformerConfig,
    mesh: "Optional[Mesh]" = None,
) -> jax.Array:
    """Next-token cross-entropy, mean over all positions but the last.
    MoE configs add the weighted load-balance auxiliary loss."""
    logits, aux = forward(params, tokens, cfg, mesh, return_aux=True)
    loss = _next_token_nll(logits, tokens).mean()
    if cfg.n_experts:
        loss = loss + cfg.moe_aux_weight * aux
    return loss


# ---------------------------------------------------------------------------
# training step
# ---------------------------------------------------------------------------


def make_train_step(
    cfg: TransformerConfig,
    optimizer: Any,
    mesh: "Optional[Mesh]" = None,
    donate: bool = True,
):
    """Build a jitted (params, opt_state, tokens) -> (params, opt_state, loss)
    full training step (fwd + bwd + optax update). With a mesh, in/out
    shardings pin params to `param_specs` and the batch to `batch_spec`."""

    def step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens, cfg, mesh)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = jax.tree_util.tree_map(lambda p, u: p + u, params, updates)
        return params, opt_state, loss

    if mesh is None:
        return jax.jit(step, donate_argnums=(0, 1) if donate else ())

    pspecs = param_specs(cfg, mesh)
    param_sh = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), pspecs)
    batch_sh = NamedSharding(mesh, batch_spec(cfg, mesh))
    return jax.jit(
        step,
        in_shardings=(param_sh, None, batch_sh),
        out_shardings=(param_sh, None, None),
        donate_argnums=(0, 1) if donate else (),
    )


def make_grad_step(
    cfg: TransformerConfig, mesh: "Optional[Mesh]" = None
):
    """Build a jitted (params, tokens) -> (loss, grads) step — the FT-DDP
    shape: grads come back to the host, `Manager.allreduce` averages them
    across replica groups over DCN, then `apply_updates` runs (reference
    ddp.py:47-79 comm-hook factored the same way)."""

    step = _grad_step(lambda p, t: loss_fn(p, t, cfg, mesh), cfg)
    if mesh is None:
        return jax.jit(step)
    pspecs = param_specs(cfg, mesh)
    param_sh = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), pspecs)
    batch_sh = NamedSharding(mesh, batch_spec(cfg, mesh))
    return jax.jit(
        step,
        in_shardings=(param_sh, batch_sh),
        out_shardings=(None, param_sh),
    )
