"""The quickest proof that the fault-tolerant training step runs on the TPU.

    python chip_smoke.py            # one chip  (legs A, B, C)
    python chip_smoke.py --chips 4  # four chips (4x1 FT-DDP, 2x(fsdp 2) HSDP)

Drives the product's main path once, through the entry points a user
calls — ``ft.Manager`` + in-process ``LighthouseServer`` +
``ft.ProcessGroupTCP`` + ``ft.DistributedDataParallel`` + ``ft.Optimizer``
wired as in ``examples/train_ddp.py``, with the flagship transformer
(``WIDTHS`` below, flash attention, dots remat) as the model — in ONE
process: the coordination servers are native threads and replica groups are
Python threads, because a chip belongs to one process at a time.

One chip:

- leg A: one replica group, full depth (16 layers).  A few FT steps — async
  quorum, jitted fwd+bwd on the chip, the WHOLE gradient pytree through
  ``ddp.allreduce_gradients`` (a group alone gets its device leaves back as
  they are; with peers: device -> host -> ring -> host -> device),
  commit, optimizer update — then the same seeds through a plain loop with
  no Manager; losses and final params must agree.
- leg B: two replica groups on the same chip at full width, depth cut to
  what two param+adamw copies and two steps' working sets leave room for;
  replica 1 is killed mid-run, restarted and live-healed from replica 0
  over the Manager's default checkpoint transport.
- leg C: one int8 ``manager.allreduce(..., should_quantize=True,
  device_quantize=True)`` of a flagship-sized fragment between two replica
  groups, and the three Pallas codec kernels against the host codec.

Four chips (``--chips 4``, no one-chip leg runs): four replica groups each
on its own device at full depth with a kill/heal, checked against the mean
of the four gradients computed directly; then two replica groups on
disjoint 2-chip fsdp meshes with a kill/heal, checked against a
single-device plain loop.

Any failed assertion or raised leg ends the run non-zero.  The LAST stdout
line is ``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count":
N}}`` on success; without a TPU it is ``{"ok": false, ...}`` and the exit
code is 1 — this script never trains on a CPU and calls it a pass.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import threading
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

# The flagship widths — the one configuration this repo sizes for a v5e.
# Never cut; depth and batch are per leg.
WIDTHS = dict(
    vocab_size=32000, d_model=1536, n_heads=6, n_kv_heads=3, d_ff=4096,
    max_seq_len=1024,
)
PLATFORM = "tpu"

LEG_A = dict(layers=16, batch=8, steps=3)
# Depth from the v5e AOT rehearsal (memory_analysis of the grad step): two
# replicas each peak at params + adamw + grads + the step's temporaries, and
# both can be mid-step at once.  L16 B8 needs 2 x 14.1 GiB; L4 B8 needs
# 2 x 5.5 GiB of a 15.75 GiB chip — the deepest with >=25% headroom.
LEG_B = dict(layers=4, batch=8, steps=6, kill_at=2)
# One Streaming-DiLoCo fragment of the 16-layer flagship (1/8 of its
# 464,438,784 params): 28,348 rows of 2048 — not a multiple of the kernels'
# 32-row tile, so the padded edge is exercised.
LEG_C_ELEMS = 464_438_784 // 8
DDP4 = dict(layers=16, batch=8, steps=5, kill_at=2)
HSDP = dict(layers=16, batch=8, steps=5, kill_at=2, fsdp=2)

LR = 3e-4
OP_TIMEOUT_S = 300.0
LEG_DEADLINE_S = 900.0
GIB = float(2**30)


def report(leg: str, **fields: Any) -> None:
    print(json.dumps({"leg": leg, **fields}, default=str), flush=True)


def result_line(ok: bool, device: Dict[str, Any], reason: Optional[str] = None) -> str:
    out: Dict[str, Any] = {"ok": ok, "device": device}
    if reason is not None:
        out["reason"] = reason
    return json.dumps(out)


def device_info() -> Dict[str, Any]:
    import jax

    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }


def refusal(device: Dict[str, Any], chips: int) -> Optional[str]:
    """Why this run may not proceed, or None."""
    if device["platform"] != PLATFORM:
        return (
            f"jax.devices()[0].platform is {device['platform']!r}, not "
            f"{PLATFORM!r}: refusing to run the chip smoke off-chip"
        )
    if device["count"] != chips:
        return f"--chips {chips} needs {chips} device(s), jax sees {device['count']}"
    return None


def require_compiled_kernels() -> None:
    """Both Pallas modules pick interpret mode from the backend; on the
    chip path that must resolve to compiled."""
    from torchft_tpu.ops import flash_attention, pallas_quant

    for mod in (flash_attention, pallas_quant):
        if mod._interpret():
            raise RuntimeError(f"{mod.__name__} would run in interpret mode")


def require_mosaic(hlo_text: str, what: str) -> None:
    if "tpu_custom_call" not in hlo_text:
        raise AssertionError(f"{what}: no Mosaic custom call in the lowered HLO")


def hbm(devices: "List[Any]") -> "List[Dict[str, float]]":
    out = []
    for d in devices:
        s = d.memory_stats() or {}
        out.append({
            k: round(s[k] / GIB, 3)
            for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
            if k in s
        })
    return out


def flagship(n_layers: int):
    from torchft_tpu.models import transformer as tfm

    return tfm.TransformerConfig(
        **WIDTHS, n_layers=n_layers, attn_impl="flash", remat=True,
        remat_policy="dots",
    )


def tree_nbytes(tree: Any) -> int:
    import jax

    return sum(x.nbytes for x in jax.tree_util.tree_leaves(tree))


def tokens_for(cfg: Any, batch: int, data_seed: int, step: int) -> np.ndarray:
    rng = np.random.default_rng([data_seed, step])
    return rng.integers(
        0, cfg.vocab_size, (batch, cfg.max_seq_len), dtype=np.int32
    )


# ---------------------------------------------------------------------------
# where one replica group's state lives
# ---------------------------------------------------------------------------


class Placement:
    """Shardings of one replica group: a single device, or an inner mesh
    (HSDP)."""

    def __init__(self, cfg: Any, devices: "List[Any]", fsdp: int = 1) -> None:
        import jax
        from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

        from torchft_tpu.models import transformer as tfm

        self.devices = list(devices)
        if fsdp == 1:
            (dev,) = self.devices
            self.mesh = None
            self.scalar = SingleDeviceSharding(dev)
            self.batch = self.scalar
            self.params = jax.tree_util.tree_map(
                lambda _: self.scalar,
                jax.eval_shape(lambda k: tfm.init_params(k, cfg), jax.random.PRNGKey(0)),
            )
        else:
            self.mesh = jax.sharding.Mesh(
                np.array(self.devices).reshape(fsdp), ("fsdp",)
            )
            self.scalar = NamedSharding(self.mesh, PartitionSpec())
            self.batch = NamedSharding(self.mesh, tfm.batch_spec(cfg, self.mesh))
            self.params = jax.tree_util.tree_map(
                lambda s: NamedSharding(self.mesh, s),
                tfm.param_specs(cfg, self.mesh),
                is_leaf=lambda s: isinstance(s, PartitionSpec),
            )

    def check(self, tree: Any, shardings: Any, what: str) -> None:
        """Every leaf is a live ``jax.Array`` with exactly its sharding on
        this group's devices — not host numpy, not replicated, not on
        someone else's chip."""
        import jax

        for x, s in zip(
            jax.tree_util.tree_leaves(tree), jax.tree_util.tree_leaves(shardings)
        ):
            if not isinstance(x, jax.Array):
                raise AssertionError(f"{what}: leaf is {type(x).__name__}, not a jax.Array")
            if not x.sharding.is_equivalent_to(s, x.ndim):
                raise AssertionError(f"{what}: leaf sharding {x.sharding} != {s}")
            if any(d.platform != PLATFORM for d in x.devices()):
                raise AssertionError(f"{what}: leaf not on a {PLATFORM} device")


def init_state(cfg: Any, tx: Any, seed: int, place: Placement) -> "tuple[Dict[str, Any], Any]":
    """Params from ``seed`` and a fresh optimizer state on the group's
    devices, plus the shardings everything must keep (and return to after
    a heal).  Same seed -> bitwise the same params on every group."""
    import jax

    from torchft_tpu.models import transformer as tfm

    with jax.default_device(place.devices[0]):
        params = jax.jit(lambda k: tfm.init_params(k, cfg))(jax.random.PRNGKey(seed))
        if place.mesh is not None:
            params = tfm.shard_params(params, place.mesh, cfg)
        opt_state = tx.init(params)
    # optax moments are zeros_like(params) and inherit their shardings;
    # scalars (the step count) land uncommitted on the default device
    shardings = {
        "params": place.params,
        "opt_state": jax.tree_util.tree_map(
            lambda x: x.sharding if x.ndim else place.scalar, opt_state
        ),
    }
    state = jax.device_put({"params": params, "opt_state": opt_state}, shardings)
    place.check(state, shardings, "fresh state")
    return state, shardings


_fingerprint_jit: "Optional[Callable[[Any], Any]]" = None


def fingerprint(params: Any) -> "List[int]":
    """Per-leaf wrap-around sum of the raw 32-bit patterns, computed where
    the params live: two trees with equal fingerprints after every commit
    have (to a 2^-32 fluke per leaf) stayed bitwise equal, without moving
    gigabytes to the host each step."""
    global _fingerprint_jit
    import jax
    import jax.numpy as jnp

    if _fingerprint_jit is None:
        _fingerprint_jit = jax.jit(
            lambda t: [
                jnp.sum(jax.lax.bitcast_convert_type(x, jnp.uint32), dtype=jnp.uint32)
                for x in jax.tree_util.tree_leaves(t)
            ]
        )
    return [int(v) for v in _fingerprint_jit(params)]


def compile_grad_step(cfg: Any, place: Placement, state: Any, batch: int, leg: str):
    """AOT-compile ``tfm.make_grad_step`` for this placement: one timed
    compile whose HLO and memory analysis are inspected before it runs."""
    import jax

    from torchft_tpu.models import transformer as tfm

    toks = jax.device_put(tokens_for(cfg, batch, 0, 0), place.batch)
    t0 = time.perf_counter()
    lowered = tfm.make_grad_step(cfg, place.mesh).lower(state["params"], toks)
    require_mosaic(lowered.as_text(), f"leg {leg} grad step")
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    report(
        leg, event="compiled_grad_step", compile_s=round(compile_s, 2),
        devices=[str(d) for d in place.devices],
        args_gib=round(mem.argument_size_in_bytes / GIB, 2),
        out_gib=round(mem.output_size_in_bytes / GIB, 2),
        temp_gib=round(mem.temp_size_in_bytes / GIB, 2),
    )
    return compiled, mem


# ---------------------------------------------------------------------------
# one replica group's training loop (examples/train_ddp.py, instrumented)
# ---------------------------------------------------------------------------


class _Kill(Exception):
    """The deliberate mid-run death of a replica group."""


def train_replica(
    name: str,
    lighthouse_addr: str,
    cfg: Any,
    place: Placement,
    grad_step: Any,
    *,
    batch: int,
    steps: int,
    seed: int,
    data_seed: int,
    kill_at: Optional[int] = None,
    rejoin: "Optional[tuple[int, threading.Event]]" = None,
    barrier: Optional[threading.Barrier] = None,
    on_grads: "Optional[Callable[[int, Any, Any], None]]" = None,
    leg: str = "?",
) -> "Dict[str, Any]":
    """FT-DDP over the elastic replica dimension; returns per-step records
    and the final params on the host.  ``kill_at``: die once when about to
    start that step, restart from fresh state, and heal live.  ``rejoin``
    = (step, event): the victim sets the event once its new incarnation is
    up; survivors wait for it before starting that step, so they cannot
    run out of steps while the victim is still rebuilding its state."""
    import jax
    import optax

    import torchft_tpu as ft

    records: "List[Dict[str, Any]]" = []
    fingerprints: "Dict[int, List[int]]" = {}
    heals: "List[Dict[str, Any]]" = []
    for incarnation in range(2):
        tx = optax.adamw(LR)
        state, shardings = init_state(cfg, tx, seed, place)
        healed = {"n": 0}

        def load_state_dict(sd: Any) -> None:
            # a heal delivers host numpy (and, for delta-reused fragments,
            # this group's own arrays): everything goes back onto the
            # group's devices before the next jitted step touches it
            state.update(jax.device_put(sd, shardings))
            healed["n"] += 1

        manager = ft.Manager(
            pg=ft.ProcessGroupTCP(timeout=OP_TIMEOUT_S),
            min_replica_size=1,
            load_state_dict=load_state_dict,
            state_dict=lambda: {"params": state["params"],
                                "opt_state": state["opt_state"]},
            replica_id=name,
            lighthouse_addr=lighthouse_addr,
            group_rank=0,
            group_world_size=1,
            use_async_quorum=True,
            timeout=OP_TIMEOUT_S,
            quorum_timeout=OP_TIMEOUT_S,
            # every group builds the same params from the seed, so the
            # step-0 broadcast from the primary is not needed — and with it
            # the others would sit step 0 out (healing replicas contribute
            # zeros), which the gradient checks below could not tell from a
            # broken ring
            init_sync=False,
        )
        ddp = ft.DistributedDataParallel(manager)
        optimizer = ft.Optimizer(manager, tx)
        phases: "Dict[str, float]" = {}
        try:
            if barrier is not None and incarnation == 0:
                barrier.wait(timeout=LEG_DEADLINE_S)
            if rejoin is not None and incarnation == 1:
                rejoin[1].set()
            while manager.current_step() < steps:
                step = manager.current_step()
                if kill_at == step and incarnation == 0:
                    raise _Kill()
                if rejoin is not None and kill_at is None and step == rejoin[0]:
                    if not rejoin[1].wait(timeout=LEG_DEADLINE_S):
                        raise TimeoutError("the killed replica never came back")
                t0 = time.perf_counter()
                toks = jax.device_put(
                    tokens_for(cfg, batch, data_seed, step), place.batch
                )
                optimizer.begin_step()
                loss, grads = grad_step(state["params"], toks)
                loss = float(loss)  # waits for the device
                t_grad = time.perf_counter()
                work = ddp.allreduce_gradients(grads)
                avg = work.wait(timeout=OP_TIMEOUT_S)
                if on_grads is not None:
                    on_grads(step, grads, avg)
                # drop the device gradients before anything else is
                # allocated: at full depth there is no room for two copies
                del grads, work
                t_ring = time.perf_counter()
                # (host numpy off a ring; a group alone gets its device
                # leaves back as themselves and nothing moves here)
                avg = jax.block_until_ready(jax.device_put(avg, place.params))
                t_h2d = time.perf_counter()
                healed_before = healed["n"]
                committed = manager.should_commit()
                if committed:
                    # the vote is where an async heal lands in `state`:
                    # read it only now
                    state["params"], state["opt_state"] = optimizer.update(
                        state["params"], avg, state["opt_state"]
                    )
                    jax.block_until_ready(state["params"])
                    place.check(state, shardings, f"{name} step {step}")
                    fingerprints[manager.current_step()] = fingerprint(state["params"])
                del avg
                t_end = time.perf_counter()
                now = manager.phase_times()
                delta = {k: round(v - phases.get(k, 0.0), 4) for k, v in now.items()
                         if v - phases.get(k, 0.0) > 0}
                phases = now
                err = manager.errored()
                rec = {
                    "replica": name, "incarnation": incarnation, "step": step,
                    "loss": loss, "committed": committed,
                    "participants": manager.num_participants(),
                    "errored": None if err is None else repr(err),
                    "healed": healed["n"] > healed_before,
                    "grad_s": round(t_grad - t0, 3),
                    "allreduce_s": round(t_ring - t_grad, 3),
                    "h2d_s": round(t_h2d - t_ring, 3),
                    "commit_update_s": round(t_end - t_h2d, 3),
                    "step_s": round(t_end - t0, 3),
                    "phases_s": delta,
                }
                records.append(rec)
                report(leg, event="step", **rec)
                if rec["healed"]:
                    heals.append({
                        "replica": name, "incarnation": incarnation, "step": step,
                        "bytes": tree_nbytes(state),
                        "seconds": {k: v for k, v in delta.items() if k.startswith("heal")},
                    })
            final = jax.tree_util.tree_map(np.asarray, state["params"])
            return {
                "name": name, "records": records, "fingerprints": fingerprints,
                "heals": heals, "final_params": final,
                "hbm_gib": hbm(place.devices),
                "final_step": manager.current_step(),
            }
        except _Kill:
            report(leg, event="killed", replica=name, step=manager.current_step())
        finally:
            manager.shutdown()
            del state
    raise RuntimeError(f"{name}: exhausted incarnations")


def plain_loop(
    cfg: Any, place: Placement, grad_step: Any, *, batch: int, steps: int,
    seed: int, data_seed: int,
) -> "Dict[str, Any]":
    """The independent reference: same seeds, same compiled grad step, a
    bare optax update — no Manager, no collective, no host round trip."""
    import jax
    import optax

    tx = optax.adamw(LR)
    state, _ = init_state(cfg, tx, seed, place)

    def update(params, grads, opt_state):
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state

    update = jax.jit(update, donate_argnums=(0, 2))
    losses, step_s = [], []
    for step in range(steps):
        t0 = time.perf_counter()
        toks = jax.device_put(tokens_for(cfg, batch, data_seed, step), place.batch)
        loss, grads = grad_step(state["params"], toks)
        state["params"], state["opt_state"] = update(
            state["params"], grads, state["opt_state"]
        )
        del grads
        jax.block_until_ready(state["params"])
        losses.append(float(loss))
        step_s.append(round(time.perf_counter() - t0, 3))
    final = jax.tree_util.tree_map(np.asarray, state["params"])
    return {"losses": losses, "step_s": step_s, "final_params": final}


def run_replicas(fns: "List[Callable[[], Any]]") -> "List[Any]":
    """Run one callable per replica group on daemon threads; any failure
    (or a thread still alive at the deadline) fails the leg."""
    out: "Dict[int, Any]" = {}
    errs: "Dict[int, BaseException]" = {}

    def runner(i: int) -> None:
        try:
            out[i] = fns[i]()
        except BaseException as e:  # noqa: BLE001 - re-raised on the main thread
            errs[i] = e

    threads = [
        threading.Thread(target=runner, args=(i,), daemon=True, name=f"replica{i}")
        for i in range(len(fns))
    ]
    for t in threads:
        t.start()
    deadline = time.monotonic() + LEG_DEADLINE_S
    # poll rather than join in turn: the first failure ends the leg at
    # once instead of after its peers' collective timeouts
    while any(t.is_alive() for t in threads) and not errs:
        if time.monotonic() > deadline:
            raise TimeoutError("replica thread still running at the leg deadline")
        time.sleep(0.1)
    if errs:
        raise next(iter(errs.values()))
    return [out[i] for i in range(len(fns))]


def lighthouse(min_replicas: int = 1):
    from torchft_tpu.coordination import LighthouseServer

    # join_timeout long, heartbeat_timeout short: a live straggler (a
    # replica still compiling) is waited for, a dead one only until its
    # heartbeat lapses
    return LighthouseServer(
        min_replicas=min_replicas, join_timeout_ms=60_000,
        heartbeat_timeout_ms=2_000,
    )


def assert_bitwise_equal(a: Any, b: Any, what: str) -> None:
    import jax

    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        if not np.array_equal(x, y):
            raise AssertionError(f"{what}: params differ bitwise")


def check_kill_heal(results: "List[Dict[str, Any]]", victim: int, steps: int, leg: str) -> None:
    """The kill/heal contract shared by leg B and both four-chip legs."""
    for r in results:
        if r["final_step"] != steps:
            raise AssertionError(f"{r['name']} ended at step {r['final_step']}, not {steps}")
        for rec in r["records"]:
            if rec["errored"] is not None:
                raise AssertionError(f"error latched outside the deliberate kill: {rec}")
    if not any(h["incarnation"] == 1 for h in results[victim]["heals"]):
        raise AssertionError("the killed replica did not restart and heal")
    if not results[victim]["records"][-1]["committed"]:
        raise AssertionError("no healthy committed step after the heal")
    # bitwise after every commit both sides saw (the healed replica's
    # commits are all post-heal), and on the full final params
    base = results[0]
    for r in results[1:]:
        common = sorted(set(base["fingerprints"]) & set(r["fingerprints"]))
        if not common:
            raise AssertionError("no common committed step to compare")
        for s in common:
            if base["fingerprints"][s] != r["fingerprints"][s]:
                raise AssertionError(f"params diverged at step {s}: {base['name']} vs {r['name']}")
        assert_bitwise_equal(base["final_params"], r["final_params"], leg)
    report(leg, event="kill_heal_ok", heals=results[victim]["heals"],
           steps=steps, bitwise_equal_after_heal=True)


def free_device_memory(leg: str, devices: "List[Any]") -> None:
    gc.collect()
    report(leg, event="hbm_after_leg", hbm_gib=hbm(devices))


# ---------------------------------------------------------------------------
# one-chip legs
# ---------------------------------------------------------------------------


def leg_a(seed: int) -> None:
    import jax
    import optax

    dev = jax.devices()[0]
    cfg = flagship(LEG_A["layers"])
    place = Placement(cfg, [dev])
    probe, _ = init_state(cfg, optax.adamw(LR), seed, place)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(probe["params"]))
    grad_bytes = tree_nbytes(probe["params"])
    grad_step, _ = compile_grad_step(cfg, place, probe, LEG_A["batch"], "A")
    del probe
    report("A", event="start", device=device_info(), layers=cfg.n_layers,
           batch=LEG_A["batch"], seq=cfg.max_seq_len, params=n_params,
           grad_bytes_per_step_each_way=grad_bytes)

    lh = lighthouse()
    try:
        ft_run = train_replica(
            "leg_a", lh.address(), cfg, place, grad_step, batch=LEG_A["batch"],
            steps=LEG_A["steps"], seed=seed, data_seed=seed + 1, leg="A",
        )
    finally:
        lh.shutdown()
    for rec in ft_run["records"]:
        if not rec["committed"] or rec["errored"] is not None:
            raise AssertionError(f"leg A step not cleanly committed: {rec}")
        if not np.isfinite(rec["loss"]):
            raise AssertionError(f"leg A non-finite loss: {rec}")
    if len(ft_run["records"]) != LEG_A["steps"]:
        raise AssertionError("leg A took a different number of steps than asked")
    gc.collect()

    ref = plain_loop(
        cfg, place, grad_step, batch=LEG_A["batch"], steps=LEG_A["steps"],
        seed=seed, data_seed=seed + 1,
    )
    ft_losses = [r["loss"] for r in ft_run["records"]]
    # world size 1: the host round trip copies and divides by one, so the
    # two loops run the same arithmetic — rounding is all that may differ
    np.testing.assert_allclose(ft_losses, ref["losses"], rtol=1e-6)
    pairs = list(zip(jax.tree_util.tree_leaves(ft_run["final_params"]),
                     jax.tree_util.tree_leaves(ref["final_params"])))
    for x, y in pairs:
        np.testing.assert_allclose(x, y, rtol=1e-6, atol=1e-7)
    steady = ft_run["records"][1:]
    report(
        "A", event="done", ft_losses=ft_losses, plain_losses=ref["losses"],
        final_params_bitwise_equal=all(np.array_equal(x, y) for x, y in pairs),
        ft_step_s=[r["step_s"] for r in ft_run["records"]],
        plain_step_s=ref["step_s"],
        steady_grad_s=min(r["grad_s"] for r in steady),
        steady_allreduce_s=min(r["allreduce_s"] for r in steady),
        steady_h2d_s=min(r["h2d_s"] for r in steady),
        hbm_gib=hbm([dev]),
    )
    del ft_run, ref, grad_step
    free_device_memory("A", [dev])


def leg_b(seed: int) -> None:
    import jax
    import optax

    dev = jax.devices()[0]
    cfg = flagship(LEG_B["layers"])
    place = Placement(cfg, [dev])
    probe, _ = init_state(cfg, optax.adamw(LR), seed, place)
    p_bytes = tree_nbytes(probe["params"])
    grad_step, mem = compile_grad_step(cfg, place, probe, LEG_B["batch"], "B")
    del probe
    # per replica: params + 2 adamw moments + grads out + step temporaries,
    # or (during the update) state + device grads + averaged grads
    peak = max(4 * p_bytes + mem.temp_size_in_bytes, 5 * p_bytes)
    limit = (dev.memory_stats() or {}).get("bytes_limit")
    report("B", event="start", device=device_info(), layers=cfg.n_layers,
           batch=LEG_B["batch"], seq=cfg.max_seq_len,
           why=("full width; depth cut so that two replicas, both mid-step, "
                "fit one chip"),
           per_replica_peak_gib=round(peak / GIB, 2),
           bytes_limit_gib=None if limit is None else round(limit / GIB, 2))
    if limit is not None and 2 * peak > 0.85 * limit:
        raise AssertionError(
            f"leg B does not fit: 2 x {peak / GIB:.2f} GiB of {limit / GIB:.2f} GiB"
        )

    lh = lighthouse()
    barrier = threading.Barrier(2)
    rejoin = (LEG_B["kill_at"] + 1, threading.Event())
    try:
        results = run_replicas([
            lambda i=i: train_replica(
                f"leg_b_{i}", lh.address(), cfg, place, grad_step,
                batch=LEG_B["batch"], steps=LEG_B["steps"], seed=seed,
                data_seed=seed + 10 + i, barrier=barrier, rejoin=rejoin,
                kill_at=LEG_B["kill_at"] if i == 1 else None, leg="B",
            )
            for i in range(2)
        ])
    finally:
        lh.shutdown()
    check_kill_heal(results, victim=1, steps=LEG_B["steps"], leg="B")
    both = [r for r in results[0]["records"] if r["participants"] == 2]
    if not both:
        raise AssertionError("leg B never ran a two-replica step")
    report("B", event="done",
           two_replica_step_s=min(r["step_s"] for r in both),
           two_replica_allreduce_s=min(r["allreduce_s"] for r in both),
           grad_bytes=p_bytes, hbm_gib=hbm([dev]))
    del results, grad_step
    free_device_memory("B", [dev])


def leg_c(seed: int) -> None:
    """Device-quantized sync between two replica groups + kernel parity."""
    import jax
    import jax.numpy as jnp

    import torchft_tpu as ft
    from torchft_tpu.ops import pallas_quant as pq
    from torchft_tpu.ops import quantization as host_q

    dev = jax.devices()[0]
    n = LEG_C_ELEMS
    cols = 2048
    frags = [
        jax.block_until_ready(
            jax.jit(lambda k: jax.random.normal(k, (n,), jnp.float32) * 3.0)(
                jax.device_put(jax.random.PRNGKey(seed + 100 + r), dev)
            )
        )
        for r in range(2)
    ]
    host_frags = [np.asarray(f) for f in frags]
    report("C", event="start", device=device_info(), elems=n,
           fragment_mib=round(n * 4 / 2**20, 1))
    findings: "List[str]" = []

    # --- the three kernels, compiled, against the host codec -------------
    rows = -(-n // cols)
    mat = np.zeros((rows * cols,), np.float32)
    mat[:n] = host_frags[0]
    mat = mat.reshape(rows, cols)
    t0 = time.perf_counter()
    d_scales, d_payload = jax.block_until_ready(
        pq.fused_quantize_into_int8(jax.device_put(mat, dev))
    )
    quantize_first_s = time.perf_counter() - t0
    require_mosaic(
        pq._quantize_2d.lower(
            jax.ShapeDtypeStruct(mat.shape, jnp.float32), interpret=pq._interpret()
        ).as_text(),
        "leg C quantize kernel",
    )
    h_scales, h_payload = host_q.quantize(mat)
    np.testing.assert_allclose(np.asarray(d_scales), h_scales, rtol=1e-6)
    step = np.abs(mat).max(axis=1, keepdims=True) / 127.0
    diff = np.abs(np.asarray(d_payload).astype(np.int32) - h_payload.astype(np.int32))
    deq = np.asarray(jax.block_until_ready(
        pq.fused_dequantize_from_int8(d_scales, d_payload, shape=mat.shape)
    ))
    roundtrip_steps = float((np.abs(deq - mat) / step).max())
    report("C", event="quantize_vs_host", codes=int(diff.size),
           codes_differing=int((diff > 0).sum()), max_code_diff=int(diff.max()),
           scales_bitwise_equal=bool(np.array_equal(np.asarray(d_scales), h_scales)),
           roundtrip_max_err_steps=roundtrip_steps)
    if diff.max() > 1:
        raise AssertionError(f"device payload differs from host codec by {int(diff.max())} codes")
    if diff.max():
        # compiled hardware arithmetic may land a near-tie on the other
        # code; the wire stays valid as long as the codec's own error
        # bound — half a quantization step — holds
        findings.append(
            f"quantize: {int((diff > 0).sum())} of {diff.size} payload codes "
            "differ from the host codec by one LSB"
        )
    # half a step, plus f32 rounding of the quotient (|x/scale| <= 127)
    if roundtrip_steps > 0.5 + 1e-4:
        raise AssertionError(
            f"device quantize/dequantize round trip is {roundtrip_steps} steps, over half a step"
        )
    np.testing.assert_allclose(
        np.asarray(pq.fused_dequantize_from_int8(h_scales, h_payload, shape=mat.shape)),
        host_q.dequantize(h_scales, h_payload, mat.shape, np.float32), rtol=1e-6,
    )
    # fused reduce over both ranks' host-quantized shards
    mat1 = np.zeros((rows * cols,), np.float32)
    mat1[:n] = host_frags[1]
    quantized = [(h_scales, h_payload), host_q.quantize(mat1.reshape(rows, cols))]
    for average_by in (0, 2):
        r_scales, r_payload = jax.block_until_ready(pq.fused_reduce_int8(
            np.stack([q[0] for q in quantized]), np.stack([q[1] for q in quantized]),
            average_by,
        ))
        h_buf = host_q.reduce_quantized(
            [host_q.pack(s, p) for s, p in quantized], rows, cols, average_by=average_by
        )
        hr_scales, hr_payload = host_q.unpack(h_buf, rows, cols)
        np.testing.assert_allclose(np.asarray(r_scales), hr_scales, rtol=1e-5)
        if np.abs(np.asarray(r_payload).astype(np.int32) - hr_payload.astype(np.int32)).max() > 1:
            raise AssertionError("fused reduce payload differs from host by more than one code")
    del d_scales, d_payload, r_scales, r_payload
    report("C", event="kernels_ok", rows=rows, cols=cols,
           quantize_first_call_s=round(quantize_first_s, 3), findings=findings)

    # --- the product path: manager.allreduce, device-quantized ----------
    lh = lighthouse(min_replicas=2)
    barrier = threading.Barrier(2)

    def replica(r: int) -> "Dict[str, Any]":
        manager = ft.Manager(
            pg=ft.ProcessGroupTCP(timeout=OP_TIMEOUT_S), min_replica_size=2,
            load_state_dict=lambda sd: None, state_dict=lambda: {"r": np.zeros(1, np.float32)},
            replica_id=f"leg_c_{r}", lighthouse_addr=lh.address(), group_rank=0,
            group_world_size=1, use_async_quorum=True, timeout=OP_TIMEOUT_S,
            quorum_timeout=OP_TIMEOUT_S,
            init_sync=False,  # as in train_replica: step 0 must not sit out
        )
        try:
            out = {}
            barrier.wait(timeout=LEG_DEADLINE_S)
            for mode, value, device_quantize in (
                ("device", frags[r], True), ("host", host_frags[r], False),
            ):
                manager.start_quorum()
                t0 = time.perf_counter()
                work = manager.allreduce(
                    {"frag": value}, should_quantize=True,
                    device_quantize=device_quantize,
                )
                got = work.wait(timeout=OP_TIMEOUT_S)["frag"]
                wall = time.perf_counter() - t0
                if manager.errored() is not None:
                    raise AssertionError(f"leg C {mode}: {manager.errored()!r}")
                if not manager.should_commit():
                    raise AssertionError(f"leg C {mode}: step did not commit")
                if work.device_quantized is not device_quantize or work.wire_dtype != "int8":
                    raise AssertionError(
                        f"leg C {mode}: device_quantized={work.device_quantized} "
                        f"wire={work.wire_dtype}"
                    )
                out[mode] = {"value": np.asarray(got), "wall_s": round(wall, 3),
                             "wire_bytes": work.wire_bytes,
                             "unquantized_wire_bytes": work.unquantized_wire_bytes}
            return out
        finally:
            manager.shutdown()

    try:
        outs = run_replicas([lambda r=r: replica(r) for r in range(2)])
    finally:
        lh.shutdown()
    exact = (host_frags[0] + host_frags[1]) / 2.0
    step_all = float(np.abs(exact).max()) / 127.0
    for mode in ("device", "host"):
        np.testing.assert_array_equal(outs[0][mode]["value"], outs[1][mode]["value"])
        # two quantization stages, as tests/test_pallas_quant.py bounds them
        if np.abs(outs[0][mode]["value"] - exact).max() > 4 * step_all:
            raise AssertionError(f"leg C {mode}: quantized mean too far from the exact mean")
    # the host path ships its own slice unquantized, the device path
    # quantizes everything on the chip: both are within the bound above,
    # not bitwise the same
    dev_vs_host = np.abs(outs[0]["device"]["value"] - outs[0]["host"]["value"]).max()
    report("C", event="done", device_vs_host_max_abs=float(dev_vs_host),
           device={k: v for k, v in outs[0]["device"].items() if k != "value"},
           host={k: v for k, v in outs[0]["host"].items() if k != "value"},
           findings=findings, hbm_gib=hbm([dev]))
    del frags, outs
    free_device_memory("C", [dev])


# ---------------------------------------------------------------------------
# four-chip legs
# ---------------------------------------------------------------------------


def leg_ddp4(seed: int) -> None:
    """Four replica groups, each on its own chip, full depth."""
    import jax
    import jax.numpy as jnp
    import optax

    devs = jax.devices()
    cfg = flagship(DDP4["layers"])
    places = [Placement(cfg, [d]) for d in devs]
    report("ddp4", event="start", device=device_info(), layers=cfg.n_layers,
           batch=DDP4["batch"], seq=cfg.max_seq_len)

    # one step's four gradient trees and the ring's answer, for the check
    # against the mean computed directly
    held: "Dict[int, Any]" = {}
    seen: "set[int]" = set()
    lock = threading.Lock()
    check_step = 0

    def on_grads(i: int, step: int, grads: Any, avg: Any) -> None:
        # once per replica: the restarted incarnation passes step 0 again
        if step != check_step or i in seen:
            return
        with lock:
            seen.add(i)
            held[i] = (grads, avg)
        sync.wait(timeout=LEG_DEADLINE_S)       # all four handed in
        if i == 0:
            direct_mean_check(held, devs[0])
        sync.wait(timeout=LEG_DEADLINE_S)       # check done
        with lock:
            held.pop(i, None)

    def direct_mean_check(held: "Dict[int, Any]", dev: Any) -> None:
        trees = [jax.tree_util.tree_leaves(held[i][0]) for i in range(len(devs))]
        ring = jax.tree_util.tree_leaves(held[0][1])
        eps = float(np.finfo(np.float32).eps)
        for li, ring_leaf in enumerate(ring):
            stack = jnp.stack([jax.device_put(t[li], dev) for t in trees])
            direct = np.asarray(jnp.mean(stack, axis=0))
            # two summation orders of four f32 terms
            bound = np.asarray(8 * eps * jnp.mean(jnp.abs(stack), axis=0)) + 1e-30
            if not np.all(np.abs(ring_leaf - direct) <= bound):
                raise AssertionError(f"ring average of leaf {li} is not the mean of the four gradients")
        for i in range(1, len(devs)):
            for a, b in zip(ring, jax.tree_util.tree_leaves(held[i][1])):
                if not np.array_equal(a, b):
                    raise AssertionError("ring result differs across replicas")
        report("ddp4", event="ring_equals_direct_mean", step=check_step, leaves=len(ring))

    sync = threading.Barrier(len(devs))
    start = threading.Barrier(len(devs))
    rejoin = (DDP4["kill_at"] + 1, threading.Event())
    lh = lighthouse()

    def replica(i: int) -> "Dict[str, Any]":
        probe, _ = init_state(cfg, optax.adamw(LR), seed, places[i])
        grad_step, _ = compile_grad_step(cfg, places[i], probe, DDP4["batch"], "ddp4")
        del probe
        return train_replica(
            f"ddp4_{i}", lh.address(), cfg, places[i], grad_step,
            batch=DDP4["batch"], steps=DDP4["steps"], seed=seed,
            data_seed=seed + 20 + i, barrier=start, rejoin=rejoin,
            kill_at=DDP4["kill_at"] if i == 1 else None,
            on_grads=lambda s, g, a: on_grads(i, s, g, a), leg="ddp4",
        )

    try:
        results = run_replicas([lambda i=i: replica(i) for i in range(len(devs))])
    finally:
        lh.shutdown()
    if len(seen) != len(devs) or held:
        raise AssertionError("direct-mean check did not complete")
    check_kill_heal(results, victim=1, steps=DDP4["steps"], leg="ddp4")
    # sampled by each replica while its state was still alive
    stats = [r["hbm_gib"][0] for r in results]
    p_bytes = tree_nbytes(results[0]["final_params"])
    for d, s in zip(devs, stats):
        if s["bytes_in_use"] * GIB < p_bytes:
            raise AssertionError(f"{d} does not hold a replica's state: {s}")
    full = [r for r in results[0]["records"] if r["participants"] == len(devs)]
    report("ddp4", event="done", devices=[str(d) for d in devs], hbm_gib=stats,
           four_replica_step_s=min(r["step_s"] for r in full),
           four_replica_allreduce_s=min(r["allreduce_s"] for r in full))
    del results
    free_device_memory("ddp4", devs)


def leg_hsdp(seed: int) -> None:
    """Two replica groups on disjoint 2-chip fsdp meshes, full width."""
    import jax

    devs = jax.devices()
    per = HSDP["fsdp"]
    cfg = flagship(HSDP["layers"])
    places = [Placement(cfg, devs[i * per:(i + 1) * per], fsdp=per) for i in range(2)]
    report("hsdp", event="start", device=device_info(), layers=cfg.n_layers,
           batch=HSDP["batch"], seq=cfg.max_seq_len,
           meshes=[[str(d) for d in p.devices] for p in places])
    start = threading.Barrier(2)
    rejoin = (HSDP["kill_at"] + 1, threading.Event())
    lh = lighthouse()

    def replica(i: int) -> "Dict[str, Any]":
        import optax

        probe, _ = init_state(cfg, optax.adamw(LR), seed, places[i])
        total = tree_nbytes(probe["params"])
        for d in places[i].devices:
            on_d = sum(
                sh.data.nbytes
                for x in jax.tree_util.tree_leaves(probe["params"])
                for sh in x.addressable_shards if sh.device == d
            )
            if on_d > 0.6 * total:
                raise AssertionError(
                    f"HSDP params not sharded: {d} holds {on_d} of {total} bytes"
                )
            report("hsdp", event="params_sharded", replica=i, device=str(d),
                   param_bytes_on_device=on_d, param_bytes_total=total)
        grad_step, _ = compile_grad_step(cfg, places[i], probe, HSDP["batch"], "hsdp")
        del probe
        # the same batches on both groups: the averaged gradient is then
        # each group's own, so every group's trajectory — through the
        # kill, the solo steps and the heal — is the plain loop's
        return train_replica(
            f"hsdp_{i}", lh.address(), cfg, places[i], grad_step,
            batch=HSDP["batch"], steps=HSDP["steps"], seed=seed,
            data_seed=seed + 30, barrier=start, rejoin=rejoin,
            kill_at=HSDP["kill_at"] if i == 1 else None, leg="hsdp",
        )

    from torchft_tpu.utils.compile_cache import compile_cache_disabled

    try:
        # group 1's mesh does not start at the process's first device: its
        # programs must be compiled, never loaded from the persistent cache
        # (compile_cache_disabled's docstring)
        with compile_cache_disabled():
            results = run_replicas([lambda i=i: replica(i) for i in range(2)])
    finally:
        lh.shutdown()
    check_kill_heal(results, victim=1, steps=HSDP["steps"], leg="hsdp")
    hsdp_losses = {r["step"]: r["loss"] for r in results[0]["records"]}
    hsdp_final = results[0]["final_params"]
    del results
    free_device_memory("hsdp", devs)

    one = Placement(cfg, [devs[0]])
    import optax

    probe, _ = init_state(cfg, optax.adamw(LR), seed, one)
    grad_step, _ = compile_grad_step(cfg, one, probe, HSDP["batch"], "hsdp_ref")
    del probe
    ref = plain_loop(cfg, one, grad_step, batch=HSDP["batch"], steps=HSDP["steps"],
                     seed=seed, data_seed=seed + 30)
    losses = [hsdp_losses[s] for s in range(HSDP["steps"])]
    # sharded bf16 matmuls reduce in another order than the single chip's
    np.testing.assert_allclose(losses, ref["losses"], rtol=2e-3)
    diffs = [
        np.abs(x - y) for x, y in zip(jax.tree_util.tree_leaves(hsdp_final),
                                      jax.tree_util.tree_leaves(ref["final_params"]))
    ]
    mean_diff = float(sum(d.sum() for d in diffs) / sum(d.size for d in diffs))
    report("hsdp", event="done", hsdp_losses=losses, plain_losses=ref["losses"],
           final_params_mean_abs_diff=mean_diff,
           final_params_max_abs_diff=max(float(d.max()) for d in diffs),
           hbm_gib=hbm(devs))
    # adamw moves a weight by about LR per step; the two runs must agree
    # far more closely than the distance either travelled
    if mean_diff > 0.25 * LR * HSDP["steps"]:
        raise AssertionError("HSDP final params drifted from the plain loop")
    free_device_memory("hsdp", devs)


# ---------------------------------------------------------------------------


def run_legs(chips: int, seed: int) -> None:
    require_compiled_kernels()
    if chips == 1:
        leg_a(seed)
        leg_b(seed)
        leg_c(seed)
    else:
        leg_ddp4(seed)
        leg_hsdp(seed)


def main(argv: "Optional[List[str]]" = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="1: legs A/B/C on one chip; 4: the cross-chip legs only")
    p.add_argument("--seed", type=int, default=0, help="weights and data seed")
    args = p.parse_args(argv)

    from torchft_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    device = device_info()
    reason = refusal(device, args.chips)
    if reason is not None:
        print(result_line(False, device, reason), flush=True)
        return 1
    import os

    report("setup", device=device,
           compile_cache=cache_dir or os.environ["JAX_COMPILATION_CACHE_DIR"],
           chips=args.chips, seed=args.seed)
    t0 = time.perf_counter()
    run_legs(args.chips, args.seed)
    report("total", seconds=round(time.perf_counter() - t0, 1))
    print(result_line(True, device), flush=True)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except BaseException:  # noqa: BLE001 - reported, then the process ends
        import os
        import traceback

        traceback.print_exc()
        sys.stdout.flush()
        sys.stderr.flush()
        # a failed leg can leave replica threads parked in a collective or
        # on the device; do not wait for them (or hang the chip) on the
        # way out
        os._exit(1)
    sys.exit(code)
