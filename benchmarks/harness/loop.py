"""The replica-group loop and the measured window.

The wiring is the one ``chip_smoke.py`` proved on the chip (PR 21), copied
here so the bring-up gate and the yardstick cannot move each other: replica
groups are threads of the one process that holds the chip(s); each builds
``ft.Manager`` + ``ft.ProcessGroupTCP`` + ``ft.DistributedDataParallel`` +
``ft.Optimizer`` against an in-process ``LighthouseServer``,
``init_sync=False``, votes and then updates the post-vote state.  A kill
raises in the victim's thread at a step boundary; its new incarnation
starts from fresh state and live-heals over the Manager's default
transport.

One loop serves every traffic file and every family: the number of groups,
their batch and the kill schedule are data, the weights and the grad step are
the configuration's family module's.  The first
``warmup_steps`` steps belong to set-up — they compile every program the
window uses and are what the reference is compared with — and run through
the same call and feed as the window, on the same state object.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from benchmarks.harness import model
from benchmarks.reference.train import delta_norms, leaf_norms

OP_TIMEOUT_S = 300.0
RUN_DEADLINE_S = 1100.0
SPAN_PREFIX = "bench."


class _Kill(Exception):
    """The scheduled death of a replica group."""


def after_heal(state: Dict[str, Any]) -> None:
    """Called with the victim's state right after its healing step commits.
    Does nothing; the rehearsal tests replace it to damage the healed state
    and see ``correct`` turn false."""


class Shared:
    """What the group threads share: the window's clock and stop rule, the
    records, and the set-up captures the comparison reads."""

    def __init__(self, n_groups: int, seconds: float, trace_steps: int,
                 tracer: "Optional[Any]") -> None:
        self.lock = threading.Lock()
        self.n_groups = n_groups
        self.seconds = seconds
        self.records: "List[Dict[str, Any]]" = []
        self.kills: "List[Dict[str, Any]]" = []
        self.first: "Dict[str, Any]" = {"losses": {}}
        self.stop_at: Optional[int] = None
        self.t0: Optional[float] = None
        self.built = threading.Barrier(n_groups)
        self.window = threading.Barrier(n_groups)
        self.ring_sync = threading.Barrier(n_groups)
        self.held: "Dict[int, Any]" = {}
        self.ring_check: "Dict[str, float]" = {}
        self.unrecovered = 0
        self.trace_steps = trace_steps
        self.tracer = tracer
        # outputs + temporaries of the compiled grad step, by device id: the
        # runtime's byte counters see buffers only (PERF.md section 4)
        self.grad_step_bytes: "Dict[int, int]" = {}
        # the executable each chip's groups call: a traced run reads the
        # program's names of its operations from it, after the window
        self.grad_step_compiled: "Dict[int, Any]" = {}

    def add(self, rec: Dict[str, Any]) -> None:
        with self.lock:
            self.records.append(rec)


def _span(name: str, **kw: Any) -> Any:
    import jax

    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name, **kw)


def make_fingerprint() -> "Callable[[Any], List[int]]":
    """Per-leaf wrap-around sum of the raw 32-bit patterns, computed where
    the state lives: equal fingerprints mean (to a 2^-32 fluke per leaf)
    bitwise equal state, without moving gigabytes to the host each step."""
    import jax
    import jax.numpy as jnp

    fn = jax.jit(lambda t: [
        jnp.sum(jax.lax.bitcast_convert_type(x, jnp.uint32), dtype=jnp.uint32)
        for x in jax.tree_util.tree_leaves(t)
    ])
    return lambda tree: [int(v) for v in fn(tree)]


def _adam_mu(opt_state: Any) -> Any:
    """The first-moment tree of an optax adam-family state."""
    import jax

    found = [s for s in jax.tree_util.tree_leaves(
        opt_state, is_leaf=lambda s: hasattr(s, "mu")) if hasattr(s, "mu")]
    if len(found) != 1:
        raise ValueError("the optimizer state has no single first moment to read")
    return found[0].mu


def direct_mean_check(held: "Dict[int, Any]", dev: Any) -> Dict[str, float]:
    """The ring's answer against the mean of the groups' gradients computed
    directly on one device (``chip_smoke.py`` leg ddp4's check, returning
    numbers).  Two summation orders of n float32 terms differ by at most a
    few ulps of the mean magnitude: the bound is 8 eps mean|g|."""
    import jax
    import jax.numpy as jnp

    groups = sorted(held)
    trees = [jax.tree_util.tree_leaves(held[i][0]) for i in groups]
    ring = jax.tree_util.tree_leaves(held[groups[0]][1])
    eps = float(np.finfo(np.float32).eps)
    worst, differing = 0.0, 0
    for li, ring_leaf in enumerate(ring):
        stack = jnp.stack([jax.device_put(t[li], dev) for t in trees])
        direct = np.asarray(jnp.mean(stack, axis=0))
        bound = np.asarray(8 * eps * jnp.mean(jnp.abs(stack), axis=0)) + 1e-30
        worst = max(worst, float(np.max(np.abs(ring_leaf - direct) / bound)))
        del stack
    for i in groups[1:]:
        for a, b in zip(ring, jax.tree_util.tree_leaves(held[i][1])):
            if not np.array_equal(a, b):
                differing += 1
    return {"ring_vs_direct_mean": worst, "ring_differs_across_groups": float(differing)}


def group_loop(
    i: int,
    shared: Shared,
    *,
    name: str,
    lighthouse_addr: str,
    family: Any,
    sizes: Dict[str, Any],
    traffic: Dict[str, Any],
    device: Any,
    grad_step: Any,
    fingerprint: "Callable[[Any], List[int]]",
    seed: int,
) -> None:
    """One replica group: FT-DDP over the elastic replica dimension."""
    import jax
    from jax.sharding import SingleDeviceSharding

    import torchft_tpu as ft

    batch, seq = traffic["batch_per_group"], traffic["seq_len"]
    warm = traffic["warmup_steps"]
    kill_at = {k["group"]: k["at_measured_step"] for k in traffic["kills"]}.get(i)
    leader = i == 0
    on_dev = SingleDeviceSharding(device)
    tx = model.optimizer(sizes)
    make = family.make_weights_fn(sizes)
    make_weights = jax.jit(make, out_shardings=on_dev)
    init_opt = jax.jit(tx.init, out_shardings=on_dev)
    key = jax.device_put(model.seed_key(seed), on_dev)
    norms_of = jax.jit(lambda t: leaf_norms(t, family.STACKED))
    change_of = jax.jit(lambda p, k: delta_norms(p, make(k), family.STACKED))
    vocab = model.vocab_rows(family, sizes)
    traced = 0
    # one compile per placement, inspected before it runs (chip_smoke.py's
    # compile_grad_step): the executable the set-up steps and the window call
    grad_step = grad_step.lower(
        jax.eval_shape(make, key),
        jax.ShapeDtypeStruct((batch, seq), np.int32, sharding=on_dev)).compile()
    analysis = grad_step.memory_analysis()
    with shared.lock:
        shared.grad_step_bytes[device.id] = int(
            analysis.output_size_in_bytes + analysis.temp_size_in_bytes)
        shared.grad_step_compiled.setdefault(device.id, grad_step)

    for incarnation in range(2 if kill_at is not None else 1):
        params = make_weights(key)
        state = {"params": params, "opt_state": init_opt(params)}
        del params
        shardings = jax.tree_util.tree_map(lambda x: x.sharding, state)
        healed = {"n": 0}

        def load_state_dict(sd: Any) -> None:
            # a heal delivers host numpy: back onto the group's device before
            # the next jitted step touches it
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
            # every group builds the same weights from the seed
            init_sync=False,
        )
        ddp = ft.DistributedDataParallel(manager)
        optimizer = ft.Optimizer(manager, tx)
        phases: "Dict[str, float]" = {}
        try:
            if incarnation == 0:
                shared.built.wait(timeout=RUN_DEADLINE_S)
            while True:
                step = manager.current_step()
                if shared.stop_at is not None and step >= shared.stop_at:
                    break
                if incarnation == 0 and step == warm:
                    shared.window.wait(timeout=RUN_DEADLINE_S)
                    if leader:
                        shared.t0 = time.perf_counter()
                        if shared.tracer is not None:
                            shared.tracer.start()
                measured = incarnation > 0 or step >= warm
                if incarnation == 0 and kill_at is not None and step == warm + kill_at:
                    raise _Kill()
                healing = incarnation > 0 and healed["n"] == 0
                t_start = time.perf_counter()
                with _span("heal" if healing else "step", group=i, step=step):
                    toks = jax.device_put(
                        model.tokens_for(vocab, batch, seq, seed, i, step), on_dev)
                    with _span("quorum", group=i):
                        optimizer.begin_step()
                    live_bytes = (device.memory_stats() or {}).get("bytes_in_use", 0)
                    with _span("fwdbwd", group=i):
                        loss, grads = grad_step(state["params"], toks)
                        loss = float(loss)  # waits for the device
                    t_grad = time.perf_counter()
                    with _span("ring", group=i):
                        work = ddp.allreduce_gradients(grads)
                        avg = work.wait(timeout=OP_TIMEOUT_S)
                    wire_bytes = getattr(work, "wire_bytes", None)
                    if incarnation == 0 and step == 1 and shared.n_groups > 1:
                        _ring_check(i, shared, grads, avg, device)
                    # no room for two copies of the gradients at full depth
                    del grads, work
                    t_ring = time.perf_counter()
                    with _span("h2d", group=i):
                        avg = jax.block_until_ready(
                            jax.device_put(avg, shardings["params"]))
                    t_h2d = time.perf_counter()
                    healed_before = healed["n"]
                    with _span("update", group=i):
                        committed = manager.should_commit()
                        if committed:
                            # the vote is where an async heal lands in `state`
                            state["params"], state["opt_state"] = optimizer.update(
                                state["params"], avg, state["opt_state"])
                            jax.block_until_ready(state["params"])
                    del avg
                    t_update = time.perf_counter()
                    did_heal = healed["n"] > healed_before
                    if did_heal:
                        after_heal(state)
                    with _span("check", group=i):
                        fp = fingerprint(state) if committed else None
                t_end = time.perf_counter()
                now = manager.phase_times()
                delta = {k: v - phases.get(k, 0.0) for k, v in now.items()
                         if v - phases.get(k, 0.0) > 0}
                phases = now
                err = manager.errored()
                shared.add({
                    "group": i, "incarnation": incarnation, "step": step,
                    "step_after": manager.current_step(), "measured": measured,
                    "loss": loss, "committed": bool(committed),
                    "participating": bool(manager.is_participating()),
                    "participants": manager.num_participants(),
                    "healed": did_heal,
                    "errored": None if err is None else repr(err),
                    "t_start": t_start, "t_end": t_end,
                    "fwdbwd_s": t_grad - t_start, "ring_s": t_ring - t_grad,
                    "h2d_s": t_h2d - t_ring, "update_s": t_update - t_h2d,
                    "check_s": t_end - t_update, "live_bytes": live_bytes,
                    "wire_bytes": wire_bytes, "phases": delta, "fingerprint": fp,
                })
                if did_heal and committed:
                    with shared.lock:
                        for k in shared.kills:
                            if k["group"] == i and k["t_recovered"] is None:
                                k["t_recovered"] = t_end
                                shared.unrecovered -= 1
                if incarnation == 0 and step < warm:
                    with shared.lock:
                        shared.first["losses"][(step, i)] = loss
                    if leader and step == 0:
                        mu = norms_of(_adam_mu(state["opt_state"]))
                        shared.first["grad0_norms"] = {
                            k: np.asarray(v) / (1.0 - sizes["adam_b1"])
                            for k, v in mu.items()}
                    if leader and step == warm - 1:
                        shared.first["delta_norms"] = {
                            k: np.asarray(v)
                            for k, v in change_of(state["params"], key).items()}
                if leader and measured:
                    traced += 1
                    if shared.tracer is not None and traced == shared.trace_steps:
                        shared.tracer.stop_later()
                    with shared.lock:
                        if (shared.stop_at is None and shared.unrecovered == 0
                                and t_end - shared.t0 >= shared.seconds):
                            # groups past this step's ring cannot exist yet
                            # (it needed this group), so one more step is the
                            # earliest stop every group can agree on
                            shared.stop_at = step + (1 if shared.n_groups == 1 else 2)
            return
        except _Kill:
            with shared.lock:
                shared.kills.append({"group": i, "t_kill": time.perf_counter(),
                                     "t_recovered": None, "step": step})
                shared.unrecovered += 1
        finally:
            manager.shutdown()
            del state


def _ring_check(i: int, shared: Shared, grads: Any, avg: Any, device: Any) -> None:
    """Set-up step 1, every group: hand in this step's gradients and the
    ring's answer; group 0 compares them with the mean computed directly."""
    with shared.lock:
        shared.held[i] = (grads, avg)
    shared.ring_sync.wait(timeout=RUN_DEADLINE_S)
    if i == 0:
        shared.ring_check.update(direct_mean_check(shared.held, device))
    shared.ring_sync.wait(timeout=RUN_DEADLINE_S)
    with shared.lock:
        shared.held.pop(i, None)


def run_threads(fns: "List[Callable[[], Any]]") -> None:
    """One callable per replica group on daemon threads; the first failure
    (or a thread still alive at the deadline) fails the run."""
    errs: "Dict[int, BaseException]" = {}

    def runner(i: int) -> None:
        try:
            fns[i]()
        except BaseException as e:  # noqa: BLE001 - re-raised on the main thread
            errs[i] = e

    threads = [threading.Thread(target=runner, args=(i,), daemon=True, name=f"group{i}")
               for i in range(len(fns))]
    for t in threads:
        t.start()
    deadline = time.monotonic() + RUN_DEADLINE_S
    while any(t.is_alive() for t in threads) and not errs:
        if time.monotonic() > deadline:
            raise TimeoutError("a replica group was still running at the deadline")
        time.sleep(0.05)
    if errs:
        raise next(iter(errs.values()))
