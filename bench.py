"""Headline benchmark suite: recovery latency, FT overhead, model MFU,
FT-around-model overhead, DiLoCo outer-sync cost.

Measurements, one JSON line:

1. **recovery_to_healthy_step_latency** (primary metric, BASELINE.json
   north star): a replica group dies mid-run and must rejoin with ZERO
   full-job restart — the survivors keep training, the dead replica
   restarts, heals its weights live from a healthy peer, and commits a
   healthy step.  Exercises the whole FT stack end to end on loopback:
   C++ Lighthouse (quorum recompute on membership change) -> C++ Manager
   servers -> quorum-keyed DCN collective reconfigure -> live checkpoint
   heal over the HTTP transport (16 MB state dict) -> zero-contribution
   allreduce -> commit vote.

2. **overhead_pct** (BASELINE.json: "step-time overhead vs non-FT DDP
   <= 5%"): twin 2-replica DDP loops with IDENTICAL compute and the
   IDENTICAL ring allreduce — one driven through the Manager protocol
   (per-step quorum RPC + commit vote + error tracking), one bare
   ProcessGroupTCP configured once.  overhead = ft/bare - 1.  The
   per-phase breakdown comes from ``Manager.phase_times()`` deltas
   (quorum_wait / host_sync / ring / commit).  Harness shape mirrors the
   reference's transport benches (reference:
   torchft/checkpointing/pg_transport_bench.py:24-95).

3. **model.mfu_pct**: the flagship TransformerConfig running
   ``make_train_step`` (fwd+bwd+adamw, one donated jit) on the attached
   TPU, sized to fill a v5e, timed over a window of steps that ends in
   ``block_until_ready``.  MFU uses model FLOPs (6*N*tokens + exact
   attention term; remat recompute NOT counted, per the standard MFU
   definition), shown in ``docs/benchmarks.md``.  Reference-scale intent:
   torchft/examples/slurm/runner.py:16-49.  ``model.ft`` then runs the
   same model through the Manager protocol with the WHOLE gradient pytree
   crossing device -> host -> ring -> host -> device each step.

``vs_baseline`` = median recovery latency / 1.0 — a 1-second recovery
target we set for ourselves (the reference publishes no numbers,
BASELINE.md; its embedded join_timeout default alone is 100 ms + 100 ms
quorum tick).  Values < 1.0 beat the target; lower is better.  The
recovery headline is the MEDIAN of ``RECOVERY_CYCLES`` independent
kill/rejoin cycles, each with a per-phase breakdown (teardown, manager
re-init, quorum RPC, PG reconfigure, heal transfer, ring step, commit)
so a regressed number is attributable to protocol vs host noise.

Recovery/overhead compute is host-side numpy on purpose: those legs
price the DCN fault-tolerance layer and run the same with or without a
chip.  The chip-touching legs (``bench_model`` and the
``diloco.int8_device`` leg) need a TPU and fail the whole run when they
fail: no toy config, no fallback kernel, no substituted step time.  The
full run therefore refuses to start without a TPU; the host-only legs
each have their own flag (``--serving``, ``--heal``, ``--wan``, ...).
"""

from __future__ import annotations

import json
import statistics
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from torchft_tpu.coordination import (
    LighthouseClient,
    LighthouseServer,
    StoreServer,
)
from torchft_tpu.diagnose import dominant_contributor
from torchft_tpu.manager import Manager
from torchft_tpu.parallel.process_group import (
    REDUCE_SUM,
    ProcessGroupTCP,
)

PARAM_SIZE = 4 * 1024 * 1024  # 4M fp32 = 16 MB state dict
TOTAL_STEPS = 20
KILL_AT_STEP = 10
KILL_REPLICA = 1
RECOVERY_CYCLES = 3  # independent kill/rejoin cycles; median is the headline

OVERHEAD_WARMUP = 5
OVERHEAD_STEPS = 30


def _phase_delta(manager, prev: "Dict[str, float]"):
    """Per-step phase delta from the NON-destructive ``phase_times()``
    snapshot (a destructive drain would corrupt any concurrent scraper).
    Returns ``(delta, new_snapshot)``; thread the snapshot through the
    loop."""
    cur = manager.phase_times()
    return {k: v - prev.get(k, 0.0) for k, v in cur.items()}, cur


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# 1. recovery-to-healthy-step latency
# ---------------------------------------------------------------------------


class _Kill(Exception):
    pass


class DivergenceError(AssertionError):
    """Replicas were NOT bitwise-equal after recovery — a protocol
    correctness failure that must fail the whole bench (unlike harness
    asserts or hangs, which only fail their cycle)."""


class Replica:
    def __init__(self, replica_id: int, lighthouse_addr: str, bench: "RecoveryBench"):
        self.replica_id = replica_id
        self.lighthouse_addr = lighthouse_addr
        self.bench = bench
        self.step_times: "List[float]" = []

    def run(self) -> dict:
        for attempt in range(3):
            try:
                return self._train(attempt)
            except _Kill:
                log(f"replica {self.replica_id}: killed at step {KILL_AT_STEP}, "
                    "restarting")
                continue
        raise RuntimeError("exhausted attempts")

    def _train(self, attempt: int) -> dict:
        params = np.zeros(PARAM_SIZE, dtype=np.float32)
        state = {"params": params}

        def load_state_dict(sd):
            state["params"] = np.array(sd["params"])

        def state_dict():
            return {"params": state["params"].copy()}

        t_init0 = time.perf_counter()
        manager = Manager(
            pg=ProcessGroupTCP(timeout=30.0),
            min_replica_size=1,
            load_state_dict=load_state_dict,
            state_dict=state_dict,
            lighthouse_addr=self.lighthouse_addr,
            replica_id=f"replica_{self.replica_id}",
            group_rank=0,
            group_world_size=1,
            use_async_quorum=True,
            timeout=30.0,
            quorum_timeout=30.0,
            # a should_commit=False livelock must terminate (an abandoned
            # cycle's thread would otherwise spin on the 1-core host
            # forever — there is no other per-replica wall deadline)
            max_retries=2 * TOTAL_STEPS,
        )
        healed = attempt > 0
        if healed and self.bench.t_killed is not None:
            self.bench.teardown_s = t_init0 - self.bench.t_killed
            self.bench.manager_init_s = time.perf_counter() - t_init0
            log(f"replica {self.replica_id}: teardown+restart took "
                f"{self.bench.teardown_s:.3f}s, manager re-init "
                f"{self.bench.manager_init_s:.3f}s")
        try:
            while manager.current_step() < TOTAL_STEPS:
                step = manager.current_step()
                if (
                    self.replica_id == KILL_REPLICA
                    and attempt == 0
                    and step == KILL_AT_STEP
                ):
                    # Stamp at the raise site: Manager teardown in the
                    # finally block is part of real kill-to-healthy time.
                    self.bench.t_killed = time.perf_counter()
                    raise _Kill()

                t0 = time.perf_counter()
                manager.start_quorum()
                grads = np.full(
                    PARAM_SIZE, float(step + 1), dtype=np.float32
                ) * (1.0 + 0.5 * self.replica_id)
                avg = manager.allreduce({"g": grads}).wait(timeout=30)
                if manager.should_commit():
                    state["params"] = state["params"] - 0.1 * avg["g"]
                    self.step_times.append(time.perf_counter() - t0)
                    if healed:
                        self.bench.t_healthy = time.perf_counter()
                        # phases accumulated since this (fresh) Manager was
                        # built == exactly the recovery step's protocol work
                        self.bench.healed_phases = manager.phase_times()
                        log(f"replica {self.replica_id}: healthy commit at "
                            f"step {manager.current_step()} after heal "
                            f"(quorum+heal+step {time.perf_counter() - t0:.3f}s)")
                        healed = False
            return {
                "replica_id": self.replica_id,
                "params": state["params"],
                "step": manager.current_step(),
            }
        finally:
            manager.shutdown()


class RecoveryBench:
    """One kill/rejoin cycle: 2 replica groups, kill one mid-run, time
    kill→healthy-commit with a per-phase breakdown of where it went."""

    def __init__(self) -> None:
        self.t_killed: "Optional[float]" = None
        self.t_healthy: "Optional[float]" = None
        self.teardown_s: "Optional[float]" = None
        self.manager_init_s: "Optional[float]" = None
        self.healed_phases: "Dict[str, float]" = {}

    def run(self) -> "Dict[str, Any]":
        lighthouse = LighthouseServer(
            min_replicas=1, join_timeout_ms=100, heartbeat_timeout_ms=1000
        )
        try:
            replicas = [Replica(i, lighthouse.address(), self) for i in range(2)]
            t_start = time.perf_counter()
            # daemon threads, not a ThreadPoolExecutor: a hung worker must
            # neither block this cycle past its deadline nor hang process
            # exit via concurrent.futures' atexit join (the worker itself
            # unwedges via its protocol deadlines / max_retries)
            out: "Dict[int, Any]" = {}
            errs: "Dict[int, BaseException]" = {}

            def runner(r: Replica) -> None:
                try:
                    out[r.replica_id] = r.run()
                except BaseException as e:  # noqa: BLE001
                    errs[r.replica_id] = e

            threads = [
                threading.Thread(target=runner, args=(r,), daemon=True)
                for r in replicas
            ]
            for t in threads:
                t.start()
            deadline = time.monotonic() + 300
            for t in threads:
                t.join(timeout=max(deadline - time.monotonic(), 0.001))
            # a still-running worker takes precedence over any error from
            # its peer: the caller keys its unwedge grace on TimeoutError,
            # and a live thread is exactly the condition the grace exists
            # for (it will contend for the single core until its own
            # deadlines fire)
            if any(t.is_alive() for t in threads):
                raise TimeoutError("recovery cycle timed out (worker hung)")
            if errs:
                raise next(iter(errs.values()))
            if len(out) != len(replicas):
                raise TimeoutError("recovery cycle timed out (worker hung)")
            results = [out[r.replica_id] for r in replicas]
            wall = time.perf_counter() - t_start
        finally:
            lighthouse.shutdown()

        assert self.t_killed is not None and self.t_healthy is not None
        try:
            np.testing.assert_array_equal(
                results[0]["params"], results[1]["params"]
            )
        except AssertionError as e:
            raise DivergenceError(str(e)) from None
        log("replicas converged bitwise after recovery")

        all_steps = [t for r in replicas for t in r.step_times]
        log(f"steady-state: median step {statistics.median(all_steps)*1e3:.1f} ms "
            f"({PARAM_SIZE*4/1e6:.0f} MB grads over loopback DCN), "
            f"total wall {wall:.1f}s for {TOTAL_STEPS} steps x 2 replicas")

        # Phase breakdown of kill -> healthy commit.  teardown + manager
        # re-init happen before the healed Manager exists; the rest comes
        # from its phase_times().  quorum_rpc / pg_configure /
        # heal_recv run on the async-quorum thread and are what the
        # caller-side quorum_wait was waiting FOR (they overlap it, not
        # add to it); ring + commit are the healed step's collective and
        # commit barrier.
        phases_ms: "Dict[str, float]" = {
            "teardown": (self.teardown_s or 0.0) * 1e3,
            "manager_init": (self.manager_init_s or 0.0) * 1e3,
        }
        for k in ("quorum_rpc", "pg_configure", "heal_recv",
                  "heal_manifest", "heal_diff", "heal_wire", "heal_decode",
                  "ring", "commit", "quorum_wait", "host_sync"):
            if k in self.healed_phases:
                phases_ms[k] = self.healed_phases[k] * 1e3
        return {
            "latency_s": self.t_healthy - self.t_killed,
            "phases_ms": {k: round(v, 1) for k, v in phases_ms.items()},
            "steady_step_ms": round(statistics.median(all_steps) * 1e3, 1),
            "wall_s": round(wall, 1),
        }


def bench_recovery(cycles: int = RECOVERY_CYCLES) -> "Dict[str, Any]":
    """>= 3 independent kill/rejoin cycles; the MEDIAN is the headline (one
    cycle on a 1-core host is a coin flip — r03's single sample measured
    1.059 s on the driver vs 0.14-0.22 s locally with no way to tell host
    noise from a protocol pathology; the per-cycle phase breakdown now
    says which)."""
    cycle_results = []
    errors = []
    for i in range(cycles):
        # one bad cycle (hung thread, host stall) must not cost the driver
        # the primary metric — the median of the surviving cycles is still
        # a better headline than r03's single-sample coin flip.
        # DivergenceError is NOT survivable: bitwise divergence after
        # recovery is a protocol correctness failure, not host noise.
        try:
            r = RecoveryBench().run()
        except DivergenceError:
            raise
        except Exception as e:  # noqa: BLE001
            log(f"recovery cycle {i} FAILED: {e!r}")
            errors.append(repr(e))
            if isinstance(e, TimeoutError) and i < cycles - 1:
                # let the abandoned cycle's worker threads unwedge via
                # their own protocol deadlines (30 s) before timing the
                # next cycle on this 1-core host; instant failures and the
                # last cycle need no grace
                time.sleep(35.0)
            continue
        log(f"recovery cycle {i}: {r['latency_s']:.3f}s phases {r['phases_ms']}")
        cycle_results.append(r)
    if not cycle_results:
        raise RuntimeError(f"all recovery cycles failed: {errors}")

    latencies = [r["latency_s"] for r in cycle_results]
    median_latency = statistics.median(latencies)
    # median per phase across cycles (phases missing in a cycle count as 0)
    keys = sorted({k for r in cycle_results for k in r["phases_ms"]})
    phase_median = {
        k: round(statistics.median([r["phases_ms"].get(k, 0.0)
                                    for r in cycle_results]), 1)
        for k in keys
    }
    out = {
        "value": round(median_latency, 3),
        "recovery_cycles_s": [round(x, 3) for x in latencies],
        "recovery_min_s": round(min(latencies), 3),
        # seconds, like every sibling top-level metric in this object
        "recovery_phases": {
            k: round(v / 1e3, 4) for k, v in phase_median.items()
        },
        "recovery_phases_ms": phase_median,
        # critical-path ledger vocabulary (torchft_tpu/diagnose.py): which
        # cost category dominated the recovery path this run
        "recovery_dominant": dominant_contributor(phase_median),
        "steady_step_ms": round(
            statistics.median([r["steady_step_ms"] for r in cycle_results]), 1
        ),
    }
    if errors:
        out["recovery_cycle_errors"] = errors
    return out


# ---------------------------------------------------------------------------
# 1b. online-parallelism-switch latency (ISSUE 11)
# ---------------------------------------------------------------------------

SWITCH_GROUPS = 4
SWITCH_KILL_STEP = 3
SWITCH_TOTAL_STEPS = 7
SWITCH_PARAM_ELEMS = 1 << 18  # 1 MB fp32 of layout-sharded state


def bench_switch() -> "Dict[str, Any]":
    """Kill-to-switched latency of online parallelism switching
    (parallel/layout.py): 4 single-rank groups under a memory ceiling
    run layout (2,2,1); killing one shrinks the fleet to 3, which
    re-plans to (1,3,1) and re-shards the 1 MB state live (slice-diff
    fetches from current owners over the HTTP transport).  Measured:
    wall seconds from the kill to the LAST survivor's fleet-synchronous
    layout commit, with the per-phase split (reshard staging wall /
    commit round wall, from ``Manager.phase_times``) and the bytes that
    actually crossed the wire — the price of "the job continuously fits
    the hardware it has", next to the recovery latency it complements."""
    from torchft_tpu.parallel.layout import (
        LayoutConstraints,
        LayoutController,
    )

    lighthouse = LighthouseServer(
        min_replicas=1, join_timeout_ms=100, heartbeat_timeout_ms=1000
    )
    t_killed: "List[Optional[float]]" = [None]
    commits: "Dict[int, Dict[str, Any]]" = {}
    errs: "Dict[int, BaseException]" = {}

    def worker(gid: int) -> None:
        shard = {"w": np.zeros(SWITCH_PARAM_ELEMS, dtype=np.float32)}
        ctrl = LayoutController(
            LayoutConstraints(
                param_bytes=SWITCH_PARAM_ELEMS * 4,
                shard_memory_bytes=SWITCH_PARAM_ELEMS * 2,
            )
        )
        ctrl.register_sharded_state(
            "model",
            {"w": SWITCH_PARAM_ELEMS},
            lambda: dict(shard),
            lambda new: shard.update(
                {k: np.array(v) for k, v in new.items()}
            ),
        )
        user = {"marker": float(gid)}
        manager = Manager(
            pg=ProcessGroupTCP(timeout=30.0),
            min_replica_size=1,
            load_state_dict=lambda sd: user.update(sd),
            state_dict=lambda: dict(user),
            lighthouse_addr=lighthouse.address(),
            replica_id=f"switch_{gid}",
            group_rank=0,
            group_world_size=1,
            use_async_quorum=True,
            init_sync=False,
            timeout=30.0,
            quorum_timeout=30.0,
            max_retries=4 * SWITCH_TOTAL_STEPS,
        )
        manager.attach_layout(ctrl)

        base_phases: "Dict[str, float]" = {}

        def on_commit(layout, info):
            if layout.key() == (2, 2, 1):
                # bootstrap shard-up: snapshot so the shrink switch's
                # phase split below is a delta, not a cumulative sum
                base_phases.update(manager.phase_times())
            elif layout.key() == (1, 3, 1):  # the shrink switch
                cur = manager.phase_times()
                commits[gid] = {
                    "ts": time.perf_counter(),
                    "bytes": info.get("fetched_bytes", 0),
                    "phases": {
                        k: v - base_phases.get(k, 0.0) for k, v in cur.items()
                    },
                }

        ctrl.add_listener(on_commit)
        try:
            while manager.current_step() < SWITCH_TOTAL_STEPS:
                step = manager.current_step()
                if gid == SWITCH_GROUPS - 1 and step == SWITCH_KILL_STEP:
                    t_killed[0] = time.perf_counter()
                    return
                manager.start_quorum()
                g = np.full(
                    SWITCH_PARAM_ELEMS, float(step + 1), dtype=np.float32
                )
                avg = manager.allreduce({"g": g}).wait(timeout=30)
                if manager.should_commit():
                    ctrl.update_sharded(
                        "model",
                        lambda leaf, arr, start: arr.__isub__(
                            np.float32(0.01)
                            * avg["g"][start : start + arr.size]
                        ),
                    )
        finally:
            manager.shutdown()

    try:
        threads = []
        for gid in range(SWITCH_GROUPS):

            def runner(gid=gid):
                try:
                    worker(gid)
                except BaseException as e:  # noqa: BLE001
                    errs[gid] = e

            threads.append(threading.Thread(target=runner, daemon=True))
        for t in threads:
            t.start()
        deadline = time.monotonic() + 180
        for t in threads:
            t.join(timeout=max(deadline - time.monotonic(), 0.001))
        if any(t.is_alive() for t in threads):
            raise TimeoutError("switch bench wedged (worker hung)")
        if errs:
            raise next(iter(errs.values()))
    finally:
        lighthouse.shutdown()

    survivors = [g for g in range(SWITCH_GROUPS - 1)]
    if t_killed[0] is None or any(g not in commits for g in survivors):
        raise RuntimeError(
            f"shrink switch did not commit on all survivors: {sorted(commits)}"
        )
    latency = max(commits[g]["ts"] for g in survivors) - t_killed[0]
    reshard_s = statistics.median(
        commits[g]["phases"].get("reshard", 0.0) for g in survivors
    )
    commit_s = statistics.median(
        commits[g]["phases"].get("layout_commit", 0.0) for g in survivors
    )
    out = {
        "latency_s": round(latency, 3),
        "reshard_s": round(reshard_s, 4),
        "layout_commit_s": round(commit_s, 4),
        "reshard_bytes": max(commits[g]["bytes"] for g in survivors),
        "layout": "(2,2,1)->(1,3,1)",
        # kill-detection (quorum re-formation, heartbeat expiry) is the
        # remainder — the same protocol cost recovery latency pays
        "detect_s": round(max(latency - reshard_s - commit_s, 0.0), 3),
    }
    # critical-path ledger vocabulary (diagnose.PHASE_CATEGORY): which
    # cost category dominated the switch (detection is quorum protocol)
    out["dominant"] = dominant_contributor(
        {
            "reshard": reshard_s,
            "layout_commit": commit_s,
            "quorum_rpc": out["detect_s"],
        }
    )
    log(f"switch latency: {out}")
    return out


# ---------------------------------------------------------------------------
# 2. FT overhead vs a bare (non-FT) DDP twin
# ---------------------------------------------------------------------------


def _ddp_compute(step: int, rank: int, reps: int = 1) -> np.ndarray:
    """The shared per-step 'gradient computation' of both twins.  ``reps``
    scales the compute (the cross-check mode lengthens steps so the
    twin-ratio estimator's scheduling noise — fixed in ms — shrinks as a
    fraction of the step)."""
    g = np.full(PARAM_SIZE, float(step + 1), dtype=np.float32) * (
        1.0 + 0.5 * rank
    )
    for _ in range(reps - 1):
        g = 0.5 * (g + np.sqrt(np.abs(g) + 1.0))
    return g


def _bare_replica(
    rank: int, world: int, store_addr: str, barrier: "threading.Barrier",
    out: "Dict[int, List[float]]", steps: int = OVERHEAD_STEPS,
    warmup: int = OVERHEAD_WARMUP, reps: int = 1,
) -> None:
    """Non-FT twin: ProcessGroupTCP configured once, no Manager, no quorum,
    no commit vote — plain DDP over the identical ring."""
    pg = ProcessGroupTCP(timeout=30.0)
    pg.configure(f"{store_addr}/bare", f"bare_{rank}", rank, world)
    try:
        params = np.zeros(PARAM_SIZE, dtype=np.float32)
        times: "List[float]" = []
        barrier.wait(timeout=30)
        cpu0 = time.process_time()
        for step in range(warmup + steps):
            if step == warmup and rank == 0:
                # CPU window starts AFTER warmup, matching the wall
                # medians (times[warmup:]) and the phase-sum estimator —
                # else one-time setup CPU biases the ratio
                cpu0 = time.process_time()
            t0 = time.perf_counter()
            grads = _ddp_compute(step, rank, reps)
            (summed,) = pg.allreduce([grads], REDUCE_SUM).wait(timeout=30)
            summed /= world
            params -= 0.1 * summed
            times.append(time.perf_counter() - t0)
        # process-wide CPU per step over the post-warmup window (both
        # ranks read the same counter; rank 0's delta is the total)
        if rank == 0:
            out[-1] = [(time.process_time() - cpu0) / steps]
        out[rank] = times[warmup:]
    finally:
        pg.shutdown()


def _ft_replica(
    rank: int, lighthouse_addr: str, barrier: "threading.Barrier",
    out: "Dict[int, List[float]]", phases: "Dict[int, Dict[str, float]]",
    steps: int = OVERHEAD_STEPS, warmup: int = OVERHEAD_WARMUP,
    reps: int = 1,
) -> None:
    """FT twin: same compute, same ring, driven through the full Manager
    per-step protocol (async quorum + allreduce + commit vote)."""
    params = np.zeros(PARAM_SIZE, dtype=np.float32)
    state = {"params": params}
    manager = Manager(
        pg=ProcessGroupTCP(timeout=30.0),
        min_replica_size=2,
        load_state_dict=lambda sd: state.update(params=np.array(sd["params"])),
        state_dict=lambda: {"params": state["params"].copy()},
        lighthouse_addr=lighthouse_addr,
        replica_id=f"ft_{rank}",
        group_rank=0,
        group_world_size=1,
        use_async_quorum=True,
        timeout=30.0,
        quorum_timeout=30.0,
    )
    try:
        times: "List[float]" = []
        acc: "Dict[str, float]" = {}
        phase_snap: "Dict[str, float]" = {}
        barrier.wait(timeout=30)
        cpu0 = time.process_time()
        cpu_marked = False
        step = 0
        attempts = 0
        while step < warmup + steps:
            if step == warmup and rank == 0 and not cpu_marked:
                # post-warmup CPU window (see _bare_replica): excludes the
                # one-time first-quorum/JIT setup the other estimators
                # also exclude
                cpu0 = time.process_time()
                cpu_marked = True
            attempts += 1
            if attempts > 3 * (warmup + steps):
                raise RuntimeError(
                    f"FT twin stuck: {step} committed after {attempts} attempts"
                )
            t0 = time.perf_counter()
            manager.start_quorum()
            grads = _ddp_compute(step, rank, reps)
            avg = manager.allreduce({"g": grads}).wait(timeout=30)
            if manager.should_commit():
                state["params"] -= 0.1 * avg["g"]
                times.append(time.perf_counter() - t0)
                phase, phase_snap = _phase_delta(manager, phase_snap)
                if step >= warmup:
                    for k, v in phase.items():
                        acc[k] = acc.get(k, 0.0) + v
                step += 1
        if rank == 0:
            # process-wide CPU/step over the post-warmup window: includes
            # the async quorum thread and manager server threads — the
            # background work the caller-side phase sum deliberately
            # excludes
            out[-1] = [(time.process_time() - cpu0) / steps]
        out[rank] = times[warmup:]
        phases[rank] = acc
    finally:
        manager.shutdown()


def _run_bare_twin(
    world: int, steps: int = OVERHEAD_STEPS, warmup: int = OVERHEAD_WARMUP,
    reps: int = 1, cpu_out: "Optional[List[float]]" = None,
) -> float:
    store = StoreServer()
    times: "Dict[int, List[float]]" = {}
    try:
        barrier = threading.Barrier(world)
        threads = [
            threading.Thread(
                target=_bare_replica,
                args=(r, world, store.address(), barrier, times, steps,
                      warmup, reps),
                daemon=True,
            )
            for r in range(world)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=240)
    finally:
        store.shutdown()
    cpu = times.pop(-1, None)
    if cpu_out is not None and cpu:
        cpu_out.append(cpu[0])
    assert len(times) == world, "bare twin failed"
    return statistics.median([t for ts in times.values() for t in ts])


def _run_ft_twin(
    world: int, phase_out: "Dict[str, float]",
    steps: int = OVERHEAD_STEPS, warmup: int = OVERHEAD_WARMUP,
    reps: int = 1, cpu_out: "Optional[List[float]]" = None,
) -> float:
    """Runs the FT twin; merges this run's mean phase ms/step into
    ``phase_out`` (caller divides by number of runs)."""
    lighthouse = LighthouseServer(
        min_replicas=world, join_timeout_ms=100, heartbeat_timeout_ms=1000
    )
    times: "Dict[int, List[float]]" = {}
    phases: "Dict[int, Dict[str, float]]" = {}
    try:
        barrier = threading.Barrier(world)
        threads = [
            threading.Thread(
                target=_ft_replica,
                args=(r, lighthouse.address(), barrier, times, phases, steps,
                      warmup, reps),
                daemon=True,
            )
            for r in range(world)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=240)
    finally:
        lighthouse.shutdown()
    cpu = times.pop(-1, None)
    if cpu_out is not None and cpu:
        cpu_out.append(cpu[0])
    assert len(times) == world, "FT twin failed"
    for acc in phases.values():
        for k, v in acc.items():
            phase_out[k] = phase_out.get(k, 0.0) + v * 1e3 / steps / len(phases)
    return statistics.median([t for ts in times.values() for t in ts])


def bench_overhead(rounds: int = 5) -> "Dict[str, Any]":
    """FT overhead vs the bare twin, phase-sum estimator.

    The two twins run identical numpy compute and the identical ring
    allreduce; the FT twin adds exactly the Manager protocol phases, which
    ``phase_times`` deltas measure per step at perf_counter precision:
    ``quorum_wait`` + ``commit`` + ``host_sync`` (``ring`` is common to
    both twins and excluded).  Headline ``overhead_pct`` = added protocol
    ms / bare step ms.

    The naive estimator — the direct ratio of the two twins' medians — is
    also reported (``twin_ratio_pct``) but is unreliable on this host: the
    bench box has ONE CPU core (nproc=1), so the ~50 ms/step twins are
    thread-scheduling-noise-bound and back-to-back paired runs measured
    ratios swinging 0.89-1.19 around the ~1.03 truth.  The phase-sum is
    immune to that noise because it subtracts within the same process,
    same steps.
    """
    world = 2
    pairs: "List[tuple]" = []
    phase_runs: "List[Dict[str, float]]" = []
    for _ in range(rounds):
        b = _run_bare_twin(world)
        phases: "Dict[str, float]" = {}
        f = _run_ft_twin(world, phases)
        pairs.append((b, f))
        phase_runs.append(phases)

    bare_ms = min(b for b, _ in pairs) * 1e3
    ft_ms = min(f for _, f in pairs) * 1e3
    # quietest-round protocol cost (load inflates RPC latency too)
    protocol_ms = min(
        p.get("quorum_wait", 0.0) + p.get("commit", 0.0) + p.get("host_sync", 0.0)
        for p in phase_runs
    )
    overhead_pct = protocol_ms / bare_ms * 100.0
    twin_ratio_pct = (
        statistics.median([f / b for b, f in pairs]) - 1.0
    ) * 100.0
    n = len(phase_runs)
    phase_ms = {
        k: round(sum(p.get(k, 0.0) for p in phase_runs) / n, 3)
        for k in sorted({k for p in phase_runs for k in p})
    }

    log(
        f"overhead: bare {bare_ms:.2f} ms/step, protocol +{protocol_ms:.3f} ms "
        f"-> {overhead_pct:+.2f}% (twin-ratio cross-check {twin_ratio_pct:+.2f}%) | "
        f"phases ms/step {phase_ms} | pair ratios "
        f"{[round(f / b, 4) for b, f in pairs]}"
    )
    return {
        "overhead_pct": round(overhead_pct, 2),
        "protocol_ms_per_step": round(protocol_ms, 3),
        "ft_step_ms": round(ft_ms, 3),
        "nonft_step_ms": round(bare_ms, 3),
        "twin_ratio_pct": round(twin_ratio_pct, 2),
        "phases_ms_per_step": phase_ms,
        # per-leg dominant-ledger-contributor (diagnose.PHASE_CATEGORY);
        # prefixed because this dict is merged into the top-level result
        "overhead_dominant": dominant_contributor(phase_ms),
    }


def bench_overhead_crosscheck(rounds: int = 4) -> "Dict[str, Any]":
    """Two-estimator convergence check (VERDICT r4 item 7): the headline
    <= 5% claim rests on the phase-sum estimator; this mode de-noises the
    twin-ratio estimator until the two can be compared on a 1-core host.

    De-contenting levers:
    - LONG steps (compute reps stretch ~50 ms steps to ~200+ ms): the
      twin-ratio's scheduling noise is fixed in ms, so its share of the
      ratio shrinks ~4x;
    - alternating windows (bare/FT/bare/FT...) with per-window pairing
      and a median-of-ratios: host drift (page cache, cron, thermal)
      lands on both twins of a pair instead of one side of a long run.

    Convergence = |cpu_ratio_pct - overhead_pct| within ~2 points (the
    CPU-time ratio is the de-contended twin estimator; the wall
    twin_ratio_pct is reported alongside for continuity with r4).  If
    the gap stays larger, the null experiment decides whether that is
    signal: bare-vs-bare CPU ratios (identical twins) measure the
    estimator's own noise floor, and a gap inside the floor means no
    twin comparison on this host can resolve the effect.  Any residual
    beyond the floor would be the ASYNC QUORUM THREAD's CPU steal: on 1
    core the Manager's background quorum thread preempts compute, which
    the caller-thread phase sum deliberately excludes because on a
    deployment host (>= 1 core per replica + servers) it runs on spare
    cores.  The JSON carries all estimators + the null spread so the
    claim is auditable either way.
    """
    world = 2
    # ~4x longer steps; fewer steps/rounds to keep the wall bounded
    reps, steps, warmup = 6, 12, 3
    ratios: "List[float]" = []
    cpu_ratios: "List[float]" = []
    null_ratios: "List[float]" = []
    protocol_ms_runs: "List[float]" = []
    bare_ms_runs: "List[float]" = []
    null_cpu_ratios: "List[float]" = []
    for rnd in range(rounds):
        bare_cpu: "List[float]" = []
        ft_cpu: "List[float]" = []
        null_cpu: "List[float]" = []
        phases: "Dict[str, float]" = {}

        def run_bare(cpu_out):
            return _run_bare_twin(
                world, steps=steps, warmup=warmup, reps=reps, cpu_out=cpu_out
            )

        def run_ft():
            return _run_ft_twin(
                world, phases, steps=steps, warmup=warmup, reps=reps,
                cpu_out=ft_cpu,
            )

        # NULL experiment: bare vs bare — identical twins.  Whatever ratio
        # spread the null shows is the estimator's noise floor; an FT-vs-
        # bare difference smaller than that floor is unmeasurable by ANY
        # twin comparison on this host, de-contended or not.  The floor is
        # computed on the SAME estimator as the gap (CPU ratios).
        #
        # Window order ALTERNATES per round (bare-then-ft / ft-then-bare):
        # later windows in a round run warmer (page cache, pool, branch
        # predictors), and a fixed order turns that warming into a
        # systematic negative "overhead" — alternation cancels it in the
        # across-rounds median.
        b_null = run_bare(null_cpu)
        if rnd % 2 == 0:
            b = run_bare(bare_cpu)
            f = run_ft()
        else:
            f = run_ft()
            b = run_bare(bare_cpu)
        null_ratios.append(b / b_null)
        if bare_cpu and null_cpu:
            null_cpu_ratios.append(bare_cpu[0] / null_cpu[0])
        ratios.append(f / b)
        if bare_cpu and ft_cpu:
            cpu_ratios.append(ft_cpu[0] / bare_cpu[0])
        bare_ms_runs.append(b * 1e3)
        protocol_ms_runs.append(
            phases.get("quorum_wait", 0.0)
            + phases.get("commit", 0.0)
            + phases.get("host_sync", 0.0)
        )
    bare_ms = min(bare_ms_runs)
    protocol_ms = min(protocol_ms_runs)
    overhead_pct = protocol_ms / bare_ms * 100.0
    twin_ratio_pct = (statistics.median(ratios) - 1.0) * 100.0
    # CPU-time ratio: the de-contended estimator.  process_time over the
    # stepping window counts every thread's ACTUAL work (incl. the async
    # quorum/background threads) and excludes idle scheduling gaps — the
    # component of the wall-ratio that made r4's 8.28% unusable.
    cpu_ratio_pct = (
        (statistics.median(cpu_ratios) - 1.0) * 100.0 if cpu_ratios else None
    )
    gap = (cpu_ratio_pct - overhead_pct) if cpu_ratio_pct is not None else None
    # noise floor: half the null twins' CPU-ratio spread, in points —
    # measured on the same estimator the gap uses (the wall null spread
    # is reported too, but excusing a CPU gap with a wall floor would
    # make the falsification unfalsifiable)
    null_spread_pts = (
        (max(null_cpu_ratios) - min(null_cpu_ratios)) / 2.0 * 100.0
        if null_cpu_ratios else None
    )
    null_wall_spread_pts = (
        (max(null_ratios) - min(null_ratios)) / 2.0 * 100.0
        if null_ratios else None
    )
    converged = gap is not None and abs(gap) <= 2.0
    # The estimator's OWN per-pair spread is a second noise floor: when
    # individual FT/bare pairs disagree by more than the median they
    # produce (e.g. pairs 0.83..1.43 around a 1.16 median), the median is
    # statistically indistinguishable from zero effect at this sample
    # size — the claim cannot rest on it.
    pair_spread_pts = (
        (max(cpu_ratios) - min(cpu_ratios)) / 2.0 * 100.0
        if cpu_ratios else None
    )
    floor = max(
        [x for x in (null_spread_pts, pair_spread_pts) if x is not None],
        default=None,
    )
    # falsified = the estimators did NOT converge, but the twin estimator
    # is demonstrably unable to resolve the effect: the gap sits inside
    # the measured noise floor (bare-vs-bare spread OR the pairs' own
    # spread), or the twin ratio reports the FT run as CHEAPER than bare
    # beyond the 2-pt budget — protocol work is strictly additive, so a
    # negative reading is noise by definition (ordering/warming bias).
    falsified = (
        not converged
        and gap is not None
        and (
            (floor is not None and abs(gap) <= floor + 2.0)
            or (cpu_ratio_pct is not None and cpu_ratio_pct < -2.0)
        )
    )
    log(
        f"overhead cross-check (long {bare_ms:.0f} ms steps, alternating "
        f"windows): phase-sum {overhead_pct:+.2f}% vs cpu-ratio "
        f"{cpu_ratio_pct:+.2f}% (gap {gap:+.2f} pts) vs wall twin-ratio "
        f"{twin_ratio_pct:+.2f}%; NULL bare-vs-bare CPU ratios "
        f"{[round(r, 4) for r in null_cpu_ratios]} -> noise floor "
        f"+-{null_spread_pts:.1f} pts (wall null +-{null_wall_spread_pts:.1f}) "
        f"({'converged' if converged else 'estimator noise-floor-bound' if falsified else 'UNEXPLAINED'})"
    )
    return {
        "long_step_ms": round(bare_ms, 1),
        "overhead_pct": round(overhead_pct, 2),
        "cpu_ratio_pct": round(cpu_ratio_pct, 2) if cpu_ratio_pct is not None else None,
        "twin_ratio_pct": round(twin_ratio_pct, 2),
        "gap_pts": round(gap, 2) if gap is not None else None,
        "converged_2pts": converged,
        "null_cpu_spread_pts": (
            round(null_spread_pts, 2) if null_spread_pts is not None else None
        ),
        "pair_spread_pts": (
            round(pair_spread_pts, 2) if pair_spread_pts is not None else None
        ),
        "null_wall_spread_pts": (
            round(null_wall_spread_pts, 2)
            if null_wall_spread_pts is not None else None
        ),
        "noise_floor_bound": falsified,
        "pair_ratios": [round(r, 4) for r in ratios],
        "cpu_pair_ratios": [round(r, 4) for r in cpu_ratios],
        "null_cpu_pair_ratios": [round(r, 4) for r in null_cpu_ratios],
        "null_pair_ratios": [round(r, 4) for r in null_ratios],
    }


# ---------------------------------------------------------------------------
# 3. DiLoCo outer sync at flagship scale (the BASELINE.json north star)
# ---------------------------------------------------------------------------

FLAGSHIP_PARAMS = int(464.4e6)  # matches the bench_model flagship config
DILOCO_FRAGMENTS = 8            # Streaming DiLoCo fragment count
DILOCO_SYNC_EVERY = 20          # inner steps per fragment cycle


def bench_diloco_vs_ddp(
    nonft_ddp_step_ms: float, gbps: "Optional[float]" = None
) -> "Dict[str, Any]":
    """BASELINE.json's own arithmetic, measured: FT Streaming DiLoCo's
    step cost vs the NON-FT DDP twin (the '<= 5% overhead on the
    train_diloco config' target).  Same per-step compute as the DDP
    twins; DiLoCo replaces the per-step 16 MB ring allreduce with one
    pseudograd sync every ``sync_every`` steps.  A fresh bare-DDP twin
    runs back-to-back in this same process so the comparison shares one
    load epoch (still a twin-loop comparison — ±20% noise-bound on the
    1-core host, docs/benchmarks.md §2 — hence the decomposition into
    inner median + per-sync cost, which is the robust part).

    ``gbps``: run BOTH twins under the token-bucket egress shaper (via
    ``TORCHFT_WIRE_GBPS``, which every ProcessGroupTCP in this process
    reads at construction) — the measured version of r4's extrapolated
    "on real DCN the sign flips": DDP pays the shaped wire every step,
    DiLoCo only at the outer sync.
    """
    import os as _os

    import torchft_tpu as ft

    prior = _os.environ.get("TORCHFT_WIRE_GBPS")
    if prior is not None and gbps is None:
        # a pre-set user knob would silently shape the "unshaped" leg
        log(f"note: TORCHFT_WIRE_GBPS={prior} is set — the nominally "
            "unshaped diloco-vs-ddp leg runs SHAPED at that rate")
    if gbps is not None:
        _os.environ["TORCHFT_WIRE_GBPS"] = str(gbps)
    try:
        return _bench_diloco_vs_ddp_body(nonft_ddp_step_ms, gbps, ft)
    finally:
        if gbps is not None:
            if prior is None:
                _os.environ.pop("TORCHFT_WIRE_GBPS", None)
            else:
                _os.environ["TORCHFT_WIRE_GBPS"] = prior


def _bench_diloco_vs_ddp_body(
    nonft_ddp_step_ms: float, gbps: "Optional[float]", ft
) -> "Dict[str, Any]":
    bare = _run_bare_twin(2) * 1e3
    nonft_ddp_step_ms = bare if gbps is not None else min(nonft_ddp_step_ms, bare)
    # warmup past the FIRST sync: it pays the outer-optimizer jit compile,
    # which amortizes to nothing over a real run's thousands of syncs
    world, sync_every, inner_steps, warmup = 2, 20, 100, 25
    lighthouse = LighthouseServer(
        min_replicas=world, join_timeout_ms=100, heartbeat_timeout_ms=1000
    )
    times: "Dict[int, List[float]]" = {}

    def replica(rank: int, barrier: "threading.Barrier") -> None:
        params = {"w": np.zeros(PARAM_SIZE, dtype=np.float32)}
        state = {"params": params}
        manager = Manager(
            pg=ProcessGroupTCP(timeout=30.0),
            min_replica_size=world,
            load_state_dict=lambda sd: state.update(params=dict(sd)),
            state_dict=lambda: dict(state["params"]),
            lighthouse_addr=lighthouse.address(),
            replica_id=f"dl_{rank}",
            group_rank=0,
            group_world_size=1,
            use_async_quorum=False,  # DiLoCo requires sync quorum
            timeout=30.0,
            quorum_timeout=30.0,
        )
        import jax
        import optax

        try:
            # host-only leg: params and pseudograds are host numpy, so the
            # outer optimizer's jax ops stay on the CPU backend — the leg
            # prices the DCN fault-tolerance layer and measures the same
            # thing with or without a chip attached
            with jax.default_device(jax.devices("cpu")[0]), ft.DiLoCo(
                manager,
                [["w"]],
                lambda: dict(state["params"]),
                lambda flat: state["params"].update(flat),
                optax.sgd(0.7, momentum=0.9, nesterov=True),
                sync_every=sync_every,
                fragment_sync_delay=1,  # overlap the sync with compute
            ) as diloco:
                ts: "List[float]" = []
                barrier.wait(timeout=30)
                for step in range(inner_steps):
                    t0 = time.perf_counter()
                    grads = _ddp_compute(step, rank)
                    state["params"]["w"] = state["params"]["w"] - 0.01 * grads
                    diloco.step()
                    ts.append(time.perf_counter() - t0)
                times[rank] = ts[warmup:]
        finally:
            manager.shutdown()

    try:
        barrier = threading.Barrier(world)
        threads = [
            threading.Thread(target=replica, args=(r, barrier), daemon=True)
            for r in range(world)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
    finally:
        lighthouse.shutdown()
    assert len(times) == world, "diloco twin failed"
    # split sync-boundary steps (prepare at count%sync_every==sync_every-1,
    # finish at ==0 with delay=1 -> local indices 18/19 mod 20) from pure
    # inner steps, so the decomposition is explicit
    inner: "List[float]" = []
    boundary: "List[float]" = []
    for ts in times.values():
        for i, t in enumerate(ts):
            step = i + warmup
            (boundary if step % sync_every >= sync_every - 2 else inner).append(t)
    inner_ms = statistics.median(inner) * 1e3
    # 2 boundary steps per sync; subtract their inner-compute share.
    # Clamped: on a noisy host the inner median can exceed the boundary
    # mean, which would read as a nonsensical negative sync cost.
    per_sync_ms = max(
        0.0,
        (sum(boundary) / len(boundary) * 2e3 - 2 * inner_ms)
        if boundary
        else 0.0,
    )
    amortized_ms = inner_ms + per_sync_ms / sync_every
    overhead_pct = (amortized_ms / nonft_ddp_step_ms - 1.0) * 100.0
    inner_vs_ddp_pct = (inner_ms / nonft_ddp_step_ms - 1.0) * 100.0
    wire_note = (
        f"both twins shaped to {gbps} GB/s egress"
        if gbps is not None
        else "loopback makes the per-step allreduce DiLoCo avoids nearly free"
    )
    log(f"diloco-vs-ddp{f' @{gbps} GB/s' if gbps else ''}: FT DiLoCo inner "
        f"step {inner_ms:.1f} ms "
        f"({inner_vs_ddp_pct:+.1f}% vs non-FT DDP {nonft_ddp_step_ms:.1f} ms"
        f" — no per-step allreduce), outer sync {per_sync_ms:.0f} ms every "
        f"{sync_every} steps -> amortized {amortized_ms:.1f} ms = "
        f"{overhead_pct:+.1f}% ({wire_note})")
    return {
        "diloco_inner_step_ms": round(inner_ms, 2),
        "diloco_inner_vs_nonft_ddp_pct": round(inner_vs_ddp_pct, 1),
        "diloco_sync_ms": round(per_sync_ms, 1),
        "diloco_amortized_step_ms": round(amortized_ms, 2),
        "diloco_vs_nonft_ddp_pct": round(overhead_pct, 1),
        "nonft_ddp_step_ms": round(nonft_ddp_step_ms, 2),
    }


def _diloco_sync_leg(
    leg: str, quantize: bool, gbps: "float | None", repeats: int = 2,
    wire_dtype: "Optional[str]" = None,
    world: int = 2,
    rtt_ms: "Optional[float]" = 0.0,
    topology: "Optional[str]" = None,
    n_fragments: int = DILOCO_FRAGMENTS,
    device: bool = False,
) -> "Dict[str, Any]":
    """Flagship-scale outer sync over the TCP ring at a shaped egress
    bandwidth (None = unshaped loopback), best of ``repeats`` runs (the
    shared host shows 2-3x wall spikes from neighbor interference — a
    single sample can turn a 5 s sync into a 15 s headline).  Returns
    wall, wire and codec seconds (codec only on the quantized leg).
    ``wire_dtype``: payload format for the quantized leg (None resolves
    through the collective's default chain: TORCHFT_QUANT_WIRE env, else
    int8 — format-comparison legs pin it explicitly).

    WAN knobs (the RTT-swept legs): ``rtt_ms`` arms the per-message
    boundary latency on every PG; ``topology`` picks the REDUCTION PLAN
    ("flat" or a TORCHFT_TOPOLOGY spec) — the wire model's boundary map
    always comes from the TORCHFT_TOPOLOGY env the caller sets, so flat
    and hierarchical legs price the same physical topology.  ``device``:
    create the fragment on-device and quantize with the Pallas kernel
    (the ``diloco.int8_device`` leg, TPU only)."""
    if repeats > 1:
        runs = [
            _diloco_sync_leg(
                f"{leg}_r{i}", quantize, gbps, repeats=1,
                wire_dtype=wire_dtype, world=world, rtt_ms=rtt_ms,
                topology=topology, n_fragments=n_fragments, device=device,
            )
            for i in range(repeats)
        ]
        return min(runs, key=lambda r: r["sync_s"])
    from torchft_tpu.ops.collectives import allreduce_quantized

    frag_elems = FLAGSHIP_PARAMS // DILOCO_FRAGMENTS
    store = StoreServer()
    barrier = threading.Barrier(world)
    walls: "Dict[int, float]" = {}
    wires: "Dict[int, int]" = {}
    inters: "Dict[int, int]" = {}
    codecs: "Dict[int, float]" = {}
    pipes: "Dict[int, Dict[str, Any]]" = {}

    def worker(rank: int) -> None:
        pg = ProcessGroupTCP(
            timeout=300.0, bandwidth_gbps=gbps, rtt_ms=rtt_ms
        )
        pg.configure(
            f"{store.address()}/diloco_{leg}_{gbps}", f"dl_{rank}", rank, world
        )
        try:
            if device:
                import jax

                _require_tpu("diloco int8_device leg")
                # fragment born ON device: what this leg prices is the
                # on-chip quantize + the int8 device->host copies, not an
                # f32 host->device upload no real sync performs
                frag = jax.jit(
                    lambda k: jax.random.normal(k, (frag_elems,))
                )(jax.random.PRNGKey(rank))
                frag.block_until_ready()
            else:
                rng = np.random.default_rng(rank)
                frag = rng.standard_normal(frag_elems).astype(np.float32)
            barrier.wait(timeout=60)
            t0 = time.perf_counter()
            wire = 0
            inter = 0
            codec = 0.0
            # per-fragment pipeline accounting (quantized legs): sums of
            # the chunked pipeline's busy walls + the efficiency of the
            # worst fragment (the honest overlap headline)
            pipe: "Dict[str, Any]" = {
                "wire_busy_s": 0.0, "n_chunks": 0, "effs": [], "hops": {},
            }
            for _ in range(n_fragments):
                if quantize:
                    w = allreduce_quantized(
                        [frag], REDUCE_SUM, pg, wire_dtype=wire_dtype,
                        topology=topology,
                        device_quantize=True if device else None,
                    )
                    w.wait(timeout=600)
                    wire += w.wire_bytes
                    inter += getattr(w, "inter_wire_bytes", 0) or 0
                    codec += w.codec_s_box[0]
                    stats = w.quant_stats
                    pipe["wire_busy_s"] += stats["wire_s"]
                    pipe["n_chunks"] = stats["n_chunks"]
                    pipe["effs"].append(stats["overlap_efficiency"])
                    for hop, s in (stats.get("hop_wire_s") or {}).items():
                        pipe["hops"][hop] = pipe["hops"].get(hop, 0.0) + s
                else:
                    aw = pg.allreduce([frag], REDUCE_SUM)
                    aw.wait(timeout=600)
                    # measured per-rank ring egress (reduce-scatter half +
                    # allgather half), reported by the PG itself
                    wire += aw.wire_bytes
            walls[rank] = time.perf_counter() - t0
            wires[rank] = wire
            inters[rank] = inter
            codecs[rank] = codec
            pipes[rank] = pipe
        finally:
            pg.shutdown()

    threads = [
        threading.Thread(target=worker, args=(r,), daemon=True)
        for r in range(world)
    ]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=900)
    finally:
        store.shutdown()
    assert len(walls) == world, f"diloco {leg} leg failed (gbps={gbps})"
    out = {
        "sync_s": round(max(walls.values()), 2),
        "wire_gb": round(wires[0] / 1e9, 3),
        "codec_s": round(max(codecs.values()), 2),
    }
    if quantize:
        pipe = pipes[0]
        out["wire_busy_s"] = round(pipe["wire_busy_s"], 2)
        out["chunks_per_fragment"] = pipe["n_chunks"]
        out["overlap_efficiency"] = round(min(pipe["effs"]), 3)
        out["overlap_efficiency_mean"] = round(
            sum(pipe["effs"]) / len(pipe["effs"]), 3
        )
        if pipe["hops"]:
            out["hop_wire_s"] = {
                h: round(s, 2) for h, s in sorted(pipe["hops"].items())
            }
        if any(inters.values()):
            # worst leader's inter-host egress — the bytes the WAN
            # actually carries
            out["inter_wire_gb"] = round(max(inters.values()) / 1e9, 3)
    # per-leg dominant-ledger-contributor: codec vs wire busy time (the
    # unquantized leg has no codec, so its sync wall IS wire)
    wire_est = out.get(
        "wire_busy_s", max(out["sync_s"] - out["codec_s"], 0.0)
    )
    out["dominant"] = "codec" if out["codec_s"] > wire_est else "wire"
    return out


def bench_diloco(model_step_ms: float) -> "Dict[str, Any]":
    """Full outer syncs of flagship-scale pseudogradients over the TCP
    ring, f32 vs int8-quantized — unshaped loopback PLUS token-bucket
    shaped legs at 1 / 0.5 / 0.1 GB/s egress (the DCN bandwidths the
    quantized wire exists for; reference fast path:
    torchft/collectives.py:297-415).  Loopback bandwidth is effectively
    infinite, so only the shaped legs measure the codec-vs-wire tradeoff
    honestly — r4 extrapolated this, r5 measures it.

    Streaming-DiLoCo shape: ~464 M params in 8 fragments, each fragment
    allreduced separately (that IS the streaming schedule — and it caps
    peak memory at one ~232 MB fragment per rank instead of 1.86 GiB).
    Pseudograds are host numpy (the outer sync runs on the DCN host path;
    the device-side Pallas quantize has its own bitwise-equivalence tests
    and here the host codec is the honest leg for host arrays).

    Amortized cost per inner step = sync wall / sync_every; overhead_pct
    prices it against the measured flagship model step.  This is the
    NO-OVERLAP upper bound — the product overlaps fragment syncs with
    inner steps (local_sgd.py fragment_sync_delay), so real overhead is
    lower.  The quantized legs run the chunked software pipeline
    (ops/collectives.py): quantize(chunk i+1) ∥ wire(chunk i) ∥
    reduce(chunk i-1), codec row-blocked across TORCHFT_QUANT_THREADS
    workers — the per-leg ``overlap_efficiency`` / ``chunks_per_fragment``
    / ``wire_busy_s`` fields report how much of the codec actually hid
    behind the wire (docs/benchmarks.md schema notes).
    """
    legs: "Dict[str, Any]" = {}
    # wire_dtype pinned EXPLICITLY on every quantized leg: this bench
    # compares formats by name, so a TORCHFT_QUANT_WIRE env default must
    # not silently swap what the "int8" label measures
    for leg, quantize, wire in (
        ("f32", False, None),
        ("int8", True, "int8"),
        ("fp8_e4m3", True, "fp8_e4m3"),
    ):
        r = _diloco_sync_leg(leg, quantize, None, wire_dtype=wire)
        sync_s = r["sync_s"]
        amortized_ms = sync_s * 1e3 / DILOCO_SYNC_EVERY
        legs[leg] = {
            "sync_s": sync_s,
            "wire_gb": r["wire_gb"],
            "codec_s": r["codec_s"],
            "amortized_ms_per_inner_step": round(amortized_ms, 1),
            "overhead_pct_vs_model_step": round(
                100.0 * amortized_ms / model_step_ms, 1
            ),
        }
        # chunked-pipeline accounting (quantized legs): per-fragment chunk
        # count, summed wire-busy wall, and overlap efficiency (worst +
        # mean fragment) — docs/benchmarks.md schema notes
        for key in (
            "wire_busy_s",
            "chunks_per_fragment",
            "overlap_efficiency",
            "overlap_efficiency_mean",
        ):
            if key in r:
                legs[leg][key] = r[key]
        pipe_note = (
            f", overlap eff {r['overlap_efficiency']:.2f} over "
            f"{r['chunks_per_fragment']} chunks/frag"
            if "overlap_efficiency" in r
            else ""
        )
        log(f"diloco {leg}: one outer sync of {FLAGSHIP_PARAMS/1e6:.0f}M "
            f"params in {sync_s:.2f}s ({r['wire_gb']:.2f} GB wire, "
            f"codec {r['codec_s']:.1f}s{pipe_note}) -> "
            f"{amortized_ms:.0f} ms/inner-step amortized at "
            f"sync_every={DILOCO_SYNC_EVERY} = "
            f"{legs[leg]['overhead_pct_vs_model_step']:.1f}% of a "
            f"{model_step_ms:.0f} ms model step (no-overlap upper bound)")
    # shaped legs: the measured break-even table (VERDICT r4 item 1/2 —
    # every bandwidth-dependent claim measured, none extrapolated)
    shaped: "Dict[str, Any]" = {}
    for gbps in (1.0, 0.5, 0.1):
        f32 = _diloco_sync_leg("f32s", False, gbps)
        i8 = _diloco_sync_leg("int8s", True, gbps, wire_dtype="int8")
        shaped[str(gbps)] = {
            "f32_sync_s": f32["sync_s"],
            "int8_sync_s": i8["sync_s"],
            "int8_codec_s": i8["codec_s"],
            "int8_overlap_efficiency": i8.get("overlap_efficiency"),
            "int8_speedup_x": round(f32["sync_s"] / max(i8["sync_s"], 1e-9), 2),
            "winner": "int8" if i8["sync_s"] < f32["sync_s"] else "f32",
        }
        log(f"diloco shaped @{gbps} GB/s: f32 {f32['sync_s']:.2f}s vs "
            f"int8 {i8['sync_s']:.2f}s (codec {i8['codec_s']:.1f}s) -> "
            f"{shaped[str(gbps)]['winner']} wins "
            f"{shaped[str(gbps)]['int8_speedup_x']:.2f}x")
    legs["shaped"] = shaped
    # diloco.int8_device: the on-chip Pallas quantize path priced on real
    # hardware — fragment born on device, quantized in one kernel launch,
    # int8 payload + row scales D2H-copied per chunk into the wire
    # pipeline.  Chip-touching: needs a TPU (interpret mode on CPU would
    # price the emulator) and a failure fails the run.
    r = _diloco_sync_leg(
        "int8_device", True, None, repeats=1, wire_dtype="int8",
        n_fragments=2, device=True,
    )
    scale = DILOCO_FRAGMENTS / 2
    amortized_ms = r["sync_s"] * scale * 1e3 / DILOCO_SYNC_EVERY
    legs["int8_device"] = {
        **r,
        "fragments_run": 2,
        "amortized_ms_per_inner_step": round(amortized_ms, 1),
        "overhead_pct_vs_model_step": round(
            100.0 * amortized_ms / model_step_ms, 1
        ),
    }
    log(f"diloco int8_device: {legs['int8_device']}")
    legs["wire_reduction_x"] = round(
        legs["f32"]["wire_gb"] / max(legs["int8"]["wire_gb"], 1e-9), 2
    )
    legs["params_m"] = round(FLAGSHIP_PARAMS / 1e6, 1)
    legs["fragments"] = DILOCO_FRAGMENTS
    legs["sync_every"] = DILOCO_SYNC_EVERY
    return legs


# ---------------------------------------------------------------------------
# 3b. WAN sweep: flat vs hierarchical int8 DiLoCo at simulated RTT
# ---------------------------------------------------------------------------

WAN_WORLD = 4            # 2 hosts x 2 ranks
WAN_TOPOLOGY = "hosts:2"
WAN_GBPS = 0.5           # per-rank shaped egress during the sweep
WAN_FRAGMENTS = 2        # flagship-scale fragments per leg (wall bound)
WAN_RTTS_MS = (0.0, 10.0, 50.0)


def bench_wan(model_step_ms: "Optional[float]") -> "Dict[str, Any]":
    """The WAN-grade leg (ROADMAP item 3): flat-ring vs hierarchical
    int8 DiLoCo outer sync swept over simulated inter-host RTT.

    Both legs run 4 thread-ranks laid out as 2 hosts x 2
    (``TORCHFT_TOPOLOGY=hosts:2`` is set process-wide so the WIRE model
    charges ``rtt_ms`` only on messages crossing the host boundary for
    BOTH schedules — same physical topology, different reduction plan).
    The flat leg pins ``topology="flat"`` (today's alltoall/allgather
    interleave, 2*(w-1) serialized inter-host-bearing ops per chunk);
    the hierarchical leg runs the synthesized plan (2 inter-host
    sendrecv per chunk).  At 0 ms they should be comparable; at WAN RTT
    the flat ring's serialized hops dominate and hierarchical must win
    — the acceptance margin the compact summary carries, next to the
    per-hop wire telemetry and inter-host byte counts.

    Also re-validates the DiLoCo overhead claim at RTT: each leg's sync
    wall scales to a full ``DILOCO_FRAGMENTS``-fragment outer sync and
    amortizes over ``DILOCO_SYNC_EVERY`` inner steps against the
    flagship model step — ``model_step_ms`` as measured by
    ``bench_model`` in the same run; ``None`` (the host-only ``--wan``
    run, no chip) leaves those percentages unmeasured rather than
    pricing them against a remembered number.
    """
    import os as _os

    # the WAN knobs go through the ENV (not ctor args) so every PG a
    # leg constructs — and anything else that resolves the wire model —
    # sees one consistent configuration per sweep point
    prior = {
        k: _os.environ.get(k)
        for k in ("TORCHFT_TOPOLOGY", "TORCHFT_WIRE_GBPS",
                  "TORCHFT_WIRE_RTT_MS")
    }
    _os.environ["TORCHFT_TOPOLOGY"] = WAN_TOPOLOGY
    _os.environ["TORCHFT_WIRE_GBPS"] = str(WAN_GBPS)
    try:
        out: "Dict[str, Any]" = {
            "world": WAN_WORLD,
            "topology": WAN_TOPOLOGY,
            "gbps": WAN_GBPS,
            "fragments_per_leg": WAN_FRAGMENTS,
        }
        scale = DILOCO_FRAGMENTS / WAN_FRAGMENTS

        def overhead_pct(leg: "Dict[str, Any]") -> "Optional[float]":
            if model_step_ms is None:
                return None
            return round(
                100.0 * leg["sync_s"] * scale * 1e3
                / DILOCO_SYNC_EVERY / model_step_ms, 1
            )

        for rtt in WAN_RTTS_MS:
            _os.environ["TORCHFT_WIRE_RTT_MS"] = str(rtt)
            flat = _diloco_sync_leg(
                "wan_flat", True, None, wire_dtype="int8",
                world=WAN_WORLD, rtt_ms=None, topology="flat",
                n_fragments=WAN_FRAGMENTS,
            )
            hier = _diloco_sync_leg(
                "wan_hier", True, None, wire_dtype="int8",
                world=WAN_WORLD, rtt_ms=None, topology=WAN_TOPOLOGY,
                n_fragments=WAN_FRAGMENTS,
            )
            speedup = flat["sync_s"] / max(hier["sync_s"], 1e-9)
            leg = {
                "flat_sync_s": flat["sync_s"],
                "hier_sync_s": hier["sync_s"],
                "hier_speedup_x": round(speedup, 2),
                "winner": "hier" if hier["sync_s"] < flat["sync_s"] else "flat",
                "flat_inter_wire_gb": flat.get("inter_wire_gb"),
                "hier_inter_wire_gb": hier.get("inter_wire_gb"),
                "hier_hop_wire_s": hier.get("hop_wire_s"),
                "flat_hop_wire_s": flat.get("hop_wire_s"),
                # overhead re-validation at this RTT (no-overlap upper
                # bound, like bench_diloco's table)
                "flat_overhead_pct_vs_model_step": overhead_pct(flat),
                "hier_overhead_pct_vs_model_step": overhead_pct(hier),
            }
            out[f"rtt_{rtt:g}ms"] = leg
            log(f"wan @rtt={rtt:g}ms {WAN_GBPS}GB/s: flat {flat['sync_s']:.2f}s "
                f"vs hier {hier['sync_s']:.2f}s -> {leg['winner']} wins "
                f"{leg['hier_speedup_x']:.2f}x | hier hops "
                f"{hier.get('hop_wire_s')} | inter GB "
                f"flat={flat.get('inter_wire_gb')} hier={hier.get('inter_wire_gb')}")
        return out
    finally:
        for k, v in prior.items():
            if v is None:
                _os.environ.pop(k, None)
            else:
                _os.environ[k] = v


# ---------------------------------------------------------------------------
# 4. flagship model MFU on the attached accelerator
# ---------------------------------------------------------------------------

# Peak dense bf16 TFLOP/s of ONE chip, keyed by a substring of
# ``device.device_kind``.  The one copy of this table: MFU is meaningless
# against a guessed peak, so a kind that is not listed is an error.
# Sources: Google Cloud TPU documentation, per-generation system
# architecture pages — "TPU v5e": 197 TFLOP/s bf16 (16 GB HBM, 819 GB/s);
# "TPU v6e" (Trillium): 918; "TPU v5p": 459; "TPU v4": 275; "TPU v3": 123;
# "TPU v2": 45.
_PEAK_TFLOPS = (
    ("v6", 918.0),
    ("v5p", 459.0),
    ("v5 lite", 197.0),  # a v5e reports device_kind "TPU v5 lite"
    ("v5e", 197.0),
    ("v4", 275.0),
    ("v3", 123.0),
    ("v2", 45.0),
)


def _peak_flops(device_kind: str) -> float:
    kind = device_kind.lower()
    for key, tf in _PEAK_TFLOPS:
        if key in kind:
            return tf * 1e12
    raise ValueError(
        f"no published bf16 peak for device kind {device_kind!r}: add it to "
        "_PEAK_TFLOPS with its source before reporting an MFU"
    )


def _model_flops_per_step(cfg, batch: int, seq: int) -> "Dict[str, float]":
    """Model FLOPs (fwd+bwd = 3x fwd) per optimizer step.

    matmul params N: block weights + tied head (embedding gather is not a
    matmul; the tied head IS one).  attention: QK^T and AV are each
    2*B*T^2*d fwd (full causal scores — the kernel does not skip the
    masked half), x3 for bwd.  Remat recompute is deliberately NOT
    counted: MFU is defined over model FLOPs (vs HFU).
    """
    e, f, l = cfg.d_model, cfg.d_ff, cfg.n_layers
    hd, nh, nkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    n_block = l * (e * nh * hd + 2 * e * nkv * hd + nh * hd * e + 3 * e * f)
    n_head = cfg.vocab_size * e
    tokens = batch * seq
    mm = 6 * (n_block + n_head) * tokens
    attn = 3 * (2 * 2 * batch * seq * seq * e) * l
    return {
        "params_matmul": float(n_block + n_head),
        "flops": float(mm + attn),
        "tokens": float(tokens),
    }


def _require_tpu(leg: str):
    """The attached TPU device, or an error: a chip-touching leg never
    runs a stand-in on another backend and files the result as its own."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            f"{leg} needs a TPU; jax.devices()[0] is {dev.platform!r} "
            f"({dev.device_kind})"
        )
    return dev


def _ft_around_model_step(
    cfg, optimizer, state, tokens, step_s: float,
    steps: int = 5, warmup: int = 1,
) -> "Dict[str, Any]":
    """The product's FT-DDP step around the REAL on-chip model.

    ``make_grad_step`` on the chip, then the WHOLE gradient pytree through
    ``Manager.allreduce`` (device -> host on the PG worker, world-size-1
    ring, host -> device), the commit vote, and ``ft.Optimizer.update`` —
    the loop of examples/train_ddp.py, timed with ``block_until_ready``.
    ``state`` is the bench's [params, opt_state]; it is consumed (the
    update donates) and rebound.

    ``ft_step_ms`` is the wall time of such a step and ``model_overhead_pct``
    prices it against the fused non-FT step (``step_s``): it contains the
    un-fusing of the optimizer, both host transfers of the gradients and
    the protocol.  ``protocol_ms_per_step`` (quorum_wait + commit +
    host_sync) is the part no single-replica layout can avoid.
    """
    import jax

    import torchft_tpu as ft
    from chip_smoke import require_mosaic
    from torchft_tpu.models.transformer import make_grad_step

    grad_step = make_grad_step(cfg)
    require_mosaic(grad_step.lower(state[0], tokens).as_text(), "FT model leg")
    grad_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(state[0]))

    lighthouse = LighthouseServer(
        min_replicas=1, join_timeout_ms=100, heartbeat_timeout_ms=1000
    )
    manager = None
    acc: "Dict[str, float]" = {}
    phase_snap: "Dict[str, float]" = {}
    ring_ms: "List[float]" = []
    walls: "List[float]" = []
    try:
        manager = Manager(
            pg=ProcessGroupTCP(timeout=120.0),
            min_replica_size=1,
            load_state_dict=lambda sd: None,
            state_dict=lambda: {"ok": np.zeros(1, np.float32)},
            lighthouse_addr=lighthouse.address(),
            replica_id="model_ft",
            group_rank=0,
            group_world_size=1,
            use_async_quorum=True,
            timeout=120.0,
            quorum_timeout=120.0,
        )
        ddp = ft.DistributedDataParallel(manager)
        opt = ft.Optimizer(manager, optimizer)
        for step in range(steps):
            t0 = time.perf_counter()
            opt.begin_step()
            loss, grads = grad_step(state[0], tokens)
            work = ddp.allreduce_gradients(grads)
            # the device gradients must be gone before the update (and the
            # next forward) allocate: there is no room for two copies
            del grads
            avg = work.wait(timeout=120)
            del work
            assert manager.errored() is None, manager.errored()
            committed = manager.should_commit()
            assert committed, "world-1 FT step failed to commit"
            state[0], state[1] = opt.update(state[0], avg, state[1])
            del avg
            jax.block_until_ready(state[0])
            assert np.isfinite(float(loss)), "non-finite loss"
            wall = time.perf_counter() - t0
            phase, phase_snap = _phase_delta(manager, phase_snap)
            if step >= warmup:
                walls.append(wall)
                ring_ms.append(phase.get("ring", 0.0) * 1e3)
                for k, v in phase.items():
                    acc[k] = acc.get(k, 0.0) + v
    finally:
        if manager is not None:
            manager.shutdown()
        lighthouse.shutdown()

    n = steps - warmup
    protocol_ms = (
        acc.get("quorum_wait", 0.0) + acc.get("commit", 0.0)
        + acc.get("host_sync", 0.0)
    ) * 1e3 / n
    ft_step_ms = statistics.median(walls) * 1e3
    out = {
        "ft_step_ms": round(ft_step_ms, 1),
        "model_overhead_pct": round(100.0 * (ft_step_ms / (step_s * 1e3) - 1.0), 1),
        "protocol_ms_per_step": round(protocol_ms, 3),
        "ring_ms": round(statistics.median(ring_ms), 1),
        "grad_bytes_each_way": grad_bytes,
        "phases_ms_per_step": {
            k: round(v * 1e3 / n, 3) for k, v in sorted(acc.items())
        },
    }
    log(f"model FT step: {ft_step_ms:.0f} ms vs fused non-FT "
        f"{step_s*1e3:.0f} ms -> {out['model_overhead_pct']:+.1f}% "
        f"(protocol {protocol_ms:.2f} ms, full-gradient ring leg "
        f"{out['ring_ms']:.0f} ms for {grad_bytes/2**30:.2f} GiB each way)")
    return out


def bench_model() -> "Dict[str, Any]":
    """Flagship train step on the attached TPU: one configuration, named
    explicitly — flash attention, dots remat, batch 8, donated state.  Any
    failure (no TPU, unknown peak, kernel not compiled in, OOM) fails the
    leg; nothing smaller or slower is measured in its place."""
    import jax
    import optax

    from chip_smoke import flagship, require_mosaic
    from torchft_tpu.models.transformer import init_params, make_train_step

    dev = _require_tpu("bench_model")
    peak = _peak_flops(dev.device_kind)
    # chip_smoke.py's flagship: ~464M params shaped for the v5e MXU (d_model
    # 1536, head_dim 256 — large aligned matmul tiles), bf16 compute, Pallas
    # flash attention and dots remat named explicitly.
    cfg = flagship(n_layers=16)
    batch, seq, timed_steps = 8, cfg.max_seq_len, 16
    optimizer = optax.adamw(3e-4)
    # donated: the 5.2 GiB params+adamw carry would otherwise be
    # double-buffered (in + out live at once); callers rebind each call
    train_step = make_train_step(cfg, optimizer, donate=True)

    params = jax.jit(lambda k: init_params(k, cfg))(jax.random.PRNGKey(0))
    opt_state = jax.jit(optimizer.init)(params)
    tokens = jax.jit(
        lambda k: jax.random.randint(k, (batch, seq), 0, cfg.vocab_size)
    )(jax.random.PRNGKey(1))
    state = [params, opt_state]
    del params, opt_state

    t_c0 = time.perf_counter()
    lowered = train_step.lower(state[0], state[1], tokens)
    require_mosaic(lowered.as_text(), "bench_model")
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t_c0

    def window(n: int) -> float:
        """Wall seconds per step over n back-to-back steps; dispatch is
        asynchronous, so the window ends in block_until_ready."""
        t0 = time.perf_counter()
        for _ in range(n):
            state[0], state[1], loss = compiled(state[0], state[1], tokens)
        jax.block_until_ready(state[0])
        dt = time.perf_counter() - t0
        assert np.isfinite(float(loss)), "non-finite loss"
        return dt / n

    window(2)  # warm
    step_s = min(window(timed_steps) for _ in range(3))

    fl = _model_flops_per_step(cfg, batch, seq)
    achieved = fl["flops"] / step_s
    stats = dev.memory_stats() or {}
    out = {
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "config": (
            f"d{cfg.d_model} L{cfg.n_layers} h{cfg.n_heads}/{cfg.n_kv_heads} "
            f"ff{cfg.d_ff} V{cfg.vocab_size} B{batch} T{seq} "
            f"{cfg.attn_impl} remat={cfg.remat_policy} donated"
        ),
        "params_matmul_m": round(fl["params_matmul"] / 1e6, 1),
        "step_ms": round(step_s * 1e3, 2),
        "compile_s": round(compile_s, 1),
        "tokens_per_s": round(fl["tokens"] / step_s),
        "tflops_per_s": round(achieved / 1e12, 1),
        "mfu_pct": round(100.0 * achieved / peak, 1),
        "peak_hbm_gib": round(stats.get("peak_bytes_in_use", 0) / 2**30, 2),
    }
    log(f"model bench: {out}")
    out["ft"] = _ft_around_model_step(cfg, optimizer, state, tokens, step_s)
    return out


# ---------------------------------------------------------------------------
# compact tail summary
# ---------------------------------------------------------------------------

# The driver keeps only the LAST 2000 bytes of stdout; the full result
# line alone is several KB, so its head (with the primary metric) was
# truncated out of r5's capture.  The compact summary printed after it
# must always fit the tail window with room for the trailing newline.
# ---------------------------------------------------------------------------
# serving: fan-out weight distribution under churn (ISSUE 12)
# ---------------------------------------------------------------------------

SERVING_SERVERS = 4
SERVING_CLIENTS = 8
SERVING_RUN_S = 12.0
SERVING_LEAVES = 8
SERVING_LEAF_ELEMS = 64 * 1024  # 8 x 64k fp32 = 2 MB payload


def bench_serving() -> "Dict[str, Any]":
    """Weight-serving tier under churn: a publisher streams versioned
    int8 payloads through a lighthouse-synthesized fan-out tree of
    ``SERVING_SERVERS`` relays while ``SERVING_CLIENTS`` stub clients
    fetch the latest version in a loop; mid-run the chaos kill takes a
    TREE NODE down while fetches are in flight.  Headlines: sustained
    published+delivered checkpoints/sec, client fetch p50/p99, failover
    count, and the bitwise-identity check after failover (a client's
    post-kill fetch must decode byte-identical to the published
    payload).  docs/architecture.md "Weight-serving tier"."""
    from torchft_tpu.ops import quantization as q
    from torchft_tpu.serving import (
        ServingClient,
        ServingReplica,
        WeightPublisher,
    )

    rng = np.random.RandomState(7)
    base = {
        f"layer{i}": rng.randn(SERVING_LEAF_ELEMS).astype(np.float32)
        for i in range(SERVING_LEAVES)
    }
    payload_bytes = sum(a.nbytes for a in base.values())

    lh = LighthouseServer(
        min_replicas=1, heartbeat_timeout_ms=1000, quorum_tick_ms=50,
        serving_fanout=2,
    )
    pub = WeightPublisher(
        lh.address(), wire="int8", fragments=2, heartbeat_interval=0.1
    )
    reps = [
        ServingReplica(
            lh.address(), replica_id=f"bench{i}", poll_interval=0.05,
            fetch_timeout=10.0,
        )
        for i in range(SERVING_SERVERS)
    ]
    stop = threading.Event()
    lat: "List[float]" = []
    errors: "List[str]" = []
    lock = threading.Lock()
    published_states: "Dict[int, Dict[str, np.ndarray]]" = {}

    def _publish(vi: int) -> int:
        state = {k: a + np.float32(vi) for k, a in base.items()}
        v = pub.publish(state)
        with lock:
            published_states[v] = state
            while len(published_states) > 8:
                published_states.pop(min(published_states))
        return v

    def _client_loop(i: int) -> None:
        c = ServingClient(lh.address(), plan_ttl=0.2, client_id=str(i))
        while not stop.is_set():
            t0 = time.perf_counter()
            try:
                c.fetch(timeout=15)
                with lock:
                    lat.append(time.perf_counter() - t0)
            except Exception as e:  # noqa: BLE001 - tallied
                with lock:
                    errors.append(repr(e))
            time.sleep(0.01)
        c.close()

    from torchft_tpu.utils import metrics as _m

    def _failover_count() -> float:
        return (
            _m.SERVING_FAILOVERS.labels(role="client").get()
            + _m.SERVING_FAILOVERS.labels(role="relay").get()
        )

    failovers0 = _failover_count()
    kill_info: "Dict[str, Any]" = {}
    bitwise_ok = False
    try:
        t_pub0 = time.perf_counter()
        vi = _publish(0)
        threads = [
            threading.Thread(target=_client_loop, args=(i,), daemon=True)
            for i in range(SERVING_CLIENTS)
        ]
        for t in threads:
            t.start()
        t_end = time.monotonic() + SERVING_RUN_S
        killed = False
        while time.monotonic() < t_end:
            vi = _publish(vi)
            if not killed and time.monotonic() > t_end - SERVING_RUN_S / 2:
                # chaos: kill a live TREE NODE mid-run, fetches in flight
                cl = ServingClient(lh.address(), plan_ttl=0.0)
                plan = cl.plan(refresh=True)
                cl.close()
                interior = [
                    n for n in plan["nodes"] if n["children"] > 0
                ] or plan["nodes"]
                victim_id = interior[0]["replica_id"]
                victim = next(
                    r for r in reps if r.replica_id() == victim_id
                )
                t_kill = time.perf_counter()
                victim.shutdown()
                killed = True
                kill_info = {
                    "victim": victim_id,
                    "victim_children": interior[0]["children"],
                    "at_version": vi,
                }
            time.sleep(0.1)
        publish_wall = time.perf_counter() - t_pub0
        published = pub.latest_version()

        # post-kill bitwise check: fetch the latest version through the
        # surviving tree and compare against the int8 round trip of the
        # exact published state
        vc = ServingClient(lh.address(), plan_ttl=0.0, client_id="verify")
        state, got = vc.fetch(timeout=30)
        vc.close()
        with lock:
            src = published_states.get(got)
        if src is not None:
            bitwise_ok = all(
                np.array_equal(
                    state[k],
                    q.dequantize(
                        *q.quantize(a.reshape(1, -1), q.WIRE_INT8),
                        a.shape,
                        np.dtype(np.float32),
                    ),
                )
                for k, a in src.items()
            )
        stop.set()
        for t in threads:
            t.join(timeout=30)
    finally:
        stop.set()
        for r in reps:
            try:
                r.shutdown()
            except Exception:  # noqa: BLE001 - victim already down
                pass
        pub.shutdown()
        lh.shutdown()

    failovers = _failover_count() - failovers0
    lat.sort()

    def _pct(p: float) -> "Optional[float]":
        if not lat:
            return None
        return round(lat[min(int(len(lat) * p), len(lat) - 1)] * 1000, 1)

    return {
        "servers": SERVING_SERVERS,
        "clients": SERVING_CLIENTS,
        "payload_mb": round(payload_bytes / 2**20, 2),
        "wire": "int8",
        "published_cps": round(published / publish_wall, 2),
        "delivered_total": len(lat),
        "delivered_cps": round(len(lat) / publish_wall, 2),
        "fetch_p50_ms": _pct(0.50),
        "fetch_p99_ms": _pct(0.99),
        "failed_fetches": len(errors),
        "failovers": int(failovers),
        "kill": kill_info,
        "bitwise_identical_after_failover": bitwise_ok,
    }


# ---------------------------------------------------------------------------
# serving depth axis (ISSUE 14): publish->leaf latency, flat vs streaming
# ---------------------------------------------------------------------------

SERVING_DEPTHS = (1, 2, 3)
SERVING_DEPTH_RTTS_MS = (0.0, 10.0, 50.0)
SERVING_DEPTH_GBPS = 0.02       # per-SOURCE uplink (serving/wire.py)
SERVING_DEPTH_BURST_MB = 0.25
SERVING_DEPTH_LEAVES = 8        # == fragments: one leaf per fragment
SERVING_DEPTH_LEAF_ELEMS = 128 * 1024  # 8 x 512 KB fp32 = 4 MB payload
SERVING_DEPTH_PUBLISHES = 4     # measured publishes per config (+1 warm)
SERVING_DEPTH_PARALLEL = 8      # in-flight frag window: overlap all RTTs


def _staged_raw_frags(transport, step: int) -> "Dict[str, bytes]":
    """Raw wire bytes of every ``frag:*`` payload staged at ``step`` —
    the bitwise ground truth both data planes must serve verbatim."""
    from torchft_tpu.checkpointing import serialization as _ser

    out: "Dict[str, bytes]" = {}
    with transport._staged_lock.r_lock(timeout=10.0):
        rec = transport._staged.get(step)
        sd = dict(rec.sd) if rec is not None else {}
    for k, v in sd.items():
        if isinstance(k, str) and k.startswith("frag:"):
            mv = _ser.raw_view(v)
            if mv is not None:
                out[k] = bytes(mv)
    return out


def _serving_depth_trial(
    base: "Dict[str, np.ndarray]", depth: int, stream: bool,
    plane_info: "Optional[Dict[str, Any]]" = None,
    warm_publishes: int = 1,
) -> "Tuple[List[float], List[float]]":
    """One (depth, mode) config: a fanout-1 CHAIN of ``depth`` relays;
    returns (full-change publish->leaf latencies, single-fragment delta
    latencies, publish-stamp staleness at leaf convergence) in seconds.
    publish->leaf = publish() call to the LEAF relay holding the
    version complete.

    When ``plane_info`` is a dict (the native data-plane comparison,
    ISSUE 20), it is filled with acceptance evidence before teardown:
    ``bitwise_payload`` (the leaf's staged fragment bytes == the
    publisher's, byte for byte), ``digest_rejects`` (provenance
    ``mismatch`` hops — a failed fetch the chain had to heal around),
    ``native_fallbacks`` (raw fetches that fell off the native plane
    mid-trial), and the chain-wide native ``serves``/``serve_copies``
    counters proving which plane actually moved the bytes."""
    from torchft_tpu.checkpointing import provenance as _prov
    from torchft_tpu.serving import ServingReplica, WeightPublisher
    from torchft_tpu.utils import flightrecorder as _flightrec

    _prov.PROV.reset()  # per-trial hop ring: versions restart at 1
    fallbacks0 = sum(
        1
        for r in _flightrec.snapshot()
        if r.get("op") == "fragment.native_fallback"
    )
    lh = LighthouseServer(
        min_replicas=1, heartbeat_timeout_ms=3000, quorum_tick_ms=50,
        serving_fanout=1,
    )
    pub = WeightPublisher(
        lh.address(), wire="f32", fragments=len(base),
        heartbeat_interval=0.05,
    )
    reps = [
        ServingReplica(
            lh.address(), replica_id=f"depth{i:02d}", poll_interval=0.02,
            fetch_timeout=60.0, stream=stream,
        )
        for i in range(depth)
    ]
    leaf = reps[-1]
    full: "List[float]" = []
    delta: "List[float]" = []
    stale: "List[float]" = []
    frag_stale: "List[float]" = []
    try:
        # wait for the full chain to form before measuring — and fail
        # LOUDLY if it never does: measuring a shallower tree would
        # silently mislabel the depth axis the headline is judged on
        cl = LighthouseClient(lh.address())
        t_end = time.monotonic() + 20
        while True:
            plan = cl.serving_plan()
            if sorted(n["depth"] for n in plan["nodes"]) == list(
                range(depth)
            ):
                break
            if time.monotonic() > t_end:
                cl.close()
                raise TimeoutError(
                    f"serving depth bench: chain of depth {depth} never "
                    f"formed (plan depths: "
                    f"{sorted(n['depth'] for n in plan['nodes'])})"
                )
            time.sleep(0.05)
        cl.close()

        def _publish_and_wait(state: "Dict[str, np.ndarray]") -> float:
            t0 = time.perf_counter()
            v = pub.publish(state)
            t_dead = time.monotonic() + 120
            while leaf.version() < v:
                if time.monotonic() > t_dead:
                    raise TimeoutError(
                        f"leaf never converged to v{v} "
                        f"(depth={depth} stream={stream})"
                    )
                time.sleep(0.005)
            dt = time.perf_counter() - t0
            # staleness-ledger cell: wall at leaf convergence minus the
            # manifest publish stamp — the publish->leaf measurement the
            # lighthouse's /serving.json staleness_ms rows report live
            v_ms = pub.latest_version_ms()
            if v_ms > 0:
                stale.append(max(time.time() - v_ms / 1e3, 0.0))
            # per-FRAGMENT staleness spread (ISSUE 18): the LAST relay
            # hold per frag id for this version is the deepest node to
            # stage it; its ring stamp minus the manifest publish stamp
            # is that fragment's individual publish->stage staleness
            last_hold: "Dict[str, Dict[str, Any]]" = {}
            for r in _prov.PROV.hop_records():
                if (
                    r.get("op") == "fragment.hold"
                    and r.get("version") == v
                    and r.get("role") == "relay"
                ):
                    last_hold[str(r.get("frag"))] = r
            for r in last_hold.values():
                if int(r.get("version_ms") or 0) > 0:
                    frag_stale.append(
                        max(
                            r["end_ns"] / 1e6 - r["version_ms"], 0.0
                        )
                        / 1e3
                    )
            return dt

        for t in range(SERVING_DEPTH_PUBLISHES + warm_publishes):
            # every leaf changes: the full payload moves each publish
            state = {k: a + np.float32(t + 1) for k, a in base.items()}
            dt = _publish_and_wait(state)
            # warm publishes prime the chain/tree; callers measuring
            # steady-state serving (the native data-plane comparison)
            # warm a full version window so the one-time window-fill
            # transient — fresh buffer allocation + first-touch page
            # faults on every node, in BOTH planes — is excluded
            if t >= warm_publishes:
                full.append(dt)
        for t in range(2):
            # one leaf changes: the delta path moves ~1 fragment/hop
            state["layer0"] = base["layer0"] + np.float32(100 + t)
            delta.append(_publish_and_wait(dict(state)))
        if plane_info is not None:
            # acceptance evidence (ISSUE 20): compare the LEAF's staged
            # fragment bytes against the publisher's for the final
            # version — the relay chain re-serves wire bytes verbatim,
            # so any divergence is a data-plane corruption
            v = leaf.version()
            want = _staged_raw_frags(pub._transport, v)
            got = _staged_raw_frags(leaf._transport, v)
            common = sorted(set(want) & set(got))
            plane_info["bitwise_payload"] = bool(
                len(common) >= len(base)
                and set(want) == set(got)
                and all(want[k] == got[k] for k in common)
            )
            plane_info["digest_rejects"] = sum(
                1
                for r in _prov.PROV.hop_records()
                if r.get("verdict") == "mismatch"
            )
            plane_info["native_fallbacks"] = (
                sum(
                    1
                    for r in _flightrec.snapshot()
                    if r.get("op") == "fragment.native_fallback"
                )
                - fallbacks0
            )
            serves = copies = 0
            for tr in [pub._transport] + [r._transport for r in reps]:
                srv = getattr(tr, "_frag_native", None)
                if srv is not None:
                    c = srv.counters()
                    serves += int(c.get("serves", 0))
                    copies += int(c.get("serve_copies", 0))
            plane_info["native_serves"] = serves
            plane_info["native_serve_copies"] = copies
    finally:
        for r in reps:
            try:
                r.shutdown()
            except Exception:  # noqa: BLE001
                pass
        pub.shutdown()
        lh.shutdown()
    return full, delta, stale, frag_stale


def bench_serving_depth() -> "Dict[str, Any]":
    """The streaming-relay acceptance leg (ISSUE 14): publish->leaf
    propagation latency over a fanout-1 relay CHAIN at depth {1,2,3} x
    simulated WAN RTT {0,10,50} ms, whole-payload store-and-forward
    (``flat``) vs cut-through fragment streaming (``stream``).  Every
    measured publish changes EVERY leaf, so the full payload moves; the
    ``delta`` rows change one leaf, so streaming relays move ~one
    fragment per hop.  Headline: the depth-3 / 50 ms speedup (flat
    store-and-forward costs ~depth x T_payload; cut-through costs
    ~T_payload + depth x T_frag)."""
    import os as _os

    rng = np.random.RandomState(11)
    base = {
        f"layer{i}": rng.randn(SERVING_DEPTH_LEAF_ELEMS).astype(np.float32)
        for i in range(SERVING_DEPTH_LEAVES)
    }
    payload_bytes = sum(a.nbytes for a in base.values())
    prior = {
        k: _os.environ.get(k)
        for k in ("TORCHFT_WIRE_RTT_MS", "TORCHFT_WIRE_GBPS",
                  "TORCHFT_WIRE_BURST_MB", "TORCHFT_TOPOLOGY",
                  "TORCHFT_SERVING_PARALLEL")
    }
    # flat/unset topology: every fetch crosses the WAN boundary; each
    # serving node's uplink is its own token bucket (per-source model)
    _os.environ.pop("TORCHFT_TOPOLOGY", None)
    _os.environ["TORCHFT_WIRE_GBPS"] = str(SERVING_DEPTH_GBPS)
    _os.environ["TORCHFT_WIRE_BURST_MB"] = str(SERVING_DEPTH_BURST_MB)
    # one in-flight slot per fragment: the per-message RTTs of a hop
    # overlap into ~one RTT instead of ceil(F/K) batches
    _os.environ["TORCHFT_SERVING_PARALLEL"] = str(SERVING_DEPTH_PARALLEL)

    def _pcts(lat: "List[float]") -> "Tuple[float, float]":
        lat = sorted(lat)
        p50 = lat[len(lat) // 2]
        return round(p50 * 1e3, 1), round(lat[-1] * 1e3, 1)

    out: "Dict[str, Any]" = {
        "payload_mb": round(payload_bytes / 2**20, 2),
        "fragments": SERVING_DEPTH_LEAVES,
        "gbps_per_uplink": SERVING_DEPTH_GBPS,
        "publishes": SERVING_DEPTH_PUBLISHES,
    }
    try:
        for rtt in SERVING_DEPTH_RTTS_MS:
            _os.environ["TORCHFT_WIRE_RTT_MS"] = str(rtt)
            leg: "Dict[str, Any]" = {}
            for depth in SERVING_DEPTHS:
                flat_full, _, _, _ = _serving_depth_trial(
                    base, depth, False
                )
                stream_full, stream_delta, stream_stale, stream_fstale = (
                    _serving_depth_trial(base, depth, True)
                )
                f50, f99 = _pcts(flat_full)
                s50, s99 = _pcts(stream_full)
                d50, _d99 = _pcts(stream_delta)
                leg[f"d{depth}"] = {
                    "flat_p50_ms": f50, "flat_p99_ms": f99,
                    "stream_p50_ms": s50, "stream_p99_ms": s99,
                    "stream_delta_p50_ms": d50,
                    "stream_speedup_x": round(f50 / max(s50, 1e-9), 2),
                }
                if stream_stale:
                    leg[f"d{depth}"]["stream_staleness_p50_ms"] = _pcts(
                        stream_stale
                    )[0]
                if stream_fstale:
                    # per-fragment staleness spread (ISSUE 18): the
                    # provenance vector's per-frag publish->stage stamps
                    fp50, fmax = _pcts(stream_fstale)
                    leg[f"d{depth}"]["frag_staleness_p50_ms"] = fp50
                    leg[f"d{depth}"]["frag_staleness_max_ms"] = fmax
                log(
                    f"serving depth d={depth} rtt={rtt}ms: flat p50 "
                    f"{f50}ms stream p50 {s50}ms delta p50 {d50}ms"
                )
            out[f"rtt_{int(rtt)}ms"] = leg
        d3 = out.get("rtt_50ms", {}).get("d3", {})
        out["d3_rtt50_speedup_x"] = d3.get("stream_speedup_x")
        out["d3_rtt50_flat_p50_ms"] = d3.get("flat_p50_ms")
        out["d3_rtt50_stream_p50_ms"] = d3.get("stream_p50_ms")
        out["d3_rtt50_delta_p50_ms"] = d3.get("stream_delta_p50_ms")
        out["d3_rtt50_staleness_p50_ms"] = d3.get("stream_staleness_p50_ms")
        out["d3_rtt50_frag_staleness_p50_ms"] = d3.get(
            "frag_staleness_p50_ms"
        )
        out["d3_rtt50_frag_staleness_max_ms"] = d3.get(
            "frag_staleness_max_ms"
        )
        out["winner"] = (
            "stream"
            if (d3.get("stream_speedup_x") or 0) > 1.0
            else "flat"
        )
    finally:
        for k, v in prior.items():
            if v is None:
                _os.environ.pop(k, None)
            else:
                _os.environ[k] = v
    return out


# ---------------------------------------------------------------------------
# native zero-copy fragment data plane (ISSUE 20): native vs python serve
# ---------------------------------------------------------------------------

SERVING_NATIVE_DEPTHS = (3, 4)
SERVING_NATIVE_RTTS_MS = (0.0, 10.0)  # 0 ms = the headline cell; 10 ms
#                                       shows where the WAN re-dominates
SERVING_NATIVE_GBPS = 1.25      # 10 GbE-class uplink: the simulated wire
#                                 is cheap+identical for both planes, so
#                                 the real serve/receive cost shows
SERVING_NATIVE_BURST_MB = 4.0
SERVING_NATIVE_LEAVES = 128     # many small fragments: the per-request
#                                 interpreter overhead the native plane
#                                 eliminates dominates the payload move
SERVING_NATIVE_LEAF_ELEMS = 64 * 1024  # 128 x 256 KB fp32 = 32 MB


def bench_serving_native() -> "Dict[str, Any]":
    """Native zero-copy fragment data plane vs pure-Python serving
    (ISSUE 20): the SAME fanout-1 relay chain as the depth bench, every
    fetch cut-through streamed, run twice per cell — once with
    ``TORCHFT_FRAG_NATIVE=0`` (Python ``BaseHTTPRequestHandler`` serve +
    ``urllib`` receive) and once armed (native writev serve out of
    pooled buffers, GIL-free receive+sha256).  Uplinks are shaped at
    10 GbE class so the (identical) simulated wire charge stays small
    and the measured difference is the data plane itself.  Headline:
    native publish->leaf p99 speedup at depth 3/4, 0 ms RTT — with
    bitwise payload verification and zero failed fetches as hard
    evidence rows, and a striped-heal leg on the same footing."""
    import os as _os

    from torchft_tpu.checkpointing import fragdata as _fragdata

    rng = np.random.RandomState(31)
    base = {
        f"layer{i}": rng.randn(SERVING_NATIVE_LEAF_ELEMS).astype(np.float32)
        for i in range(SERVING_NATIVE_LEAVES)
    }
    payload_bytes = sum(a.nbytes for a in base.values())
    prior = {
        k: _os.environ.get(k)
        for k in ("TORCHFT_WIRE_RTT_MS", "TORCHFT_WIRE_GBPS",
                  "TORCHFT_WIRE_BURST_MB", "TORCHFT_TOPOLOGY",
                  "TORCHFT_SERVING_PARALLEL", "TORCHFT_HEAL_PARALLEL",
                  "TORCHFT_FRAG_NATIVE")
    }
    _os.environ.pop("TORCHFT_TOPOLOGY", None)
    _os.environ["TORCHFT_WIRE_GBPS"] = str(SERVING_NATIVE_GBPS)
    _os.environ["TORCHFT_WIRE_BURST_MB"] = str(SERVING_NATIVE_BURST_MB)
    _os.environ["TORCHFT_SERVING_PARALLEL"] = str(SERVING_DEPTH_PARALLEL)
    _os.environ["TORCHFT_HEAL_PARALLEL"] = str(HEAL_PARALLEL)

    def _pcts(lat: "List[float]") -> "Tuple[float, float]":
        lat = sorted(lat)
        return round(lat[len(lat) // 2] * 1e3, 1), round(lat[-1] * 1e3, 1)

    out: "Dict[str, Any]" = {
        "native_available": _fragdata.available(),
        "payload_mb": round(payload_bytes / 2**20, 2),
        "fragments": SERVING_NATIVE_LEAVES,
        "gbps_per_uplink": SERVING_NATIVE_GBPS,
        "publishes": SERVING_DEPTH_PUBLISHES,
        "warm_publishes": 5,
    }
    if not _fragdata.available():
        out["error"] = "native library unavailable: nothing to compare"
        for k, v in prior.items():
            if v is None:
                _os.environ.pop(k, None)
            else:
                _os.environ[k] = v
        return out
    try:
        for rtt in SERVING_NATIVE_RTTS_MS:
            _os.environ["TORCHFT_WIRE_RTT_MS"] = str(rtt)
            leg: "Dict[str, Any]" = {}
            for depth in SERVING_NATIVE_DEPTHS:
                cell: "Dict[str, Any]" = {}
                for plane in ("python", "native"):
                    _os.environ["TORCHFT_FRAG_NATIVE"] = (
                        "1" if plane == "native" else "0"
                    )
                    _fragdata.reset_port_cache()
                    info: "Dict[str, Any]" = {}
                    # warm a full staged-version window (4) + 1: the
                    # window-fill transient (fresh buffer allocation +
                    # first-touch faults on every node, both planes)
                    # is a one-time cost, not the steady-state serving
                    # regime this cell compares
                    full, _, _, _ = _serving_depth_trial(
                        base, depth, True, plane_info=info,
                        warm_publishes=5,
                    )
                    p50, p99 = _pcts(full)
                    cell[f"{plane}_p50_ms"] = p50
                    cell[f"{plane}_p99_ms"] = p99
                    cell[f"{plane}_bitwise_payload"] = info.get(
                        "bitwise_payload"
                    )
                    # a failed fetch = a digest reject the chain healed
                    # around; leaf convergence itself is the
                    # zero-timeout proof (the trial raises otherwise)
                    cell[f"{plane}_failed_fetches"] = info.get(
                        "digest_rejects"
                    )
                    if plane == "native":
                        cell["native_serves"] = info.get("native_serves")
                        cell["native_serve_copies"] = info.get(
                            "native_serve_copies"
                        )
                        cell["native_fallbacks"] = info.get(
                            "native_fallbacks"
                        )
                cell["native_speedup_p99_x"] = round(
                    cell["python_p99_ms"] / max(cell["native_p99_ms"], 1e-9),
                    2,
                )
                cell["native_speedup_p50_x"] = round(
                    cell["python_p50_ms"] / max(cell["native_p50_ms"], 1e-9),
                    2,
                )
                leg[f"d{depth}"] = cell
                log(
                    f"serving native d={depth} rtt={int(rtt)}ms: python "
                    f"p99 {cell['python_p99_ms']}ms native p99 "
                    f"{cell['native_p99_ms']}ms "
                    f"({cell['native_speedup_p99_x']}x, serves="
                    f"{cell['native_serves']}, copies="
                    f"{cell['native_serve_copies']})"
                )
            out[f"rtt_{int(rtt)}ms"] = leg

        # striped-heal leg on the same footing: one healer pulls the
        # 8 MB heal state striped across 4 sources at 0 ms / 10 GbE,
        # python vs native receive path
        _os.environ["TORCHFT_WIRE_RTT_MS"] = "0"
        rng2 = np.random.RandomState(37)
        heal_state = {
            "user": {
                f"w{i}": rng2.randn(HEAL_LEAF_ELEMS).astype(np.float32)
                for i in range(HEAL_STATE_LEAVES)
            },
            "torchft": {"step": 5, "batches_committed": 10},
        }
        heal_leg: "Dict[str, Any]" = {}
        for plane in ("python", "native"):
            _os.environ["TORCHFT_FRAG_NATIVE"] = (
                "1" if plane == "native" else "0"
            )
            _fragdata.reset_port_cache()
            walls: "List[float]" = []
            for _t in range(HEAL_TRIALS):
                wall, _info = _heal_trial(heal_state, max(HEAL_SOURCES))
                walls.append(wall)
            walls.sort()
            heal_leg[f"{plane}_wall_p50_s"] = round(
                walls[len(walls) // 2], 3
            )
        heal_leg["native_speedup_x"] = round(
            heal_leg["python_wall_p50_s"]
            / max(heal_leg["native_wall_p50_s"], 1e-9),
            2,
        )
        out["heal_stripe"] = heal_leg
        log(
            f"serving native heal stripe: python p50 "
            f"{heal_leg['python_wall_p50_s']}s native p50 "
            f"{heal_leg['native_wall_p50_s']}s "
            f"({heal_leg['native_speedup_x']}x)"
        )

        # headline: the 0 ms cells the acceptance judges
        r0 = out.get("rtt_0ms", {})
        for depth in SERVING_NATIVE_DEPTHS:
            d = r0.get(f"d{depth}", {})
            out[f"d{depth}_rtt0_speedup_p99_x"] = d.get(
                "native_speedup_p99_x"
            )
        d3 = r0.get("d3", {})
        out["bitwise"] = bool(
            d3.get("native_bitwise_payload")
            and d3.get("python_bitwise_payload")
        )
        out["failed_fetches"] = (
            (d3.get("native_failed_fetches") or 0)
            + (d3.get("python_failed_fetches") or 0)
        )
        out["heal_speedup_x"] = heal_leg.get("native_speedup_x")
        out["winner"] = (
            "native"
            if (out.get("d3_rtt0_speedup_p99_x") or 0) > 1.0
            else "python"
        )
    finally:
        for k, v in prior.items():
            if v is None:
                _os.environ.pop(k, None)
            else:
                _os.environ[k] = v
    return out


# ---------------------------------------------------------------------------
# striped multi-source heal (ISSUE 15)
# ---------------------------------------------------------------------------

HEAL_STATE_LEAVES = 16
HEAL_LEAF_ELEMS = 1 << 17  # 16 x 512 KB = 8 MB f32 heal state
HEAL_FRAGMENTS = 16
HEAL_SOURCES = (1, 2, 4)
HEAL_RTTS_MS = (0.0, 10.0, 50.0)
HEAL_GBPS = 0.02  # per-SOURCE uplink: striping aggregates them
HEAL_BURST_MB = 0.25
HEAL_PARALLEL = 4
HEAL_TRIALS = 3


def _heal_trial(
    state: "Dict[str, Any]", n_sources: int,
    local: "Optional[Dict[str, Any]]" = None,
) -> "Tuple[float, Dict[str, Any]]":
    """One striped heal against ``n_sources`` freshly stream-staging
    transports (staging runs CONCURRENTLY with the healer's fetch — the
    cut-through overlap the design claims); returns ``(wall_s, info)``."""
    from torchft_tpu.checkpointing.http_transport import HTTPTransport

    srcs = [HTTPTransport(timeout=60.0) for _ in range(n_sources)]
    healer = HTTPTransport(timeout=60.0)
    threads = [
        threading.Thread(
            target=t.send_checkpoint_streamed,
            args=([1], 5, state, 60.0, HEAL_FRAGMENTS),
            daemon=True,
        )
        for t in srcs
    ]
    try:
        for t in threads:
            t.start()
        t0 = time.perf_counter()
        _got, info = healer.recv_checkpoint_striped(
            [t.metadata() for t in srcs], 5, timeout=120.0,
            local_state_fn=(lambda: local) if local is not None else None,
            delta=local is not None,
        )
        wall = time.perf_counter() - t0
    finally:
        for t in threads:
            t.join(timeout=10)
        healer.shutdown()
        for t in srcs:
            t.shutdown()
    return wall, info


def bench_heal() -> "Dict[str, Any]":
    """Striped multi-source delta heal (ISSUE 15): recovery over the
    fragment plane, measured on shaped links.  One healer pulls an
    8 MB heal state striped across {1, 2, 4} sources at WAN RTT
    {0, 10, 50} ms, each source's uplink its own HEAL_GBPS token bucket
    (Prime CCL's premise: striping aggregates source uplinks).  The
    acceptance row is the 4-source wire-time speedup over single-source
    (>= 1.5x on bandwidth-bound links).  The ``delta`` row rejoins with
    a state differing in ONE leaf: wire bytes must scale with the
    changed-fragment count, not the model."""
    import os as _os

    rng = np.random.RandomState(23)
    state = {
        "user": {
            f"w{i}": rng.randn(HEAL_LEAF_ELEMS).astype(np.float32)
            for i in range(HEAL_STATE_LEAVES)
        },
        "torchft": {"step": 5, "batches_committed": 10},
    }
    payload_bytes = sum(a.nbytes for a in state["user"].values())
    prior = {
        k: _os.environ.get(k)
        for k in ("TORCHFT_WIRE_RTT_MS", "TORCHFT_WIRE_GBPS",
                  "TORCHFT_WIRE_BURST_MB", "TORCHFT_TOPOLOGY",
                  "TORCHFT_HEAL_PARALLEL")
    }
    _os.environ.pop("TORCHFT_TOPOLOGY", None)  # flat: every fetch is WAN
    _os.environ["TORCHFT_WIRE_GBPS"] = str(HEAL_GBPS)
    _os.environ["TORCHFT_WIRE_BURST_MB"] = str(HEAL_BURST_MB)
    _os.environ["TORCHFT_HEAL_PARALLEL"] = str(HEAL_PARALLEL)

    out: "Dict[str, Any]" = {
        "state_mb": round(payload_bytes / 2**20, 2),
        "fragments": HEAL_FRAGMENTS,
        "gbps_per_uplink": HEAL_GBPS,
        "trials": HEAL_TRIALS,
    }
    try:
        for rtt in HEAL_RTTS_MS:
            _os.environ["TORCHFT_WIRE_RTT_MS"] = str(rtt)
            leg: "Dict[str, Any]" = {}
            for n in HEAL_SOURCES:
                walls: "List[float]" = []
                wires: "List[float]" = []
                for _t in range(HEAL_TRIALS):
                    wall, info = _heal_trial(state, n)
                    walls.append(wall)
                    wires.append(info["phases"]["heal_wire"])
                walls.sort()
                wires.sort()
                leg[f"s{n}"] = {
                    "wall_p50_s": round(walls[len(walls) // 2], 3),
                    "wire_p50_s": round(wires[len(wires) // 2], 3),
                }
            for n in HEAL_SOURCES[1:]:
                leg[f"s{n}"]["wire_speedup_x"] = round(
                    leg["s1"]["wire_p50_s"]
                    / max(leg[f"s{n}"]["wire_p50_s"], 1e-9),
                    2,
                )
            out[f"rtt_{int(rtt)}ms"] = leg
            log(
                f"heal rtt={rtt}ms: wire p50 "
                + " ".join(
                    f"s{n}={leg[f's{n}']['wire_p50_s']}s" for n in HEAL_SOURCES
                )
                + f" (s4 speedup {leg['s4'].get('wire_speedup_x')}x)"
            )
        # delta-rejoin row (unshaped RTT, max sources): one changed leaf
        _os.environ["TORCHFT_WIRE_RTT_MS"] = "0"
        local = {
            "user": {k: v.copy() for k, v in state["user"].items()},
            "torchft": {"step": 3, "batches_committed": 6},
        }
        local["user"]["w7"] = local["user"]["w7"] + np.float32(1.0)
        wall, info = _heal_trial(state, max(HEAL_SOURCES), local=local)
        out["delta"] = {
            "wall_s": round(wall, 3),
            "changed_fragments": info["changed"],
            "total_fragments": info["fragments"],
            "wire_bytes": info["wire_bytes"],
            "full_bytes": payload_bytes,
            "bytes_ratio": round(info["wire_bytes"] / payload_bytes, 4),
        }
        log(
            f"heal delta rejoin: {info['changed']}/{info['fragments']} "
            f"fragments, {info['wire_bytes']} B "
            f"({out['delta']['bytes_ratio']:.1%} of full)"
        )
        s4_0 = out.get("rtt_0ms", {}).get("s4", {})
        s4_50 = out.get("rtt_50ms", {}).get("s4", {})
        out["s4_rtt0_speedup_x"] = s4_0.get("wire_speedup_x")
        out["s4_rtt50_speedup_x"] = s4_50.get("wire_speedup_x")
        out["winner"] = (
            "striped" if (s4_0.get("wire_speedup_x") or 0) > 1.0 else "single"
        )
    finally:
        for k, v in prior.items():
            if v is None:
                _os.environ.pop(k, None)
            else:
                _os.environ[k] = v
    return out


# ---------------------------------------------------------------------------
# durable cold restore (ISSUE 17)
# ---------------------------------------------------------------------------

CR_STATE_LEAVES = 16
CR_LEAF_ELEMS = 1 << 17  # 16 x 512 KB = 8 MB f32 restore state
CR_FRAGMENTS = 16
CR_TRIALS = 3
CR_DISKS = (1, 2)


def _dir_bytes(path: str) -> int:
    import os

    total = 0
    for root, _dirs, files in os.walk(path):
        for f in files:
            try:
                total += os.path.getsize(os.path.join(root, f))
            except OSError:
                pass
    return total


def _cold_restore_trial(
    stores: "List[Any]", version: int,
    local: "Optional[Dict[str, Any]]" = None,
) -> "Tuple[float, Dict[str, Any]]":
    """One cold restore against ``stores`` as stripe sources: transports
    with NO RAM staging (the fleet is dead — every ``frag_<name>`` fetch
    is served straight off the attached disk store), reassembled by the
    PR 15 striped fetch path; returns ``(wall_s, info)``."""
    from torchft_tpu.checkpointing.http_transport import HTTPTransport

    srcs = [HTTPTransport(timeout=60.0) for _ in stores]
    for t, s in zip(srcs, stores):
        t.attach_store(s)
    healer = HTTPTransport(timeout=60.0)
    try:
        t0 = time.perf_counter()
        _got, info = healer.recv_checkpoint_striped(
            [t.metadata() for t in srcs], version, timeout=120.0,
            local_state_fn=(lambda: local) if local is not None else None,
            delta=local is not None,
        )
        wall = time.perf_counter() - t0
    finally:
        healer.shutdown()
        for t in srcs:
            t.shutdown()
    return wall, info


def bench_cold_restore() -> "Dict[str, Any]":
    """Durable fragment store (ISSUE 17): spill + whole-fleet cold
    restore off disk.  An 8 MB state is spilled to 2 rank-local stores;
    the headline is the cold-restore wall (disk -> reassembled state)
    striped over {1, 2} disks, plus the spill-side rows the design
    claims: content-addressed DEDUP (respilling an unchanged state
    writes ~0 new blob bytes) and the WARM delta restore (a rejoiner
    whose memory survived fetches only the manifest)."""
    import os
    import shutil
    import tempfile

    from torchft_tpu.checkpointing.store import FragmentStore

    rng = np.random.RandomState(41)
    state = {
        "user": {
            f"w{i}": rng.randn(CR_LEAF_ELEMS).astype(np.float32)
            for i in range(CR_STATE_LEAVES)
        },
        "torchft": {"step": 7, "batches_committed": 14},
    }
    payload_bytes = sum(a.nbytes for a in state["user"].values())
    root = tempfile.mkdtemp(prefix="tft_bench_store_")
    out: "Dict[str, Any]" = {
        "state_mb": round(payload_bytes / 2**20, 2),
        "fragments": CR_FRAGMENTS,
        "trials": CR_TRIALS,
    }
    try:
        stores = [
            FragmentStore(os.path.join(root, f"rank{i}"), max_versions=0)
            for i in range(max(CR_DISKS))
        ]
        # spill row: wall to durably persist one full version per disk
        spill_walls: "List[float]" = []
        for s in stores:
            t0 = time.perf_counter()
            s.put_state(7, state, fragments=CR_FRAGMENTS)
            spill_walls.append(time.perf_counter() - t0)
        spill_walls.sort()
        out["spill"] = {
            "wall_p50_s": round(spill_walls[len(spill_walls) // 2], 3),
            "disk_bytes": _dir_bytes(stores[0].directory),
        }
        # dedup row: respill the SAME state as a newer version — blobs
        # are content-addressed, so only the manifest should hit disk
        before = _dir_bytes(stores[0].directory)
        t0 = time.perf_counter()
        stores[0].put_state(8, state, fragments=CR_FRAGMENTS)
        dedup_wall = time.perf_counter() - t0
        out["dedup"] = {
            "wall_s": round(dedup_wall, 3),
            "new_bytes": _dir_bytes(stores[0].directory) - before,
            "payload_bytes": payload_bytes,
        }
        stores[1].put_state(8, state, fragments=CR_FRAGMENTS)
        # cold-restore rows: striped reassembly with disks as sources
        for n in CR_DISKS:
            walls: "List[float]" = []
            for _t in range(CR_TRIALS):
                wall, info = _cold_restore_trial(stores[:n], 8)
                walls.append(wall)
            walls.sort()
            out[f"d{n}"] = {
                "wall_p50_s": round(walls[len(walls) // 2], 3),
                "sources": n,
            }
            log(
                f"cold restore d{n}: wall p50 "
                f"{out[f'd{n}']['wall_p50_s']}s"
            )
        # warm delta row: local memory survived — only the manifest moves
        local = {
            "user": {k: v.copy() for k, v in state["user"].items()},
            "torchft": dict(state["torchft"]),
        }
        wall, info = _cold_restore_trial(stores[:2], 8, local=local)
        out["warm_delta"] = {
            "wall_s": round(wall, 3),
            "changed_fragments": info["changed"],
            "wire_bytes": info["wire_bytes"],
            "bytes_ratio": round(info["wire_bytes"] / payload_bytes, 4),
        }
        log(
            f"cold restore warm delta: {info['changed']} changed, "
            f"{info['wire_bytes']} B "
            f"({out['warm_delta']['bytes_ratio']:.1%} of full)"
        )
        out["restore_wall_p50_s"] = out["d2"]["wall_p50_s"]
        out["dedup_new_bytes"] = out["dedup"]["new_bytes"]
        out["winner"] = (
            "dedup"
            if out["dedup"]["new_bytes"] < payload_bytes / 10
            else "rewrite"
        )
    finally:
        shutil.rmtree(root, ignore_errors=True)
    return out


COMPACT_SUMMARY_MAX_BYTES = 1500


HA_PEERS = 3
HA_TRIALS = 3
HA_LEASE_MS = 500


HA_RTTS_MS = (0.0, 50.0)


def _ha_failover_trials(n_trials: int, tag: str) -> "Dict[str, Any]":
    """``n_trials`` leader-kill -> next-quorum measurements (one fleet
    per trial); the per-leg body of :func:`bench_ha`."""
    from torchft_tpu.ha import LighthouseFleet

    trials: "List[float]" = []
    monotone = True
    term_advanced = True
    takeover_terms: "List[int]" = []
    for t in range(n_trials):
        fleet = LighthouseFleet(
            n=HA_PEERS, min_replicas=1, lease_timeout_ms=HA_LEASE_MS,
            quorum_tick_ms=50,
        )
        try:
            fleet.wait_for_leader(20)
            cli = LighthouseClient(fleet.addresses(), connect_timeout=5.0)
            try:
                q1 = cli.quorum(f"bench_ha:{tag}{t}a", timeout=15.0)
                t0 = time.monotonic()
                fleet.kill_leader()
                q2 = cli.quorum(f"bench_ha:{tag}{t}b", timeout=30.0)
                trials.append(time.monotonic() - t0)
                monotone = monotone and q2.quorum_id > q1.quorum_id
                term_advanced = term_advanced and (
                    (q2.quorum_id >> 32) > (q1.quorum_id >> 32)
                )
                takeover_terms.append(q2.quorum_id >> 32)
            finally:
                cli.close()
        finally:
            fleet.shutdown()
    trials.sort()
    return {
        "trials": len(trials),
        "kill_to_quorum_p50_s": round(trials[len(trials) // 2], 3),
        "kill_to_quorum_max_s": round(trials[-1], 3),
        "kill_to_quorum_s": [round(x, 3) for x in trials],
        "quorum_id_monotone": monotone,
        "term_advanced": term_advanced,
        "takeover_terms": takeover_terms,
    }


def bench_ha() -> "Dict[str, Any]":
    """Coordination-plane HA failover: HA_PEERS in-process lighthouse
    peers with leased leadership; a replica-group stub quorums through
    the endpoint-list client, the LEADER is killed, and the headline is
    leader-kill -> next formed quorum latency (the coordination-plane
    twin of the recovery metric).  Also asserts what the chaos tests
    assert: quorum_id strictly monotone with an advancing term word.

    WAN-shaped legs (ISSUE 14 satellite, the PR 13 carry-over): the
    sweep re-runs the measurement with ``TORCHFT_WIRE_RTT_MS`` in
    HA_RTTS_MS and ``TORCHFT_WIRE_RPC=1``, pricing one first-byte RTT on
    every Python coordination RPC round trip — the client-visible share
    of lease/election cost under WAN (the native peers' own lease
    exchanges are in-process and unshaped; docs/observability.md
    ``TORCHFT_WIRE_RPC``).  docs/architecture.md "Coordination-plane
    HA"."""
    import os as _os

    prior = {
        k: _os.environ.get(k)
        for k in ("TORCHFT_WIRE_RTT_MS", "TORCHFT_WIRE_RPC",
                  "TORCHFT_TOPOLOGY")
    }
    _os.environ.pop("TORCHFT_TOPOLOGY", None)  # flat: every RPC is WAN
    _os.environ["TORCHFT_WIRE_RPC"] = "1"
    wan: "Dict[str, Any]" = {}
    try:
        for rtt in HA_RTTS_MS:
            _os.environ["TORCHFT_WIRE_RTT_MS"] = str(rtt)
            n = HA_TRIALS if rtt == 0.0 else max(HA_TRIALS - 1, 1)
            wan[f"rtt_{int(rtt)}ms"] = _ha_failover_trials(
                n, f"r{int(rtt)}_"
            )
            log(
                f"ha failover rtt={rtt}ms: p50 "
                f"{wan[f'rtt_{int(rtt)}ms']['kill_to_quorum_p50_s']}s"
            )
    finally:
        for k, v in prior.items():
            if v is None:
                _os.environ.pop(k, None)
            else:
                _os.environ[k] = v
    base = wan.get("rtt_0ms", {})
    return {
        "peers": HA_PEERS,
        "lease_ms": HA_LEASE_MS,
        **base,
        "wan": {
            leg: {
                "kill_to_quorum_p50_s": d.get("kill_to_quorum_p50_s"),
                "kill_to_quorum_max_s": d.get("kill_to_quorum_max_s"),
            }
            for leg, d in sorted(wan.items())
        },
    }


def links_summary() -> "Optional[Dict[str, Any]]":
    """Distill this process's passive link-state registry (ISSUE 16)
    into a handful of fleet-health cells: tracked pair count, matrix
    version, the worst WAN link by goodput, and the worst observed RTT
    tail.  The registry fills as a side effect of the shaped legs (WAN
    sweep, striped heal, relay depth) — no probe traffic of its own.
    Returns None when nothing was recorded (e.g. a CPU-only quick leg)."""
    from torchft_tpu.utils import linkstats

    matrix = linkstats.LINKS.snapshot()
    if not matrix.entries:
        return None
    out: "Dict[str, Any]" = {
        "pairs": len(matrix.entries),
        "version": matrix.version,
    }
    wan = [
        s for s in matrix.entries
        if not s.local and s.goodput_bps > 0
    ]
    if wan:
        worst = min(wan, key=lambda s: s.goodput_bps)
        out["worst_wan_goodput_bps"] = round(worst.goodput_bps)
        out["worst_wan_link"] = f"{worst.peer}/{worst.plane}"
    tails = [s.rtt_p99_ms for s in matrix.entries if s.rtt_p99_ms > 0]
    if tails:
        out["rtt_p99_max_ms"] = round(max(tails), 3)
    return out


def compact_summary(result: "Dict[str, Any]") -> "Dict[str, Any]":
    """Distill the full bench result into one < 1.5 KB JSON line: the
    primary recovery metric + cycle medians, overhead + cross-check
    verdict, MFU, and the DiLoCo winners table.  Degrades field by field
    (never errors) so a partially failed run still tails its primary
    metric."""
    model = result.get("model") or {}
    diloco = result.get("diloco") or {}
    crosscheck = result.get("crosscheck") or {}
    phases = result.get("recovery_phases_ms") or {}
    top_phases = dict(
        sorted(phases.items(), key=lambda kv: -abs(kv[1]))[:4]
    )
    winners = {
        gbps: {
            "winner": leg.get("winner"),
            "int8_speedup_x": leg.get("int8_speedup_x"),
        }
        for gbps, leg in sorted((diloco.get("shaped") or {}).items())
        if isinstance(leg, dict)
    }
    wan = result.get("wan") or {}
    wan_winners = {
        key: {
            "winner": leg.get("winner"),
            "hier_speedup_x": leg.get("hier_speedup_x"),
        }
        for key, leg in sorted(wan.items())
        if isinstance(leg, dict) and key.startswith("rtt_")
    }
    # per-hop wire telemetry of the highest-RTT hierarchical leg — the
    # acceptance surface (hier must beat flat at 50 ms, hops visible)
    wan_hops = (
        (wan.get("rtt_50ms") or {}).get("hier_hop_wire_s")
        if isinstance(wan.get("rtt_50ms"), dict)
        else None
    )
    switch = result.get("switch") or {}
    serving = result.get("serving") or {}
    ha = result.get("ha") or {}
    ha_compact = {
        k: ha.get(k)
        for k in (
            "kill_to_quorum_p50_s",
            "kill_to_quorum_max_s",
            "lease_ms",
            "quorum_id_monotone",
            "term_advanced",
        )
        if ha.get(k) is not None
    } or None
    # WAN-shaped HA legs (ISSUE 14 satellite): kill->quorum p50 per RTT
    ha_wan = {
        leg: d.get("kill_to_quorum_p50_s")
        for leg, d in sorted((ha.get("wan") or {}).items())
        if isinstance(d, dict)
    }
    if ha_compact is not None and ha_wan:
        ha_compact["wan_p50_s"] = ha_wan
    heal = result.get("heal") or {}
    heal_compact = {
        k: heal.get(k)
        for k in ("s4_rtt0_speedup_x", "s4_rtt50_speedup_x", "winner")
        if heal.get(k) is not None
    }
    if isinstance(heal.get("delta"), dict):
        heal_compact["delta_changed"] = heal["delta"].get(
            "changed_fragments"
        )
        heal_compact["delta_bytes_ratio"] = heal["delta"].get("bytes_ratio")
    heal_compact = heal_compact or None
    cr = result.get("cold_restore") or {}
    cold_restore_compact = {
        k: cr.get(k)
        for k in ("restore_wall_p50_s", "dedup_new_bytes", "winner")
        if cr.get(k) is not None
    }
    if isinstance(cr.get("warm_delta"), dict):
        cold_restore_compact["warm_bytes_ratio"] = cr["warm_delta"].get(
            "bytes_ratio"
        )
    cold_restore_compact = cold_restore_compact or None
    sdepth = result.get("serving_depth") or {}
    serving_depth_compact = {
        k: sdepth.get(k)
        for k in (
            "d3_rtt50_speedup_x",
            "d3_rtt50_flat_p50_ms",
            "d3_rtt50_stream_p50_ms",
            "d3_rtt50_delta_p50_ms",
            "winner",
        )
        if sdepth.get(k) is not None
    } or None
    # native data-plane headline (ISSUE 20): native-vs-python p99
    # speedup at the 0 ms cells + the bitwise / failed-fetch evidence
    snative = result.get("serving_native") or {}
    native_compact = {
        k: snative.get(k)
        for k in (
            "d3_rtt0_speedup_p99_x",
            "d4_rtt0_speedup_p99_x",
            "heal_speedup_x",
            "bitwise",
            "failed_fetches",
            "winner",
        )
        if snative.get(k) is not None
    } or None
    # fragment-provenance headline (ISSUE 18): per-fragment staleness
    # spread at the deepest WAN leg of the streaming-relay bench
    fragments_compact = {
        key: sdepth.get(src)
        for key, src in (
            ("stale_p50_ms", "d3_rtt50_frag_staleness_p50_ms"),
            ("stale_max_ms", "d3_rtt50_frag_staleness_max_ms"),
        )
        if sdepth.get(src) is not None
    } or None
    serving_compact = {
        k: serving.get(k)
        for k in (
            "published_cps",
            "delivered_cps",
            "fetch_p50_ms",
            "fetch_p99_ms",
            "failovers",
            "failed_fetches",
            "bitwise_identical_after_failover",
        )
        if serving.get(k) is not None
    } or None
    out: "Dict[str, Any]" = {
        "compact": True,
        "metric": result.get("metric", "recovery_to_healthy_step_latency"),
        "unit": result.get("unit", "s"),
        "value": result.get("value"),
        "vs_baseline": result.get("vs_baseline"),
        # online-parallelism-switch latency (kill -> fleet-synchronous
        # layout commit) next to the recovery headline it complements
        "switch_latency_s": switch.get("latency_s"),
        "switch": {
            k: switch.get(k)
            for k in ("reshard_s", "layout_commit_s", "detect_s",
                      "reshard_bytes", "layout")
            if switch.get(k) is not None
        } or None,
        "recovery_cycles_s": result.get("recovery_cycles_s"),
        "recovery_phases_ms_top": top_phases,
        "overhead_pct": result.get("overhead_pct"),
        "model_overhead_pct": result.get("model_overhead_pct"),
        "crosscheck": {
            "converged_2pts": crosscheck.get("converged_2pts"),
            "gap_pts": crosscheck.get("gap_pts"),
            "noise_floor_bound": crosscheck.get("noise_floor_bound"),
        },
        "mfu_pct": model.get("mfu_pct"),
        "step_ms": model.get("step_ms"),
        "diloco_winners": winners,
        "diloco_wire_reduction_x": diloco.get("wire_reduction_x"),
        # serving-tier headline (ISSUE 12): sustained checkpoints/sec +
        # p99 fetch under churn + the post-failover bitwise verdict
        "serving": serving_compact,
        # streaming-relay headline (ISSUE 14): publish->leaf at depth 3 /
        # 50 ms RTT, cut-through vs store-and-forward + the delta row
        "serving_depth": serving_depth_compact,
        # native data-plane headline (ISSUE 20): zero-copy serve +
        # GIL-free receive vs the pure-Python path on the same chain
        "native": native_compact,
        # coordination-plane HA headline (ISSUE 13): leader-kill -> next
        # formed quorum latency + the monotonicity verdicts
        "ha": ha_compact,
        # striped-heal headline (ISSUE 15): 4-source wire-time speedup
        # over single-source on shaped links + the delta-rejoin row
        "heal": heal_compact,
        # durable-store headline (ISSUE 17): cold-restore wall off 2
        # disks + the content-addressed dedup and warm-delta verdicts
        "cold_restore": cold_restore_compact,
        # link-state headline (ISSUE 16): pairs the passive registry
        # tracked + the worst WAN link it singled out
        "links": result.get("links"),
        # staleness-ledger headline (ISSUE 16): publish->leaf staleness
        # at depth 3 / 50 ms RTT from the streaming-relay leg
        "staleness": sdepth.get("d3_rtt50_staleness_p50_ms"),
        # fragment-provenance headline (ISSUE 18): per-fragment
        # staleness spread (p50/max) on the same leg
        "fragments": fragments_compact,
        "wan": wan_winners,
        "wan_hops_50ms": wan_hops,
        # per-leg dominant-ledger-contributor (torchft_tpu/diagnose.py
        # PHASE_CATEGORY vocabulary): which cost category ate each leg
        "dominant": {
            k: v
            for k, v in {
                "recovery": result.get("recovery_dominant"),
                "overhead": result.get("overhead_dominant"),
                "switch": switch.get("dominant"),
                **{
                    f"diloco.{leg}": legd.get("dominant")
                    for leg, legd in sorted(diloco.items())
                    if isinstance(legd, dict) and legd.get("dominant")
                },
            }.items()
            if v
        },
    }
    if "error" in result:
        out["error"] = str(result["error"])[:200]
    # Enforce the byte budget structurally: drop the least essential
    # fields first rather than shipping an unparseable truncation.
    droppable = [
        "diloco_wire_reduction_x", "step_ms", "wan_hops_50ms",
        "switch", "diloco_winners", "dominant", "crosscheck",
        "recovery_phases_ms_top", "recovery_cycles_s", "wan",
        "links", "staleness", "fragments", "ha", "serving",
        "serving_depth", "native", "heal", "cold_restore",
    ]
    while (
        len(json.dumps(out).encode()) > COMPACT_SUMMARY_MAX_BYTES and droppable
    ):
        out.pop(droppable.pop(0), None)
    return out


def last_json_line(text: str) -> "Dict[str, Any]":
    """Parse the last complete JSON line of a captured emission tail —
    exactly what the driver's 2000-byte tail parser needs to do.  A
    truncated first line (the tail window cutting into the full result
    line) is skipped, not fatal."""
    for line in reversed(text.strip().splitlines()):
        line = line.strip()
        if not line:
            continue
        try:
            obj = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(obj, dict):
            return obj
    raise ValueError("no parseable JSON line in tail")


# ---------------------------------------------------------------------------


def main() -> None:
    # Opt-in live scrape surface for long runs: TORCHFT_METRICS_PORT serves
    # the telemetry registry (phase histograms, abort/heal counters) this
    # bench's Managers populate — watchable mid-run alongside the
    # non-destructive phase_times() snapshots the estimators diff.
    from torchft_tpu.utils import metrics as _metrics
    from torchft_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    _metrics.maybe_serve_from_env()
    if "--serving" in sys.argv:
        # `make bench-serving`: the weight-serving churn leg alone, with
        # the compact tail (same last-line contract as the full run)
        serving = bench_serving()
        result = {"metric": "serving_fanout_under_churn", "serving": serving}
        print(json.dumps(result), flush=True)
        print(json.dumps(compact_summary(result)), flush=True)
        return
    if "--serving-depth" in sys.argv:
        # `make bench-serving-depth`: the streaming-relay depth axis
        # alone (flat vs cut-through publish->leaf at depth x RTT), with
        # the compact tail (same last-line contract as the full run)
        sdepth = bench_serving_depth()
        result = {
            "metric": "serving_publish_to_leaf_latency",
            "serving_depth": sdepth,
            "links": links_summary(),
        }
        print(json.dumps(result), flush=True)
        print(json.dumps(compact_summary(result)), flush=True)
        return
    if "--serving-native" in sys.argv:
        # `make bench-serving-native`: the native-vs-python fragment
        # data-plane comparison alone (zero-copy serve + GIL-free
        # receive vs pure Python on the same cut-through chain, plus
        # the striped-heal leg), with the compact tail (same last-line
        # contract as the full run)
        snative = bench_serving_native()
        result = {
            "metric": "native_data_plane_speedup",
            "serving_native": snative,
            "links": links_summary(),
        }
        print(json.dumps(result), flush=True)
        print(json.dumps(compact_summary(result)), flush=True)
        return
    if "--heal" in sys.argv:
        # `make bench-heal`: the striped multi-source heal leg alone
        # (stripe sources x RTT on shaped per-source uplinks + the
        # delta-rejoin row), with the compact tail (same last-line
        # contract as the full run)
        heal = bench_heal()
        result = {
            "metric": "striped_heal_wire_time",
            "heal": heal,
            "links": links_summary(),
        }
        print(json.dumps(result), flush=True)
        print(json.dumps(compact_summary(result)), flush=True)
        return
    if "--cold-restore" in sys.argv:
        # `make bench-cold-restore`: the durable-store leg alone (spill,
        # dedup, disk-striped cold restore, warm delta), with the
        # compact tail (same last-line contract as the full run)
        cr = bench_cold_restore()
        result = {
            "metric": "cold_restore_wall_time",
            "cold_restore": cr,
        }
        print(json.dumps(result), flush=True)
        print(json.dumps(compact_summary(result)), flush=True)
        return
    if "--ha-failover" in sys.argv:
        # `make bench-ha`: the coordination-plane failover leg alone
        # (incl. the WAN-shaped RTT legs), with the compact tail (same
        # last-line contract as the full run)
        ha = bench_ha()
        result = {"metric": "ha_leader_failover", "ha": ha}
        print(json.dumps(result), flush=True)
        print(json.dumps(compact_summary(result)), flush=True)
        return
    if "--wan" in sys.argv:
        # `make bench-wan`: the RTT sweep alone, with the compact tail
        # (same last-line contract as the full run)
        wan = bench_wan(None)
        result = {
            "metric": "wan_rtt_sweep",
            "wan": wan,
            "links": links_summary(),
        }
        print(json.dumps(result), flush=True)
        print(json.dumps(compact_summary(result)), flush=True)
        return
    # The full run has chip-touching legs: refuse up front rather than
    # spend minutes on host legs and then fail (or, worse, file a
    # stand-in).  The host-only legs each run alone under their flag.
    import jax

    device = _require_tpu(
        "the full bench (host-only legs: --serving --serving-depth "
        "--serving-native --heal --cold-restore --ha-failover --wan)"
    )
    recovery = bench_recovery()
    # switch latency (ISSUE 11): the membership-change twin of recovery
    # latency — a shrink triggers a live re-shard instead of a restart.
    # Degrades to an error field like every secondary bench.
    try:
        switch = bench_switch()
    except Exception as e:  # noqa: BLE001
        log(f"switch bench failed: {e!r}")
        switch = {"error": repr(e)}
    # Insurance against an external wall-cap killing the process mid-run:
    # emit a parseable JSON line with the PRIMARY metric as soon as it
    # exists.  A completed run prints the full line at the end (later on
    # stdout, so a tail-parser picks it up); a killed run still leaves
    # this one.
    print(
        json.dumps(
            {
                "metric": "recovery_to_healthy_step_latency",
                "unit": "s",
                "vs_baseline": round(recovery["value"] / 1.0, 3),
                **recovery,
                "preliminary": True,
            }
        ),
        flush=True,
    )
    # A failed HOST-ONLY secondary leg degrades to an "error" field: the
    # primary metric line above is already out.
    try:
        overhead = bench_overhead()
    except Exception as e:  # noqa: BLE001
        log(f"overhead bench failed: {e!r}")
        overhead = {"overhead_error": repr(e)}
    try:
        overhead["crosscheck"] = bench_overhead_crosscheck()
    except Exception as e:  # noqa: BLE001
        log(f"overhead cross-check failed: {e!r}")
        overhead["crosscheck"] = {"error": repr(e)}
    # chip-touching legs: a failure here fails the run (no except)
    model: "Dict[str, Any]" = bench_model()
    diloco = bench_diloco(model["step_ms"])
    try:
        diloco.update(
            bench_diloco_vs_ddp(overhead.get("nonft_step_ms") or 50.0)
        )
    except Exception as e:  # noqa: BLE001
        log(f"diloco-vs-ddp bench failed: {e!r}")
        diloco["vs_ddp_error"] = repr(e)
    try:
        # the measured version of "on real DCN the sign flips": both twins
        # under the 0.5 GB/s egress shaper — DDP pays the wire every step
        diloco["vs_ddp_shaped_0p5gbps"] = bench_diloco_vs_ddp(
            1e9, gbps=0.5
        )
    except Exception as e:  # noqa: BLE001
        log(f"shaped diloco-vs-ddp bench failed: {e!r}")
        diloco["vs_ddp_shaped_0p5gbps"] = {"error": repr(e)}
    try:
        wan = bench_wan(model["step_ms"])
    except Exception as e:  # noqa: BLE001
        log(f"wan bench failed: {e!r}")
        wan = {"error": repr(e)}
    try:
        # the "millions of users" axis: fan-out weight serving under
        # churn (chaos kills a tree node mid-fetch)
        serving = bench_serving()
    except Exception as e:  # noqa: BLE001
        log(f"serving bench failed: {e!r}")
        serving = {"error": repr(e)}
    try:
        # streaming-relay depth axis (ISSUE 14): publish->leaf flat vs
        # cut-through at depth {1,2,3} x RTT {0,10,50} ms
        serving_depth = bench_serving_depth()
    except Exception as e:  # noqa: BLE001
        log(f"serving depth bench failed: {e!r}")
        serving_depth = {"error": repr(e)}
    try:
        # native data-plane comparison (ISSUE 20): zero-copy serve +
        # GIL-free receive vs the pure-Python path on the same chain
        serving_native = bench_serving_native()
    except Exception as e:  # noqa: BLE001
        log(f"serving native bench failed: {e!r}")
        serving_native = {"error": repr(e)}
    try:
        # coordination-plane HA: leader-kill -> next-quorum latency over
        # a replicated lighthouse (ISSUE 13)
        ha = bench_ha()
    except Exception as e:  # noqa: BLE001
        log(f"ha bench failed: {e!r}")
        ha = {"error": repr(e)}
    try:
        # striped multi-source heal (ISSUE 15): recovery over the
        # fragment plane — stripe sources x RTT + the delta-rejoin row
        heal = bench_heal()
    except Exception as e:  # noqa: BLE001
        log(f"heal bench failed: {e!r}")
        heal = {"error": repr(e)}
    result = {
        "metric": "recovery_to_healthy_step_latency",
        "unit": "s",
        "device": {
            "platform": device.platform,
            "kind": device.device_kind,
            "count": len(jax.devices()),
        },
        "vs_baseline": round(recovery["value"] / 1.0, 3),
        **recovery,
        **overhead,
        "model_overhead_pct": model["ft"]["model_overhead_pct"],
        "model": model,
        "diloco": diloco,
        "wan": wan,
        "switch": switch,
        "serving": serving,
        "serving_depth": serving_depth,
        "serving_native": serving_native,
        "ha": ha,
        "heal": heal,
        # passive link-state registry distilled (ISSUE 16): fills as a
        # side effect of the shaped legs above, no probe traffic
        "links": links_summary(),
    }
    print(json.dumps(result), flush=True)
    # LAST line, always < 1500 bytes: the driver's 2000-byte stdout tail
    # must carry the primary metric no matter how large the full result
    # line grew (VERDICT r5 #2 — r5's number was truncated out).
    print(json.dumps(compact_summary(result)), flush=True)


if __name__ == "__main__":
    main()
