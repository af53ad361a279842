"""Integration tests: threads-as-replicas with a real coordination stack.

The reference's central testing trick (reference:
torchft/manager_integ_test.py:179-359): each replica group is a thread with
its own Manager + store + PG; one real LighthouseServer binds port 0.
Fault injection goes through the production chaos layer
(``torchft_tpu.utils.faults`` — the same registry ``TORCHFT_FAULTS``
configures in deployments), NOT a test-local injector: the reference's
EventInjector/FakeProcessGroupWrapper pattern is superseded so integration
tests and production share one injection mechanism.  Recovery must make
state dicts converge **bitwise** across replicas (reference :361-362) —
the zero-contribution allreduce hands the healer the same averaged
gradients the participants applied, so one step after healing everyone is
identical.
"""

import threading
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np
import pytest

from torchft_tpu.coordination import LighthouseServer
from torchft_tpu.manager import Manager
from torchft_tpu.parallel.process_group import ProcessGroupTCP
from torchft_tpu.utils import faults
from torchft_tpu.utils.faults import FaultRule, InjectedFault


@pytest.fixture(autouse=True)
def clean_faults():
    """Every test starts and ends with an empty chaos schedule (the
    registry is process-wide by design)."""
    faults.FAULTS.configure([], seed=0)
    yield
    faults.FAULTS.configure([])


def fail_at(replica: int, step: int) -> FaultRule:
    """Replica-crash rule: ``train.step`` raises in the training loop of
    ``replica_<replica>`` at ``step`` — the Runner treats it as a process
    death and restarts (the EventInjector.fail_at analog)."""
    return FaultRule(site="train.step", replica=f"replica_{replica}", step=step)


def fail_allreduce_at(replica: int, step: int) -> FaultRule:
    """Collective-failure rule: ``pg.allreduce`` fails inside
    ``Manager.allreduce`` — latched via report_error, the step aborts
    cleanly and the quorum re-forms (the fail_allreduce_at analog)."""
    return FaultRule(site="pg.allreduce", replica=f"replica_{replica}", step=step)


@dataclass
class Runner:
    """One replica group (single local rank) running a toy DDP loop.

    ``pgs``: optional shared sink every created ProcessGroup is appended
    to — the chaos suite's watchdog aborts them on deadline expiry.
    """

    replica_id: int
    lighthouse_addr: str
    total_steps: int = 5
    min_replica_size: int = 1
    use_async_quorum: bool = True
    attempts: int = 3
    lr: float = 0.1
    state_history: "List[dict]" = field(default_factory=list)
    pgs: "Optional[List[ProcessGroupTCP]]" = None

    def run(self) -> dict:
        last_exc: "Optional[BaseException]" = None
        for attempt in range(self.attempts):
            try:
                return self._train(attempt)
            except InjectedFault as e:
                last_exc = e
                continue
        raise RuntimeError(f"replica {self.replica_id} exhausted attempts") from last_exc

    def _train(self, attempt: int) -> dict:
        # Toy model: params w; deterministic "gradient" = f(step). Fresh
        # params each (re)start — healing must restore them.
        params = {"w": np.zeros(4, dtype=np.float32)}
        momentum = {"w": np.zeros(4, dtype=np.float32)}

        def load_state_dict(sd):
            params["w"] = np.array(sd["params"]["w"])
            momentum["w"] = np.array(sd["momentum"]["w"])

        def state_dict():
            return {
                "params": {"w": params["w"].copy()},
                "momentum": {"w": momentum["w"].copy()},
            }

        pg = ProcessGroupTCP(timeout=10.0)
        if self.pgs is not None:
            self.pgs.append(pg)
        manager = Manager(
            pg=pg,
            min_replica_size=self.min_replica_size,
            load_state_dict=load_state_dict,
            state_dict=state_dict,
            lighthouse_addr=self.lighthouse_addr,
            replica_id=f"replica_{self.replica_id}",
            group_rank=0,
            group_world_size=1,
            use_async_quorum=self.use_async_quorum,
            timeout=20.0,
            quorum_timeout=20.0,
        )
        try:
            while manager.current_step() < self.total_steps:
                step = manager.current_step()
                # production injection point for replica-crash chaos: a
                # scheduled train.step fault raises InjectedFault here
                faults.check(
                    "train.step", replica=f"replica_{self.replica_id}", step=step
                )

                manager.start_quorum()
                # deterministic per-step pseudo-gradient, same on every
                # replica so DDP averaging is an identity check
                grads = {
                    "w": np.full(4, float(step + 1), dtype=np.float32)
                    * (1.0 + 0.5 * self.replica_id)
                }
                avg_grads = manager.allreduce(grads).wait(timeout=30)
                if manager.should_commit():
                    momentum["w"] = 0.9 * momentum["w"] + avg_grads["w"]
                    params["w"] = params["w"] - self.lr * momentum["w"]
                    self.state_history.append(
                        {"step": manager.current_step(), "w": params["w"].copy()}
                    )
            return {
                "replica_id": self.replica_id,
                "state_dict": state_dict(),
                "manager_state": manager.state_dict(),
            }
        finally:
            manager.shutdown()


def run_replicas(runners: "List[Runner]") -> "List[dict]":
    with ThreadPoolExecutor(max_workers=len(runners)) as ex:
        futures = [ex.submit(r.run) for r in runners]
        return [f.result(timeout=120) for f in futures]


@pytest.fixture
def lighthouse():
    server = LighthouseServer(
        min_replicas=2, join_timeout_ms=100, heartbeat_timeout_ms=1000
    )
    yield server
    server.shutdown()


def assert_bitwise_equal(results):
    base = results[0]["state_dict"]
    for other in results[1:]:
        np.testing.assert_array_equal(
            base["params"]["w"], other["state_dict"]["params"]["w"]
        )
        np.testing.assert_array_equal(
            base["momentum"]["w"], other["state_dict"]["momentum"]["w"]
        )


class TestDDPInteg:
    def test_ddp_healthy(self, lighthouse):
        runners = [
            Runner(i, lighthouse.address(), total_steps=4, min_replica_size=2)
            for i in range(2)
        ]
        results = run_replicas(runners)
        assert faults.FAULTS.injected() == 0
        assert all(r["manager_state"]["step"] == 4 for r in results)
        # 2 participants x 4 steps
        assert all(r["manager_state"]["batches_committed"] == 8 for r in results)
        assert_bitwise_equal(results)

    @pytest.mark.parametrize("use_async", [True, False])
    def test_ddp_recovery(self, lighthouse, use_async):
        faults.FAULTS.configure([fail_at(replica=1, step=2)])
        runners = [
            Runner(
                i,
                lighthouse.address(),
                total_steps=5,
                min_replica_size=1,
                use_async_quorum=use_async,
            )
            for i in range(2)
        ]
        results = run_replicas(runners)
        assert faults.FAULTS.injected() == 1
        assert faults.FAULTS.counts() == {("train.step", "raise"): 1}
        assert all(r["manager_state"]["step"] == 5 for r in results)
        assert_bitwise_equal(results)

    def test_ddp_allreduce_failure_recovers(self, lighthouse):
        faults.FAULTS.configure([fail_allreduce_at(replica=1, step=1)])
        runners = [
            Runner(i, lighthouse.address(), total_steps=4, min_replica_size=1)
            for i in range(2)
        ]
        results = run_replicas(runners)
        assert faults.FAULTS.injected() == 1
        assert faults.FAULTS.counts() == {("pg.allreduce", "raise"): 1}
        assert all(r["manager_state"]["step"] == 4 for r in results)
        assert_bitwise_equal(results)

    def test_multi_replica_recovery(self, lighthouse):
        # two different replicas die at different steps
        faults.FAULTS.configure([fail_at(1, 1), fail_at(2, 2)])
        runners = [
            Runner(i, lighthouse.address(), total_steps=5, min_replica_size=1)
            for i in range(3)
        ]
        results = run_replicas(runners)
        assert faults.FAULTS.injected() == 2
        assert all(r["manager_state"]["step"] == 5 for r in results)
        assert_bitwise_equal(results)


class TestEventExport:
    def test_events_file_written_on_replica_kill(self, lighthouse, tmp_path, monkeypatch):
        """The persistent JSONL sink (TORCHFT_EVENTS_FILE) must capture the
        quorum churn, the injected fault, and the post-heal commits of a
        replica-kill run — the crash-durable analog of the reference's OTLP
        exporter (reference torchft/otel.py:42-86)."""
        import json

        events_file = tmp_path / "events.jsonl"
        monkeypatch.setenv("TORCHFT_EVENTS_FILE", str(events_file))

        faults.FAULTS.configure([fail_at(replica=1, step=2)])
        runners = [
            Runner(i, lighthouse.address(), total_steps=5, min_replica_size=1)
            for i in range(2)
        ]
        results = run_replicas(runners)
        assert faults.FAULTS.injected() == 1
        assert_bitwise_equal(results)

        lines = events_file.read_text().strip().splitlines()
        events = [json.loads(line) for line in lines]
        kinds = {e["kind"] for e in events}
        assert "quorum" in kinds and "commit" in kinds
        # the chaos layer writes its injection as a structured event too
        assert any(
            e["kind"] == "fault" and e.get("site") == "train.step" for e in events
        )
        # quorum changed at least twice: initial formation + post-kill rejoin
        assert sum(1 for e in events if e["kind"] == "quorum") >= 2
        # the killed replica's post-heal commits are present
        assert any(
            e["kind"] == "commit" and str(e.get("replica_id", "")).startswith("replica_1")
            for e in events
        )
        # every record carries the structured context fields and a timestamp
        for e in events:
            assert {"ts", "kind", "message", "replica_id", "step"} <= set(e)

    def test_events_file_rotation(self, tmp_path, monkeypatch):
        from torchft_tpu.utils.logging import log_event

        events_file = tmp_path / "ring.jsonl"
        monkeypatch.setenv("TORCHFT_EVENTS_FILE", str(events_file))
        monkeypatch.setenv("TORCHFT_EVENTS_MAX_BYTES", "2000")
        for i in range(100):
            log_event("commit", "x" * 50, replica_id="r", rank=0, step=i)
        assert events_file.exists()
        rotated = events_file.with_name(events_file.name + ".1")
        assert rotated.exists()
        assert events_file.stat().st_size <= 2000 + 200


class TestFixedWithSpares:
    def test_spare_computes_zero_contributes_then_promoted(self, lighthouse):
        """FIXED_WITH_SPARES end to end (reference torchft/manager.py:112-127
        semantics; VERDICT r4 item 4): with 3 replica groups and
        min_replica_size=2, the world is capped at 2 — the 3rd replica is a
        hot spare that computes every step but contributes zeros and holds
        no participating rank; averages divide by 2 and exclude the spare's
        gradients.  When a participant dies, the spare is promoted within
        one quorum, and survivors converge bitwise."""
        from torchft_tpu.manager import WorldSizeMode

        TOTAL, KILL_AT = 10, 5
        results: "Dict[int, dict]" = {}
        errors: "Dict[int, BaseException]" = {}
        # replica_id -> list of (committed_step, participating, num_participants)
        participation: "Dict[int, list]" = {0: [], 1: [], 2: []}
        avg_samples: "Dict[int, dict]" = {0: {}, 1: {}, 2: {}}

        def run(rid: int) -> None:
            params = {"w": np.zeros(4, dtype=np.float32)}

            def load_state_dict(sd):
                params["w"] = np.array(sd["w"])

            def state_dict():
                return {"w": params["w"].copy()}

            manager = Manager(
                pg=ProcessGroupTCP(timeout=10.0),
                min_replica_size=2,
                world_size_mode=WorldSizeMode.FIXED_WITH_SPARES,
                load_state_dict=load_state_dict,
                state_dict=state_dict,
                lighthouse_addr=lighthouse.address(),
                replica_id=f"replica_{rid}",
                group_rank=0,
                group_world_size=1,
                use_async_quorum=False,  # eager heal: spares join in-step
                timeout=20.0,
                quorum_timeout=20.0,
            )
            try:
                while manager.current_step() < TOTAL:
                    step = manager.current_step()
                    if rid == 0 and step == KILL_AT:
                        return  # permanent death: spare must take over
                    manager.start_quorum()
                    grads = {
                        "w": np.full(4, float(step + 1), dtype=np.float32)
                        * (1.0 + 0.5 * rid)
                    }
                    avg = manager.allreduce(grads).wait(timeout=30)
                    if manager.should_commit():
                        params["w"] = params["w"] - 0.1 * avg["w"]
                        participation[rid].append(
                            (
                                manager.current_step(),
                                manager.is_participating(),
                                manager.num_participants(),
                            )
                        )
                        avg_samples[rid][manager.current_step()] = avg["w"].copy()
                results[rid] = state_dict()
            except BaseException as e:  # noqa: BLE001
                errors[rid] = e
            finally:
                manager.shutdown()

        threads = [
            threading.Thread(target=run, args=(r,), daemon=True)
            for r in range(3)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
        assert not any(t.is_alive() for t in threads), "replica hung"
        assert not errors, errors
        assert set(results) == {1, 2}, results

        # world size stays capped at min_replica_size=2 on EVERY commit
        for rid, hist in participation.items():
            for step, _, nparts in hist:
                assert nparts == 2, (rid, step, nparts)

        # before the kill: replica_2 is the spare (computes, never holds a
        # rank); replicas 0/1 participate
        pre2 = [p for p in participation[2] if p[0] <= KILL_AT]
        assert pre2, "spare committed no steps before the kill"
        assert all(not participating for _, participating, _ in pre2), pre2
        assert all(p for _, p, _ in participation[0]), participation[0]
        pre1 = [p for p in participation[1] if p[0] <= KILL_AT]
        assert all(p for _, p, _ in pre1), pre1

        # spare's zero-contribution is real: phase-1 averages exclude its
        # gradients — avg(step s) = (s+1)*(1.0 + 1.5)/2, not .../3 variants
        for step, avg in avg_samples[1].items():
            if step <= KILL_AT:
                expected = np.full(4, float(step) * 1.25, dtype=np.float32)
                np.testing.assert_allclose(avg, expected, rtol=1e-6)

        # promotion: within one quorum of replica_0's death the spare
        # holds a rank (committed steps after the kill are participating)
        post2 = [p for p in participation[2] if p[0] > KILL_AT + 1]
        assert post2, "spare committed nothing after the kill"
        assert all(p for _, p, _ in post2), post2

        # bitwise convergence of the survivors
        np.testing.assert_array_equal(results[1]["w"], results[2]["w"])


class TestAllreduceReleasesInputs:
    def test_inputs_die_with_the_callers_reference(self):
        """A completed managed allreduce must not pin its input leaves: for
        device gradients that is a whole extra copy of the model held in
        HBM across the next forward/backward (the PG worker's frame and the
        error-path closure used to keep them until the NEXT collective).
        Checked with the cyclic collector off, i.e. by refcount alone."""
        import gc
        import weakref

        import jax.numpy as jnp

        server = LighthouseServer(min_replicas=1, join_timeout_ms=100)
        manager = Manager(
            pg=ProcessGroupTCP(timeout=10.0), min_replica_size=1,
            load_state_dict=lambda sd: None, state_dict=lambda: {},
            replica_id="release", lighthouse_addr=server.address(),
            group_rank=0, group_world_size=1, timeout=10.0,
        )
        gc.disable()
        try:
            for step in range(2):
                manager.start_quorum()
                grads = {"w": jnp.ones((1024,)) * (step + 1)}
                ref = weakref.ref(grads["w"])
                work = manager.allreduce(grads)
                avg = work.wait(timeout=10)
                np.testing.assert_array_equal(avg["w"], np.full(1024, step + 1.0))
                # at world size 1 the result is the leaf itself, still on
                # the device: the result references it, and once the
                # caller's own names are gone, nothing else does
                assert avg["w"] is ref()
                del grads, work
                assert ref() is not None, "the result is not the input leaf"
                del avg
                assert ref() is None, "input leaf still referenced after wait()"
                assert manager.should_commit()
        finally:
            gc.enable()
            manager.shutdown()
            server.shutdown()
