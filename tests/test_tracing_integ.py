"""Live distributed-tracing round trip (`make trace-smoke`).

Acceptance for the fleet-wide tracing leg: a real 2-replica +
lighthouse run (threads-as-replicas, the test_manager_integ pattern)
with a forced heal, read back ENTIRELY from the ``TORCHFT_TRACE_FILE``
span sink:

- ONE trace id per step across the fleet — both managers' ``quorum_round``
  roots, their phase children, and the native lighthouse's ``rpc.quorum``
  server span share the step's deterministic trace id;
- the heal's source and destination land in one trace, parented to the
  healing replica's root (``heal.send`` from the source's HTTP server,
  ``heal_recv`` phase from the destination);
- chaos variant: an injected ``manager.quorum`` fault marks the victim's
  span ``ok=false`` and ``torchft-diagnose --trace`` names the faulted
  replica from the trace file alone.
"""

import json
from collections import defaultdict
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List

import numpy as np
import pytest

from torchft_tpu.coordination import LighthouseServer
from torchft_tpu.manager import Manager, PROTOCOL_PHASES
from torchft_tpu.parallel.process_group import ProcessGroupTCP
from torchft_tpu.utils import faults, tracing
from torchft_tpu.utils.faults import FaultRule, InjectedFault


@pytest.fixture(autouse=True)
def clean_faults():
    faults.FAULTS.configure([], seed=0)
    yield
    faults.FAULTS.configure([])


@pytest.fixture
def trace_file(tmp_path, monkeypatch):
    """Install a file-sink tracer for the duration of one test; yields
    the sink path (spans are readable after uninstall closes it)."""
    path = tmp_path / "trace.jsonl"
    monkeypatch.setenv("TORCHFT_TRACE_FILE", str(path))
    monkeypatch.delenv("TORCHFT_USE_OTEL", raising=False)
    tracing.uninstall_tracer()
    tracer = tracing.maybe_install_from_env()
    assert tracer is not None and tracer.sink is not None
    yield path
    tracing.uninstall_tracer()


@pytest.fixture
def lighthouse():
    server = LighthouseServer(
        min_replicas=2, join_timeout_ms=100, heartbeat_timeout_ms=1000
    )
    yield server
    server.shutdown()


def _train_replica(
    replica_id: int, lighthouse_addr: str, total_steps: int, attempts: int = 3
) -> dict:
    """One replica group running the toy DDP loop (fresh params per
    (re)start so a crash forces a real heal)."""
    last_exc = None
    for _ in range(attempts):
        try:
            return _train_once(replica_id, lighthouse_addr, total_steps)
        except InjectedFault as e:
            last_exc = e
            continue
    raise RuntimeError(f"replica {replica_id} exhausted attempts") from last_exc


def _train_once(replica_id: int, lighthouse_addr: str, total_steps: int) -> dict:
    params = {"w": np.zeros(4, dtype=np.float32)}

    def load_state_dict(sd):
        params["w"] = np.array(sd["params"]["w"])

    def state_dict():
        return {"params": {"w": params["w"].copy()}}

    pg = ProcessGroupTCP(timeout=10.0)
    manager = Manager(
        pg=pg,
        min_replica_size=1,
        load_state_dict=load_state_dict,
        state_dict=state_dict,
        lighthouse_addr=lighthouse_addr,
        replica_id=f"replica_{replica_id}",
        group_rank=0,
        group_world_size=1,
        timeout=20.0,
        quorum_timeout=20.0,
    )
    try:
        while manager.current_step() < total_steps:
            step = manager.current_step()
            faults.check(
                "train.step", replica=f"replica_{replica_id}", step=step
            )
            manager.start_quorum()
            grads = {"w": np.full(4, float(step + 1), dtype=np.float32)}
            avg = manager.allreduce(grads).wait(timeout=30)
            if manager.should_commit():
                params["w"] = params["w"] - 0.1 * avg["w"]
        return {"replica_id": replica_id, "w": params["w"].copy()}
    finally:
        manager.shutdown()


def _run_fleet(lighthouse, total_steps: int, n: int = 2) -> List[dict]:
    with ThreadPoolExecutor(max_workers=n) as ex:
        futs = [
            ex.submit(_train_replica, i, lighthouse.address(), total_steps)
            for i in range(n)
        ]
        return [f.result(timeout=120) for f in futs]


def _load_spans(path) -> List[dict]:
    return [
        json.loads(line)
        for line in path.read_text().splitlines()
        if line.strip()
    ]


def _base(rid: str) -> str:
    return rid.split(":", 1)[0]


class TestLiveRoundTrip:
    def test_one_trace_id_spans_fleet_and_heal(self, lighthouse, trace_file):
        faults.FAULTS.configure(
            [FaultRule(site="train.step", replica="replica_1", step=2)]
        )
        _run_fleet(lighthouse, total_steps=4)
        tracing.uninstall_tracer()  # flush/close the sink before reading
        spans = _load_spans(trace_file)
        assert spans, "file sink is empty"

        by_trace: Dict[str, List[dict]] = defaultdict(list)
        for s in spans:
            by_trace[s["trace_id"]].append(s)

        # --- one trace per step, spanning lighthouse + both managers ----
        roots_by_step: Dict[int, List[dict]] = defaultdict(list)
        for s in spans:
            if s["name"] == "quorum_round":
                roots_by_step[s["attributes"]["step"]].append(s)
        both = [
            step
            for step, roots in sorted(roots_by_step.items())
            if {_base(r["attributes"]["replica_id"]) for r in roots}
            >= {"replica_0", "replica_1"}
        ]
        assert both, f"no step has roots from both replicas: {roots_by_step}"
        step = both[-1]
        roots = roots_by_step[step]
        # deterministic derivation: every root of this step shares the id
        expected = tracing.step_trace_id(step)
        assert {r["trace_id"] for r in roots} == {expected}
        trace = by_trace[expected]
        # the native lighthouse served this step's quorum in the SAME trace
        lh = [
            s
            for s in trace
            if s["name"] == "rpc.quorum"
            and s["attributes"].get("server") == "lighthouse"
        ]
        assert lh, f"no lighthouse rpc.quorum span in step-{step} trace"
        # every root has phase children parented to it
        for root in roots:
            kids = [
                s for s in trace if s.get("parent_span_id") == root["span_id"]
            ]
            phase_names = {s["name"] for s in kids} & set(PROTOCOL_PHASES)
            assert phase_names, (
                f"root of {root['attributes']['replica_id']} has no phase "
                f"children"
            )
        # native manager server spans joined too (same trace)
        assert any(
            s["name"].startswith("rpc.")
            and s["attributes"].get("server") == "manager"
            for s in trace
        )

        # --- heal: source and destination spans in one trace ------------
        heal_sends = [s for s in spans if s["name"] == "heal.send"]
        assert heal_sends, "no heal.send span (forced heal did not trace)"
        root_by_span = {
            s["span_id"]: s for s in spans if s["name"] == "quorum_round"
        }
        parented = [
            s for s in heal_sends if s.get("parent_span_id") in root_by_span
        ]
        assert parented, "heal.send is not parented to any round root"
        send = parented[-1]
        dest_root = root_by_span[send["parent_span_id"]]
        assert send["trace_id"] == dest_root["trace_id"]
        # the destination's own heal_recv phase hangs off the same root
        dest_kids = {
            s["name"]
            for s in spans
            if s.get("parent_span_id") == dest_root["span_id"]
        }
        assert "heal_recv" in dest_kids, (
            f"destination root has children {dest_kids}, no heal_recv"
        )

    def test_store_rpcs_join_the_trace(self, lighthouse, trace_file):
        """PG configure's store barrier RPCs run inside the round: their
        rpc.* server spans (server=store) land in the step trace."""
        _run_fleet(lighthouse, total_steps=2)
        tracing.uninstall_tracer()
        spans = _load_spans(trace_file)
        assert any(
            s["attributes"].get("server") == "store"
            and s["name"].startswith("rpc.")
            for s in spans
        )


class TestRingOpened:
    """The ``ring`` phase, opened into its parts on the PG worker thread:
    a two-group ``ProcessGroupTCP`` step at a size where ``ring`` takes
    tens of milliseconds."""

    # 36 MB of float32 in one solo bucket (a chunk of 18 MB: two slices
    # since ISSUE 46) + a coalesced one; large enough that what a loaded
    # host adds between the parts, some 7 ms a run, stays under a tenth
    N = 9_000_000

    def _replica(self, replica_id: int, addr: str, steps: int) -> dict:
        params = {
            "w": np.zeros(self.N, dtype=np.float32),
            "b0": np.zeros(16, dtype=np.float32),
            "b1": np.zeros(16, dtype=np.float32),
        }
        manager = Manager(
            pg=ProcessGroupTCP(timeout=20.0),
            min_replica_size=2,
            load_state_dict=lambda sd: params.update(sd),
            state_dict=lambda: dict(params),
            lighthouse_addr=addr,
            replica_id=f"replica_{replica_id}",
            group_rank=0,
            group_world_size=1,
            timeout=20.0,
            quorum_timeout=20.0,
            init_sync=False,  # both build the same zeros: no step-0 heal
        )
        try:
            while manager.current_step() < steps:
                manager.start_quorum()
                grads = {k: np.ones_like(v) for k, v in params.items()}
                work = manager.allreduce(grads)
                avg = work.wait(timeout=30)
                assert float(avg["w"][0]) == 1.0
                manager.should_commit()
            return {
                "phases": manager.phase_times(),
                "histogram": set(manager._phase_hist),
            }
        finally:
            manager.shutdown()

    def test_parts_account_for_ring(self, lighthouse, trace_file):
        from torchft_tpu.manager import PHASE_PARTS

        with ThreadPoolExecutor(max_workers=2) as ex:
            futs = [
                ex.submit(self._replica, i, lighthouse.address(), 3)
                for i in range(2)
            ]
            results = [f.result(timeout=120) for f in futs]
        tracing.uninstall_tracer()
        named = {p for p in PHASE_PARTS if p.startswith("ring.")}
        # seconds beside the parts and no stretch of ring's wall (ISSUE 46):
        # there when a chunk moved in slices, and never a span
        named.remove("ring.reduce.hidden")
        # ring's own parts, and the parts of one of them (ISSUE 38)
        wire_parts = {p for p in named if p.startswith("ring.wire.")}
        ring_parts = named - wire_parts
        assert wire_parts == {
            "ring.wire." + p for p in ("arrive", "wait", "recv", "send")
        }
        for res in results:
            phases = res["phases"]
            assert named <= set(phases), sorted(phases)
            # the histogram's ``phase`` label takes phases, never a part
            assert "ring" in res["histogram"]
            assert not {k for k in res["histogram"] if tracing.is_part(k)}
            opened = sum(phases[p] for p in ring_parts)
            assert phases["ring"] > 0.03, phases  # tens of ms and more
            assert opened <= phases["ring"]
            assert opened >= 0.9 * phases["ring"], (opened, phases["ring"])
            # and the same one level down, where an exchange blocks (how
            # closely, without a tracer's exports inside the wall, is
            # tests/test_process_group.py's to say)
            inside = sum(phases[p] for p in wire_parts)
            assert 0.5 * phases["ring.wire"] <= inside <= phases["ring.wire"]
        # in the trace every part is a child of a ring span and lies in it
        spans = _load_spans(trace_file)
        assert not [s for s in spans if s["name"] == "ring.reduce.hidden"]
        by_id = {s["span_id"]: s for s in spans}
        parts = [s for s in spans if s["name"] in ring_parts]
        assert {s["name"] for s in parts} == ring_parts
        for s in parts:
            whole = by_id[s["parent_span_id"]]
            assert whole["name"] == "ring"
            assert whole["start_ns"] <= s["start_ns"]
            assert s["end_ns"] <= whole["end_ns"]
            assert s["attributes"]["step"] == whole["attributes"]["step"]
        # one span per part and bucket, never one per exchange: two
        # buckets here, so two wire spans per ring
        rings = [s for s in spans if s["name"] == "ring"]
        wires = [s for s in parts if s["name"] == "ring.wire"]
        assert len(wires) == 2 * len(rings)
        # the same holds inside ring.wire: a span a bucket for each part
        # that accumulates, carrying the seconds it booked, and one
        # ``arrive`` a ring, a plain span in its first bucket's wire
        inside = [s for s in spans if s["name"] in wire_parts]
        for s in inside:
            whole = by_id[s["parent_span_id"]]
            assert whole["name"] == "ring.wire"
            assert whole["start_ns"] <= s["start_ns"]
            assert s["end_ns"] <= whole["end_ns"] + 1000  # ns: int rounding
        count = {p: sum(s["name"] == p for s in inside) for p in wire_parts}
        assert count["ring.wire.arrive"] == len(rings)
        for p in ("wait", "recv", "send"):
            assert count["ring.wire." + p] == len(wires)
        assert all(
            ("seconds" in s["attributes"]) == (s["name"] != "ring.wire.arrive")
            for s in inside
        )
        # ring.d2h is one span a ring over its stretches (the start of the
        # copies, then a wait a bucket): it carries the seconds it booked,
        # and host leaves are their own host arrays, so nothing was sent
        # ahead of the ring
        d2h = [s for s in parts if s["name"] == "ring.d2h"]
        assert len(d2h) == len(rings)
        for s in d2h:
            attrs = s["attributes"]
            assert attrs["bytes"] == 4 * (self.N + 32)
            assert attrs["overlapped"] == 0 and attrs["relaid"] == 0
            assert 0 < attrs["seconds"] <= (s["end_ns"] - s["start_ns"]) / 1e9 + 1e-6


class TestChaosTrace:
    def test_faulted_round_marks_span_and_ledger_names_culprit(
        self, lighthouse, trace_file, capsys
    ):
        faults.FAULTS.configure(
            [FaultRule(site="manager.quorum", replica="replica_1", step=1)]
        )
        _run_fleet(lighthouse, total_steps=3)
        assert faults.FAULTS.injected() == 1
        tracing.uninstall_tracer()
        spans = _load_spans(trace_file)

        failed = [
            s
            for s in spans
            if s["name"] == "quorum_round" and not s.get("ok", True)
        ]
        assert failed, "no ok=false root span for the faulted round"
        assert all(
            _base(s["attributes"]["replica_id"]) == "replica_1" for s in failed
        )

        # the ledger names the culprit FROM THE TRACE FILE ALONE
        from torchft_tpu import diagnose

        rc = diagnose.main(["--trace", str(trace_file), "--json"])
        assert rc == 0
        report = json.loads(capsys.readouterr().out)
        culprit = report["culprit"]
        assert culprit is not None
        assert culprit["signal"] == "trace_error"
        assert _base(culprit["replica_id"]) == "replica_1"
        ledger = report["trace_ledger"]
        assert ledger["steps"], "ledger has no steps"
        for row in ledger["steps"]:
            assert row["dominant"] in (
                "compute", "codec", "wire", "protocol", "straggler-wait",
            ) or row["dominant"] is None
        # healthy steps name a dominant contributor
        assert ledger["dominant_overall"] is not None
