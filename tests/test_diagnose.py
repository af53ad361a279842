"""torchft-diagnose tests: selftest wiring, culprit attribution units,
and the tier-1 chaos smoke (kill one of two DDP replicas mid-step; every
survivor dumps flight state on abort; diagnose names the killed replica
and the failed phase; the lighthouse exports nonzero step lag for the
dead replica before eviction)."""

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

from torchft_tpu import diagnose
from torchft_tpu.coordination import LighthouseServer
from torchft_tpu.manager import Manager
from torchft_tpu.parallel.process_group import ProcessGroupTCP
from torchft_tpu.utils import faults
from torchft_tpu.utils import flightrecorder as fr
from torchft_tpu.utils.faults import FaultRule, InjectedFault
from torchft_tpu.utils.metrics import parse_text_exposition


@pytest.fixture(autouse=True)
def clean_faults():
    faults.FAULTS.configure([], seed=0)
    yield
    faults.FAULTS.configure([])


# ---------------------------------------------------------------------------
# selftest wiring (satellite: the CLI can never silently rot)
# ---------------------------------------------------------------------------


class TestSelftest:
    def test_selftest_passes(self):
        assert diagnose.selftest(verbose=False)

    def test_cli_selftest_exit_code(self, capsys):
        assert diagnose.main(["--selftest"]) == 0
        assert "selftest OK" in capsys.readouterr().out

    def test_cli_no_input_is_usage_error(self, capsys):
        assert diagnose.main([]) == 2

    def test_cli_unreadable_input(self, capsys):
        assert diagnose.main(["/nonexistent/flight.jsonl"]) == 1


# ---------------------------------------------------------------------------
# cluster timeline (--timeline: the lighthouse's fleet view)
# ---------------------------------------------------------------------------


def _timeline_doc(worst=None, steps=None):
    return {
        "quorum_id": 3,
        "now_ms": 1_000_000,
        "ring": 256,
        "steps_tracked": len(steps or []),
        "steps": steps
        or [
            {
                "step": 41,
                "replicas": 4,
                "reports": 4,
                "first_ms": 999_000,
                "last_ms": 999_100,
                "span_ms": 100,
                "phases": {"ring": {"n": 4, "mean_ms": 12.0, "max_ms": 30.0}},
                "codec_busy_s": 0.4,
                "wire_busy_s": 0.8,
            }
        ],
        "stragglers_worst": worst or [],
    }


class TestClusterTimeline:
    def test_timeline_straggler_named_without_any_dumps(self, tmp_path, capsys):
        """One /timeline.json scrape alone (no flight dumps collected)
        names the wedged replica — the acceptance path the churn soak
        exercises live."""
        doc = _timeline_doc(
            worst=[
                {
                    "replica_id": "stub007:u2", "step": 38, "step_lag": 3,
                    "progress_age_ms": 9000, "straggler_score": 18.0,
                    "inflight_op": "wedged", "stale": False,
                },
                {
                    "replica_id": "stub001:u0", "step": 41, "step_lag": 0,
                    "progress_age_ms": 400, "straggler_score": 1.1,
                    "inflight_op": "train", "stale": False,
                },
            ]
        )
        path = tmp_path / "timeline.json"
        path.write_text(json.dumps(doc))
        assert diagnose.main(["--timeline", str(path)]) == 0
        out = capsys.readouterr().out
        assert "LIKELY CULPRIT: stub007:u2" in out
        assert "timeline_straggler" in out
        assert "cluster timeline" in out
        assert "step 41" in out and "replicas=4" in out
        assert "worst stragglers" in out

    def test_stale_replica_beats_score_threshold(self, tmp_path):
        doc = _timeline_doc(
            worst=[
                {
                    "replica_id": "dead:u1", "step": 10, "step_lag": 5,
                    "progress_age_ms": 30000, "straggler_score": 2.0,
                    "inflight_op": "", "stale": True,
                }
            ]
        )
        report = diagnose.analyze_timeline(doc)
        assert report["culprit"]["replica_id"] == "dead:u1"
        assert "stale" in report["culprit"]["reason"]

    def test_healthy_timeline_names_nobody(self):
        doc = _timeline_doc(
            worst=[
                {
                    "replica_id": "ok:u1", "step": 41, "step_lag": 0,
                    "progress_age_ms": 100, "straggler_score": 1.2,
                    "inflight_op": "train", "stale": False,
                }
            ]
        )
        assert diagnose.analyze_timeline(doc)["culprit"] is None

    def test_flight_evidence_outranks_timeline(self, tmp_path, capsys):
        """A dump-implicated replica wins over the timeline straggler:
        inside-the-replica evidence is stronger than the outside view."""
        t0 = 1_000_000_000_000
        dump = tmp_path / "a.jsonl"
        with open(dump, "w") as fh:
            for rid, last in (("replica_a:u1", 5), ("replica_b:u2", 1)):
                for step in range(last):
                    fh.write(json.dumps({
                        "flight": "rec", "op": "quorum_rpc", "status": "ok",
                        "start_ns": t0 + step * 10**9,
                        "end_ns": t0 + step * 10**9 + 10**6,
                        "replica_id": rid, "step": step, "quorum_id": 1,
                    }) + "\n")
            fh.write(json.dumps({
                "flight": "rec", "op": "allreduce", "status": "error",
                "start_ns": t0 + 5 * 10**9, "end_ns": t0 + 6 * 10**9,
                "replica_id": "replica_a:u1", "step": 4, "quorum_id": 1,
                "reason": "peer gone",
            }) + "\n")
        tl = tmp_path / "timeline.json"
        tl.write_text(json.dumps(_timeline_doc(worst=[{
            "replica_id": "unrelated:u9", "step": 2, "step_lag": 3,
            "progress_age_ms": 9000, "straggler_score": 30.0,
            "inflight_op": "", "stale": True,
        }])))
        assert diagnose.main([str(dump), "--timeline", str(tl)]) == 0
        out = capsys.readouterr().out
        # silent-death signal from the dumps wins; timeline still rendered
        assert "LIKELY CULPRIT: replica_b:u2" in out
        assert "cluster timeline" in out

    def test_unreadable_timeline_degrades_with_warning(self, tmp_path, capsys):
        assert diagnose.main(["--timeline", str(tmp_path / "nope.json")]) == 1
        assert "--timeline" in capsys.readouterr().err

    def test_load_timeline_rejects_non_timeline_json(self, tmp_path):
        p = tmp_path / "x.json"
        p.write_text('{"not": "a timeline"}')
        with pytest.raises(ValueError):
            diagnose.load_timeline(str(p))


# ---------------------------------------------------------------------------
# attribution units
# ---------------------------------------------------------------------------


class TestAttribution:
    def test_silent_death_culprit_and_text_render(self, tmp_path):
        import tempfile

        with tempfile.TemporaryDirectory() as td:
            a, b = diagnose._synthetic_dumps(td)
            entries, warnings = diagnose.load_records([a, b])
            report = diagnose.analyze(entries)
            text = diagnose.render_text(entries, report, warnings)
        assert report["culprit"]["replica_id"] == "replica_b:u2"
        assert report["culprit"]["signal"] == "silent_death"
        assert report["failure"]["phase"] == "allreduce"
        assert report["failure"]["step"] == 3
        assert "LIKELY CULPRIT: replica_b:u2" in text
        assert "FAILED PHASE: allreduce" in text

    def test_injected_fault_wins_attribution(self, tmp_path):
        dump = tmp_path / "d.jsonl"
        s = 1_000_000_000  # 1s in ns
        t0 = 1_000 * s
        recs = [
            {"flight": "rec", "op": "quorum_rpc", "status": "ok",
             "start_ns": t0, "end_ns": t0 + s, "replica_id": "a", "step": 2},
            {"flight": "rec", "op": "fault", "status": "fault",
             "start_ns": t0 + 2 * s, "end_ns": t0 + 2 * s, "replica_id": "b",
             "step": 2, "fault": "train.step:raise", "site": "train.step",
             "action": "raise"},
            {"flight": "rec", "op": "allreduce", "status": "error",
             "start_ns": t0 + 3 * s, "end_ns": t0 + 10 * s,
             "replica_id": "a", "step": 2, "reason": "peer closed"},
        ]
        dump.write_text("\n".join(json.dumps(r) for r in recs) + "\n")
        entries, _ = diagnose.load_records([str(dump)])
        report = diagnose.analyze(entries)
        assert report["culprit"]["replica_id"] == "b"
        assert report["culprit"]["signal"] == "injected_fault"
        assert report["faults"][0]["fault"] == "train.step:raise"

    def test_recovered_fault_does_not_mask_real_death(self, tmp_path):
        """A fault the system survived (its replica kept producing records
        to the end) must NOT win attribution over a later silent death of
        a different replica."""
        dump = tmp_path / "d.jsonl"
        s = 1_000_000_000
        t0 = 1_000 * s
        recs = [
            # replica a absorbs an injected transport fault at step 1...
            {"flight": "rec", "op": "fault", "status": "fault",
             "start_ns": t0, "end_ns": t0, "replica_id": "a", "step": 1,
             "fault": "transport.recv:raise", "site": "transport.recv",
             "action": "raise"},
        ]
        # ...and both replicas keep training; b silently dies at step 8
        for step in range(1, 10):
            for rid in ("a", "b"):
                if rid == "b" and step >= 8:
                    continue
                base = t0 + step * s
                recs.append(
                    {"flight": "rec", "op": "ring", "status": "ok",
                     "start_ns": base, "end_ns": base + 1000,
                     "replica_id": rid, "step": step}
                )
        recs.append(
            {"flight": "rec", "op": "allreduce", "status": "error",
             "start_ns": t0 + 8 * s, "end_ns": t0 + 18 * s,
             "replica_id": "a", "step": 8, "reason": "deadline"}
        )
        dump.write_text("\n".join(json.dumps(r) for r in recs) + "\n")
        entries, _ = diagnose.load_records([str(dump)])
        report = diagnose.analyze(entries)
        assert report["culprit"]["replica_id"] == "b", report["culprit"]
        assert report["culprit"]["signal"] == "silent_death"

    def test_healthy_run_yields_no_culprit(self, tmp_path):
        """Staggered shutdown of a clean run (no error/abort/fault
        anywhere) must NOT produce a culprit, even when one replica's
        last record is seconds after the other's."""
        dump = tmp_path / "d.jsonl"
        s = 1_000_000_000
        t0 = 1_000 * s
        recs = []
        for step in range(5):
            for rid in ("a:u0", "b:u1"):
                base = t0 + step * s
                recs.append(
                    {"flight": "rec", "op": "ring", "status": "ok",
                     "start_ns": base, "end_ns": base + 1000,
                     "replica_id": rid, "step": step}
                )
        # a's shutdown-time dump logs one extra record much later
        recs.append(
            {"flight": "rec", "op": "commit", "status": "ok",
             "start_ns": t0 + 8 * s, "end_ns": t0 + 8 * s,
             "replica_id": "a:u0", "step": 4}
        )
        dump.write_text("\n".join(json.dumps(r) for r in recs) + "\n")
        entries, _ = diagnose.load_records([str(dump)])
        report = diagnose.analyze(entries)
        assert report["culprit"] is None, report["culprit"]
        assert report["failure"] is None

    def test_recovered_fault_phantom_id_not_blamed(self, tmp_path):
        """A bare-id fault record (the faults layer stamps no incarnation
        suffix) must not mint a phantom 'dead' replica: a run where the
        faulted replica restarted and kept training stays culprit-free."""
        dump = tmp_path / "d.jsonl"
        s = 1_000_000_000
        t0 = 1_000 * s
        recs = [
            {"flight": "rec", "op": "fault", "status": "fault",
             "start_ns": t0 + s, "end_ns": t0 + s, "replica_id": "b",
             "step": 1, "fault": "train.step:raise", "site": "train.step",
             "action": "raise"},
        ]
        for step in range(5):
            for rid in ("a:u0", "b:u1"):
                base = t0 + step * s
                recs.append(
                    {"flight": "rec", "op": "ring", "status": "ok",
                     "start_ns": base, "end_ns": base + 1000,
                     "replica_id": rid, "step": step}
                )
        dump.write_text("\n".join(json.dumps(r) for r in recs) + "\n")
        entries, _ = diagnose.load_records([str(dump)])
        report = diagnose.analyze(entries)
        # no phantom 'b' liveness entry, no verdict on a recovered run
        assert all(":" in rid for rid in report["replicas"]), report["replicas"]
        assert report["culprit"] is None, report["culprit"]

    def test_one_sided_evidence_points_at_peer_not_reporter(self, tmp_path):
        """Only the survivor's dump collected (the victim was SIGKILLed —
        no dump): the tool must NOT blame the replica that reported the
        failure; it points at the peer rank from the failing transfer."""
        dump = tmp_path / "d.jsonl"
        s = 1_000_000_000
        t0 = 1_000 * s
        recs = [
            {"flight": "rec", "op": "quorum_rpc", "status": "ok",
             "start_ns": t0, "end_ns": t0 + s, "replica_id": "a:u1",
             "step": 4, "quorum_id": 2},
            {"flight": "rec", "op": "allreduce", "status": "error",
             "start_ns": t0 + 2 * s, "end_ns": t0 + 12 * s,
             "replica_id": "a:u1", "rank": 0, "world": 2, "recv_peer": 1,
             "reason": "collective failed: timeout"},
        ]
        dump.write_text("\n".join(json.dumps(r) for r in recs) + "\n")
        entries, _ = diagnose.load_records([str(dump)])
        report = diagnose.analyze(entries)
        assert report["culprit"] is not None
        assert report["culprit"]["signal"] == "peer_without_evidence"
        assert "rank 1" in report["culprit"]["replica_id"]
        assert not report["culprit"]["replica_id"].startswith("a:")

    def test_retry_storm_flagged(self, tmp_path):
        dump = tmp_path / "d.jsonl"
        t0 = 1_000_000_000_000
        recs = [
            {"flight": "rec", "op": "retry", "status": "retry",
             "start_ns": t0 + i, "end_ns": t0 + i, "replica_id": "a",
             "retry_op": "rpc.connect", "attempt": i}
            for i in range(5)
        ]
        dump.write_text("\n".join(json.dumps(r) for r in recs) + "\n")
        entries, _ = diagnose.load_records([str(dump)])
        report = diagnose.analyze(entries)
        assert report["retry_storms"] == [
            {"replica_id": "a", "op": "rpc.connect", "retries": 5}
        ]
        assert report["culprit"]["signal"] == "retry_storm"

    def test_events_merge_and_dedupe(self, tmp_path):
        """TORCHFT_EVENTS_FILE records merge into the same timeline, and a
        record dumped twice (two ring snapshots) appears once."""
        dump = tmp_path / "d.jsonl"
        rec = {"flight": "rec", "op": "allreduce", "status": "error",
               "start_ns": 5, "end_ns": 9, "replica_id": "a", "step": 1}
        dump.write_text(json.dumps(rec) + "\n" + json.dumps(rec) + "\n")
        events = tmp_path / "ev.jsonl"
        events.write_text(json.dumps(
            {"ts": 1.0, "kind": "quorum", "message": "quorum changed",
             "replica_id": "a", "step": 1, "quorum_id": 3}
        ) + "\n")
        entries, warnings = diagnose.load_records(
            [str(dump)], [str(events)]
        )
        assert not warnings
        assert len(entries) == 2  # deduped flight rec + one event
        sources = {e["source"] for e in entries}
        assert sources == {"flight", "event"}

    def test_json_output(self, tmp_path, capsys):
        dump = tmp_path / "d.jsonl"
        dump.write_text(json.dumps(
            {"flight": "rec", "op": "ring", "status": "ok",
             "start_ns": 1, "end_ns": 2, "replica_id": "a", "step": 0}
        ) + "\n")
        assert diagnose.main([str(dump), "--json"]) == 0
        payload = json.loads(capsys.readouterr().out)
        assert payload["entries"] == 1
        assert payload["timeline"][0]["op"] == "ring"


# ---------------------------------------------------------------------------
# tier-1 chaos smoke (acceptance criteria end to end)
# ---------------------------------------------------------------------------


@pytest.mark.chaos
class TestDiagnoseChaosSmoke:
    def test_kill_mid_step_dump_diagnose_and_step_lag(
        self, tmp_path, monkeypatch
    ):
        """Kill one of two DDP replicas mid-step (after quorum, before its
        collective — the worst moment for its peer): the survivor's wedged
        collective fails and dumps flight state, torchft-diagnose names
        the killed replica and the failed phase, and the lighthouse
        exports nonzero torchft_replica_step_lag for the dead replica
        (its progress entry outlives its heartbeat until supersession)."""
        TOTAL, KILL_AT = 6, 2
        flight_file = tmp_path / "flight.jsonl"
        monkeypatch.setenv("TORCHFT_FLIGHT_FILE", str(flight_file))
        fr.RECORDER.clear()
        faults.FAULTS.configure(
            [FaultRule(site="train.step", replica="replica_1", step=KILL_AT)],
            seed=11,
        )

        # min_replicas=1 so the survivor can form a singleton quorum after
        # the permanent kill.  Warm-up heartbeats for two placeholder ids
        # arm the split-brain guard, holding the FIRST quorum open until
        # both real managers have joined (the placeholders expire after
        # heartbeat_timeout_ms and never participate).
        lighthouse = LighthouseServer(
            min_replicas=1, join_timeout_ms=100, heartbeat_timeout_ms=1000
        )
        from torchft_tpu.coordination import LighthouseClient

        warm = LighthouseClient(lighthouse.address())
        warm.heartbeat("warm_a")
        warm.heartbeat("warm_b")
        warm.close()
        results = {}
        errors = {}

        def run(rid: int) -> None:
            params = {"w": np.zeros(4, dtype=np.float32)}

            def load_state_dict(sd):
                params["w"] = np.array(sd["w"])

            def state_dict():
                return {"w": params["w"].copy()}

            pg = ProcessGroupTCP(timeout=10.0)
            manager = Manager(
                pg=pg,
                min_replica_size=1,
                load_state_dict=load_state_dict,
                state_dict=state_dict,
                lighthouse_addr=lighthouse.address(),
                replica_id=f"replica_{rid}",
                group_rank=0,
                group_world_size=1,
                use_async_quorum=False,  # quorum forms BEFORE the kill site
                timeout=20.0,
                quorum_timeout=20.0,
            )
            try:
                while manager.current_step() < TOTAL:
                    step = manager.current_step()
                    manager.start_quorum()
                    # kill site sits between quorum formation and the
                    # collective: the peer is left blocked mid-ring
                    faults.check(
                        "train.step", replica=f"replica_{rid}", step=step
                    )
                    grads = {
                        "w": np.full(4, float(step + 1), dtype=np.float32)
                        * (1.0 + 0.5 * rid)
                    }
                    avg = manager.allreduce(grads).wait(timeout=30)
                    if manager.should_commit():
                        params["w"] = params["w"] - 0.1 * avg["w"]
                results[rid] = {
                    "state": state_dict(), "step": manager.current_step()
                }
            except InjectedFault:
                # "process death": the OS would close every socket — abort
                # does exactly that (and dumps this replica's flight ring)
                pg.abort()
                results[rid] = {"killed_at": manager.current_step()}
            except BaseException as e:  # noqa: BLE001
                errors[rid] = e
            finally:
                manager.shutdown()

        threads = [
            threading.Thread(target=run, args=(r,), daemon=True)
            for r in range(2)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads), "replica hung"
        assert not errors, errors
        assert results[0].get("step") == TOTAL, results
        assert results[1].get("killed_at") == KILL_AT, results

        # --- every surviving process dumped on abort -------------------
        lines = [
            json.loads(l) for l in flight_file.read_text().splitlines()
        ]
        metas = [l for l in lines if l.get("flight") == "meta"]
        assert any(m["trigger"] == "pg_abort" for m in metas), metas
        recs = [l for l in lines if l.get("flight") == "rec"]
        # survivor's failed collective is in the dump with error status
        assert any(
            r["status"] == "error"
            and str(r.get("replica_id", "")).startswith("replica_0")
            for r in recs
        ), "survivor's collective failure not captured"

        # --- diagnose names the killed replica and the failed phase ----
        entries, _warnings = diagnose.load_records([str(flight_file)])
        report = diagnose.analyze(entries)
        assert report["culprit"] is not None, report
        assert report["culprit"]["replica_id"].startswith("replica_1"), report[
            "culprit"
        ]
        assert report["failure"] is not None
        assert report["failure"]["phase"] in ("allreduce", "manager.error", "abort")
        # the CLI agrees (exit 0, culprit in the rendered text)
        assert diagnose.main([str(flight_file)]) == 0

        # --- lighthouse exports nonzero step lag for the dead replica --
        body = (
            urllib.request.urlopen(
                f"http://{lighthouse.address()}/metrics", timeout=5
            )
            .read()
            .decode()
        )
        fams = parse_text_exposition(body)
        lags = fams["torchft_replica_step_lag"]["samples"]
        dead_lag = [
            v
            for (name, labels), v in lags.items()
            if name == "torchft_replica_step_lag"
            and dict(labels).get("replica", "").startswith("replica_1")
        ]
        assert dead_lag and dead_lag[0] > 0, lags
        survivor_lag = [
            v
            for (name, labels), v in lags.items()
            if name == "torchft_replica_step_lag"
            and dict(labels).get("replica", "").startswith("replica_0")
        ]
        assert survivor_lag and survivor_lag[0] == 0, lags
        # straggler score for the dead replica dwarfs the survivor's
        scores = fams["torchft_straggler_score"]["samples"]
        dead_score = [
            v
            for (name, labels), v in scores.items()
            if dict(labels).get("replica", "").startswith("replica_1")
        ]
        assert dead_score and dead_score[0] >= 1.0, scores
        lighthouse.shutdown()


# ---------------------------------------------------------------------------
# trace ledger (torchft-diagnose --trace)
# ---------------------------------------------------------------------------


def _span(name, trace, sid, parent, t0_ms, t1_ms, ok=True, **attrs):
    return {
        "name": name, "trace_id": trace, "span_id": sid,
        "parent_span_id": parent, "start_ns": t0_ms * 1_000_000,
        "end_ns": t1_ms * 1_000_000, "attributes": attrs, "ok": ok,
    }


class TestTraceLedger:
    """analyze_trace over synthetic span files: category attribution,
    the quant.pipeline codec/wire substitution, the lighthouse
    straggler-wait refinement, and the CLI with --trace as the ONLY
    input."""

    def _write(self, tmp_path, spans):
        path = tmp_path / "trace.jsonl"
        path.write_text("".join(json.dumps(s) + "\n" for s in spans))
        return path

    def test_categories_and_critical_path(self, tmp_path):
        T = "a" * 32
        spans = [
            _span("quorum_round", T, "ra" + "0" * 14, None, 0, 1000,
                  replica_id="rep_a", step=5, quorum_id=2),
            _span("quorum_rpc", T, "p1" + "0" * 14, "ra" + "0" * 14, 0, 100,
                  replica_id="rep_a", step=5),
            # quant.pipeline REPLACES ring in the sums
            _span("ring", T, "p2" + "0" * 14, "ra" + "0" * 14, 100, 900,
                  replica_id="rep_a", step=5),
            _span("quant.pipeline", T, "p3" + "0" * 14, "ra" + "0" * 14,
                  100, 900, collective="allreduce", codec_s=0.25,
                  wire_s=0.55),
            # faster replica, protocol-dominant
            _span("quorum_round", T, "rb" + "0" * 14, None, 0, 400,
                  replica_id="rep_b", step=5, quorum_id=2),
            _span("commit", T, "p4" + "0" * 14, "rb" + "0" * 14, 0, 300,
                  replica_id="rep_b", step=5),
        ]
        report = diagnose.analyze_trace(spans)
        assert len(report["steps"]) == 1
        row = report["steps"][0]
        assert row["step"] == 5 and row["quorum_id"] == 2
        assert row["critical_replica"] == "rep_a"
        a = row["replicas"]["rep_a"]
        # ring (0.8s) replaced by pipeline codec 0.25 + wire 0.55
        assert a["categories"]["codec"] == pytest.approx(0.25)
        assert a["categories"]["wire"] == pytest.approx(0.55)
        assert a["categories"]["protocol"] == pytest.approx(0.1)
        assert a["dominant"] == "wire" and row["dominant"] == "wire"
        assert row["replicas"]["rep_b"]["dominant"] == "protocol"
        assert report["culprit"] is None

    def test_lighthouse_span_refines_straggler_wait(self, tmp_path):
        T = "b" * 32
        spans = [
            _span("quorum_round", T, "r0" + "0" * 14, None, 0, 1000,
                  replica_id="rep_a", step=1, quorum_id=1),
            # the caller blocked 0.9 s; the lighthouse says 0.7 s of that
            # was waiting for the quorum to form
            _span("quorum_wait", T, "w0" + "0" * 14, "r0" + "0" * 14, 0, 900,
                  replica_id="rep_a", step=1),
            _span("rpc.quorum", T, "l0" + "0" * 14, "r0" + "0" * 14, 0, 700,
                  server="lighthouse", method="quorum"),
        ]
        report = diagnose.analyze_trace(spans)
        cats = report["steps"][0]["replicas"]["rep_a"]["categories"]
        # 0.7 measured + 0.2 excess quorum_wait = 0.9 total, not 1.6
        assert cats["straggler-wait"] == pytest.approx(0.9)

    def test_cli_trace_only_names_culprit(self, tmp_path, capsys):
        T = "c" * 32
        spans = [
            _span("quorum_round", T, "r0" + "0" * 14, None, 0, 500,
                  replica_id="rep_a", step=2, quorum_id=1),
            _span("quorum_round", T, "r1" + "0" * 14, None, 0, 400, ok=False,
                  replica_id="rep_bad", step=2, quorum_id=1),
        ]
        path = self._write(tmp_path, spans)
        rc = diagnose.main(["--trace", str(path)])
        out = capsys.readouterr().out
        assert rc == 0
        assert "critical-path ledger" in out
        # the verdict block names the failed replica, trace-only input
        assert "LIKELY CULPRIT: rep_bad" in out
        assert "[trace_error]" in out

    def test_parts_never_count_against_their_whole(self, tmp_path):
        """ISSUE 24, the one rule for nesting: a key with a dot is contained
        in the key before the dot, so a summing consumer gives the same
        total with and without the dotted keys — over ``phase_times()``
        and over a span file."""
        from torchft_tpu.manager import PHASE_PARTS, PROTOCOL_PHASES

        whole = {name: 1.0 + i for i, name in enumerate(PROTOCOL_PHASES)}
        plain = [p for p in PHASE_PARTS if p not in diagnose.PART_CATEGORY]
        opened = dict(whole, **{part: 0.25 for part in plain})
        assert diagnose.ledger_categories(opened) == diagnose.ledger_categories(whole)
        # a refining part moves seconds between categories and adds none
        opened.update({part: 0.25 for part in diagnose.PART_CATEGORY})
        assert set(diagnose.PART_CATEGORY) <= set(PHASE_PARTS)
        assert sum(diagnose.ledger_categories(opened).values()) == pytest.approx(
            sum(whole.values())
        )
        assert diagnose.dominant_contributor(
            {"ring": 1.0, "ring.d2h": 0.9, "ring.pack": 0.9, "commit": 1.5}
        ) == "protocol"
        assert set(PROTOCOL_PHASES) == set(diagnose.PHASE_CATEGORY)

        T = "c" * 32
        root = "ra" + "0" * 14
        spans = [
            _span("quorum_round", T, root, None, 0, 1000,
                  replica_id="rep_a", step=5, quorum_id=2),
            _span("ring", T, "p1" + "0" * 14, root, 0, 600, replica_id="rep_a"),
            # heal_recv spans the whole receive and books what its split
            # phases leave (tracing.phase.exclude): 50 of its 300 ms
            _span("heal_recv", T, "p2" + "0" * 14, root, 600, 900, seconds=0.05),
            _span("heal_diff", T, "p3" + "0" * 14, root, 650, 900),
        ]
        parts = [
            _span("ring.d2h", T, "q1" + "0" * 14, "p1" + "0" * 14, 0, 400),
            _span("ring.wire", T, "q2" + "0" * 14, "p1" + "0" * 14, 400, 600),
            _span("heal_diff.hash", T, "q3" + "0" * 14, "p3" + "0" * 14, 700, 900),
        ]
        without = diagnose.analyze_trace(spans)["steps"][0]["replicas"]["rep_a"]
        with_parts = diagnose.analyze_trace(spans + parts)["steps"][0]["replicas"]["rep_a"]
        assert with_parts["categories"] == without["categories"]
        assert without["categories"] == {"codec": 0.25, "wire": 0.65}

    def test_the_rings_waits_move_from_wire_to_straggler_wait(self):
        """ISSUE 38 (e): ``ring.wire.arrive`` + ``ring.wire.wait`` refine
        ``ring``: the PG worker blocked on a peer is straggler-wait, not
        wire.  Nothing is counted twice, and without the parts the phase
        reads as before."""
        cats = diagnose.ledger_categories(
            {"ring": 1.0, "ring.wire.arrive": 0.3, "ring.wire.wait": 0.1}
        )
        assert cats == {
            "wire": pytest.approx(0.6), "straggler-wait": pytest.approx(0.4)
        }
        assert diagnose.ledger_categories({"ring": 1.0}) == {"wire": 1.0}
        # the other parts of the wire, and of the ring, move nothing
        assert diagnose.ledger_categories(
            {"ring": 1.0, "ring.wire": 0.9, "ring.wire.recv": 0.5,
             "ring.wire.send": 0.1, "ring.d2h": 0.1}
        ) == {"wire": 1.0}
        # a part takes at most its phase's seconds, and none of a phase
        # that is not there
        assert diagnose.ledger_categories(
            {"ring": 0.2, "ring.wire.arrive": 0.3, "commit": 0.1}
        ) == {
            "wire": pytest.approx(0.0), "straggler-wait": pytest.approx(0.2),
            "protocol": 0.1,
        }
        assert diagnose.ledger_categories({"ring.wire.arrive": 0.3}) == {}
        assert diagnose.dominant_contributor(
            {"ring": 1.0, "ring.wire.arrive": 0.6, "commit": 0.3}
        ) == "straggler-wait"
        assert diagnose.dominant_contributor({"ring": 1.0, "commit": 0.3}) == "wire"

    def test_trace_ledger_finds_the_waits_below_ring_wire(self):
        """In a span file the two parts are grandchildren of ``ring``
        (round -> ring -> ring.wire -> the part): the ledger files them
        under their round, books the lapped one by its ``seconds``, and a
        quantized pipeline, which replaces ``ring``, leaves them nothing
        to refine."""
        T = "d" * 32
        root, ring, wire = ("r" + c + "0" * 14 for c in "abc")

        def spans(replica, late):
            rid = f"rep_{replica}"
            ids = [x.replace("r", replica, 1) for x in (root, ring, wire)]
            return [
                _span("quorum_round", T, ids[0], None, 0, 1000,
                      replica_id=rid, step=5, quorum_id=2),
                _span("commit", T, replica + "c" + "0" * 14, ids[0], 900, 1000),
                _span("ring", T, ids[1], ids[0], 0, 900, replica_id=rid),
                _span("ring.wire", T, ids[2], ids[1], 100, 900),
                _span("ring.wire.arrive", T, replica + "d" + "0" * 14, ids[2],
                      100, 100 + late),
                # lapped: the span runs first stretch to last, books 50 ms
                _span("ring.wire.wait", T, replica + "e" + "0" * 14, ids[2],
                      100 + late, 900, seconds=0.05),
                _span("ring.wire.recv", T, replica + "f" + "0" * 14, ids[2],
                      100 + late, 900, seconds=0.2),
            ]

        both = spans("a", 600) + spans("b", 0)
        step = diagnose.analyze_trace(both)["steps"][0]
        waited, late = step["replicas"]["rep_a"], step["replicas"]["rep_b"]
        assert waited["categories"] == {
            "protocol": pytest.approx(0.1), "wire": pytest.approx(0.25),
            "straggler-wait": pytest.approx(0.65),
        }
        assert waited["dominant"] == "straggler-wait"
        assert late["categories"]["wire"] == pytest.approx(0.85)
        assert late["dominant"] == "wire"
        # without the parts the same trace reads as before
        plain = [s for s in both if not s["name"].startswith("ring.wire.")]
        for info in diagnose.analyze_trace(plain)["steps"][0]["replicas"].values():
            assert info["categories"]["wire"] == pytest.approx(0.9)
            assert "straggler-wait" not in info["categories"]
        # quant.pipeline replaces ring: nothing is left to move
        quant = both + [
            _span("quant.pipeline", T, "q" * 16, "aa" + "0" * 14, 0, 900,
                  codec_s=0.3, wire_s=0.5)
        ]
        cats = diagnose.analyze_trace(quant)["steps"][0]["replicas"]["rep_a"]["categories"]
        assert cats["wire"] == pytest.approx(0.5)
        assert cats.get("straggler-wait", 0.0) == 0.0

    def test_bench_vocabulary_matches(self):
        """``dominant_contributor`` names a step's cost in the ledger's
        categories — pin the vocabulary so reports stay joinable with it."""
        assert diagnose.dominant_contributor(
            {"quorum_rpc": 1.0, "ring": 5.0}
        ) == "wire"
        assert diagnose.dominant_contributor(
            {"quorum_wait": 9.0, "commit": 1.0}
        ) == "straggler-wait"
        assert diagnose.dominant_contributor({}) is None
        for cat in diagnose.PHASE_CATEGORY.values():
            assert cat in diagnose.LEDGER_CATEGORIES


class TestRingStragglerTrace:
    """ISSUE 38, acceptance: with an async quorum a slow replica group is
    not waited for at the quorum but inside the first exchange of the
    ring.  A live two-replica trace in which one replica sleeps 0.2 s
    before its allreduce: ``torchft-diagnose --trace`` names
    ``straggler-wait``, not ``wire``, for the replica that waited, and the
    counter that holds the same seconds is in ``/metrics``."""

    STEPS = 3

    def _replica(self, rid, addr, out):
        params = {"w": np.zeros(1024, dtype=np.float32)}
        manager = Manager(
            pg=ProcessGroupTCP(timeout=20.0),
            min_replica_size=2,
            load_state_dict=lambda sd: params.update(sd),
            state_dict=lambda: dict(params),
            lighthouse_addr=addr,
            replica_id=f"replica_{rid}",
            group_rank=0,
            group_world_size=1,
            use_async_quorum=True,
            timeout=20.0,
            quorum_timeout=20.0,
            init_sync=False,
        )
        try:
            while manager.current_step() < self.STEPS:
                manager.start_quorum()
                if rid == 1:
                    time.sleep(0.2)  # its grad step, on a slower chip
                manager.allreduce({"w": np.ones(1024, np.float32)}).wait(timeout=30)
                manager.should_commit()
            out[rid] = manager.phase_times()
        finally:
            manager.shutdown()

    def test_a_late_replica_is_its_peers_straggler_wait(
        self, tmp_path, monkeypatch, capsys
    ):
        from torchft_tpu.utils import metrics, tracing

        path = tmp_path / "trace.jsonl"
        monkeypatch.setenv("TORCHFT_TRACE_FILE", str(path))
        monkeypatch.delenv("TORCHFT_USE_OTEL", raising=False)
        tracing.uninstall_tracer()
        assert tracing.maybe_install_from_env() is not None
        lighthouse = LighthouseServer(
            min_replicas=2, join_timeout_ms=100, heartbeat_timeout_ms=1000
        )
        out = {}
        try:
            threads = [
                threading.Thread(target=self._replica, args=(rid, lighthouse.address(), out))
                for rid in range(2)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            assert not any(t.is_alive() for t in threads) and set(out) == {0, 1}
        finally:
            tracing.uninstall_tracer()
            lighthouse.shutdown()
        # the replica that went first waited in the ring, by name
        assert out[0]["ring.wire.arrive"] >= 0.15 * self.STEPS
        assert out[1]["ring.wire.arrive"] < 0.1

        assert diagnose.main(["--trace", str(path), "--json"]) == 0
        ledger = json.loads(capsys.readouterr().out)["trace_ledger"]
        steps = [s for s in ledger["steps"] if "replica_0" in str(s["replicas"])]
        assert len(steps) == self.STEPS
        for s in steps:
            (waited,) = [v for k, v in s["replicas"].items() if k.startswith("replica_0")]
            assert waited["dominant"] == "straggler-wait", (s["step"], waited)
            assert waited["categories"]["straggler-wait"] >= 0.15
            assert waited["categories"]["straggler-wait"] > waited["categories"]["wire"]
        # the same seconds, where an operator scrapes them
        fams = parse_text_exposition(metrics.REGISTRY.render())
        samples = fams["torchft_ring_peer_wait_seconds_total"]["samples"]
        by_kind = {
            dict(labels)["kind"]: v for (_, labels), v in samples.items()
            if dict(labels).get("replica_id") == "replica_0"
        }
        assert set(by_kind) == {"arrive", "wait"}
        assert by_kind["arrive"] >= 0.15 * self.STEPS
