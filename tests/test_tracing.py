"""Unit tests for the distributed-tracing layer (utils/tracing.py):
context encoding, deterministic per-step trace ids, sampling, the JSONL
file sink, thread-local propagation state, and the zero-cost budget of
the disabled path (same bar discipline as the flight recorder's)."""

import json
import os
import threading
import time

import pytest

from torchft_tpu.utils import tracing


@pytest.fixture(autouse=True)
def _clean_tracer():
    tracing.uninstall_tracer()
    yield
    tracing.uninstall_tracer()


class TestTraceContext:
    def test_traceparent_round_trip(self):
        ctx = tracing.TraceContext(
            tracing.new_trace_id(), tracing.new_span_id(), True
        )
        tp = ctx.to_traceparent()
        assert tp.startswith("00-") and tp.endswith("-01")
        back = tracing.TraceContext.from_traceparent(tp)
        assert back == ctx

    def test_unsampled_flag(self):
        ctx = tracing.TraceContext("a" * 32, "b" * 16, sampled=False)
        assert ctx.to_traceparent().endswith("-00")
        back = tracing.TraceContext.from_traceparent(ctx.to_traceparent())
        assert back is not None and not back.sampled

    @pytest.mark.parametrize(
        "bad",
        [
            None,
            "",
            "garbage",
            "00-short-span-01",
            "00-" + "x" * 32 + "-" + "b" * 16 + "-01",  # non-hex trace
            "00-" + "a" * 31 + "_" + "-" + "b" * 16 + "-01",  # underscore
            "00-" + "a" * 32 + "-" + "b" * 15 + "-01",  # short span
            "00-" + "a" * 32 + "-" + "b" * 16 + "-0",  # short flags
            "00-" + "a" * 32 + "-" + "b" * 16 + "-zz",  # non-hex flags
            "00-" + "a" * 32 + "-" + "b" * 16 + "-01-extra",
            42,
        ],
    )
    def test_malformed_traceparent_parses_to_none(self, bad):
        assert tracing.TraceContext.from_traceparent(bad) is None

    def test_child_keeps_trace_changes_span(self):
        ctx = tracing.TraceContext("a" * 32, "b" * 16)
        kid = ctx.child()
        assert kid.trace_id == ctx.trace_id
        assert kid.span_id != ctx.span_id

    def test_step_trace_id_deterministic_and_distinct(self):
        assert tracing.step_trace_id(7) == tracing.step_trace_id(7)
        assert tracing.step_trace_id(7) != tracing.step_trace_id(8)
        assert tracing.step_trace_id(7, "jobA") != tracing.step_trace_id(
            7, "jobB"
        )
        assert len(tracing.step_trace_id(0)) == 32
        int(tracing.step_trace_id(0), 16)  # valid hex


class TestSampling:
    def test_extremes(self):
        always = tracing.Tracer(sample=1.0)
        never = tracing.Tracer(sample=0.0)
        assert all(always.sample_step(s) for s in range(50))
        assert not any(never.sample_step(s) for s in range(50))

    def test_deterministic_across_instances(self):
        """Every replica must make the SAME per-step decision — a sampled
        step's trace is complete or absent, never partial."""
        a = tracing.Tracer(sample=0.5)
        b = tracing.Tracer(sample=0.5)
        decisions = [a.sample_step(s, "job") for s in range(200)]
        assert decisions == [b.sample_step(s, "job") for s in range(200)]
        # a half-rate sampler actually samples some and skips some
        assert 20 < sum(decisions) < 180


class TestFileSpanSink:
    def test_jsonl_round_trip(self, tmp_path):
        path = tmp_path / "trace.jsonl"
        tracer = tracing.Tracer(sink=tracing.FileSpanSink(str(path)))
        sid = tracer.export_span(
            "ring", "a" * 32, 100, 200,
            parent_span_id="b" * 16,
            attributes={"step": 3, "replica_id": "r0"},
        )
        tracer.export_span("commit", "a" * 32, 200, 300, ok=False)
        tracer.close()
        lines = [json.loads(l) for l in path.read_text().splitlines()]
        assert len(lines) == 2
        assert lines[0]["name"] == "ring"
        assert lines[0]["span_id"] == sid
        assert lines[0]["parent_span_id"] == "b" * 16
        assert lines[0]["attributes"]["step"] == 3
        assert lines[1]["ok"] is False

    def test_append_across_sinks(self, tmp_path):
        """Two sinks on one path (≈ two processes sharing the file) must
        append, not clobber — the O_APPEND contract."""
        path = tmp_path / "trace.jsonl"
        for i in range(2):
            sink = tracing.FileSpanSink(str(path))
            sink.export({"name": f"s{i}", "trace_id": "t", "span_id": "x",
                         "start_ns": 0, "end_ns": 1, "ok": True})
            sink.close()
        lines = path.read_text().splitlines()
        assert len(lines) == 2

    def test_closed_sink_drops_instead_of_reopening(self, tmp_path):
        """A racing emitter that grabbed the tracer before uninstall must
        not resurrect the file after close() (that fd would leak)."""
        path = tmp_path / "trace.jsonl"
        sink = tracing.FileSpanSink(str(path))
        sink.export({"name": "ring", "trace_id": "t", "span_id": "s",
                     "start_ns": 0, "end_ns": 1, "ok": True})
        sink.close()
        sink.export({"name": "late", "trace_id": "t", "span_id": "s2",
                     "start_ns": 0, "end_ns": 1, "ok": True})
        assert len(path.read_text().splitlines()) == 1

    def test_env_install(self, tmp_path, monkeypatch):
        monkeypatch.setenv("TORCHFT_TRACE_FILE", str(tmp_path / "t.jsonl"))
        monkeypatch.setenv("TORCHFT_TRACE_SAMPLE", "0.25")
        tracer = tracing.maybe_install_from_env()
        assert tracer is not None
        assert tracer.sink is not None and tracer.exporter is None
        assert tracer.sample == 0.25
        assert tracing.get_tracer() is tracer

    def test_env_disabled(self, monkeypatch):
        monkeypatch.delenv("TORCHFT_TRACE_FILE", raising=False)
        monkeypatch.delenv("TORCHFT_USE_OTEL", raising=False)
        assert tracing.maybe_install_from_env() is None


class TestCurrentContext:
    def test_no_tracer_means_no_context(self):
        tracing.set_current(tracing.TraceContext("a" * 32, "b" * 16))
        try:
            # fast path: without an installed tracer nothing propagates
            assert tracing.get_current() is None
            assert tracing.current_traceparent() is None
        finally:
            tracing.set_current(None)

    def test_thread_local(self, tmp_path):
        tracing.install_tracer(
            tracing.Tracer(sink=tracing.FileSpanSink(str(tmp_path / "t")))
        )
        ctx = tracing.TraceContext("a" * 32, "b" * 16)
        tracing.set_current(ctx)
        seen = {}

        def other():
            seen["ctx"] = tracing.get_current()

        t = threading.Thread(target=other)
        t.start()
        t.join()
        assert seen["ctx"] is None  # contexts do not leak across threads
        assert tracing.get_current() == ctx
        assert tracing.current_traceparent() == ctx.to_traceparent()
        tracing.set_current(None)

    def test_unsampled_context_not_injected(self, tmp_path):
        tracing.install_tracer(
            tracing.Tracer(sink=tracing.FileSpanSink(str(tmp_path / "t")))
        )
        tracing.set_current(
            tracing.TraceContext("a" * 32, "b" * 16, sampled=False)
        )
        assert tracing.current_traceparent() is None
        tracing.set_current(None)


def _spans(path):
    return [json.loads(l) for l in path.read_text().splitlines() if l.strip()]


@pytest.fixture
def span_file(tmp_path):
    """A file-sink tracer and a bound round context; yields (path, ctx)."""
    path = tmp_path / "spans.jsonl"
    tracing.install_tracer(tracing.Tracer(sink=tracing.FileSpanSink(str(path))))
    ctx = tracing.TraceContext("a" * 32, "b" * 16)
    tracing.set_current(ctx)
    yield path, ctx
    tracing.set_current(None)


class TestPhase:
    """``tracing.phase``: the one span primitive (sink, true start and
    end, parent, nesting by the dot, no jax where jax is not loaded)."""

    def test_feeds_the_sink(self):
        sink = {}
        with tracing.phase("ring", sink) as p:
            time.sleep(0.01)
        assert p.seconds >= 0.01
        assert sink == {"ring": p.seconds}
        with tracing.phase("ring", sink):
            pass
        assert sink["ring"] > p.seconds  # accumulates
        assert tracing.get_tracer() is None  # all of it with tracing off

    def test_exports_true_start_end_and_parent(self, span_file):
        path, ctx = span_file
        sink = {}
        before = time.time_ns()
        with tracing.phase("commit", sink, replica_id="r0", step=7):
            time.sleep(0.02)
        after = time.time_ns()
        time.sleep(0.05)  # a start rebuilt at export time would land here
        tracing.uninstall_tracer()
        (span,) = _spans(path)
        assert span["name"] == "commit"
        assert span["trace_id"] == ctx.trace_id
        assert span["parent_span_id"] == ctx.span_id
        assert before <= span["start_ns"] <= span["end_ns"] <= after
        assert (span["end_ns"] - span["start_ns"]) / 1e9 == pytest.approx(
            sink["commit"], abs=1e-6
        )
        assert span["attributes"] == {"replica_id": "r0", "step": 7}
        assert span["ok"] is True

    def test_failed_phase_is_marked(self, span_file):
        path, _ = span_file
        sink = {}
        with pytest.raises(ValueError):
            with tracing.phase("commit", sink):
                raise ValueError("boom")
        tracing.uninstall_tracer()
        assert _spans(path)[0]["ok"] is False
        assert sink["commit"] >= 0.0

    def test_part_lies_inside_its_whole(self, span_file):
        path, ctx = span_file
        sink = {}
        with tracing.phase("heal_send", sink, replica_id="r0"):
            time.sleep(0.002)
            with tracing.phase(".hash", fragment="3"):  # its whole's sink
                time.sleep(0.005)
            with tracing.phase(".stage", bytes=9):
                time.sleep(0.002)
            time.sleep(0.002)
        tracing.uninstall_tracer()
        by = {s["name"]: s for s in _spans(path)}
        assert set(by) == {"heal_send", "heal_send.hash", "heal_send.stage"}
        whole = by["heal_send"]
        assert whole["parent_span_id"] == ctx.span_id
        for name in ("heal_send.hash", "heal_send.stage"):
            part = by[name]
            assert part["parent_span_id"] == whole["span_id"]
            assert whole["start_ns"] <= part["start_ns"]
            assert part["end_ns"] <= whole["end_ns"]
            # who is timing flows down the thread
            assert part["attributes"]["replica_id"] == "r0"
        assert by["heal_send.hash"]["attributes"]["fragment"] == "3"
        assert set(sink) == {"heal_send", "heal_send.hash", "heal_send.stage"}
        assert sink["heal_send.hash"] + sink["heal_send.stage"] <= sink["heal_send"]
        assert tracing.is_part("heal_send.hash") and not tracing.is_part("heal_send")

    def test_unrelated_phase_inside_is_not_a_part(self, span_file):
        """``heal_manifest`` opened while ``heal_recv`` is open is a phase
        of its own: under the round's root, the ledger's children."""
        path, ctx = span_file
        sink, below = {}, {}
        with tracing.phase("heal_recv", sink, step=4):
            with tracing.phase("heal_manifest", below):
                pass
        tracing.uninstall_tracer()
        by = {s["name"]: s for s in _spans(path)}
        assert by["heal_manifest"]["parent_span_id"] == ctx.span_id
        assert by["heal_manifest"]["attributes"] == {"step": 4}
        assert set(below) == {"heal_manifest"} and set(sink) == {"heal_recv"}

    def test_begin_end_across_threads_and_under(self, span_file):
        """``ring`` begins on the caller's thread and ends on the worker's;
        its parts run on the worker, carried there by ``under``."""
        path, _ = span_file
        sink = {}
        ring = tracing.phase("ring", sink, step=1).begin()
        with tracing.under(ring):
            whole = tracing.open_phase()
            queued = tracing.phase(".queue").begin()
        assert whole is ring and tracing.open_phase() is None

        def worker():
            queued.end()
            with tracing.under(whole):
                with tracing.phase(".d2h", bytes=4):
                    time.sleep(0.005)
            ring.end()

        t = threading.Thread(target=worker)
        t.start()
        t.join()
        tracing.uninstall_tracer()
        by = {s["name"]: s for s in _spans(path)}
        assert set(sink) == {"ring", "ring.queue", "ring.d2h"}
        for name in ("ring.queue", "ring.d2h"):
            assert by[name]["parent_span_id"] == by["ring"]["span_id"]
            assert by["ring"]["start_ns"] <= by[name]["start_ns"]
            assert by[name]["end_ns"] <= by["ring"]["end_ns"]
        assert by["ring.d2h"]["attributes"] == {"step": 1, "bytes": 4}
        assert sink["ring.queue"] + sink["ring.d2h"] <= sink["ring"]

    def test_lap_accumulates_into_one_span(self, span_file):
        path, _ = span_file
        from torchft_tpu.utils import flightrecorder

        flightrecorder.RECORDER.clear()
        sink, seen = {}, []
        decode = tracing.phase(
            "heal_decode", sink, observe=lambda n, s: seen.append((n, s))
        )
        for i in range(3):
            # what a stretch opens is the phase's part
            with decode.lap(), tracing.phase(".fragment", fragment=str(i)):
                time.sleep(0.003)
            assert tracing.open_phase() is None
            time.sleep(0.01)  # the wire between the stretches
        assert decode.end() == sink["heal_decode"]
        never = tracing.phase("heal_decode", sink)
        assert never.end() == 0.0  # no lap ran: nothing recorded
        tracing.uninstall_tracer()
        by = {}
        for s in _spans(path):
            by.setdefault(s["name"], []).append(s)
        (span,) = by["heal_decode"]
        wall = (span["end_ns"] - span["start_ns"]) / 1e9
        assert 0.009 <= sink["heal_decode"] < 0.02 < wall
        assert span["attributes"]["seconds"] == pytest.approx(sink["heal_decode"])
        # one part span per stretch, one observation and flight record in all
        assert [s["attributes"]["fragment"] for s in by["heal_decode.fragment"]] == [
            "0", "1", "2"
        ]
        assert {s["parent_span_id"] for s in by["heal_decode.fragment"]} == {
            span["span_id"]
        }
        assert sink["heal_decode.fragment"] <= sink["heal_decode"]
        assert seen == [("heal_decode", sink["heal_decode"])]
        recs = [r for r in flightrecorder.snapshot() if r.get("kind") == "phase"]
        assert [r["op"] for r in recs] == ["heal_decode"]

    def test_a_lapped_part_among_its_siblings_and_a_count_made_on_the_way(
        self, span_file
    ):
        """``ring.d2h`` as the PG worker times it: stretches between the
        other parts of ``ring`` (a wait a bucket), one span whose
        ``seconds`` are the stretches alone, so the parts still add up to
        the whole; ``overlapped`` is counted while the stretches run and
        the span carries what it read at the end."""
        path, _ = span_file
        sink = {}
        with tracing.phase("ring", sink, step=7):
            d2h = tracing.phase(".d2h", bytes=12, overlapped=0)
            for bucket in range(3):
                with d2h.lap():
                    time.sleep(0.004)
                    d2h.attrs["overlapped"] += 4 if bucket else 0
                with tracing.phase(".wire", bytes=4):
                    time.sleep(0.006)
            assert d2h.end() == sink["ring.d2h"]
        tracing.uninstall_tracer()
        by = {}
        for s in _spans(path):
            by.setdefault(s["name"], []).append(s)
        (span,), (ring,) = by["ring.d2h"], by["ring"]
        assert span["parent_span_id"] == ring["span_id"]
        assert span["attributes"] == {
            "step": 7, "bytes": 12, "overlapped": 8,
            "seconds": pytest.approx(sink["ring.d2h"]),
        }
        # the span runs from the first stretch to the last, across two
        # exchanges; what is booked is the stretches
        wall = (span["end_ns"] - span["start_ns"]) / 1e9
        assert 0.012 <= sink["ring.d2h"] < 0.024 <= wall
        assert len(by["ring.wire"]) == 3
        parts = sink["ring.d2h"] + sink["ring.wire"]
        assert parts <= sink["ring"] and parts >= 0.9 * sink["ring"]

    def test_a_part_has_parts_the_same_way(self, span_file):
        """ISSUE 38, ``ring.wire`` as the PG worker opens it: inside
        ``.wire`` a plain ``.arrive`` once and lapped ``.wait`` / ``.recv``
        over the messages.  Since ISSUE 46 ``.reduce`` is only the
        stretches in which the wire stood still for a reduce (here one
        after each message: the receiver held back for ``scratch``); they
        are excluded from the wire and from all its parts, so the parts
        still add up to the wire and the wire and the reduce to no more
        than the ring.  What the reducer did under the wire is seconds in
        the same sink, ``ring.reduce.hidden``, and no span.  The names go
        through the dot rule, the spans hang off the wire's, the seconds
        land in ring's sink."""
        path, _ = span_file
        sink, seen = {}, []
        with tracing.phase("ring", sink, step=7, observe=lambda n, s: seen.append(n)):
            reduce = tracing.phase(".reduce")
            with tracing.phase(".wire", bytes=64) as wire:
                arrive = tracing.phase(".arrive")
                wait, recv = tracing.phase(".wait"), tracing.phase(".recv")
                assert arrive.name == "ring.wire.arrive" and arrive.sink is sink
                for exchange in range(3):
                    with (wait.lap() if exchange else arrive):
                        time.sleep(0.004)
                        assert tracing.open_phase().name.startswith("ring.wire.")
                    assert tracing.open_phase() is wire
                    with recv.lap():
                        time.sleep(0.002)
                    with reduce.lap():
                        time.sleep(0.003)
                tracing.add_seconds(reduce.sink, "ring.reduce.hidden", 0.5)
                wire.exclude(reduce.end())
            assert wait.end() == sink["ring.wire.wait"]
            assert recv.end() == sink["ring.wire.recv"]
            assert tracing.phase(".send").end() == 0.0  # no lap: no record
        tracing.uninstall_tracer()
        by = {s["name"]: s for s in _spans(path)}
        assert sink.pop("ring.reduce.hidden") == 0.5  # seconds, no span
        assert set(by) == set(sink) == {
            "ring", "ring.reduce", "ring.wire", "ring.wire.arrive",
            "ring.wire.wait", "ring.wire.recv",
        }
        assert all(tracing.is_part(n) for n in sink if n != "ring")
        assert seen == ["ring"]  # the histogram takes phases alone
        for name in ("ring.wire.arrive", "ring.wire.wait", "ring.wire.recv"):
            part = by[name]
            assert part["parent_span_id"] == by["ring.wire"]["span_id"]
            assert by["ring.wire"]["start_ns"] <= part["start_ns"]
            assert part["end_ns"] <= by["ring.wire"]["end_ns"] + 1000
            assert part["attributes"]["step"] == 7
            # the lapped ones carry what they booked, the plain one its wall
            assert ("seconds" in part["attributes"]) == (name != "ring.wire.arrive")
        assert sink["ring.wire.arrive"] >= 0.004
        assert sink["ring.wire.wait"] >= 0.008 and sink["ring.wire.recv"] >= 0.006
        inside = sum(sink["ring.wire." + p] for p in ("arrive", "wait", "recv"))
        assert inside <= sink["ring.wire"]
        assert inside == pytest.approx(sink["ring.wire"], rel=0.1)
        assert sink["ring.wire"] + sink["ring.reduce"] <= sink["ring"]

    def test_a_part_of_an_orphan_part_is_only_the_annotation(self, span_file):
        """A bare ``pg.allreduce`` (no ``ring`` open): ``.wire`` is only
        the annotation, and so is what is opened inside it; the seconds
        are still there for the counter."""
        path, _ = span_file
        with tracing.phase(".wire") as wire:
            with tracing.phase(".arrive") as arrive:
                time.sleep(0.002)
            wait = tracing.phase(".wait")
            with wait.lap():
                time.sleep(0.002)
            assert wait.end() >= 0.002
        assert (wire.name, arrive.name, wait.name) == ("wire", "wire.arrive", "wire.wait")
        assert arrive.sink is None and arrive.seconds >= 0.002
        tracing.uninstall_tracer()
        assert not path.exists()

    def test_exclude_books_less_and_span_keeps_its_ends(self, span_file):
        path, _ = span_file
        sink = {}
        with tracing.phase("heal_recv", sink) as p:
            time.sleep(0.02)
            assert p.elapsed() >= 0.02
            p.exclude(0.015)
        tracing.uninstall_tracer()
        (span,) = _spans(path)
        assert (span["end_ns"] - span["start_ns"]) / 1e9 >= 0.02
        assert sink["heal_recv"] == pytest.approx(
            (span["end_ns"] - span["start_ns"]) / 1e9 - 0.015, abs=1e-6
        )
        assert span["attributes"]["seconds"] == pytest.approx(sink["heal_recv"])

    def test_cancel_records_nothing(self, span_file):
        path, _ = span_file
        sink = {}
        with tracing.phase("reshard", sink) as p:
            p.cancel()
        tracing.uninstall_tracer()
        assert sink == {} and not path.exists()

    def test_orphan_part_is_only_the_annotation(self, span_file):
        path, _ = span_file
        from torchft_tpu.utils import flightrecorder

        n = flightrecorder.RECORDER.total_recorded()
        with tracing.phase(".snapshot", fragment="0") as p:
            pass
        assert p.name == "snapshot" and p.sink is None
        tracing.uninstall_tracer()
        assert not path.exists()
        assert flightrecorder.RECORDER.total_recorded() == n

    def test_flight_ring_takes_phases_not_parts(self):
        from torchft_tpu.utils import flightrecorder

        flightrecorder.RECORDER.clear()
        sink = {}
        with tracing.phase("ring", sink, replica_id="r0", step=2):
            with tracing.phase(".d2h"):
                pass
        recs = [r for r in flightrecorder.snapshot() if r.get("kind") == "phase"]
        assert [r["op"] for r in recs] == ["ring"]
        assert recs[0]["replica_id"] == "r0" and recs[0]["step"] == 2
        assert recs[0]["start_ns"] <= recs[0]["end_ns"]

    def test_observe_gets_phases_not_parts(self):
        """The histogram's sum over ``phase`` counts no part against its
        whole; a phase opened inside another inherits ``observe``."""
        seen = []
        with tracing.phase("heal_recv", {}, observe=lambda n, s: seen.append(n)):
            with tracing.phase(".wait"):
                pass
            with tracing.phase("heal_manifest", {}):
                pass
        assert seen == ["heal_manifest", "heal_recv"]

    def test_sink_loses_no_update_across_threads(self):
        """Phases end on the caller's, the quorum and the PG worker's
        threads into one sink: more threads than cores, a short switch
        interval, and a sum a lost read-modify-write would break."""
        import sys

        sink = {}
        n_threads, n_adds = 16, 2000
        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)
        try:
            threads = [
                threading.Thread(
                    target=lambda: [
                        tracing.add_seconds(sink, "ring", 1.0)
                        for _ in range(n_adds)
                    ]
                )
                for _ in range(n_threads)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
        finally:
            sys.setswitchinterval(interval)
        assert not any(t.is_alive() for t in threads)
        assert sink == {"ring": float(n_threads * n_adds)}

    def test_silent_where_jax_is_not_loaded(self, monkeypatch):
        """A process that only moves host bytes (``process_group`` loads
        without jax) must not be made to import jax: no annotation where
        jax is not already in ``sys.modules``, and everything else works."""
        import sys

        monkeypatch.setattr(tracing, "_TraceAnnotation", None)
        monkeypatch.delitem(sys.modules, "jax")
        sink = {}
        with tracing.phase("ring", sink, bytes=1) as p:
            with tracing.phase(".wire") as part:
                assert p._ann is None and part._ann is None
        assert "jax" not in sys.modules
        assert tracing._TraceAnnotation is None
        assert sink["ring.wire"] >= 0.0

    def test_process_group_module_does_not_import_jax(self):
        import ast
        import inspect

        from torchft_tpu.parallel import process_group

        for node in ast.walk(ast.parse(inspect.getsource(process_group))):
            if isinstance(node, ast.Import):
                assert all(a.name.split(".")[0] != "jax" for a in node.names)
            if isinstance(node, ast.ImportFrom):
                assert (node.module or "").split(".")[0] != "jax"

    def test_annotation_lands_in_the_profilers_host_plane(self, tmp_path):
        """A jitted toy step under ``jax.profiler.trace`` with a phase
        around it: ``torchft.<name>`` is an event of the host plane, on
        the clock the device's events are on, with the attributes."""
        import glob

        import jax
        import jax.numpy as jnp
        from jax.profiler import ProfileData

        step = jax.jit(lambda x: (x @ x).sum())
        x = jnp.ones((128, 128))
        step(x).block_until_ready()
        sink = {}
        with jax.profiler.trace(str(tmp_path)):
            with tracing.phase("ring", sink, step=3):
                with tracing.phase(".d2h", bytes=12):
                    step(x).block_until_ready()
                with tracing.phase(".wire"):
                    # once an op and plain, so an annotation; the lapped
                    # parts beside it are none
                    with tracing.phase(".arrive"):
                        time.sleep(0.002)
                    wait = tracing.phase(".wait")
                    with wait.lap():
                        pass
                    wait.end()
        (pb,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
        found = {}
        for plane in ProfileData.from_file(pb).planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("torchft."):
                        found[ev.name] = (ev.start_ns, ev.duration_ns, dict(ev.stats))
        assert set(found) == {
            "torchft.ring", "torchft.ring.d2h", "torchft.ring.wire",
            "torchft.ring.wire.arrive",
        }
        a0, ad, _ = found["torchft.ring.wire.arrive"]
        v0, vd, _ = found["torchft.ring.wire"]
        assert v0 <= a0 and a0 + ad <= v0 + vd and ad >= 2e6
        w0, wd, wstats = found["torchft.ring"]
        p0, pd, pstats = found["torchft.ring.d2h"]
        assert w0 <= p0 and p0 + pd <= w0 + wd
        assert wstats["step"] == 3 and pstats["bytes"] == 12
        assert pd / 1e9 == pytest.approx(sink["ring.d2h"], rel=0.5, abs=2e-3)


class TestWirePartsVocabulary:
    """ISSUE 38 (g): the four parts of ``ring.wire`` are in
    ``manager.PHASE_PARTS`` and the ``span-vocab`` lint, run against the
    tree's own tuples, takes a part of a part by its last component and
    keeps refusing a name outside them."""

    PARTS = tuple("ring.wire." + p for p in ("arrive", "wait", "recv", "send"))

    def _findings(self, tmp_path, body):
        import inspect
        import textwrap

        from torchft_tpu import manager
        from torchft_tpu.analysis import PASSES, Project, run_passes

        files = {
            "pkg/manager.py": inspect.getsource(manager),
            "pkg/mod.py": "from torchft_tpu.utils import tracing\n\n"
            + textwrap.dedent(body),
        }
        paths = []
        for rel, src in files.items():
            path = tmp_path / rel
            path.parent.mkdir(parents=True, exist_ok=True)
            path.write_text(src)
            paths.append(str(path))
        (tmp_path / "docs").mkdir()
        lint = next(p for p in PASSES if p.id == "span-vocab")
        results = run_passes(
            [lint], Project(str(tmp_path), paths), baseline_dir=str(tmp_path / "nb")
        )
        return [f for r in results for f in r.findings if f.file.endswith("mod.py")]

    def test_the_four_names_are_parts(self):
        from torchft_tpu.manager import PHASE_PARTS

        assert set(self.PARTS) <= set(PHASE_PARTS)
        assert "ring.wire" in PHASE_PARTS
        assert all(tracing.is_part(p) for p in self.PARTS)

    def test_span_vocab_accepts_them(self, tmp_path):
        assert not self._findings(
            tmp_path,
            """
            def ring():
                with tracing.phase(".wire"):
                    with tracing.phase(".arrive"):
                        pass
                    wait = tracing.phase(".wait")
                    recv, send = tracing.phase(".recv"), tracing.phase(".send")
            """,
        )

    @pytest.mark.parametrize("name", [".bogus", "ring.wire.bogus"])
    def test_span_vocab_refuses_a_name_outside_the_tuples(self, tmp_path, name):
        findings = self._findings(
            tmp_path,
            f"""
            def ring():
                with tracing.phase(".wire"), tracing.phase({name!r}):
                    pass
            """,
        )
        assert [f.code for f in findings] == ["unknown-span-name"]
        assert findings[0].symbol == name


class TestDisabledPathBudget:
    def test_disabled_injection_is_zero_cost(self):
        """Acceptance bar: the disabled hot path (no tracer installed) —
        exactly what every RPC call and collective submit runs — must be
        a single module-global check, ≤ the flight recorder's record()
        budget (2.5 us; this is ~50 ns in practice).  Best-of-batches so
        a loaded CI host doesn't flake the measurement."""
        assert tracing.get_tracer() is None
        n = 50_000
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            for _ in range(n):
                tracing.current_traceparent()
                tracing.get_current()
            best = min(best, (time.perf_counter() - t0) / n)
        assert best <= 2.5e-6, f"disabled trace path {best * 1e9:.0f} ns/call"
        # the one span primitive with tracing off and no profiler session:
        # one annotation enter/exit, three clock reads, the sink, the
        # flight record (~4 us here; a healthy step opens some tens of
        # them against seconds).  Same best-of-batches discipline.
        sink = {}
        n = 10_000
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            for _ in range(n):
                with tracing.phase("ring", sink, replica_id="r0", step=1):
                    pass
            best = min(best, (time.perf_counter() - t0) / n)
        assert best <= 25e-6, f"disabled phase {best * 1e6:.1f} us/span"

    def test_disabled_sampling_check_is_cheap(self):
        """Manager.start_quorum's disabled path is one get_tracer() call."""
        assert tracing.get_tracer() is None
        n = 100_000
        t0 = time.perf_counter()
        for _ in range(n):
            if tracing.get_tracer() is not None:  # pragma: no cover
                raise AssertionError
        per = (time.perf_counter() - t0) / n
        assert per <= 1e-6, f"get_tracer {per * 1e9:.0f} ns/call"
