"""Fleet link-state plane (ISSUE 16): the passive per-link registry, its
hot-path budget, closed-loop estimator accuracy against the declared wire
shaping, the heartbeat-digest -> lighthouse matrix -> /links.json
aggregation round trip, the serving staleness ledger, the
``lighthouse.links`` chaos degradation, and the ``torchft-diagnose
--links`` slow-link analysis.
"""

import json
import time
import urllib.request

import numpy as np
import pytest

from tests.test_process_group import make_group, run_parallel, store  # noqa: F401
from torchft_tpu.coordination import LighthouseClient, LighthouseServer
from torchft_tpu.parallel.process_group import ProcessGroupTCP
from torchft_tpu.utils import linkstats
from torchft_tpu.utils import metrics as _metrics
from torchft_tpu.utils.faults import (
    FAULTS,
    FaultRule,
    InjectedConnectionDrop,
)


@pytest.fixture(autouse=True)
def _fresh_registry():
    linkstats.LINKS.reset()
    yield
    linkstats.LINKS.reset()


def _row(peer="h1", plane="reduction", local=False, goodput=1e8,
         rtt_p99=2.0, samples=16, src=None):
    r = {
        "peer": peer, "plane": plane, "local": local,
        "goodput_bps": goodput, "rtt_ms": rtt_p99 / 2,
        "rtt_p99_ms": rtt_p99, "samples": samples, "bytes": 1 << 20,
        "age_s": 0.1,
    }
    if src is not None:
        r["src"] = src
    return r


class TestRegistry:
    def test_record_and_snapshot(self):
        reg = linkstats.LinkRegistry()
        # 10 MB in 0.1 s post-first-byte => 100 MB/s
        for _ in range(4):
            reg.record("h1", "reduction", 10_000_000, 0.105,
                       first_byte_s=0.005)
        m = reg.snapshot()
        assert m.version == 4
        (s,) = m.entries
        assert (s.peer, s.plane, s.local) == ("h1", "reduction", False)
        assert s.samples == 4 and s.bytes_total == 40_000_000
        assert s.goodput_bps == pytest.approx(1e8, rel=0.01)
        assert s.rtt_p50_ms == pytest.approx(5.0, rel=0.01)
        assert s.rtt_p99_ms == pytest.approx(5.0, rel=0.01)

    def test_version_monotone_and_frozen(self):
        reg = linkstats.LinkRegistry()
        reg.record("h1", "rpc", 0, 0.001, first_byte_s=0.001)
        m1 = reg.snapshot()
        m2 = reg.snapshot()
        # equal versions name an identical matrix
        assert m1.version == m2.version
        assert [e.peer for e in m1.entries] == [e.peer for e in m2.entries]
        reg.record("h2", "rpc", 0, 0.001, first_byte_s=0.001)
        assert reg.snapshot().version > m1.version

    def test_rpc_plane_is_rtt_only(self):
        reg = linkstats.LinkRegistry()
        # whole wall == first byte: zero transfer leg, no goodput claim
        reg.record("h1", "rpc", 0, 0.002, first_byte_s=0.002)
        s = reg.snapshot().get("h1", "rpc")
        assert s.goodput_bps == 0.0
        assert s.rtt_p50_ms == pytest.approx(2.0, rel=0.01)

    def test_wan_pseudo_host_never_merges_with_local(self):
        reg = linkstats.LinkRegistry()
        # the same physical host measured as local fabric AND as a
        # shaped (WAN-modeled) boundary link: distinct keys, distinct
        # estimates — the two can never average together
        reg.record("hostA", "reduction", 1 << 20, 0.001, local=True)
        reg.record("hostA#g1", "reduction", 1 << 20, 0.1,
                   first_byte_s=0.05, local=False)
        m = reg.snapshot()
        loc = m.get("hostA", "reduction")
        wan = m.get("hostA#g1", "reduction")
        assert loc.local and not wan.local
        assert loc.goodput_bps > wan.goodput_bps * 10

    def test_decay_tracks_regime_change(self):
        reg = linkstats.LinkRegistry()
        for _ in range(32):  # old regime: 100 MB/s
            reg.record("h1", "fragments", 1_000_000, 0.01)
        for _ in range(200):  # new regime: 10 MB/s
            reg.record("h1", "fragments", 1_000_000, 0.1)
        g = reg.snapshot().get("h1", "fragments").goodput_bps
        assert g == pytest.approx(1e7, rel=0.3)

    def test_reset_rereads_env(self, monkeypatch):
        monkeypatch.setenv("TORCHFT_LINK_WINDOW", "4")
        reg = linkstats.LinkRegistry()
        reg.reset()
        for ms in (1, 2, 3, 4, 5, 6, 7, 8):
            reg.record("h1", "rpc", 0, ms / 1e3, first_byte_s=ms / 1e3)
        # window 4: only the last 4 first-byte samples survive
        s = reg.snapshot().get("h1", "rpc")
        assert s.rtt_p50_ms >= 6.0


class TestTopkLabel:
    def test_first_k_keep_name_then_fold(self, monkeypatch):
        monkeypatch.setenv("TORCHFT_LINK_TOPK", "3")
        reg = linkstats.LinkRegistry()
        reg.reset()
        labels = [reg.peer_topk_label(f"h{i}") for i in range(8)]
        assert labels[:3] == ["h0", "h1", "h2"]
        assert set(labels[3:]) == {"other"}
        # stable on re-ask: at most K+1 distinct label values ever
        assert reg.peer_topk_label("h0") == "h0"
        assert reg.peer_topk_label("h7") == "other"
        assert len(set(labels)) == 4


class TestDigest:
    def test_empty_registry_yields_none(self):
        assert linkstats.LinkRegistry().maybe_digest("me") is None

    def test_digest_shape_and_rate_limit(self, monkeypatch):
        monkeypatch.setenv("TORCHFT_LINK_REPORT_S", "60")
        reg = linkstats.LinkRegistry()
        reg.reset()
        reg.record("h1", "reduction", 1 << 20, 0.01, first_byte_s=0.001)
        d = reg.maybe_digest("me")
        assert d["host"] == "me"
        (row,) = d["rows"]
        assert row["peer"] == "h1" and row["plane"] == "reduction"
        assert not row["local"] and row["samples"] == 1
        # rate-limited: not due again for 60 s
        assert reg.maybe_digest("me") is None

    def test_rows_bounded_to_worst_k_per_plane(self, monkeypatch):
        monkeypatch.setenv("TORCHFT_LINK_TOPK", "4")
        monkeypatch.setenv("TORCHFT_LINK_REPORT_S", "0")
        reg = linkstats.LinkRegistry()
        reg.reset()
        for i in range(12):  # goodput ascending with i
            reg.record(f"h{i}", "reduction", 1 << 20, 0.1 / (i + 1))
        d = reg.maybe_digest("me")
        assert len(d["rows"]) == 4
        # worst (lowest goodput) first — the links worth shipping
        assert [r["peer"] for r in d["rows"]] == ["h0", "h1", "h2", "h3"]

    def test_digest_refreshes_bounded_gauges(self, monkeypatch):
        monkeypatch.setenv("TORCHFT_LINK_REPORT_S", "0")
        reg = linkstats.LinkRegistry()
        reg.reset()
        reg.record("h1", "reduction", 1 << 20, 0.1, first_byte_s=0.01)
        reg.record("loc", "reduction", 1 << 20, 0.001, local=True)
        assert reg.maybe_digest("me") is not None
        assert _metrics.LINK_PAIRS.get() == 2
        # the min-goodput aggregate is WAN-only: the local row's memory-
        # speed estimate must not mask a slow wire
        wan_g = reg.snapshot().get("h1", "reduction").goodput_bps
        assert _metrics.LINK_GOODPUT_MIN.get() == pytest.approx(
            wan_g, rel=0.01
        )
        assert _metrics.LINK_GOODPUT.labels(
            peer="h1", plane="reduction"
        ).get() == pytest.approx(wan_g, rel=0.01)


class TestHotPathBudget:
    def test_record_overhead_under_budget(self):
        """Acceptance bar: <= ~2.5 us per record() — it sits inside the
        collective send path.  Best of several batches so a loaded CI
        host doesn't flake the measurement (the flight-recorder budget
        test's protocol); the implementation is one plain lock + a few
        float ops + one deque append."""
        reg = linkstats.LinkRegistry()
        n = 20_000
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            for _i in range(n):
                reg.record("h1", "reduction", 1024, 1e-3,
                           first_byte_s=1e-4)
            best = min(best, (time.perf_counter() - t0) / n)
        assert best <= 2.5e-6, f"record() hot path {best * 1e6:.2f} us"


class TestClosedLoopAccuracy:
    @staticmethod
    def _drive(store, prefix, payload_words, sends, burst=None, **pg_kw):  # noqa: F811
        world = 2
        pgs = [ProcessGroupTCP(timeout=30.0, **pg_kw) for _ in range(world)]
        if burst is not None:
            # what the bucket may save up while its sender is idle
            for pg in pgs:
                pg._bucket.burst = pg._bucket._tokens = float(burst)

        def cfg(rank, _):
            pgs[rank].configure(
                f"{store.address()}/{prefix}", f"r{rank}", rank, world
            )

        run_parallel(world, cfg)
        payload = np.ones(payload_words, dtype=np.float32)

        def run(rank, _):
            for i in range(sends):
                if rank == 0:
                    pgs[0].send(payload, 1, tag=i).wait(timeout=30)
                else:
                    pgs[1].recv(0, tag=i).wait(timeout=30)

        run_parallel(world, run)
        for pg in pgs:
            pg.shutdown()
        wan = [
            s for s in linkstats.LINKS.snapshot().entries
            if s.plane == "reduction" and not s.local
            and s.samples >= sends
        ]
        assert wan, "shaped sends never reached the registry"
        (s,) = wan
        return s

    def test_goodput_matches_declared_bandwidth(self, store):  # noqa: F811
        """The acceptance loop, bandwidth leg: pace a PG wire at a
        declared rate, drive real sends through it, and require the
        passive goodput estimate to land within +/-30% of the declared
        value.  RTT stays off here so the token bucket cannot refill
        during first-byte sleeps (that credit is real bandwidth-delay
        headroom, not pacing error — the RTT leg is measured below).

        Nor may it refill between two sends: on a loaded host the sender
        is off its core for milliseconds between one message's ``wait()``
        and the next, the bucket saves that up (8 ms buy a whole 2 MiB
        message at this rate) and the next message goes at memory speed:
        335.4 and 325.5 MB/s against the bound of 325.0 in two runs of
        the suite.  So the burst is a quarter of a pacing piece here: an
        idle gap buys at most an eighth of a message."""
        linkstats.LINKS.reset()
        gbps = 0.25
        # ~63 MB, 2 MiB per message
        s = self._drive(
            store, "lclpb", 1 << 19, 30, burst=1 << 18, bandwidth_gbps=gbps
        )
        declared = gbps * 1e9
        assert declared * 0.7 <= s.goodput_bps <= declared * 1.3, (
            f"goodput {s.goodput_bps / 1e6:.1f} MB/s vs declared "
            f"{declared / 1e6:.1f} MB/s"
        )

    def test_rtt_matches_declared_latency(self, store):  # noqa: F811
        """...and the RTT leg: small messages on a latency-shaped wire;
        the first-byte p50 must land within +/-30% of the declared RTT."""
        linkstats.LINKS.reset()
        rtt_ms = 20.0
        s = self._drive(store, "lclpr", 256, 6, rtt_ms=rtt_ms)
        assert rtt_ms * 0.7 <= s.rtt_p50_ms <= rtt_ms * 1.3
        assert rtt_ms * 0.7 <= s.rtt_p99_ms <= rtt_ms * 1.3

    def test_boundary_pairs_key_separately_from_local(
        self, store, monkeypatch  # noqa: F811
    ):
        """A same-host peer across the declared topology boundary keys
        under the ``host#gN`` pseudo-host (WAN row); an intra-group peer
        keys under the plain host (local row)."""
        linkstats.LINKS.reset()
        monkeypatch.setenv("TORCHFT_TOPOLOGY", "0;1")
        pgs = make_group(store, 2, prefix="lsep1")
        payload = np.ones(256, dtype=np.float32)

        def run(rank, _):
            if rank == 0:
                pgs[0].send(payload, 1, tag=1).wait(timeout=20)
            else:
                pgs[1].recv(0, tag=1).wait(timeout=20)

        run_parallel(2, run)
        for pg in pgs:
            pg.shutdown()
        wan = [
            s for s in linkstats.LINKS.snapshot().entries
            if s.plane == "reduction" and not s.local
        ]
        assert wan and all("#g" in s.peer for s in wan)

        linkstats.LINKS.reset()
        monkeypatch.setenv("TORCHFT_TOPOLOGY", "0,1")
        pgs = make_group(store, 2, prefix="lsep2")
        run_parallel(2, run)
        for pg in pgs:
            pg.shutdown()
        entries = [
            s for s in linkstats.LINKS.snapshot().entries
            if s.plane == "reduction"
        ]
        assert entries
        assert all(s.local and "#" not in s.peer for s in entries)


class TestEndToEndSlowLink:
    def test_throttled_pair_reaches_diagnose_via_lighthouse(
        self, store, tmp_path  # noqa: F811
    ):
        """The whole plane, closed loop: two wires shaped at declared
        rates -> passive registry -> heartbeat digests -> lighthouse
        matrix (estimates still near the declared rates) -> serialized
        /links.json artifact -> ``torchft-diagnose --links`` names the
        deliberately-throttled pair as the ``slow_link`` culprit.

        The estimate is bytes over the wall clock of each send, and a
        loaded host moves it both ways: a pacing sleep of 4 ms a MB on the
        fast wire overshoots by as much when six test workers share eight
        cores (it reads low), and a sender kept off its core between two
        sends finds the token bucket refilled, up to its burst of 4 MB,
        and sends that at memory speed (it reads high: 325.5 MB/s against
        a bound of 325.0 in PR 45's run of the suite).  The +/-30 % is
        ``TestClosedLoopAccuracy``'s to hold on a quiet host; here the
        fast wire may read 0.35-2 times its rate and the slow one 0.5-1.6
        times, and the rates are 25x apart so that the culprit
        (``SLOW_LINK_RATIO`` 4 under the fleet median) stands at both
        ends of that room: 0.35 * 0.25 > 4 * 1.6 * 0.01."""
        from torchft_tpu.diagnose import analyze_links, load_links

        fast_gbps, slow_gbps = 0.25, 0.01
        linkstats.LINKS.reset()
        TestClosedLoopAccuracy._drive(
            store, "e2ef", 1 << 19, 30, bandwidth_gbps=fast_gbps
        )
        d_fast = linkstats.LINKS.maybe_digest("hfast")
        linkstats.LINKS.reset()
        TestClosedLoopAccuracy._drive(
            store, "e2es", 1 << 18, 24, bandwidth_gbps=slow_gbps
        )
        d_slow = linkstats.LINKS.maybe_digest("hslow")
        assert d_fast and d_slow
        with LighthouseServer(min_replicas=1, join_timeout_ms=50) as srv:
            c = LighthouseClient(srv.address())
            try:
                # two healthy reporters of the fast wire + the throttled
                # one: the fleet median is the fast rate
                c.heartbeat("rf", links=d_fast)
                c.heartbeat("rf2", links=dict(d_fast, host="hfast2"))
                c.heartbeat("rs", links=d_slow)
                doc = c.links()
            finally:
                c.close()
        by_src = {
            (r["src"], r["plane"]): r["goodput_bps"] for r in doc["rows"]
        }
        for src, declared, low, high in (("hfast", fast_gbps * 1e9, 0.35, 2.0),
                                         ("hslow", slow_gbps * 1e9, 0.5, 1.6)):
            g = by_src[(src, "reduction")]
            assert declared * low <= g <= declared * high, (
                f"{src} matrix goodput {g / 1e6:.1f} MB/s vs declared "
                f"{declared / 1e6:.1f} MB/s"
            )
        # the serialized-artifact path the CLI takes
        artifact = tmp_path / "links.json"
        artifact.write_text(json.dumps(doc))
        rep = analyze_links(load_links(str(artifact)))
        assert rep["culprit"]["signal"] == "slow_link"
        assert rep["culprit"]["replica_id"].startswith("link hslow->")


class TestLighthouseAggregation:
    def test_heartbeat_digest_to_matrix_round_trip(self):
        with LighthouseServer(min_replicas=1, join_timeout_ms=50) as srv:
            c = LighthouseClient(srv.address())
            try:
                c.heartbeat("r0", links={
                    "host": "h0",
                    "rows": [_row(peer="h1", goodput=5e7),
                             _row(peer="h2", plane="rpc", goodput=0.0,
                                  rtt_p99=8.0)],
                })
                doc = c.links()
                assert doc["rows_total"] == 2 and doc["hosts"] == 1
                assert doc["reports_total"] == 1
                v1 = doc["version"]
                assert v1 > 0
                by_peer = {r["peer"]: r for r in doc["rows"]}
                assert by_peer["h1"]["src"] == "h0"
                assert by_peer["h1"]["goodput_bps"] == pytest.approx(5e7)
                assert by_peer["h2"]["rtt_p99_ms"] == pytest.approx(8.0)
                assert by_peer["h1"]["age_ms"] >= 0
                # worst = lowest-goodput WAN row, on every page
                assert doc["worst"]["peer"] == "h1"

                # latest-wins per host: a re-report REPLACES h0's rows
                c.heartbeat("r0", links={
                    "host": "h0", "rows": [_row(peer="h3", goodput=9e7)],
                })
                doc2 = c.links()
                assert doc2["rows_total"] == 1
                assert doc2["rows"][0]["peer"] == "h3"
                # monotone matrix version: the new matrix supersedes
                assert doc2["version"] > v1
            finally:
                c.close()

    def test_http_links_json_matches_rpc_and_stays_bounded(self):
        """64 reporting hosts: GET /links.json (default page) stays under
        the 16 KB acceptance budget while fleet truth (rows_total, hosts,
        version, worst) survives pagination."""
        with LighthouseServer(min_replicas=1, join_timeout_ms=50) as srv:
            c = LighthouseClient(srv.address())
            try:
                for i in range(64):
                    c.heartbeat(f"r{i}", links={
                        "host": f"h{i:02d}",
                        "rows": [
                            _row(peer=f"h{(i + 1) % 64:02d}",
                                 goodput=1e8 + i),
                            _row(peer=f"h{(i + 2) % 64:02d}",
                                 plane="fragments", goodput=2e8 + i),
                            _row(peer=f"h{(i + 3) % 64:02d}",
                                 plane="rpc", goodput=0.0, rtt_p99=3.0),
                        ],
                    })
                raw = urllib.request.urlopen(
                    f"http://{srv.address()}/links.json", timeout=5
                ).read()
                assert len(raw) < 16 * 1024, (
                    f"/links.json default page is {len(raw)} B"
                )
                doc = json.loads(raw.decode())
                assert doc["rows_total"] == 192 and doc["hosts"] == 64
                assert doc["pages"] * doc["per_page"] >= 192
                # RPC serves the same document; explicit paging walks it
                page1 = c.links(page=1, per_page=10)
                assert len(page1["rows"]) == 10
                assert page1["rows_total"] == 192
                assert page1["version"] == doc["version"]
            finally:
                c.close()

    def test_serving_staleness_ledger(self):
        """Publisher stamps publish time; nodes carry their held stamp;
        the lighthouse differences them on the single publish clock."""
        with LighthouseServer(min_replicas=1, join_timeout_ms=50) as srv:
            c = LighthouseClient(srv.address())
            try:
                c.serving_heartbeat("pub", "http://p:1", role="publisher",
                                    version=5, version_ms=10_000)
                c.serving_heartbeat("fresh", "http://a:1", role="server",
                                    version=5, version_ms=10_000)
                c.serving_heartbeat("behind", "http://b:1", role="server",
                                    version=4, version_ms=9_400)
                c.serving_heartbeat("unstamped", "http://c:1",
                                    role="server", version=4)
                nodes = {
                    n["replica_id"]: n for n in c.serving_plan()["nodes"]
                }
                assert nodes["fresh"]["staleness_ms"] == 0
                assert nodes["behind"]["staleness_ms"] == 600
                # no stamp = unknown, not zero — never fake freshness
                assert nodes["unstamped"]["staleness_ms"] == -1
            finally:
                c.close()


class TestChaosLinksDrop:
    def test_dropped_report_degrades_to_stale_rows(self):
        """The ``lighthouse.links`` site: an injected drop loses the
        digest (rows age in place) but the heartbeat plane itself keeps
        working — telemetry loss must never wedge liveness."""
        with LighthouseServer(min_replicas=1, join_timeout_ms=50) as srv:
            c = LighthouseClient(srv.address())
            try:
                c.heartbeat("r0", links={
                    "host": "h0", "rows": [_row(peer="h1", goodput=5e7)],
                })
                v1 = c.links()["version"]
                FAULTS.configure([
                    FaultRule(site="lighthouse.links", action="drop",
                              times=1)
                ])
                with pytest.raises(InjectedConnectionDrop):
                    c.heartbeat("r0", links={
                        "host": "h0",
                        "rows": [_row(peer="h1", goodput=6e7)],
                    })
                # liveness survives: the next plain heartbeat goes through
                assert "error" not in c.heartbeat("r0")
                # the matrix degraded to the STALE previous rows — never
                # emptied, never wedged
                doc = c.links()
                assert doc["version"] == v1
                (row,) = doc["rows"]
                assert row["goodput_bps"] == pytest.approx(5e7)
                assert row["age_ms"] >= 0
            finally:
                FAULTS.configure([])
                c.close()


class TestDiagnoseLinks:
    def _doc(self, rows):
        return {"rows": rows, "rows_total": len(rows), "hosts": 3,
                "version": 7}

    def test_sustained_slow_link_named_as_culprit(self):
        from torchft_tpu.diagnose import analyze_links

        rows = [_row(src="h0", peer=f"h{i}", goodput=1e8, samples=20)
                for i in range(1, 5)]
        rows.append(_row(src="h0", peer="h9", goodput=1e7, samples=20))
        rep = analyze_links(self._doc(rows))
        assert rep["culprit"]["signal"] == "slow_link"
        assert rep["culprit"]["replica_id"] == "link h0->h9"
        assert rep["slow_links"][0]["peer"] == "h9"
        assert rep["rows_wan"] == 5

    def test_thin_evidence_never_names_a_culprit(self):
        from torchft_tpu.diagnose import (
            SLOW_LINK_MIN_SAMPLES,
            analyze_links,
        )

        rows = [_row(src="h0", peer=f"h{i}", goodput=1e8, samples=20)
                for i in range(1, 5)]
        # 10x below median but under the sample floor: one unlucky
        # transfer, not a sustained slow wire
        rows.append(_row(src="h0", peer="h9", goodput=1e7,
                         samples=SLOW_LINK_MIN_SAMPLES - 1))
        assert analyze_links(self._doc(rows))["culprit"] is None

    def test_local_rows_never_skew_the_median(self):
        from torchft_tpu.diagnose import analyze_links

        # memory-speed local rows + uniform WAN rows: nothing is slow
        rows = [_row(src="h0", peer="self", local=True, goodput=1e11,
                     samples=50)]
        rows += [_row(src="h0", peer=f"h{i}", goodput=1e8, samples=20)
                 for i in range(1, 4)]
        rep = analyze_links(self._doc(rows))
        assert rep["culprit"] is None
        assert rep["median_wan_goodput_bps"] == pytest.approx(1e8)

    def test_wire_split_quantifies_the_named_culprit(self):
        from torchft_tpu.diagnose import analyze_links, apply_wire_split

        rows = [_row(src="h0", peer=f"h{i}", goodput=1e8, samples=20)
                for i in range(1, 5)]
        rows.append(_row(src="h0", peer="h9", goodput=2e7, samples=20))
        links_rep = analyze_links(self._doc(rows))
        step = {
            "step": 3, "critical_replica": "r0",
            "replicas": {"r0": {"categories": {"wire": 2.0}}},
        }
        trace_rep = {"steps": [step]}
        apply_wire_split(trace_rep, links_rep)
        # 20 MB/s on a 100 MB/s-median fleet: 1/5 expected, 4/5 excess
        assert step["wire_expected_s"] == pytest.approx(0.4)
        assert step["wire_excess_s"] == pytest.approx(1.6)
        assert step["wire_slow_link"] == "h0->h9"

    def test_wire_split_noop_without_slow_link(self):
        from torchft_tpu.diagnose import analyze_links, apply_wire_split

        rows = [_row(src="h0", peer=f"h{i}", goodput=1e8, samples=20)
                for i in range(1, 5)]
        links_rep = analyze_links(self._doc(rows))
        step = {
            "step": 3, "critical_replica": "r0",
            "replicas": {"r0": {"categories": {"wire": 2.0}}},
        }
        apply_wire_split({"steps": [step]}, links_rep)
        # the split exists to quantify a named culprit, not to invent one
        assert "wire_expected_s" not in step

    def test_render_links_text_calls_out_slow_links(self):
        from torchft_tpu.diagnose import analyze_links, render_links_text

        rows = [_row(src="h0", peer=f"h{i}", goodput=1e8, samples=20)
                for i in range(1, 5)]
        rows.append(_row(src="h0", peer="h9", goodput=1e7, samples=20))
        doc = self._doc(rows)
        text = render_links_text(doc, analyze_links(doc))
        assert "SLOW LINK: h0->h9" in text
        assert "fleet link matrix" in text

    def test_load_links_over_http_and_rejects_garbage(self, tmp_path):
        from torchft_tpu.diagnose import load_links

        with LighthouseServer(min_replicas=1, join_timeout_ms=50) as srv:
            c = LighthouseClient(srv.address())
            try:
                c.heartbeat("r0", links={
                    "host": "h0", "rows": [_row(peer="h1")],
                })
            finally:
                c.close()
            doc = load_links(f"http://{srv.address()}")
            assert doc["rows_total"] == 1
        p = tmp_path / "not_links.json"
        p.write_text(json.dumps({"steps": []}))
        with pytest.raises(ValueError, match="links.json"):
            load_links(str(p))


# ---------------------------------------------------------------------------
# frozen snapshot contract (ISSUE 19 satellite): LinkMatrix.snapshot()
# is the input surface the future plan synthesizer (ROADMAP item 4)
# consumes, so its row schema is pinned in analysis/plan_ir.py the same
# way the native RPC schemas are pinned in protocol.lock — a rename
# breaks HERE, not in the synthesizer.
# ---------------------------------------------------------------------------


class TestSnapshotFrozenContract:
    def _live_stat(self):
        reg = linkstats.LinkRegistry()
        reg.record("h1", "reduction", 10_000_000, 0.105, first_byte_s=0.005)
        (stat,) = reg.snapshot().entries
        return stat

    def test_linkstat_fields_pinned(self):
        import dataclasses as _dc

        from torchft_tpu.analysis import plan_ir as pir

        got = tuple(f.name for f in _dc.fields(linkstats.LinkStat))
        assert got == pir.LINK_SNAPSHOT_FIELDS, (
            "LinkStat changed shape; update plan_ir.LINK_SNAPSHOT_FIELDS "
            "and the plan synthesizer's consumers TOGETHER"
        )

    def test_wire_row_keys_pinned(self):
        from torchft_tpu.analysis import plan_ir as pir

        row = self._live_stat().to_dict()
        assert tuple(row) == pir.LINK_ROW_KEYS, (
            "LinkStat.to_dict() changed the /links.json row schema; "
            "update plan_ir.LINK_ROW_KEYS and every aggregator TOGETHER"
        )
        # the wire row round-trips through JSON without loss of keys
        assert tuple(json.loads(json.dumps(row))) == pir.LINK_ROW_KEYS

    def test_seeded_rename_is_caught(self):
        """Drift-gate selfcheck, wire-drift style: seed a field rename
        and prove the contract comparison actually fires for EVERY
        pinned key (a vacuous gate is worse than none)."""
        from torchft_tpu.analysis import plan_ir as pir

        row = self._live_stat().to_dict()
        for key in pir.LINK_ROW_KEYS:
            mutated = dict(row)
            mutated[f"{key}_v2"] = mutated.pop(key)
            assert tuple(mutated) != pir.LINK_ROW_KEYS, key

    def test_snapshot_values_survive_the_wire_row(self):
        stat = self._live_stat()
        row = stat.to_dict()
        assert row["peer"] == stat.peer and row["plane"] == stat.plane
        assert row["local"] is stat.local
        assert row["samples"] == stat.samples
        assert row["bytes"] == stat.bytes_total  # deliberate short name
        assert row["rtt_ms"] == pytest.approx(stat.rtt_p50_ms, abs=1e-3)
