"""Weight-serving tier: tree synthesis, payload codec, live fan-out
round trips, and the chaos smoke (kill a tree node mid-fetch -> the
client completes from a failover source with bitwise-identical weights).

docs/architecture.md "Weight-serving tier"; ISSUE 12.
"""

import threading
import time

import numpy as np
import pytest

from torchft_tpu.coordination import LighthouseClient, LighthouseServer
from torchft_tpu.ops import quantization as q
from torchft_tpu.serving import (
    ServingClient,
    ServingReplica,
    WeightPublisher,
    changed_fragments,
    decode_payload,
    encode_payload,
    fetch_resource,
)
from torchft_tpu.utils import faults as _faults


def _wait_until(cond, timeout=15.0, interval=0.02, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(interval)
    raise AssertionError(f"timed out waiting for {msg}")


def _state(seed=0):
    rng = np.random.RandomState(seed)
    return {
        "w": rng.randn(16, 32).astype(np.float32),
        "b": rng.randn(8).astype(np.float32),
        "step": int(seed),
    }


def _int8_roundtrip(a):
    return q.dequantize(
        *q.quantize(a, q.WIRE_INT8), a.shape, np.dtype(np.float32)
    )


# ---------------------------------------------------------------------------
# lighthouse plan synthesis
# ---------------------------------------------------------------------------


class TestServingPlan:
    def test_tree_shape_and_determinism(self):
        with LighthouseServer(min_replicas=1, serving_fanout=2) as server:
            c = LighthouseClient(server.address())
            c.serving_heartbeat("pub", "http://p:1", role="publisher",
                                version=3)
            for i in range(7):
                c.serving_heartbeat(f"s{i}", f"http://s{i}:1", role="server")
            plan = c.serving_plan()
            assert plan["root_source"] == "http://p:1"
            assert plan["latest_version"] == 3
            assert plan["fanout"] == 2
            nodes = {n["replica_id"]: n for n in plan["nodes"]}
            assert len(nodes) == 7
            roots = [n for n in plan["nodes"] if n["parent"] == ""]
            assert len(roots) == 1 and roots[0]["replica_id"] == "s0"
            # binary fan-out: depths 0,1,1,2,2,2,2
            assert sorted(n["depth"] for n in plan["nodes"]) == [
                0, 1, 1, 2, 2, 2, 2,
            ]
            assert plan["depth"] == 2
            # every non-root parent is a real node address
            addrs = {n["address"] for n in plan["nodes"]}
            for n in plan["nodes"]:
                if n["parent"]:
                    assert n["parent"] in addrs
            # child counts match the parent edges
            for rid, n in nodes.items():
                kids = sum(
                    1 for m in plan["nodes"] if m["parent"] == n["address"]
                )
                assert kids == n["children"], rid
            # identical membership -> identical tree on re-read
            plan2 = c.serving_plan()
            assert plan2["nodes"] == plan["nodes"]
            assert plan2["epoch"] == plan["epoch"]

    def test_epoch_bumps_on_membership_not_version(self):
        with LighthouseServer(min_replicas=1) as server:
            c = LighthouseClient(server.address())
            e0 = c.serving_heartbeat("a", "http://a:1", role="server")[
                "plan_epoch"
            ]
            # refresh with a new VERSION only: no tree-shape change
            e1 = c.serving_heartbeat(
                "a", "http://a:1", role="server", version=9
            )["plan_epoch"]
            assert e1 == e0
            # a join changes the shape
            e2 = c.serving_heartbeat("b", "http://b:1", role="server")[
                "plan_epoch"
            ]
            assert e2 > e1
            # so does an address change of an existing member
            e3 = c.serving_heartbeat("a", "http://a:2", role="server")[
                "plan_epoch"
            ]
            assert e3 > e2

    def test_expiry_reforms_tree(self):
        with LighthouseServer(
            min_replicas=1, heartbeat_timeout_ms=300, quorum_tick_ms=50
        ) as server:
            c = LighthouseClient(server.address())
            c.serving_heartbeat("a", "http://a:1", role="server")
            e = c.serving_heartbeat("b", "http://b:1", role="server")[
                "plan_epoch"
            ]

            def alive():
                # keep "a" fresh; let "b" expire
                c.serving_heartbeat("a", "http://a:1", role="server")
                plan = c.serving_plan()
                return (
                    [n["replica_id"] for n in plan["nodes"]],
                    plan["epoch"],
                )

            _wait_until(
                lambda: alive() == (["a"], e + 1) or alive()[0] == ["a"],
                timeout=10,
                msg="expired member pruned",
            )
            ids, epoch = alive()
            assert ids == ["a"]
            assert epoch > e

    def test_capacity_overrides_fanout(self):
        with LighthouseServer(min_replicas=1, serving_fanout=2) as server:
            c = LighthouseClient(server.address())
            c.serving_heartbeat("s0", "http://s0:1", role="server",
                                capacity=4)
            for i in range(1, 5):
                c.serving_heartbeat(f"s{i}", f"http://s{i}:1", role="server")
            plan = c.serving_plan()
            root = [n for n in plan["nodes"] if n["parent"] == ""][0]
            assert root["replica_id"] == "s0"
            assert root["children"] == 4  # capacity=4 beat the fanout
            assert plan["depth"] == 1

    def test_bad_role_rejected(self):
        from torchft_tpu.coordination import RpcError

        with LighthouseServer(min_replicas=1) as server:
            c = LighthouseClient(server.address())
            with pytest.raises(RpcError, match="role"):
                c.serving_heartbeat("x", "http://x:1", role="tree")

    def test_status_and_serving_json_surface(self):
        import json as _json
        import urllib.request

        with LighthouseServer(min_replicas=1) as server:
            c = LighthouseClient(server.address())
            c.serving_heartbeat("pub", "http://p:1", role="publisher",
                                version=5)
            c.serving_heartbeat("s0", "http://s0:1", role="server")
            st = c.status()
            assert st["serving"]["publishers"] == 1
            assert st["serving"]["servers"] == 1
            assert st["serving"]["latest_version"] == 5
            with urllib.request.urlopen(
                f"http://{server.address()}/serving.json"
            ) as f:
                doc = _json.load(f)
            assert doc["latest_version"] == 5
            assert [n["replica_id"] for n in doc["nodes"]] == ["s0"]
            mtx = urllib.request.urlopen(
                f"http://{server.address()}/metrics"
            ).read().decode()
            assert "torchft_lighthouse_serving_epoch" in mtx
            assert (
                'torchft_lighthouse_serving_replicas{role="publisher"} 1'
                in mtx
            )


# ---------------------------------------------------------------------------
# payload codec
# ---------------------------------------------------------------------------


class TestPayloadCodec:
    def test_f32_roundtrip_bitwise(self):
        sd = _state(1)
        doc = encode_payload(sd, 7, wire="f32", fragments=2)
        state, manifest, _ = decode_payload(doc)
        assert manifest["version"] == 7
        np.testing.assert_array_equal(state["w"], sd["w"])
        np.testing.assert_array_equal(state["b"], sd["b"])
        assert state["step"] == sd["step"]

    def test_int8_matches_collective_codec(self):
        sd = _state(2)
        doc = encode_payload(sd, 1, wire="int8")
        state, _, _ = decode_payload(doc)
        np.testing.assert_array_equal(state["w"], _int8_roundtrip(sd["w"]))
        np.testing.assert_array_equal(state["b"], _int8_roundtrip(sd["b"]))
        # non-float leaves pass through untouched
        assert state["step"] == sd["step"]

    def test_encoding_deterministic(self):
        sd = _state(3)
        d1 = encode_payload(sd, 1, wire="int8", fragments=3)
        d2 = encode_payload(sd, 1, wire="int8", fragments=3)
        m1 = d1["frag:manifest"]["digests"]
        m2 = d2["frag:manifest"]["digests"]
        assert m1 == m2

    def test_changed_fragments_detects_delta(self):
        sd = _state(4)
        doc1 = encode_payload(sd, 1, fragments=4)
        man1 = doc1["frag:manifest"]
        sd2 = dict(sd)
        sd2["b"] = sd["b"] + 1.0
        doc2 = encode_payload(sd2, 2, fragments=4)
        man2 = doc2["frag:manifest"]
        moved = changed_fragments(man2, man1)
        # only the fragment holding "b" moved
        assert len(moved) == 1
        # no previous manifest -> everything moved
        assert changed_fragments(man2, None) == man2["fragments"]
        # delta decode: merge the moved fragment over v1's leaves
        _, _, leaves1 = decode_payload(doc1)
        subset = {"frag:manifest": man2}
        for name in moved:
            subset[f"frag:{name}"] = doc2[f"frag:{name}"]
        state, _, _ = decode_payload(subset, prev=(man1, leaves1))
        np.testing.assert_array_equal(state["b"], sd2["b"])
        np.testing.assert_array_equal(state["w"], sd["w"])

    def test_incomplete_delta_is_loud(self):
        sd = _state(5)
        doc = encode_payload(sd, 1, fragments=2)
        subset = {
            "frag:manifest": doc["frag:manifest"],
            "frag:0": doc["frag:0"],
        }
        with pytest.raises(ValueError, match="missing leaf"):
            decode_payload(subset)

    def test_bad_wire_rejected(self):
        with pytest.raises(ValueError, match="wire"):
            encode_payload(_state(0), 1, wire="fp4")


# ---------------------------------------------------------------------------
# live fan-out round trips
# ---------------------------------------------------------------------------


@pytest.fixture
def tier():
    """lighthouse + int8 publisher + 3 serving replicas + client."""
    lh = LighthouseServer(
        min_replicas=1, heartbeat_timeout_ms=1000, quorum_tick_ms=50,
        serving_fanout=2,
    )
    pub = WeightPublisher(
        lh.address(), wire="int8", fragments=2, heartbeat_interval=0.1
    )
    reps = [
        ServingReplica(
            lh.address(), replica_id=f"srv{i}", poll_interval=0.05,
            fetch_timeout=10.0,
        )
        for i in range(3)
    ]
    client = ServingClient(lh.address(), plan_ttl=0.1)
    yield lh, pub, reps, client
    client.close()
    for r in reps:
        try:
            r.shutdown()
        except Exception:  # noqa: BLE001 - some are killed by the test
            pass
    pub.shutdown()
    lh.shutdown()


class TestServingRoundtrip:
    def test_publish_relay_fetch_bitwise(self, tier):
        lh, pub, reps, client = tier
        sd = _state(10)
        v = pub.publish(sd)
        state, got = client.fetch(timeout=20)
        assert got == v
        np.testing.assert_array_equal(state["w"], _int8_roundtrip(sd["w"]))
        assert state["step"] == sd["step"]
        # relays converge to the published version
        _wait_until(
            lambda: all(r.version() == v for r in reps),
            msg="relays converged",
        )
        # every node serves BITWISE-identical decoded weights
        from torchft_tpu.serving import fetch_resource, payload as _p

        docs = [
            fetch_resource(r.address(), v, "full", timeout=10) for r in reps
        ]
        states = [_p.decode_payload(d)[0] for d in docs]
        for s in states:
            np.testing.assert_array_equal(s["w"], states[0]["w"])
            np.testing.assert_array_equal(s["w"], state["w"])

    def test_delta_fetch_moves_changed_fragment_only(self, tier):
        lh, pub, reps, client = tier
        sd = _state(11)
        v1 = pub.publish(sd)
        state1, _ = client.fetch(timeout=20)
        sd2 = dict(sd)
        sd2["b"] = sd["b"] + 1.0
        v2 = pub.publish(sd2)

        def fetched_v2():
            state, got = client.fetch(timeout=10)
            return got == v2 and np.array_equal(
                state["b"], _int8_roundtrip(sd2["b"])
            )

        _wait_until(fetched_v2, msg="delta fetch of v2")
        # the held version advanced (delta path keeps the leaf cache)
        assert client._held_version == v2

    def test_publish_version_monotone(self, tier):
        lh, pub, reps, client = tier
        pub.publish(_state(0), version=5)
        with pytest.raises(ValueError, match="monotone"):
            pub.publish(_state(0), version=5)

    def test_manager_publish_hook(self, tier):
        """Manager.attach_weight_publisher publishes the committed user
        state as version=step — DEFERRED until the next round / shutdown
        (the user's optimizer update lands after should_commit returns),
        and a publisher failure never escapes."""
        from torchft_tpu.manager import Manager

        lh, pub, reps, client = tier
        m = object.__new__(Manager)
        from torchft_tpu.utils.rwlock import RWLock
        import logging as _logging

        m._state_dict_lock = RWLock(timeout=5)
        m._user_state_dicts = {"model": lambda: _state(12)}
        m._logger = _logging.getLogger("test_manager_publish")
        m._weight_publisher = None
        m._publish_executor = None
        m._publish_pending = 3
        m._flush_pending_publish()  # unattached: no-op, pending cleared
        assert m._publish_pending is None
        assert pub.latest_version() == 0
        m.attach_weight_publisher(pub)
        m._publish_pending = 3  # what a committed step 3 would set
        # publish runs on the manager's single-worker executor (the
        # training thread only snapshots); wait=True drains it
        m._flush_pending_publish(wait=True)
        assert pub.latest_version() == 3
        m._flush_pending_publish(wait=True)  # idempotent: nothing pending
        assert pub.latest_version() == 3
        state, got = client.fetch(timeout=20)
        assert got == 3
        np.testing.assert_array_equal(
            state["model"]["w"], _int8_roundtrip(_state(12)["w"])
        )

        class _Boom:
            def publish(self, *a, **k):
                raise RuntimeError("publisher down")

        m.attach_weight_publisher(_Boom())
        m._publish_pending = 4
        m._flush_pending_publish(wait=True)  # logged, never raised


# ---------------------------------------------------------------------------
# chaos: kill a tree node mid-fetch -> failover completes bitwise
# ---------------------------------------------------------------------------


class TestServingChaos:
    def test_kill_tree_node_mid_fetch_failover_bitwise(self, tier):
        """The tier-1 serving chaos smoke (`make serve-smoke`): one
        interior/root tree node dies while clients fetch; every client
        completes from a failover source with weights bitwise-identical
        to the published payload, and the lighthouse re-forms the tree
        (epoch bump) around the corpse."""
        lh, pub, reps, client = tier
        sd = _state(20)
        v = pub.publish(sd)
        expected, _ = client.fetch(timeout=20)
        _wait_until(
            lambda: all(r.version() == v for r in reps),
            msg="relays converged",
        )
        plan = client.plan(refresh=True)
        epoch0 = plan["epoch"]
        # victim: the ROOT relay (every other node's ancestor — the
        # worst-case interior death)
        root = [n for n in plan["nodes"] if n["parent"] == ""][0]
        victim = next(r for r in reps if r.replica_id() == root["replica_id"])

        results = {}

        def _fetch(i):
            try:
                state, got = ServingClient(
                    lh.address(), plan_ttl=0.1, client_id=str(i)
                ).fetch(version=v, timeout=30)
                results[i] = (state, got)
            except Exception as e:  # noqa: BLE001 - asserted below
                results[i] = e

        threads = [
            threading.Thread(target=_fetch, args=(i,), daemon=True)
            for i in range(4)
        ]
        for t in threads:
            t.start()
        victim.shutdown()  # mid-fetch kill
        for t in threads:
            t.join(timeout=60)
            assert not t.is_alive(), "client fetch wedged"
        for i, res in results.items():
            assert not isinstance(res, Exception), f"client {i}: {res}"
            state, got = res
            assert got == v
            np.testing.assert_array_equal(state["w"], expected["w"])
            np.testing.assert_array_equal(state["b"], expected["b"])
        # the tree re-forms without the victim
        def reformed():
            p = client.plan(refresh=True)
            ids = [n["replica_id"] for n in p["nodes"]]
            return victim.replica_id() not in ids and p["epoch"] > epoch0

        _wait_until(reformed, msg="tree re-formed after node death")
        # and a NEW publish still reaches clients through the survivors
        sd2 = _state(21)
        v2 = pub.publish(sd2)
        state2, got2 = client.fetch(version=v2, timeout=30)
        assert got2 == v2
        np.testing.assert_array_equal(
            state2["w"], _int8_roundtrip(sd2["w"])
        )

    def test_injected_fetch_fault_fails_over(self, tier):
        """serving.fetch chaos injection: the client's own site firing
        surfaces (scheduled), while relay-side transport drops are
        absorbed by failover."""
        lh, pub, reps, client = tier
        v = pub.publish(_state(30))
        client.fetch(timeout=20)  # warm, no faults
        _faults.FAULTS.configure(
            [_faults.FaultRule(site="serving.fetch", action="raise",
                               step=v, times=1)],
            seed=7,
        )
        try:
            with pytest.raises(_faults.InjectedFault):
                client.fetch(version=v, timeout=10)
            assert _faults.FAULTS.injected("serving.fetch") == 1
            # schedule exhausted: the next fetch completes normally
            state, got = client.fetch(version=v, timeout=20)
            assert got == v
        finally:
            _faults.FAULTS.clear()

    def test_tree_commit_fault_degrades_not_wedges(self):
        """An injected serving.tree_commit failure leaves the replica on
        its old plan (serving what it holds); the next beat adopts."""
        lh = LighthouseServer(
            min_replicas=1, heartbeat_timeout_ms=1000, quorum_tick_ms=50
        )
        pub = WeightPublisher(lh.address(), heartbeat_interval=0.1)
        _faults.FAULTS.configure(
            [_faults.FaultRule(site="serving.tree_commit", action="raise",
                               times=1)],
            seed=3,
        )
        try:
            rep = ServingReplica(
                lh.address(), replica_id="solo", poll_interval=0.05
            )
            v = pub.publish(_state(31))
            # despite the first adoption failing, the replica converges
            _wait_until(lambda: rep.version() == v, msg="replica converged")
            assert _faults.FAULTS.injected("serving.tree_commit") == 1
            assert rep.plan_epoch() >= 0
            rep.shutdown()
        finally:
            _faults.FAULTS.clear()
            pub.shutdown()
            lh.shutdown()


# ---------------------------------------------------------------------------
# streaming relay (ISSUE 14): cut-through, delta relay pulls, zero-decode
# passthrough, poisoned-fragment integrity, deep-tree chaos
# ---------------------------------------------------------------------------


def _chain_tier(n_relays, fragments=4, wire="f32", stream=True,
                poll=0.02):
    """fanout=1 lighthouse + publisher + a CHAIN of n relays (depth
    0..n-1): the deep-tree shape the cut-through path exists for."""
    lh = LighthouseServer(
        min_replicas=1, heartbeat_timeout_ms=1500, quorum_tick_ms=50,
        serving_fanout=1,
    )
    pub = WeightPublisher(
        lh.address(), wire=wire, fragments=fragments,
        heartbeat_interval=0.05,
    )
    reps = [
        ServingReplica(
            lh.address(), replica_id=f"chain{i}", poll_interval=poll,
            fetch_timeout=10.0, stream=stream,
        )
        for i in range(n_relays)
    ]
    return lh, pub, reps


def _teardown(lh, pub, reps):
    for r in reps:
        try:
            r.shutdown()
        except Exception:  # noqa: BLE001 - some are killed by the test
            pass
    pub.shutdown()
    lh.shutdown()


class TestStreamingRelay:
    def test_chain_converges_bitwise_and_decode_stays_manifest_only(self):
        """Depth-3 chain on the streaming path: every relay ends up
        serving bitwise-identical raw fragment bytes (zero-decode
        passthrough — the relay never re-encodes), and the relay decode
        histogram's stream leg stays manifest-sized (~0) while a flat
        pull decodes the whole payload."""
        from torchft_tpu.serving import fetcher as _fetcher
        from torchft_tpu.utils import metrics as _m
        from torchft_tpu.utils.bufpool import POOL

        dec0 = _m.SERVING_RELAY_DECODE.labels(mode="stream").get()
        lh, pub, reps = _chain_tier(3, fragments=4, wire="int8")
        try:
            sd = _state(40)
            v = pub.publish(sd)
            _wait_until(
                lambda: all(r.version() == v for r in reps),
                msg="chain converged",
            )
            # depth really is a chain
            plan = LighthouseClient(lh.address()).serving_plan()
            assert sorted(n["depth"] for n in plan["nodes"]) == [0, 1, 2]
            # passthrough: the raw fragment bytes on every relay are the
            # PUBLISHER'S bytes, verbatim
            man = fetch_resource(
                pub.address(), v, "frag_manifest", timeout=10
            )
            for name in man["fragments"]:
                src = _fetcher.fetch_raw(
                    pub.address(), v, f"frag_{name}", timeout=10
                )
                want = bytes(memoryview(src))
                POOL.give(src)
                for r in reps:
                    got = _fetcher.fetch_raw(
                        r.address(), v, f"frag_{name}", timeout=10
                    )
                    assert bytes(memoryview(got)) == want, (
                        f"relay {r.replica_id()} frag {name} not verbatim"
                    )
                    POOL.give(got)
            # relay decode on the streaming path = manifests only: the
            # 3-relay chain pulled a multi-fragment int8 payload, yet
            # total decode time stays ~0 (no payload codec pass)
            dec = _m.SERVING_RELAY_DECODE.labels(mode="stream").get()
            assert dec["count"] - dec0["count"] >= 3
            assert dec["sum"] - dec0["sum"] < 0.25
            # cut-through occupancy gauge was set to a sane value
            occ = _m.SERVING_CUT_OCCUPANCY.get()
            assert 0.0 <= occ <= 1.0
        finally:
            _teardown(lh, pub, reps)

    def test_relay_delta_pull_moves_only_changed_fragment_bytes(self):
        """Steady-state relay wire bytes scale with the update delta:
        a publish changing ONE leaf moves ~one fragment + manifest per
        relay, not the payload (asserted via
        torchft_serving_fetch_bytes{role=relay})."""
        from torchft_tpu.utils import metrics as _m

        lh, pub, reps = _chain_tier(2, fragments=4, wire="f32")
        try:
            rng = np.random.RandomState(3)
            sd = {
                f"l{i}": rng.randn(256, 32).astype(np.float32)
                for i in range(4)
            }
            payload_bytes = sum(a.nbytes for a in sd.values())
            v1 = pub.publish(sd)
            _wait_until(
                lambda: all(r.version() == v1 for r in reps),
                msg="v1 converged",
            )
            b0 = _m.SERVING_FETCH_BYTES.labels(role="relay").get()
            sd2 = dict(sd)
            sd2["l0"] = sd["l0"] + 1.0
            v2 = pub.publish(sd2)
            _wait_until(
                lambda: all(r.version() == v2 for r in reps),
                msg="v2 converged",
            )
            moved = _m.SERVING_FETCH_BYTES.labels(role="relay").get() - b0
            # 2 relays x (manifest + 1 changed fragment of 4): well under
            # one full payload, let alone two
            assert moved < payload_bytes, (
                f"delta relay pull moved {moved} bytes "
                f">= payload {payload_bytes}"
            )
            # and the content is right everywhere
            state, _, _ = decode_payload(
                fetch_resource(reps[-1].address(), v2, "full", timeout=10)
            )
            np.testing.assert_array_equal(state["l0"], sd2["l0"])
            np.testing.assert_array_equal(state["l1"], sd["l1"])
        finally:
            _teardown(lh, pub, reps)

    def test_flat_mode_roundtrip_still_works(self):
        """``stream=False`` keeps the whole-payload store-and-forward
        path functional, and its decode histogram leg is NON-zero."""
        from torchft_tpu.utils import metrics as _m

        dec0 = _m.SERVING_RELAY_DECODE.labels(mode="flat").get()
        lh, pub, reps = _chain_tier(2, fragments=2, wire="int8",
                                    stream=False)
        try:
            sd = _state(41)
            v = pub.publish(sd)
            _wait_until(
                lambda: all(r.version() == v for r in reps),
                msg="flat chain converged",
            )
            state, _, _ = decode_payload(
                fetch_resource(reps[-1].address(), v, "full", timeout=10)
            )
            np.testing.assert_array_equal(
                state["w"], _int8_roundtrip(sd["w"])
            )
            dec = _m.SERVING_RELAY_DECODE.labels(mode="flat").get()
            assert dec["count"] - dec0["count"] >= 2
        finally:
            _teardown(lh, pub, reps)

    def test_torn_version_never_serves_whole_document(self):
        """Cut-through safety at the transport: while a version streams
        in, staged fragments serve individually but full/metadata 503
        (retryable) — a torn payload can never be read whole."""
        import urllib.error
        import urllib.request

        from torchft_tpu.checkpointing.http_transport import HTTPTransport

        tr = HTTPTransport(timeout=5.0)
        try:
            doc = encode_payload(_state(42), 7, fragments=2)
            manifest = doc["frag:manifest"]
            tr.begin_streamed_checkpoint(7, {"frag:manifest": manifest})
            tr.stage_streamed_part(7, "frag:0", doc["frag:0"])
            base = tr.metadata()
            # staged fragment serves mid-stream (this IS cut-through)
            raw = urllib.request.urlopen(
                f"{base}/checkpoint/7/frag_0", timeout=5
            ).read()
            assert raw == doc["frag:0"]
            # missing fragment: retryable 503, not 404
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(
                    f"{base}/checkpoint/7/frag_1", timeout=5
                )
            assert ei.value.code == 503
            # whole-document reads refuse the torn version
            for what in ("full", "metadata"):
                with pytest.raises(urllib.error.HTTPError) as ei:
                    urllib.request.urlopen(
                        f"{base}/checkpoint/7/{what}", timeout=5
                    )
                assert ei.value.code == 503, what
            tr.stage_streamed_part(7, "frag:1", doc["frag:1"])
            tr.finish_streamed_checkpoint(7)
            got = urllib.request.urlopen(
                f"{base}/checkpoint/7/full", timeout=5
            )
            assert got.status == 200
            # complete document: an unknown fragment is back to 404
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(
                    f"{base}/checkpoint/7/frag_9", timeout=5
                )
            assert ei.value.code == 404
        finally:
            tr.shutdown()


class TestRelayIntegrity:
    def _poisoned_pair(self, version=1):
        """Two standalone staged sources for one version: POISONED (one
        fragment's bytes flipped, manifest digests untouched) and GOOD."""
        from torchft_tpu.checkpointing.http_transport import HTTPTransport

        sd = _state(50)
        doc = encode_payload(sd, version, fragments=2)
        bad = dict(doc)
        raw = bytearray(doc["frag:0"])
        raw[-1] ^= 0xFF
        bad["frag:0"] = bytes(raw)
        poisoned = HTTPTransport(timeout=5.0)
        poisoned.send_checkpoint([], version, bad, timeout=5)
        good = HTTPTransport(timeout=5.0)
        good.send_checkpoint([], version, doc, timeout=5)
        return sd, doc, poisoned, good

    def test_poisoned_fragment_refetched_from_other_source(self):
        """Digest mismatch on a relayed fragment = dead source: the pull
        fails over and completes from a good source, and the poisoned
        bytes are NEVER staged or served."""
        lh = LighthouseServer(
            min_replicas=1, heartbeat_timeout_ms=1500, quorum_tick_ms=50
        )
        sd, doc, poisoned, good = self._poisoned_pair()
        rep = ServingReplica(
            lh.address(), replica_id="victim", poll_interval=5.0,
            fetch_timeout=8.0,
        )
        try:
            rep._parent = poisoned.metadata()
            rep._root_source = good.metadata()
            rep._pull(1)
            assert rep.version() == 1
            # served fragment bytes are the GOOD ones
            from torchft_tpu.serving import fetcher as _fetcher
            from torchft_tpu.utils.bufpool import POOL

            buf = _fetcher.fetch_raw(rep.address(), 1, "frag_0", timeout=5)
            got = bytes(memoryview(buf))
            POOL.give(buf)
            assert got == doc["frag:0"]
            state, _, _ = decode_payload(
                fetch_resource(rep.address(), 1, "full", timeout=5)
            )
            np.testing.assert_array_equal(state["w"], sd["w"])
        finally:
            rep.shutdown()
            poisoned.shutdown()
            good.shutdown()
            lh.shutdown()

    def test_poisoned_only_source_never_stages(self):
        """With no clean source, the pull fails loudly and the relay
        keeps advertising nothing — children polling the fragment get
        503s, never poisoned bytes."""
        import urllib.error
        import urllib.request

        lh = LighthouseServer(
            min_replicas=1, heartbeat_timeout_ms=1500, quorum_tick_ms=50
        )
        _sd, _doc, poisoned, good = self._poisoned_pair()
        good.shutdown()  # only the poisoned source remains
        rep = ServingReplica(
            lh.address(), replica_id="victim2", poll_interval=5.0,
            fetch_timeout=2.0,
        )
        try:
            rep._parent = poisoned.metadata()
            rep._root_source = ""
            with pytest.raises(ConnectionError):
                rep._pull(1)
            assert rep.version() == 0
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(
                    f"{rep.address()}/checkpoint/1/frag_0", timeout=5
                )
            assert ei.value.code == 503
        finally:
            rep.shutdown()
            poisoned.shutdown()
            lh.shutdown()


class TestDeepTreeChaos:
    def test_depth3_kill_interior_mid_stream_bitwise(self):
        """Depth-3 chaos variant of the tree test: an INTERIOR relay is
        killed while the cut-through stream is in flight (serving.frag
        delay stretches it); the chain re-forms, every concurrent client
        completes bitwise-identical, and the leaf still converges."""
        lh, pub, reps = _chain_tier(3, fragments=6, wire="int8",
                                    poll=0.02)
        try:
            sd0 = _state(60)
            v0 = pub.publish(sd0)
            _wait_until(
                lambda: all(r.version() == v0 for r in reps),
                msg="warm converge",
            )
            plan = LighthouseClient(lh.address()).serving_plan()
            interior = [
                n for n in plan["nodes"] if 0 < n["depth"] < 2
            ][0]
            victim = next(
                r for r in reps if r.replica_id() == interior["replica_id"]
            )
            # stretch every fragment fetch so the kill lands mid-stream
            _faults.FAULTS.configure(
                [_faults.FaultRule(site="serving.frag", action="delay",
                                   delay=0.08, times=-1)],
                seed=11,
            )
            sd1 = _state(61)
            expected = {
                k: (_int8_roundtrip(a) if isinstance(a, np.ndarray) else a)
                for k, a in sd1.items()
            }
            results = {}

            def _fetch(i):
                try:
                    c = ServingClient(
                        lh.address(), plan_ttl=0.1, client_id=f"deep{i}"
                    )
                    state, got = c.fetch(version=v0 + 1, timeout=45)
                    c.close()
                    results[i] = (state, got)
                except Exception as e:  # noqa: BLE001 - asserted below
                    results[i] = e

            v1 = pub.publish(sd1)
            threads = [
                threading.Thread(target=_fetch, args=(i,), daemon=True)
                for i in range(4)
            ]
            for t in threads:
                t.start()
            time.sleep(0.15)  # the stream is mid-flight (6 x 80 ms/hop)
            victim.shutdown()
            for t in threads:
                t.join(timeout=90)
                assert not t.is_alive(), "client fetch wedged"
            _faults.FAULTS.clear()
            for i, res in results.items():
                assert not isinstance(res, Exception), f"client {i}: {res}"
                state, got = res
                assert got == v1
                np.testing.assert_array_equal(state["w"], expected["w"])
                np.testing.assert_array_equal(state["b"], expected["b"])
            # survivors (root + leaf) converge to v1 despite the corpse
            survivors = [r for r in reps if r is not victim]
            _wait_until(
                lambda: all(r.version() >= v1 for r in survivors),
                timeout=30, msg="survivors converged past the kill",
            )
        finally:
            _faults.FAULTS.clear()
            _teardown(lh, pub, reps)


class TestClientDeterminism:
    def test_rotation_stable_across_processes(self):
        """Source rotation must not depend on PYTHONHASHSEED: the seed
        is a sha256 digest of the client id (pinned literal), so a
        restarted client lands on the same leaf."""
        import hashlib

        lh = LighthouseServer(min_replicas=1)
        try:
            a = ServingClient(lh.address(), client_id="client_a")
            b = ServingClient(lh.address(), client_id="client_a")
            c = ServingClient(lh.address(), client_id="client_b")
            want = int.from_bytes(
                hashlib.sha256(b"client_a").digest()[:8], "big"
            )
            assert a._rot == b._rot == want
            assert c._rot != a._rot
            for cl in (a, b, c):
                cl.close()
        finally:
            lh.shutdown()

    def test_frag_drop_absorbed_by_poll_policy(self):
        """The documented serving.frag contract: an injected drop takes
        the broken-connection path INSIDE the 503-poll policy and is
        retried within the budget — the fetch still completes."""
        from torchft_tpu.checkpointing.http_transport import HTTPTransport
        from torchft_tpu.serving import fetcher as _fetcher
        from torchft_tpu.utils.bufpool import POOL

        tr = HTTPTransport(timeout=5.0)
        try:
            doc = encode_payload(_state(70), 1, fragments=2)
            tr.send_checkpoint([], 1, doc, timeout=5)
            _faults.FAULTS.configure(
                [_faults.FaultRule(site="serving.frag", action="drop",
                                   times=1)],
                seed=2,
            )
            buf = _fetcher.fetch_raw(tr.metadata(), 1, "frag_0", timeout=10)
            assert bytes(memoryview(buf)) == doc["frag:0"]
            POOL.give(buf)
            assert _faults.FAULTS.injected("serving.frag") == 1
        finally:
            _faults.FAULTS.clear()
            tr.shutdown()

    def test_exhausted_budget_never_goes_negative(self):
        """Satellite regression: the delta manifest fetch clamps its
        deadline — an exhausted budget surfaces as a timeout/connection
        error, never a negative-timeout ValueError from the socket
        layer."""
        lh = LighthouseServer(min_replicas=1)
        try:
            client = ServingClient(lh.address())
            client._held = ({"fragments": [], "digests": {},
                             "num_leaves": 0}, {})
            client._held_version = 1
            with pytest.raises((TimeoutError, ConnectionError, OSError)):
                client._fetch_from(
                    "http://127.0.0.1:9", 2, budget=-1.0, delta=True
                )
            client.close()
        finally:
            lh.shutdown()


# ---------------------------------------------------------------------------
# slow soak: 32 clients, staggered server kills
# ---------------------------------------------------------------------------


@pytest.mark.slow
class TestServingSoak:
    def test_soak_32_clients_staggered_kills(self):
        """32 stub clients fetch continuously while versions publish at
        a cadence and two servers die mid-run: p99 fetch latency stays
        bounded and — after the tree settles around each kill — zero
        fetches fail (failovers are allowed and counted)."""
        lh = LighthouseServer(
            min_replicas=1, heartbeat_timeout_ms=800, quorum_tick_ms=50,
            serving_fanout=2,
        )
        pub = WeightPublisher(
            lh.address(), wire="int8", fragments=2, heartbeat_interval=0.1
        )
        reps = [
            ServingReplica(
                lh.address(), replica_id=f"soak{i}", poll_interval=0.05,
                fetch_timeout=10.0,
            )
            for i in range(6)
        ]
        stop = threading.Event()
        lat: "list" = []
        errors: "list" = []
        lock = threading.Lock()

        def _client_loop(i):
            c = ServingClient(lh.address(), plan_ttl=0.2, client_id=str(i))
            while not stop.is_set():
                t0 = time.perf_counter()
                try:
                    _, got = c.fetch(timeout=20)
                    with lock:
                        lat.append(time.perf_counter() - t0)
                except Exception as e:  # noqa: BLE001 - tallied
                    with lock:
                        errors.append(repr(e))
                time.sleep(0.02)
            c.close()

        try:
            pub.publish(_state(0))
            threads = [
                threading.Thread(target=_client_loop, args=(i,), daemon=True)
                for i in range(32)
            ]
            for t in threads:
                t.start()
            t_end = time.monotonic() + 20
            vi = 1
            killed = 0
            while time.monotonic() < t_end:
                pub.publish(_state(vi))
                vi += 1
                # staggered kills at ~1/3 and ~2/3 of the run
                elapsed = 20 - (t_end - time.monotonic())
                if killed == 0 and elapsed > 6:
                    reps[0].shutdown()
                    killed = 1
                elif killed == 1 and elapsed > 13:
                    reps[3].shutdown()
                    killed = 2
                time.sleep(0.25)
            stop.set()
            for t in threads:
                t.join(timeout=30)
                assert not t.is_alive(), "soak client wedged"
            assert killed == 2
            assert len(lat) > 200, f"too few fetches completed: {len(lat)}"
            # zero failed fetches: every fetch either completed directly
            # or failed over within its deadline
            assert not errors, f"{len(errors)} failed fetches: {errors[:3]}"
            p99 = sorted(lat)[int(len(lat) * 0.99)]
            assert p99 < 10.0, f"p99 fetch latency {p99:.2f}s out of bound"
        finally:
            stop.set()
            for r in reps:
                try:
                    r.shutdown()
                except Exception:  # noqa: BLE001
                    pass
            pub.shutdown()
            lh.shutdown()
