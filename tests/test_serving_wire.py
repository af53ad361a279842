"""Serving-tier WAN realism + client version pinning (ISSUE 13
satellites).

Leg 1: the serving fetch/relay paths honor the training-side wire model
(``TORCHFT_WIRE_RTT_MS`` / ``TORCHFT_WIRE_GBPS`` scoped by
``TORCHFT_TOPOLOGY``) via utils/wire.py — including the shaped-link
test pinning that fetch p99 stays bounded at 50 ms RTT.

Leg 2: ``ServingClient(pin_version=..., min_version=...)`` — pin-hit,
pin-miss (evicted version 503s to the deadline instead of silently
substituting), rollback-floor refusal, and unpinned re-resolution
staying intact.
"""

from __future__ import annotations

import time

import numpy as np
import pytest

from torchft_tpu.checkpointing.http_transport import HTTPTransport
from torchft_tpu.coordination import LighthouseServer
from torchft_tpu.serving import WeightPublisher, ServingClient, fetch_resource
from torchft_tpu.serving import payload as _payload
from torchft_tpu.utils import wire as _wire


def _state(seed: int):
    rng = np.random.default_rng(seed)
    return {
        "w": rng.standard_normal((64, 32)).astype(np.float32),
        "b": rng.standard_normal((32,)).astype(np.float32),
        "step": seed,
    }


class TestWireShaperUnits:
    def test_flat_topology_shapes_every_source(self):
        s = _wire.WireShaper(10.0, 0.0, "", local_hosts={"me"})
        assert s.crosses_boundary("http://me:1234")
        assert s.crosses_boundary("http://far:1234")

    def test_declared_topology_exempts_local_host(self):
        s = _wire.WireShaper(10.0, 0.0, "hosts:2", local_hosts={"me"})
        assert not s.crosses_boundary("http://me:1234")
        assert not s.crosses_boundary("me:1234")
        assert s.crosses_boundary("http://far:1234")

    def test_charge_sleeps_one_rtt(self):
        s = _wire.WireShaper(40.0, 0.0, "", local_hosts={"me"})
        t0 = time.monotonic()
        slept = s.charge("http://far:1", 1024)
        assert time.monotonic() - t0 >= 0.035
        assert slept >= 0.035

    def test_unshaped_or_local_is_free(self):
        assert _wire.WireShaper(0.0, 0.0, "", None).charge("x:1", 1 << 20) == 0.0
        s = _wire.WireShaper(50.0, 0.5, "hosts:2", local_hosts={"me"})
        assert s.charge("http://me:1", 1 << 20) == 0.0

    def test_bandwidth_debt_beyond_burst(self):
        # 1 GB/s, 4 MiB burst: a 12 MiB message owes ~8 MiB of debt
        s = _wire.WireShaper(0.0, 1.0, "", local_hosts={"me"})
        t0 = time.monotonic()
        s.charge("http://far:1", 12 << 20)
        elapsed = time.monotonic() - t0
        assert elapsed >= (8 << 20) / 1e9 * 0.8

    def test_get_shaper_tracks_env(self, monkeypatch):
        monkeypatch.setenv("TORCHFT_WIRE_RTT_MS", "0")
        monkeypatch.setenv("TORCHFT_WIRE_GBPS", "0")
        assert not _wire.get_shaper().active
        monkeypatch.setenv("TORCHFT_WIRE_RTT_MS", "25")
        assert _wire.get_shaper().active


class TestShapedServingFetch:
    """The satellite's shaped-link test: real staged payload, real HTTP
    fetch path, 50 ms simulated RTT — p50 pays the RTT, p99 stays
    bounded (no retry storm or compounding sleeps)."""

    def test_fetch_p99_bounded_at_50ms_rtt(self, monkeypatch):
        transport = HTTPTransport()
        try:
            doc = _payload.encode_payload(_state(3), 5, fragments=2)
            transport.send_checkpoint([], 5, doc, timeout=10)
            base = transport.metadata()
            # unshaped warm-up proves the path works without the model
            fetch_resource(base, 5, "full", timeout=10)
            monkeypatch.setenv("TORCHFT_WIRE_RTT_MS", "50")
            durations = []
            for _ in range(10):
                t0 = time.monotonic()
                got = fetch_resource(base, 5, "full", timeout=10)
                durations.append(time.monotonic() - t0)
            state = _payload.decode_payload(got)[0]
            np.testing.assert_array_equal(state["w"], _state(3)["w"])
            durations.sort()
            p50 = durations[len(durations) // 2]
            p99 = durations[-1]
            # every fetch pays the 50 ms first-byte latency once ...
            assert p50 >= 0.05, f"p50 {p50:.3f}s below the simulated RTT"
            # ... and only once: the tail stays a small multiple of it
            assert p99 < 0.5, f"p99 {p99:.3f}s unbounded under 50 ms RTT"
        finally:
            transport.shutdown()

    def test_declared_topology_keeps_local_fetch_fast(self, monkeypatch):
        transport = HTTPTransport()
        try:
            doc = _payload.encode_payload(_state(4), 2, fragments=1)
            transport.send_checkpoint([], 2, doc, timeout=10)
            monkeypatch.setenv("TORCHFT_WIRE_RTT_MS", "200")
            monkeypatch.setenv("TORCHFT_TOPOLOGY", "hosts:2")
            t0 = time.monotonic()
            fetch_resource(transport.metadata(), 2, "full", timeout=10)
            # transport metadata advertises this machine's hostname:
            # intra-host rides the local fabric unshaped
            assert time.monotonic() - t0 < 0.15
        finally:
            transport.shutdown()


@pytest.fixture
def pub_tier():
    """lighthouse + publisher with a 2-version staging window."""
    lh = LighthouseServer(
        min_replicas=1, heartbeat_timeout_ms=1000, quorum_tick_ms=50
    )
    pub = WeightPublisher(
        lh.address(), fragments=2, max_versions=2, heartbeat_interval=0.05
    )
    yield lh, pub
    pub.shutdown()
    lh.shutdown()


def _wait_latest(client: ServingClient, v: int, timeout: float = 10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if client.latest_version() >= v:
            return
        time.sleep(0.02)
    raise TimeoutError(f"serving tier never advertised v{v}")


class TestServingClientPinning:
    def test_pin_hit_serves_the_pinned_version(self, pub_tier):
        lh, pub = pub_tier
        v1 = pub.publish(_state(1))
        v2 = pub.publish(_state(2))
        client = ServingClient(lh.address(), plan_ttl=0.05, pin_version=v1)
        try:
            _wait_latest(client, v2)
            state, got = client.fetch(timeout=20)
            assert got == v1  # NOT silently upgraded to v2
            np.testing.assert_array_equal(state["w"], _state(1)["w"])
        finally:
            client.close()

    def test_pin_miss_evicted_version_errors_on_503(self, pub_tier):
        lh, pub = pub_tier
        v1 = pub.publish(_state(1))
        pub.publish(_state(2))
        pub.publish(_state(3))  # window=2: v1 evicted
        client = ServingClient(lh.address(), plan_ttl=0.05, pin_version=v1)
        try:
            _wait_latest(client, v1 + 2)
            with pytest.raises(TimeoutError):
                client.fetch(timeout=2.0)
        finally:
            client.close()

    def test_unpinned_re_resolution_still_works(self, pub_tier):
        lh, pub = pub_tier
        v1 = pub.publish(_state(1))
        client = ServingClient(lh.address(), plan_ttl=0.05)
        try:
            _wait_latest(client, v1)
            _, got1 = client.fetch(timeout=20)
            assert got1 == v1
            v2 = pub.publish(_state(2))
            _wait_latest(client, v2)
            state2, got2 = client.fetch(timeout=20)
            assert got2 == v2
            np.testing.assert_array_equal(state2["w"], _state(2)["w"])
        finally:
            client.close()

    def test_min_version_floor_refuses_rollback(self, pub_tier):
        lh, pub = pub_tier
        v1 = pub.publish(_state(1))
        client = ServingClient(
            lh.address(), plan_ttl=0.05, min_version=v1 + 10
        )
        try:
            _wait_latest_any = client.latest_version()  # plan warm
            assert _wait_latest_any >= 0
            with pytest.raises(RuntimeError, match="rollback floor"):
                client.fetch(timeout=5.0)
        finally:
            client.close()

    def test_floor_ratchets_to_fetched_version(self, pub_tier):
        lh, pub = pub_tier
        v1 = pub.publish(_state(1))
        v2 = pub.publish(_state(2))
        client = ServingClient(lh.address(), plan_ttl=0.05)
        try:
            _wait_latest(client, v2)
            _, got = client.fetch(timeout=20)
            assert got == v2
            # an explicit fetch of the OLDER (still staged) version is
            # now a refused rollback, not a silent downgrade
            with pytest.raises(RuntimeError, match="rollback floor"):
                client.fetch(version=v1, timeout=5.0)
        finally:
            client.close()

    def test_pin_below_floor_rejected_at_construction(self, pub_tier):
        lh, _pub = pub_tier
        with pytest.raises(ValueError):
            ServingClient(lh.address(), pin_version=1, min_version=5)
