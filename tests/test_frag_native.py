"""Native zero-copy fragment data plane (chaos + contract tests).

Contract layer: bitwise serve with zero user-space copies server-side
(allocation/copy counters), pool-miss-flat republish idiom, GIL-free
receive+digest (budget test), the ``fragdata.enabled`` gate, the
``/nativeport`` discovery route, and per-fetch Python fallback for
unmirrored resources.

Chaos layer: a native-served relay killed mid-stripe fails over
per-fragment and the heal converges bitwise; a poisoned fragment over
the native path is rejected by the digest-of-record (source treated
dead, provenance hop verdict ``mismatch``); a mixed native<->python
fleet interoperates bitwise.

Everything here requires the native library, and fails where it cannot
be loaded: the coordination core is the same ``.so``, so a tree without
it runs nothing.
"""

import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from torchft_tpu.checkpointing import fragdata
from torchft_tpu.checkpointing import fragments as frags
from torchft_tpu.checkpointing.http_transport import HTTPTransport
from torchft_tpu.checkpointing.provenance import PROV
from torchft_tpu.utils import faults
from torchft_tpu.utils import flightrecorder as fr
from torchft_tpu.utils.faults import FaultRule

@pytest.fixture(autouse=True)
def clean_slate():
    assert fragdata.available(), "the native library could not be loaded"
    faults.FAULTS.configure([], seed=0)
    fragdata.reset_port_cache()
    yield
    faults.FAULTS.configure([])
    fragdata.reset_port_cache()


def make_state(leaves: int = 12, seed: int = 7) -> dict:
    rng = np.random.default_rng(seed)
    return {
        "user": {
            f"w{i}": rng.standard_normal(257).astype(np.float32)
            for i in range(leaves)
        },
        "torchft": {"step": 5, "batches_committed": 10},
    }


def clone_state(state: dict) -> dict:
    return {
        "user": {k: v.copy() for k, v in state["user"].items()},
        "torchft": dict(state["torchft"]),
    }


def assert_state_equal(a: dict, b: dict) -> None:
    assert a["torchft"] == b["torchft"]
    assert set(a["user"]) == set(b["user"])
    for k in a["user"]:
        np.testing.assert_array_equal(a["user"][k], b["user"][k])


def stage_raw(transport: HTTPTransport, step: int, parts: dict) -> None:
    transport.begin_streamed_checkpoint(step, {"frag:header": {"n": 1}})
    for name, payload in parts.items():
        transport.stage_streamed_part(step, f"frag:{name}", payload)
    transport.finish_streamed_checkpoint(step)


def python_only_transport(**kw) -> HTTPTransport:
    """A node without the native data plane (one peer of a mixed fleet):
    built while the plane reads as absent."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(fragdata, "enabled", lambda: False)
        return HTTPTransport(**kw)


def served(transport: HTTPTransport, at_least: int) -> dict:
    """The native server's counters once it has booked ``at_least``
    serves: it counts a serve after its last byte is sent, which the
    client that already holds the bytes does not wait for."""
    deadline = time.monotonic() + 5.0
    while (
        transport._frag_native.counters()["serves"] < at_least
        and time.monotonic() < deadline
    ):
        time.sleep(0.01)
    return transport._frag_native.counters()


def fetch_bytes(base: str, step: int, resource: str, timeout=5.0) -> bytes:
    buf = frags.fetch_raw(base, step, resource, timeout=timeout)
    return bytes(memoryview(buf).cast("B"))


@pytest.fixture
def sources():
    """Three native-armed transports stream-staging the SAME state at
    step 5 — bitwise-replicated heal sources over the native plane."""
    state = make_state()
    transports = [HTTPTransport(timeout=10.0) for _ in range(3)]
    threads = [
        threading.Thread(
            target=t.send_checkpoint_streamed,
            args=([1], 5, state, 10.0, 6),
        )
        for t in transports
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    yield state, transports
    for t in transports:
        t.shutdown()


class TestNativeContract:
    def test_serves_bitwise_with_zero_copies(self):
        payload = np.random.default_rng(0).integers(
            0, 256, size=1 << 20, dtype=np.uint8
        ).tobytes()
        t = HTTPTransport(timeout=10.0)
        try:
            assert t._frag_native is not None
            base = t.metadata()
            stage_raw(t, 7, {"w0": payload})
            for _ in range(3):
                assert fetch_bytes(base, 7, "frag_w0") == payload
            c = served(t, 3)
            # steady-state serve is pure writev out of the staged pooled
            # buffer: the ONE copy in the plane is at stage time
            assert c["serves"] >= 3
            assert c["serve_copies"] == 0
            assert c["serve_bytes"] >= 3 * len(payload)
            assert c["stage_copy_bytes"] == len(payload)
        finally:
            t.shutdown()

    def test_pool_misses_flat_across_republishes(self):
        """Fragment sizes repeat across publishes, so after the first
        version warms the pool every restage is a pool hit — the bufpool
        miss-flat idiom, natively."""
        sizes = [1 << 16, 1 << 16, 1 << 18]
        t = HTTPTransport(timeout=10.0)
        try:
            srv = t._frag_native
            assert srv is not None
            for v in range(5):
                if v > 0:
                    t.retire_checkpoint(v - 1)
                stage_raw(
                    t, v,
                    {f"w{i}": bytes([v]) * n for i, n in enumerate(sizes)},
                )
                if v == 0:
                    warm = srv.counters()["pool_misses"]
            c = srv.counters()
            assert c["pool_misses"] == warm, c
            assert c["pool_hits"] >= 4 * len(sizes)
        finally:
            t.shutdown()

    def test_gate_off_forces_python_path(self, monkeypatch):
        payload = b"x" * 4096
        t = HTTPTransport(timeout=10.0)
        monkeypatch.setattr(fragdata, "enabled", lambda: False)
        try:
            stage_raw(t, 2, {"w0": payload})
            assert fetch_bytes(t.metadata(), 2, "frag_w0") == payload
            # the gate is consulted on the CLIENT: the armed server saw
            # no data request
            assert t._frag_native.counters()["serves"] == 0
        finally:
            t.shutdown()

    def test_unmirrored_resource_falls_back_per_fetch(self):
        """A part that is not raw wire bytes (here a dict) is never
        mirrored natively: the native 404 falls back to the Python
        serializer for THAT fetch — and the fallback is flight-recorded
        so a fleet on the slow path is visible post-mortem."""
        t = HTTPTransport(timeout=10.0)
        try:
            raw = b"r" * 2048
            t.begin_streamed_checkpoint(9, {"frag:header": {"n": 1}})
            t.stage_streamed_part(9, "frag:raw", raw)
            t.stage_streamed_part(9, "frag:obj", {"k": 1})
            t.finish_streamed_checkpoint(9)
            base = t.metadata()
            assert fetch_bytes(base, 9, "frag_raw") == raw  # native
            assert len(fetch_bytes(base, 9, "frag_obj")) > 0  # python
            ops = [
                r for r in fr.snapshot()
                if r["op"] == "fragment.native_fallback"
                and r.get("resource") == "frag_obj"
            ]
            assert ops, "fallback fetch not flight-recorded"
            assert served(t, 1)["serves"] == 1
        finally:
            t.shutdown()

    def test_nativeport_discovery_route(self):
        armed = HTTPTransport(timeout=5.0)
        plain = python_only_transport(timeout=5.0)
        try:
            armed_url = (
                f"http://127.0.0.1:{armed._server.server_address[1]}"
                "/nativeport"
            )
            with urllib.request.urlopen(armed_url, timeout=5) as resp:
                assert int(resp.read()) == armed._frag_native.port
            plain_url = (
                f"http://127.0.0.1:{plain._server.server_address[1]}"
                "/nativeport"
            )
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(plain_url, timeout=5)
            assert ei.value.code == 404
        finally:
            armed.shutdown()
            plain.shutdown()

    def test_receive_and_digest_release_the_gil(self):
        """Budget test: while the native client is blocked in a fetch
        (server delays the body via chaos injection), OTHER Python
        threads must keep executing — ctypes drops the GIL around the
        begin/body calls, so a pure-Python ticker makes real progress
        during the native wait.  A GIL-holding receive would freeze it."""
        payload = b"g" * (1 << 20)
        t = HTTPTransport(timeout=10.0)
        try:
            stage_raw(t, 1, {"w0": payload})
            base = t.metadata()
            fetch_bytes(base, 1, "frag_w0")  # warm conn + port cache
            t._frag_native.inject("delay", param_ms=300, count=1)
            stop = threading.Event()
            ticks = [0]

            def ticker():
                while not stop.is_set():
                    ticks[0] += 1

            th = threading.Thread(target=ticker, daemon=True)
            th.start()
            time.sleep(0.02)
            before = ticks[0]
            t0 = time.monotonic()
            got = fetch_bytes(base, 1, "frag_w0")
            elapsed = time.monotonic() - t0
            during = ticks[0] - before
            stop.set()
            th.join(timeout=5)
            assert got == payload
            assert elapsed >= 0.25, elapsed  # the delay actually applied
            # generous floor: a held GIL would yield ~0 progress
            assert during > 10_000, during
            assert t._frag_native.counters()["injected_delays"] == 1
        finally:
            t.shutdown()


class TestStagedInPlace:
    """ISSUE 45: a heal source reserves the buffer a fragment will be
    served from, writes it once and commits it where it lies.  The buffer
    is the native server's, lent to Python until the last view of it is
    gone; ownership is what these tests hold it to."""

    def _slow_reader(self, port: int, step: int, resource: str):
        """A GET against the native server whose body is left in the
        socket: the serve blocks in ``sendmsg`` holding its reference."""
        import socket

        s = socket.socket()
        # asked for before connecting, so the window is small from the start
        s.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 1 << 16)
        s.connect(("127.0.0.1", port))
        s.sendall(
            f"GET /checkpoint/{step}/{resource} HTTP/1.1\r\n"
            f"Host: x\r\n\r\n".encode()
        )
        head = b""
        while b"\r\n\r\n" not in head:
            head += s.recv(256)
        head, _, body = head.partition(b"\r\n\r\n")
        assert head.startswith(b"HTTP/1.1 200")
        length = int(head.lower().split(b"content-length:")[1].split()[0])
        return s, body, length

    def test_fetch_in_flight_across_retire_then_the_buffer_recycles(self):
        import gc
        import hashlib

        # one fragment, far larger than the loopback's buffers
        state = {"w": np.random.default_rng(1).standard_normal(6_000_000)
                 .astype(np.float32)}
        t = HTTPTransport(timeout=10.0)
        try:
            srv = t._frag_native
            digest = t.send_checkpoint_streamed(
                [1], 5, state, 10.0, fragments=1
            )["digests"]["0"]
            c = srv.counters()
            assert (c["pool_misses"], c["pool_hits"]) == (1, 0)
            sock, body, length = self._slow_reader(srv.port, 5, "frag_0")
            # retired under the serve: the slot and its view of the buffer
            # go, the serve's reference keeps the memory as it was
            t.retire_checkpoint(5)
            gc.collect()
            assert t.staged_steps() == []
            # the same size again while the serve still reads the first
            # buffer: a fresh one, never the one in flight
            t.send_checkpoint_streamed([1], 6, state, 10.0, fragments=1)
            c = srv.counters()
            assert (c["pool_misses"], c["pool_hits"]) == (2, 0)
            assert c["serves"] == 0  # the first serve is still under way
            # scribble over the second: the first must not be the same memory
            t._staged[6].sd["frag:0"][:] = 0
            sock.settimeout(10.0)
            while len(body) < length:
                chunk = sock.recv(1 << 20)
                assert chunk, "serve ended short"
                body += chunk
            sock.close()
            assert len(body) == length
            assert hashlib.sha256(body).hexdigest() == digest
            # the serve has let go: now, and only now, the buffer recycles
            deadline = time.monotonic() + 5.0
            while srv.counters()["serves"] < 1 and time.monotonic() < deadline:
                time.sleep(0.01)
            t.send_checkpoint_streamed([1], 7, state, 10.0, fragments=1)
            c = srv.counters()
            assert (c["pool_misses"], c["pool_hits"]) == (2, 1)
            assert c["stage_copy_bytes"] == 0 and c["serve_copies"] == 0
        finally:
            t.shutdown()

    @pytest.mark.parametrize("plane", ["native", "python"])
    def test_reserved_unpublished_fragment_parks_never_partial(
        self, plane, monkeypatch
    ):
        """A reader that asks for a fragment between its reservation and
        its publish waits (long-poll, then 503): it never sees the bytes
        written so far.  Published, it gets all of them."""
        from concurrent.futures import ThreadPoolExecutor

        payload = np.random.default_rng(2).integers(
            0, 256, size=300_000, dtype=np.uint8
        )
        # the server is native either way: the python plane is the
        # fallback a peer without the native plane takes against it
        t = HTTPTransport(timeout=10.0)
        if plane == "python":
            monkeypatch.setattr(fragdata, "enabled", lambda: False)
        try:
            base = t.metadata()
            t.begin_streamed_checkpoint(9, {"frag:header": {"n": 1}})
            buf = t.reserve_streamed_part(9, "frag:0", payload.nbytes)
            assert buf.base is not None  # the native server's memory, lent
            buf[:100_000] = payload[:100_000]  # half-written
            t0 = time.monotonic()
            with pytest.raises(urllib.error.HTTPError) as ei:
                frags._raw_data_plane(
                    base, "/checkpoint/9/frag_0", 9, "frag_0", 0.6
                )
            assert ei.value.code == 503
            assert time.monotonic() - t0 >= 0.3  # it parked first
            with ThreadPoolExecutor(1) as ex:
                fut = ex.submit(
                    frags.fetch_raw, base, 9, "frag_0", 10.0, "heal"
                )
                time.sleep(0.2)
                assert not fut.done()
                buf[100_000:] = payload[100_000:]
                assert t.stage_streamed_part(9, "frag:0", buf, pooled=True) == 0
                got = fut.result(timeout=10.0)
            assert bytes(memoryview(got)) == payload.tobytes()
            c = t._frag_native.counters()
            assert c["stage_inplace_bytes"] == payload.nbytes
            if plane == "native":
                assert c["parked_waits"] >= 2 and c["busy_replies"] >= 1
            else:
                assert c["serves"] == 0  # the slot served the same memory
        finally:
            t.shutdown()

    def test_a_lend_commits_once_whole_and_only_for_its_fragment(self):
        """What the native side refuses to publish in place takes the
        copy: a buffer it never lent, a lend for another fragment, a lend
        handed back in part, a lend already committed."""
        t = HTTPTransport(timeout=10.0)
        try:
            srv = t._frag_native
            t.begin_streamed_checkpoint(3, {"frag:header": {"n": 1}})
            own = np.arange(4096, dtype=np.uint8)
            assert srv.stage(3, "frag_a", own) == own.nbytes
            lent = srv.reserve(3, "frag_b", 4096)
            lent[:] = 7
            assert srv.stage(3, "frag_c", lent) == 4096  # another's name
            assert srv.stage(3, "frag_b", lent[:100]) == 100  # in part
            assert srv.stage(3, "frag_b", lent) == 0  # whole: in place
            assert srv.stage(3, "frag_b", lent) == 4096  # once
            assert srv.reserve(4, "frag_b", 16) is None  # no such version
            assert srv.stage(4, "frag_b", own) is None
            t.finish_streamed_checkpoint(3)
            assert fetch_bytes(t.metadata(), 3, "frag_b") == lent.tobytes()
            c = srv.counters()
            assert c["stage_inplace_bytes"] == 4096
            assert c["stage_copy_bytes"] == 3 * 4096 + 100
        finally:
            t.shutdown()

    def test_concurrent_stagers_and_readers_never_cross_buffers(self):
        """More stagers than cores on one server, each reserving, writing,
        publishing, reading back over the native plane and retiring its
        own versions while the others recycle the same buffer sizes: a
        lend handed to two writers, or a buffer recycled under a reader,
        shows as a body that is not the writer's pattern."""
        import sys
        from concurrent.futures import ThreadPoolExecutor

        t = HTTPTransport(timeout=20.0, max_staged=64)
        base = t.metadata()
        n = 96 * 1024  # one size for all: every release feeds every reserve

        def stager(i: int) -> int:
            done = 0
            for it in range(12):
                step = 1000 * (i + 1) + it
                t.begin_streamed_checkpoint(step, {"frag:header": {"n": 1}})
                buf = t.reserve_streamed_part(step, "frag:0", n)
                buf[:] = (i * 16 + it) % 251
                assert t.stage_streamed_part(step, "frag:0", buf, pooled=True) == 0
                del buf
                t.finish_streamed_checkpoint(step)
                body = fetch_bytes(base, step, "frag_0", timeout=20.0)
                assert body == bytes([(i * 16 + it) % 251]) * n, (i, it)
                t.retire_checkpoint(step)
                done += 1
            return done

        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            with ThreadPoolExecutor(16) as ex:
                futs = [ex.submit(stager, i) for i in range(16)]
                assert [f.result(timeout=120) for f in futs] == [12] * 16
            c = t._frag_native.counters()
            assert c["stage_copy_bytes"] == 0 and c["serve_copies"] == 0
            assert c["stage_inplace_bytes"] == 16 * 12 * n
            assert c["pool_hits"] + c["pool_misses"] == 16 * 12
            assert c["pool_misses"] <= 64  # recycled, not grown without end
        finally:
            sys.setswitchinterval(old)
            t.shutdown()


class TestConditionalGet:
    """ISSUE 51: "send ``frag_n`` unless it hashes to <my digest of n>".
    A fragment GET that carries ``If-None-Match`` parks like any other
    until the fragment lands, and is then answered "same" (304, no body)
    when the fragment was staged under that digest, with the bytes
    otherwise.  A GET that carries no condition is answered as ever."""

    PAYLOAD = np.random.default_rng(5).integers(
        0, 256, size=200_000, dtype=np.uint8
    )

    @staticmethod
    def digest(payload) -> str:
        import hashlib

        return hashlib.sha256(memoryview(payload)).hexdigest()

    @pytest.mark.parametrize("plane", ["native", "python"])
    def test_parks_then_same_without_a_body_or_the_bytes(
        self, plane, monkeypatch
    ):
        from concurrent.futures import ThreadPoolExecutor

        payload, sha = self.PAYLOAD, self.digest(self.PAYLOAD)
        other = self.digest(b"some other bytes")
        # the server is native either way: the python plane is the
        # fallback a peer without the native plane takes against it
        t = HTTPTransport(timeout=10.0)
        if plane == "python":
            monkeypatch.setattr(fragdata, "enabled", lambda: False)
        try:
            base = t.metadata()
            t.begin_streamed_checkpoint(9, {"frag:header": {"n": 1}})
            c0 = t._frag_native.counters()
            with ThreadPoolExecutor(2) as ex:
                same = ex.submit(
                    frags.fetch_raw, base, 9, "frag_0", 10.0, "heal",
                    None, sha,
                )
                differs = ex.submit(
                    frags.fetch_raw, base, 9, "frag_0", 10.0, "heal",
                    None, other,
                )
                time.sleep(0.3)
                # both parked at the source: the fragment has not landed
                assert not same.done() and not differs.done()
                t.stage_streamed_part(9, "frag:0", payload, digest=sha)
                assert same.result(timeout=10.0) is None
                got = differs.result(timeout=10.0)
            assert bytes(memoryview(got)) == payload.tobytes()
            # a fragment staged under no digest: the bytes, whatever is asked
            t.stage_streamed_part(9, "frag:1", payload)
            got = frags.fetch_raw(base, 9, "frag_1", 10.0, "heal", unless=sha)
            assert bytes(memoryview(got)) == payload.tobytes()
            # and a restage under none forgets the one it had
            t.stage_streamed_part(9, "frag:0", payload)
            got = frags.fetch_raw(base, 9, "frag_0", 10.0, "heal", unless=sha)
            assert bytes(memoryview(got)) == payload.tobytes()
            if plane == "native":
                c = served(t, c0["serves"] + 3)
                assert c["parked_waits"] - c0["parked_waits"] == 2
                assert c["same_replies"] - c0["same_replies"] == 1
                assert c["serves"] - c0["serves"] == 3
                assert c["serve_bytes"] - c0["serve_bytes"] == 3 * payload.nbytes
                assert c["serve_copies"] == 0
            else:
                assert t._frag_native.counters()["serves"] == c0["serves"]
                recs = [
                    r for r in fr.RECORDER.snapshot()
                    if r.get("op") == "checkpoint.http.send"
                    and r.get("resource") in ("frag_0", "frag_1")
                ]
                assert [r.get("bytes") for r in recs].count(0) == 1
                assert sum(1 for r in recs if r.get("same")) == 1
        finally:
            t.shutdown()

    @pytest.mark.parametrize("plane", ["native", "python"])
    def test_on_the_wire_304_keeps_the_connection_and_200_is_as_ever(
        self, plane
    ):
        """The two answers as a plain HTTP client sees them, on either
        server: a 304 with the digest as ETag, no body, the connection
        kept; and, WITHOUT the condition, exactly the response there was
        before there were conditions."""
        import http.client
        from urllib.parse import urlparse

        payload, sha = self.PAYLOAD, self.digest(self.PAYLOAD)
        t = HTTPTransport(timeout=10.0)
        try:
            stage = dict(digest=sha)
            t.begin_streamed_checkpoint(9, {"frag:header": {"n": 1}})
            t.stage_streamed_part(9, "frag:0", payload, **stage)
            t.finish_streamed_checkpoint(9)
            u = urlparse(t.metadata())
            port = t._frag_native.port if plane == "native" else u.port
            conn = http.client.HTTPConnection(u.hostname, port, timeout=5.0)
            conn.request(
                "GET", "/checkpoint/9/frag_0",
                headers={"If-None-Match": f'"{sha}"'},
            )
            resp = conn.getresponse()
            assert resp.status == 304 and resp.read() == b""
            assert resp.getheader("ETag") == f'"{sha}"'
            # the same connection, no condition: today's response
            conn.request("GET", "/checkpoint/9/frag_0")
            resp = conn.getresponse()
            assert resp.status == 200
            assert resp.read() == payload.tobytes()
            names = {k.lower() for k, _ in resp.getheaders()}
            assert resp.getheader("Content-Length") == str(payload.nbytes)
            assert resp.getheader("Content-Type") == "application/octet-stream"
            assert "etag" not in names
            if plane == "native":
                assert names == {"content-type", "content-length", "connection"}
            else:
                assert names == {
                    "server", "date", "content-type", "content-length"
                }
            # a condition that is another digest: the same 200
            conn.request(
                "GET", "/checkpoint/9/frag_0",
                headers={"If-None-Match": '"' + "0" * 64 + '"'},
            )
            resp = conn.getresponse()
            assert resp.status == 200 and resp.read() == payload.tobytes()
            conn.close()
        finally:
            t.shutdown()

    def test_a_spilled_version_answers_same_from_its_manifest(self, tmp_path):
        """Restore: a version served from the durable store knows every
        fragment's digest (its manifest), so a restorer that holds the
        bytes is told "same" without the blob being read."""
        from torchft_tpu.checkpointing.store import FragmentStore

        state = make_state(leaves=4)
        store = FragmentStore(str(tmp_path / "store"))
        t = HTTPTransport(timeout=10.0)
        try:
            manifest = store.put_state(3, state, fragments=2)
            parts = {
                name: (store.fragment(3, name), sha)
                for name, sha in manifest["digests"].items()
            }
            t.attach_store(store)
            base = t.metadata()
            for name, (raw, sha) in parts.items():
                assert frags.fetch_raw(
                    base, 3, f"frag_{name}", 5.0, "heal", unless=sha
                ) is None
                got = frags.fetch_raw(
                    base, 3, f"frag_{name}", 5.0, "heal", unless="0" * 64
                )
                assert bytes(memoryview(got)) == bytes(memoryview(raw))
        finally:
            t.shutdown()


class TestNativeChaos:
    def test_kill_native_relay_mid_stripe(self, sources):
        """SIGKILL-equivalent (full shutdown: Python control + native
        data server) of a native-served source MID-heal: its in-flight
        fragments fail over per-fragment and the heal converges
        bitwise."""
        state, transports = sources
        assert all(t._frag_native is not None for t in transports)
        faults.FAULTS.configure(
            [FaultRule(site="transport.heal.frag", action="delay",
                       delay=0.15, times=100)],
            seed=0,
        )
        local = clone_state(state)
        for v in local["user"].values():
            v[:] = 0.0
        killer = threading.Timer(0.05, transports[2].shutdown)
        killer.start()
        healer = HTTPTransport(timeout=10.0)
        try:
            got, info = healer.recv_checkpoint_striped(
                [t.metadata() for t in transports], 5, timeout=30.0,
                local_state_fn=lambda: local, delta=False,
            )
        finally:
            killer.cancel()
            healer.shutdown()
        assert_state_equal(got, state)
        assert info["failovers"] >= 1
        assert info["sources_used"] >= 2
        # the survivors actually served over the native plane
        native_serves = sum(
            t._frag_native.counters()["serves"] for t in transports[:2]
        )
        assert native_serves >= 1

    def test_poisoned_fragment_over_native_path(self, sources):
        """Bitwise-corrupt bytes arriving over the NATIVE plane are
        rejected by the Python digest-of-record exactly like the Python
        plane: the source is treated dead for that fragment and the
        provenance trail records the ``mismatch`` hop verdict."""
        state, transports = sources
        victim = transports[1]
        # poison EVERY fragment on the victim, restaged through the
        # transport API so the corruption lands in the Python slot AND
        # the native mirror; pacing below guarantees the dynamic stripe
        # routes the victim at least one fragment
        for i in range(6):
            with victim._staged_lock.r_lock():
                raw = bytearray(victim._staged[5].sd[f"frag:{i}"])
            raw[len(raw) // 2] ^= 0xFF
            victim.stage_streamed_part(5, f"frag:{i}", bytes(raw))
        # (0.3 s and not 0.02: on a loaded host the stripe workers start
        # further apart than that, and the primary's two drain the queue
        # before the victim's hold anything; PR 42 saw it under 4 workers)
        faults.FAULTS.configure(
            [FaultRule(site="transport.heal.frag", action="delay",
                       delay=0.3, times=100)],
            seed=0,
        )
        hops_before = len(PROV.hop_records())
        local = clone_state(state)
        for v in local["user"].values():
            v[:] = 0.0
        healer = HTTPTransport(timeout=10.0)
        try:
            got, info = healer.recv_checkpoint_striped(
                [t.metadata() for t in transports], 5, timeout=30.0,
                local_state_fn=lambda: local, delta=True,
            )
        finally:
            healer.shutdown()
        # healed state is bitwise the fleet's, never the poison
        assert_state_equal(got, state)
        mismatches = [
            r for r in PROV.hop_records()[hops_before:]
            if r.get("verdict") == "mismatch"
        ]
        assert mismatches, "poisoned native fetch left no mismatch hop"
        assert any(
            victim.metadata() in str(r.get("source", "")) for r in mismatches
        )
        # the poison travelled the native plane, not a Python serve
        assert victim._frag_native.counters()["serves"] >= 1

    def test_mixed_fleet_interop_bitwise(self):
        """A stripe across native-armed AND python-only sources heals
        bitwise — per-fetch fallback makes the fleets interoperable in
        any mix."""
        state = make_state()
        transports = [
            HTTPTransport(timeout=10.0),
            python_only_transport(timeout=10.0),
            HTTPTransport(timeout=10.0),
        ]
        try:
            threads = [
                threading.Thread(
                    target=t.send_checkpoint_streamed,
                    args=([1], 5, state, 10.0, 6),
                )
                for t in transports
            ]
            for th in threads:
                th.start()
            for th in threads:
                th.join()
            # pace fetches so every source holds work: both planes serve
            faults.FAULTS.configure(
                [FaultRule(site="transport.heal.frag", action="delay",
                           delay=0.02, times=100)],
                seed=0,
            )
            local = clone_state(state)
            for v in local["user"].values():
                v[:] = 0.0
            healer = HTTPTransport(timeout=10.0)
            try:
                got, info = healer.recv_checkpoint_striped(
                    [t.metadata() for t in transports], 5, timeout=30.0,
                    local_state_fn=lambda: local, delta=False,
                )
            finally:
                healer.shutdown()
            assert_state_equal(got, state)
            assert info["sources"] == 3
            assert transports[1]._frag_native is None
        finally:
            for t in transports:
                t.shutdown()

    def test_injected_native_drop_is_absorbed(self):
        """A native-side injected drop (connection closed mid-exchange)
        takes the transport-error path: the fetch falls back to Python
        for that attempt and still lands the right bytes."""
        payload = b"d" * 8192
        t = HTTPTransport(timeout=10.0)
        try:
            stage_raw(t, 6, {"w0": payload})
            base = t.metadata()
            fetch_bytes(base, 6, "frag_w0")  # warm
            t._frag_native.inject("drop", count=1)
            assert fetch_bytes(base, 6, "frag_w0") == payload
            assert t._frag_native.counters()["injected_drops"] == 1
        finally:
            t.shutdown()
