"""Subprocess-isolated ("Baby") process groups + monitored pipe.

Mirrors the reference's Baby-PG tests (reference:
torchft/process_group_test.py:910-1020 and multiprocessing tests): ops run
in a spawned worker, worker crash surfaces as a clean error in the parent,
reconfigure restarts the worker, and the parent process always survives.
"""

import multiprocessing as mp
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from torchft_tpu.coordination import StoreServer
from torchft_tpu.multiprocessing import _MonitoredPipe
from torchft_tpu.parallel.process_group import ProcessGroupBabyTCP


@pytest.fixture
def store():
    server = StoreServer()
    yield server
    server.shutdown()


def _configure_pair(store, prefix, timeout=30.0):
    pgs = [ProcessGroupBabyTCP(timeout=timeout) for _ in range(2)]
    with ThreadPoolExecutor(max_workers=2) as ex:
        futs = [
            ex.submit(
                pgs[r].configure, f"{store.address()}/{prefix}", f"rank{r}", r, 2
            )
            for r in range(2)
        ]
        for f in futs:
            f.result(timeout=60)
    return pgs


class TestMonitoredPipe:
    def test_roundtrip_and_timeout(self):
        a, b = mp.Pipe()
        pa, pb = _MonitoredPipe(a), _MonitoredPipe(b)
        pa.send({"x": 1})
        assert pb.recv(timeout=5) == {"x": 1}
        with pytest.raises(TimeoutError):
            pb.recv(timeout=0.2)

    def test_exception_passthrough(self):
        a, b = mp.Pipe()
        pa, pb = _MonitoredPipe(a), _MonitoredPipe(b)
        pa.send(ValueError("shipped"))
        with pytest.raises(ValueError, match="shipped"):
            pb.recv(timeout=5)

    def test_eof_on_close(self):
        a, b = mp.Pipe()
        pa, pb = _MonitoredPipe(a), _MonitoredPipe(b)
        pa.close()
        with pytest.raises(EOFError):
            pb.recv(timeout=5)


class TestProcessGroupBabyTCP:
    def test_configure_failure_propagates_root_cause(self):
        pg = ProcessGroupBabyTCP(timeout=10.0)
        # unreachable store: the worker's configure error must surface in
        # the parent with the real cause, not a generic protocol error
        with pytest.raises(Exception) as exc_info:
            pg.configure("127.0.0.1:1/none", "rank0", 0, 2)
        assert not isinstance(exc_info.value, AssertionError)
        pg.shutdown()

    def test_allreduce_and_broadcast(self, store):
        pgs = _configure_pair(store, "baby1")
        try:
            with ThreadPoolExecutor(max_workers=2) as ex:
                futs = [
                    ex.submit(
                        lambda r: pgs[r]
                        .allreduce([np.full(4, float(r + 1), np.float32)])
                        .wait(timeout=30),
                        r,
                    )
                    for r in range(2)
                ]
                results = [f.result(timeout=60) for f in futs]
            for res in results:
                np.testing.assert_array_equal(res[0], np.full(4, 3.0, np.float32))

            with ThreadPoolExecutor(max_workers=2) as ex:
                futs = [
                    ex.submit(
                        lambda r: pgs[r]
                        .broadcast(
                            np.arange(4, dtype=np.float32) if r == 0 else np.zeros(4, np.float32),
                            root=0,
                        )
                        .wait(timeout=30),
                        r,
                    )
                    for r in range(2)
                ]
                results = [f.result(timeout=60) for f in futs]
            for res in results:
                np.testing.assert_array_equal(res, np.arange(4, dtype=np.float32))
        finally:
            for pg in pgs:
                pg.shutdown()

    def test_pipelined_ops_preserve_order(self, store):
        # submit two collectives without waiting in between: the worker
        # must enqueue them in pipe order so ranks' streams match
        pgs = _configure_pair(store, "babyp")
        try:
            def both(r):
                w1 = pgs[r].allreduce([np.full(4, 1.0 + r, np.float32)])
                w2 = pgs[r].allreduce([np.full(2, 10.0 * (1 + r), np.float32)])
                return w1.wait(timeout=30), w2.wait(timeout=30)

            with ThreadPoolExecutor(max_workers=2) as ex:
                futs = [ex.submit(both, r) for r in range(2)]
                results = [f.result(timeout=60) for f in futs]
            for r1, r2 in results:
                np.testing.assert_array_equal(r1[0], np.full(4, 3.0, np.float32))
                np.testing.assert_array_equal(r2[0], np.full(2, 30.0, np.float32))
        finally:
            for pg in pgs:
                pg.shutdown()

    def test_live_reconfigure_keeps_clean_state(self, store):
        # reconfigure over a healthy PG (quorum-change path): the stale
        # reader of the old worker must not latch an error afterwards
        import time

        pgs = _configure_pair(store, "babyr1")
        try:
            with ThreadPoolExecutor(max_workers=2) as ex:
                futs = [
                    ex.submit(
                        pgs[r].configure, f"{store.address()}/babyr2", f"rank{r}", r, 2
                    )
                    for r in range(2)
                ]
                for f in futs:
                    f.result(timeout=60)
            time.sleep(0.5)  # give the old readers time to wake on the closed pipe
            assert all(pg.errored() is None for pg in pgs)
            with ThreadPoolExecutor(max_workers=2) as ex:
                futs = [
                    ex.submit(
                        lambda r: pgs[r]
                        .allreduce([np.ones(2, np.float32)])
                        .wait(timeout=30),
                        r,
                    )
                    for r in range(2)
                ]
                for f in futs:
                    np.testing.assert_array_equal(
                        f.result(timeout=60)[0], np.full(2, 2.0, np.float32)
                    )
            assert all(pg.errored() is None for pg in pgs)
        finally:
            for pg in pgs:
                pg.shutdown()

    def test_worker_crash_is_isolated(self, store):
        pgs = _configure_pair(store, "baby2")
        try:
            # kill rank 1's worker out from under it — the parent must see a
            # clean error on both sides (peer detects the dropped socket)
            pgs[1]._proc.kill()
            with ThreadPoolExecutor(max_workers=2) as ex:
                futs = [
                    ex.submit(
                        lambda r: pgs[r]
                        .allreduce([np.zeros(2, np.float32)])
                        .wait(timeout=30),
                        r,
                    )
                    for r in range(2)
                ]
                errs = 0
                for f in futs:
                    try:
                        f.result(timeout=60)
                    except Exception:
                        errs += 1
            assert errs == 2
            assert pgs[1].errored() is not None
        finally:
            for pg in pgs:
                pg.shutdown()

    def test_reconfigure_after_abort(self, store):
        pgs = _configure_pair(store, "baby3")
        try:
            for pg in pgs:
                pg.abort()
            assert all(pg.errored() is not None for pg in pgs)
            # ops fail fast while aborted
            with pytest.raises(Exception):
                pgs[0].allreduce([np.zeros(1)]).wait(timeout=5)

            # reconfigure restarts workers and clears the error
            with ThreadPoolExecutor(max_workers=2) as ex:
                futs = [
                    ex.submit(
                        pgs[r].configure,
                        f"{store.address()}/baby3b",
                        f"rank{r}",
                        r,
                        2,
                    )
                    for r in range(2)
                ]
                for f in futs:
                    f.result(timeout=60)
            assert all(pg.errored() is None for pg in pgs)

            with ThreadPoolExecutor(max_workers=2) as ex:
                futs = [
                    ex.submit(
                        lambda r: pgs[r]
                        .allreduce([np.ones(2, np.float32)])
                        .wait(timeout=30),
                        r,
                    )
                    for r in range(2)
                ]
                for f in futs:
                    np.testing.assert_array_equal(
                        f.result(timeout=60)[0], np.full(2, 2.0, np.float32)
                    )
        finally:
            for pg in pgs:
                pg.shutdown()


class TestShmDataPath:
    def test_large_allreduce_uses_shm_and_is_correct(self, store):
        """Arrays >= 1 MiB cross the pipe as shared-memory refs (zero pickle
        of the payload); results must match the direct-PG math exactly."""
        pgs = _configure_pair(store, "shm")
        try:
            n = 2 * 1024 * 1024  # 8 MB f32, well over _SHM_MIN_BYTES
            data = [np.full(n, 1.0 + r, dtype=np.float32) for r in range(2)]

            with ThreadPoolExecutor(max_workers=2) as ex:
                futs = [
                    ex.submit(
                        lambda r: pgs[r].allreduce([data[r]], "sum").wait(timeout=60),
                        r,
                    )
                    for r in range(2)
                ]
                results = [f.result(timeout=90) for f in futs]
            for (got,) in results:
                np.testing.assert_array_equal(got, np.full(n, 3.0, np.float32))
            # no leaked segments
            import glob
            assert not glob.glob("/dev/shm/psm_*"), glob.glob("/dev/shm/*")
        finally:
            for pg in pgs:
                pg.shutdown()

    def test_mixed_small_and_large_leaves(self, store):
        pgs = _configure_pair(store, "shmmix")
        try:
            small = np.arange(16, dtype=np.float32)
            big = np.full(512 * 1024, 2.0, dtype=np.float32)  # 2 MB

            def run(r):
                return pgs[r].allreduce([small.copy(), big.copy()], "sum").wait(
                    timeout=60
                )

            with ThreadPoolExecutor(max_workers=2) as ex:
                results = [f.result(timeout=90)
                           for f in [ex.submit(run, r) for r in range(2)]]
            for got_small, got_big in results:
                np.testing.assert_array_equal(got_small, 2 * small)
                np.testing.assert_array_equal(got_big, 2 * big)
        finally:
            for pg in pgs:
                pg.shutdown()

    def test_backpressure_bounds_inflight_ops(self, store):
        """max_active_work caps queued ops; submissions past the cap wait
        and everything still completes in order."""
        pgs = [ProcessGroupBabyTCP(timeout=30.0, max_active_work=2) for _ in range(2)]
        with ThreadPoolExecutor(max_workers=2) as ex:
            futs = [
                ex.submit(
                    pgs[r].configure, f"{store.address()}/bp", f"rank{r}", r, 2
                )
                for r in range(2)
            ]
            for f in futs:
                f.result(timeout=60)
        try:
            def run(r):
                works = [
                    pgs[r].allreduce([np.full(1024, float(i), np.float32)], "sum")
                    for i in range(8)
                ]
                return [w.wait(timeout=60)[0][0] for w in works]

            with ThreadPoolExecutor(max_workers=2) as ex:
                results = [f.result(timeout=90)
                           for f in [ex.submit(run, r) for r in range(2)]]
            for vals in results:
                assert vals == [2.0 * i for i in range(8)]
        finally:
            for pg in pgs:
                pg.shutdown()


class TestBabyQuantizedCollective:
    def test_quantized_allreduce_over_baby(self, store):
        """The int8 quantized allreduce composes with the subprocess-
        isolated backend: packed wire buffers cross the parent<->worker
        boundary (pipe or shm), and the pool-recycling in the collective
        must only ever recycle parent-side allocations it owns."""
        from torchft_tpu.ops.collectives import allreduce_quantized
        from torchft_tpu.parallel.process_group import REDUCE_SUM

        pgs = _configure_pair(store, "qbaby")
        try:
            data = [
                np.full(60_000, 1.0 + r, dtype=np.float32) for r in range(2)
            ]
            expected = np.full(60_000, 3.0, dtype=np.float32)

            def run(rank):
                return allreduce_quantized(
                    [data[rank]], REDUCE_SUM, pgs[rank]
                ).wait(timeout=60)

            with ThreadPoolExecutor(max_workers=2) as ex:
                results = [
                    f.result(timeout=90)
                    for f in [ex.submit(run, r) for r in range(2)]
                ]
            for (got,) in results:
                rel = np.abs(got - expected).max() / 3.0
                assert rel < 0.05, rel
            np.testing.assert_array_equal(results[0][0], results[1][0])
            # run a second round so any wrongly-recycled buffer from round
            # one would corrupt round two
            with ThreadPoolExecutor(max_workers=2) as ex:
                results2 = [
                    f.result(timeout=90)
                    for f in [ex.submit(run, r) for r in range(2)]
                ]
            np.testing.assert_array_equal(results2[0][0], results2[1][0])
        finally:
            for pg in pgs:
                pg.shutdown()


def test_wire_gbps_env_reaches_baby_worker(store, monkeypatch):
    """TORCHFT_WIRE_GBPS must shape the SUBPROCESS worker's sends too:
    the Baby worker builds its inner ProcessGroupTCP in the spawned
    process, which inherits the env — an 8 MB allreduce at 50 MB/s
    must take >= ~80 ms where unshaped loopback takes < 40 ms."""
    import time as _time

    monkeypatch.setenv("TORCHFT_WIRE_GBPS", "0.05")
    pgs = _configure_pair(store, "shapedbaby", timeout=60.0)
    try:
        data = np.ones(2 << 20, dtype=np.float32)  # 8 MB

        def run(rank):
            t0 = _time.monotonic()
            pgs[rank].allreduce([data.copy()], "sum").wait(timeout=60)
            return _time.monotonic() - t0

        with ThreadPoolExecutor(max_workers=2) as ex:
            walls = [f.result(timeout=90) for f in [ex.submit(run, r) for r in range(2)]]
        assert max(walls) >= 0.06, walls
    finally:
        for pg in pgs:
            pg.shutdown()
    # unshaped control: without the env the same transfer must be faster
    # (guards against the shaped assertion passing vacuously on a slow
    # host where even unshaped baby allreduces exceed the floor)
    monkeypatch.delenv("TORCHFT_WIRE_GBPS")
    pgs2 = _configure_pair(store, "unshapedbaby", timeout=60.0)
    try:
        data = np.ones(2 << 20, dtype=np.float32)

        def run2(rank):
            t0 = _time.monotonic()
            pgs2[rank].allreduce([data.copy()], "sum").wait(timeout=60)
            return _time.monotonic() - t0

        with ThreadPoolExecutor(max_workers=2) as ex:
            walls2 = [
                f.result(timeout=90) for f in [ex.submit(run2, r) for r in range(2)]
            ]
        assert max(walls2) < max(walls), (walls2, walls)
    finally:
        for pg in pgs2:
            pg.shutdown()


@pytest.mark.parametrize("parent", ["tpu,cpu", None])
def test_worker_is_spawned_cpu_only(monkeypatch, parent):
    """One process per chip: the byte-moving worker is started with
    JAX_PLATFORMS=cpu in its environment (spawn copies os.environ at
    start()), and the parent's own setting comes back afterwards."""
    import os

    from torchft_tpu.parallel.process_group import _cpu_only_child_env

    if parent is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", parent)
    with _cpu_only_child_env():
        assert os.environ["JAX_PLATFORMS"] == "cpu"
    assert os.environ.get("JAX_PLATFORMS") == parent
