"""ProcessGroup conformance + resiliency tests.

Mirrors reference torchft/process_group_test.py: per-backend collective
smoke over threads-as-ranks, reconfigure, and the kill-a-rank resiliency
scenario (reference :961-1020) where survivors must error, reconfigure to a
smaller world, and succeed.
"""

import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from torchft_tpu.coordination import StoreServer
from torchft_tpu.parallel.process_group import (
    REDUCE_AVG,
    REDUCE_MAX,
    REDUCE_SUM,
    ErrorSwallowingProcessGroupWrapper,
    FakeProcessGroupWrapper,
    ProcessGroupDummy,
    ProcessGroupTCP,
    ProcessGroupWrapper,
)


def run_parallel(world, fn, pgs=None):
    """Run fn(rank, pg) on one thread per rank; returns results by rank."""
    if pgs is None:
        pgs = [None] * world
    with ThreadPoolExecutor(max_workers=world) as ex:
        futures = [ex.submit(fn, r, pgs[r]) for r in range(world)]
        return [f.result(timeout=60) for f in futures]


@pytest.fixture
def store():
    server = StoreServer()
    yield server
    server.shutdown()


def make_group(store, world, prefix="test", timeout=20.0):
    """Configure a TCP process group across `world` thread-ranks."""
    pgs = [ProcessGroupTCP(timeout=timeout) for _ in range(world)]

    def configure(rank, _):
        pgs[rank].configure(f"{store.address()}/{prefix}", f"rank{rank}", rank, world)

    run_parallel(world, configure)
    return pgs


class TestProcessGroupTCP:
    @pytest.mark.parametrize("world", [2, 3, 5])
    def test_allreduce_sum(self, store, world):
        pgs = make_group(store, world)
        data = [np.arange(10, dtype=np.float32) + r for r in range(world)]
        expected = sum(data)

        def op(rank, _):
            return pgs[rank].allreduce([data[rank]], REDUCE_SUM).wait()[0]

        for result in run_parallel(world, op):
            np.testing.assert_allclose(result, expected, rtol=1e-6)
        for pg in pgs:
            pg.shutdown()

    def test_allreduce_avg_and_max(self, store):
        world = 3
        pgs = make_group(store, world)
        data = [np.full((4,), float(r + 1), dtype=np.float32) for r in range(world)]

        def op_avg(rank, _):
            return pgs[rank].allreduce([data[rank]], REDUCE_AVG).wait()[0]

        for result in run_parallel(world, op_avg):
            np.testing.assert_allclose(result, np.full((4,), 2.0), rtol=1e-6)

        def op_max(rank, _):
            return pgs[rank].allreduce([data[rank]], REDUCE_MAX).wait()[0]

        for result in run_parallel(world, op_max):
            np.testing.assert_allclose(result, np.full((4,), 3.0))
        for pg in pgs:
            pg.shutdown()

    def test_allreduce_large_buffer(self, store):
        # Bigger than socket buffers: exercises the deadlock-free exchange.
        world = 2
        pgs = make_group(store, world)
        data = [np.random.default_rng(r).standard_normal(1 << 20).astype(np.float32) for r in range(world)]

        def op(rank, _):
            return pgs[rank].allreduce([data[rank]], REDUCE_SUM).wait()[0]

        results = run_parallel(world, op)
        np.testing.assert_allclose(results[0], data[0] + data[1], rtol=1e-5)
        for pg in pgs:
            pg.shutdown()

    def test_allgather(self, store):
        world = 3
        pgs = make_group(store, world)

        def op(rank, _):
            return pgs[rank].allgather(np.array([rank, rank * 10])).wait()

        for result in run_parallel(world, op):
            assert len(result) == world
            for r, piece in enumerate(result):
                np.testing.assert_array_equal(piece, [r, r * 10])
        for pg in pgs:
            pg.shutdown()

    def test_broadcast(self, store):
        world = 3
        pgs = make_group(store, world)

        def op(rank, _):
            arr = np.array([42.0]) if rank == 1 else np.zeros(1)
            return pgs[rank].broadcast(arr, root=1).wait()

        for result in run_parallel(world, op):
            np.testing.assert_array_equal(result, [42.0])
        for pg in pgs:
            pg.shutdown()

    def test_reduce_scatter(self, store):
        world = 2
        pgs = make_group(store, world)
        data = [np.arange(8, dtype=np.float32).reshape(4, 2) * (r + 1) for r in range(world)]
        expected_total = data[0] + data[1]

        def op(rank, _):
            return pgs[rank].reduce_scatter(data[rank], REDUCE_SUM).wait()

        results = run_parallel(world, op)
        np.testing.assert_allclose(results[0], expected_total[:2], rtol=1e-6)
        np.testing.assert_allclose(results[1], expected_total[2:], rtol=1e-6)
        for pg in pgs:
            pg.shutdown()

    def test_alltoall(self, store):
        world = 3
        pgs = make_group(store, world)

        def op(rank, _):
            inputs = [np.array([rank * 10 + dst]) for dst in range(world)]
            return pgs[rank].alltoall(inputs).wait()

        results = run_parallel(world, op)
        for rank, out in enumerate(results):
            for src, piece in enumerate(out):
                np.testing.assert_array_equal(piece, [src * 10 + rank])
        for pg in pgs:
            pg.shutdown()

    def test_send_recv(self, store):
        world = 2
        pgs = make_group(store, world)

        def op(rank, _):
            if rank == 0:
                pgs[0].send(np.array([1.5, 2.5]), dst=1, tag=7).wait()
                return None
            return pgs[1].recv(src=0, tag=7).wait()

        results = run_parallel(world, op)
        np.testing.assert_array_equal(results[1], [1.5, 2.5])
        for pg in pgs:
            pg.shutdown()

    def test_barrier(self, store):
        world = 3
        pgs = make_group(store, world)
        run_parallel(world, lambda r, _: pgs[r].barrier().wait())
        for pg in pgs:
            pg.shutdown()

    def test_world_size_one_local(self, store):
        (pg,) = make_group(store, 1)
        result = pg.allreduce([np.arange(3)], REDUCE_SUM).wait()
        np.testing.assert_array_equal(result[0], [0, 1, 2])
        pg.shutdown()

    def test_abort_latches_error(self, store):
        world = 2
        pgs = make_group(store, world)
        pgs[0].abort()
        assert pgs[0].errored() is not None
        work = pgs[0].allreduce([np.zeros(2)])
        with pytest.raises(RuntimeError):
            work.wait(timeout=5)

    def test_resiliency_kill_rank_then_reconfigure(self, store):
        # reference process_group_test.py:961-1020: kill the last rank,
        # survivors raise, then reconfigure to a smaller world and succeed.
        world = 3
        pgs = make_group(store, world, prefix="r1", timeout=3.0)

        # rank 2 "dies" (abort closes its sockets)
        pgs[2].abort()

        def failing_op(rank, _):
            try:
                pgs[rank].allreduce([np.ones(4)]).wait(timeout=10)
                return None
            except Exception as e:  # noqa: BLE001
                return e

        errors = run_parallel(2, failing_op)
        assert all(e is not None for e in errors), "survivors must observe failure"
        assert all(pgs[r].errored() is not None for r in range(2))

        # survivors reconfigure under a fresh prefix into world=2
        def reconfigure(rank, _):
            pgs[rank].configure(f"{store.address()}/r2", f"rank{rank}", rank, 2)

        run_parallel(2, reconfigure)
        assert all(pgs[r].errored() is None for r in range(2))

        def op(rank, _):
            return pgs[rank].allreduce([np.ones(4)]).wait()[0]

        for result in run_parallel(2, op):
            np.testing.assert_array_equal(result, np.full(4, 2.0))
        for pg in pgs[:2]:
            pg.shutdown()

    def test_timeout_on_missing_peer(self, store):
        # rank 0 configures against a world of 2 but rank 1 never shows up.
        pg = ProcessGroupTCP(timeout=1.0)
        with pytest.raises((TimeoutError, OSError)):
            pg.configure(f"{store.address()}/lonely", "rank0", 1, 2)


class TestWrappers:
    def test_dummy_ops(self):
        pg = ProcessGroupDummy()
        np.testing.assert_array_equal(
            pg.allreduce([np.array([1.0, 2.0])]).wait()[0], [1.0, 2.0]
        )
        assert pg.size() == 1
        pg.configure("", "r", 0, 1)
        assert pg.configure_count == 1

    def test_error_swallowing(self, store):
        inner = ProcessGroupDummy()
        pg = ErrorSwallowingProcessGroupWrapper(inner)
        assert pg.errored() is None
        pg.report_error(RuntimeError("boom"))
        assert pg.errored() is not None
        # ops become pass-through no-ops
        result = pg.allreduce([np.array([3.0])]).wait()
        np.testing.assert_array_equal(result[0], [3.0])
        # configure clears the error
        pg.configure("", "r", 0, 1)
        assert pg.errored() is None

    def test_error_swallowing_catches_op_failure(self):
        inner = ProcessGroupDummy()
        pg = ErrorSwallowingProcessGroupWrapper(inner)
        # recv fails on dummy; wrapper must swallow with a None result
        work = pg.recv(src=0)
        assert work.wait(timeout=5) is None
        assert pg.errored() is not None

    def test_error_swallowing_keeps_result_shapes(self):
        pg = ErrorSwallowingProcessGroupWrapper(ProcessGroupDummy())
        pg.report_error(RuntimeError("down"))
        # single-array ops return a bare array, list ops a list — matching
        # the success path so training code doesn't branch on failure.
        bc = pg.broadcast(np.arange(4.0)).wait(timeout=5)
        assert isinstance(bc, np.ndarray) and bc.shape == (4,)
        ar = pg.allreduce([np.arange(4.0)]).wait(timeout=5)
        assert isinstance(ar, list) and ar[0].shape == (4,)
        rs = pg.reduce_scatter(np.arange(4.0).reshape(4, 1)).wait(timeout=5)
        assert isinstance(rs, np.ndarray)

    def test_fake_injects_future_error(self):
        inner = ProcessGroupDummy()
        pg = FakeProcessGroupWrapper(inner)
        pg.report_future_error(RuntimeError("injected"))
        with pytest.raises(RuntimeError, match="injected"):
            pg.allreduce([np.zeros(1)]).wait(timeout=5)
        # next op is clean
        pg.allreduce([np.zeros(1)]).wait(timeout=5)

    def test_fake_injects_configure_error(self):
        pg = FakeProcessGroupWrapper(ProcessGroupDummy())
        pg.report_configure_error(RuntimeError("cfg boom"))
        with pytest.raises(RuntimeError, match="cfg boom"):
            pg.configure("", "r", 0, 1)
        pg.configure("", "r", 0, 1)  # second attempt clean

    def test_wrapper_forwards(self):
        inner = ProcessGroupDummy()
        pg = ProcessGroupWrapper(inner)
        assert pg.size() == 1
        assert pg.parent is inner

    def test_managed_forwards_allreduce_to_manager(self):
        from unittest.mock import MagicMock

        from torchft_tpu.parallel.process_group import ManagedProcessGroup
        from torchft_tpu.parallel.work import completed_work

        manager = MagicMock()
        manager.num_participants.return_value = 3
        manager.participating_rank.return_value = 1
        manager.errored.return_value = None
        manager.allreduce.return_value = completed_work([np.array([6.0])])

        pg = ManagedProcessGroup(manager)
        assert pg.size() == 3
        assert pg.rank() == 1
        assert pg.errored() is None

        out = pg.allreduce([np.array([2.0])], op="sum").wait(timeout=5)
        np.testing.assert_array_equal(out[0], [6.0])
        manager.allreduce.assert_called_once()
        assert manager.allreduce.call_args.kwargs["reduce_op"] == "sum"

        # non-allreduce collectives are rejected — the Manager owns quorum
        with pytest.raises(RuntimeError):
            pg.broadcast(np.zeros(1)).wait(timeout=5)
        with pytest.raises(RuntimeError):
            pg.configure("", "r", 0, 1)

    def test_managed_rank_when_not_participating(self):
        from unittest.mock import MagicMock

        from torchft_tpu.parallel.process_group import (
            ManagedProcessGroup,
            NotParticipatingError,
        )

        manager = MagicMock()
        manager.participating_rank.return_value = None
        pg = ManagedProcessGroup(manager)
        # a healing replica must NOT silently read rank-0's data shard
        with pytest.raises(NotParticipatingError):
            pg.rank()


class TestBucketing:
    def test_many_mixed_leaves_roundtrip(self, store):
        # mixed dtypes + a leaf above BUCKET_BYTES: bucketing must preserve
        # order, dtypes, shapes, and values
        world = 2
        pgs = make_group(store, world, "bucket")
        rng = np.random.default_rng(0)
        big = ProcessGroupTCP.BUCKET_BYTES // 4 + 100  # f32 elems, solo path
        leaves = [
            rng.standard_normal((5, 3)).astype(np.float32),
            (rng.standard_normal(7) * 10).astype(np.int32),
            rng.standard_normal(big).astype(np.float32),
            rng.standard_normal((2, 2, 2)).astype(np.float64),
            rng.standard_normal(11).astype(np.float32),
            (rng.standard_normal(4) * 10).astype(np.int32),
        ]

        def run(rank, _):
            return pgs[rank].allreduce([l.copy() for l in leaves], REDUCE_SUM).wait(
                timeout=30
            )

        results = run_parallel(world, run)
        for res in results:
            assert len(res) == len(leaves)
            for out, inp in zip(res, leaves):
                assert out.dtype == inp.dtype and out.shape == inp.shape
                np.testing.assert_allclose(
                    out.astype(np.float64), inp.astype(np.float64) * world,
                    rtol=1e-6,
                )
        for pg in pgs:
            pg.shutdown()

    def test_allreduce_reports_ring_wire_bytes(self, store):
        """The unquantized path carries measured wire accounting too
        (parity with the quantized collectives' wire_bytes, so
        bench/diagnose compare f32 vs int8 traffic honestly)."""
        world = 2
        pgs = make_group(store, world, "wirebytes")
        n = 10_000
        data = np.ones(n, dtype=np.float32)

        def run(rank, _):
            w = pgs[rank].allreduce([data.copy()], REDUCE_SUM)
            w.wait(timeout=30)
            return w.wire_bytes, w.unquantized_wire_bytes

        chunk = -(-n // world)
        expected = 2 * (world - 1) * chunk * 4  # ring: rs half + ag half
        for wire, unq in run_parallel(world, run):
            assert wire == expected
            assert unq == expected  # f32 IS the unquantized wire
        # bucketized multi-leaf: accounting follows the same bucket plan
        leaves = [np.ones(100, np.float32), np.ones(7, np.float64)]

        def run_multi(rank, _):
            w = pgs[rank].allreduce([l.copy() for l in leaves], REDUCE_SUM)
            w.wait(timeout=30)
            return w.wire_bytes

        per_bucket = 2 * (world - 1)
        expected_multi = per_bucket * (-(-100 // world)) * 4 + per_bucket * (
            -(-7 // world)
        ) * 8
        for wire in run_parallel(world, run_multi):
            assert wire == expected_multi
        for pg in pgs:
            pg.shutdown()


class TestNumerics:
    def test_bfloat16_allreduce_and_sendrecv(self, store):
        # bf16 is THE TPU training dtype; ml_dtypes arrays have no buffer-
        # protocol format char, so the zero-copy wire path must use uint8
        # views, and accumulation must widen to f32
        import ml_dtypes

        bf16 = np.dtype(ml_dtypes.bfloat16)
        world = 2
        pgs = make_group(store, world, "bf16")

        def ar(rank, _):
            x = np.full((4, 3), 1.5 + rank, dtype=bf16)
            out = pgs[rank].allreduce([x], REDUCE_SUM).wait(timeout=20)
            return out[0]

        results = run_parallel(world, ar)
        for res in results:
            assert res.dtype == bf16
            np.testing.assert_array_equal(
                res.astype(np.float32), np.full((4, 3), 4.0, np.float32)
            )

        def sr(rank, _):
            if rank == 0:
                pgs[0].send(np.arange(6, dtype=bf16), dst=1, tag=9).wait(timeout=20)
                return None
            return pgs[1].recv(src=0, tag=9).wait(timeout=20)

        got = run_parallel(world, sr)[1]
        assert got.dtype == bf16
        np.testing.assert_array_equal(got.astype(np.float32), np.arange(6.0))
        for pg in pgs:
            pg.shutdown()

    def test_accumulation_dtype_widens_ml_floats(self):
        import ml_dtypes

        from torchft_tpu.parallel.process_group import _accumulation_dtype

        assert _accumulation_dtype(np.dtype(ml_dtypes.bfloat16)) == np.float32
        assert _accumulation_dtype(np.dtype(np.float16)) == np.float32
        assert _accumulation_dtype(np.dtype(np.float32)) == np.float32
        assert _accumulation_dtype(np.dtype(np.float64)) == np.float64

    def test_int32_allreduce_no_overflow(self, store):
        # Partial ring sums must widen to i64 (values near 2**30, world 3).
        world = 3
        pgs = make_group(store, world, prefix="ovf")
        data = [np.full(4, 2**30 - 1, dtype=np.int64) for _ in range(world)]

        def op(rank, _):
            return pgs[rank].allreduce([data[rank].astype(np.int64)]).wait()[0]

        for result in run_parallel(world, op):
            np.testing.assert_array_equal(result, np.full(4, 3 * (2**30 - 1)))
        # int32 inputs widen internally and cast back
        data32 = [np.full(4, 1000, dtype=np.int32) for _ in range(world)]

        def op32(rank, _):
            out = pgs[rank].allreduce([data32[rank]]).wait()[0]
            assert out.dtype == np.int32
            return out

        for result in run_parallel(world, op32):
            np.testing.assert_array_equal(result, np.full(4, 3000))
        for pg in pgs:
            pg.shutdown()


class TestFlightRecorder:
    """On abort/deadline of a wedged collective, the in-flight op table
    (op, peer, tag, bytes progressed, deadline, generation) must land in
    the structured event pipeline — reference dumps the NCCL flight
    recorder on abort for the same postmortems
    (torchft/process_group.py:89-108,830-838)."""

    def test_wedged_collective_dumps_flight_record(self, store, tmp_path, monkeypatch):
        import json

        events_file = tmp_path / "events.jsonl"
        monkeypatch.setenv("TORCHFT_EVENTS_FILE", str(events_file))

        world = 2
        pgs = make_group(store, world, prefix="fr", timeout=2.0)
        try:
            # rank 0 submits an allreduce; rank 1 never does -> rank 0's ring
            # exchange wedges on the recv until its deadline fires
            with pytest.raises(Exception):
                pgs[0].allreduce([np.ones(1024, np.float32)]).wait(timeout=10)

            events = [
                json.loads(line)
                for line in events_file.read_text().strip().splitlines()
            ]
            aborts = [e for e in events if e["kind"] == "abort"]
            assert aborts, f"no abort record in {events}"
            rec = aborts[-1]
            assert rec["op"] == "allreduce"
            assert rec["rank"] == 0 and rec["world"] == 2
            assert "generation" in rec and "in_flight_s" in rec
            # it wedged waiting on rank 1 with an expired deadline
            assert rec["recv_peer"] == 1
            assert rec["deadline_remaining_s"] <= 0.1
        finally:
            for pg in pgs:
                pg.shutdown()

    def test_abort_mid_op_dumps_flight_record(self, store, monkeypatch):
        from torchft_tpu.utils.logging import recent_events

        world = 2
        pgs = make_group(store, world, prefix="fr2", timeout=30.0)
        try:
            # wedge rank 0 (long deadline), then abort it from another thread
            work = pgs[0].allreduce([np.ones(8, np.float32)])
            import time as _t

            _t.sleep(0.2)  # let the worker enter the blocked recv
            pgs[0].abort()
            with pytest.raises(Exception):
                work.wait(timeout=10)
            aborts = [e for e in recent_events() if e["kind"] == "abort"]
            assert aborts and aborts[-1]["op"] == "allreduce"
        finally:
            for pg in pgs:
                pg.shutdown()


class TestBandwidthShaper:
    """Egress token-bucket shaping (the measured-DCN bench harness and
    the TORCHFT_WIRE_GBPS knob)."""

    def test_token_bucket_rate(self):
        from torchft_tpu.parallel.process_group import _TokenBucket

        bucket = _TokenBucket(100e6, burst=1 << 20)  # 100 MB/s, 1 MB burst
        t0 = time.monotonic()
        total = 0
        while total < 20 << 20:  # 20 MB
            bucket.consume(1 << 20)
            total += 1 << 20
        elapsed = time.monotonic() - t0
        # fluid-model time for 20 MB minus the 1 MB burst at 100 MB/s is
        # ~0.199 s; allow generous slop above (slow CI) but the floor
        # proves the shaper actually paces
        assert 0.15 <= elapsed <= 1.0, elapsed

    def test_shaped_allreduce_measures_rate(self, store):
        """Asserts on the token bucket's OWN ledger (bytes debited,
        seconds slept serving debt) rather than comparing wall-clock
        legs: a loaded CI box can stretch the unshaped leg past the
        shaped one, but it cannot make the shaper's accounting lie."""
        from torchft_tpu.parallel.process_group import _TokenBucket

        world = 2
        pgs = [ProcessGroupTCP(timeout=60.0) for _ in range(world)]

        def configure(rank, _):
            pgs[rank].configure(
                f"{store.address()}/shaped", f"rank{rank}", rank, world
            )

        run_parallel(world, configure)
        # 50 MB/s with a 1 MB burst: a ring allreduce of 8 MB at w=2
        # moves ~8 MB per rank, so every sender runs well past its burst
        # and MUST serve debt (sleep) in its own bucket
        for pg in pgs:
            pg._bucket = _TokenBucket(50e6, burst=1 << 20)
        data = np.ones(2 << 20, dtype=np.float32)

        def run(rank, _):
            pgs[rank].allreduce([data.copy()], REDUCE_SUM).wait(timeout=60)

        run_parallel(world, run)
        for pg in pgs:
            bucket = pg._bucket
            assert bucket is not None
            # each rank's egress (reduce-scatter + allgather halves) ran
            # through its bucket: at least half the payload was debited
            assert bucket.consumed_bytes >= data.nbytes // 2, (
                bucket.consumed_bytes
            )
            # debt beyond the burst was actually paced off
            assert bucket.slept_s > 0.0
        for pg in pgs:
            pg.set_bandwidth(None)
            assert pg._bucket is None
        # unshaped leg still reduces correctly with shaping removed
        run_parallel(world, run)
        for pg in pgs:
            pg.shutdown()

    def test_env_knob(self, store, monkeypatch):
        monkeypatch.setenv("TORCHFT_WIRE_GBPS", "0.25")
        pg = ProcessGroupTCP(timeout=5.0)
        assert pg._bucket is not None
        assert pg._bucket.rate == 0.25e9
        monkeypatch.delenv("TORCHFT_WIRE_GBPS")
        pg2 = ProcessGroupTCP(timeout=5.0)
        assert pg2._bucket is None


# ---------------------------------------------------------------------------
# The plain ring's contract (PR 25): no copy of what came off the device, a
# leased ring buffer, the average in place by the buffer's owner
# ---------------------------------------------------------------------------


def _contract_leaves(rank):
    """Mixed leaves whose sums are exact in every dtype (small integers),
    so any order of additions gives the same bits as plain numpy.  Sizes
    that need padding at world sizes 2 and 3; one leaf over BUCKET_BYTES
    rings alone, the small ones share a bucket."""
    import ml_dtypes

    rng = np.random.default_rng(100 + rank)

    def ints(shape, lo=-40, hi=40):
        return rng.integers(lo, hi, size=shape)

    return [
        ints(7).astype(np.float32),
        ints((1 << 20) + 3).astype(np.float32),
        ints(5, -8, 8).astype(ml_dtypes.bfloat16),
        ints(11).astype(np.int32),
        ints((3, 5)).astype(np.float32),
        ints(2).astype(np.float64),
        # dimensions in another order in memory, as a leaf comes off a TPU
        # when its last dimension is no multiple of 128
        ints((6, 4)).astype(np.float32).T,
        np.swapaxes(ints((2, 5, 3)).astype(np.float32), 1, 2),
    ]


def _numpy_reduce(per_rank, op, divisor=None):
    """What plain numpy gives: the leaves stacked over ranks, reduced in
    the accumulation dtype, divided there, cast back."""
    from torchft_tpu.parallel.process_group import _accumulation_dtype

    out = []
    for leaves in zip(*per_rank):
        acc = _accumulation_dtype(leaves[0].dtype)
        stack = np.stack([x.astype(acc) for x in leaves])
        if op == REDUCE_MAX:
            total = stack.max(axis=0)
        else:
            total = stack.sum(axis=0, dtype=acc)
        by = len(per_rank) if op == REDUCE_AVG else divisor
        if by not in (None, 1):
            total = total / by if acc.kind != "f" else (total / acc.type(by))
        out.append(np.asarray(total).astype(leaves[0].dtype))
    return out


def _assert_same_bits(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert g.tobytes() == w.tobytes()


def _world(store, world, prefix):
    if world == 1:
        pg = ProcessGroupTCP(timeout=20.0)
        pg.configure("", "rank0", 0, 1)
        return [pg]
    return make_group(store, world, prefix)


def _shutdown(pgs):
    for pg in pgs:
        pg.shutdown()


@pytest.fixture
def ring_spans(tmp_path):
    """Runs ``fn()`` under an open ``ring`` phase with a file-sink tracer
    installed; returns its result and the spans of ``ring``'s parts, as
    ``(name, attributes)`` in the order they ended."""
    import json

    from torchft_tpu.utils import tracing

    def run(fn):
        path = tmp_path / "spans.jsonl"
        path.unlink(missing_ok=True)  # the sink appends
        tracing.install_tracer(
            tracing.Tracer(sink=tracing.FileSpanSink(str(path)))
        )
        tracing.set_current(tracing.TraceContext("a" * 32, "b" * 16))
        try:
            ring = tracing.phase("ring", {}).begin()
            with tracing.under(ring):
                out = fn()
            ring.end()
        finally:
            tracing.set_current(None)
            tracing.uninstall_tracer()
        spans = [json.loads(l) for l in path.read_text().splitlines() if l]
        return out, [(s["name"], s["attributes"]) for s in spans]

    return run


@pytest.fixture
def pack_spans(ring_spans):
    """As ``ring_spans``, the ``ring.pack`` spans' attributes alone."""

    def run(fn):
        out, spans = ring_spans(fn)
        return out, [attrs for name, attrs in spans if name == "ring.pack"]

    return run


class TestRingContract:
    @pytest.mark.parametrize("op", [REDUCE_SUM, REDUCE_AVG, REDUCE_MAX])
    @pytest.mark.parametrize("world", [1, 2, 3])
    def test_bit_identical_to_numpy(self, store, world, op):
        pgs = _world(store, world, f"contract-{world}-{op}")
        data = [_contract_leaves(r) for r in range(world)]
        before = [[x.copy() for x in leaves] for leaves in data]
        want = _numpy_reduce(data, op)

        def run(rank, _):
            return pgs[rank].allreduce(data[rank], op).wait(timeout=30)

        results = run_parallel(world, run)
        for rank, got in enumerate(results):
            _assert_same_bits(got, want)
            # the caller's arrays: not written, not part of the result
            _assert_same_bits(data[rank], before[rank])
            for g, x in zip(got, data[rank]):
                assert not np.shares_memory(g, x)
        _shutdown(pgs)

    @pytest.mark.parametrize("world,divisor", [(1, 3), (2, 3), (3, 2), (2, 1)])
    def test_mean_by_a_divisor_that_is_not_the_world_size(
        self, store, world, divisor
    ):
        """The Manager's average: the sum over the group divided by the
        live participant count, by the group, in the accumulation dtype."""
        pgs = _world(store, world, f"mean-{world}-{divisor}")
        data = [_contract_leaves(r) for r in range(world)]
        floats = [i for i, x in enumerate(data[0]) if x.dtype.kind != "i"]
        data = [[leaves[i] for i in floats] for leaves in data]
        before = [[x.copy() for x in leaves] for leaves in data]
        want = _numpy_reduce(data, REDUCE_SUM, divisor)

        def run(rank, _):
            work = pgs[rank].allreduce(data[rank], REDUCE_SUM, divisor=divisor)
            return work.wait(timeout=30)

        for rank, got in enumerate(run_parallel(world, run)):
            _assert_same_bits(got, want)
            _assert_same_bits(data[rank], before[rank])
        _shutdown(pgs)

    def test_random_floats_agree_across_ranks(self, store):
        # each chunk is reduced in one fixed order on one rank, so every
        # rank holds the same bits whatever the values
        world = 3
        pgs = make_group(store, world, "bits")
        rng = np.random.default_rng(7)
        data = [
            [rng.standard_normal(1001).astype(np.float32),
             rng.standard_normal((1 << 20) + 5).astype(np.float32)]
            for _ in range(world)
        ]

        def run(rank, _):
            return pgs[rank].allreduce(data[rank], REDUCE_AVG).wait(timeout=30)

        results = run_parallel(world, run)
        for got in results[1:]:
            _assert_same_bits(got, results[0])
        np.testing.assert_allclose(
            results[0][0], sum(d[0] for d in data) / world, rtol=1e-5, atol=1e-6
        )
        _shutdown(pgs)

    @pytest.mark.parametrize("kind", ["tcp", "dummy"])
    def test_device_leaf_at_world_one_is_handed_through(self, kind, ring_spans):
        """A ``jax.Array`` leaf at world size 1 is the result itself: it
        never leaves the device, no pool allocation, no bytes copied.  The
        host leaf beside it is still copied."""
        import jax
        import jax.numpy as jnp

        from torchft_tpu.utils.bufpool import POOL

        if kind == "tcp":
            pg = ProcessGroupTCP(timeout=20.0)
            pg.configure("", "rank0", 0, 1)
        else:
            pg = ProcessGroupDummy()
        dev = jnp.arange(1 << 16, dtype=jnp.float32)
        host = np.arange(8, dtype=np.float32)
        misses, hits = POOL.misses, POOL.hits

        def run():
            return pg.allreduce([dev, host], REDUCE_AVG).wait(timeout=20)

        (got_dev, got_host), spans = ring_spans(run)
        assert (POOL.misses, POOL.hits) == (misses, hits)
        assert isinstance(got_dev, jax.Array)
        assert got_dev.unsafe_buffer_pointer() == dev.unsafe_buffer_pointer()
        assert got_dev.sharding == dev.sharding
        # nothing left the device, and the span says so
        assert _attr(spans, "ring.d2h", "bytes") == [0]
        assert _attr(spans, "ring.d2h", "kept") == [dev.nbytes]
        assert _attr(spans, "ring.pack", "copied") == [host.nbytes]
        assert _attr(spans, "ring.pack", "handed") == [dev.nbytes]
        np.testing.assert_array_equal(got_dev, np.arange(1 << 16))
        # the caller's host leaf is copied: the result is its own memory
        assert isinstance(got_host, np.ndarray) and got_host.flags.writeable
        assert not np.shares_memory(got_host, host)
        got_host += 1
        np.testing.assert_array_equal(host, np.arange(8))
        pg.shutdown()

    @pytest.mark.parametrize("kind", ["tcp", "dummy"])
    def test_sharded_leaf_at_world_one_keeps_its_sharding(
        self, kind, ring_spans, monkeypatch
    ):
        """A leaf spread over several devices is not gathered to the host:
        it comes back as it is, shard for shard."""
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec

        from torchft_tpu.parallel import process_group

        def no_host(x):
            raise AssertionError("a sharded leaf was gathered to the host")

        mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("fsdp", "tp"))
        rows = NamedSharding(mesh, PartitionSpec("fsdp", "tp"))
        whole = NamedSharding(mesh, PartitionSpec())
        x = np.arange(8 * 6, dtype=np.float32).reshape(8, 6)
        leaves = [jax.device_put(x, rows), jax.device_put(x[0], whole)]
        (pg,) = _world(None, 1, "sharded") if kind == "tcp" else [ProcessGroupDummy()]
        monkeypatch.setattr(process_group, "_as_numpy", no_host)
        got, spans = ring_spans(
            lambda: pg.allreduce(leaves, REDUCE_AVG).wait(timeout=20)
        )
        monkeypatch.undo()
        for g, leaf in zip(got, leaves):
            assert isinstance(g, jax.Array) and g.sharding == leaf.sharding
            assert len(g.addressable_shards) == 4
            for a, b in zip(g.addressable_shards, leaf.addressable_shards):
                assert a.device == b.device
                assert a.data.unsafe_buffer_pointer() == b.data.unsafe_buffer_pointer()
        np.testing.assert_array_equal(got[0], x)
        assert _attr(spans, "ring.d2h", "kept") == [x.nbytes + x[0].nbytes]
        assert _attr(spans, "ring.d2h", "bytes") == [0]
        pg.shutdown()

    @pytest.mark.parametrize("kind", ["tcp", "dummy"])
    def test_a_divisor_above_one_at_world_one_still_divides_on_the_host(
        self, kind, ring_spans
    ):
        """No loop passes one to a lone group; the contract holds anyway, on
        the host path as before: the device leaf leaves the device."""
        import jax.numpy as jnp
        import ml_dtypes

        (pg,) = _world(None, 1, "div2") if kind == "tcp" else [ProcessGroupDummy()]
        dev = jnp.arange(10, dtype=jnp.float32) * 3
        bf16 = jnp.arange(6, dtype=jnp.float32).astype(jnp.bfloat16)
        host = np.arange(4, dtype=np.float32) + 1
        got, spans = ring_spans(
            lambda: pg.allreduce([dev, bf16, host], REDUCE_SUM, divisor=2).wait(timeout=20)
        )
        assert all(type(g) is np.ndarray for g in got)
        want = [
            np.arange(10, dtype=np.float32) * 3 / 2,
            (np.arange(6, dtype=np.float32) / 2).astype(ml_dtypes.bfloat16),
            (np.arange(4, dtype=np.float32) + 1) / 2,
        ]
        _assert_same_bits(got, want)
        np.testing.assert_array_equal(host, np.arange(4) + 1)
        assert not np.shares_memory(got[2], host)
        assert _attr(spans, "ring.d2h", "bytes") == [dev.nbytes + bf16.nbytes]
        assert _attr(spans, "ring.d2h", "kept") == [0]
        total = dev.nbytes + bf16.nbytes + host.nbytes
        assert _attr(spans, "ring.pack", "copied") == [total]
        pg.shutdown()

    @pytest.mark.parametrize("kind", ["tcp", "dummy"])
    def test_leaves_kept_counter(self, store, kind):
        """``torchft_ring_leaves_kept_total``: the device leaves a lone
        group handed back, under the stable replica id; a host leaf, a
        divisor above 1 and a group that rings add nothing."""
        import jax.numpy as jnp

        from torchft_tpu.utils import metrics

        def count(replica_id):
            return metrics.RING_LEAVES_KEPT.labels(replica_id=replica_id).get()

        name = f"kept-{kind}"
        if kind == "tcp":
            pg = ProcessGroupTCP(timeout=20.0)
        else:
            pg = ProcessGroupDummy()
        pg.configure("", f"{name}:incarnation-1", 0, 1)
        dev = [jnp.ones((3,)), jnp.ones((2, 2)), jnp.zeros((1,))]
        start = count(name)
        pg.allreduce(dev + [np.ones(2, np.float32)], REDUCE_AVG).wait(timeout=20)
        assert count(name) == start + 3
        pg.allreduce(dev[:2], REDUCE_SUM).wait(timeout=20)
        assert count(name) == start + 5
        pg.allreduce(dev, REDUCE_SUM, divisor=2).wait(timeout=20)
        pg.allreduce([np.ones(2, np.float32)]).wait(timeout=20)
        assert count(name) == start + 5
        pg.shutdown()
        if kind == "tcp":
            # a pair rings: nothing is kept
            pgs = make_group(store, 2, "kept-pair")
            pair = count("rank0")
            run_parallel(2, lambda r, _: pgs[r].allreduce(dev).wait(timeout=30))
            assert count("rank0") == pair
            _shutdown(pgs)

    def test_pack_says_what_was_copied_and_whether_the_pool_hit(
        self, store, pack_spans
    ):
        world = 2
        pgs = make_group(store, world, "packattrs")
        n = (1 << 20) + 3  # odd: one padded tail chunk
        big = [np.full(n, r + 1.0, np.float32) for r in range(world)]
        half = -(-n // world)

        def rank0_packs():
            # the other rank runs without an open phase: its parts are
            # annotations only, so the spans are rank 0's
            def run(rank, _):
                if rank == 0:
                    return pack_spans(
                        lambda: pgs[0].allreduce([big[0]]).wait(timeout=30)[0]
                    )
                return pgs[1].allreduce([big[1]]).wait(timeout=30)[0], None

            return run_parallel(world, run)[0]

        from torchft_tpu.utils.bufpool import POOL

        POOL.clear()
        first, packs = rank0_packs()
        np.testing.assert_array_equal(first, np.full(n, 3.0))
        (pack,) = packs
        # only the chunk with the zero-padded tail is copied in
        assert pack["handed"] == half * 4 and pack["copied"] == (n - half) * 4
        assert pack["pool"] == "miss"
        del first
        second, packs = rank0_packs()
        assert packs[0]["pool"] == "hit"
        np.testing.assert_array_equal(second, np.full(n, 3.0))
        _shutdown(pgs)

    def test_ring_buffer_counter(self, store):
        from torchft_tpu.utils import metrics
        from torchft_tpu.utils.bufpool import POOL

        world = 2
        pgs = make_group(store, world, "ringctr")
        x = np.ones(300_001, np.float32)  # a size no other test leases

        def count(result):
            return metrics.RING_BUFFERS.labels(
                replica_id="rank0", result=result
            ).get()

        def run(rank, _):
            pgs[rank].allreduce([x]).wait(timeout=30)

        POOL.clear()
        hit, miss = count("hit"), count("miss")
        run_parallel(world, run)
        assert (count("hit"), count("miss")) == (hit, miss + 1)
        for _ in range(3):
            run_parallel(world, run)
        assert (count("hit"), count("miss")) == (hit + 3, miss + 1)
        _shutdown(pgs)

    @pytest.mark.parametrize("case", ["swallowed", "failed-op"])
    def test_error_fallback_hands_the_input_back_unwritten(self, case):
        """The error-swallowing fallback's result IS the caller's array: a
        division in place on it, by anyone who guessed it owned the
        result, would write the caller's memory."""
        x = np.arange(6, dtype=np.float32)
        if case == "swallowed":
            pg = ErrorSwallowingProcessGroupWrapper(ProcessGroupDummy())
            pg.report_error(RuntimeError("down"))
        else:
            inner = FakeProcessGroupWrapper(ProcessGroupDummy())
            inner.report_future_error(RuntimeError("injected"))
            pg = ErrorSwallowingProcessGroupWrapper(inner)
        (got,) = pg.allreduce([x], divisor=2).wait(timeout=5)
        assert got is x and pg.errored() is not None
        np.testing.assert_array_equal(x, np.arange(6))

    def test_a_leaf_in_another_memory_order_is_copied_in_once(
        self, store, pack_spans
    ):
        """No ``ravel()`` into fresh memory first: the strided leaf goes
        into the leased buffer in one pass, and the result is C order."""
        world = 2
        pgs = make_group(store, world, "strided")
        n = (1 << 20) + 8
        leaves = [
            np.arange(2 * n, dtype=np.float32).reshape(2, n).T * (r + 1)
            for r in range(world)
        ]
        assert not leaves[0].flags.c_contiguous

        def run(rank, _):
            if rank == 0:
                return pack_spans(
                    lambda: pgs[0].allreduce([leaves[0]]).wait(timeout=30)[0]
                )
            return pgs[1].allreduce([leaves[1]]).wait(timeout=30)[0], None

        got, packs = run_parallel(world, run)[0]
        (pack,) = packs
        assert pack["copied"] == leaves[0].nbytes and pack["handed"] == 0
        assert got.flags.c_contiguous and got.shape == (n, 2)
        np.testing.assert_array_equal(got, leaves[0] + leaves[1])
        _shutdown(pgs)


# ---------------------------------------------------------------------------
# One entry point (PR 30): every group reduces AND divides behind
# ``allreduce(arrays, op, divisor)``, the wrappers included
# ---------------------------------------------------------------------------

_GROUP_KINDS = [
    "dummy", "tcp-alone", "tcp-pair", "wrapper(dummy)", "swallowing(dummy)",
    "fake(dummy)",
]


def _groups_of(kind, store, prefix):
    """The ranks of one group of ``kind``: two for ``tcp-pair``, else one."""
    if kind == "tcp-pair":
        return make_group(store, 2, prefix)
    if kind == "tcp-alone":
        return _world(None, 1, prefix)
    wrap = {
        "dummy": lambda pg: pg,
        "wrapper(dummy)": ProcessGroupWrapper,
        "swallowing(dummy)": ErrorSwallowingProcessGroupWrapper,
        "fake(dummy)": FakeProcessGroupWrapper,
    }[kind]
    return [wrap(ProcessGroupDummy())]


def _typed_leaves(rank, dtype):
    """Small integers (sums exact in every dtype): a size that pads at
    world size 2, a matrix, and one whose memory is in another order."""
    rng = np.random.default_rng(300 + rank)
    return [
        rng.integers(-40, 40, size=shape).astype(dtype)
        for shape in [(7,), (3, 5)]
    ] + [rng.integers(-40, 40, size=(6, 4)).astype(dtype).T]


class TestAllreduceContract:
    @pytest.mark.parametrize("divisor", [None, 3])
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int32"])
    @pytest.mark.parametrize("kind", _GROUP_KINDS)
    def test_sum_over_divisor_in_the_leafs_dtype(
        self, store, kind, dtype, divisor
    ):
        import ml_dtypes

        dtype = np.dtype(getattr(ml_dtypes, dtype, dtype))
        pgs = _groups_of(kind, store, f"one-{kind}-{dtype.name}-{divisor}")
        data = [_typed_leaves(r, dtype) for r in range(len(pgs))]
        before = [[x.copy() for x in leaves] for leaves in data]
        want = _numpy_reduce(data, REDUCE_SUM, divisor)

        def run(rank, _):
            work = pgs[rank].allreduce(data[rank], REDUCE_SUM, divisor=divisor)
            return work.wait(timeout=30)

        for rank, got in enumerate(run_parallel(len(pgs), run)):
            _assert_same_bits(got, want)  # value, dtype and shape
            # the caller's arrays: not written, not part of the result
            _assert_same_bits(data[rank], before[rank])
            for g, x in zip(got, data[rank]):
                assert not np.shares_memory(g, x)
        _shutdown(pgs)

    def test_managed_group_refuses_a_divisor(self):
        from unittest.mock import MagicMock

        from torchft_tpu.parallel.process_group import ManagedProcessGroup

        manager = MagicMock()
        pg = ManagedProcessGroup(manager)
        x = np.ones(3, np.float32)
        with pytest.raises(ValueError, match="divisor"):
            pg.allreduce([x], REDUCE_SUM, divisor=2)
        manager.allreduce.assert_not_called()
        # the Manager it routes to averages by the live count itself
        pg.allreduce([x], REDUCE_AVG)
        manager.allreduce.assert_called_once_with([x], reduce_op=REDUCE_AVG)

    def test_every_group_takes_the_keyword(self):
        import inspect

        from torchft_tpu.parallel import process_group
        from torchft_tpu.parallel.process_group import ProcessGroup

        groups = [
            cls
            for _, cls in inspect.getmembers(process_group, inspect.isclass)
            if issubclass(cls, ProcessGroup) and not inspect.isabstract(cls)
        ]
        assert {cls.__name__ for cls in groups} >= {
            "ProcessGroupDummy", "ProcessGroupTCP", "ProcessGroupWrapper",
            "ErrorSwallowingProcessGroupWrapper", "FakeProcessGroupWrapper",
            "ManagedProcessGroup",
        }
        for cls in groups:
            params = inspect.signature(cls.allreduce).parameters
            assert list(params)[:4] == ["self", "arrays", "op", "divisor"], cls
            assert params["divisor"].default is None, cls
        assert not hasattr(ProcessGroup, "_allreduce_mean")


# ---------------------------------------------------------------------------
# A device leaf held in another order of dimensions leaves the device flat:
# the ring re-orders nothing on the host, and the result is bit for bit what
# the host-side path gives
# ---------------------------------------------------------------------------


def _on_device(x, order=None, device=0):
    """``x`` as a ``jax.Array``, held with its dimensions in ``order``
    (major to minor) where one is given: what a TPU does of its own accord
    to a leaf whose last dimension is no multiple of 128.  The CPU backend
    takes the layout and hands the host copy back in it, as strides."""
    import jax
    from jax.experimental.layout import Format, Layout

    where = jax.sharding.SingleDeviceSharding(jax.devices()[device])
    if order is not None:
        where = Format(Layout(major_to_minor=order), where)
    return jax.device_put(x, where)


def _relayout_leaves(rank, dtype, held):
    """One leaf over ``BUCKET_BYTES`` that rings alone, with a size that
    pads at world sizes 2 and 3, two small ones that share a bucket, and a
    vector; ``held`` = ``"device-order"`` puts every leaf of two or more
    dimensions in another order than its shape's."""
    rng = np.random.default_rng(200 + rank)
    shapes = [(1025, 1027), (3, 5, 7), (6, 4), (11,)]
    orders = [(1, 0), (0, 2, 1), (1, 0), None]
    return [
        _on_device(
            rng.standard_normal(shape).astype(np.float32).astype(dtype),
            order if held == "device-order" else None,
        )
        for shape, order in zip(shapes, orders)
    ]


def _spans_of_rank0(pgs, ring_spans, per_rank, op=REDUCE_AVG):
    """One allreduce of ``per_rank[r]`` on every rank; rank 0 runs under an
    open ``ring`` and hands back ``(work, result, spans)``, the other ranks
    run without a phase (their parts are annotations only)."""

    def run(rank, _):
        def once():
            work = pgs[rank].allreduce(per_rank[rank], op)
            return work, work.wait(timeout=30)

        if rank == 0:
            (work, got), spans = ring_spans(once)
            return work, got, spans
        return once()

    return run_parallel(len(pgs), run)[0]


def _attr(spans, name, key):
    return [attrs[key] for n, attrs in spans if n == name]


class TestDeviceRelayout:
    @pytest.mark.parametrize("held", ["default", "device-order"])
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("world", [2, 3])
    def test_result_is_bitwise_the_host_paths(
        self, store, ring_spans, world, dtype, held
    ):
        import ml_dtypes

        dtype = np.dtype(getattr(ml_dtypes, dtype, dtype))
        pgs = make_group(store, world, f"relay-{world}-{dtype.name}-{held}")
        leaves = [_relayout_leaves(r, dtype, held) for r in range(world)]
        # the path before: the leaves' host copies as the device hands them
        # over (strides and all), re-ordered by the ring on the host
        hosts = [[np.asarray(x) for x in rank] for rank in leaves]
        strided = [not h.flags.c_contiguous for h in hosts[0]]
        assert strided == [held == "device-order"] * 3 + [False]
        _, want, host_spans = _spans_of_rank0(pgs, ring_spans, hosts)
        work, got, spans = _spans_of_rank0(pgs, ring_spans, leaves)

        _assert_same_bits(got, want)
        for g, leaf in zip(got, leaves[0]):
            assert g.shape == leaf.shape and g.dtype == leaf.dtype
            assert g.flags.c_contiguous
        # ring.d2h says what was laid out on the device: the leaves held in
        # another order, nothing else, and nothing of a host leaf
        moved = sum(h.nbytes for h, s in zip(hosts[0], strided) if s)
        assert _attr(spans, "ring.d2h", "relaid") == [moved]
        assert _attr(host_spans, "ring.d2h", "relaid") == [0]
        # the leaf that rings alone: a float32 one is the ring's source
        # where it lies and only the chunk with the padded tail is copied;
        # one that widens is cast into the buffer whole, as before
        n = hosts[0][0].size
        chunk = -(-n // world)
        tail = n - (n // chunk) * chunk
        solo = next(a for m, a in spans if m == "ring.pack" and "copied" in a)
        if dtype == np.float32:
            assert (solo["copied"], solo["handed"]) == (tail * 4, (n - tail) * 4)
        else:
            assert (solo["copied"], solo["handed"]) == (n * 4, 0)
        # same plan, same bytes over the link
        assert _attr(spans, "ring.wire", "bytes") == _attr(host_spans, "ring.wire", "bytes")
        assert work.wire_bytes == sum(_attr(spans, "ring.wire", "bytes"))
        _shutdown(pgs)

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    def test_alone_the_leaf_is_still_its_own_device_to_host_copy(
        self, ring_spans, monkeypatch, dtype
    ):
        """(The name is the contract before PR 31.)  Alone, the leaf never
        leaves the device: nothing is laid out, nothing is copied, and it
        comes back in the order of dimensions the device holds it in."""
        import jax
        import ml_dtypes

        from torchft_tpu.parallel import process_group

        def no_host(x):
            raise AssertionError("a leaf left the device")

        monkeypatch.setattr(process_group, "_as_numpy", no_host)
        monkeypatch.setattr(process_group, "_to_host", no_host)
        dtype = np.dtype(getattr(ml_dtypes, dtype, dtype))
        (pg,) = _world(None, 1, "relay-alone")
        leaf = _on_device(
            np.arange(6 * 4, dtype=np.float32).reshape(6, 4).astype(dtype), (1, 0)
        )
        _, (got,), spans = _spans_of_rank0([pg], ring_spans, [[leaf]], REDUCE_SUM)
        assert _attr(spans, "ring.d2h", "relaid") == [0]
        assert _attr(spans, "ring.d2h", "bytes") == [0]
        assert _attr(spans, "ring.d2h", "kept") == [leaf.nbytes]
        assert _attr(spans, "ring.pack", "copied") == [0]
        assert _attr(spans, "ring.pack", "handed") == [leaf.nbytes]
        assert got is leaf and isinstance(got, jax.Array)
        assert got.format == leaf.format  # the device's order kept
        monkeypatch.undo()
        _assert_same_bits([np.asarray(got)], [np.asarray(leaf)])
        pg.shutdown()

    @pytest.mark.parametrize("order", ["c", "strided"])
    def test_a_numpy_leaf_is_never_handed_to_jax(
        self, store, ring_spans, monkeypatch, order
    ):
        from torchft_tpu.parallel import process_group

        def no_jax():
            raise AssertionError("a host leaf went to the device")

        monkeypatch.setattr(process_group, "_flatten_jit", no_jax)
        world = 2
        pgs = make_group(store, world, f"relay-numpy-{order}")
        n = (1 << 20) + 8
        data = [
            np.arange(2 * n, dtype=np.float32).reshape(2, n) * (r + 1)
            for r in range(world)
        ]
        if order == "strided":
            data = [x.T for x in data]
        _, (got,), spans = _spans_of_rank0(pgs, ring_spans, [[x] for x in data], REDUCE_SUM)
        assert _attr(spans, "ring.d2h", "relaid") == [0]
        # a strided host array is still copied in, in one pass
        copied = data[0].nbytes if order == "strided" else 0
        assert _attr(spans, "ring.pack", "copied") == [copied]
        np.testing.assert_array_equal(got, data[0] + data[1])
        _shutdown(pgs)

    @pytest.mark.parametrize("world", [2, 3])
    def test_a_mixed_list_keeps_the_plan_and_the_wire_bytes(
        self, store, ring_spans, world
    ):
        """Device leaves in either order, host leaves, small ones that
        share a bucket: the buckets and ``Work.wire_bytes`` are those of
        the same list as host arrays."""
        import ml_dtypes

        pgs = make_group(store, world, f"relay-mixed-{world}")

        def mixed(rank):
            f32 = _relayout_leaves(rank, np.dtype(np.float32), "device-order")
            bf16 = _relayout_leaves(rank, np.dtype(ml_dtypes.bfloat16), "default")
            host = _contract_leaves(rank)
            return [f32[1], host[0], bf16[2], f32[0], host[6], f32[2], host[3], bf16[0]]

        leaves = [mixed(r) for r in range(world)]
        hosts = [[np.asarray(x) for x in rank] for rank in leaves]
        host_work, want, host_spans = _spans_of_rank0(pgs, ring_spans, hosts, REDUCE_SUM)
        work, got, spans = _spans_of_rank0(pgs, ring_spans, leaves, REDUCE_SUM)
        _assert_same_bits(got, want)
        assert work.wire_bytes == host_work.wire_bytes > 0
        for name, key in [("ring.wire", "bytes"), ("ring.pack", "leaves"), ("ring.unpack", "leaves")]:
            assert [a.get(key) for n, a in spans if n == name] == [
                a.get(key) for n, a in host_spans if n == name
            ]
        # only the device leaves held in another order were laid out there
        assert _attr(spans, "ring.d2h", "relaid") == [
            sum(hosts[0][i].nbytes for i in (0, 3, 5))
        ]
        _shutdown(pgs)

    def test_leaves_on_two_devices_are_laid_out_on_each(self, store, ring_spans):
        """A list may hold leaves of more than one device (the stages of a
        pipeline): one program cannot take both, each device runs its own."""
        world = 2
        pgs = make_group(store, world, "relay-two-devices")
        rng = np.random.default_rng(5)
        leaves = [
            [
                _on_device(rng.standard_normal((6, 4)).astype(np.float32), (1, 0), d)
                for d in (0, 1, 0)
            ]
            for _ in range(world)
        ]
        hosts = [[np.asarray(x) for x in rank] for rank in leaves]
        _, want, _ = _spans_of_rank0(pgs, ring_spans, hosts)
        _, got, spans = _spans_of_rank0(pgs, ring_spans, leaves)
        _assert_same_bits(got, want)
        assert _attr(spans, "ring.d2h", "relaid") == [3 * 6 * 4 * 4]
        _shutdown(pgs)

    def test_ring_buffers_hit_the_pool_from_the_second_step(self, store, ring_spans):
        from torchft_tpu.utils.bufpool import POOL

        world = 2
        pgs = make_group(store, world, "relay-pool")
        POOL.clear()
        pools = []
        for step in range(3):
            leaves = [
                [_relayout_leaves(r + 10 * step, np.dtype(np.float32), "device-order")[0]]
                for r in range(world)
            ]
            work, got, spans = _spans_of_rank0(pgs, ring_spans, leaves)
            pools.append(_attr(spans, "ring.pack", "pool"))
            # the lease ends with the last view of the result
            del work, got
        assert pools == [["miss"], ["hit"], ["hit"]]
        _shutdown(pgs)


class _SlowLeaf:
    """A device leaf's stand-in: it can start its own host copy
    (``copy_to_host_async``, which notes the call in ``log``), and handing
    the host array over (``__array__``) waits until ``release`` is set, as
    ``np.asarray`` of a ``jax.Array`` waits for a copy still under way."""

    def __init__(self, name, value, log, release=None):
        self.name, self.value, self.log, self.release = name, value, log, release
        self.shape, self.dtype = value.shape, value.dtype
        self.size, self.nbytes, self.ndim = value.size, value.nbytes, value.ndim

    def copy_to_host_async(self):
        self.log.append(("started", self.name))

    def __array__(self, dtype=None, copy=None):
        self.log.append(("asked", self.name))
        if self.release is not None:
            assert self.release.wait(timeout=30), f"{self.name} never released"
        return self.value


_BIG = (1 << 20) + 3  # elements: over BUCKET_BYTES, pads at world size 2


def _pipeline_leaves(rank, log, release):
    """Four leaves over ``BUCKET_BYTES``, alike, that ring alone and two
    small ones that share a bucket ACROSS the second large one: the list
    holds ``big0, a, big1, b, big2, last``, the plan rings ``big0 | a + b
    | big1 | big2 | last``.  The last leaf's copy waits for ``release``."""
    shapes = {"big0": _BIG, "a": 5, "big1": _BIG, "b": (2, 3), "big2": _BIG, "last": _BIG}
    return [
        _SlowLeaf(
            name,
            np.full(shape, rank + 1.0 + k, np.float32),
            log,
            release if name == "last" else None,
        )
        for k, (name, shape) in enumerate(shapes.items())
    ]


def _wait_for(logs, reached):
    """Poll until ``reached()``; the logs are the message when it never is."""
    deadline = time.monotonic() + 30
    while not reached():
        assert time.monotonic() < deadline, logs
        time.sleep(0.01)


def _prefetched(replica_id):
    from torchft_tpu.utils import metrics

    return sum(
        metrics.RING_LEAVES_PREFETCHED.labels(
            replica_id=replica_id, result=result
        ).get()
        for result in ("ready", "waited")
    )


class TestLinkAheadOfTheRing:
    """At world size > 1 the device leaves' host copies are started ahead
    of the ring, in the order the plan rings the buckets, and each bucket
    waits only for its own leaves."""

    @pytest.mark.parametrize("world", [2, 3])
    def test_a_mixed_list_is_bitwise_each_leaf_rung_alone(
        self, store, ring_spans, world
    ):
        """Large float32 leaves, a bfloat16 leaf that widens, small leaves
        that share a bucket across the large ones, leaves the device holds
        in another order of dimensions, an ``np.ndarray`` leaf: what every
        rank gets is what ringing each leaf by itself gives (values whose
        sums are exact, so that a chunk's order of additions cannot
        tell)."""
        import ml_dtypes

        pgs = make_group(store, world, f"ahead-{world}")

        def mixed(rank):
            rng = np.random.default_rng(300 + rank)

            def ints(shape, dtype=np.float32):
                return rng.integers(-40, 40, size=shape).astype(dtype)

            return [
                _on_device(ints((1025, 1027))),
                _on_device(ints(7)),
                _on_device(ints((1 << 20) + 5, ml_dtypes.bfloat16)),
                _on_device(ints((6, 4)), (1, 0)),
                ints((1 << 20) + 3),  # the caller's host memory
                _on_device(ints((3, 5, 7)), (0, 2, 1)),
                _on_device(ints((1027, 1025)), (1, 0)),
                _on_device(ints(11)),
            ]

        leaves = [mixed(r) for r in range(world)]
        device_leaves = sum(not isinstance(x, np.ndarray) for x in leaves[0])
        before = [_prefetched(f"rank{r}") for r in range(world)]

        def alone(rank, _):
            return [
                pgs[rank].allreduce([np.asarray(x)], REDUCE_AVG).wait(timeout=30)[0]
                for x in leaves[rank]
            ]

        want = run_parallel(world, alone)
        assert [_prefetched(f"rank{r}") for r in range(world)] == before

        def together(rank, _):
            def once():
                return pgs[rank].allreduce(leaves[rank], REDUCE_AVG).wait(timeout=30)

            return ring_spans(once) if rank == 0 else (once(), None)

        got = run_parallel(world, together)
        for rank in range(world):
            _assert_same_bits(got[rank][0], want[rank])
            _assert_same_bits(got[rank][0], want[0])
            # every device leaf was sent ahead, the host leaf was not
            assert _prefetched(f"rank{rank}") == before[rank] + device_leaves
        spans = got[0][1]
        (nbytes,), (overlapped,) = (
            _attr(spans, "ring.d2h", key) for key in ("bytes", "overlapped")
        )
        assert nbytes == sum(x.nbytes for x in leaves[0])
        assert 0 <= overlapped <= nbytes - leaves[0][4].nbytes
        _shutdown(pgs)

    def test_a_bucket_rings_while_a_later_copy_is_still_under_way(self, store):
        """The copies are started in the plan's order, not the list's
        (``b`` before ``big1``), each before its bucket's turn and no
        further ahead than the plan's largest bucket (one large leaf here),
        and four buckets have rung on both ranks while the last leaf's copy
        is still under way."""
        world = 2
        pgs = make_group(store, world, "ahead-pipeline")
        release = threading.Event()
        logs = [[] for _ in range(world)]
        leaves = [_pipeline_leaves(r, logs[r], release) for r in range(world)]
        for rank, pg in enumerate(pgs):
            ring_one = pg._allreduce_one

            def rung(array, *args, _one=ring_one, _log=logs[rank]):
                out = _one(array, *args)
                _log.append(("rung", array.size))
                return out

            pg._allreduce_one = rung
        works = [pgs[r].allreduce(leaves[r], REDUCE_SUM) for r in range(world)]
        _wait_for(logs, lambda: all(("asked", "last") in log for log in logs))
        for log in logs:
            assert log == [
                ("started", "big0"), ("started", "a"), ("started", "b"),
                ("started", "big1"), ("asked", "big0"), ("rung", _BIG),
                ("asked", "a"), ("asked", "b"), ("rung", 11),
                ("started", "big2"), ("asked", "big1"), ("rung", _BIG),
                ("started", "last"), ("asked", "big2"), ("rung", _BIG),
                ("asked", "last"),
            ]
        assert not any(w.done() for w in works)
        release.set()
        for work in works:
            got = work.wait(timeout=30)
            for k, (g, leaf) in enumerate(zip(got, leaves[0])):
                assert g.shape == leaf.shape
                np.testing.assert_array_equal(
                    g, np.full(leaf.shape, 3.0 + 2 * k, np.float32)
                )
        assert [log[-1] for log in logs] == [("rung", _BIG)] * world
        _shutdown(pgs)

    def test_an_abort_on_the_wire_leaves_no_thread_and_no_lease(self, store):
        """Rank 1 never gets bucket 1's leaves, so rank 0 waits for it on
        bucket 1's wire: an abort there resolves rank 0's ``Work`` with the
        error; the copies started for the later buckets are dropped with
        their arrays, no thread outlives the op, and bucket 0's ring buffer
        goes back to the pool with the failed op."""
        import gc

        from torchft_tpu.utils.bufpool import POOL

        gc.collect()
        threads, leased = set(threading.enumerate()), POOL.leased_bytes
        world = 2
        pgs = make_group(store, world, "ahead-abort")
        stuck = threading.Event()
        logs = [[] for _ in range(world)]
        leaves = [_pipeline_leaves(r, logs[r], None) for r in range(world)]
        leaves[1][1].release = stuck  # "a", of bucket 1, on rank 1
        works = [pgs[r].allreduce(leaves[r], REDUCE_SUM) for r in range(world)]
        _wait_for(
            logs, lambda: ("asked", "b") in logs[0] and ("asked", "a") in logs[1]
        )
        time.sleep(0.05)  # rank 0 is inside bucket 1's first exchange
        pgs[0].abort()
        with pytest.raises(Exception):
            works[0].wait(timeout=30)
        assert pgs[0].errored() is not None
        # the later buckets: no leaf asked for, and the copies beyond the
        # one bucket that was sent ahead never started, on either rank
        for name in ("big2", "last"):
            assert ("started", name) not in logs[0] + logs[1]
        assert ("asked", "big1") not in logs[0] + logs[1]
        stuck.set()
        with pytest.raises(Exception):
            works[1].wait(timeout=30)
        _shutdown(pgs)
        # a failed op's traceback holds its frames, and they the buffer
        del works, pgs
        gc.collect()
        deadline = time.monotonic() + 10
        while set(threading.enumerate()) - threads and time.monotonic() < deadline:
            time.sleep(0.01)
        assert not set(threading.enumerate()) - threads
        assert POOL.leased_bytes == leased

    @pytest.mark.parametrize("kind", ["tcp", "dummy"])
    def test_alone_no_copy_is_started(self, kind, ring_spans):
        """World size 1 returns from ``_allreduce_alone`` before any of
        this: a leaf that could start its copy is not asked to, a
        ``jax.Array`` stays on its device (``kept`` as before)."""
        import jax
        import jax.numpy as jnp

        (pg,) = _world(None, 1, "ahead-alone") if kind == "tcp" else [ProcessGroupDummy()]
        log = []
        dev = jnp.arange(12, dtype=jnp.float32).reshape(3, 4)
        slow = _SlowLeaf("s", np.arange(4, dtype=np.float32), log)
        before = _prefetched("rank0")
        got, spans = ring_spans(
            lambda: pg.allreduce([dev, slow], REDUCE_AVG).wait(timeout=20)
        )
        assert ("started", "s") not in log
        assert got[0] is dev and isinstance(got[0], jax.Array)
        np.testing.assert_array_equal(got[1], slow.value)
        assert _attr(spans, "ring.d2h", "kept") == [dev.nbytes]
        assert _attr(spans, "ring.d2h", "bytes") == [0]
        assert "overlapped" not in dict(spans)["ring.d2h"]
        assert _prefetched("rank0") == before
        pg.shutdown()


def _ring_phases(pgs, leaves_of, before=None, rounds=1):
    """Each rank's allreduce of ``leaves_of(rank)`` on a thread of its own,
    under an open ``ring`` phase: the seconds of ``ring`` and its parts by
    rank, and when each rank called ``allreduce``.  ``before(rank, round)``
    runs on the rank's thread first (a rank that comes late)."""
    from torchft_tpu.utils import tracing

    sinks = [{} for _ in pgs]
    entered = [[] for _ in pgs]

    def op(rank, pg):
        for n in range(rounds):
            leaves = leaves_of(rank)
            if before is not None:
                before(rank, n)
            ring = tracing.phase("ring", sinks[rank]).begin()
            with tracing.under(ring):
                entered[rank].append(time.perf_counter())
                work = pg.allreduce(leaves, REDUCE_SUM)
            work.wait(timeout=20)
            ring.end()

    run_parallel(len(pgs), op, pgs)
    return sinks, entered


_WIRE_PARTS = tuple("ring.wire." + p for p in ("arrive", "wait", "recv", "send"))
_RING_PARTS = tuple("ring." + p for p in ("queue", "d2h", "pack", "wire", "reduce", "unpack"))


def _peer_wait(replica_id):
    from torchft_tpu.utils import metrics

    return {
        kind: metrics.RING_PEER_WAIT.labels(replica_id=replica_id, kind=kind).get()
        for kind in ("arrive", "wait")
    }


class TestWireOpened:
    """ISSUE 38: ``ring.wire`` opened on the PG worker thread, at the lines
    where an exchange blocks it, into ``arrive`` (the op's first exchange:
    the previous rank had not reached the ring), ``wait`` (a later one: it
    is late with a chunk), ``recv`` (the bytes) and ``send`` (the send's
    tail).  Every wait of these tests has a time limit of its own; the
    tolerances are on differences a loaded host moves together."""

    def test_a_rank_that_comes_late_is_its_successors_arrive(self, store):
        """(a) rank 1 calls 0.2 s after rank 0: rank 0's first exchange
        waits that long for rank 1's first byte, rank 1 finds rank 0's
        waiting.  Held against the skew the threads really had."""
        pgs = make_group(store, 2, "wire-late")
        tiny = lambda rank: [np.full(64, rank + 1.0, np.float32)]
        sinks, entered = _ring_phases(
            pgs, tiny, before=lambda rank, n: time.sleep(0.2 * rank)
        )
        skew = entered[1][0] - entered[0][0]
        assert 0.15 < skew < 5.0
        assert sinks[0]["ring.wire.arrive"] == pytest.approx(skew, abs=0.03)
        assert sinks[1]["ring.wire.arrive"] < 0.03
        # and it is all of rank 0's wire, not the bytes' or the send's
        assert sinks[0]["ring.wire.arrive"] >= 0.9 * sinks[0]["ring.wire"]
        for part in ("ring.wire.wait", "ring.wire.recv", "ring.wire.send"):
            assert sinks[0][part] < 0.03
        _shutdown(pgs)

    @pytest.mark.parametrize("world", [2, 3])
    def test_the_four_parts_add_up_to_wire_and_the_six_to_ring(self, store, world):
        """(b) at a size where an exchange takes milliseconds, over three
        ops: what lies between the parts is bookkeeping."""
        pgs = make_group(store, world, f"wire-sum{world}")
        big = lambda rank: [
            np.full(6_000_000, rank + 1.0, np.float32) for _ in range(2)
        ] + [np.ones(8, np.float32), np.ones(3, np.float32)]
        sinks, _ = _ring_phases(pgs, big, rounds=3)
        for s in sinks:
            assert set(_WIRE_PARTS) | set(_RING_PARTS) <= set(s)
            inside = sum(s[p] for p in _WIRE_PARTS)
            assert inside <= s["ring.wire"]
            assert inside == pytest.approx(s["ring.wire"], rel=0.02)
            opened = sum(s[p] for p in _RING_PARTS)
            assert opened <= s["ring"]
            assert opened >= 0.9 * s["ring"]
        _shutdown(pgs)

    def test_a_peer_that_stalls_between_buckets_is_wait_not_arrive(self, store):
        """(c) rank 1 reaches the ring on time and is then held 0.2 s
        before its second bucket (a leaf whose host copy takes that long,
        booked as its ``ring.d2h``): rank 0 waits as long in a later
        exchange of the op."""

        class Held(_SlowLeaf):
            def __array__(self, dtype=None, copy=None):
                time.sleep(0.2)
                return super().__array__(dtype, copy)

        pgs = make_group(store, 2, "wire-stall")
        n = ProcessGroupTCP.BUCKET_BYTES // 4  # a leaf that rings alone
        log = []

        def leaves_of(rank):
            second = np.full(n, 2.0, np.float32)
            return [
                np.full(n, 1.0, np.float32),
                Held("held", second, log) if rank else second,
            ]

        sinks, _ = _ring_phases(pgs, leaves_of)
        assert ("asked", "held") in log
        stall = sinks[1]["ring.d2h"]
        assert 0.19 < stall < 5.0
        assert sinks[0]["ring.wire.wait"] == pytest.approx(stall, abs=0.05)
        assert sinks[0]["ring.wire.arrive"] < 0.05
        assert sinks[1]["ring.wire.wait"] < 0.05
        _shutdown(pgs)

    def test_in_a_longer_ring_only_the_stragglers_successor_reads_arrive(self, store):
        """The first exchange depends on nothing the previous rank
        received, every later one does: with rank 2 of 3 late, rank 0
        (next after it) waits in ``arrive``, rank 1 gets rank 0's first
        chunk at once and waits for the late rank's share in ``wait``."""
        pgs = make_group(store, 3, "wire-long")
        tiny = lambda rank: [np.full(64, rank + 1.0, np.float32)]
        sinks, entered = _ring_phases(
            pgs, tiny, before=lambda rank, n: time.sleep(0.2 * (rank == 2))
        )
        skew = entered[2][0] - max(entered[0][0], entered[1][0])
        assert sinks[0]["ring.wire.arrive"] == pytest.approx(skew, abs=0.04)
        assert sinks[1]["ring.wire.arrive"] < 0.04
        assert sinks[1]["ring.wire.wait"] == pytest.approx(skew, abs=0.04)
        assert sinks[2]["ring.wire.arrive"] + sinks[2]["ring.wire.wait"] < 0.04
        _shutdown(pgs)

    @pytest.mark.parametrize("kind", ["tcp", "dummy"])
    def test_alone_there_is_no_wire(self, kind, ring_spans):
        """(d) world size 1 opens none of it: the keys of a lone group's
        ``phases`` are the parent's."""
        (pg,) = _world(None, 1, "wire-alone") if kind == "tcp" else [ProcessGroupDummy()]
        before = _peer_wait("rank0")
        _, spans = ring_spans(
            lambda: pg.allreduce([np.ones(100, np.float32)], REDUCE_AVG).wait(timeout=20)
        )
        names = {name for name, _ in spans}
        assert names and not {n for n in names if n.startswith("ring.wire")}
        assert _peer_wait("rank0") == before
        pg.shutdown()

    def test_the_counter_moves_by_the_two_waits_seconds(self, store):
        """(f) ``torchft_ring_peer_wait_seconds_total{kind}`` is incremented
        where the parts end, by what they booked."""
        pgs = make_group(store, 2, "wire-counter")
        before = [_peer_wait(f"rank{r}") for r in range(2)]
        tiny = lambda rank: [np.full(64, 1.0, np.float32), np.ones(3, np.float64)]
        sinks, _ = _ring_phases(
            pgs, tiny, before=lambda rank, n: time.sleep(0.05 * rank), rounds=2
        )
        for rank, s in enumerate(sinks):
            after = _peer_wait(f"rank{rank}")
            for kind in ("arrive", "wait"):
                assert after[kind] - before[rank][kind] == pytest.approx(
                    s["ring.wire." + kind], abs=1e-9
                )
        assert sinks[0]["ring.wire.arrive"] >= 0.08  # two ops, 0.05 s each
        _shutdown(pgs)

    def test_a_peer_that_never_comes_is_booked_when_the_ring_fails(self, store):
        """Rank 1 never calls: rank 0's ring fails at its deadline, and what
        it waited until then is its ``arrive``, in the sink and in the
        counter, like a wait that ended well."""
        from torchft_tpu.utils import tracing

        pgs = make_group(store, 2, "wire-never", timeout=1.0)
        before = _peer_wait("rank0")
        sink = {}
        ring = tracing.phase("ring", sink).begin()
        with tracing.under(ring):
            work = pgs[0].allreduce([np.ones(64, np.float32)], REDUCE_SUM)
        with pytest.raises(Exception):
            work.wait(timeout=20)
        ring.end(ok=False)
        assert 0.9 < sink["ring.wire.arrive"] < 10.0
        assert sink["ring.wire.arrive"] >= 0.9 * sink["ring.wire"]
        # the send was handed over; a part no stretch of which began books nothing
        assert "ring.wire.send" in sink
        assert "ring.wire.wait" not in sink and "ring.wire.recv" not in sink
        after = _peer_wait("rank0")
        assert after["arrive"] - before["arrive"] == pytest.approx(
            sink["ring.wire.arrive"], abs=1e-9
        )
        assert after["wait"] == before["wait"]
        _shutdown(pgs)

    def test_other_collectives_read_the_same_bytes_and_open_nothing(
        self, store, ring_spans
    ):
        """``allgather`` and ``send`` / ``recv`` go through the one
        ``_recv_msg``: same results, and no part of a wire they do not
        have."""
        pgs = make_group(store, 2, "wire-others")

        def both():
            def op(rank, pg):
                got = pg.allgather(np.array([rank, 7 * rank])).wait(timeout=20)
                if rank == 0:
                    pg.send(np.arange(5, dtype=np.float32), 1).wait(timeout=20)
                else:
                    got.append(pg.recv(0).wait(timeout=20))
                return got

            return run_parallel(2, op, pgs)

        results, spans = ring_spans(both)
        for got in results:
            np.testing.assert_array_equal(got[0], [0, 0])
            np.testing.assert_array_equal(got[1], [1, 7])
        np.testing.assert_array_equal(results[1][2], np.arange(5, dtype=np.float32))
        assert not [name for name, _ in spans if "wire" in name]
        _shutdown(pgs)
