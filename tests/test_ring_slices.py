"""ISSUE 46: a ring step moves its chunk in slices.  The previous rank's
stream is read a slice at a time, the reducer reduces (and, in the last
reduce-scatter step, divides) slice ``k`` while slice ``k + 1`` comes in,
and the sender pushes a slice of the next message on as soon as it is
final.  The messages on the wire are the whole-chunk ring's, which is the
case of one slice.

The slice floor is shrunk through the class attribute, the way
``BUCKET_BYTES`` is one; every wait has a time limit of its own."""

import sys
import threading
import time

import ml_dtypes
import numpy as np
import pytest

from torchft_tpu.coordination import StoreServer
from torchft_tpu.parallel import process_group
from torchft_tpu.parallel.process_group import (
    REDUCE_AVG,
    REDUCE_MAX,
    REDUCE_SUM,
    ProcessGroupTCP,
)
from torchft_tpu.utils import flightrecorder, metrics, tracing

from test_process_group import _shutdown, make_group, run_parallel

# the floor the tests shrink SLICE_BYTES to: a float32 chunk of 16,384
# elements then moves in SLICES = 8 slices of 2,048
FLOOR = 4096


@pytest.fixture(scope="module")
def store():
    server = StoreServer()
    yield server
    server.shutdown()


@pytest.fixture
def small_slices(monkeypatch):
    monkeypatch.setattr(ProcessGroupTCP, "SLICE_BYTES", FLOOR)


@pytest.fixture(scope="module")
def groups(store):
    """One configured group a world size, shared by the cases that leave
    it healthy."""
    made = {}

    def of(world):
        if world not in made:
            made[world] = make_group(store, world, f"slices-shared{world}")
        return made[world]

    yield of
    for pgs in made.values():
        _shutdown(pgs)


def _acc(dtype):
    """The accumulation dtype, written out: the reference imports nothing
    of the ring's."""
    dtype = np.dtype(dtype)
    if dtype == np.int32:
        return np.dtype(np.int64)
    return np.dtype(np.float32)


def _reference(per_rank, op, divisor):
    """``((a_c + a_c+1) + ...) / divisor`` chunk by chunk in the ring's
    operand order: chunk ``c`` starts as rank ``c``'s and meets rank
    ``c + 1``'s, ``c + 2``'s, ... as it goes round, each time as
    ``ufunc(own, received)``; then one division in the accumulation dtype,
    then the cast back."""
    w = len(per_rank)
    dtype, shape = per_rank[0].dtype, per_rank[0].shape
    acc = _acc(dtype)
    n = per_rank[0].size
    chunk = -(-n // w)
    padded = []
    for a in per_rank:
        p = np.zeros(chunk * w, acc)
        p[:n] = a.reshape(-1).astype(acc)
        padded.append(p)
    ufunc = np.maximum if op == REDUCE_MAX else np.add
    out = np.empty(chunk * w, acc)
    for c in range(w):
        at = slice(c * chunk, (c + 1) * chunk)
        total = padded[c][at]
        for j in range(1, w):
            total = ufunc(padded[(c + j) % w][at], total)
        out[at] = total
    by = w if op == REDUCE_AVG else divisor
    if by not in (None, 1):
        if acc.kind == "f":
            out = out / acc.type(by)
        else:
            out = (out / by).astype(acc)
    return out[:n].astype(dtype).reshape(shape)


def _leaves(world, dtype, n, seed=0):
    rng = np.random.default_rng(1000 * seed + n)
    if np.dtype(dtype) == np.int32:
        return [
            rng.integers(-(2**30), 2**30, size=n).astype(np.int32)
            for _ in range(world)
        ]
    return [(rng.standard_normal(n) * 3).astype(dtype) for _ in range(world)]


def _allreduce(pgs, per_rank, op=REDUCE_SUM, divisor=None, sinks=None):
    """Every rank's allreduce of its one leaf on a thread of its own, under
    an open ``ring`` phase where ``sinks`` are given; the results and the
    ``Work`` handles by rank."""
    works = [None] * len(pgs)

    def run(rank, pg):
        ring = None
        if sinks is not None:
            ring = tracing.phase("ring", sinks[rank]).begin()
        with tracing.under(ring):
            works[rank] = pg.allreduce([per_rank[rank]], op, divisor=divisor)
        got = works[rank].wait(timeout=30)
        if ring is not None:
            ring.end()
        return got[0]

    return run_parallel(len(pgs), run, pgs), works


def _same_bits(got, want):
    assert got.dtype == want.dtype and got.shape == want.shape
    assert got.tobytes() == want.tobytes()


def _slices(replica_id):
    return {
        h: metrics.RING_SLICES.labels(replica_id=replica_id, hidden=h).get()
        for h in ("1", "0")
    }


def _reducer_threads():
    return [t for t in threading.enumerate() if t.name.startswith("pg_tcp_reducer")]


# one slice | a whole number of slices (8 x 2,048 float32 a chunk) | neither a
# multiple of the world size nor of a slice: a padded tail, a short last slice
LENGTHS = {"one_slice": 1000, "whole_slices": None, "ragged": 40_009}
MODES = {
    "sum_by_divisor": (REDUCE_SUM, 3),
    "avg": (REDUCE_AVG, None),
    "max": (REDUCE_MAX, None),
}


@pytest.mark.usefixtures("small_slices")
class TestBitForBit:
    """(a) the sliced ring's result is the reference's, bit for bit, and
    every rank's is every other's."""

    @pytest.mark.parametrize("mode", list(MODES))
    @pytest.mark.parametrize("length", list(LENGTHS))
    @pytest.mark.parametrize(
        "dtype", [np.float32, ml_dtypes.bfloat16, np.int32], ids=lambda d: np.dtype(d).name
    )
    @pytest.mark.parametrize("world", [2, 3, 4])
    def test_the_ring_is_the_reference(self, groups, world, dtype, length, mode):
        op, divisor = MODES[mode]
        n = LENGTHS[length] or world * 8 * 2048
        per_rank = _leaves(world, dtype, n, seed=world)
        sinks = [{} for _ in range(world)]
        got, _ = _allreduce(groups(world), per_rank, op, divisor, sinks)
        want = _reference(per_rank, op, divisor)
        for g in got:
            _same_bits(g, want)
        sliced = length != "one_slice"
        assert all(("ring.reduce.hidden" in s) <= sliced for s in sinks)


class TestOneSlice:
    """(b) a chunk of one slice is the whole-chunk ring: reduced by the
    worker between two messages, nothing handed to a reducer."""

    @pytest.mark.parametrize(
        "case", ["scalar", "coalesced_bucket", "under_two_slices"]
    )
    @pytest.mark.parametrize("world", [2, 3])
    def test_books_one_slice_and_starts_no_reducer(
        self, store, world, case, tmp_path
    ):
        import json

        if case == "scalar":
            leaves = lambda r: [np.float32(r + 1.0).reshape(())]
        elif case == "coalesced_bucket":
            # what the coalesced bucket holds at the most, in small leaves
            n = ProcessGroupTCP.BUCKET_BYTES // 4 // 8
            leaves = lambda r: [np.full(n, r + 1.0, np.float32) for _ in range(8)]
        else:
            n = world * (2 * ProcessGroupTCP.SLICE_BYTES // 4 - 1)
            leaves = lambda r: [np.full(n, r + 1.0, np.float32)]
        pgs = make_group(store, world, f"one-{case}{world}")
        before = [_slices(f"rank{r}") for r in range(world)]
        reducers = _reducer_threads()  # of groups other cases share
        path = tmp_path / "spans.jsonl"
        tracing.install_tracer(tracing.Tracer(sink=tracing.FileSpanSink(str(path))))
        sinks = [{} for _ in range(world)]
        try:

            def run(rank, pg):
                tracing.set_current(tracing.TraceContext("a" * 32, "b" * 16))
                ring = tracing.phase("ring", sinks[rank]).begin()
                with tracing.under(ring):
                    work = pg.allreduce(leaves(rank), REDUCE_SUM, divisor=world)
                got = work.wait(timeout=30)
                ring.end()
                tracing.set_current(None)
                return got

            got = run_parallel(world, run, pgs)
        finally:
            tracing.uninstall_tracer()
        mean = np.float32(sum(range(1, world + 1))) / np.float32(world)
        for leaves_got in got:
            for leaf in leaves_got:
                assert (leaf == mean).all()
        spans = [json.loads(l) for l in path.read_text().splitlines() if l]
        reduces = [s["attributes"] for s in spans if s["name"] == "ring.reduce"]
        assert len(reduces) == world  # one bucket a rank
        assert all(a["slices"] == 1 and a["hidden"] == 0 for a in reduces)
        for rank, s in enumerate(sinks):
            assert "ring.reduce.hidden" not in s and s["ring.reduce"] > 0
            after = _slices(f"rank{rank}")
            assert after["1"] == before[rank]["1"]
            assert after["0"] - before[rank]["0"] == world - 1
        assert _reducer_threads() == reducers
        _shutdown(pgs)


@pytest.mark.usefixtures("small_slices")
class TestUnderTheWire:
    @pytest.mark.parametrize("world", [2, 3])
    def test_behind_a_throttled_peer_the_reduce_hides(self, store, world, monkeypatch):
        """(c) every rank's egress shaped to 0.1 GB/s: a slice takes
        milliseconds to come in and microseconds to reduce, so the reduce
        is hidden, the wire hardly stands still for it, and the receiving
        role's three parts still add up to the wire."""
        monkeypatch.setattr(ProcessGroupTCP, "SLICE_BYTES", 256 * 1024)
        pgs = make_group(store, world, f"throttled{world}")
        for pg in pgs:
            pg.set_bandwidth(0.1)
        n = world * (1 << 20)  # a chunk of 4 MiB: 8 slices of 512 KiB
        per_rank = _leaves(world, np.float32, n)
        # twice unmeasured: the buffers come from the pool, faulted, and the
        # shaper's burst is spent, so the first message is paced too
        for _ in range(2):
            _allreduce(pgs, per_rank, REDUCE_SUM, world)
        before = [_slices(f"rank{r}") for r in range(world)]
        sinks = [{} for _ in range(world)]
        got, _ = _allreduce(pgs, per_rank, REDUCE_SUM, world, sinks)
        want = _reference(per_rank, REDUCE_SUM, world)
        for rank, s in enumerate(sinks):
            _same_bits(got[rank], want)
            assert s["ring.wire"] > 0.03  # 4 MiB a rank beyond the burst
            assert s["ring.reduce.hidden"] > 0
            assert s["ring.reduce"] < 0.25 * s["ring.wire"]
            # the partition tests/test_tracing.py asserts of the wire's
            # parts keeps holding: the receiving role's three and the
            # send's tail (a loaded host may keep the sender thread off its
            # core for a tenth of this wire) leave only bookkeeping
            inside = sum(
                s["ring.wire." + p] for p in ("arrive", "wait", "recv", "send")
            )
            assert inside <= s["ring.wire"]
            assert inside == pytest.approx(s["ring.wire"], rel=0.1)
            assert s["ring.wire"] + s["ring.reduce"] <= s["ring"]
            after = _slices(f"rank{rank}")
            moved = {h: after[h] - before[rank][h] for h in after}
            assert moved["1"] + moved["0"] == 8 * (world - 1)
            # (most of them on a quiet host; one that is loaded may keep
            # the reducer off its core for longer than a slice takes)
            assert moved["1"] >= 1
        _shutdown(pgs)

    def test_a_shaped_slice_costs_the_bucket_what_the_whole_chunk_did(
        self, store, monkeypatch
    ):
        """The shaper stays where it is: the same token bucket is debited
        the same bytes whether a message goes whole or in slices."""
        consumed = {}
        for slices in (1, 8):
            monkeypatch.setattr(ProcessGroupTCP, "SLICES", slices)
            pgs = make_group(store, 2, f"bucket{slices}")
            for pg in pgs:
                pg.set_bandwidth(5.0)
            per_rank = _leaves(2, np.float32, 2 * 16_384 + 5)
            _allreduce(pgs, per_rank, REDUCE_AVG)
            consumed[slices] = [pg._bucket.consumed_bytes for pg in pgs]
            _shutdown(pgs)
        assert consumed[1] == consumed[8] and consumed[1][0] > 2 * 65_536


@pytest.mark.usefixtures("small_slices")
class TestScratchIsNotOverwritten:
    @pytest.mark.parametrize("world", [3, 4])
    def test_a_slow_reduce_holds_the_receiver_back(self, store, world, monkeypatch):
        """(d) rank 0's reduce slowed to two milliseconds a slice, the
        others' at full speed: its second reduce-scatter message is on the
        socket long before ``scratch`` is free.  The receiver holds back
        (the result stays exact) and the seconds are ``ring.reduce``'s: the
        wire stood still for them."""
        n = world * 8 * 2048
        per_rank = _leaves(world, np.float32, n, seed=7)
        calls = []

        def slow_add(a, b, out):
            calls.append(a.size)
            if np.may_share_memory(a, per_rank[0]):  # rank 0's own values
                time.sleep(0.002)
            return np.add(a, b, out=out)

        monkeypatch.setitem(process_group._REDUCE_UFUNCS, REDUCE_SUM, slow_add)
        pgs = make_group(store, world, f"slow{world}")
        sinks = [{} for _ in range(world)]
        got, _ = _allreduce(pgs, per_rank, REDUCE_SUM, world, sinks)
        want = _reference(per_rank, REDUCE_SUM, world)
        for g in got:
            _same_bits(g, want)
        assert calls == [2048] * (world * (world - 1) * 8)
        # its first message's 8 slices take 16 ms to reduce; the second's
        # were there after one or two
        assert sinks[0]["ring.reduce"] >= 0.008
        for s in sinks:
            assert s["ring.wire"] + s["ring.reduce"] <= s["ring"]
        _shutdown(pgs)


def _close_after(pg, k):
    """``pg``'s peers are gone once it has read ``k`` payload slices."""
    read = pg._read_into_sock
    seen = []

    def reading(sock, view, deadline):
        if len(seen) == k:
            for peer in list(pg._peers.values()):
                peer.close()
        seen.append(len(view))
        return read(sock, view, deadline)

    pg._read_into_sock = reading


@pytest.mark.usefixtures("small_slices")
class TestFailure:
    @pytest.mark.parametrize("after", [1, 5, 11])
    def test_a_peer_gone_mid_slice_fails_every_rank_and_the_next_ring_is_clean(
        self, store, after
    ):
        """(e) rank 2 of 3 closes its sockets after ``after`` slices (in
        its first message, inside it, in its second): every rank's ``Work``
        raises inside the deadline, no thread outlives ``shutdown()``, and
        the survivors' first ring in the smaller world is exact."""
        threads = threading.active_count()
        world = 3
        pgs = make_group(store, world, f"gone{after}", timeout=3.0)
        _close_after(pgs[2], after)
        per_rank = _leaves(world, np.float32, world * 8 * 2048)
        works = [pg.allreduce([a], REDUCE_SUM) for pg, a in zip(pgs, per_rank)]
        t0 = time.monotonic()
        for work in works:
            with pytest.raises(Exception):
                work.wait(timeout=10)
        assert time.monotonic() - t0 < 6.0
        assert all(pg.errored() is not None for pg in pgs)
        pgs[2].shutdown()
        survivors = pgs[:2]

        def configure(rank, pg):
            pg.configure(f"{store.address()}/gone{after}-next", f"rank{rank}", rank, 2)

        run_parallel(2, configure, survivors)
        got, _ = _allreduce(survivors, per_rank[:2], REDUCE_AVG)
        want = _reference(per_rank[:2], REDUCE_AVG, None)
        for g in got:
            _same_bits(g, want)
        _shutdown(survivors)
        deadline = time.monotonic() + 10
        while threading.active_count() > threads and time.monotonic() < deadline:
            time.sleep(0.01)
        assert threading.active_count() <= threads

    def test_abort_unwinds_a_ring_that_is_mid_slice(self, store, monkeypatch):
        """What a kill does to the survivors' PG: ``abort()`` while the
        reducer is at work and the receiver is held back; the ``Work``
        fails at once, not at the deadline, and ``configure()`` of the next
        quorum starts a clean ring."""
        entered = threading.Event()

        def stuck_add(a, b, out):
            entered.set()
            time.sleep(0.02)
            return np.add(a, b, out=out)

        monkeypatch.setitem(process_group._REDUCE_UFUNCS, REDUCE_SUM, stuck_add)
        threads = threading.active_count()
        pgs = make_group(store, 3, "abort-mid", timeout=30.0)
        per_rank = _leaves(3, np.float32, 3 * 8 * 2048)
        works = [pg.allreduce([a], REDUCE_SUM) for pg, a in zip(pgs, per_rank)]
        assert entered.wait(timeout=10)
        t0 = time.monotonic()
        for pg in pgs:
            pg.abort()
        for work in works:
            with pytest.raises(Exception):
                work.wait(timeout=10)
        assert time.monotonic() - t0 < 5.0
        monkeypatch.setitem(process_group._REDUCE_UFUNCS, REDUCE_SUM, np.add)

        def configure(rank, pg):
            pg.configure(f"{store.address()}/abort-mid-next", f"rank{rank}", rank, 3)

        run_parallel(3, configure, pgs)
        got, _ = _allreduce(pgs, per_rank, REDUCE_SUM, 3)
        for g in got:
            _same_bits(g, _reference(per_rank, REDUCE_SUM, 3))
        _shutdown(pgs)
        deadline = time.monotonic() + 10
        while threading.active_count() > threads and time.monotonic() < deadline:
            time.sleep(0.01)
        assert threading.active_count() <= threads

    def test_a_reduce_that_raises_is_the_works_error(self, store, monkeypatch):
        """The first error of any role is the op's: here the reducer's."""

        def broken(a, b, out):
            raise FloatingPointError("reduce failed")

        monkeypatch.setitem(process_group._REDUCE_UFUNCS, REDUCE_SUM, broken)
        pgs = make_group(store, 2, "reduce-raises", timeout=3.0)
        per_rank = _leaves(2, np.float32, 2 * 8 * 2048)
        works = [pg.allreduce([a], REDUCE_SUM) for pg, a in zip(pgs, per_rank)]
        for work in works:
            with pytest.raises(FloatingPointError, match="reduce failed"):
                work.wait(timeout=10)
        _shutdown(pgs)

    def test_a_wedged_slice_says_whom_it_waits_for_and_how_far_it_came(
        self, store, tmp_path, monkeypatch
    ):
        """The flight recorder of a ring that stops mid-message: the peer
        and tag of the blocked receive, and the bytes that had come."""
        import json

        events_file = tmp_path / "events.jsonl"
        monkeypatch.setenv("TORCHFT_EVENTS_FILE", str(events_file))
        pgs = make_group(store, 2, "wedged", timeout=1.5)
        # rank 1 sends three slices of its first message and then stalls
        conn, sent = pgs[1]._peers[0], []
        real = conn.sock

        class Stalling:
            def __getattr__(self, name):
                return getattr(real, name)

            def sendall(self, data):
                if len(data) == 2048 * 4:
                    sent.append(len(data))
                    if len(sent) > 3:
                        time.sleep(3.0)
                return real.sendall(data)

        conn.sock = Stalling()
        per_rank = _leaves(2, np.float32, 2 * 8 * 2048)
        works = [pg.allreduce([a], REDUCE_SUM) for pg, a in zip(pgs, per_rank)]
        with pytest.raises(Exception):
            works[0].wait(timeout=10)
        aborts = [
            json.loads(line)
            for line in events_file.read_text().strip().splitlines()
        ]
        rec = [e for e in aborts if e["kind"] == "abort" and e["rank"] == 0][-1]
        assert rec["op"] == "allreduce" and rec["recv_peer"] == 1
        assert rec["recv_tag"] == 100 and rec["recv_bytes"] == 8 * 2048 * 4
        assert rec["bytes_done"] == 3 * 2048 * 4
        with pytest.raises(Exception):
            works[1].wait(timeout=10)
        _shutdown(pgs)


@pytest.mark.usefixtures("small_slices")
class TestTheWireIsTheSame:
    @pytest.mark.parametrize("slices", [1, 8])
    @pytest.mark.parametrize("world", [2, 3])
    def test_wire_bytes_and_the_flight_recorders_count(
        self, store, world, slices, monkeypatch
    ):
        """(f) ``Work.wire_bytes`` and the bytes the flight recorder saw
        come in are 2 (w-1) chunks, sliced or whole."""
        monkeypatch.setattr(ProcessGroupTCP, "SLICES", slices)
        pgs = make_group(store, world, f"bytes{world}x{slices}")
        n = world * 8 * 2048 - 3
        per_rank = _leaves(world, np.float32, n)
        flightrecorder.RECORDER.clear()
        got, works = _allreduce(pgs, per_rank, REDUCE_AVG)
        for g in got:
            _same_bits(g, _reference(per_rank, REDUCE_AVG, None))
        expect = 2 * (world - 1) * -(-n // world) * 4
        assert [w.wire_bytes for w in works] == [expect] * world
        ops = [
            r for r in flightrecorder.snapshot()
            if r.get("kind") == "collective" and r.get("op") == "allreduce"
        ]
        assert sorted(r["rank"] for r in ops) == list(range(world))
        assert [r["bytes_done"] for r in ops] == [expect] * world
        assert all(r["status"] == "ok" for r in ops)
        _shutdown(pgs)

    @pytest.mark.parametrize("world", [2, 3])
    def test_a_rank_that_sends_whole_chunks_sits_in_the_same_ring(self, store, world):
        """The messages are the parent's: a rank that moves its chunks
        whole (as one built from the parent does) and ranks that slice
        them agree, bit for bit."""
        pgs = make_group(store, world, f"mixed{world}")
        pgs[0].SLICES = 1
        per_rank = _leaves(world, ml_dtypes.bfloat16, 40_009, seed=3)
        got, _ = _allreduce(pgs, per_rank, REDUCE_SUM, world)
        for g in got:
            _same_bits(g, _reference(per_rank, REDUCE_SUM, world))
        _shutdown(pgs)

    def test_an_empty_leaf_still_rings(self, groups):
        per_rank = [np.zeros((0, 3), np.float32) for _ in range(2)]
        got, works = _allreduce(groups(2), per_rank, REDUCE_AVG)
        assert all(g.shape == (0, 3) for g in got)
        assert [w.wire_bytes for w in works] == [0, 0]


@pytest.mark.usefixtures("small_slices")
class TestManyRingsOneInterpreter:
    def test_more_threads_than_cores_and_a_short_switch_interval(self, store):
        """Four rings of two ranks in one process, as the four-chip cell
        holds four of them: twenty-four role threads, the interpreter made
        to switch every microsecond; every slice still goes where it
        belongs, three ops running."""
        rings = [make_group(store, 2, f"stress{i}") for i in range(4)]
        per_ring = [_leaves(2, np.float32, 2 * 8 * 2048 + i, seed=i) for i in range(4)]
        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)
        try:

            def run(i, _):
                return [_allreduce(rings[i], per_ring[i], REDUCE_AVG)[0] for _ in range(3)]

            results = run_parallel(4, run)
        finally:
            sys.setswitchinterval(interval)
        for i, rounds in enumerate(results):
            want = _reference(per_ring[i], REDUCE_AVG, None)
            for got in rounds:
                for g in got:
                    _same_bits(g, want)
        for pgs in rings:
            _shutdown(pgs)


class TestVocabulary:
    def test_hidden_is_a_part_and_is_counted_in_no_category_twice(self):
        from torchft_tpu.diagnose import ledger_categories
        from torchft_tpu.manager import PHASE_PARTS

        assert "ring.reduce.hidden" in PHASE_PARTS
        assert tracing.is_part("ring.reduce.hidden")
        base = {"ring": 0.5, "ring.wire": 0.4, "ring.reduce": 0.01}
        assert ledger_categories({**base, "ring.reduce.hidden": 0.08}) == (
            ledger_categories(base)
        )

    def test_the_counter_is_registered_with_its_two_labels(self):
        assert metrics.RING_SLICES.name == "torchft_ring_slices_total"
        child = metrics.RING_SLICES.labels(replica_id="vocab", hidden="1")
        before = child.get()
        child.inc(2)
        assert child.get() == before + 2
