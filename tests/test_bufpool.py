"""BufferPool: the host-collective staging allocator (utils/bufpool.py).

The pool's contract is safety-critical for the quantized collectives:
give() must only ever accept memory the caller exclusively owns, because
a pooled buffer is handed out again to arbitrary concurrent takers."""

import threading

import pytest

import numpy as np

from torchft_tpu.utils.bufpool import BufferPool


class TestBufferPool:
    def test_take_give_reuse(self):
        pool = BufferPool(max_bytes=1 << 20)
        a = pool.take((16, 32), np.float32)
        assert a.shape == (16, 32) and a.dtype == np.float32
        addr = a.ctypes.data
        pool.give(a)
        b = pool.take((16, 32), np.float32)
        assert b.ctypes.data == addr  # same allocation came back
        c = pool.take((16, 32), np.float32)
        assert c.ctypes.data != addr  # pool was empty again -> fresh

    def test_reshape_views_normalize_to_base(self):
        pool = BufferPool(max_bytes=1 << 20)
        a = pool.take(512, np.uint8)
        pool.give(a)
        # take() reshapes the pooled base; giving the view back must
        # re-pool the WHOLE allocation
        v = pool.take((2, 256), np.uint8)
        assert v.base is not None
        pool.give(v)
        w = pool.take(512, np.uint8)
        assert w.ctypes.data == a.ctypes.data

    def test_rejects_foreign_memory_views(self):
        # arrays over memory numpy does not own (frombuffer, shm-style)
        # must never enter the pool: pooling them would pin their owner's
        # finalizer and alias foreign memory to future takers
        pool = BufferPool(max_bytes=1 << 20)
        raw = bytearray(1024)
        foreign = np.frombuffer(raw, dtype=np.uint8)
        pool.give(foreign)
        assert pool.take(1024, np.uint8).ctypes.data != foreign.ctypes.data

    def test_rejects_slices_and_noncontiguous(self):
        pool = BufferPool(max_bytes=1 << 20)
        owner = np.empty(1024, np.uint8)
        pool.give(owner[100:200])  # partial view: base nbytes differ
        assert pool._held == 0
        mat = np.empty((8, 8), np.float32)
        pool.give(mat[:, ::2])  # non-contiguous
        assert pool._held == 0

    def test_cap_drops_excess(self):
        pool = BufferPool(max_bytes=1000)
        a = np.empty(600, np.uint8)
        b = np.empty(600, np.uint8)
        pool.give(a)
        pool.give(b)  # would exceed the cap -> dropped
        assert pool._held == 600

    def test_zero_byte_noop(self):
        pool = BufferPool(max_bytes=1 << 20)
        pool.give(np.empty(0, np.uint8))
        assert pool._held == 0

    def test_concurrent_take_give(self):
        pool = BufferPool(max_bytes=8 << 20)
        errs = []

        def worker(seed):
            rng = np.random.default_rng(seed)
            for _ in range(200):
                a = pool.take(int(rng.integers(1, 4)) * 1024, np.uint8)
                a[:] = seed  # exclusive ownership: nobody else writes it
                if not np.all(a == seed):
                    errs.append("shared buffer observed")
                    return
                pool.give(a)

        threads = [
            threading.Thread(target=worker, args=(s,)) for s in range(4)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert not errs, errs


class TestLease:
    """``lease``: memory for a result that escapes.  It returns to the
    pool when the last view of it dies, never before and never by a call
    the holder of a view could outlive."""

    def test_not_handed_out_while_a_view_lives(self):
        pool = BufferPool(max_bytes=1 << 20)
        buf, hit = pool.lease(256, np.float32)
        assert not hit and buf.shape == (256,) and buf.dtype == np.float32
        addr = buf.ctypes.data
        kept = buf[16:32].reshape(4, 4)  # what user code might keep
        kept[...] = 7.0
        del buf
        assert pool.leased_bytes == 1024
        other, hit = pool.lease(256, np.float32)
        assert not hit and other.ctypes.data != addr
        other[...] = -1.0
        assert (kept == 7.0).all()
        del kept
        assert pool.leased_bytes == 1024  # only ``other`` is out
        again, hit = pool.lease(256, np.float32)
        assert hit and again.ctypes.data == addr

    def test_views_of_any_kind_hold_the_lease(self):
        pool = BufferPool(max_bytes=1 << 20)
        buf, _ = pool.lease(64, np.float32)
        views = [
            buf[:10],
            buf.reshape(8, 8)[2],
            np.asarray(buf[5:], dtype=np.float32),
            buf.view(np.uint8)[8:16],
            buf[:32].astype(np.float32, copy=False),
        ]
        del buf
        while views:
            assert pool.leased_bytes == 256
            views.pop()
        assert pool.leased_bytes == 0

    def test_recycled_by_bytes_across_dtypes(self):
        pool = BufferPool(max_bytes=1 << 20)
        a, _ = pool.lease(100, np.float32)
        addr = a.ctypes.data
        del a
        b, hit = pool.lease(50, np.int64)
        assert hit and b.ctypes.data == addr and b.shape == (50,)

    def test_give_refuses_leased_memory(self):
        # a view of a lease does not own its memory: give() drops it, so a
        # caller that gives a result back by mistake cannot hand the same
        # memory to two takers
        pool = BufferPool(max_bytes=1 << 20)
        buf, _ = pool.lease(128, np.uint8)
        pool.give(buf)
        pool.give(buf[:])
        assert pool._held == 0

    def test_kept_outside_the_cap_up_to_what_was_out_at_once(self):
        # four replica groups in one process lease four gradients against
        # one process-wide cap: every one hits from the second step on
        pool = BufferPool(max_bytes=1000)
        for step in range(3):
            out = [pool.lease(600, np.uint8) for _ in range(4)]
            assert [hit for _, hit in out] == [step > 0] * 4
            del out
        assert pool.leased_bytes == 0
        assert (pool.hits, pool.misses) == (8, 4)
        # and no more than that: what is kept never exceeds what the
        # program itself had out at once
        assert pool._lease_held == pool._lease_peak == 2400
        odd = pool.lease(700, np.uint8)[0]
        del odd
        assert pool.leased_bytes == 0 and pool._lease_held == 1900

    def test_sizes_nobody_asks_for_go_first(self):
        pool = BufferPool(max_bytes=1000)
        old = [pool.lease(200, np.uint8)[0] for _ in range(3)]
        del old  # 600 bytes were out at once: that much is kept
        new = pool.lease(350, np.uint8)[0]
        del new  # does not fit beside the three old buffers: two go
        assert pool.leased_bytes == 0
        assert {k: len(v) for k, v in pool._lease_free.items()} == {200: 1, 350: 1}
        assert pool.lease(350, np.uint8)[1]

    def test_pool_turned_off(self):
        pool = BufferPool(max_bytes=0)
        a, hit = pool.lease(64, np.uint8)
        del a
        assert not hit and not pool.lease(64, np.uint8)[1]
        assert pool.leased_bytes == 0

    def test_zero_size_lease(self):
        pool = BufferPool(max_bytes=1 << 20)
        buf, _ = pool.lease(0, np.float32)
        assert buf.shape == (0,) and pool.misses == 0

    def test_a_lease_may_end_on_any_thread(self):
        # more workers than cores and a short switch interval: a lease
        # that two holders shared, or one lost in the books, would show
        import os
        import sys

        pool = BufferPool(max_bytes=1 << 20)
        workers, rounds = (os.cpu_count() or 4) + 4, 100
        errs = []

        def worker(seed):
            for _ in range(rounds):
                buf, _ = pool.lease(1024, np.uint8)
                buf[:] = seed
                view = buf[100:200]
                del buf
                if not np.all(view == seed):
                    errs.append("shared buffer observed")
                    return
                del view

        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            threads = [
                threading.Thread(target=worker, args=(s,)) for s in range(workers)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
        finally:
            sys.setswitchinterval(interval)
        assert not any(t.is_alive() for t in threads)
        assert not errs, errs
        assert pool.leased_bytes == 0
        assert pool.hits + pool.misses == workers * rounds
        # about one buffer a worker is ever made (a lease that ends between
        # the books and the free list may cost one more)
        assert pool.misses <= 4 * workers


class TestRingLease:
    """The TCP ring's buffer is a lease from the process-wide pool."""

    @staticmethod
    def _group(world=2, timeout=20.0):
        from torchft_tpu.coordination import StoreServer
        from torchft_tpu.parallel.process_group import ProcessGroupTCP

        store = StoreServer()
        pgs = [ProcessGroupTCP(timeout=timeout) for _ in range(world)]
        threads = [
            threading.Thread(
                target=pgs[r].configure,
                args=(f"{store.address()}/lease", f"lease{r}", r, world),
            )
            for r in range(world)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        return store, pgs

    @staticmethod
    def _ring(pgs, leaves_by_rank):
        """One allreduce on every rank; returns rank 0's result."""
        works = [
            pg.allreduce(list(leaves))
            for pg, leaves in zip(pgs, leaves_by_rank)
        ]
        results = [w.wait(timeout=30) for w in works]
        return results[0]

    def test_a_kept_slice_survives_later_rings(self):
        store, pgs = self._group()
        n = 50_001  # a padded tail at world size 2
        try:
            first = self._ring(pgs, [[np.full(n, 1.0, np.float32)]] * 2)
            kept = first[0][1000:2000]  # user code keeps a slice ...
            del first  # ... and drops the result
            for value in (5.0, 9.0):
                again = self._ring(pgs, [[np.full(n, value, np.float32)]] * 2)
                np.testing.assert_array_equal(again[0], np.full(n, 2 * value))
                assert not np.shares_memory(again[0], kept)
                del again
            np.testing.assert_array_equal(kept, np.full(1000, 2.0))
        finally:
            for pg in pgs:
                pg.shutdown()
            store.shutdown()

    def test_steady_state_allocates_nothing(self):
        from torchft_tpu.utils.bufpool import POOL

        store, pgs = self._group()
        rng = np.random.default_rng(0)
        leaves = [
            [rng.standard_normal(70_001).astype(np.float32),
             rng.standard_normal((1 << 20) + 1).astype(np.float32),
             rng.standard_normal(33).astype(np.float32)]
            for _ in range(2)
        ]
        try:
            POOL.clear()
            del self._ring(pgs, leaves)[:]
            hits, misses = POOL.hits, POOL.misses
            for _ in range(3):
                result = self._ring(pgs, leaves)
                np.testing.assert_allclose(
                    result[0], leaves[0][0] + leaves[1][0], rtol=1e-6
                )
                del result
            assert POOL.misses == misses  # every buffer came back warm
            assert POOL.hits > hits
            assert POOL.leased_bytes == 0
        finally:
            for pg in pgs:
                pg.shutdown()
            store.shutdown()

    def test_allreduces_in_flight_get_distinct_buffers(self):
        store, pgs = self._group()
        n = 40_000
        try:
            for _ in range(2):  # the second round runs on recycled memory
                works = [
                    [pg.allreduce([np.full(n, k + 1.0, np.float32)]) for k in range(3)]
                    for pg in pgs
                ]
                held = [[w.wait(timeout=30)[0] for w in ws] for ws in works]
                flat = [a for per_rank in held for a in per_rank]
                for i, a in enumerate(flat):
                    for b in flat[i + 1:]:
                        assert not np.shares_memory(a, b)
                for per_rank in held:
                    for k, a in enumerate(per_rank):
                        np.testing.assert_array_equal(a, np.full(n, 2.0 * (k + 1)))
                del works, held, flat, a, b
        finally:
            for pg in pgs:
                pg.shutdown()
            store.shutdown()

    def test_an_aborted_ring_leaks_no_lease(self):
        import gc

        from torchft_tpu.utils.bufpool import POOL

        store, pgs = self._group(timeout=1.0)
        try:
            out = POOL.leased_bytes
            # rank 1 never joins: rank 0's ring dies at its deadline
            work = pgs[0].allreduce([np.ones(30_000, np.float32)])
            with pytest.raises(Exception):
                work.wait(timeout=10)
            assert pgs[0].errored() is not None
            del work
            # the latched error's traceback holds the ring's frame, and with
            # it the buffer, until the group is formed anew
            pgs[0].configure("", "lease0", 0, 1)
            gc.collect()
            assert POOL.leased_bytes == out
        finally:
            for pg in pgs:
                pg.shutdown()
            store.shutdown()
