"""Size-keyed scratch-buffer pool for host collective staging.

Large numpy allocations are mmap-backed: every fresh buffer pays a
page-fault per 4 KiB on first touch, which on the DCN host path costs
~5x the actual write (measured 144 ms vs 28 ms to fill 232 MB on the
bench host).  The quantized-collective codec stages (accumulators, packed
wire buffers, padded row-blocks) and the TCP ring's scratch chunks have
exact, repeating sizes and clear ownership windows — a pool turns their
per-fragment page-fault bill into a one-time warmup.

The reference has the same concept on device (its CUDA caching allocator
does this transparently for torch tensors); on the host side numpy has no
caching allocator, so the framework carries a small explicit one.

Contract: ``take`` returns an UNINITIALIZED array (np.empty semantics);
``give`` hands memory back — the caller must guarantee no other live
reference (views included) escapes.  A buffer that is returned to user
code is never ``give``n: it is ``lease``d, and the lease ends with the
life of the last array that views it, not at a call the user could
outlive.

``lease`` is for a result that escapes (the TCP ring's buffer, which the
caller gets back as the reduced gradients): an UNINITIALIZED 1-d array
whose memory returns to the pool by itself when it and every view of it
have been dropped — a slice kept by user code, a ``jax.device_put`` still
reading it, a sender thread still writing it to a socket all hold it.
Leased memory is kept outside ``max_bytes``: the pool keeps at
most as many bytes of it as the program itself had on lease at once,
which is what a steady state asks for again (four replica groups in one
process lease four gradients), and sizes nobody asked for lately go
first.  ``max_bytes=0`` turns both off.
"""

from __future__ import annotations

import threading
import weakref
from collections import OrderedDict, deque
from typing import Deque, Dict, List, Tuple

import numpy as np


class BufferPool:
    def __init__(self, max_bytes: int = 2048 << 20) -> None:
        self.max_bytes = max_bytes
        self._free: "Dict[Tuple[int, str], List[np.ndarray]]" = {}
        self._held = 0
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        # leases: free memory by byte size, least recently returned first
        self._lease_free: "OrderedDict[int, List[np.ndarray]]" = OrderedDict()
        self._lease_held = 0  # bytes in _lease_free
        self._lease_out = 0  # bytes on lease now
        self._lease_peak = 0  # most bytes ever on lease at once
        # A lease ends in a finalizer, on whichever thread drops the last
        # view and possibly inside a garbage collection that interrupted
        # take()/lease() under the lock: it only appends here (atomic), and
        # the next call that holds the lock does the bookkeeping.
        self._lease_ended: "Deque[np.ndarray]" = deque()

    def take(self, shape, dtype=np.float32) -> np.ndarray:
        dt = np.dtype(dtype)
        size = int(np.prod(shape, dtype=np.int64)) if not np.isscalar(shape) else int(shape)
        key = (size, dt.str)
        with self._lock:
            lst = self._free.get(key)
            if lst:
                arr = lst.pop()
                self._held -= arr.nbytes
                self.hits += 1
                return arr.reshape(shape)
            self.misses += 1
        return np.empty(shape, dtype=dt)

    def give(self, arr: "np.ndarray | None") -> None:
        if arr is None or arr.nbytes == 0 or not arr.flags.c_contiguous:
            return
        # normalize views produced by take()'s reshape back to their base
        # allocation so the whole buffer is reusable
        base = arr
        while isinstance(base.base, np.ndarray) and base.base.nbytes == arr.nbytes:
            base = base.base
        # Only pool arrays that OWN their memory (malloc'd by numpy).  A
        # view over foreign memory (an mmap, a device's host copy), whose
        # owner would be pinned for as long as the pool holds the view,
        # must fall to the GC instead.  This is enforced here, at the
        # seam, so no recycle call site has to know where a buffer came
        # from.
        if base.base is not None:
            return
        key = (base.size, base.dtype.str)
        with self._lock:
            if self._held + base.nbytes > self.max_bytes:
                return  # over cap: drop on the floor, OS reclaims
            self._free.setdefault(key, []).append(base)
            self._held += base.nbytes

    def lease(self, size: int, dtype=np.float32) -> "Tuple[np.ndarray, bool]":
        """``(buffer, hit)``: an uninitialized 1-d array of ``size``
        elements for a result that escapes to the caller, and whether its
        memory was recycled.  There is nothing to give back: the memory
        returns to the pool when the array and all its views are gone."""
        dt = np.dtype(dtype)
        nbytes = int(size) * dt.itemsize
        if nbytes == 0:
            return np.empty(0, dt), True  # nothing to recycle or to fault
        mem = None
        with self._lock:
            self._settle_leases()
            lst = self._lease_free.get(nbytes)
            if lst:
                mem = lst.pop()
                if not lst:
                    del self._lease_free[nbytes]
                self._lease_held -= nbytes
                self.hits += 1
            else:
                self.misses += 1
            self._lease_out += nbytes
            self._lease_peak = max(self._lease_peak, self._lease_out)
        hit = mem is not None
        if mem is None:
            mem = np.empty(nbytes, np.uint8)
        # numpy collapses a view's ``base`` to the first array that owns
        # its memory or whose own base is no array: over a memoryview that
        # is ``buf`` itself, so every view of the result keeps ``buf``
        # alive, and ``mem`` stays the pool's
        buf = np.frombuffer(memoryview(mem), dtype=dt)
        weakref.finalize(buf, self._lease_ended.append, mem).atexit = False
        return buf, hit

    def _settle_leases(self) -> None:
        """Book the leases that ended since the last call (lock held)."""
        while self._lease_ended:
            mem = self._lease_ended.popleft()
            n = mem.nbytes
            self._lease_out -= n
            if self.max_bytes == 0:
                continue
            while self._lease_held + n > self._lease_peak:
                stale = next((k for k in self._lease_free if k != n), None)
                if stale is None:
                    break
                lst = self._lease_free[stale]
                lst.pop()
                if not lst:
                    del self._lease_free[stale]
                self._lease_held -= stale
            if self._lease_held + n > self._lease_peak:
                continue  # more than was ever out at once: the OS reclaims
            self._lease_free.setdefault(n, []).append(mem)
            self._lease_free.move_to_end(n)
            self._lease_held += n

    @property
    def leased_bytes(self) -> int:
        """Bytes on lease now: handed out and not yet dropped."""
        with self._lock:
            self._settle_leases()
            return self._lease_out

    def clear(self) -> None:
        with self._lock:
            self._free.clear()
            self._held = 0
            self._settle_leases()
            self._lease_free.clear()
            self._lease_held = 0


# Process-wide default pool: collective staging buffers repeat sizes
# across fragments AND across replica ranks hosted in one process.
POOL = BufferPool()
