"""Reconfigurable process groups: the fault-tolerant collective layer.

TPU-native rebuild of the reference's reconfigurable ProcessGroup hierarchy
(reference: torchft/process_group.py:133-2023).  The key fault-tolerance
properties reproduced here (reference §5 semantics):

- **reconfigure**: ``configure(store_addr, replica_id, rank, world_size)``
  tears down and re-forms the group with new membership (keyed by the
  per-quorum store prefix) without restarting the process.
- **abortable with deadline**: every op takes the group timeout; ``abort()``
  cancels in-flight ops by closing sockets, never killing the process.
- **error latching**: after a failure every op fails fast (or is swallowed by
  ``ErrorSwallowingProcessGroupWrapper``) until the next configure.
- **host-mediated DCN path**: collectives run over TCP on host buffers
  (numpy), the Gloo analog.  On TPU the *inner* dimensions (FSDP/TP over ICI)
  are XLA collectives inside jit and are fault-free by assumption; this layer
  owns only the elastic replica dimension, so membership changes never
  trigger re-jit (zero-fill participation keeps compiled shapes static).

Design divergences from the reference, by intent: no subprocess-isolated
groups (reference torchft/process_group.py:1358-2023: a socket ring aborts
in-process by closing its sockets, and a chip belongs to one process), and
no fake world-size-1 backend registration (a torch-DeviceMesh-specific
trick; the JAX mesh composition lives in torchft_tpu/parallel/device_mesh.py).
"""

from __future__ import annotations

import contextlib
import functools
import itertools
import logging
import pickle
import queue
import socket
import struct
import sys
import threading
import time
import concurrent.futures as concurrent_futures
from abc import ABC, abstractmethod
from concurrent.futures import Future
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from torchft_tpu.coordination import StoreClient
from torchft_tpu.parallel.work import Work, completed_work, failed_work
from torchft_tpu.utils import faults as _faults
from torchft_tpu.utils import flightrecorder as _flightrec
from torchft_tpu.utils import linkstats as _linkstats
from torchft_tpu.utils import lockcheck as _lockcheck
from torchft_tpu.utils import metrics as _metrics
from torchft_tpu.utils import tracing as _tracing
from torchft_tpu.utils.bufpool import POOL as _pool
from torchft_tpu.utils.env import env_float

logger = logging.getLogger(__name__)

REDUCE_SUM = "sum"
REDUCE_AVG = "avg"
REDUCE_MAX = "max"
REDUCE_MIN = "min"

# What times the stretches of an exchange where no ring is open on it
# (allgather, send / recv, the PG heal transport): nothing.
_UNTIMED = contextlib.nullcontext()

# in-place reduction ufuncs for ring steps (AVG divides at the end)
_REDUCE_UFUNCS: Dict[str, Any] = {
    REDUCE_SUM: np.add,
    REDUCE_AVG: np.add,
    REDUCE_MAX: np.maximum,
    REDUCE_MIN: np.minimum,
}


def _is_float_dtype(dtype: np.dtype) -> bool:
    """True for numpy floats AND ml_dtypes extension floats (bfloat16,
    float8_*) — np.issubdtype misses the latter (they register as kind 'V';
    same pitfall as manager._is_floating, manager.py:67)."""
    return np.issubdtype(dtype, np.floating) or dtype.name.startswith(
        ("bfloat", "float8")
    )


def _accumulation_dtype(dtype: np.dtype) -> np.dtype:
    """Accumulation dtype for ring partial sums.

    Floats accumulate in f32 (f64 stays f64): the replica dimension is
    small, the ring reduces each chunk in a fixed order on exactly one rank
    before allgather, so results are bitwise identical across ranks at any
    precision — and f32 halves the wire bytes vs f64 promotion. Half-width
    floats (f16 and the ml_dtypes TPU types bf16/fp8) widen to f32 for
    precision; integers widen to 64-bit to avoid silent overflow.
    """
    if _is_float_dtype(dtype):
        return np.dtype(np.float64) if dtype.itemsize >= 8 else np.dtype(np.float32)
    if np.issubdtype(dtype, np.signedinteger):
        return np.dtype(np.int64)
    if np.issubdtype(dtype, np.unsignedinteger):
        return np.dtype(np.uint64)
    return dtype


def _as_numpy(x: Any) -> np.ndarray:
    """Host view of an array (device->host copy for jax arrays)."""
    return np.asarray(x)


def _off_device(x: Any) -> bool:
    """True for a ``jax.Array``: nobody can write it, nor its host array
    (:func:`_as_numpy`, read-only memory), so a result may be the leaf
    itself, still on its devices, or that host array.  Anything else may be
    memory the caller still writes and is copied.  (No import: this module
    loads without jax.)"""
    jax = sys.modules.get("jax")
    return jax is not None and isinstance(x, jax.Array)


def _in_device_order(x: Any) -> bool:
    """True for a ``jax.Array`` on one device that holds it with its
    dimensions in another order than its shape's (a TPU does so with a
    leaf whose last dimension is no multiple of 128): the host copy of
    such a leaf comes in the device's order, as strides.  The leaf says so
    itself, ``format.layout.major_to_minor``; one spread over several
    devices is put together on the host, in C order."""
    if not _off_device(x) or x.ndim < 2 or len(x.sharding.device_set) != 1:
        return False
    order = getattr(x.format.layout, "major_to_minor", None)
    return order is not None and tuple(order) != tuple(range(x.ndim))


@functools.lru_cache(maxsize=None)
def _flatten_jit() -> Any:
    def ring_relayout(leaves: "List[Any]") -> "List[Any]":
        return [x.reshape(-1) for x in leaves]

    # (a device trace shows it under this name, ``jit_ring_relayout``)
    return sys.modules["jax"].jit(ring_relayout)


# A copy that is complete hands its host array over in tens of microseconds;
# one still under way holds the asker for what is left of it.  Below this
# wait the leaf counts as having been on the host when its bucket asked
# (``ring.d2h``'s ``overlapped``, ``torchft_ring_leaves_prefetched_total``).
_COPY_READY_S = 1e-3


def _plan_leaf(a: Any) -> "Tuple[np.dtype, int]":
    """A leaf as the bucket plan sees it, ``(accumulation dtype, element
    count)``: from its shape and dtype alone, so a device leaf stays
    unmaterialized."""
    if not hasattr(a, "dtype") or not hasattr(a, "size"):
        a = np.asarray(a)
    return _accumulation_dtype(np.dtype(a.dtype)), int(a.size)


def _to_host(arrays: "List[Any]", relay: "List[int]") -> "List[Any]":
    """What each leaf's host array is made from (:func:`_as_numpy`): the
    leaf itself, or for those named in ``relay`` (leaves
    :func:`_in_device_order`) a copy laid out flat, row-major, on the device:
    one jitted program for all of them (one a device, should they differ),
    at the memory's speed where the host re-orders at under 1 GB/s.  Such a
    leaf arrives as a C-contiguous vector of its dtype.  The program is only
    dispatched here, and queues behind whatever else the device runs
    (another group's grad step, where two share a chip); nothing is waited
    for and nothing is copied yet (:func:`_send_to_host`)."""
    sources = list(arrays)
    by_device: "Dict[Any, List[int]]" = {}
    for i in relay:
        by_device.setdefault(arrays[i].sharding, []).append(i)
    for idxs in by_device.values():
        for i, flat in zip(idxs, _flatten_jit()([arrays[i] for i in idxs])):
            sources[i] = flat
    return sources


def _send_to_host(sources: "List[Any]", idxs: "List[int]") -> None:
    """Starts the host copy of whatever can start its own
    (``copy_to_host_async``: a ``jax.Array``, one copy a shard where it is
    spread over devices) and waits for none: :func:`_as_numpy` is then a
    wait for a copy already under way.  An ``np.ndarray`` leaf is its own
    host array and is not touched."""
    for i in idxs:
        start = getattr(sources[i], "copy_to_host_async", None)
        if start is not None:
            start()


def _divide(a: np.ndarray, divisor: "Optional[int]") -> np.ndarray:
    """``a / divisor`` in ``a``'s dtype: in place where ``a`` can be
    written, which only the owner of ``a`` may ask for; nothing at all for
    a divisor of 1 (``x / 1`` is ``x`` bit for bit) or none."""
    if divisor is None or divisor == 1:
        return a
    if a.flags.writeable and _is_float_dtype(a.dtype):
        a /= divisor
        return a
    return np.asarray(a / divisor, dtype=a.dtype)


def _divide_in_place(a: np.ndarray, divisor: "Optional[int]") -> None:
    """:func:`_divide` of a slice of a buffer its caller owns, whatever the
    dtype: what an integer's division made beside it is written back."""
    divided = _divide(a, divisor)
    if divided is not a:
        a[...] = divided


def _allreduce_alone(
    arrays: "List[Any]", divisor: "Optional[int]", kept_leaves: Any
) -> "List[Any]":
    """Allreduce at world size 1, of the leaves as they were handed in: the
    one place that decides, for every group that can be alone.

    With nothing to divide by (``divisor`` none or 1) the result is the
    input, and a ``jax.Array`` leaf comes back as itself: nobody can write
    it, so it never leaves its devices, whether it lies on one or is sharded
    over many.  A leaf the caller passed as host memory is copied, because
    the result must not alias what the caller can still write.  A divisor
    above 1 (no loop passes one to a lone group) takes the host path: the
    leaf comes off the device and is divided there, in its dtype.

    ``.d2h`` says how many bytes left the device (``bytes``) and how many
    stayed on it (``kept``), ``.pack`` how many bytes the host copied or
    handed through (a leaf that stayed counts as handed); ``kept_leaves``
    (``torchft_ring_leaves_kept_total``) counts the leaves that stayed."""
    scaled = divisor not in (None, 1)
    device = [_off_device(a) for a in arrays]
    on_device = sum(int(a.nbytes) for a, dev in zip(arrays, device) if dev)
    with _tracing.phase(
        ".d2h",
        bytes=on_device if scaled else 0,
        kept=0 if scaled else on_device,
        relaid=0,
    ):
        if scaled:
            arrays = [_as_numpy(a) for a in arrays]
        else:  # a host leaf is its own host array
            arrays = [a if dev else _as_numpy(a) for a, dev in zip(arrays, device)]
            kept_leaves.inc(sum(device))
    sizes = [int(a.nbytes) for a in arrays]
    copied = sum(n for n, dev in zip(sizes, device) if scaled or not dev)
    with _tracing.phase(".pack", copied=copied, handed=sum(sizes) - copied):
        return [
            _divide(a if dev else a.copy(), divisor)
            for a, dev in zip(arrays, device)
        ]


def _stable_replica_id(replica_id: str) -> str:
    """What precedes the ``:<uuid>`` of an incarnation: the id a group's
    series are labelled with, as the Manager labels its own."""
    return replica_id.split(":", 1)[0] or replica_id


def _check_recv_buffer(out: np.ndarray, shape: Any, dtype: str) -> None:
    """Validate a caller-supplied in-place recv buffer against the wire
    header: shape, dtype, and contiguity must all match (a silent
    value-cast or reshape would mask a buffer-setup bug)."""
    if (
        str(out.dtype) != dtype
        or tuple(out.shape) != tuple(shape)
        or not out.flags.c_contiguous
    ):
        raise RuntimeError(
            f"in-place recv buffer mismatch: {out.shape}/{out.dtype} vs "
            f"wire {tuple(shape)}/{dtype}"
        )


def _routable_local_ip(store_addr: str) -> str:
    """Local IP of the interface that routes to the store host.

    Hostnames are not guaranteed resolvable across hosts/containers; the
    interface used to reach the rendezvous store is by construction routable
    from every peer that also reaches the store.
    """
    host, _, port = store_addr.rpartition(":")
    try:
        probe = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        try:
            probe.connect((host or "127.0.0.1", int(port or 1)))
            return probe.getsockname()[0]
        finally:
            probe.close()
    except OSError:
        return socket.gethostname()


class ProcessGroup(ABC):
    """Abstract reconfigurable process group over host buffers.

    API parity with the reference base ProcessGroup
    (reference: torchft/process_group.py:133-386), adapted to numpy/pytree
    data instead of torch tensors.
    """

    def __init__(self, timeout: float = 60.0) -> None:
        self._timeout = timeout

    # -- lifecycle ---------------------------------------------------------

    @abstractmethod
    def configure(
        self, store_addr: str, replica_id: str, rank: int, world_size: int
    ) -> None:
        """(Re)initialize membership. store_addr is ``host:port/prefix``."""

    @abstractmethod
    def abort(self) -> None:
        """Cancel in-flight ops and latch an aborted error."""

    @abstractmethod
    def errored(self) -> Optional[Exception]:
        """Latched failure, or None if healthy."""

    def shutdown(self) -> None:
        self.abort()

    def set_timeout(self, timeout: float) -> None:
        self._timeout = timeout

    # -- topology ----------------------------------------------------------

    @abstractmethod
    def rank(self) -> int: ...

    @abstractmethod
    def size(self) -> int: ...

    # -- collectives -------------------------------------------------------

    @abstractmethod
    def allreduce(
        self,
        arrays: "List[Any]",
        op: str = REDUCE_SUM,
        divisor: "Optional[int]" = None,
    ) -> Work:
        """Resolves to one array per leaf, in the leaf's shape and dtype:
        the leaves reduced over the group by ``op`` and, where a
        ``divisor`` is given, divided by it in the leaf's dtype (the
        Manager's live participant count, which is not always ``size()``;
        ``REDUCE_AVG`` is the divisor ``size()``).  The group divides, in
        place where it owns the buffer it reduced into.  The result is
        arrays the caller may not write: host arrays as a rule, private to
        the caller for as long as the caller holds them (or any view of
        them), never an alias of an ``np.ndarray`` the caller passed in,
        and that array is not written.  A ``jax.Array`` leaf may come back
        as a ``jax.Array``: at world size 1 with nothing to divide by the
        mean over one participant is the leaf, and it comes back as itself,
        on its devices, sharding intact (:func:`_allreduce_alone`; a
        divisor above 1 there takes the host path).  Convert
        (``np.array(x)``) before writing into a result or calling an
        ``ndarray``-only method on it."""

    @abstractmethod
    def allgather(self, array: Any) -> Work:
        """Resolves to a list of ``size()`` arrays, indexed by rank."""

    @abstractmethod
    def broadcast(self, array: Any, root: int = 0) -> Work: ...

    @abstractmethod
    def reduce_scatter(self, array: Any, op: str = REDUCE_SUM) -> Work:
        """Reduce then scatter row-chunks; resolves to this rank's chunk.

        ``array.shape[0]`` must be divisible by ``size()``.
        """

    @abstractmethod
    def alltoall(self, arrays: "List[Any]") -> Work:
        """Exchange: sends arrays[i] to rank i; resolves to received list."""

    def sendrecv(self, array: Any, dst: int, src: int, tag: int = 0) -> Work:
        """Simultaneous send-to-``dst`` + receive-from-``src`` as ONE op;
        resolves to the received array.  The deadlock-free pairwise
        exchange primitive multi-hop reduction plans are built from
        (ops/topology.py): both directions drain concurrently even when
        payloads exceed socket buffers, which two serialized send/recv
        ops on the single worker cannot guarantee.  Backends without a
        native implementation reject it."""
        return failed_work(
            RuntimeError(f"{type(self).__name__} does not support sendrecv")
        )

    @abstractmethod
    def send(self, array: Any, dst: int, tag: int = 0) -> Work: ...

    @abstractmethod
    def recv(self, src: int, tag: int = 0, out: "Optional[np.ndarray]" = None) -> Work:
        """Resolves to the received array (shape/dtype carried on the wire).
        ``out``: backends that can, receive in place into this buffer."""

    def barrier(self) -> Work:
        return self.allreduce([np.zeros(1, dtype=np.float32)])


class ProcessGroupDummy(ProcessGroup):
    """World-size-1 no-op group (reference: torchft/process_group.py:960-1081).

    Used to bootstrap wrappers before the first quorum and in tests.
    """

    def __init__(self, rank: int = 0, world: int = 1, timeout: float = 60.0) -> None:
        super().__init__(timeout)
        assert world == 1, "ProcessGroupDummy only supports world_size 1"
        self._rank = rank
        self._world = world
        self._errored: Optional[Exception] = None
        self._bind_metrics("")
        self.configure_count = 0

    def configure(self, store_addr: str, replica_id: str, rank: int, world_size: int) -> None:
        self.configure_count += 1
        self._errored = None
        self._bind_metrics(replica_id)

    def _bind_metrics(self, replica_id: str) -> None:
        self._metric_replica_id = _stable_replica_id(replica_id)
        self._m_leaves_kept = _metrics.RING_LEAVES_KEPT.labels(
            replica_id=self._metric_replica_id
        )

    def abort(self) -> None:
        self._errored = RuntimeError("aborted")

    def errored(self) -> Optional[Exception]:
        return self._errored

    def rank(self) -> int:
        return self._rank

    def size(self) -> int:
        return self._world

    def allreduce(
        self,
        arrays: "List[Any]",
        op: str = REDUCE_SUM,
        divisor: "Optional[int]" = None,
    ) -> Work:
        by = self._world if op == REDUCE_AVG else divisor
        return completed_work(_allreduce_alone(arrays, by, self._m_leaves_kept))

    def allgather(self, array: Any) -> Work:
        return completed_work([_as_numpy(array).copy()])

    def broadcast(self, array: Any, root: int = 0) -> Work:
        return completed_work(_as_numpy(array).copy())

    def reduce_scatter(self, array: Any, op: str = REDUCE_SUM) -> Work:
        return completed_work(_as_numpy(array).copy())

    def alltoall(self, arrays: "List[Any]") -> Work:
        return completed_work([_as_numpy(a).copy() for a in arrays])

    def send(self, array: Any, dst: int, tag: int = 0) -> Work:
        return failed_work(RuntimeError("send not supported on world-size-1 group"))

    def recv(self, src: int, tag: int = 0, out: "Optional[np.ndarray]" = None) -> Work:
        return failed_work(RuntimeError("recv not supported on world-size-1 group"))


# ---------------------------------------------------------------------------
# TCP backend (host-mediated DCN collectives — the Gloo analog)
# ---------------------------------------------------------------------------

_HELLO_MAGIC = 0x7F7A11AA


class _PeerConn:
    """A connected, rank-identified socket to one peer."""

    def __init__(self, sock: socket.socket, rank: int) -> None:
        self.sock = sock
        self.rank = rank
        sock.setblocking(True)

    def close(self) -> None:
        try:
            self.sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        try:
            self.sock.close()
        except OSError:
            pass


class _TokenBucket:
    """Egress token bucket shared by a PG's sender threads.

    ``consume(n)`` debits ``n`` bytes and sleeps off any debt, so the
    long-run egress rate converges to ``rate`` bytes/s while short bursts
    up to ``burst`` pass unthrottled (one socket-buffer's worth — shaping
    below that granularity would only measure syscall overhead).  The
    sleep happens OUTSIDE the lock: concurrent senders each serve their
    own debt, and because debits are serialized under the lock the debt
    each sender sleeps for is its own marginal contribution.
    """

    def __init__(self, rate_bytes_per_s: float, burst: int = 4 << 20) -> None:
        self.rate = float(rate_bytes_per_s)
        self.burst = float(burst)
        self._tokens = self.burst
        self._t = time.monotonic()
        self._lock = _lockcheck.lock("pg.token_bucket")
        # Own ledger (bytes debited / seconds slept serving debt): tests
        # assert pacing on these instead of wall-clock deltas, which CI
        # scheduler noise can invert.
        self.consumed_bytes = 0
        self.slept_s = 0.0

    def consume(self, nbytes: int) -> float:
        """Debit ``nbytes``; returns the seconds slept serving the debt
        (the shaper-wait the per-peer wait accounting attributes)."""
        with self._lock:
            now = time.monotonic()
            self._tokens = min(
                self.burst, self._tokens + (now - self._t) * self.rate
            )
            self._t = now
            self._tokens -= nbytes
            self.consumed_bytes += int(nbytes)
            debt = -self._tokens
        if debt > 0:
            wait = debt / self.rate
            time.sleep(wait)
            with self._lock:
                self.slept_s += wait
            return wait
        return 0.0


class _PGAborted(RuntimeError):
    pass


class _RingStream:
    """What the three roles of one bucket's ring share (:meth:`ProcessGroupTCP.
    _allreduce_one`): the slices of the previous rank's stream, in its
    order, message after message, handed from role to role as tokens.

    The receiver (the PG worker) says a slice has :meth:`land`-ed; the
    reducer takes the reduce-scatter's slices in turn (:meth:`reduce_all`, on
    a thread of its own where a message has more than one; the receiver
    itself, there and then, where it has one: the whole-chunk ring, which
    hands nothing over); the sender (:meth:`gate`) pushes slice ``k`` of its
    next message once slice ``k`` of the last one in is reduced or, in the
    allgather, has landed.  A message of the reduce-scatter lands in
    ``scratch``, a chunk long, so the receiver will :meth:`hold` back a slice
    whose place is not reduced yet: the one place where the wire waits for a
    host pass, entered as ``stalled`` (``ring.reduce``) like the one-slice
    reduce and the worker's wait for the reducer at the end
    (:meth:`drain`).  What the reducer did while the next slice was still
    coming in is ``hidden``.

    A hand-off is one token in a queue and wakes the one role that waits
    for it.  The first error of any role is the ring's; every wait ends
    with it, or at the deadline."""

    def __init__(
        self,
        slices: int,
        slice_bytes: int,
        messages: int,
        reduce_slice: "Callable[[int], None]",
        stalled: Any,
        deadline: float,
    ) -> None:
        self.slices = slices  # of one message
        self.slice_bytes = slice_bytes
        self.reduces = messages // 2 * slices  # the reduce-scatter's slices
        self.forwards = (messages - 1) * slices  # all but the last message's
        self.deadline = deadline
        self.landed = 0  # the receiver's count
        self.reduced = 0  # the reducer's
        self.hidden = 0  # of those, reduced before the next one was in
        self.hidden_s = 0.0
        self.error: "Optional[BaseException]" = None
        self._reduce_slice = reduce_slice
        self._stalled = stalled
        self._lock = _lockcheck.lock("pg.tcp.ring_stream")
        # receiver -> reducer: a slice of the reduce-scatter is in
        self._to_reduce: "queue.SimpleQueue[bool]" = queue.SimpleQueue()
        # reducer -> receiver: a slice of ``scratch`` may be overwritten
        self._free: "queue.SimpleQueue[bool]" = queue.SimpleQueue()
        # -> sender: a slice may go on, reduced (from the reducer) or, in
        # the allgather, as it landed (from the receiver)
        self._final = {
            "reduced": queue.SimpleQueue(),
            "landed": queue.SimpleQueue(),
        }

    def fail(self, exc: BaseException) -> BaseException:
        """``exc`` ends the ring unless something has already; returns
        what did."""
        with self._lock:
            if self.error is None:
                self.error = exc
        for q in (self._to_reduce, self._free, *self._final.values()):
            q.put(False)
        return self.error

    def _take(self, q: "queue.SimpleQueue[bool]") -> None:
        try:
            token = q.get(timeout=max(self.deadline - time.monotonic(), 0.001))
        except queue.Empty:
            raise TimeoutError("ring slice not ready by the deadline") from None
        if not token:
            raise _PGAborted("ring failed in another role")

    def _reduce_next(self, under_the_wire: bool) -> None:
        g = self.reduced
        t0 = time.perf_counter()
        self._reduce_slice(g)
        seconds = time.perf_counter() - t0
        self.reduced = g + 1
        # the wire was not kept waiting: the next slice was still coming
        if under_the_wire and self.landed <= g + 1:
            self.hidden += 1
            self.hidden_s += seconds
        self._final["reduced"].put(True)

    # -- the receiver
    def hold(self) -> None:
        """Before a slice is read: it may not overwrite a slice of
        ``scratch`` that is not reduced."""
        if self.error is not None:
            raise _PGAborted("ring failed in another role")
        if 1 < self.slices <= self.landed < self.reduces:
            # (booked only where the token is not there yet)
            with self._stalled if self._free.empty() else _UNTIMED:
                self._take(self._free)

    def land(self) -> None:
        self.landed = g = self.landed + 1
        if g > self.reduces:
            if g <= self.forwards:
                self._final["landed"].put(True)
        elif self.slices > 1:
            self._to_reduce.put(True)
        else:
            with self._stalled:
                self._reduce_next(under_the_wire=False)

    def drain(self, reducer: "Optional[Future]") -> None:
        """After the last byte is in: what is left to reduce."""
        if reducer is not None:
            with self._stalled:
                reducer.result(
                    timeout=max(self.deadline - time.monotonic(), 0.001) + 1.0
                )

    # -- the reducer
    def reduce_all(self) -> None:
        try:
            for _ in range(self.reduces):
                self._take(self._to_reduce)
                self._reduce_next(under_the_wire=True)
                self._free.put(True)
        except BaseException as e:  # noqa: BLE001 - the ring's, raised by the worker
            self.fail(e)
            raise

    # -- the sender
    def gate(self, message: int) -> "Optional[Callable[[int], None]]":
        """What outgoing ``message`` waits for before its first ``end``
        bytes go: the slices of the message that came in before it,
        reduced (reduce-scatter) or landed (allgather).  The first goes at
        once, from the source."""
        if message == 0:
            return None
        q = self._final[
            "reduced" if (message - 1) * self.slices < self.reduces else "landed"
        ]
        taken = 0

        def ready(end: int) -> None:
            nonlocal taken
            while taken * self.slice_bytes < end:
                self._take(q)
                taken += 1

        return ready


class NotParticipatingError(RuntimeError):
    """Raised by ``ManagedProcessGroup.rank()`` when the replica has no rank
    in the current quorum (it is healing or excluded).  Contrast with the
    reference, whose managed PG always has a local rank (torchft/
    process_group.py:1233-1266) because healing replicas still hold one."""


class ProcessGroupTCP(ProcessGroup):
    """Fault-tolerant collectives over a full TCP mesh of host processes.

    The cross-replica-group (DCN) collective backend: rendezvous through the
    quorum primary's store under a per-quorum prefix (set by the Manager,
    reference: torchft/manager.py:659-690), full-mesh connect, then ring
    algorithms on host buffers.  Bandwidth-optimal ring allreduce /
    reduce-scatter; direct sends for broadcast/gather at the small world
    sizes of the replica dimension.

    All ops run in submission order on a single worker thread; both
    endpoints of each socket submit the same collective sequence so streams
    stay in sync (the standard collective contract).
    """

    def __init__(
        self,
        timeout: float = 60.0,
        bandwidth_gbps: "Optional[float]" = None,
        rtt_ms: "Optional[float]" = None,
    ) -> None:
        super().__init__(timeout)
        self._rank = -1
        self._world = 0
        self._peers: Dict[int, _PeerConn] = {}
        self._listener: Optional[socket.socket] = None
        self._errored: Optional[Exception] = None
        self._aborted = False
        self._generation = 0
        # Egress bandwidth shaping (token bucket across all sender
        # threads).  Two uses: benchmarking the quantized wire under a
        # *measured* DCN bandwidth instead of loopback's effectively
        # infinite one, and capping a training job's DCN footprint on
        # shared links.  None = unshaped; TORCHFT_WIRE_GBPS supplies a
        # default (decimal GB/s, e.g. "0.5").
        if bandwidth_gbps is None:
            env = env_float("TORCHFT_WIRE_GBPS", 0.0)
            bandwidth_gbps = env if env > 0 else None
        self._bucket: "Optional[_TokenBucket]" = (
            _TokenBucket(bandwidth_gbps * 1e9) if bandwidth_gbps else None
        )
        # WAN latency model (TORCHFT_WIRE_RTT_MS): per-MESSAGE first-byte
        # delay on the shaped path, charged only on sends that cross a
        # host/slice boundary of the TORCHFT_TOPOLOGY descriptor (flat /
        # unset topology = every peer is across a boundary, the
        # multi-region flat-ring premise).  Deliberately decoupled from
        # the token bucket: the bucket paces PAYLOAD CHUNKS (bandwidth
        # debt accumulates per byte), while latency is paid once per
        # message no matter how many pacing chunks it splits into — so a
        # K-chunk message costs rtt + bytes/rate, never K*rtt
        # (tests/test_topology.py pins the composition).  The token
        # bucket is boundary-scoped the same way: with a declared
        # topology, BOTH shaping legs model the WAN boundary and
        # intra-host messages ride the (loopback/ICI-fast) local fabric
        # unshaped; with flat/unset topology every peer is across the
        # boundary, so existing shaped setups behave byte-identically.
        if rtt_ms is None:
            rtt_ms = env_float("TORCHFT_WIRE_RTT_MS", 0.0)
        self._rtt_s = max(rtt_ms, 0.0) / 1e3
        # ranks whose messages cross a topology boundary (computed per
        # configure from TORCHFT_TOPOLOGY; empty while unconfigured)
        self._inter_peers: "frozenset[int]" = frozenset()
        # link-state plane identities (utils/linkstats.py): per peer
        # rank, the peer host learned at configure and the derived
        # (link label, is_local) pair — a same-host peer across a
        # declared topology boundary gets a ``host#gN`` pseudo-host so
        # the shaped link is never averaged into the local fabric
        self._peer_hosts: "Dict[int, str]" = {}
        self._link_labels: "Dict[int, Tuple[str, bool]]" = {}
        # In-flight op handle in the process-wide flight recorder
        # (utils/flightrecorder.py; subsumes the old ad-hoc ``_flight``
        # dict).  The FlightOp serializes its own updates (worker + sender
        # threads write); _flight_swap_lock guards the TAKE of the handle
        # so the worker's success path and a concurrent abort() cannot
        # both finish the same op (the loser would mislabel a completed
        # collective as aborted).
        self._flight_op: "Optional[_flightrec.FlightOp]" = None
        self._flight_swap_lock = _lockcheck.lock("pg.tcp.flight_swap")
        self._replica_id = ""
        self._bind_metrics()
        self._lock = _lockcheck.lock("pg.tcp.state")
        self._worker: Optional[threading.Thread] = None
        self._sender: "Optional[concurrent_futures.ThreadPoolExecutor]" = None
        self._reducer: "Optional[concurrent_futures.ThreadPoolExecutor]" = None
        self._queue: "queue.Queue[Optional[Tuple[int, Callable[[], Any], Future]]]" = (
            queue.Queue()
        )

    def _bind_metrics(self) -> None:
        """``torchft_ring_buffers_total`` children by pool hit,
        ``torchft_ring_leaves_prefetched_total`` children by whether the
        copy was ready, ``torchft_ring_slices_total`` children by whether
        the reduce hid under the wire, ``torchft_ring_peer_wait_seconds_total``
        children by which wait it was, and ``torchft_ring_leaves_kept_total``,
        under the stable replica id."""
        self._metric_replica_id = _stable_replica_id(self._replica_id)
        self._m_leaves_kept = _metrics.RING_LEAVES_KEPT.labels(
            replica_id=self._metric_replica_id
        )
        self._m_leaves_prefetched = {
            ready: _metrics.RING_LEAVES_PREFETCHED.labels(
                replica_id=self._metric_replica_id,
                result="ready" if ready else "waited",
            )
            for ready in (True, False)
        }
        self._m_ring_buffers = {
            hit: _metrics.RING_BUFFERS.labels(
                replica_id=self._metric_replica_id,
                result="hit" if hit else "miss",
            )
            for hit in (True, False)
        }
        self._m_ring_slices = {
            hidden: _metrics.RING_SLICES.labels(
                replica_id=self._metric_replica_id,
                hidden="1" if hidden else "0",
            )
            for hidden in (True, False)
        }
        self._m_peer_wait = {
            kind: _metrics.RING_PEER_WAIT.labels(
                replica_id=self._metric_replica_id, kind=kind
            )
            for kind in ("arrive", "wait")
        }

    def set_bandwidth(self, gbps: "Optional[float]") -> None:
        """(Re)shape egress to ``gbps`` decimal GB/s; None removes the cap.
        Takes effect from the next send — in-flight chunks finish at the
        old rate."""
        self._bucket = _TokenBucket(gbps * 1e9) if gbps else None

    def set_rtt(self, rtt_ms: "Optional[float]") -> None:
        """(Re)set the modeled per-message boundary latency; None/0
        removes it.  Takes effect from the next send; boundary membership
        re-derives at the next configure."""
        self._rtt_s = max(rtt_ms or 0.0, 0.0) / 1e3

    def _boundary_peers(self, rank: int, world: int) -> "frozenset[int]":
        """Peers across a TORCHFT_TOPOLOGY host/slice boundary — the set
        BOTH wire-model legs (RTT and token bucket) charge on.
        Flat/unset topology: every peer (a flat ring spanning regions
        pays the boundary on every hop — and pre-topology shaped setups
        keep their exact behavior).  Computed unconditionally per
        configure: ``set_bandwidth``/``set_rtt`` may arm shaping AFTER
        membership forms."""
        if world <= 1:
            return frozenset()
        from torchft_tpu.ops.topology import resolve_topology

        topo = resolve_topology(world)
        if topo is None:
            return frozenset(r for r in range(world) if r != rank)
        return frozenset(
            r for r in range(world) if r != rank and topo.inter(rank, r)
        )

    def _link_peer_labels(
        self, world: int
    ) -> "Dict[int, Tuple[str, bool]]":
        """(link label, is_local) per connected peer for the passive
        link-state plane.  Cross-host peers key by their real host; a
        same-host peer across the declared topology boundary keys by the
        ``host#gN`` pseudo-host (its topology group) so WAN-modeled and
        local-fabric traffic never share an estimator — intra-host pairs
        report unshaped-fast, boundary pairs report the modeled link."""
        from torchft_tpu.ops.topology import resolve_topology
        from torchft_tpu.utils.hostident import local_host_identities

        topo = resolve_topology(world) if world > 1 else None
        local_ids = local_host_identities()
        labels: "Dict[int, Tuple[str, bool]]" = {}
        for r, host in self._peer_hosts.items():
            wan = r in self._inter_peers
            if wan and topo is not None and host in local_ids:
                label = f"{host}#g{topo.group_index(r)}"
            else:
                label = host
            labels[r] = (label, not wan)
        return labels

    # -- lifecycle ---------------------------------------------------------

    def configure(
        self, store_addr: str, replica_id: str, rank: int, world_size: int
    ) -> None:
        # chaos site: a reconfigure failure here surfaces to the Manager's
        # configure try-block, which latches it and re-forms next quorum
        _faults.check("pg.reconfigure", replica=replica_id)
        self._replica_id = replica_id
        self._bind_metrics()
        t_cfg_ns = time.time_ns()
        self._teardown()
        deadline = time.monotonic() + self._timeout

        with self._lock:
            self._errored = None
            self._aborted = False
            self._generation += 1
            gen = self._generation
        self._rank = rank
        self._world = world_size
        self._inter_peers = self._boundary_peers(rank, world_size)

        if world_size == 1:
            self._peers = {}
            self._peer_hosts = {}
            self._link_labels = {}
            self._start_worker(gen)
            _metrics.PG_RECONFIGURES.labels(transport="tcp").inc()
            _flightrec.record(
                "pg.configure", start_ns=t_cfg_ns, replica_id=replica_id,
                rank=rank, world=world_size,
            )
            return

        addr, _, prefix = store_addr.partition("/")
        store = StoreClient(addr, connect_timeout=self._timeout)
        try:
            try:
                listener = socket.socket(socket.AF_INET6, socket.SOCK_STREAM)
                listener.bind(("", 0))
            except OSError:
                # Host without IPv6 (ipv6.disable=1 containers).
                listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
                listener.bind(("", 0))
            listener.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            listener.listen(world_size)
            self._listener = listener
            # Advertise the interface address peers can actually route to:
            # the local IP of a connection toward the store host (hostnames
            # may not resolve across container boundaries).
            host = _routable_local_ip(addr)
            port = listener.getsockname()[1]
            store.set(f"{prefix}/rank_{rank}", f"{host}:{port}")

            peers: Dict[int, _PeerConn] = {}
            peer_hosts: Dict[int, str] = {}
            # Deterministic connect direction avoids duplicate links: lower
            # ranks dial higher ranks; higher ranks accept.
            for peer in range(rank + 1, world_size):
                peer_addr = store.get(
                    f"{prefix}/rank_{peer}",
                    timeout=max(deadline - time.monotonic(), 0.001),
                )
                phost, _, pport = peer_addr.rpartition(":")
                sock = socket.create_connection(
                    (phost, int(pport)),
                    timeout=max(deadline - time.monotonic(), 0.001),
                )
                sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                sock.sendall(struct.pack(">II", _HELLO_MAGIC, rank))
                peers[peer] = _PeerConn(sock, peer)
                peer_hosts[peer] = phost
            for _ in range(rank):
                listener.settimeout(max(deadline - time.monotonic(), 0.001))
                sock, _ = listener.accept()
                sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                magic, peer_rank = struct.unpack(">II", self._read_exact_sock(sock, 8, deadline))
                if magic != _HELLO_MAGIC:
                    raise RuntimeError("bad hello from peer")
                peers[peer_rank] = _PeerConn(sock, peer_rank)
                try:
                    peer_hosts[peer_rank] = sock.getpeername()[0]
                except OSError:
                    peer_hosts[peer_rank] = "unknown"
            self._peers = peers
            self._peer_hosts = peer_hosts
            self._link_labels = self._link_peer_labels(world_size)
            self._start_worker(gen)
            _metrics.PG_RECONFIGURES.labels(transport="tcp").inc()
            _flightrec.record(
                "pg.configure", start_ns=t_cfg_ns, replica_id=replica_id,
                rank=rank, world=world_size,
            )
        except Exception as e:
            _flightrec.record(
                "pg.configure", status="error", start_ns=t_cfg_ns,
                replica_id=replica_id, rank=rank, world=world_size,
                error=repr(e),
            )
            self._teardown()
            raise
        finally:
            store.close()

    def _start_worker(self, gen: int) -> None:
        # Fresh queue per generation so stale ops/poison pills from a prior
        # configure can never reach the new worker. Swapped under the lock so
        # _submit can never enqueue onto a retired queue.
        with self._lock:
            self._queue = queue.Queue()
            self._sender = concurrent_futures.ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="pg_tcp_sender"
            )
            # (its thread starts with the first ring that moves in slices)
            self._reducer = concurrent_futures.ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="pg_tcp_reducer"
            )
            self._worker = threading.Thread(
                target=self._worker_loop,
                args=(gen, self._queue),
                name="pg_tcp_worker",
                daemon=True,
            )
            self._worker.start()

    def _teardown(self) -> None:
        with self._lock:
            self._generation += 1  # invalidate the running worker
            peers = list(self._peers.values())
            self._peers = {}
            listener = self._listener
            self._listener = None
            old_queue = self._queue
            old_queue.put(None)  # wake the worker so it can exit
        for p in peers:
            p.close()
        if listener is not None:
            try:
                listener.close()
            except OSError:
                pass
        worker = self._worker
        if worker is not None and worker is not threading.current_thread():
            worker.join(timeout=5.0)
        with self._lock:
            # After this, _submit fails fast instead of enqueueing into limbo.
            self._worker = None
            sender, self._sender = self._sender, None
            reducer, self._reducer = self._reducer, None
        for role in (sender, reducer):
            if role is not None:
                # don't wait: a sendall stuck on a dead peer unwedges itself
                # when the socket close (above) fails it, and the ring's
                # reducer with the ring
                role.shutdown(wait=False)
        # Fail any ops still sitting in the retired queue so no Work handle
        # is left unresolved (a hang is worse than an error in FT code).
        while True:
            try:
                item = old_queue.get_nowait()
            except queue.Empty:
                break
            if item is not None:
                item[2].set_exception(_PGAborted("process group torn down"))

    def abort(self) -> None:
        self._dump_flight("process group aborted", dump=False)
        _flightrec.record(
            "pg.abort", status="abort", replica_id=self._replica_id,
            rank=self._rank, world=self._world,
        )
        # one dump per abort, whether or not an op was in flight: the ring
        # around the abort IS the postmortem evidence
        _flightrec.dump("process group aborted", trigger="pg_abort")
        _metrics.PG_ABORTS.labels(transport="tcp").inc()
        with self._lock:
            self._aborted = True
            if self._errored is None:
                self._errored = _PGAborted("process group aborted")
        self._teardown()

    def errored(self) -> Optional[Exception]:
        return self._errored

    def rank(self) -> int:
        return self._rank

    def size(self) -> int:
        return self._world

    # -- op submission -----------------------------------------------------

    def _submit(self, fn: "Callable[[], Any]", op: str = "op") -> Work:
        fut: Future = Future()
        with self._lock:
            if self._errored is not None:
                return failed_work(self._errored)
            if self._worker is None:
                return failed_work(
                    _PGAborted("process group not configured/running")
                )
            # Enqueue under the lock: the queue object is swapped by
            # _teardown/_start_worker under the same lock, so this item can
            # never land on a retired queue with no worker to fail it.
            self._queue.put((self._generation, fn, fut, op))
        return Work(fut)

    def _worker_loop(self, gen: int, q: "queue.Queue") -> None:
        while True:
            item = q.get()
            if item is None:
                return
            self._run_item(gen, *item)
            # The op closure pins its inputs — device buffers when the
            # caller passed jax arrays.  Drop it BEFORE blocking on the
            # next get(): a flagship gradient pytree held across the next
            # forward/backward is the difference between fitting a 16 GB
            # chip and not.
            del item

    def _run_item(
        self, gen: int, item_gen: int, fn: "Callable[[], Any]", fut: Future,
        op: str,
    ) -> None:
        with self._lock:
            superseded = self._generation != gen
            errored = self._errored
        if superseded or item_gen != gen or errored is not None:
            # Keep draining so every queued Work resolves — abandoned
            # futures would hang their waiters forever.
            fut.set_exception(
                errored or _PGAborted("process group reconfigured")
            )
            return
        self._flight_op = _flightrec.start(
            op,
            kind="collective",
            generation=item_gen,
            rank=self._rank,
            world=self._world,
            replica_id=self._replica_id,
        )
        try:
            result = fn()
            with self._flight_swap_lock:
                flight_op, self._flight_op = self._flight_op, None
            if flight_op is not None:
                flight_op.finish("ok")
            fut.set_result(result)
        except Exception as e:  # noqa: BLE001 - latch every op failure
            # Flight-recorder dump BEFORE latching: when a wedged
            # collective dies (deadline, peer reset), the op-level state
            # — what was in flight, with whom, how far it got — is the
            # evidence the postmortem needs (reference dumps the NCCL
            # flight recorder on abort for the same reason,
            # torchft/process_group.py:89-108,830-838).
            self._dump_flight(f"collective failed: {e!r}", error=repr(e))
            with self._lock:
                if self._errored is None:
                    self._errored = e
            fut.set_exception(e)

    # -- flight recorder ---------------------------------------------------

    def _flight_io(self, **kw: Any) -> None:
        """Merge current transfer state (direction, peer, tag, bytes) into
        the in-flight op record (worker or sender thread)."""
        op = self._flight_op
        if op is not None:
            op.update(**kw)

    def _flight_progress(self, nbytes: int) -> None:
        op = self._flight_op
        if op is not None:
            op.add_bytes(nbytes)

    def _dump_flight(self, reason: str, dump: bool = True, **extra: Any) -> None:
        """Finish the in-flight op as failed: the completed record lands in
        the process flight ring, a legacy ``abort`` event goes to the
        structured pipeline (JSONL sink when TORCHFT_EVENTS_FILE is set),
        and — unless the caller dumps separately — the whole ring is
        dumped to TORCHFT_FLIGHT_FILE."""
        with self._flight_swap_lock:
            flight_op, self._flight_op = self._flight_op, None
        if flight_op is None:
            return
        # Best-effort: the recorder must never mask the collective error.
        try:
            rec = flight_op.finish("error", reason=reason, **extra)
            from torchft_tpu.utils.logging import log_event

            f = {
                k: v
                for k, v in rec.items()
                if k not in ("status", "start_ns", "end_ns", "kind")
            }
            deadline = f.pop("deadline_mono", None)
            if deadline is not None:
                f["deadline_remaining_s"] = round(
                    deadline - time.monotonic(), 3
                )
            f["in_flight_s"] = round(
                (rec["end_ns"] - rec["start_ns"]) / 1e9, 3
            )
            log_event("abort", reason, **f)
            if dump:
                _flightrec.dump(reason, trigger="pg_abort")
        except Exception:  # noqa: BLE001 - recorder must never mask the error
            logger.exception("flight-recorder dump failed")

    # -- wire helpers ------------------------------------------------------

    @staticmethod
    def _read_exact_sock(sock: socket.socket, n: int, deadline: float) -> bytes:
        buf = bytearray()
        while len(buf) < n:
            sock.settimeout(max(deadline - time.monotonic(), 0.001))
            chunk = sock.recv(n - len(buf))
            if not chunk:
                raise ConnectionError("peer closed connection")
            buf.extend(chunk)
        return bytes(buf)

    def _peer(self, rank: int) -> _PeerConn:
        peer = self._peers.get(rank)
        if peer is None:
            raise _PGAborted(f"no connection to rank {rank}")
        return peer

    def _read_into_sock(
        self, sock: socket.socket, view: memoryview, deadline: float
    ) -> None:
        """recv_into a buffer — zero intermediate copies for payloads."""
        off, n = 0, len(view)
        while off < n:
            sock.settimeout(max(deadline - time.monotonic(), 0.001))
            got = sock.recv_into(view[off:], n - off)
            if got == 0:
                raise ConnectionError("peer closed connection")
            off += got
            self._flight_progress(got)

    def _send_msg(
        self,
        dst: int,
        tag: int,
        array: np.ndarray,
        deadline: float,
        step: "Optional[int]" = None,
        ready: "Optional[Callable[[int], None]]" = None,
    ) -> None:
        """One tagged array to ``dst``.  A payload that is still being made
        (a ring's chunk, reduced slice by slice) goes in pieces of ``step``
        bytes, each once ``ready(end)`` has returned: the payload's first
        ``end`` bytes are final.  The bytes on the wire are the same."""
        peer = self._peer(dst)
        array = np.ascontiguousarray(array)
        header = pickle.dumps(
            {"tag": tag, "shape": array.shape, "dtype": str(array.dtype)}
        )
        self._flight_io(
            send_peer=dst, send_tag=tag, send_bytes=array.nbytes,
            deadline_mono=deadline,
        )
        wan = dst in self._inter_peers
        if ready is not None and array.nbytes:
            # a message starts when its first piece is final: until then the
            # peer waits for the message, not in it
            ready(1)
        t0 = time.perf_counter()
        shaper_wait = starved = 0.0
        if wan and self._rtt_s > 0.0:
            # First-byte latency of the WAN model: once per MESSAGE,
            # before any byte moves, independent of the bandwidth debt
            # the pacing loop below accrues (K pacing chunks still pay
            # 1x RTT).  Charged in the sender so a blocked receiver
            # observes the first byte RTT late, like a real WAN socket.
            time.sleep(self._rtt_s)
            shaper_wait += self._rtt_s
        # boundary-scoped shaping: only messages crossing the declared
        # topology boundary ride the modeled WAN link (flat/unset
        # topology: every peer — see __init__)
        bucket = self._bucket if wan else None
        if bucket is not None:
            shaper_wait += bucket.consume(8 + len(header))
        peer.sock.settimeout(max(deadline - time.monotonic(), 0.001))
        peer.sock.sendall(struct.pack(">II", len(header), array.nbytes) + header)
        if array.nbytes:
            # uint8 view, not memoryview.cast("B"): ml_dtypes arrays
            # (bfloat16/fp8 — the TPU training dtypes) have no
            # buffer-protocol format char and raise in cast(). The payload
            # still goes to the kernel straight from the array's buffer.
            view = memoryview(array.reshape(-1).view(np.uint8))
            piece = step or len(view)
            if bucket is not None:
                # shaped path: pace in 1 MB chunks so the bucket's sleeps
                # interleave with the peer's compute at sub-fragment
                # granularity (a single consume() of a GB payload would
                # model a link with GB-deep switch buffers)
                piece = min(piece, 1 << 20)
            for off in range(0, len(view), piece):
                chunk = view[off : off + piece]
                if ready is not None:
                    waited = time.perf_counter()
                    ready(off + len(chunk))
                    starved += time.perf_counter() - waited
                if bucket is not None:
                    shaper_wait += bucket.consume(len(chunk))
                peer.sock.settimeout(max(deadline - time.monotonic(), 0.001))
                peer.sock.sendall(chunk)
        # Passive link-state measurement (utils/linkstats.py): every
        # completed send is one sample — bytes + wall on the reduction
        # plane, first-byte = the modeled RTT leg.  Shaper waits are
        # additionally attributed per peer host (worst-K label tier).
        # What the send waited for its own payload is no time of the link's.
        label, is_local = self._link_labels.get(dst, ("unknown", not wan))
        _linkstats.record(
            label,
            "reduction",
            8 + len(header) + array.nbytes,
            time.perf_counter() - t0 - starved,
            first_byte_s=self._rtt_s if (wan and self._rtt_s > 0.0) else 0.0,
            local=is_local,
        )
        if shaper_wait > 0.0:
            _metrics.PG_WIRE_WAIT.labels(
                peer=_linkstats.LINKS.peer_topk_label(label)
            ).inc(shaper_wait)

    def _recv_msg(
        self,
        src: int,
        tag: int,
        deadline: float,
        out: "Optional[np.ndarray]" = None,
        head: Any = _UNTIMED,
        body: Any = _UNTIMED,
        stream: "Optional[_RingStream]" = None,
    ) -> np.ndarray:
        """Receive one tagged array; ``out`` receives in place (zero-alloc
        fast path for ring steps — reference pg_transport in-place recv
        analog, torchft/checkpointing/pg_transport.py:230-300).

        The two places a receive blocks are entered as ``head`` (until the
        message's first 8 bytes are here: the wait for the peer) and
        ``body`` (the rest of it: the bytes); a ring hands in the parts of
        its ``ring.wire``, everyone else nothing.  A ring's message is read
        a slice of its ``stream`` at a time: each is asked for
        (:meth:`_RingStream.hold`, outside ``body``) and said to have
        landed; anyone else's payload is read whole."""
        peer = self._peer(src)
        with head:
            # record the blocked-on peer BEFORE the header read: a wedged
            # recv (peer never sends) hangs right here, and that is exactly
            # the state the flight recorder must capture
            self._flight_io(recv_peer=src, recv_tag=tag, deadline_mono=deadline)
            lengths = self._read_exact_sock(peer.sock, 8, deadline)
        with body:
            hlen, nbytes = struct.unpack(">II", lengths)
            header = pickle.loads(
                self._read_exact_sock(peer.sock, hlen, deadline)
            )
            if header["tag"] != tag:
                raise RuntimeError(
                    f"collective tag mismatch: expected {tag}, got {header['tag']}"
                )
            if out is None:
                # Pool-backed receive: repeated collective shapes (ring chunks,
                # the quantized pipeline's per-chunk wire buffers) re-take the
                # SAME pages their consumers gave back, so steady-state receive
                # allocation — and its mmap page-fault bill — is zero.  Buffers
                # that escape to callers simply never return to the pool (take
                # falls back to np.empty on a miss), same contract as before.
                out = _pool.take(header["shape"], np.dtype(header["dtype"]))
                if out.nbytes != nbytes:
                    raise RuntimeError(
                        f"collective payload size mismatch: header says {nbytes},"
                        f" shape/dtype imply {out.nbytes}"
                    )
            else:
                _check_recv_buffer(out, header["shape"], header["dtype"])
                if out.nbytes != nbytes:
                    raise RuntimeError(
                        f"collective payload size mismatch: header says {nbytes},"
                        f" shape/dtype imply {out.nbytes}"
                    )
            self._flight_io(recv_bytes=nbytes)
            # uint8 view for ml_dtypes compat (see _send_msg)
            view = memoryview(out.reshape(-1).view(np.uint8))
        if stream is None:
            with body:
                self._read_into_sock(peer.sock, view, deadline)
            return out
        # (an empty message is one empty slice)
        for off in range(0, max(nbytes, 1), stream.slice_bytes or 1):
            stream.hold()
            with body:
                self._read_into_sock(
                    peer.sock, view[off : off + stream.slice_bytes], deadline
                )
            stream.land()
        return out

    def _exchange(
        self,
        send_dst: int,
        send_tag: int,
        send_array: np.ndarray,
        recv_src: int,
        recv_tag: int,
        deadline: float,
        recv_out: "Optional[np.ndarray]" = None,
    ) -> np.ndarray:
        """Simultaneous send+recv without deadlocking on full TCP buffers.

        Pushing the send to the persistent sender thread keeps both
        directions draining even when payloads exceed socket buffer sizes.
        (A ring allreduce streams its messages itself: :meth:`_allreduce_one`.)
        """
        sender = self._sender
        if sender is None:
            raise _PGAborted("process group not configured/running")
        send_fut = sender.submit(
            self._send_msg, send_dst, send_tag, send_array, deadline
        )
        send_err: "Optional[BaseException]" = None
        try:
            received = self._recv_msg(recv_src, recv_tag, deadline, out=recv_out)
        finally:
            # always reap the send: the socket stream must never be left
            # mid-write when the next step starts (a recv error still
            # propagates; it takes precedence over any send error)
            try:
                send_fut.result(
                    timeout=max(deadline - time.monotonic(), 0.001) + 1.0
                )
            except concurrent_futures.TimeoutError:
                send_err = TimeoutError(
                    "collective send did not complete by deadline"
                )
            except BaseException as e:  # noqa: BLE001 - re-raised below
                send_err = e
        if send_err is not None:
            raise send_err
        return received

    # -- collectives -------------------------------------------------------

    def allreduce(
        self,
        arrays: "List[Any]",
        op: str = REDUCE_SUM,
        divisor: "Optional[int]" = None,
    ) -> Work:
        """``divisor``: what the reduced result is divided by, in place, by
        the ring that owns the buffer; ``REDUCE_AVG`` is the divisor
        ``size()``.  Alone (world size 1) there is no ring: the op still
        takes its turn on the worker thread, and what it resolves to is
        :func:`_allreduce_alone`'s to decide."""
        deadline_budget = self._timeout
        # The ring, opened (manager.PHASE_PARTS ``ring.*``): each part is
        # timed here, where it runs, as a part of the caller's open phase
        # (the Manager's ``ring``), which is carried to the worker thread:
        # the seconds land in its sink, the spans are its children.
        whole = _tracing.open_phase()
        queued = _tracing.phase(".queue").begin()

        def run() -> "List[Any]":
            queued.end()
            with _tracing.under(whole):
                by = self._world if op == REDUCE_AVG else divisor
                if self._world == 1:
                    # the post-failure shrunken-group hot path: with nothing
                    # to divide by, a device leaf stays where it is
                    return _allreduce_alone(arrays, by, self._m_leaves_kept)
                # The device→host leg is STARTED here, on the PG worker, and
                # waited for bucket by bucket (`_allreduce_coalesced`): on
                # the caller thread even the waiting would stall it for the
                # whole sync instead of letting the submit return immediately
                # (the DiLoCo overlap pattern: outer-grad allreduce rides
                # behind the next fragment's inner steps).
                deadline = time.monotonic() + deadline_budget
                return self._allreduce_coalesced(arrays, op, by, deadline)

        work = self._submit(run, op="allreduce")
        # Wire accounting on the UNQUANTIZED path too (parity with the
        # quantized collectives' measured wire_bytes, so bench/diagnose
        # compare f32 vs int8 traffic honestly): per-rank ring egress from
        # the same bucket plan the reduce will use, computed synchronously
        # from shapes/dtypes — device arrays stay unmaterialized.
        try:
            work.wire_bytes = self._ring_wire_bytes(
                [_plan_leaf(a) for a in arrays], self._world
            )
            work.unquantized_wire_bytes = work.wire_bytes
        except Exception:  # noqa: BLE001 - accounting must not fail the op
            logger.debug("allreduce wire accounting failed", exc_info=True)
        return work

    # Pack small same-acc-dtype leaves into buckets up to this many bytes.
    # Below the cap, coalescing wins (one ring amortizes per-message
    # latency: measured 10x at 28 tiny leaves); above it, the extra
    # concat/split memcpy costs more than the saved round trips, so big
    # leaves ring solo (zero-copy path).
    BUCKET_BYTES = 4 * 1024 * 1024

    # A ring moves a chunk in slices, each reduced and sent on while the next
    # one comes in (``_allreduce_one``): ``SLICES`` of them, fewer where a
    # slice would hold less than ``SLICE_BYTES``, and one, the chunk whole,
    # below twice that.
    SLICES = 8
    SLICE_BYTES = 8 * 1024 * 1024

    @classmethod
    def _plan_buckets(
        cls, leaves: "List[Tuple[np.dtype, int]]"
    ) -> "List[Tuple[np.dtype, List[int], int]]":
        """Greedy same-accumulation-dtype buckets under ``BUCKET_BYTES``.

        ``leaves``: per-leaf (acc dtype, element count).  Returns
        ``(acc, leaf indices, total elements)`` per bucket, order-
        preserving — the one plan both the reduce and the wire-byte
        accounting derive from.
        """
        buckets: "List[Tuple[np.dtype, List[int], int]]" = []
        bucket_bytes: "List[int]" = []
        open_bucket: "Dict[np.dtype, int]" = {}  # acc dtype -> bucket index
        for i, (acc, size) in enumerate(leaves):
            nbytes = size * acc.itemsize
            if nbytes >= cls.BUCKET_BYTES:
                buckets.append((acc, [i], size))
                bucket_bytes.append(nbytes)
                continue
            bi = open_bucket.get(acc)
            if bi is not None and bucket_bytes[bi] + nbytes <= cls.BUCKET_BYTES:
                buckets[bi][1].append(i)
                buckets[bi] = (acc, buckets[bi][1], buckets[bi][2] + size)
                bucket_bytes[bi] += nbytes
            else:
                buckets.append((acc, [i], size))
                bucket_bytes.append(nbytes)
                open_bucket[acc] = len(buckets) - 1
        return buckets

    @classmethod
    def _ring_wire_bytes(
        cls, leaves: "List[Tuple[np.dtype, int]]", world: int
    ) -> int:
        """Per-rank ring-allreduce egress for these leaves: each bucket
        rings once, sending 2*(w-1) chunk-sized messages (reduce-scatter
        half + allgather half) of its accumulation dtype."""
        if world <= 1:
            return 0
        total = 0
        for acc, _idxs, elems in cls._plan_buckets(leaves):
            chunk = -(-elems // world)
            total += 2 * (world - 1) * chunk * acc.itemsize
        return total

    def _allreduce_coalesced(
        self,
        arrays: "List[Any]",
        op: str,
        divisor: "Optional[int]",
        deadline: float,
    ) -> "List[np.ndarray]":
        """Bucketized allreduce of a gradient pytree's leaves, the device
        link running ahead of the ring.

        A gradient pytree is many small leaves; ringing each one costs
        2*(w-1) latency-bound exchanges per leaf. Same-accumulation-dtype
        leaves pack greedily into <= BUCKET_BYTES buckets that ring once
        (the reference's bucketized-allreduce idea,
        TORCHFT_USE_BUCKETIZATION, local_sgd.py:29); oversized leaves ring
        solo on the zero-copy path. Order-preserving.

        The plan comes first, from shapes and dtypes alone.  The device
        leaves' host copies are started in the plan's order and none is
        waited for before its bucket's turn (:func:`_send_to_host`): while
        bucket i is on the wire the leaves of i+1.. cross the link on the
        runtime's transfer thread.  The link shares itself out among the
        copies under way (five started together: the first is there after
        78 ms where alone it takes 32), so only as many are started as keep
        the bytes on their way behind the bucket now taken at the plan's
        largest bucket: one bucket ahead where leaves are alike, several
        where small buckets come before a large one.  The buckets ring in
        the plan's order on every rank, whatever arrived first.  Should a
        bucket fail, the copies under way are dropped with their arrays:
        nothing waits for them.

        Timed as the parts ``.d2h|pack|wire|reduce|unpack`` of the open
        phase: one span per part and bucket, never one per exchange;
        ``.d2h`` is one span over its stretches, those in which this thread
        is held by the link (the relayout's dispatch, then a bucket's
        starts and its wait).  Its ``overlapped`` are the bytes of the
        leaves sent ahead that were on the host when their bucket asked
        (``torchft_ring_leaves_prefetched_total`` counts the leaves).
        """
        buckets = self._plan_buckets([_plan_leaf(a) for a in arrays])
        sizes = [
            sum(int(getattr(arrays[i], "nbytes", 0)) for i in idxs)
            for _, idxs, _ in buckets
        ]
        window = max(sizes, default=0)
        before = [0, *itertools.accumulate(sizes)]  # bytes of the buckets < k
        # A ring re-orders on the host whatever does not arrive in C order,
        # so such leaves leave the device flat.
        relay = [i for i, a in enumerate(arrays) if _in_device_order(a)]
        d2h = _tracing.phase(
            ".d2h",
            bytes=before[-1],
            relaid=sum(arrays[i].nbytes for i in relay),
            overlapped=0,
        )
        with d2h.lap():
            sources = _to_host(arrays, relay)

        def host_array(i: int) -> np.ndarray:
            source, sources[i] = sources[i], None  # a flat copy goes here
            if not hasattr(source, "copy_to_host_async"):
                return _as_numpy(source)
            waited = time.perf_counter()
            a = _as_numpy(source)
            ready = time.perf_counter() - waited < _COPY_READY_S
            self._m_leaves_prefetched[ready].inc()
            if ready:
                d2h.attrs["overlapped"] += a.nbytes
            return a

        results: "List[Optional[np.ndarray]]" = [None] * len(arrays)
        sent = 0  # buckets whose copies are started
        for b, (acc_dtype, idxs, _) in enumerate(buckets):
            with d2h.lap():
                while sent < len(buckets) and (
                    sent <= b or before[sent] - before[b + 1] < window
                ):
                    _send_to_host(sources, buckets[sent][1])
                    sent += 1
                host = [host_array(i) for i in idxs]
            if len(idxs) == 1:
                results[idxs[0]] = self._allreduce_one(
                    host[0], op, divisor, deadline, b == 0
                )
                continue
            # cast leaves individually: mixed input dtypes sharing one acc
            # dtype (f16+f32, bf16) may not have a numpy promotion rule
            with _tracing.phase(".pack", leaves=len(idxs)):
                flat = np.concatenate(
                    [
                        np.ascontiguousarray(a)
                        .ravel()
                        .astype(acc_dtype, copy=False)
                        for a in host
                    ]
                )
            reduced = self._allreduce_one(flat, op, divisor, deadline, b == 0)
            with _tracing.phase(".unpack", leaves=len(idxs)):
                off = 0
                for i, a in zip(idxs, host):
                    results[i] = reduced[off : off + a.size].astype(
                        a.dtype, copy=False
                    )
                    off += a.size
        d2h.end()
        # in the leaf's own shape (a flat copy's result too): a view
        return [r.reshape(np.shape(a)) for r, a in zip(results, arrays)]

    def _allreduce_one(
        self,
        array: np.ndarray,
        op: str,
        divisor: "Optional[int]",
        deadline: float,
        first: bool = False,
    ) -> np.ndarray:
        """One ring over one buffer, the op's ``first`` or a later one.  A
        gradient byte is written on the host only by an operation that
        changes it, and only into memory that is already faulted:

        - the buffer is leased from the pool (``utils/bufpool.py``): the
          result is a view of it, and its memory comes back when the caller
          has dropped the last view of the result, not before;
        - the source is not copied in.  In the reduce-scatter a rank
          receives every chunk but its own exactly once, so the first
          write of chunk ``c`` is its reduce, ``buf[c] = src[c] +
          received``; the rank's own chunk is sent from the source at step
          0 and written by the allgather.  Only what the source does not
          hold as a whole chunk, in the accumulation dtype and in C order,
          is copied: a leaf that widens (bf16, ints), one the caller
          passed as strided host memory, and the zero-padded tail (a
          device leaf held in another order of dimensions arrives flat:
          :func:`_to_host`);
        - the division (``REDUCE_AVG``, the Manager's participant count)
          is applied once, by the rank that ends the reduce-scatter with a
          chunk fully reduced, to that chunk before the allgather ships
          it: every rank divides 1/w of the buffer, none for a divisor of
          1, and all hold the same bits.

        The 2(w-1) messages a rank receives and the 2(w-1) it sends are two
        streams, and a chunk of at least two ``SLICE_BYTES`` moves through
        them in slices (:class:`_RingStream`): this thread reads the
        previous rank's stream message after message, the reducer thread
        reduces (and, in the last step, divides) a slice while the next one
        comes in, and the sender thread pushes a slice of the next message
        on as soon as it is final.  The messages, their order, headers and
        payloads are those of the whole-chunk ring, which is the case of
        one slice a message: reduced by this thread between two messages,
        with nothing handed to the reducer.
        """
        w, r = self._world, self._rank
        acc_dtype = _accumulation_dtype(array.dtype)
        reduce_into = _REDUCE_UFUNCS[op]
        n = array.size
        chunk = -(-n // w)
        # The source as the ring orders it, where that is a view: so
        # arrives what came off a device (``_to_host``); a caller's strided
        # host array is copied in.
        src = array.reshape(-1) if array.flags.c_contiguous else None
        # chunks read straight from the source; the rest starts at ``lo``
        direct = (
            n // chunk
            if chunk and src is not None and src.dtype == acc_dtype
            else 0
        )
        lo = direct * chunk
        buf, hit = _pool.lease(chunk * w, acc_dtype)
        self._m_ring_buffers[hit].inc()
        with _tracing.phase(
            ".pack",
            copied=(n - lo) * acc_dtype.itemsize,
            handed=lo * acc_dtype.itemsize,
            pool="hit" if hit else "miss",
        ):
            if direct:
                buf[lo:n] = src[lo:]
            else:
                # one pass, cast and strides included, into warm memory
                buf[:n].reshape(array.shape)[...] = array
            buf[n:] = 0
            # chunks are views of the one buffer, so ring steps receive in
            # place and reduce in place; scratch is private to this call
            # and its size repeats every ring
            chunks = [buf[i * chunk : (i + 1) * chunk] for i in range(w)]
            # where a chunk's own values are until its first write
            own = [
                src[i * chunk : (i + 1) * chunk] if i < direct else chunks[i]
                for i in range(w)
            ]
            scratch = _pool.take(chunk, acc_dtype)

        nxt, prv = (r + 1) % w, (r - 1) % w
        # slices a message, as even as they come, and the elements of one
        slices = min(
            max(chunk * acc_dtype.itemsize // self.SLICE_BYTES, 1), self.SLICES
        )
        span = -(-chunk // slices)

        def reduce_slice(g: int) -> None:
            # slice g of the stream: of the reduce-scatter's message ``step``
            step, k = divmod(g, slices)
            idx = (r - step - 1) % w
            at = slice(k * span, (k + 1) * span)
            out = chunks[idx][at]
            reduce_into(own[idx][at], scratch[at], out=out)
            if step == w - 2:  # fully reduced, and this rank's to ship
                _divide_in_place(out, divisor)

        # ring.reduce is the seconds in which the wire stood still for a
        # reduce or a division: the one-slice reduce between two messages,
        # the receiver held back for ``scratch``, the wait for the reducer
        # once the last byte is in.  What the reducer did under the wire is
        # ring.reduce.hidden: seconds, no span.
        reduce = _tracing.phase(".reduce", slices=slices, hidden=0)
        stream = _RingStream(
            slices, span * acc_dtype.itemsize, 2 * (w - 1), reduce_slice,
            reduce.lap(), deadline,
        )
        # ring.wire is the wall of the bucket's two streams, the first
        # header sought to the last byte in and out, less what is booked
        # as ring.reduce.  Its parts are this thread's, the receiving
        # role's.  The wait for a message's first bytes is the peer's: for
        # the op's first message, which depends on nothing the previous
        # rank received, it is how much later than this rank that one
        # reached the ring (``.arrive``, once an op: a plain phase, so also
        # an annotation beside the device trace); for every later one the
        # peer is in the ring and late with this chunk (``.wait``).
        # ``.recv`` is a message's bytes coming in, a stall between two of
        # its slices included; ``.send`` what the sending costs this
        # thread: handing the stream to the sender, then what is left of it
        # once the last byte is in.  The last three accumulate, like
        # ``.reduce``: one span a part and bucket.  They are made as the
        # wire's before its wall starts and booked after it has ended, so
        # that the wall holds the streams and little else.
        wire = _tracing.phase(
            ".wire", bytes=2 * (w - 1) * chunk * acc_dtype.itemsize
        )
        with _tracing.under(wire):
            arrive = _tracing.phase(".arrive") if first else None
            wait = _tracing.phase(".wait")
            recv = _tracing.phase(".recv")
            send = _tracing.phase(".send")
        head, body, tail = wait.lap(), recv.lap(), send.lap()
        # reduce-scatter: after w-1 messages chunk (r+1)%w is fully reduced;
        # allgather of the reduced chunks, received straight into place
        incoming = [(100 + step, scratch) for step in range(w - 1)] + [
            (200 + step, chunks[(r - step) % w]) for step in range(w - 1)
        ]
        outgoing = [
            (100 + step, chunks[(r - step) % w] if step else own[r])
            for step in range(w - 1)
        ] + [(200 + step, chunks[(r - step + 1) % w]) for step in range(w - 1)]
        sender, reducer = self._sender, self._reducer
        if sender is None or reducer is None:
            raise _PGAborted("process group not configured/running")
        sending: "Optional[Future]" = None
        reducing: "Optional[Future]" = None
        try:
            with wire:
                with tail:
                    sending = sender.submit(
                        self._ring_send, stream, nxt, outgoing, deadline
                    )
                    if slices > 1:
                        reducing = reducer.submit(stream.reduce_all)
                for m, (tag, out) in enumerate(incoming):
                    self._recv_msg(
                        prv, tag, deadline, out=out,
                        head=head if m or arrive is None else arrive,
                        body=body, stream=stream,
                    )
                stream.drain(reducing)
                with tail:
                    sent, _ = concurrent_futures.wait(
                        [sending],
                        timeout=max(deadline - time.monotonic(), 0.001) + 1.0,
                    )
                    if not sent:
                        raise TimeoutError(
                            "collective send did not complete by deadline"
                        )
                    sending.result()
                reduce.attrs["hidden"] = stream.hidden
                if stream.hidden and reduce.sink is not None:
                    _tracing.add_seconds(
                        reduce.sink, "ring.reduce.hidden", stream.hidden_s
                    )
                wire.exclude(reduce.end())
        except BaseException as e:  # noqa: BLE001 - the ring's first error is raised
            raise stream.fail(e)
        finally:
            # no role outlives the ring: the reducer reads ``scratch`` and
            # the sender must not be left mid-message (their errors are the
            # stream's already)
            concurrent_futures.wait(
                [role for role in (sending, reducing) if role is not None],
                timeout=max(deadline - time.monotonic(), 0.001) + 1.0,
            )
            # of a ring that failed too: how long it waited for a peer that
            # never came is what its operator asks first
            recv.end()
            send.end()
            self._m_peer_wait["wait"].inc(wait.end())
            if arrive is not None:
                self._m_peer_wait["arrive"].inc(arrive.seconds)
            self._m_ring_slices[True].inc(stream.hidden)
            self._m_ring_slices[False].inc(stream.reduced - stream.hidden)
            _pool.give(scratch)
        with _tracing.phase(".unpack", bytes=array.nbytes):
            # a leaf that widened is cast back into a new array, and the
            # ring buffer's lease ends here
            return np.asarray(buf[:n], dtype=array.dtype).reshape(array.shape)

    def _ring_send(
        self,
        stream: _RingStream,
        dst: int,
        outgoing: "List[Tuple[int, np.ndarray]]",
        deadline: float,
    ) -> None:
        """The sending role of a ring, on the sender thread: message after
        message to the next rank, each slice as soon as it is final."""
        try:
            for m, (tag, array) in enumerate(outgoing):
                self._send_msg(
                    dst, tag, array, deadline,
                    step=stream.slice_bytes, ready=stream.gate(m),
                )
        except BaseException as e:  # noqa: BLE001 - the ring's, raised by the worker
            stream.fail(e)
            raise

    def allgather(self, array: Any) -> Work:
        np_array = _as_numpy(array)
        deadline_budget = self._timeout

        def run() -> List[np.ndarray]:
            deadline = time.monotonic() + deadline_budget
            w, r = self._world, self._rank
            if w == 1:
                return [np_array.copy()]
            pieces: List[Optional[np.ndarray]] = [None] * w
            pieces[r] = np.ascontiguousarray(np_array)
            nxt, prv = (r + 1) % w, (r - 1) % w
            for step in range(w - 1):
                send_idx = (r - step) % w
                recv_idx = (r - step - 1) % w
                pieces[recv_idx] = self._exchange(
                    nxt, 300 + step, pieces[send_idx], prv, 300 + step, deadline
                )
            # received pieces are already private allocations from
            # _recv_msg; only the own piece aliases the caller's array and
            # needs a defensive copy
            pieces[r] = pieces[r].copy()  # type: ignore[union-attr]
            return pieces  # type: ignore[return-value]

        return self._submit(run, op="allgather")

    def broadcast(self, array: Any, root: int = 0) -> Work:
        np_array = _as_numpy(array)
        deadline_budget = self._timeout

        def run() -> np.ndarray:
            deadline = time.monotonic() + deadline_budget
            w, r = self._world, self._rank
            if w == 1:
                return np_array.copy()
            if r == root:
                for peer in range(w):
                    if peer != r:
                        self._send_msg(peer, 400, np_array, deadline)
                return np_array.copy()
            return self._recv_msg(root, 400, deadline)

        return self._submit(run, op="broadcast")

    def reduce_scatter(self, array: Any, op: str = REDUCE_SUM) -> Work:
        np_array = _as_numpy(array)
        deadline_budget = self._timeout

        def run() -> np.ndarray:
            deadline = time.monotonic() + deadline_budget
            w, r = self._world, self._rank
            if w == 1:
                return np_array.copy()
            if np_array.shape[0] % w != 0:
                raise ValueError(
                    f"reduce_scatter dim0 {np_array.shape[0]} not divisible by {w}"
                )
            inplace_reduce = _REDUCE_UFUNCS[op]
            rows = np_array.shape[0] // w
            acc_dtype = _accumulation_dtype(np_array.dtype)
            buf = np.empty(np_array.shape, dtype=acc_dtype)
            buf[...] = np_array
            chunks = [buf[i * rows : (i + 1) * rows] for i in range(w)]
            scratch = np.empty(chunks[0].shape, dtype=acc_dtype)
            nxt, prv = (r + 1) % w, (r - 1) % w
            # Ring schedule shifted by one vs allreduce so each rank ends
            # holding its *own* fully-reduced chunk r.
            for step in range(w - 1):
                send_idx = (r - step - 1) % w
                recv_idx = (r - step - 2) % w
                self._exchange(
                    nxt, 500 + step, chunks[send_idx], prv, 500 + step, deadline,
                    recv_out=scratch,
                )
                inplace_reduce(chunks[recv_idx], scratch, out=chunks[recv_idx])
            result = chunks[r]
            if op == REDUCE_AVG:
                if np.issubdtype(acc_dtype, np.floating):
                    result /= w
                else:
                    result = result / w
            # copy: returning a view of chunks[r] would pin the w-times
            # larger accumulation buffer for as long as the caller holds
            # the result
            return np.array(result, dtype=np_array.dtype)

        return self._submit(run, op="reduce_scatter")

    def alltoall(self, arrays: "List[Any]") -> Work:
        np_arrays = [_as_numpy(a) for a in arrays]
        deadline_budget = self._timeout

        def run() -> List[np.ndarray]:
            deadline = time.monotonic() + deadline_budget
            w, r = self._world, self._rank
            if len(np_arrays) != w:
                raise ValueError(f"alltoall needs {w} arrays, got {len(np_arrays)}")
            out: List[Optional[np.ndarray]] = [None] * w
            out[r] = np_arrays[r].copy()
            for offset in range(1, w):
                dst = (r + offset) % w
                src = (r - offset) % w
                out[src] = self._exchange(
                    dst, 600 + offset, np_arrays[dst], src, 600 + offset, deadline
                )
            return out  # type: ignore[return-value]

        return self._submit(run, op="alltoall")

    def sendrecv(self, array: Any, dst: int, src: int, tag: int = 0) -> Work:
        np_array = _as_numpy(array)
        deadline_budget = self._timeout

        def run() -> np.ndarray:
            deadline = time.monotonic() + deadline_budget
            if dst == self._rank and src == self._rank:
                return np.ascontiguousarray(np_array).copy()
            # the same concurrent send+recv primitive the ring steps use:
            # the send drains on the sender thread while this worker
            # blocks on the receive, so paired exchanges never deadlock
            # on full TCP buffers
            return self._exchange(
                dst, 2000 + tag, np_array, src, 2000 + tag, deadline
            )

        return self._submit(run, op="sendrecv")

    def send(self, array: Any, dst: int, tag: int = 0) -> Work:
        np_array = _as_numpy(array)
        deadline_budget = self._timeout

        def run() -> None:
            deadline = time.monotonic() + deadline_budget
            self._send_msg(dst, 1000 + tag, np_array, deadline)

        return self._submit(run, op="send")

    def recv(self, src: int, tag: int = 0, out: "Optional[np.ndarray]" = None) -> Work:
        """``out``: receive straight into this buffer (shape/dtype must
        match the wire) — the zero-alloc path for healing into live state."""
        deadline_budget = self._timeout

        def run() -> np.ndarray:
            deadline = time.monotonic() + deadline_budget
            return self._recv_msg(src, 1000 + tag, deadline, out=out)

        return self._submit(run, op="recv")


# ---------------------------------------------------------------------------
# wrappers
# ---------------------------------------------------------------------------


class ProcessGroupWrapper(ProcessGroup):
    """Forwards every op to an inner PG; base for behavior-modifying wrappers."""

    def __init__(self, pg: ProcessGroup) -> None:
        super().__init__(pg._timeout)
        self._pg = pg

    @property
    def parent(self) -> ProcessGroup:
        return self._pg

    def configure(self, store_addr: str, replica_id: str, rank: int, world_size: int) -> None:
        self._pg.configure(store_addr, replica_id, rank, world_size)

    def abort(self) -> None:
        self._pg.abort()

    def errored(self) -> Optional[Exception]:
        return self._pg.errored()

    def set_timeout(self, timeout: float) -> None:
        self._pg.set_timeout(timeout)

    def rank(self) -> int:
        return self._pg.rank()

    def size(self) -> int:
        return self._pg.size()

    def allreduce(
        self,
        arrays: "List[Any]",
        op: str = REDUCE_SUM,
        divisor: "Optional[int]" = None,
    ) -> Work:
        # The fallback hands the caller's own arrays back, undivided and
        # unwritten: a failed step is never committed.
        return self._wrap(
            self._pg.allreduce(arrays, op, divisor),
            lambda: [_as_numpy(a) for a in arrays],
        )

    def allgather(self, array: Any) -> Work:
        return self._wrap(self._pg.allgather(array), lambda: [_as_numpy(array)])

    def broadcast(self, array: Any, root: int = 0) -> Work:
        return self._wrap(self._pg.broadcast(array, root), lambda: _as_numpy(array))

    def reduce_scatter(self, array: Any, op: str = REDUCE_SUM) -> Work:
        # Fallback keeps the success-path *shape*: this rank's row chunk.
        def fallback() -> np.ndarray:
            np_array = _as_numpy(array)
            w = max(self._pg.size(), 1)
            rows = np_array.shape[0] // w if np_array.shape[0] >= w else 1
            r = max(self._pg.rank(), 0)
            return np_array[r * rows : (r + 1) * rows]

        return self._wrap(self._pg.reduce_scatter(array, op), fallback)

    def alltoall(self, arrays: "List[Any]") -> Work:
        return self._wrap(
            self._pg.alltoall(arrays), lambda: [_as_numpy(a) for a in arrays]
        )

    def sendrecv(self, array: Any, dst: int, src: int, tag: int = 0) -> Work:
        # fallback shaped like the success path: plan exchanges are
        # same-shape both directions, so the sent array stands in
        return self._wrap(
            self._pg.sendrecv(array, dst, src, tag),
            lambda: _as_numpy(array),
        )

    def send(self, array: Any, dst: int, tag: int = 0) -> Work:
        return self._wrap(self._pg.send(array, dst, tag), lambda: None)

    def recv(self, src: int, tag: int = 0, out: "Optional[np.ndarray]" = None) -> Work:
        return self._wrap(self._pg.recv(src, tag, out=out), lambda: None)

    def _wrap(self, work: Work, fallback: "Callable[[], Any]") -> Work:
        """Hook: ``fallback()`` builds a success-path-shaped substitute result."""
        return work


class ErrorSwallowingProcessGroupWrapper(ProcessGroupWrapper):
    """After the first error, ops become no-ops returning their inputs.

    Reference: torchft/process_group.py:1123-1179 — lets the training loop
    continue through a failed step; Manager.should_commit observes the error
    and triggers reconfigure.
    """

    def __init__(self, pg: ProcessGroup) -> None:
        super().__init__(pg)
        self._swallowed: Optional[Exception] = None

    def configure(self, store_addr: str, replica_id: str, rank: int, world_size: int) -> None:
        self._swallowed = None
        super().configure(store_addr, replica_id, rank, world_size)

    def errored(self) -> Optional[Exception]:
        return self._swallowed or super().errored()

    def report_error(self, exc: Exception) -> None:
        self._swallowed = exc

    def _wrap(self, work: Work, fallback: "Callable[[], Any]") -> Work:
        if self._swallowed is not None:
            return completed_work(fallback())

        out: Future = Future()

        def _done(f: "Future[Any]") -> None:
            exc = f.exception()
            if exc is not None:
                if self._swallowed is None:
                    self._swallowed = (
                        exc if isinstance(exc, Exception) else RuntimeError(str(exc))
                    )
                # Resolve with a result shaped like the success path so the
                # training loop proceeds; Manager observes errored() later.
                out.set_result(fallback())
            else:
                out.set_result(f.result())

        work.get_future().add_done_callback(_done)
        return Work(out)


class FakeProcessGroupWrapper(ProcessGroupWrapper):
    """Test-only fault injection: fail the *future* of upcoming ops.

    Reference: torchft/process_group.py:1182-1230 — lets integration tests
    inject an allreduce failure at a chosen step without touching sockets.
    """

    def __init__(self, pg: ProcessGroup) -> None:
        super().__init__(pg)
        self._next_op_error: Optional[Exception] = None
        self._next_configure_error: Optional[Exception] = None

    def report_future_error(self, exc: Exception) -> None:
        self._next_op_error = exc

    def report_configure_error(self, exc: Exception) -> None:
        self._next_configure_error = exc

    def configure(self, store_addr: str, replica_id: str, rank: int, world_size: int) -> None:
        if self._next_configure_error is not None:
            exc, self._next_configure_error = self._next_configure_error, None
            raise exc
        super().configure(store_addr, replica_id, rank, world_size)

    def _wrap(self, work: Work, fallback: "Callable[[], Any]") -> Work:
        if self._next_op_error is not None:
            exc, self._next_op_error = self._next_op_error, None
            return failed_work(exc)
        return work


class ManagedProcessGroup(ProcessGroup):
    """A ProcessGroup whose allreduce routes through a ``Manager``.

    Reference: torchft/process_group.py:1233-1266 — lets code written
    against the plain ProcessGroup API (e.g. a gradient-averaging hook or a
    mesh dimension) transparently get quorum-aware, error-swallowing,
    participant-count-scaled allreduce.  ``size()`` reports the *live*
    participant count so loss/gradient scaling stays correct as replicas
    fail and join; all other collectives and lifecycle calls are invalid on
    this wrapper — the Manager owns quorum and reconfiguration.
    """

    def __init__(self, manager: Any) -> None:
        super().__init__()
        self._manager = manager

    def configure(self, store_addr: str, replica_id: str, rank: int, world_size: int) -> None:
        raise RuntimeError(
            "ManagedProcessGroup is configured by its Manager, not directly"
        )

    def abort(self) -> None:
        raise RuntimeError("ManagedProcessGroup cannot be aborted directly")

    def shutdown(self) -> None:
        """No-op: the Manager owns the underlying PG's lifecycle."""

    def errored(self) -> Optional[Exception]:
        return self._manager.errored()

    def rank(self) -> int:
        """Replica rank within the live quorum.

        Raises ``NotParticipatingError`` while this replica is healing /
        excluded from the current quorum.  Returning a fake 0 here would let
        a healing replica silently consume rank-0's data shard; callers that
        can tolerate non-participation should use
        ``Manager.participating_rank()`` (returns ``None``) or
        ``ManagedDeviceMesh.global_batch_slice`` (returns the empty slice).
        """
        r = self._manager.participating_rank()
        if r is None:
            raise NotParticipatingError(
                "replica is not participating in the current quorum "
                "(healing or excluded); no rank is defined this step"
            )
        return r

    def size(self) -> int:
        return self._manager.num_participants()

    def allreduce(
        self,
        arrays: "List[Any]",
        op: str = REDUCE_SUM,
        divisor: "Optional[int]" = None,
    ) -> Work:
        if divisor is not None:
            raise ValueError(
                "ManagedProcessGroup takes no divisor: its Manager averages "
                "by the live participant count (op=REDUCE_AVG)"
            )
        # Manager.allreduce takes a pytree; a list of arrays is one.
        return self._manager.allreduce(list(arrays), reduce_op=op)

    def allgather(self, array: Any) -> Work:
        return failed_work(RuntimeError("ManagedProcessGroup only supports allreduce"))

    def broadcast(self, array: Any, root: int = 0) -> Work:
        return failed_work(RuntimeError("ManagedProcessGroup only supports allreduce"))

    def reduce_scatter(self, array: Any, op: str = REDUCE_SUM) -> Work:
        return failed_work(RuntimeError("ManagedProcessGroup only supports allreduce"))

    def alltoall(self, arrays: "List[Any]") -> Work:
        return failed_work(RuntimeError("ManagedProcessGroup only supports allreduce"))

    def send(self, array: Any, dst: int, tag: int = 0) -> Work:
        return failed_work(RuntimeError("ManagedProcessGroup only supports allreduce"))

    def recv(self, src: int, tag: int = 0, out: "Optional[np.ndarray]" = None) -> Work:
        return failed_work(RuntimeError("ManagedProcessGroup only supports allreduce"))
