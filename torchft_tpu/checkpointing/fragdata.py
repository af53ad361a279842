"""Native zero-copy fragment data plane — the Python control side.

The C++ server/client pair in ``native/fragserver.{h,cc}`` owns the
fragment *data* plane: staged payload bytes are served verbatim via
writev out of pooled registered buffers (zero user-space copies
steady-state), and the receive path lands bytes straight into this
process's bufpool buffers and sha256-digests them with the GIL released
(ctypes drops it around every native call).  Python keeps the *control*
plane: plans, manifests, digests-of-record, staging lifecycle, version
advertisement, and ALL telemetry (fault sites, linkstats, provenance,
wire-shaper charging, flight/span records stay in ``fragments.py``).

Wiring:

- ``HTTPTransport`` owns one :class:`FragDataServer` per transport and
  mirrors its raw ``frag:*`` staging into it (begin/stage/finish/retire)
  — the handoff contract in docs/architecture.md;
- the Python HTTP server advertises the native data port at
  ``/nativeport`` (404 = this node serves fragments from Python only);
- ``fragments.fetch_raw`` dispatches raw ``frag_*`` GETs through
  :func:`fetch_native` wherever the library has the plane
  (:func:`enabled`), falling back to the Python path on any native miss
  — Mock transports, non-mirrored resources, and peers without a native
  port keep working unchanged.
"""

from __future__ import annotations

import ctypes
import http.client
import json
import threading
import urllib.error
import weakref
from typing import Dict, Optional, Tuple
from urllib.parse import urlparse

import numpy as np

from torchft_tpu.utils.bufpool import POOL

__all__ = [
    "FragDataServer",
    "STREAMING_HEADER",
    "StillStreaming",
    "available",
    "copy_transposed",
    "enabled",
    "fetch_native",
    "native_sha256",
    "reset_port_cache",
]

_gate_lock = threading.Lock()
_lib_ok: "Optional[bool]" = None

_U8P = None  # lazily bound ctypes.POINTER(c_uint8)


def _native_lib():
    from torchft_tpu import _native

    return _native.get_lib()


def available() -> bool:
    """True when the native library loads and exposes the fragment C API
    (cached — the first call may trigger the in-place native build)."""
    global _lib_ok
    if _lib_ok is None:
        with _gate_lock:
            if _lib_ok is None:
                try:
                    _lib_ok = bool(
                        hasattr(_native_lib(), "tft_frag_server_create")
                    )
                except Exception:
                    _lib_ok = False
    return bool(_lib_ok)


def enabled() -> bool:
    """Whether raw fragments ride the native data plane: wherever the
    library has it.  Called per use, under its own name, so the tests of
    the Python fallback can patch it off."""
    return available()


#: the header that marks such a 503 on the Python plane (the native
#: server answers 503 for a streaming version alone)
STREAMING_HEADER = "X-TFT-Streaming"


class StillStreaming(urllib.error.HTTPError):
    """The retryable 503 of a source that IS staging the asked version and
    has not reached the asked fragment yet (its long-poll ran out first).
    Retried like any 503; to a striped healer it is also a sign of life,
    which the 503 of a node that has staged nothing is not."""

    def __init__(self, url: str, msg: str = "still streaming") -> None:
        super().__init__(url, 503, msg, None, None)  # type: ignore[arg-type]


def _u8ptr(arr: np.ndarray):
    global _U8P
    if _U8P is None:
        _U8P = ctypes.POINTER(ctypes.c_uint8)
    return arr.ctypes.data_as(_U8P)


class FragDataServer:
    """Lifecycle wrapper for one native fragment data server.

    ``HTTPTransport`` drives it with the staging handoff contract:
    ``begin(step)`` opens a streaming version, ``stage()`` hands one raw
    payload down (the native side copies ONCE into a pooled registered
    buffer and wakes parked long-pollers; not at all where the payload
    was written into a buffer ``reserve()`` lent, which is committed
    where it lies), ``finish(step)`` seals the version, ``retire(step)``
    drops it (non-blocking: buffers referenced by in-flight serves, or
    still lent, are recycled on last deref)."""

    def __init__(self, bind_host: str = "") -> None:
        lib = _native_lib()
        handle = lib.tft_frag_server_create(bind_host.encode(), 0)
        if handle < 0:
            from torchft_tpu import _native

            raise RuntimeError(
                f"native fragserver create failed: {_native.last_error()}"
            )
        self._lib = lib
        self._handle = handle
        self.port = int(lib.tft_frag_server_port(handle))

    def begin(self, step: int) -> None:
        self._lib.tft_frag_begin(self._handle, int(step))

    def reserve(
        self, step: int, resource: str, nbytes: int
    ) -> "Optional[np.ndarray]":
        """Lend a pooled native buffer of ``nbytes`` for ``(step,
        resource)`` as a writable ``uint8`` view (uninitialized; not
        zero-filled), or ``None`` when the version is unknown here.  No
        reader sees it until ``stage()`` is handed the view, whole, and
        commits it where it lies.  The lend ends when the view and every
        view of it are gone (the ``bufpool`` lease idiom): until then the
        memory stays valid and unchanged whatever the version's fate, and
        only then, once retired and with no serve in flight, does it
        return to the native pool."""
        if nbytes <= 0 or self._handle < 0:
            return None
        ptr = self._lib.tft_frag_reserve(
            self._handle, int(step), resource.encode(), int(nbytes)
        )
        if not ptr:
            return None
        view = np.frombuffer(
            (ctypes.c_uint8 * int(nbytes)).from_address(ptr), dtype=np.uint8
        )
        # numpy collapses a view's ``base`` to ``view`` itself (its own
        # base is no array), so every slice or memoryview keeps it alive
        weakref.finalize(
            view, self._lib.tft_frag_release, self._handle, ptr
        ).atexit = False
        return view

    def stage(
        self, step: int, resource: str, value, digest: "Optional[str]" = None
    ) -> "Optional[int]":
        """Mirror one raw wire-bytes payload; returns the bytes COPIED to
        do so: 0 for a buffer ``reserve()`` lent for this fragment
        (committed in place), its length otherwise, ``None`` when the
        version is unknown/retired (not mirrored — Python still owns
        serving it).  ``digest``: the payload's sha256 where the stager
        knows it, what a conditional GET is held against."""
        mv = memoryview(value)
        if not mv.c_contiguous:
            return None
        arr = (
            np.frombuffer(mv, dtype=np.uint8)
            if mv.nbytes
            else np.empty(0, dtype=np.uint8)
        )
        name = resource.encode()
        sha = digest.encode() if digest else None
        # the native side knows its lends by address: anything else (and
        # a lend made for another fragment, or not handed back whole) is
        # refused there and takes the copy
        if self._lib.tft_frag_commit(
            self._handle, int(step), name, arr.ctypes.data, arr.nbytes, sha
        ) == 0:
            return 0
        rc = self._lib.tft_frag_stage(
            self._handle, int(step), name, _u8ptr(arr), arr.nbytes, sha
        )
        return arr.nbytes if rc == 0 else None

    def finish(self, step: int) -> None:
        self._lib.tft_frag_finish(self._handle, int(step))

    def retire(self, step: int) -> None:
        self._lib.tft_frag_retire(self._handle, int(step))

    def counters(self) -> "Dict[str, int]":
        from torchft_tpu import _native

        ptr = self._lib.tft_frag_counters(self._handle)
        return json.loads(_native.take_string(ptr))

    def inject(self, mode: str, param_ms: int = 0, count: int = 0) -> None:
        """Chaos hook: the next ``count`` data requests ``drop`` (close
        mid-exchange) or ``delay`` ``param_ms`` before the body."""
        rc = self._lib.tft_frag_inject(
            self._handle, mode.encode(), int(param_ms), int(count)
        )
        if rc != 0:
            raise ValueError(f"bad inject mode: {mode}")

    def shutdown(self) -> None:
        if self._handle >= 0:
            self._lib.tft_server_shutdown(self._handle)
            self._handle = -1


# ---- client-side endpoint resolution --------------------------------------
# One control round trip per base: GET /nativeport on the Python control
# server names the data port (404 = python-only node, cached; transport
# errors are NOT cached so a transient outage can't pin a peer to the
# slow path forever).

_ports_lock = threading.Lock()
_ports: "Dict[str, Optional[int]]" = {}


def reset_port_cache() -> None:
    """Test hook: forget resolved data ports (transports are ephemeral
    in-process, so a stale positive entry can otherwise outlive its
    server across test cases)."""
    with _ports_lock:
        _ports.clear()


def _drop_port(base: str) -> None:
    """Invalidate one cached data-port mapping (the peer restarted, or
    an ephemeral-port collision aliased a dead native server onto a new
    transport's control port) — the next fetch re-resolves."""
    with _ports_lock:
        _ports.pop(base, None)


def _resolve_port(base: str, timeout: float) -> "Optional[int]":
    with _ports_lock:
        if base in _ports:
            return _ports[base]
    u = urlparse(base)
    port: "Optional[int]" = None
    cache = False
    try:
        conn = http.client.HTTPConnection(
            u.hostname or "127.0.0.1",
            u.port or 80,
            timeout=max(timeout, 0.05),
        )
        try:
            conn.request("GET", "/nativeport")
            resp = conn.getresponse()
            body = resp.read()
            cache = True  # a definitive control-plane answer either way
            if resp.status == 200:
                port = int(body.strip() or b"0") or None
        finally:
            conn.close()
    except (OSError, ValueError, http.client.HTTPException):
        port = None
    if cache:
        with _ports_lock:
            if len(_ports) > 4096:
                _ports.clear()
            _ports[base] = port
    return port


def fetch_native(
    base: str,
    version: int,
    resource: str,
    timeout: float,
    unless: "Optional[str]" = None,
) -> "Optional[Tuple[Optional[np.ndarray], str, float]]":
    """Try the native data plane for one raw fragment GET.

    Returns ``(pooled uint8 buffer, sha256 hex, first_byte_seconds)`` on
    success; ``None`` when the caller should fall back to the Python
    path (peer has no native server, the fragment isn't mirrored there,
    or the data connection failed — a transport error also invalidates
    the cached port so a stale mapping cannot pin the slow path).
    With ``unless`` (a sha256 hex) the GET is conditional: a source that
    staged the fragment under that digest answers "same" with no body,
    and the buffer returned is ``None``.
    Raises :class:`StillStreaming` (an ``HTTPError`` 503) for
    retryable-busy (the cut-through long-poll contract) — exactly the
    exception surface the fragment retry policy already handles."""
    port = _resolve_port(base, timeout)
    if port is None:
        return None
    lib = _native_lib()
    u = urlparse(base)
    addr = f"{u.hostname or '127.0.0.1'}:{port}".encode()
    n = ctypes.c_int64(0)
    fb = ctypes.c_double(0.0)
    timeout_ms = max(int(timeout * 1000), 1)
    rc = lib.tft_frag_fetch_begin(
        addr,
        int(version),
        resource.encode(),
        timeout_ms,
        unless.encode() if unless else None,
        ctypes.byref(n),
        ctypes.byref(fb),
    )
    if rc == 503:
        # the native server answers 503 for a streaming version alone
        raise StillStreaming(
            f"{base}/checkpoint/{version}/{resource}",
            "native fragment still streaming",
        )
    if rc == 304 and unless:
        return None, unless, float(fb.value)
    if rc < 0:
        _drop_port(base)
        return None  # transport error: Python path decides (it shares
        # the peer's fate — a live peer serves, a dead one raises the
        # URLError the retry/failover ladder already handles)
    if rc != 200:
        return None  # 404 (or anything unexpected): Python owns this one
    nbytes = int(n.value)
    buf = POOL.take(nbytes, np.uint8)
    sha = ctypes.create_string_buffer(65)
    # ctypes releases the GIL here: body receive + sha256 over the wire
    # buffer run native while other Python threads keep executing
    rc = lib.tft_frag_fetch_body(_u8ptr(buf), nbytes, sha, timeout_ms)
    if rc != 0:
        POOL.give(buf)
        _drop_port(base)
        return None  # connection died mid-body: refetch via Python
    return buf, sha.value.decode(), float(fb.value)


def copy_transposed(
    dst: np.ndarray, arr: np.ndarray, matrix: int, row: int, rows: int
) -> None:
    """Rows ``row .. row + rows`` of matrix ``matrix`` of ``arr`` (shape
    ``[..., R, C]``, memory holding each matrix column by column:
    ``fragments._stored_swapped``) into ``dst`` in C order, by the native
    tiled kernel with the interpreter's lock released.  ``dst``:
    contiguous ``uint8`` of exactly ``rows * C * itemsize`` bytes."""
    total, cols = arr.shape[-2:]
    item = arr.itemsize
    if not (
        0 <= row and 0 < rows and row + rows <= total
        and 0 <= matrix < arr.size // (total * cols)
        and dst.dtype == np.uint8 and dst.flags.c_contiguous
        and dst.nbytes == rows * cols * item and dst.flags.writeable
    ):
        raise ValueError("copy_transposed: rows or destination out of range")
    rc = _native_lib().tft_copy_transposed(
        dst.ctypes.data,
        arr.ctypes.data + (matrix * total * cols + row) * item,
        rows, cols, total, item,
    )
    if rc != 0:
        raise ValueError(f"copy_transposed: no kernel for items of {item} B")


def native_sha256(buf) -> "Optional[str]":
    """sha256 hex of one buffer via the native kernel (GIL released), or
    None when the native library is unavailable."""
    if not available():
        return None
    mv = memoryview(buf)
    if not mv.c_contiguous:
        return None
    arr = (
        np.frombuffer(mv, dtype=np.uint8)
        if mv.nbytes
        else np.empty(0, dtype=np.uint8)
    )
    out = ctypes.create_string_buffer(65)
    if _native_lib().tft_sha256_hex(_u8ptr(arr), arr.nbytes, out) != 0:
        return None
    return out.value.decode()
