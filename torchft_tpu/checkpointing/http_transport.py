"""HTTP checkpoint transport: pull-based live weight streaming.

Analog of the reference HTTP transport
(reference: torchft/checkpointing/http_transport.py:73-299): each worker runs
a daemon HTTP server; ``send_checkpoint`` stages the state dict (host copies)
under an RWLock and serves ``GET /checkpoint/{step}/{full|metadata|chunk_i}``;
receivers fetch the full stream or parallel-fetch round-robin chunks with a
thread pool.  The RWLock guarantees the staged snapshot cannot be replaced
mid-serve; ``disallow_checkpoint`` retires it before the optimizer mutates
parameters.

Striped heal (ISSUE 15, docs/architecture.md "Striped heal"): heal
snapshots can instead stage as a cut-through fragment stream
(``send_checkpoint_streamed`` — header first, digest manifest last) and
a healer stripes disjoint fragment ranges across every max-step quorum
peer (``recv_checkpoint_striped`` — from the header on, while the sources
encode; per-fragment failover; a fragment it holds already asked for
"unless it hashes to mine"; decode overlapping wire into retained
``into=`` buffers; the manifest last, and everything held against it),
all over the shared fragment plane (``checkpointing/fragments.py``).
"""

from __future__ import annotations

import logging
import socket
import threading
import time
import urllib.error
import urllib.request
from concurrent.futures import Future, ThreadPoolExecutor
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Callable, List, Optional

from torchft_tpu.checkpointing import fragdata as _fragdata
from torchft_tpu.checkpointing import serialization as ser
from torchft_tpu.checkpointing.transport import CheckpointTransport
from torchft_tpu.utils import faults as _faults
from torchft_tpu.utils import flightrecorder as _flightrec
from torchft_tpu.utils import metrics as _metrics
from torchft_tpu.utils import tracing as _tracing
from torchft_tpu.utils.retry import RetryPolicy
from torchft_tpu.utils.rwlock import RWLock

logger = logging.getLogger(__name__)

# Checkpoint fetch retry: the healer and the sender learn the quorum
# simultaneously, so the sender may still be device->host staging the
# snapshot — poll through retryable 503s (and connection errors during a
# sender restart) with jittered backoff until the receiver's deadline.
# Permanent failures (404 bad path / chunk range) fail immediately.
#: Staged-snapshot slots kept live at once (heal steps + reshard epochs);
#: oldest-inserted evicts first.  4 covers a heal and a reshard in flight
#: plus one superseded generation of each.
_MAX_STAGED = 4

#: Per-fragment budget on NON-primary stripe sources: a dead/silent/unstaged
#: peer costs a heal at most this before its fragments fail over (the
#: primary and the last survivor get the whole remaining deadline).  A
#: source that is still staging the version renews it with every
#: "streaming, not yet" it answers (``fragments.striped_fetch``).
HEAL_FAILOVER_S = 2.0

_FETCH_POLICY = RetryPolicy(
    name="transport.http.fetch",
    base_delay=0.05,
    multiplier=2.0,
    max_delay=1.0,
    retry_if=lambda e: (
        e.code == 503
        if isinstance(e, urllib.error.HTTPError)
        else isinstance(e, (urllib.error.URLError, ConnectionError, OSError))
    ),
)


class _Staged:
    """One staged snapshot slot.

    ``complete=False`` is the serving tier's CUT-THROUGH state: the
    document is still streaming in fragment by fragment
    (``stage_streamed_part``).  While incomplete, a missing ``frag_*``
    resource is a retryable 503 (the child/client polls until the relay
    stages it — that IS the cut-through overlap) and whole-document
    resources (``full``/``metadata``/``chunk_*``) 503 too: a torn
    version must never serve.  ``pooled`` tracks the buffers this slot
    owns; on retirement a bufpool-backed one returns to the pool, and a
    view lent by the native data server (which the pool declines: it owns
    no memory) is let go of, to be recycled there.

    ``grace``: streamed HEAL slots hold serialized BYTES — immutable
    copies, unlike the legacy host-array snapshot that aliases the live
    optimizer state — so they may legally outlive the step commit.  A
    positive grace survives that many ``disallow_checkpoint`` rounds
    before retiring, which keeps a striped healer's multi-request fetch
    window open across the sources' commit instead of tearing it at the
    first fast peer's ``should_commit``.

    ``digests``: the sha256 of a raw part where its stager gave one (a
    heal source knows each fragment's at the moment it stages it): what a
    conditional fragment GET (``If-None-Match``) is held against.
    """

    __slots__ = ("sd", "num_chunks", "complete", "pooled", "grace", "digests")

    def __init__(
        self,
        sd: Any,
        num_chunks: int = 1,
        complete: bool = True,
        grace: int = 0,
    ):
        self.sd = sd
        self.num_chunks = num_chunks
        self.complete = complete
        self.pooled: "List[Any]" = []
        self.grace = grace
        self.digests: "dict[str, str]" = {}

    def release(self) -> None:
        from torchft_tpu.utils.bufpool import POOL

        for buf in self.pooled:
            POOL.give(buf)
        self.pooled = []


class _HTTPServerIPv6(ThreadingHTTPServer):
    address_family = socket.AF_INET6
    daemon_threads = True


def _make_server() -> ThreadingHTTPServer:
    # IPv6 dual-stack when available (reference: torchft/http.py:5-7).
    try:
        return _HTTPServerIPv6(("::", 0), _Handler)
    except OSError:
        return ThreadingHTTPServer(("0.0.0.0", 0), _Handler)


class _Handler(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"
    # Idle keep-alive reap: persistent fetcher connections (serving tier,
    # serving/fetcher.py) would otherwise pin one server thread each for
    # the life of the client; a timed-out WAIT for the next request
    # closes the connection.  Scoped to the between-requests wait only
    # (re-armed below, disarmed before serving): an in-flight response
    # body — a multi-GB heal stream stalling on a congested link — must
    # block like it always did, not die at the idle timeout.
    timeout = 30.0
    transport: "HTTPTransport"  # injected per-server subclass attr

    def handle_one_request(self) -> None:
        self.connection.settimeout(self.timeout)
        super().handle_one_request()

    def log_message(self, fmt: str, *args: Any) -> None:  # quiet
        logger.debug("http: " + fmt, *args)

    def _retry_later(self, message: str, streaming: bool = False) -> None:
        # Retryable 503 WITHOUT closing the connection (``send_error``
        # sends ``Connection: close``): the cut-through pollers re-ask
        # the same keep-alive connection every few ms — a reconnect per
        # poll would dominate the poll itself at WAN RTTs.
        # ``streaming``: this node IS staging the version and has not
        # reached the fragment; marked, so that a striped healer can tell
        # it from the 503 of a node that has staged nothing.
        body = message.encode("utf-8", "replace")
        self.send_response(503, "retry later")
        if streaming:
            self.send_header(_fragdata.STREAMING_HEADER, "1")
        self.send_header("Content-Type", "text/plain")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        try:
            self.wfile.write(body)
        except BrokenPipeError:
            pass

    def _asked_unless(self) -> "Optional[str]":
        """The digest of a conditional fragment GET (``If-None-Match``:
        "send it unless it hashes to this"), or None."""
        cond = self.headers.get("If-None-Match")
        return (cond.strip(' "') or None) if cond else None

    def _send_same(self, step: int, what: str, digest: str) -> None:
        """Answer a conditional fragment GET whose digest is the staged
        fragment's: 304, and no body crosses the wire."""
        t0_ns = time.time_ns()
        self.send_response(304)
        self.send_header("ETag", f'"{digest}"')
        self.end_headers()
        _flightrec.record(
            "checkpoint.http.send", start_ns=t0_ns, step=step,
            bytes=0, resource=what, same=1,
        )

    def _send_bytes(self, body: bytes, content_type: str) -> None:
        self.send_response(200)
        self.send_header("Content-Type", content_type)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        try:
            self.wfile.write(body)
        except BrokenPipeError:
            pass

    def _serve_store_catalog(self, transport: "HTTPTransport") -> None:
        """``/store/versions``: this rank's durable-store restore
        inventory (version -> cut id, fragment list, digest-valid
        fragments) for fleet-wide cold-start cut selection."""
        import json

        store = transport._store
        if store is None:
            self.send_error(404, "no durable store attached")
            return
        try:
            body = json.dumps(store.catalog()).encode()
        except Exception as e:
            self.send_error(503, f"store catalog unavailable: {e}")
            return
        self._send_bytes(body, "application/json")

    def _serve_from_store(
        self, transport: "HTTPTransport", step: int, what: str
    ) -> bool:
        """Serve a ``frag_*`` resource for a version that is NOT
        RAM-staged from the attached durable store.  Returns True when a
        response (200 or permanent 404) was written; False falls through
        to the retryable 503 (the version may simply be staging late).

        Called under the staged read lock — disk reads are local and
        bounded, and the lock is writer-priority so stagers stay live.
        """
        from torchft_tpu.checkpointing import fragments as frags

        store = transport._store
        if store is None or not what.startswith("frag_"):
            return False
        name = what[len("frag_"):]
        t0_ns = time.time_ns()
        if name == frags.MANIFEST_FRAG:
            body = store.manifest_bytes(step)
            if body is None:
                return False
        elif name == frags.HEADER_FRAG:
            manifest = store.manifest(step)
            if manifest is None:
                return False
            body = ser.serialize(
                {k: v for k, v in manifest.items() if k != "digests"}
            )
        else:
            manifest = store.manifest(step)
            if manifest is None:
                return False
            unless = self._asked_unless()
            if unless and (manifest.get("digests") or {}).get(name) == unless:
                self._send_same(step, what, unless)
                return True
            frag = store.fragment(step, name)
            if frag is None:
                # Version known but this blob is torn/missing: permanent
                # 404 so the striped restorer fails over to another disk
                # immediately instead of polling a hole.
                self.send_error(404, "fragment missing or torn on disk")
                return True
            body = frag
        self._send_bytes(body, "application/octet-stream")
        _metrics.CHECKPOINT_BYTES.labels(
            transport="http", direction="send"
        ).inc(len(body))
        _flightrec.record(
            "checkpoint.http.send", start_ns=t0_ns, step=step,
            bytes=len(body), resource=what, source="store",
        )
        return True

    def do_GET(self) -> None:  # noqa: N802 - http.server API
        # request received: the idle-reap timeout must not bound the
        # serve itself (see class docstring; re-armed per request above)
        self.connection.settimeout(None)
        transport = self.server.transport  # type: ignore[attr-defined]
        parts = self.path.strip("/").split("/")
        # /store/versions — the durable store's restore catalog (plain
        # JSON, not a framed RPC: the wire-schema lock is untouched).
        if parts == ["store", "versions"]:
            self._serve_store_catalog(transport)
            return
        # /nativeport — native fragment data-plane discovery: 200 + port
        # when this node mirrors frag_* payloads into the C++ server,
        # 404 = python-only node.  Clients cache either definitive
        # answer (checkpointing/fragdata.py _resolve_port).
        if parts == ["nativeport"]:
            native = transport._frag_native
            if native is None:
                self.send_error(404, "no native data plane")
            else:
                self._send_bytes(str(native.port).encode(), "text/plain")
            return
        # /checkpoint/{step}/{what}
        if len(parts) != 3 or parts[0] != "checkpoint":
            self.send_error(404, "unknown path")
            return
        try:
            step = int(parts[1])
        except ValueError:
            self.send_error(400, "bad step")
            return
        what = parts[2]
        if what.startswith("frag_"):
            # Cut-through long-poll: when the step is STREAMING in and
            # this fragment hasn't landed yet, block briefly server-side
            # until the relay stages it — a child's fragment request
            # then costs one round trip, not a client poll loop whose
            # backoff would add dead time between fragment arrivals.
            # Returns immediately for complete/absent steps (those take
            # the plain 404/503 paths below).  Its read-lock timeout
            # maps to the same retryable busy-503 every other lock
            # timeout in this request takes, never an unhandled raise.
            # Client-driven park window (X-TFT-Poll-Ms): a cut-through
            # chain's child would rather wait here — woken the moment
            # the fragment stages — than eat a 503 + retry-ladder cycle
            # that duplicates request load exactly when the parent is
            # busiest.  Absent/garbage header keeps the 250 ms default.
            try:
                poll_ms = float(
                    self.headers.get("X-TFT-Poll-Ms") or 250.0
                )
            except (TypeError, ValueError):
                poll_ms = 250.0
            max_wait = min(max(poll_ms, 0.0), 5000.0) / 1e3
            try:
                transport.await_streamed_part(
                    step, f"frag:{what[len('frag_'):]}", max_wait=max_wait
                )
            except TimeoutError:
                self.send_error(503, "checkpoint busy")
                return
        try:
            # Hold the read lock for the whole serve so the snapshot can't be
            # retired mid-stream (reference http_transport.py:77-131).
            with transport._staged_lock.r_lock(timeout=transport._lock_timeout):
                staged = transport._staged.get(step)
                if staged is None:
                    # Not in RAM: a cold-start restorer may still be able
                    # to serve this version from the attached durable
                    # fragment store (blobs digest-verified at read; a
                    # torn blob 404s so the striped fetch fails over).
                    if self._serve_from_store(transport, step, what):
                        return
                    # Healer raced the sender's staging: retryable 503 (the
                    # receiver polls until its deadline). Permanent problems
                    # (bad path, chunk out of range) stay 404 and fail fast.
                    self._retry_later(
                        f"no checkpoint staged for step {step}"
                    )
                    return
                state_dict, num_chunks = staged.sd, staged.num_chunks
                raw: "Optional[memoryview]" = None
                if not staged.complete and not what.startswith("frag_"):
                    # A streaming (cut-through) slot serves ONLY its
                    # staged fragments: a whole-document read of a torn
                    # version must never complete — poll until finished.
                    self._retry_later(
                        f"step {step} is still streaming in"
                    )
                    return
                if what == "full":
                    indices = None
                elif what == "metadata":
                    indices = []
                elif what.startswith("chunk_"):
                    idx = int(what[len("chunk_"):])
                    chunks = ser.split_chunks(ser.num_leaves(state_dict), num_chunks)
                    if idx >= len(chunks):
                        self.send_error(404, "chunk out of range")
                        return
                    indices = chunks[idx]
                elif what.startswith("frag_"):
                    # Version-keyed fragment serving (serving/ tier): the
                    # staged doc maps "frag:<name>" to one fragment.  A
                    # fragment staged as raw wire bytes (publisher encode
                    # or relay cut-through passthrough) is served
                    # VERBATIM — no serialize pass, Content-Length is the
                    # buffer length; a decoded sub-dict takes the pytree
                    # path.  A missing name on a COMPLETE document is a
                    # permanent 404 (the staged manifest names every
                    # fragment); on a streaming document it is the
                    # retryable not-yet-relayed 503 — that poll IS the
                    # cut-through overlap.
                    key = f"frag:{what[len('frag_'):]}"
                    frag = state_dict.get(key)
                    if frag is None:
                        if not staged.complete:
                            self._retry_later(
                                f"fragment {what} of step {step} not "
                                f"relayed yet",
                                streaming=True,
                            )
                        else:
                            self.send_error(404, "unknown fragment")
                        return
                    # A conditional GET ("unless it hashes to mine"): a
                    # fragment staged under that very digest is answered
                    # "same", with no body.  Under another digest, or
                    # under none, the bytes go out as ever.
                    unless = self._asked_unless()
                    if unless and staged.digests.get(key) == unless:
                        self._send_same(step, what, unless)
                        return
                    raw = ser.raw_view(frag)
                    state_dict = frag
                    indices = None
                elif what.startswith("part_"):
                    # Reshard slice-diff serving (parallel/layout.py): the
                    # staged doc maps "for:<rank>" to the slices planned
                    # for that destination; serve exactly that sub-dict so
                    # the wire carries only the destination's missing
                    # intervals.  An empty sub-dict (nothing routed through
                    # this source) is a valid, tiny payload — NOT a 404 —
                    # so a racing fetcher can distinguish "staged, nothing
                    # for you" from "not staged yet" (503 above).
                    try:
                        part = int(what[len("part_"):])
                    except ValueError:
                        self.send_error(400, "bad part rank")
                        return
                    state_dict = state_dict.get(f"for:{part}", {})
                    indices = None
                else:
                    self.send_error(404, "unknown resource")
                    return
                # Stream straight to the socket: no materialized copy per
                # fetcher (multi-GB state dicts, N concurrent healers).
                # Raw passthrough fragments skip the serialize pass
                # entirely — the relay's verified bytes go out verbatim.
                if raw is not None:
                    total = len(raw)

                    def writer(out: Any, _raw: memoryview = raw) -> None:
                        out.write(_raw)

                else:
                    total, writer = ser.prepare(
                        state_dict, chunk_indices=indices
                    )
                self.send_response(200)
                self.send_header("Content-Type", "application/octet-stream")
                self.send_header("Content-Length", str(total))
                self.end_headers()
                t0 = time.perf_counter()
                t0_ns = time.time_ns()
                writer(self.wfile)
                _metrics.CHECKPOINT_BYTES.labels(
                    transport="http", direction="send"
                ).inc(total)
                _metrics.CHECKPOINT_DURATION.labels(
                    transport="http", direction="send"
                ).observe(time.perf_counter() - t0)
                _flightrec.record(
                    "checkpoint.http.send", start_ns=t0_ns, step=step,
                    bytes=total, resource=what,
                )
                # Distributed tracing: the healing destination sends its
                # round context as a ``traceparent`` header; the source's
                # serve lands as a heal.send span IN THE DESTINATION'S
                # TRACE — source and destination of one heal share a
                # trace (docs/observability.md "Distributed tracing").
                tracer = _tracing.get_tracer()
                if tracer is not None:
                    ctx = _tracing.TraceContext.from_traceparent(
                        self.headers.get("traceparent")
                    )
                    if ctx is not None and ctx.sampled:
                        tracer.export_span(
                            name="heal.send",
                            trace_id=ctx.trace_id,
                            parent_span_id=ctx.span_id,
                            start_ns=t0_ns,
                            end_ns=time.time_ns(),
                            attributes={
                                "transport": "http",
                                "step": step,
                                "bytes": total,
                                "resource": what,
                            },
                        )
        except TimeoutError:
            self.send_error(503, "checkpoint busy")
        except BrokenPipeError:
            pass


class HTTPTransport(CheckpointTransport[Any]):
    """Pull-based checkpoint transport over HTTP.

    Args:
        timeout: default lock/serve timeout.
        num_chunks: if > 0, receivers parallel-fetch this many round-robin
            leaf chunks; 0 fetches one full stream.
        state_dict_fn: optional callable returning a same-structure state
            dict whose numpy buffers are received into — the in-place
            warm-page fast path (PGTransport parity; cold allocations
            page-fault during recv and halve effective bandwidth).
    """

    #: This transport can serve the live-reshard slice-diff protocol
    #: (multi-slot staging + ``part_<rank>`` resources + ``resource=``
    #: fetches); parallel/layout.py gates data-moving switches on it.
    supports_reshard = True

    #: This transport can stage/receive the striped fragment heal
    #: protocol (ISSUE 15: ``send_checkpoint_streamed`` +
    #: ``recv_checkpoint_striped``); the Manager gates the streamed heal
    #: path on this being literally ``True`` so duck-typed test doubles
    #: keep the legacy whole-document path.
    supports_striped_heal = True

    def __init__(
        self,
        timeout: float = 60.0,
        num_chunks: int = 0,
        state_dict_fn: "Optional[Callable[[], Any]]" = None,
        max_staged: int = _MAX_STAGED,
    ) -> None:
        self._lock_timeout = timeout
        self._num_chunks = num_chunks
        self._state_dict_fn = state_dict_fn
        # Durable fragment store (checkpointing/store.py): when attached,
        # versions absent from RAM serve their fragments from disk —
        # cold-start restore rides the exact same frag_* resources and
        # striped fetch path as live heal.
        self._store: "Optional[Any]" = None
        # Staged-slot budget: heal/reshard transports keep the default;
        # the weight-serving tier sizes it to its version window so a
        # burst of publishes cannot retire a version clients still fetch.
        self._max_staged = max(int(max_staged), 1)
        # Staged snapshots keyed by step.  Heal staging uses the real
        # (>= 0) step and is retired per step by disallow_checkpoint();
        # live-reshard staging (parallel/layout.py) uses NEGATIVE keys
        # derived from the layout epoch so it survives the per-step heal
        # retirement until the switch commits or rolls back.  Bounded:
        # oldest slots are evicted past _MAX_STAGED.
        self._staged: "dict[int, _Staged]" = {}
        # writer_priority: staging/retirement must acquire in bounded
        # time even under a dense fetch storm (the serving tier's
        # 503-polling clients keep the read side continuously occupied —
        # a reader-preferring lock starves the stager forever).
        self._staged_lock = RWLock(timeout=timeout, writer_priority=True)
        # wakes fragment long-pollers (await_streamed_part) whenever the
        # staged set changes — never held together with _staged_lock
        self._stream_cond = threading.Condition()
        self._server = _make_server()
        self._server.transport = self  # type: ignore[attr-defined]
        self._thread = threading.Thread(
            # small poll interval: shutdown() blocks until the serve loop
            # polls, and transport teardown sits on the recovery-latency
            # critical path (default 0.5s poll = up to 0.5s per shutdown)
            target=lambda: self._server.serve_forever(poll_interval=0.05),
            name="torchft_http",
            daemon=True,
        )
        self._thread.start()
        host = socket.gethostname()
        self._address = f"http://{host}:{self._server.server_address[1]}"
        # Native zero-copy fragment DATA plane: raw ``frag:*`` staging is
        # mirrored into a C++ sidecar server (native/fragserver.cc) that
        # serves payload bytes via writev out of pooled buffers, GIL-free.
        # Python keeps every control decision — plans, manifests, staging
        # lifecycle, telemetry — and advertises the data port at
        # ``/nativeport``.  On where the library has the plane; any create
        # failure degrades this node to python-only serving (the mirror is
        # an accelerator, never a correctness dependency).
        self._frag_native: "Optional[_fragdata.FragDataServer]" = None
        if _fragdata.enabled():
            try:
                self._frag_native = _fragdata.FragDataServer()
            except Exception:
                logger.warning(
                    "native fragment data plane unavailable; "
                    "serving fragments from Python",
                    exc_info=True,
                )

    def metadata(self) -> str:
        return self._address

    def attach_store(self, store: Any) -> None:
        """Expose a durable :class:`~torchft_tpu.checkpointing.store.
        FragmentStore` through this server: peers' cold-start restores
        fetch ``frag_*`` resources of spilled versions (and the
        ``/store/versions`` catalog) exactly like a live heal."""
        self._store = store

    def send_checkpoint(
        self, dst_ranks: "List[int]", step: int, state_dict: Any, timeout: float
    ) -> None:
        _faults.check("transport.send", step=step)
        # Pull transport: stage a host snapshot; receivers fetch within their
        # own timeout. Device arrays are copied to host once here.
        import numpy as np
        import jax

        t0_ns = time.time_ns()
        host_sd = jax.tree_util.tree_map(
            lambda x: np.asarray(x) if hasattr(x, "__array__") else x, state_dict
        )
        with self._staged_lock.w_lock(timeout=timeout):
            self._put_locked(step, _Staged(host_sd, max(self._num_chunks, 1)))
        self._native_mirror_complete(step, host_sd)
        self._wake_stream_waiters()
        _flightrec.record(
            "checkpoint.http.stage", start_ns=t0_ns, step=step,
            dst_ranks=list(dst_ranks),
        )

    def _put_locked(self, step: int, staged: _Staged) -> None:
        old = self._staged.pop(step, None)
        if old is not None:
            old.release()
            self._native_retire(step)
        self._staged[step] = staged
        while len(self._staged) > self._max_staged:
            evicted = next(iter(self._staged))
            self._staged.pop(evicted).release()
            self._native_retire(evicted)

    # -- native data-plane mirror -------------------------------------
    #
    # Every mirror call is best-effort: the native server accelerates
    # raw frag_* serves, but the Python slot remains the source of truth
    # — on any mirror failure peers transparently fall back to the
    # Python data path (fragments._raw_data_plane), so these helpers
    # swallow rather than surface errors.  ``retire`` is non-blocking
    # native-side (in-flight serves recycle their buffer on last deref),
    # so calling it under the staged write lock is safe.

    def _native_retire(self, step: int) -> None:
        if self._frag_native is not None:
            try:
                self._frag_native.retire(step)
            except Exception:
                logger.debug("native frag retire failed", exc_info=True)

    def _native_begin(self, step: int) -> None:
        if self._frag_native is not None:
            try:
                self._frag_native.begin(step)
            except Exception:
                logger.debug("native frag begin failed", exc_info=True)

    def _native_stage(
        self, step: int, key: Any, value: Any, digest: "Optional[str]" = None
    ) -> int:
        """Mirror one raw part, with its digest where the stager gave one;
        returns the bytes that took a copy (0 for a buffer the native
        server lent, published where it lies)."""
        srv = self._frag_native
        if (
            srv is None
            or not isinstance(key, str)
            or not key.startswith("frag:")
        ):
            return 0
        raw = ser.raw_view(value)
        if raw is None:
            return 0  # control parts (header/manifest dicts) stay Python
        try:
            return srv.stage(
                step, "frag_" + key[len("frag:"):], raw, digest
            ) or 0
        except Exception:
            logger.debug("native frag stage failed", exc_info=True)
            return 0

    def _native_finish(self, step: int) -> None:
        if self._frag_native is not None:
            try:
                self._frag_native.finish(step)
            except Exception:
                logger.debug("native frag finish failed", exc_info=True)

    def _native_mirror_complete(self, step: int, sd: Any) -> None:
        """Mirror the raw ``frag:*`` parts of a COMPLETE document in one
        begin/stage*/finish stroke (the ``send_checkpoint`` path — e.g. a
        pre-serialized fragment document staged whole)."""
        if self._frag_native is None or not isinstance(sd, dict):
            return
        raws = [
            (k, ser.raw_view(v))
            for k, v in sd.items()
            if isinstance(k, str) and k.startswith("frag:")
        ]
        raws = [(k, r) for k, r in raws if r is not None]
        if not raws:
            return
        self._native_begin(step)
        for k, raw in raws:
            self._native_stage(step, k, raw)
        self._native_finish(step)

    # -- per-fragment (cut-through) staging ---------------------------------
    #
    # The serving tier's streaming relay (serving/replica.py, ISSUE 14)
    # stages one version FRAGMENT BY FRAGMENT: children and clients poll
    # ``frag_<name>`` and get each fragment the moment it lands (503
    # while missing), while whole-document reads 503 until the version
    # is finished — cut-through can never serve a torn version.

    def _wake_stream_waiters(self) -> None:
        with self._stream_cond:
            self._stream_cond.notify_all()

    def await_streamed_part(
        self, step: int, key: str, max_wait: float
    ) -> None:
        """Server-side fragment long-poll: block up to ``max_wait``
        while the slot for ``step`` is STREAMING and ``key`` has not
        landed.  Returns immediately for absent/complete slots and when
        the part arrives — the caller re-reads state under the lock and
        takes the normal serve/503/404 path."""
        deadline = time.monotonic() + max_wait
        while True:
            with self._staged_lock.r_lock(timeout=self._lock_timeout):
                staged = self._staged.get(step)
                if staged is None or staged.complete or key in staged.sd:
                    return
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                return
            with self._stream_cond:
                self._stream_cond.wait(min(remaining, 0.05))

    def begin_streamed_checkpoint(
        self,
        step: int,
        state_dict: Any,
        timeout: "Optional[float]" = None,
        grace: int = 1,
    ) -> None:
        """Stage an INCOMPLETE document (normally just the manifest);
        fragments arrive via :meth:`stage_streamed_part`.  ``grace``:
        ``disallow_checkpoint`` rounds the finished slot survives (see
        ``_Staged`` — streamed slots hold immutable bytes, so one round
        of grace keeps a striped healer's window open across the
        sources' commit)."""
        with self._staged_lock.w_lock(timeout=timeout or self._lock_timeout):
            self._put_locked(
                step, _Staged(dict(state_dict), 1, complete=False, grace=grace)
            )
        self._native_begin(step)
        for k, v in dict(state_dict).items():
            self._native_stage(step, k, v)
        self._wake_stream_waiters()

    def reserve_streamed_part(
        self, step: int, key: str, nbytes: int
    ) -> Any:
        """The buffer part ``key`` of streaming slot ``step`` will be
        SERVED from, handed out before its bytes exist: an uninitialized
        1-d ``uint8`` array of ``nbytes`` for the caller to write whole
        and pass to :meth:`stage_streamed_part` with ``pooled=True``,
        which then publishes it without a copy.  No reader sees it before
        that.  With the native data plane up it is a view of one of the
        native server's pooled buffers, lent until the last view of it is
        gone (``FragDataServer.reserve``); otherwise, or when the native
        server cannot lend one, a ``bufpool`` buffer.  One that is never
        staged (a torn encode) simply falls to the garbage collector."""
        import numpy as np

        from torchft_tpu.utils.bufpool import POOL

        if self._frag_native is not None and key.startswith("frag:"):
            try:
                buf = self._frag_native.reserve(
                    step, "frag_" + key[len("frag:"):], nbytes
                )
            except Exception:
                logger.debug("native frag reserve failed", exc_info=True)
                buf = None
            if buf is not None:
                return buf
        return POOL.take(nbytes, np.uint8)

    def stage_streamed_part(
        self,
        step: int,
        key: str,
        value: Any,
        pooled: bool = False,
        timeout: "Optional[float]" = None,
        digest: "Optional[str]" = None,
    ) -> int:
        """Add one part (``frag:<name>`` -> raw wire bytes) to a
        streaming slot.  ``digest``: the part's sha256 where the stager
        knows it (a heal source, of each fragment): kept beside the part
        on both planes, and a GET that asks for the part "unless it
        hashes to" that digest is answered "same", with no body.  ``pooled=True`` transfers ownership of the
        buffer to the slot: a bufpool-backed one returns to the pool on
        retirement; a view the native server lent
        (:meth:`reserve_streamed_part`) is dropped there, which ends the
        lend once no serve of the Python plane still reads it.  The slot
        serves ``value`` itself, and so does the native mirror when it
        lent the buffer; any other raw part it copies once.  Returns the
        bytes so copied.  Raises ``KeyError`` when the slot was evicted
        mid-stream (version window overrun by newer publishes)."""
        with self._staged_lock.w_lock(timeout=timeout or self._lock_timeout):
            staged = self._staged.get(step)
            if staged is None:
                raise KeyError(
                    f"streamed staging slot for step {step} was evicted"
                )
            staged.sd[key] = value
            if digest is None:
                staged.digests.pop(key, None)  # a restage under no digest
            else:
                staged.digests[key] = digest
            if pooled:
                staged.pooled.append(value)
        copied = self._native_stage(step, key, value, digest)
        self._wake_stream_waiters()
        return copied

    def finish_streamed_checkpoint(
        self, step: int, timeout: "Optional[float]" = None
    ) -> None:
        """Mark a streaming slot complete: whole-document reads serve."""
        with self._staged_lock.w_lock(timeout=timeout or self._lock_timeout):
            staged = self._staged.get(step)
            if staged is None:
                raise KeyError(
                    f"streamed staging slot for step {step} was evicted"
                )
            staged.complete = True
        self._native_finish(step)
        self._wake_stream_waiters()

    def streamed_parts(self, step: int) -> "Optional[set]":
        """Part keys of a still-streaming slot (``None`` when absent or
        already complete) — lets an interrupted relay pull RESUME from
        the fragments it already verified instead of refetching."""
        with self._staged_lock.r_lock(timeout=self._lock_timeout):
            staged = self._staged.get(step)
            if staged is None or staged.complete:
                return None
            return set(staged.sd)

    def copy_staged_part(
        self, step: int, key: str, timeout: "Optional[float]" = None
    ) -> "Optional[Any]":
        """Pooled copy of one raw part of a COMPLETE staged document
        (``None`` when absent or not raw wire bytes) — the delta relay
        pull reuses unchanged fragments from version v-1 without wire.
        A copy, not a shared reference: the source slot may retire (and
        return ITS buffer to the pool) while the new slot still serves.
        """
        import numpy as np

        from torchft_tpu.utils.bufpool import POOL

        with self._staged_lock.r_lock(timeout=timeout or self._lock_timeout):
            staged = self._staged.get(step)
            if staged is None or not staged.complete:
                return None
            raw = ser.raw_view(staged.sd.get(key))
            if raw is None:
                return None
            buf = POOL.take(len(raw), np.uint8)
            buf[:] = np.frombuffer(raw, dtype=np.uint8)
            return buf

    def send_checkpoint_streamed(
        self,
        dst_ranks: "List[int]",
        step: int,
        state_dict: Any,
        timeout: float,
        fragments: "Optional[int]" = None,
    ) -> "dict":
        """Stage a heal snapshot as a CUT-THROUGH fragment stream
        (docs/architecture.md "Striped heal"): the digest-less header
        serves immediately, each fragment serves the moment it encodes
        (a healer's striped fetch overlaps this host's snapshot/encode),
        and the digest manifest lands last.  Returns the manifest.

        The step protocol calls this instead of :meth:`send_checkpoint`
        when the transport carries the fragment protocol
        (``supports_striped_heal``); the staged document serves the same
        ``frag_*`` resources the serving tier uses, so the whole fragment fetch plane applies."""
        from torchft_tpu.checkpointing import fragments as frags

        _faults.check("transport.send", step=step)
        t0_ns = time.time_ns()
        manifest = frags.stage_heal_checkpoint(
            self, step, state_dict, fragments=fragments, timeout=timeout
        )
        _flightrec.record(
            "checkpoint.http.stage", start_ns=t0_ns, step=step,
            dst_ranks=list(dst_ranks),
            fragments=len(manifest.get("fragments", ())),
        )
        return manifest

    def recv_checkpoint_striped(
        self,
        sources: "List[str]",
        step: int,
        timeout: float,
        local_state_fn: "Optional[Callable[[], Any]]" = None,
        delta: bool = True,
        plane: str = "heal",
    ) -> "tuple[Any, dict]":
        """Striped multi-source heal receive (ISSUE 15; one path since
        ISSUE 51).

        ``plane`` names the provenance plane these transfers audit
        under: ``heal`` for live heals, ``restore`` when the sources
        are durable-store disks (the cold-start path).

        ``sources`` are transport base addresses in trust order —
        ``sources[0]`` is the quorum-assigned PRIMARY whose manifest
        defines truth; the rest are max-step peers whose bitwise-
        replicated state lets the healer stripe disjoint fragment
        ranges across every uplink at once.  Per-fragment failover: a
        dead/silent/poisoned stripe source's fragments move to the
        survivors (ultimately the primary); one that is still staging is
        neither.

        One path, in the order a source stages:

        1. The digest-less **header**, served before the source has
           encoded anything, gives the layout.
        2. The **stripe** begins at once, over every fragment, while the
           sources encode: a request for a fragment not yet staged parks
           at its source and is answered the moment it lands.  A healer
           that has state of the header's layout (``delta``, the default,
           and a local snapshot) hashes it into that layout on the one
           ``tft_heal_digest`` thread, A FRAGMENT AT A TIME, and asks for
           fragment *n* conditionally, "send it unless it hashes to my
           digest of *n*": a source that staged *n* under that digest
           answers "same" and no body crosses the wire, so rejoin wire
           scales with the update delta, not model size.  A healer with
           no such state, or ``delta=False``, sends no conditions: the
           only difference.  A source that knows no digests sends the
           bytes, and what hashes to the healer's own digest is dropped
           on arrival.
        3. Decode of fragment *i* (straight into the retained ``into=``
           leaf buffers) runs on this thread, under the wire of every
           in-flight stripe and the sources' encode of the rest.
        4. The **manifest** (every digest; staged last, so there by the
           time the stripe drains) is fetched from the primary and
           EVERYTHING is held against it: each fetched fragment's
           recorded hash, and each reused local fragment's own digest,
           must equal the primary's.  Whatever does not (a poisoned
           source's bytes, a false "same", a decode failure, a layout
           the manifest does not confirm) goes to the repair pass:
           fetched again, every fragment verified against the primary's
           digest ON RECEIPT, from the primary and the stripe sources not
           implicated.  The returned state is bitwise the primary's.

        The digest thread is joined before the call returns or raises,
        and an error in it is raised here.

        Returns ``(state_dict, info)`` where ``info`` carries the phase
        split (``phases``: ``heal_manifest``, the header's fetch;
        ``heal_wire``, from there until the state is whole, less
        ``heal_decode`` and ``heal_diff``, stretches of this thread's
        wall time within it: the busy sum of the decodes, and the stretch
        from the manifest's arrival until the digests are joined and all
        is held against it, what the digests still cost the recovery;
        ``parts``: what lies inside them, ``heal_manifest.wait`` and
        ``heal_decode.fragment``, and the digests' work where it runs,
        ``heal_diff.snapshot|hash``, which begins at the header, long
        before ``heal_diff`` does), ``mode`` (``delta``: conditions were
        sent; ``full``: none; ``legacy``), ``hidden`` (also the part
        ``heal_diff.hidden``: the seconds of digest work that had ended
        when the manifest arrived; all of it when conditions were sent,
        each request having waited for its digest, 0 otherwise or when
        the manifest did not confirm the header's layout),
        ``overlapped`` (also ``heal_wire.overlapped``, BYTES: the
        fragment bytes that had landed here when the source made its
        manifest, by the two hosts' wall clocks; near ``wire_bytes`` less
        the last fragment when the stripe ran beside the source's encode,
        0 when it began behind the manifest: a complete version, a server
        that parks nothing), fragment counts (``changed``: those whose
        bytes had to come) and wire bytes.  Each phase is a
        ``tracing.phase`` timed here, when it happens; the Manager folds
        the seconds into ``phase_times()`` and emits no span of its own
        for them.  Falls back to the legacy single-source whole-document
        fetch when the primary's staged document has no fragments
        (mixed-config fleet)."""
        import urllib.error as _uerr

        import jax

        from torchft_tpu.checkpointing import fragments as frags
        from torchft_tpu.checkpointing import provenance as _prov
        from torchft_tpu.ops.codec_pool import merged_seconds
        from torchft_tpu.utils.bufpool import POOL

        _faults.check("transport.recv", step=step)
        if not sources:
            raise ValueError("striped heal: no sources")
        primary = sources[0]
        deadline = time.monotonic() + timeout
        # one sink for the four phases and their parts; ``info`` hands
        # them back apart, by the dot
        timed: "dict[str, float]" = {}
        info: "dict[str, Any]" = {"sources": len(sources)}
        # ``digester``: the one thread the healer's own digests run on,
        # beside the stripe; joined when the block is left, however it is
        # left
        with _flightrec.track(
            "checkpoint.http.recv", step=step, src_rank=0,
            sources=len(sources),
        ) as op, ThreadPoolExecutor(
            1, thread_name_prefix="tft_heal_digest"
        ) as digester:
            local_state, into = self._build_into_map(local_state_fn)
            local_leaves = (
                jax.tree_util.tree_flatten(local_state)[0]
                if delta and local_state is not None
                else None
            )
            # opened when the manifest is in; its parts (the digests'
            # ``.snapshot`` and ``.hash``) run under it from the header on
            p_diff = _tracing.phase("heal_diff", timed)
            caller_ctx = _tracing.get_current()

            def _fetch_control(which: str) -> "dict[str, Any]":
                buf = frags.fetch_raw(
                    primary, step, f"frag_{which}",
                    timeout=max(deadline - time.monotonic(), 0.001),
                    role="heal",
                )
                try:
                    return frags.decode_manifest(buf)
                finally:
                    POOL.give(buf)

            # -- the header: the layout, staged before the source has
            # encoded anything
            header: "Optional[dict[str, Any]]" = None
            with _tracing.phase("heal_manifest", timed) as p_manifest:
                try:
                    # long-poll and retries while the source has not
                    # staged it: the healer waiting for the source
                    with _tracing.phase(".wait"):
                        header = _fetch_control(frags.HEADER_FRAG)
                except _uerr.HTTPError as e:
                    if e.code != 404:
                        raise
                    p_manifest.cancel()  # a legacy source: no split
            if header is None:
                # Source staged a legacy whole-document snapshot (mixed
                # config): take the classic path against the primary.
                result = self._recv_checkpoint(
                    0, primary, step,
                    max(deadline - time.monotonic(), 0.001),
                )
                op.update(mode="legacy")
                info.update(
                    mode="legacy", hidden=0.0, overlapped=0, phases={}
                )
                return frags.maybe_decode_heal_doc(result), info

            names = [str(n) for n in header["fragments"]]
            num_leaves = int(header["num_leaves"])

            # TORCHFT_PLAN_VERIFY: the stripe assignment is a plan —
            # validate its coverage (disjoint, exhaustive round-robin
            # leaf ranges across the resolved sources) before any
            # fragment goes on the wire.
            from torchft_tpu.analysis import plan_verify as _pv

            if _pv.enabled():
                from torchft_tpu.analysis import plan_ir as _pir

                _pv.check_live(
                    _pir.stripe_ir(sources, len(names), num_leaves,
                                   step=step)
                )

            # -- the healer's own digests, where it has state of the
            # header's layout: handed over a fragment at a time, in the
            # order the stripe asks for them, so that fragment n is asked
            # for conditionally while n + 1 is still being hashed
            mine: "dict[str, Future]" = {}
            if local_leaves is not None and len(local_leaves) == num_leaves:
                mine = {name: Future() for name in names}

                def _digests() -> None:
                    _tracing.set_current(caller_ctx)
                    error: BaseException = RuntimeError(
                        "striped heal: no digest of this fragment was taken"
                    )
                    try:
                        with _tracing.under(p_diff):
                            for name, sha in frags.iter_local_fragment_digests(
                                local_state, len(names)
                            ):
                                mine[name].set_result(sha)
                    except BaseException as e:  # noqa: BLE001 - raised at the join
                        error = e
                    for fut in mine.values():
                        if not fut.done():
                            fut.set_exception(error)

                digester.submit(_digests)

            def _unless(name: str) -> "Optional[str]":
                try:
                    return mine[name].result(
                        timeout=max(deadline - time.monotonic(), 0.0)
                    )
                except Exception:  # noqa: BLE001 - raised where they are joined
                    return None  # no condition: the bytes come

            # -- wire + decode: striped fetch across every source,
            # decode of fragment i overlapping the wire of the rest.
            decode_failed: "List[str]" = []
            leaves: "dict[int, Any]" = {}

            def _decode(name: str, buf: Any, _sha: str) -> None:
                # heal_decode is one phase a heal, the busy sum of these
                # stretches; each fragment decoded is a part span in it
                with p_decode.lap(), _tracing.phase(
                    ".fragment", fragment=name, bytes=buf.nbytes
                ):
                    _decode_into(name, buf)

            def _decode_into(name: str, buf: Any) -> None:
                try:
                    sub_into = (
                        frags.fragment_into_map(
                            name, num_leaves, len(names), into
                        )
                        if into
                        else None
                    )
                    decoded = frags.decode_fragment(buf, into=sub_into)
                    # Trust boundary: the slot keys come from fragment
                    # bytes the stripe has not verified yet — a corrupt
                    # fragment claiming FOREIGN slots could otherwise
                    # overwrite other fragments' leaves with garbage the
                    # per-fragment repair pass would never restore.
                    # Anything but exactly this fragment's round-robin
                    # slot set is a decode failure.
                    expected = set(
                        frags.fragment_slots(name, num_leaves, len(names))
                    )
                    if set(decoded) != expected:
                        raise ValueError(
                            f"fragment {name}: slots {sorted(decoded)[:4]}"
                            f"... do not match its layout"
                        )
                    leaves.update(decoded)
                except Exception:  # noqa: BLE001 - repaired below
                    # Garbage that happened to land before verification
                    # (the stripe is verified AFTER it drains): remember
                    # the fragment for the digest-verified repair pass.
                    decode_failed.append(name)
                finally:
                    POOL.give(buf)

            hidden = 0.0
            with _tracing.phase("heal_wire", timed) as p_wire:
                p_decode = _tracing.phase("heal_decode", timed)
                stats = frags.striped_fetch(
                    sources, step, names, deadline,
                    source_budget=HEAL_FAILOVER_S,
                    on_buf=_decode,
                    plane=plane,
                    unless=_unless if mine else None,
                )
                wire_bytes = stats["wire_bytes"]
                failovers = stats["failovers"]
                sources_used = set(stats["sources_used"])

                # -- the manifest, staged last: the source has finished
                # encoding by the time the stripe drains.  It defines
                # truth: every recorded hash and every reused fragment's
                # own digest is held against it.
                manifest = _fetch_control(frags.MANIFEST_FRAG)
                digests = manifest.get("digests") or {}
                made_ns = int(manifest.get("created_ns") or 0)
                overlapped = sum(
                    nbytes for _src, nbytes, at_ns in stats["landed"].values()
                    if at_ns <= made_ns
                )
                with p_diff:
                    # joined: an error in the digests is raised here
                    own = {name: fut.result() for name, fut in mine.items()}
                    if all(
                        header[k] == manifest[k]
                        for k in ("fragments", "num_leaves")
                    ):
                        # the digest work done beside the stripe: the
                        # parts that had ended when the manifest came
                        hidden = sum(
                            timed.get(k, 0.0)
                            for k in ("heal_diff.snapshot", "heal_diff.hash")
                        )
                        reused = sorted(
                            n for n in stats["same"]
                            if n in digests and own.get(n) == digests[n]
                        )
                        # what the manifest refutes: bytes of another
                        # digest, a "same" of a fragment that is not
                        refuted = (set(stats["same"]) - set(reused)) | {
                            n for n, sha in stats["hashes"].items()
                            if digests.get(n, sha) != sha
                        }
                        bad = sorted(set(decode_failed) | refuted)
                    else:
                        # a layout the manifest does not confirm: nothing
                        # taken or kept under the header's stands
                        names = [str(n) for n in manifest["fragments"]]
                        num_leaves = int(manifest["num_leaves"])
                        leaves.clear()
                        reused, refuted, bad = [], set(), list(names)
                    for name in reused:
                        for slot in frags.fragment_slots(
                            name, num_leaves, len(names)
                        ):
                            leaves[slot] = local_leaves[slot]
                _tracing.add_seconds(timed, "heal_diff.hidden", hidden)
                _tracing.add_seconds(timed, "heal_wire.overlapped", overlapped)
                if bad:
                    # Repair pass: mismatched/undecodable fragments and
                    # false "same"s are fetched again, digest-verified on
                    # receipt, from the primary and the stripe sources
                    # that neither failed nor delivered one of them; a
                    # decode failure here is terminal (bytes of the
                    # primary's digest are truth — there is nothing left
                    # to fail over to).
                    _metrics.HEAL_FRAG_FAILOVERS.inc(len(bad))
                    failovers += len(bad)
                    decode_failed.clear()
                    # who delivered each of them, or said "same" of it: the
                    # audit names it for what the manifest refuted
                    sent = {
                        name: stats["landed"].get(
                            name, (stats["same"].get(name), 0)
                        )
                        for name in bad
                    }
                    for name in sorted(refuted):
                        _prov.note_hop(
                            _prov.frag_id("heal", name), step, sent[name][0],
                            plane, verdict="mismatch", nbytes=sent[name][1],
                        )
                    suspects = stats["dead"] | {src for src, *_ in sent.values()}
                    restats = frags.striped_fetch(
                        [primary]
                        + [s for s in sources[1:] if s not in suspects],
                        step, bad, deadline,
                        digests=digests, source_budget=HEAL_FAILOVER_S,
                        on_buf=_decode, plane=plane,
                    )
                    wire_bytes += restats["wire_bytes"]
                    failovers += restats["failovers"]
                    sources_used |= set(restats["sources_used"])
                    if decode_failed:
                        raise ValueError(
                            f"striped heal: fragments {decode_failed} of "
                            f"the primary's digest failed to decode"
                        )
                # heal_wire: the loop's wall less decode and diff, and no
                # less than the wire's own busy seconds (decode is a busy
                # sum; a request parked at a source is not the wire)
                p_wire.exclude(
                    p_diff.seconds
                    + min(
                        p_decode.end(),
                        max(
                            p_wire.elapsed() - p_diff.seconds
                            - merged_seconds(stats["spans"]),
                            0.0,
                        ),
                    )
                )
            timed.setdefault("heal_decode", 0.0)  # nothing moved: no decode
            phases = {
                k: v for k, v in timed.items() if not _tracing.is_part(k)
            }
            mode = "delta" if mine else "full"
            changed = len(names) - len(reused)

            _metrics.HEAL_WIRE_BYTES.labels(mode=mode).inc(wire_bytes)
            # the gauge reports sources that DELIVERED fragments, not
            # the configured list — a degraded stripe (dead peers, all
            # bytes from the primary) must read as 1, not len(sources);
            # a delta heal that fetched nothing still talked to the
            # primary for the manifest, hence the floor of 1
            _metrics.HEAL_STRIPE_SOURCES.set(max(len(sources_used), 1))
            _metrics.HEAL_CHANGED_FRAGMENTS.set(changed)
            _metrics.CHECKPOINT_DURATION.labels(
                transport="http", direction="recv"
            ).observe(sum(phases.values()))
            state = frags.assemble(manifest, leaves)
            # provenance: the heal destination now holds every fragment
            # of this version (fetched AND delta-reused — reuse means
            # the local bytes already hash to the source digest)
            h_ms = int(manifest.get("created_ns", 0) // 1_000_000)
            for name in names:
                _prov.note_hold(
                    _prov.frag_id("heal", name), step,
                    digests.get(name, ""), version_ms=h_ms, role="heal",
                )
            info.update(
                mode=mode,
                hidden=hidden,
                overlapped=overlapped,
                fragments=len(names),
                changed=changed,
                wire_bytes=wire_bytes,
                failovers=failovers,
                sources_used=len(sources_used),
                phases=phases,
                parts={k: v for k, v in timed.items() if k not in phases},
            )
            op.update(
                mode=mode, fragments=len(names), changed=changed,
                bytes=wire_bytes, failovers=failovers,
            )
        return state, info

    def recv_checkpoint(
        self,
        src_rank: int,
        metadata: str,
        step: int,
        timeout: float,
        resource: "Optional[str]" = None,
    ) -> Any:
        """Fetch a staged snapshot from ``metadata``'s server.  With
        ``resource`` (e.g. ``part_<rank>``, the reshard slice-diff
        payload) that single resource is fetched instead of the
        full/chunked stream."""
        _faults.check("transport.recv", step=step)
        # in-flight op for the whole heal fetch: a healer wedged mid-fetch
        # shows up in the flight dump with src/step context
        with _flightrec.track(
            "checkpoint.http.recv", step=step, src_rank=src_rank,
        ):
            return self._recv_checkpoint(
                src_rank, metadata, step, timeout, resource
            )

    def _build_into_map(
        self, state_fn: "Optional[Callable[[], Any]]" = None
    ) -> "tuple[Optional[Any], Optional[dict]]":
        """Snapshot the local state and build the ``{global leaf slot:
        ndarray}`` in-place receive map for ``serialization.deserialize_from``
        (the warm-buffer fast path — cold allocations page-fault during
        the socket reads and roughly halve effective recv bandwidth).

        Only the user-supplied state callable may fail (it is arbitrary
        training code); that fallback is LOUD — logged and counted in
        ``torchft_heal_into_fallbacks_total`` — because silently decoding
        into fresh arrays every heal is a decode-path perf regression,
        not a benign default.  Returns ``(state, into)``, both ``None``
        when no state callable is available."""
        import jax
        import numpy as np

        fn = state_fn if state_fn is not None else self._state_dict_fn
        if fn is None:
            return None, None
        try:
            state = fn()
        except Exception as e:  # noqa: BLE001 - user state fn, but LOUD
            logger.warning(
                "heal recv: state_dict_fn failed (%s: %s); decoding into "
                "freshly allocated arrays this heal",
                type(e).__name__, e,
            )
            _metrics.HEAL_INTO_FALLBACKS.inc()
            return None, None
        existing = jax.tree_util.tree_flatten(state)[0]
        into = {
            i: leaf
            for i, leaf in enumerate(existing)
            if isinstance(leaf, np.ndarray)
        }
        return state, into

    def _recv_checkpoint(
        self,
        src_rank: int,
        metadata: str,
        step: int,
        timeout: float,
        resource: "Optional[str]" = None,
    ) -> Any:
        base = f"{metadata}/checkpoint/{step}"
        deadline = time.monotonic() + timeout
        t_recv = time.perf_counter()

        _state, into = self._build_into_map()

        # Trace propagation: the destination's round context rides a
        # ``traceparent`` header so the SOURCE's serve spans join this
        # replica's per-step trace (None when tracing is off/unsampled).
        traceparent = _tracing.current_traceparent()

        def fetch(path: str):
            # Retry/backoff policy: _FETCH_POLICY (module top) — retryable
            # 503s and connection errors poll until the receiver's deadline.
            def attempt(budget: "Optional[float]"):
                t = max(budget if budget is not None else 0.001, 0.001)
                req = urllib.request.Request(
                    f"{base}/{path}",
                    headers=(
                        {"traceparent": traceparent} if traceparent else {}
                    ),
                )
                with urllib.request.urlopen(req, timeout=t) as resp:
                    _metrics.CHECKPOINT_BYTES.labels(
                        transport="http", direction="recv"
                    ).inc(int(resp.headers.get("Content-Length") or 0))
                    return ser.deserialize_from(resp, into=into)

            return _FETCH_POLICY.run(
                attempt,
                timeout=max(deadline - time.monotonic(), 0.001),
                op="transport.http.fetch",
                on_retry=lambda e, n, d: _metrics.CHECKPOINT_RETRIES.labels(
                    transport="http"
                ).inc(),
            )

        def _done() -> None:
            _metrics.CHECKPOINT_DURATION.labels(
                transport="http", direction="recv"
            ).observe(time.perf_counter() - t_recv)

        if resource is not None:
            skeleton, leaves, n = fetch(resource)
            _done()
            return ser.reassemble(skeleton, leaves, n)

        if self._num_chunks <= 0:
            skeleton, leaves, n = fetch("full")
            _done()
            return ser.reassemble(skeleton, leaves, n)

        # Parallel chunk fetch (reference http_transport.py:244-267).
        with ThreadPoolExecutor(max_workers=self._num_chunks) as pool:
            results = list(pool.map(fetch, [f"chunk_{i}" for i in range(self._num_chunks)]))
        _done()
        skeleton, _, n = results[0]
        merged: dict = {}
        for _, leaves, _ in results:
            merged.update(leaves)
        return ser.reassemble(skeleton, merged, n)

    def disallow_checkpoint(self) -> None:
        """Retire heal snapshots (real, >= 0 step keys) before the
        optimizer mutates parameters.  Reshard staging (negative keys)
        stays until its switch commits/rolls back — peers may still be
        mid-fetch when this group's step commits.  Streamed heal slots
        with remaining ``grace`` survive (they hold immutable serialized
        bytes, not aliases of the live state — see ``_Staged``); each
        call burns one grace round so nothing lingers unbounded."""
        retired: "List[int]" = []
        with self._staged_lock.w_lock(timeout=self._lock_timeout):
            for k in [k for k in self._staged if k >= 0]:
                staged = self._staged[k]
                if staged.grace > 0:
                    staged.grace -= 1
                    continue
                self._staged.pop(k).release()
                retired.append(k)
        for k in retired:
            self._native_retire(k)
        self._wake_stream_waiters()

    def retire_checkpoint(self, step: int) -> None:
        """Drop one staged snapshot (the reshard slots' explicit
        retirement path); no-op when absent."""
        with self._staged_lock.w_lock(timeout=self._lock_timeout):
            staged = self._staged.pop(step, None)
            if staged is not None:
                staged.release()
        self._native_retire(step)
        self._wake_stream_waiters()

    def staged_steps(self) -> "List[int]":
        """Step/version keys currently staged (insertion order — the
        eviction order).  The serving tier uses this as "which versions
        do I still hold"; tests assert retention windows with it."""
        with self._staged_lock.r_lock(timeout=self._lock_timeout):
            return list(self._staged)

    def shutdown(self, wait: bool = True) -> None:
        # The slots go first: a fragment staged in place is a view of the
        # native server's memory, lent until the last view is dropped.
        # Dropped, not released: a serve still in flight keeps its own
        # reference, and with it the memory, until it ends.
        self._staged = {}
        if self._frag_native is not None:
            try:
                self._frag_native.shutdown()
            except Exception:
                pass
        self._server.shutdown()
        self._server.server_close()
        if wait:
            self._thread.join(timeout=5)
