"""Shared fragment plane: digest-manifested payloads + pipelined fetches.

One fragment data path used by BOTH consumers of bulk weight movement
(ISSUE 15 promoted it out of ``serving/`` so live healing could ride it
too; ``serving/payload.py`` and ``serving/fetcher.py`` remain as thin
aliases):

- the **weight-serving tier** (``serving/``): versioned payload docs,
  cut-through relays, delta client fetches;
- the **heal path** (``checkpointing/http_transport.py`` +
  ``manager.py``): a stale replica stripes disjoint fragment ranges
  across every max-step quorum peer in parallel, verifies each fragment
  against the primary source's manifest digest, and — on a transient
  rejoin — fetches only the fragments whose digest differs from its own
  state (docs/architecture.md "Striped heal").

A payload/heal document is one staged checkpoint-transport document:

.. code-block:: text

    {
      "frag:header":   {version, wire, fragments, skeleton, num_leaves}   (heal only; staged FIRST)
      "frag:manifest": {header fields + digests, created_ns}              (staged last on the heal path)
      "frag:0": <serialized fragment wire bytes>,
      ...
    }

Every fragment is independently fetchable via the transport's
``frag_<name>`` resource.  Fragments are stored (and staged, and
relayed) as the **serialized wire stream itself**
(``checkpointing/serialization.py`` format), and the digest is the
sha256 of exactly those bytes: any node can verify a fragment on receipt
and re-serve it **verbatim** — zero decode passes — and replicas holding
bitwise-identical state produce bitwise-identical fragments by
construction, which is what makes cross-peer striped fetches safe.  A
fragment may appear as ``bytes`` (the serving publisher's encoder
output), a ``uint8`` ndarray (bufpool-backed on fetch/relay passthrough;
on a heal source the buffer the fragment was written into once and is
served from, lent by the native data server where there is one), or a
decoded ``{slot: leaf}`` dict (tests/legacy); :func:`fragment_wire`
normalizes the raw forms.

The fetch plane (persistent per-``(thread, netloc)`` HTTP/1.1
connections, bufpool ``readinto`` receive, 503-poll retry, WAN
wire-model charging, flight/span/fault instrumentation) is shared
verbatim; callers select the telemetry identity — the serving tier uses
the ``serving.frag`` site/record/span, heal uses ``transport.heal.frag``
+ ``heal.frag``.

Leaves are optionally int8-quantized through the same per-row absmax
codec the quantized collectives use (``ops/quantization.py``): a float32
leaf becomes ``{"q8": int8 payload, "scale": f32 row scales,
"shape": [...]}``.  The heal path never quantizes — heal is bitwise.
"""

from __future__ import annotations

import hashlib
import http.client
import io
import threading
import time
import urllib.error
from collections import deque
from concurrent.futures import Future, ThreadPoolExecutor
from typing import (
    Any,
    Callable,
    Dict,
    Iterator,
    List,
    Optional,
    Set,
    Tuple,
)
from urllib.parse import urlparse

import numpy as np

from torchft_tpu.checkpointing import fragdata as _fragdata
from torchft_tpu.checkpointing import provenance as _prov
from torchft_tpu.checkpointing import serialization as ser
from torchft_tpu.utils import faults as _faults
from torchft_tpu.utils import flightrecorder as _flightrec
from torchft_tpu.utils import linkstats as _linkstats
from torchft_tpu.utils import metrics as _metrics
from torchft_tpu.utils import tracing as _tracing
from torchft_tpu.utils import wire as _wire
from torchft_tpu.utils.bufpool import POOL
from torchft_tpu.utils.retry import RetryPolicy

__all__ = [
    # payload codec
    "WIRE_F32",
    "WIRE_INT8",
    "MANIFEST_FRAG",
    "HEADER_FRAG",
    "encode_payload",
    "decode_fragment",
    "decode_manifest",
    "decode_payload",
    "assemble",
    "changed_fragments",
    "fragment_wire",
    "fragment_slots",
    "fragment_into_map",
    "verify_fragment",
    # heal-side helpers
    "heal_fragment_names",
    "iter_heal_fragments",
    "stage_heal_checkpoint",
    "iter_local_fragment_digests",
    "local_fragment_digests",
    "maybe_decode_heal_doc",
    # fetch plane
    "FragmentFetcher",
    "fetch_raw",
    "fetch_serialized",
    "close_connections",
    "striped_fetch",
    "StripeError",
    "StillStreaming",
]

WIRE_F32 = "f32"
WIRE_INT8 = "int8"

#: the manifest travels as a fragment itself so the delta path is
#: uniform: fetch ``frag_manifest``, diff digests, fetch what moved.
MANIFEST_FRAG = "manifest"

#: heal-only: the digest-less manifest prefix staged BEFORE any fragment
#: encodes, so the healer's striped fetch can start while the source is
#: still snapshotting — the full manifest (with digests) lands last.
HEADER_FRAG = "header"

_Q8_KEY = "q8"


# ---------------------------------------------------------------------------
# payload codec (digest-manifested fragment documents)
# ---------------------------------------------------------------------------


def _encode_leaf(leaf: Any, wire: str) -> Any:
    if wire != WIRE_INT8:
        return leaf
    if not isinstance(leaf, np.ndarray) and hasattr(leaf, "__array__"):
        leaf = np.asarray(leaf)
    if (
        not isinstance(leaf, np.ndarray)
        or leaf.dtype != np.float32
        or leaf.size == 0
    ):
        return leaf
    from torchft_tpu.ops import quantization as q

    # The codec's own row view (``_as_rows``: leading dim = rows, rest
    # flattened) — passing the leaf straight through keeps serving
    # payload bytes in lockstep with the collective wire bytes by
    # construction, not by a mirrored re-implementation.
    scales, payload = q.quantize(np.ascontiguousarray(leaf), q.WIRE_INT8)
    return {
        _Q8_KEY: payload,
        "scale": scales,
        "shape": np.asarray(leaf.shape, dtype=np.int64),
    }


def _decode_leaf(leaf: Any) -> Any:
    if isinstance(leaf, dict) and _Q8_KEY in leaf:
        from torchft_tpu.ops import quantization as q

        shape = tuple(int(d) for d in np.asarray(leaf["shape"]).tolist())
        return q.dequantize(
            np.asarray(leaf["scale"]),
            np.asarray(leaf[_Q8_KEY]),
            shape,
            np.dtype(np.float32),
        )
    return leaf


def fragment_wire(frag: Any) -> "Optional[memoryview]":
    """Raw wire view of a fragment in passthrough form (``bytes`` from
    the encoder, a bufpool-backed ``uint8`` ndarray on a relay/fetch);
    ``None`` for decoded/pytree fragments."""
    return ser.raw_view(frag)


class _ViewReader(io.RawIOBase):
    """Zero-copy BinaryIO over a memoryview: ``deserialize_from`` reads
    straight out of the received buffer into the final leaf arrays —
    ``io.BytesIO(raw)`` would copy the whole fragment first."""

    def __init__(self, view: memoryview) -> None:
        self._view = view
        self._off = 0

    def readable(self) -> bool:
        return True

    def readinto(self, b: Any) -> int:
        n = min(len(b), len(self._view) - self._off)
        if n:
            # numpy's copy and not a memoryview's slice assignment, which
            # holds the interpreter's lock for the whole leaf (100 ms for
            # 100 MB): a healer decodes beside its sources' encode, whose
            # pass takes the lock back for every block (PERF.md, PR 51)
            np.copyto(
                np.frombuffer(b, np.uint8, n),
                np.frombuffer(self._view, np.uint8, n, self._off),
            )
        self._off += n
        return n


def verify_fragment(name: str, frag: Any, manifest: "Dict[str, Any]") -> None:
    """Check a raw fragment against the publisher-computed sha256 in the
    manifest; raises ``ValueError`` on mismatch.  Decoded fragments (no
    raw view) and fragments the manifest carries no digest for pass —
    integrity is a property of the wire form."""
    raw = fragment_wire(frag)
    if raw is None:
        return
    want = (manifest.get("digests") or {}).get(name)
    if want is None:
        return
    # wire_digest (not hashlib directly): when the native data plane
    # landed this buffer it already digested it GIL-free — re-hashing
    # every fragment on every hop would throw that work away
    got = wire_digest(frag)
    if got != want:
        raise ValueError(
            f"serving fragment {name!r} v{manifest.get('version')}: digest "
            f"mismatch ({got[:12]} != {want[:12]}) — corrupted or torn "
            f"fragment must never be staged or served"
        )


def encode_payload(
    state_dict: Any,
    version: int,
    wire: str = WIRE_F32,
    fragments: int = 1,
) -> "Dict[str, Any]":
    """Build the staged document for one published weight version.

    ``fragments``: leaf slots are split round-robin into this many
    independently fetchable fragments (the delta unit); pass the DiLoCo
    fragment count to align delta fetches with training's sync unit.
    Fragment values are the serialized wire bytes; ``digests`` is the
    sha256 of those bytes, so relays verify and re-serve them verbatim.
    """
    import jax

    if wire not in (WIRE_F32, WIRE_INT8):
        raise ValueError(f"serving wire must be f32|int8, got {wire!r}")
    fragments = max(int(fragments), 1)
    leaves, treedef = jax.tree_util.tree_flatten(state_dict)
    skeleton = jax.tree_util.tree_unflatten(treedef, list(range(len(leaves))))
    frag_names = [str(i) for i in range(min(fragments, max(len(leaves), 1)))]
    doc: "Dict[str, Any]" = {}
    digests: "Dict[str, str]" = {}
    for name in frag_names:
        frag: "Dict[str, Any]" = {}
        for slot in fragment_slots(name, len(leaves), len(frag_names)):
            frag[str(slot)] = _encode_leaf(leaves[slot], wire)
        raw = ser.serialize(frag)
        doc[f"frag:{name}"] = raw
        digests[name] = hashlib.sha256(raw).hexdigest()
    doc[f"frag:{MANIFEST_FRAG}"] = {
        "version": int(version),
        "wire": wire,
        "fragments": frag_names,
        "digests": digests,
        "skeleton": skeleton,
        "num_leaves": len(leaves),
        "created_ns": time.time_ns(),
    }
    return doc


def decode_fragment(
    frag: Any, into: "Optional[Dict[int, np.ndarray]]" = None
) -> "Dict[int, Any]":
    """Decode one fragment (raw wire bytes or an already-deserialized
    sub-dict) into ``{GLOBAL leaf slot: decoded leaf}``.

    ``into`` maps the fragment's LOCAL leaf slots (its own flatten
    order — build it with :func:`fragment_into_map`) to arrays received
    **in place** (the heal path's warm retained buffers,
    ``serialization.deserialize_from`` semantics); inapplicable slots
    fall back to fresh arrays."""
    raw = fragment_wire(frag)
    if raw is not None:
        skeleton, leaves, n = ser.deserialize_from(
            _ViewReader(raw), into=into
        )
        frag = ser.reassemble(skeleton, leaves, n)
    return {int(slot): _decode_leaf(leaf) for slot, leaf in frag.items()}


def fragment_slots(
    name: str, num_leaves: int, num_fragments: int
) -> "List[int]":
    """GLOBAL leaf slots belonging to fragment ``name`` — the one
    round-robin layout rule (``serialization.split_chunks``) every
    producer/consumer of the fragment plane shares."""
    return ser.split_chunks(num_leaves, num_fragments)[int(name)]


def fragment_into_map(
    name: str,
    num_leaves: int,
    num_fragments: int,
    into: "Dict[int, np.ndarray]",
) -> "Dict[int, np.ndarray]":
    """Remap a GLOBAL-slot ``into`` buffer map onto fragment ``name``'s
    LOCAL leaf slots, for :func:`decode_fragment`'s in-place receive.

    A fragment serializes as the sub-dict ``{str(global_slot): leaf}``;
    jax's dict flatten orders keys LEXICOGRAPHICALLY, so the fragment's
    local slot *i* is the *i*-th key in sorted-string order — not the
    numeric order the round-robin assignment suggests."""
    keys = sorted(
        str(s) for s in fragment_slots(name, num_leaves, num_fragments)
    )
    return {
        i: into[int(k)] for i, k in enumerate(keys) if int(k) in into
    }


def decode_manifest(raw: Any) -> "Dict[str, Any]":
    """Decode a raw ``frag_manifest`` (or ``frag_header``) fetch into
    the manifest dict."""
    view = fragment_wire(raw)
    skeleton, leaves, n = ser.deserialize_from(
        _ViewReader(view) if view is not None else io.BytesIO(raw)
    )
    manifest = ser.reassemble(skeleton, leaves, n)
    if not isinstance(manifest, dict) or "fragments" not in manifest:
        raise ValueError("serving fetch: frag_manifest is not a manifest")
    return manifest


def changed_fragments(
    manifest: "Dict[str, Any]", prev_manifest: "Optional[Dict[str, Any]]"
) -> "List[str]":
    """Fragment names whose digest differs from ``prev_manifest`` (all of
    them when there is no previous version or the shape changed)."""
    names = list(manifest["fragments"])
    if prev_manifest is None or prev_manifest.get("num_leaves") != manifest.get(
        "num_leaves"
    ):
        return names
    prev = prev_manifest.get("digests") or {}
    return [n for n in names if manifest["digests"].get(n) != prev.get(n)]


def assemble(
    manifest: "Dict[str, Any]", leaves: "Dict[int, Any]"
) -> Any:
    """Rebuild the state dict from a complete ``{slot: decoded leaf}``
    map and the manifest skeleton (the tail of :func:`decode_payload`,
    split out so pipelined fetchers can merge leaves incrementally)."""
    import jax

    n = int(manifest["num_leaves"])
    missing = [i for i in range(n) if i not in leaves]
    if missing:
        raise ValueError(
            f"serving payload v{manifest.get('version')}: missing leaf "
            f"slots {missing[:5]}{'...' if len(missing) > 5 else ''} "
            f"(delta fetch without a complete previous version?)"
        )
    return jax.tree_util.tree_map(
        lambda slot: leaves[slot], manifest["skeleton"]
    )


def decode_payload(
    doc: "Dict[str, Any]",
    prev: "Optional[Tuple[Dict[str, Any], Dict[int, Any]]]" = None,
) -> "Tuple[Any, Dict[str, Any], Dict[int, Any]]":
    """Decode a full fetched document (or a manifest + changed-fragment
    subset merged over ``prev = (prev_manifest, prev_leaves)``).

    Returns ``(state_dict, manifest, leaves)`` — keep ``(manifest,
    leaves)`` around to decode the next delta fetch.
    """
    manifest = doc[f"frag:{MANIFEST_FRAG}"]
    leaves: "Dict[int, Any]" = dict(prev[1]) if prev is not None else {}
    for name in manifest["fragments"]:
        frag = doc.get(f"frag:{name}")
        if frag is not None:
            verify_fragment(name, frag, manifest)
            leaves.update(decode_fragment(frag))
    state = assemble(manifest, leaves)
    return state, manifest, leaves


# ---------------------------------------------------------------------------
# heal-side encode: streamed staging + local digests
# ---------------------------------------------------------------------------

#: Fragments a heal checkpoint is split into (the stripe/delta unit).
#: More fragments = finer striping + finer deltas but more per-fragment
#: message overhead; healers read the count from the source's header.
DEFAULT_HEAL_FRAGMENTS = 8

#: Concurrent fragment fetches per stripe source (each rides its own
#: persistent connection).
HEAL_PARALLEL = 2


def heal_fragment_names(num_leaves: int, fragments: int) -> "List[str]":
    return [str(i) for i in range(min(max(fragments, 1), max(num_leaves, 1)))]


def _snapshot(leaves: "List[Any]", slots: "List[int]") -> "Dict[str, Any]":
    """One fragment's leaves as the writer takes them, ``{str(slot):
    leaf}``: the device leaves' one copy to the host.  A host array is laid
    out as the device held the leaf (:func:`_stored_swapped`)."""
    import jax

    return {
        str(slot): (
            np.asarray(leaves[slot])
            if isinstance(leaves[slot], jax.Array)
            else leaves[slot]
        )
        for slot in slots
    }


def _stored_swapped(arr: np.ndarray) -> "Optional[Tuple[int, int, int]]":
    """``(matrices, rows, cols)`` for an array of shape ``[..., rows,
    cols]`` whose memory holds each ``[rows, cols]`` matrix column by
    column, one matrix after another: what a TPU hands the host for a leaf
    whose last dimension is no multiple of 128 (it tiles such a leaf by the
    dimension before and ``np.asarray`` keeps the device's order, as
    strides).  ``None`` for any other order."""
    if arr.ndim < 2 or arr.size == 0:
        return None
    item = arr.itemsize
    rows, cols = arr.shape[-2:]
    lead, step = [], rows * cols * item
    for n in reversed(arr.shape[:-2]):
        lead.append(step)
        step *= n
    if arr.strides != (*reversed(lead), item, rows * item):
        return None
    return arr.size // (rows * cols), rows, cols


#: The sink's block: a leaf is copied into the serving buffer and hashed
#: this many bytes at a time, so the digest reads what the copy has just
#: written while it is still in cache — one pass over memory for both.
_SINK_BLOCK = 4 << 20


class _HashedWrite:
    """Sink of ``serialization.prepare``'s writer that feeds ``sha`` the
    stream's bytes and, given ``buf``, lands them there at the running
    offset: the same bytes, block by block, so copy and hash are one pass
    over memory.  Without ``buf`` nothing is kept (a healer's digests of
    its own state).  The copy, the update and the native re-ordering all
    release the interpreter's lock for blocks of this size: a source
    encodes beside the other groups' threads of its process.

    ``write_array`` is the writer's hook for a leaf in any order of
    memory.  One that lies as the device held it (:func:`_stored_swapped`)
    is re-ordered by the native kernel, a block of rows at a time,
    straight to where its bytes go (``buf``, or a scratch block when
    nothing is kept) — where ``np.ascontiguousarray`` writes the whole
    leaf a second time, into a temporary nobody has touched, at 0.5 GB/s
    on a v5e's host."""

    __slots__ = ("_sha", "_buf", "_off", "_scratch")

    def __init__(self, sha: Any, buf: "Optional[np.ndarray]" = None) -> None:
        self._sha, self._buf, self._off = sha, buf, 0
        self._scratch: "Optional[np.ndarray]" = None

    def _next(self, nbytes: int) -> np.ndarray:
        """Where the stream's next ``nbytes`` (a block at most) land."""
        if self._buf is not None:
            self._off += nbytes
            return self._buf[self._off - nbytes:self._off]
        if self._scratch is None:
            self._scratch = np.empty(_SINK_BLOCK, np.uint8)
        return self._scratch[:nbytes]

    def write(self, data: Any) -> None:
        src = memoryview(data)
        if not src.nbytes:
            return  # an empty leaf: frombuffer refuses an empty buffer
        if self._buf is None:
            self._sha.update(src)
            return
        src = np.frombuffer(src, dtype=np.uint8)
        for lo in range(0, src.size, _SINK_BLOCK):
            block = src[lo:lo + _SINK_BLOCK]
            dst = self._next(block.size)
            np.copyto(dst, block)
            self._sha.update(dst)

    def write_array(self, arr: np.ndarray) -> None:
        swapped = None if arr.flags.c_contiguous else _stored_swapped(arr)
        if swapped is None or not _fragdata.available():
            # as the writer hands a plain sink its leaves' bytes
            self.write(np.ascontiguousarray(arr).reshape(-1).view(np.uint8))
            return
        matrices, rows, cols = swapped
        row = cols * arr.itemsize
        step = max(_SINK_BLOCK // row, 1)  # rows a block
        for m in range(matrices):
            for r0 in range(0, rows, step):
                n = min(step, rows - r0)
                dst = self._next(n * row)
                _fragdata.copy_transposed(dst, arr, m, r0, n)
                self._sha.update(dst)


def iter_heal_fragments(
    state_dict: Any,
    fragments: "Optional[int]" = None,
    reserve: "Optional[Callable[[str, int], np.ndarray]]" = None,
) -> "Tuple[Dict[str, Any], Iterator[Tuple[str, np.ndarray, str]]]":
    """Split ``state_dict`` into heal fragments.

    Returns ``(header, iterator)`` where ``header`` is the digest-less
    manifest prefix (available BEFORE any encoding work) and the
    iterator lazily yields ``(name, wire, sha256)`` — each ``next()``
    performs that fragment's host snapshot and its ONE write, which is
    what lets the streamed staging overlap a healer's fetch of fragment
    *i* with the encode of fragment *i+1*.

    ``wire`` is a 1-d ``uint8`` array holding the fragment's serialized
    stream, byte for byte ``serialization.serialize(fragment)``, and the
    digest is the sha256 of exactly those bytes.  They are written once:
    ``serialization.prepare`` sizes the stream, ``reserve(name, nbytes)``
    hands out the buffer they will be SERVED from (a source passes its
    transport's ``reserve_streamed_part``; by default a fresh array, which
    is what the durable store writes to disk), and the writer's sink
    copies each leaf straight into it and hashes the bytes as they land
    — no ``BytesIO``, no ``bytes``, no second copy at staging.  The
    buffer is the consumer's from the yield on.

    Each step is timed as a part of whatever phase the consumer has open
    (``heal_send`` on a source): ``.snapshot``; ``.encode``, the one
    pass (sizing, copy and hash; attrs ``bytes``, ``in_place=1``; one
    span a fragment, its seconds the two stretches around the
    reservation); ``.hash``, what hashing is left outside that pass (the
    digest's finalisation).  (A healer wants its own state's digests and
    no bytes: :func:`local_fragment_digests`.)

    Heal fragments are always ``f32`` wire (bitwise — a healed replica
    must converge exactly), leaf slots split round-robin like
    :func:`encode_payload`.
    """
    import jax

    if fragments is None:
        fragments = DEFAULT_HEAL_FRAGMENTS
    leaves, treedef = jax.tree_util.tree_flatten(state_dict)
    skeleton = jax.tree_util.tree_unflatten(treedef, list(range(len(leaves))))
    names = heal_fragment_names(len(leaves), fragments)
    header: "Dict[str, Any]" = {
        "wire": WIRE_F32,
        "fragments": names,
        "skeleton": skeleton,
        "num_leaves": len(leaves),
    }

    def gen() -> "Iterator[Tuple[str, np.ndarray, str]]":
        for name in names:
            slots = fragment_slots(name, len(leaves), len(names))
            with _tracing.phase(".snapshot", fragment=name):
                frag = _snapshot(leaves, slots)
            sha = hashlib.sha256()
            p_encode = _tracing.phase(".encode", fragment=name, in_place=1)
            with p_encode.lap():
                total, writer = ser.prepare(frag)
                p_encode.attrs["bytes"] = total
            # between the stretches: the consumer times its reservation
            wire = (
                reserve(name, total)
                if reserve is not None
                else np.empty(total, np.uint8)
            )
            with p_encode.lap():
                writer(_HashedWrite(sha, wire))
            p_encode.end()
            del frag, writer
            with _tracing.phase(".hash", fragment=name, bytes=total):
                digest = sha.hexdigest()
            yield name, wire, digest

    return header, gen()


def stage_heal_checkpoint(
    transport: Any,
    step: int,
    state_dict: Any,
    fragments: "Optional[int]" = None,
    timeout: "Optional[float]" = None,
) -> "Dict[str, Any]":
    """Stage ``state_dict`` for heal as a CUT-THROUGH fragment stream.

    The digest-less header is staged first (every healer fetches it and
    starts striping at once, whether or not it has state of its own to
    compare), each fragment is staged the moment it encodes, UNDER ITS
    DIGEST, known at that moment (healer wire overlaps source
    snapshot/encode — the transport's fragment long-poll hands each one
    out one round trip after it lands, or answers a healer that asked for
    it "unless it hashes to mine" with "same" and no body), and the full
    manifest (with every digest) lands LAST, which is also what flips the
    slot complete: a healer fetches it when its stripe has drained and
    holds everything it took or kept against it.  Returns the manifest so
    the source can keep its own digests for delta bookkeeping.

    Each fragment's bytes are written once, into the buffer the
    transport will serve them from (``reserve_streamed_part``: invisible
    to readers until staged, whole), and staging publishes that buffer
    where it lies; the slot owns it from then on.  ``.stage`` times the
    reservation and the publish.  What the transport copied beyond that
    one write (its data plane could not lend a buffer, or not publish it
    in place) is counted in bytes as ``heal_send.copied``, beside the
    parts' seconds in the open phase's sink: it reads 0 when the
    mechanism is engaged."""

    def reserve(name: str, nbytes: int) -> np.ndarray:
        with _tracing.phase(".stage", fragment=name, bytes=nbytes, reserve=1):
            return transport.reserve_streamed_part(
                step, f"frag:{name}", nbytes
            )

    header, frag_iter = iter_heal_fragments(state_dict, fragments, reserve)
    header = dict(header, version=int(step))
    transport.begin_streamed_checkpoint(
        step, {f"frag:{HEADER_FRAG}": header}, timeout=timeout
    )
    digests: "Dict[str, str]" = {}
    copied = 0
    try:
        for name, raw, digest in frag_iter:
            with _tracing.phase(".stage", fragment=name, bytes=raw.nbytes):
                copied += transport.stage_streamed_part(
                    step, f"frag:{name}", raw, pooled=True, timeout=timeout,
                    digest=digest,
                )
            digests[name] = digest
    except BaseException:
        # a torn stage must never linger half-served: retire the slot so
        # healers fail over to another source instead of polling forever
        try:
            transport.retire_checkpoint(step)
        except Exception:  # noqa: BLE001 - teardown best-effort
            pass
        raise
    whole = _tracing.open_phase()
    if whole is not None and whole.sink is not None:
        _tracing.add_seconds(whole.sink, "heal_send.copied", copied)
    manifest = dict(header, digests=digests, created_ns=time.time_ns())
    transport.stage_streamed_part(
        step, f"frag:{MANIFEST_FRAG}", manifest, timeout=timeout
    )
    transport.finish_streamed_checkpoint(step, timeout=timeout)
    # provenance: the heal source is these fragments' publisher — its
    # manifest stamp is the reference clock fleet staleness compares on
    v_ms = int(manifest["created_ns"] // 1_000_000)
    for name, digest in digests.items():
        _prov.note_hold(
            _prov.frag_id("heal", name), step, digest,
            version_ms=v_ms, role="source", publisher=True,
        )
    return manifest


def iter_local_fragment_digests(
    state_dict: Any, fragments: int
) -> "Iterator[Tuple[str, str]]":
    """Hash ``state_dict`` IN PLACE (no wire bytes built, no staging) into
    the heal fragment layout, yielding ``(name, sha256)`` A FRAGMENT AT A
    TIME, in the layout's order — the delta-heal diff base: a rejoiner
    whose fragment hashes to the same digest as the source's already holds
    those bytes bitwise and skips their wire entirely, and it can say so
    of fragment *n* (a conditional GET, :func:`fetch_raw`'s ``unless``)
    while it is still hashing *n + 1*.

    Per fragment: the same host snapshot of the device leaves as
    :func:`iter_heal_fragments` takes, then ``serialization.prepare``'s
    writer streams the 8-byte length, the pickled header and the leaves'
    buffers, in wire order, into ``sha256.update`` (the source's sink with
    nothing to land the bytes in): byte for byte the digest of
    ``sha256(ser.serialize(frag))``, with nothing allocated beyond the
    snapshot and one scratch block, through which a leaf that lies as the
    device held it is re-ordered on its way into the digest.  Each fragment is timed as the parts
    ``.snapshot`` and ``.hash`` of whatever phase the consumer has open
    (``heal_diff``).  One fragment after another on the consumer's thread,
    though both parts release the interpreter's lock: a healer runs this
    beside its sources' encode and its own fetch, and three or four
    threads of it slowed the sources by 0.2-1.0 s on a v5e's host, to end
    sooner than one thread, which stays ahead of a source's pass as it is
    (PERF.md section 6, PR 42 and PR 51)."""
    import jax

    leaves = jax.tree_util.tree_flatten(state_dict)[0]
    names = heal_fragment_names(len(leaves), fragments)
    for name in names:
        with _tracing.phase(".snapshot", fragment=name):
            frag = _snapshot(
                leaves, fragment_slots(name, len(leaves), len(names))
            )
        sha = hashlib.sha256()
        with _tracing.phase(".hash", fragment=name) as p_hash:
            p_hash.attrs["bytes"], writer = ser.prepare(frag)
            writer(_HashedWrite(sha))  # nothing kept: the digest alone
        del frag, writer
        yield name, sha.hexdigest()


def local_fragment_digests(
    state_dict: Any, fragments: int
) -> "Tuple[int, Dict[str, str]]":
    """``(num_leaves, {name: sha256})``: every digest of
    :func:`iter_local_fragment_digests`, once all are taken."""
    import jax

    return (
        len(jax.tree_util.tree_flatten(state_dict)[0]),
        dict(iter_local_fragment_digests(state_dict, fragments)),
    )


def maybe_decode_heal_doc(doc: Any) -> Any:
    """Decode a whole-document fetch that turned out to be a fragment
    doc (a legacy ``full`` fetch against a source that staged the
    streamed form); any other value passes through unchanged."""
    if isinstance(doc, dict) and f"frag:{MANIFEST_FRAG}" in doc:
        state, _manifest, _leaves = decode_payload(doc)
        return state
    return doc


# ---------------------------------------------------------------------------
# fetch plane (persistent connections, bufpool receive, 503-poll retry)
# ---------------------------------------------------------------------------

# Fragment fetch retry: 503 = the version/fragment exists fleet-wide but
# this node has not staged it yet (publisher encoding, parent relay
# still streaming it — the cut-through poll) — poll within the source's
# budget.  Connection errors (server killed mid-fetch, stale keep-alive
# connection) retry here too; budget expiry surfaces so the caller fails
# over to the next source.  The backoff ceiling is deliberately LOW:
# cut-through fragments land every few ms–tens of ms, so a 0.5 s ceiling
# would add more cascade latency per hop than the fragment wire itself
# (the polls ride a kept-alive connection, so each one is cheap).


def _frag_retry_if(e: BaseException) -> bool:
    return (
        e.code == 503
        if isinstance(e, urllib.error.HTTPError)
        else isinstance(e, (urllib.error.URLError, ConnectionError, OSError))
    )


_FRAG_POLICY = RetryPolicy(
    name="serving.frag",
    base_delay=0.01,
    multiplier=1.6,
    max_delay=0.1,
    retry_if=_frag_retry_if,
)

#: the heal stripe's identity on the shared policy shape — separate so
#: ``torchft_retries_total{op}`` tells serving churn from heal churn
_HEAL_FRAG_POLICY = RetryPolicy(
    name="transport.heal.frag",
    base_delay=0.01,
    multiplier=1.6,
    max_delay=0.1,
    retry_if=_frag_retry_if,
)

def _role_identity(
    fault_site: str, record: str, policy: RetryPolicy
) -> "Tuple[str, str, RetryPolicy]":
    """One fetch role's telemetry identity; the ``fault_site=`` keyword
    is the fault-coverage pass's deferred-wiring idiom — the literal
    site names here ARE the registered injection points fetch_raw/
    fetch_serialized consult per attempt."""
    return fault_site, record, policy


#: telemetry identities per fetch role: (fault site, flight/span name,
#: retry policy).  The serving tier keeps the ISSUE-14 vocabulary; heal
#: fetches are their own site so chaos schedules can kill a stripe
#: source without touching serving traffic.
_ROLE_TELEMETRY: "Dict[str, Tuple[str, str, RetryPolicy]]" = {
    "client": _role_identity(
        fault_site="serving.frag", record="serving.frag",
        policy=_FRAG_POLICY,
    ),
    "relay": _role_identity(
        fault_site="serving.frag", record="serving.frag",
        policy=_FRAG_POLICY,
    ),
    "heal": _role_identity(
        fault_site="transport.heal.frag", record="heal.frag",
        policy=_HEAL_FRAG_POLICY,
    ),
}


def _role_telemetry(role: str) -> "Tuple[str, str, RetryPolicy]":
    return _ROLE_TELEMETRY.get(role, _ROLE_TELEMETRY["client"])


def _count_fetch_bytes(role: str, nbytes: int) -> None:
    if role == "heal":
        _metrics.CHECKPOINT_BYTES.labels(
            transport="http", direction="recv"
        ).inc(nbytes)
    else:
        _metrics.SERVING_FETCH_BYTES.labels(role=role).inc(nbytes)


def _charge_wire(base: str, nbytes: int) -> float:
    # WAN wire model (utils/wire.py): one RTT + bytes/rate of source-
    # uplink bucket debt per fetch message crossing the topology
    # boundary.  Returns the seconds charged so the link-state plane can
    # fold the modeled WAN cost into its passive goodput estimate.
    return _wire.get_shaper().charge(base, nbytes)


#: per-thread first-byte latency of the most recent _request_once, and
#: (``body_from``) the ``perf_counter`` at which that answer began (the
#: fetch planes are thread-confined, like the keep-alive connections)
_fb_local = threading.local()


def _record_link(base: str, nbytes: int, seconds: float) -> None:
    """Feed the fragment plane's passive link estimator
    (utils/linkstats.py): bytes + whole-message wall (shaper charge
    included — the modeled WAN cost IS the link cost) + first-byte
    latency (connection RTT + the shaper's modeled first-byte leg)."""
    shaper = _wire.get_shaper()
    host = _wire.source_host(base) or "unknown"
    fb = getattr(_fb_local, "seconds", 0.0) + shaper.first_byte_s(base)
    _linkstats.record(
        host,
        "fragments",
        nbytes,
        seconds,
        first_byte_s=fb,
        local=not shaper.crosses_boundary(base),
    )


#: what a conditional fragment GET and a "streaming, not yet" 503 are
#: known by, on both planes (``native/fragserver.cc`` answers the first
#: and raises the second itself, through ``fragdata.fetch_native``)
StillStreaming = _fragdata.StillStreaming

_conns = threading.local()


def _conn_cache() -> "Dict[str, http.client.HTTPConnection]":
    cache = getattr(_conns, "cache", None)
    if cache is None:
        cache = _conns.cache = {}
    return cache


def _conn_for(base: str, timeout: float) -> http.client.HTTPConnection:
    cache = _conn_cache()
    conn = cache.get(base)
    if conn is None:
        p = urlparse(base)
        conn = http.client.HTTPConnection(
            p.hostname or "127.0.0.1", p.port, timeout=timeout
        )
        cache[base] = conn
    conn.timeout = timeout
    if conn.sock is not None:
        conn.sock.settimeout(timeout)
    return conn


def _drop_conn(base: str) -> None:
    conn = _conn_cache().pop(base, None)
    if conn is not None:
        try:
            conn.close()
        except Exception:  # noqa: BLE001 - teardown best-effort
            pass


def close_connections() -> None:
    """Close THIS thread's cached keep-alive connections (tests; worker
    threads drop theirs when their executor shuts down)."""
    for base in list(_conn_cache()):
        _drop_conn(base)


def _request_once(
    base: str, path: str, timeout: float,
    extra_headers: "Optional[Dict[str, str]]" = None,
) -> http.client.HTTPResponse:
    """One GET over the cached keep-alive connection; returns the live
    200 response (the caller consumes the body), or the 304 that answers
    a request carrying ``If-None-Match`` (no body).  Raises
    ``urllib.error.HTTPError`` on anything else (503 = retryable
    not-yet-staged, drained so the connection stays reusable;
    :class:`StillStreaming` where the source says it is staging the
    version) and ``ConnectionError`` / ``OSError`` on transport
    failure."""
    conn = _conn_for(base, timeout)
    headers = dict(extra_headers) if extra_headers else {}
    traceparent = _tracing.current_traceparent()
    if traceparent:
        headers["traceparent"] = traceparent
    try:
        t0 = time.perf_counter()
        conn.request("GET", path, headers=headers)
        resp = conn.getresponse()
        # observed first-byte latency of this request (headers arrived);
        # the link-state plane adds the shaper's modeled RTT on top
        _fb_local.seconds = time.perf_counter() - t0
        if resp.status == 304 and "If-None-Match" in headers:
            resp.read()
            return resp
        if resp.status != 200:
            body = resp.read()  # drain so the connection could be reused
            if resp.will_close:
                _drop_conn(base)
            if resp.status == 503 and resp.headers.get(
                _fragdata.STREAMING_HEADER
            ):
                raise StillStreaming(
                    f"{base}{path}", body[:200].decode("utf-8", "replace")
                )
            raise urllib.error.HTTPError(
                f"{base}{path}",
                resp.status,
                body[:200].decode("utf-8", "replace") or resp.reason,
                resp.headers,
                None,
            )
        return resp
    except (OSError, http.client.HTTPException) as e:
        if isinstance(e, urllib.error.HTTPError):
            raise
        _drop_conn(base)
        if isinstance(e, OSError):
            raise
        raise ConnectionError(f"http fetch {base}{path}: {e}") from e


def _get_raw_once(
    base: str, path: str, timeout: float,
    extra_headers: "Optional[Dict[str, str]]" = None,
) -> "Optional[np.ndarray]":
    """One GET returning a POOLED uint8 buffer the caller owns; ``None``
    for the "same" that answers a conditional request."""
    resp = _request_once(base, path, timeout, extra_headers)
    if resp.status == 304:
        return None
    try:
        n = int(resp.headers.get("Content-Length") or 0)
        buf = POOL.take(n, np.uint8)
        try:
            view = memoryview(buf)
            off = 0
            while off < n:
                got = resp.readinto(view[off:])
                if not got:
                    raise ConnectionError(
                        f"http fetch {base}{path}: body ended {n - off} "
                        f"bytes short"
                    )
                off += got
        except BaseException:
            POOL.give(buf)
            raise
        if resp.will_close:
            _drop_conn(base)
        return buf
    except (OSError, http.client.HTTPException) as e:
        _drop_conn(base)
        if isinstance(e, OSError):
            raise
        raise ConnectionError(f"http fetch {base}{path}: {e}") from e


_digest_local = threading.local()


def _note_native_digest(buf: np.ndarray, sha_hex: str) -> None:
    """Remember the digest the native receive path already computed
    GIL-free over this exact buffer (one-shot, consumed by
    :func:`wire_digest` on the same thread)."""
    _digest_local.entry = (id(buf), sha_hex)


def _consume_native_digest(buf) -> "Optional[str]":
    """Pop this thread's native-computed digest for ``buf`` (or None) —
    used to HAND the digest across a thread boundary: the pipelined
    fetcher's worker consumes it here and re-notes it on the consumer
    thread so verify still skips the re-hash."""
    entry = getattr(_digest_local, "entry", None)
    if entry is not None and entry[0] == id(buf):
        _digest_local.entry = None
        return entry[1]
    return None


def wire_digest(buf) -> str:
    """sha256 hex of one wire buffer.  Reuses the digest the native
    data plane computed over this buffer as it landed (same thread, same
    object — consumed one-shot so a pool-recycled buffer can never
    inherit a stale digest); otherwise hashes here."""
    entry = getattr(_digest_local, "entry", None)
    if entry is not None and entry[0] == id(buf):
        _digest_local.entry = None
        return entry[1]
    return hashlib.sha256(memoryview(buf)).hexdigest()


def _raw_data_plane(
    base: str,
    path: str,
    version: int,
    resource: str,
    timeout: float,
    unless: "Optional[str]" = None,
) -> "Optional[np.ndarray]":
    """Route one raw fragment GET: native data plane where the library
    has it, Python HTTP otherwise and on any native miss.  The miss
    fallback is what keeps Mock transports, peers without a native
    port, and non-mirrored resources (manifests, legacy docs) working
    unchanged — and it is recorded so a fleet silently running the slow
    path shows up in the flight recorder.  ``unless``: see
    :func:`fetch_raw`; ``None`` comes back for "same"."""
    headers: "Dict[str, str]" = (
        {"If-None-Match": f'"{unless}"'} if unless else {}
    )
    if resource.startswith("frag_"):
        # Client-driven cut-through park (X-TFT-Poll-Ms): ask the server
        # to hold a not-yet-staged fragment as long as our own budget
        # allows (bounded) — parking on the server's staging wake beats
        # a 503 + retry-ladder cycle that duplicates request load.  The
        # margin keeps the park ending before our socket deadline.
        poll_ms = int(min(max(timeout * 1000 - 150, 0), 5000))
        if poll_ms > 0:
            headers["X-TFT-Poll-Ms"] = str(poll_ms)
        # Not the header: a control part, never mirrored to the native
        # server (``HTTPTransport._native_stage``), which takes a name it
        # does not hold in a streaming version for a fragment still to
        # land and parks the request, 503 after 503, until the version is
        # complete — the one resource staged to be read BEFORE that.
        if _fragdata.enabled() and resource != f"frag_{HEADER_FRAG}":
            t_asked = time.perf_counter()
            got = _fragdata.fetch_native(
                base, version, resource, timeout, unless
            )
            if got is not None:
                buf, sha_hex, first_byte_s = got
                _fb_local.seconds = first_byte_s
                _fb_local.body_from = t_asked + first_byte_s
                if buf is not None:
                    _note_native_digest(buf, sha_hex)
                return buf
            _flightrec.record(
                "fragment.native_fallback",
                step=version,
                resource=resource,
                source=base,
            )
    t_asked = time.perf_counter()
    buf = _get_raw_once(base, path, timeout, headers or None)
    # when the answer began: what came before is the source's park, not
    # the wire (``_request_once`` timed the first byte)
    _fb_local.body_from = t_asked + getattr(_fb_local, "seconds", 0.0)
    return buf


def fetch_raw(
    base: str,
    version: int,
    resource: str,
    timeout: float,
    role: str = "client",
    frag_index: "Optional[int]" = None,
    unless: "Optional[str]" = None,
    on_retry: "Optional[Callable[[BaseException, int, float], None]]" = None,
) -> "Optional[np.ndarray]":
    """Fetch one staged resource as raw wire bytes (POOLED uint8 buffer —
    the caller owns giving it back or staging it), with the 503-poll
    retry, the WAN wire-model charge, and per-fragment telemetry.

    ``unless`` (a sha256 hex) makes the GET conditional: "send it unless
    it hashes to this" (``If-None-Match``).  A source that staged the
    fragment under that digest answers "same" with no body, and ``None``
    is returned; one that staged it under another digest, or knows none
    (or not the header), sends the bytes as ever.  Parked and retried
    like any request.  ``on_retry`` sees every error the policy retries
    (``RetryPolicy.run``): a :class:`StillStreaming` among them is the
    source saying it is alive.

    ``role`` selects the telemetry identity: serving roles consult the
    ``serving.frag`` chaos site and record ``serving.frag``; ``"heal"``
    consults ``transport.heal.frag`` and records/spans ``heal.frag``
    (the striped-heal vocabulary, docs/robustness.md)."""
    site, record, policy = _role_telemetry(role)
    path = f"/checkpoint/{version}/{resource}"
    t0_ns = time.time_ns()

    def attempt(budget: "Optional[float]") -> np.ndarray:
        # Chaos INSIDE the attempt: an injected drop takes exactly the
        # broken-connection path a real one would — absorbed by this
        # policy's in-budget retries (docs/robustness.md serving.frag),
        # while raise surfaces to the caller's source-failover walk.
        _faults.check(
            site,
            step=frag_index if frag_index is not None else version,
        )
        t = max(budget if budget is not None else 0.001, 0.001)
        return _raw_data_plane(base, path, version, resource, t, unless)

    t0p = time.perf_counter()
    buf = policy.run(
        attempt, timeout=max(timeout, 0.001), op=site, on_retry=on_retry
    )
    if buf is None:
        # "same": a round trip and no payload; nothing for the wire
        # model, the link estimate or the byte counters to weigh
        _flightrec.record(
            record, start_ns=t0_ns, step=version, resource=resource,
            bytes=0, same=1, source=base, role=role,
        )
        return None
    wall_s = time.perf_counter() - t0p
    wall_s += _charge_wire(base, buf.nbytes)
    _record_link(base, buf.nbytes, wall_s)
    _count_fetch_bytes(role, buf.nbytes)
    _flightrec.record(
        record, start_ns=t0_ns, step=version, resource=resource,
        bytes=buf.nbytes, source=base, role=role,
    )
    tracer = _tracing.get_tracer()
    ctx = _tracing.get_current()
    if tracer is not None and ctx is not None and ctx.sampled:
        # the per-role span identity resolves via _ROLE_TELEMETRY; both
        # values ("serving.frag" / "heal.frag") live in allowed families
        tracer.export_span(  # tft-lint: allow(span-vocab)
            name=record,
            trace_id=ctx.trace_id,
            parent_span_id=ctx.span_id,
            start_ns=t0_ns,
            end_ns=time.time_ns(),
            attributes={
                "version": version, "resource": resource,
                "bytes": buf.nbytes, "role": role,
            },
        )
    return buf


def fetch_serialized(
    base: str,
    version: int,
    resource: str,
    timeout: float,
    role: str = "client",
) -> "Tuple[Any, Dict[int, Any], int]":
    """Fetch one resource and deserialize it STRAIGHT OFF the socket —
    the whole-payload (``full``) path: a multi-GB document lands
    directly in its final leaf buffers (serialization.py's streaming
    contract) instead of being buffered raw and copied again.  Returns
    ``(skeleton, leaves, num_leaves)``; same retry/wire/telemetry
    envelope as :func:`fetch_raw`."""
    site, record, policy = _role_telemetry(role)
    path = f"/checkpoint/{version}/{resource}"
    t0_ns = time.time_ns()

    def attempt(budget: "Optional[float]") -> "Tuple[Any, Dict[int, Any], int, int]":
        _faults.check(site, step=version)
        t = max(budget if budget is not None else 0.001, 0.001)
        resp = _request_once(base, path, t)
        nbytes = int(resp.headers.get("Content-Length") or 0)
        try:
            out = ser.deserialize_from(resp)
            resp.read()  # drain any trailer so the connection is reusable
        except BaseException as e:
            # mid-body failure: unknown remainder, the conn can't be kept
            _drop_conn(base)
            if isinstance(e, EOFError):
                # truncated stream = broken connection: retryable
                raise ConnectionError(
                    f"http fetch {base}{path}: truncated stream: {e}"
                ) from e
            raise
        if resp.will_close:
            _drop_conn(base)
        return out + (nbytes,)

    t0p = time.perf_counter()
    skeleton, leaves, n, nbytes = policy.run(
        attempt, timeout=max(timeout, 0.001), op=site
    )
    wall_s = time.perf_counter() - t0p
    wall_s += _charge_wire(base, nbytes)
    _record_link(base, nbytes, wall_s)
    _count_fetch_bytes(role, nbytes)
    _flightrec.record(
        record, start_ns=t0_ns, step=version, resource=resource,
        bytes=nbytes, source=base, role=role,
    )
    return skeleton, leaves, n


class FragmentFetcher:
    """Bounded-parallel pipelined fragment fetcher.

    ``parallel`` raw fetches ride persistent per-thread connections
    concurrently; results come back in SUBMISSION order so the consumer's
    verify/decode/stage of fragment *i* overlaps the wire of fragments *i+1..i+K*.
    """

    def __init__(
        self, parallel: int = 4, role: str = "client"
    ) -> None:
        self._parallel = parallel
        self._role = role
        self._pool: "Optional[ThreadPoolExecutor]" = None
        self._lock = threading.Lock()

    def _executor(self) -> ThreadPoolExecutor:
        with self._lock:
            if self._pool is None:
                self._pool = ThreadPoolExecutor(
                    max_workers=self._parallel,
                    thread_name_prefix="tft_serving_fetch",
                )
            return self._pool

    def fetch_raw(
        self, base: str, version: int, resource: str, timeout: float
    ) -> np.ndarray:
        return fetch_raw(base, version, resource, timeout, role=self._role)

    def fetch_stream(
        self,
        base: str,
        version: int,
        resources: "List[str]",
        deadline: float,
    ) -> "Iterator[Tuple[str, np.ndarray, Tuple[float, float]]]":
        """Pipelined raw fetches of ``resources`` from one source; yields
        ``(resource, pooled_buffer, (wire_start, wire_end))`` in
        submission order — the perf-counter interval each fetch occupied
        the wire, so the consumer can compute true (union) wire busy
        time across the concurrent in-flight window.  On failure,
        buffers still in flight are drained back to the pool and the
        error re-raised (the caller fails over to another source;
        already-yielded items stay valid and staged)."""
        if not resources:
            return
        ex = self._executor()
        pending: "deque[Tuple[str, Future]]" = deque()
        it = iter(enumerate(resources))

        def _timed(
            res: str, idx: int
        ) -> "Tuple[np.ndarray, Tuple[float, float], Optional[str]]":
            t0 = time.perf_counter()
            buf = fetch_raw(
                base, version, res,
                timeout=max(deadline - time.monotonic(), 0.001),
                role=self._role, frag_index=idx,
            )
            # the native digest is noted thread-locally on THIS worker;
            # carry it to the consumer thread so verify can reuse it
            sha = _consume_native_digest(buf)
            return buf, (t0, time.perf_counter()), sha

        def _submit_next() -> bool:
            try:
                idx, res = next(it)
            except StopIteration:
                return False
            pending.append((res, ex.submit(_timed, res, idx)))
            return True

        def _drain_pending() -> None:
            while pending:
                _res, fut = pending.popleft()
                try:
                    buf, _, _ = fut.result()
                except BaseException:  # noqa: BLE001 - already failing
                    continue
                POOL.give(buf)

        for _ in range(self._parallel):
            if not _submit_next():
                break
        try:
            while pending:
                res, fut = pending.popleft()
                try:
                    buf, span, sha = fut.result()
                except BaseException:
                    _drain_pending()
                    raise
                _submit_next()
                if sha is not None:
                    _note_native_digest(buf, sha)
                yield res, buf, span
        except GeneratorExit:
            # consumer abandoned the stream mid-flight (failover after a
            # verify failure): nothing may leak out of the pool
            _drain_pending()
            raise

    def close(self) -> None:
        with self._lock:
            pool, self._pool = self._pool, None
        if pool is not None:
            pool.shutdown(wait=False)


# ---------------------------------------------------------------------------
# striped multi-source fetch (the heal wire plane)
# ---------------------------------------------------------------------------


class StripeError(ConnectionError):
    """Every stripe source died/failed before the fragment set
    completed (the heal falls back to report_error like any other
    recovery failure)."""


class _Stripe:
    """One source's live state inside a striped fetch."""

    __slots__ = ("base", "alive", "is_primary")

    def __init__(self, base: str, is_primary: bool) -> None:
        self.base = base
        self.alive = True
        self.is_primary = is_primary


def striped_fetch(
    sources: "List[str]",
    step: int,
    names: "List[str]",
    deadline: float,
    digests: "Optional[Dict[str, str]]" = None,
    source_budget: "Optional[float]" = None,
    role: str = "heal",
    on_buf: "Optional[Callable[[str, np.ndarray, str], None]]" = None,
    plane: str = "heal",
    unless: "Optional[Callable[[str], Optional[str]]]" = None,
) -> "Dict[str, Any]":
    """Fetch ``names`` striped across ``sources`` in parallel with
    per-fragment failover.  One path for every healer; what it can put
    into a request and what a source has staged so far decide the rest.

    ``plane`` is the provenance-plane identity of these transfers
    (``heal`` for live heals, ``restore`` when the stripe sources are
    durable-store disks) — every fragment that lands (or is rejected on
    digest mismatch) appends a ``fragment.hop`` audit record.

    ``sources[0]`` is the PRIMARY (the quorum-assigned heal source —
    the one whose manifest defines truth); the rest are max-step quorum
    peers whose state is bitwise-replicated, so any fragment they serve
    must hash to the primary's digest.  Work assignment is dynamic (a
    shared work queue, :data:`HEAL_PARALLEL` concurrent fetches a source):
    faster uplinks finish more fragments, a dead/slow/poisoned source's
    fragments fail over to the survivors, and the fetch only fails when
    EVERY source has been exhausted for some fragment.

    The sources may still be STAGING the version (a heal begins at the
    header): a request for a fragment not yet encoded parks at the source
    and is answered the moment it lands.  ``source_budget`` therefore
    bounds what a non-primary source may cost WITHOUT A SIGN OF LIFE, not
    how long its encode may take: its "streaming, not yet" answer
    (:class:`StillStreaming`, when a long-poll runs out) renews the
    budget, while a refused connection, silence, or the 503 of a node that
    has staged nothing does not, and marks it dead at the bound as ever.

    ``unless(name)`` gives the caller's own digest of fragment ``name``
    (it may block until that is known; ``None``: none), and the request
    becomes conditional (:func:`fetch_raw`): a source that staged the
    fragment under that digest answers "same" and no body crosses the
    wire; ``on_buf`` is not called and the name is reported in ``same``
    with the source that said so.  Bytes that arrive all the same and
    hash to the caller's digest (a source that knows no digests) are
    given back and reported there too.  THE CALLER VERIFIES a "same"
    against the primary's manifest, as it does ``hashes``: a source may
    lie.

    With ``digests``, each fragment is verified the moment it lands
    (mismatch = dead source, fragment requeued: the repair pass of a
    heal, a restore from disks); without, the caller verifies later
    against the sha256 handed to ``on_buf`` (the manifest lands after
    the stream).

    ``on_buf(name, pooled_buffer, sha256)`` is invoked on the CALLER
    thread for each completed fragment, in arrival order — decode of
    fragment *i* overlaps the wire of every in-flight stripe and the
    sources' encode of the ones after.  Buffer ownership transfers to
    the callback.

    Returns stats: ``{"wire_bytes", "failovers", "spans", "hashes",
    "sources_used", "same", "landed", "dead"}`` — ``sources_used`` is
    the set of source addresses that actually delivered at least one
    fragment (a degraded stripe is visible as fewer used sources than
    configured), ``landed`` each delivered fragment's ``(source, bytes,
    time.time_ns() it was whole here)``, ``dead`` the sources given up
    on.
    """
    if not sources:
        raise StripeError("striped fetch: no sources")
    stripes = [_Stripe(s, i == 0) for i, s in enumerate(sources)]
    frag_index = {name: i for i, name in enumerate(names)}

    # Shared state, all guarded by ``cv``: the dynamic work queue (a
    # requeued fragment lands at the FRONT — it is the oldest debt), the
    # completed set, completed results awaiting the consumer, and the
    # last per-source error (the failure chain when everything dies).
    cv = threading.Condition()
    work: "deque[str]" = deque(names)
    done: "Set[str]" = set()
    out_q: "deque[Tuple[str, Optional[np.ndarray], str]]" = deque()
    last_err: "List[BaseException]" = []
    stopped = False
    failovers = 0
    wire_bytes = 0
    inflight = 0
    spans: "List[Tuple[float, float]]" = []
    hashes: "Dict[str, str]" = {}
    same: "Dict[str, str]" = {}
    landed: "Dict[str, Tuple[str, int, int]]" = {}
    sources_used: "Set[str]" = set()

    def _alive_locked() -> int:
        return sum(1 for s in stripes if s.alive)

    def _fail_locked(stripe: "_Stripe", name: str, e: BaseException) -> None:
        nonlocal failovers, inflight
        stripe.alive = False
        inflight -= 1
        work.appendleft(name)
        last_err.append(e)
        if _alive_locked() > 0:
            failovers += 1
            _metrics.HEAL_FRAG_FAILOVERS.inc()
        cv.notify_all()

    def _budget_locked(stripe: "_Stripe") -> float:
        # Non-primary sources are capped so a dead one costs the
        # failover bound, not the whole heal; the primary (and the last
        # stripe standing) gets the full remaining deadline — striping
        # must never make the heal LESS available than the
        # single-source path it replaced.
        remaining = deadline - time.monotonic()
        if (
            source_budget is not None
            and not stripe.is_primary
            and _alive_locked() > 1
        ):
            return min(source_budget, remaining)
        return remaining

    # the caller's per-step trace context rides into the worker threads
    # so every heal.frag span (and the traceparent header the source's
    # heal.send span joins on) lands in the healer's round trace
    caller_ctx = _tracing.get_current()

    def _worker(stripe: "_Stripe") -> None:
        nonlocal wire_bytes, inflight
        _tracing.set_current(caller_ctx)
        while True:
            with cv:
                while True:
                    if stopped or not stripe.alive or len(done) >= len(names):
                        return
                    if work:
                        name = work.popleft()
                        inflight += 1
                        break
                    # idle but not finished: a failing peer may requeue
                    cv.wait(0.02)
            # the caller's own digest of this fragment, when it has one:
            # waited for here, outside the lock (its digests come a
            # fragment at a time, in the order the queue hands them out)
            mine = unless(name) if unless is not None else None
            t0 = time.perf_counter()
            while True:
                with cv:
                    if stopped:
                        inflight -= 1
                        return
                    budget = _budget_locked(stripe)
                if budget <= 0:
                    with cv:
                        _fail_locked(
                            stripe, name,
                            TimeoutError("striped fetch: deadline expired"),
                        )
                    return
                streaming: "List[BaseException]" = []
                try:
                    buf = fetch_raw(
                        stripe.base, step, f"frag_{name}",
                        timeout=budget, role=role,
                        frag_index=frag_index[name],
                        unless=mine,
                        on_retry=lambda e, _n, _d: (
                            streaming.append(e)
                            if isinstance(e, StillStreaming) else None
                        ),
                    )
                    break
                except Exception as e:  # noqa: BLE001 - per-fragment failover
                    if streaming:
                        # within this budget the source said "streaming,
                        # not yet": it is staging the version and has not
                        # reached this fragment.  Alive: ask again
                        continue
                    with cv:
                        _fail_locked(stripe, name, e)
                    return
            fb_ms = getattr(_fb_local, "seconds", 0.0) * 1e3
            sha = wire_digest(buf) if buf is not None else mine or ""
            if buf is not None and sha == mine:
                # a source that knows no digest for it sent what the
                # caller holds already: as good as its "same"
                _prov.note_hop(
                    _prov.frag_id("heal", name), step, stripe.base, plane,
                    verdict="ok", nbytes=buf.nbytes, first_byte_ms=fb_ms,
                )
                with cv:
                    wire_bytes += buf.nbytes
                POOL.give(buf)
                buf = None
            if buf is None:
                # "same": no transfer, so no hop in the audit either
                with cv:
                    inflight -= 1
                    if not stopped and name not in done:
                        done.add(name)
                        same[name] = stripe.base
                        out_q.append((name, None, sha))
                    cv.notify_all()
                    if stopped:
                        return
                continue
            if digests is not None and digests.get(name, sha) != sha:
                # poisoned/diverged source: its bytes must never land in
                # the healed state — treat exactly like a dead source
                _prov.note_hop(
                    _prov.frag_id("heal", name), step, stripe.base, plane,
                    verdict="mismatch", nbytes=buf.nbytes,
                    first_byte_ms=fb_ms,
                )
                POOL.give(buf)
                with cv:
                    _fail_locked(
                        stripe, name,
                        ValueError(
                            f"heal fragment {name!r} from {stripe.base}: "
                            f"digest mismatch ({sha[:12]} != "
                            f"{digests.get(name, '')[:12]})"
                        ),
                    )
                return
            _prov.note_hop(
                _prov.frag_id("heal", name), step, stripe.base, plane,
                verdict="ok", nbytes=buf.nbytes, first_byte_ms=fb_ms,
            )
            with cv:
                inflight -= 1
                if stopped or name in done:
                    POOL.give(buf)
                    cv.notify_all()
                    if stopped:
                        return
                    continue
                done.add(name)
                wire_bytes += buf.nbytes
                sources_used.add(stripe.base)
                # busy from the answer's first byte on: a request parked
                # at a source that had not staged the fragment yet is the
                # source's encode, not this wire
                spans.append((
                    max(t0, getattr(_fb_local, "body_from", t0)),
                    time.perf_counter(),
                ))
                hashes[name] = sha
                landed[name] = (stripe.base, buf.nbytes, time.time_ns())
                out_q.append((name, buf, sha))
                cv.notify_all()

    threads: "List[threading.Thread]" = []
    for si, stripe in enumerate(stripes):
        for w in range(max(min(HEAL_PARALLEL, len(names)), 1)):
            t = threading.Thread(
                target=_worker, args=(stripe,),
                name=f"tft_heal_stripe{si}_{w}", daemon=True,
            )
            threads.append(t)
            t.start()

    delivered = 0
    try:
        while delivered < len(names):
            with cv:
                while not out_q:
                    # "every source failed" only once nothing is still in
                    # flight: a final fetch racing its stripe's death may
                    # yet deliver the missing fragment
                    if _alive_locked() == 0 and inflight == 0:
                        raise StripeError(
                            f"striped fetch: every source failed with "
                            f"{len(names) - delivered} fragment(s) missing"
                        ) from (last_err[-1] if last_err else None)
                    if time.monotonic() > deadline:
                        raise StripeError(
                            f"striped fetch: deadline expired with "
                            f"{len(names) - delivered} fragment(s) missing"
                        )
                    cv.wait(0.05)
                name, buf, sha = out_q.popleft()
            delivered += 1
            if buf is None:
                continue  # "same": nothing crossed, nothing to decode
            if on_buf is not None:
                on_buf(name, buf, sha)
            else:
                POOL.give(buf)
    finally:
        with cv:
            stopped = True
            cv.notify_all()
        for t in threads:
            t.join(timeout=5.0)
        # drain anything that landed after the consumer stopped
        with cv:
            while out_q:
                _name, buf, _sha = out_q.popleft()
                if buf is not None:
                    POOL.give(buf)
    return {
        "wire_bytes": wire_bytes,
        "failovers": failovers,
        "spans": spans,
        "hashes": hashes,
        "sources_used": sources_used,
        "same": same,
        "landed": landed,
        "dead": {s.base for s in stripes if not s.alive},
    }
