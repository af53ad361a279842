"""Manager: the per-worker fault-tolerance state machine.

TPU-native rebuild of the reference Manager (reference: torchft/manager.py).
Orchestrates the per-step protocol: quorum (async, overlapped with forward),
process-group reconfiguration on quorum change, live healing (send/recv of
the composite state dict), error capture, and the commit vote.

JAX-first adaptations:
- state dicts are pytrees (params/opt-state/step), not torch module dicts;
- no CUDA streams: JAX dispatch is async on its own, and the DCN collective
  layer runs host-side with Work handles; ``should_commit`` blocks on any
  outstanding recovery future instead of stream events;
- the allreduce hot path zero-fills non-participants and divides by the live
  participant count (reference manager.py:416-417,447-454) so membership
  changes never change compiled shapes — no re-jit on fail/join.

Env knobs (parity with reference manager.py:76-89):
``TORCHFT_LIGHTHOUSE`` (a single ``host:port`` or the coordination-plane
HA comma list ``h1:p,h2:p,h3:p`` — the native manager's lighthouse
client walks dead peers and follows ``NOT_LEADER`` redirects to the
current lease holder, so a replicated lighthouse needs no Manager-side
changes; docs/architecture.md "Coordination-plane HA"),
``TORCHFT_MANAGER_PORT``, ``TORCHFT_TIMEOUT_SEC``,
``TORCHFT_QUORUM_TIMEOUT_SEC``, ``TORCHFT_CONNECT_TIMEOUT_SEC``,
``TORCHFT_QUORUM_RETRIES`` (quorum RPC attempts on connection failure,
with exponential backoff + full jitter via ``utils.retry.RetryPolicy``
inside the quorum timeout budget — no longer a bare loop count).
Chaos: ``TORCHFT_FAULTS`` / ``TORCHFT_FAULTS_SEED`` (utils/faults.py)
inject failures at ``manager.quorum`` / ``manager.heal`` /
``pg.allreduce`` (docs/robustness.md).
"""

from __future__ import annotations

import concurrent.futures
import logging
import os
import socket
import threading
import time
import uuid
from concurrent.futures import ThreadPoolExecutor
from datetime import timedelta
from enum import Enum
from typing import Any, Callable, Dict, List, Optional, TypeVar, cast

import jax
import numpy as np

from torchft_tpu.checkpointing import provenance as provenance
from torchft_tpu.checkpointing import store as fragment_store
from torchft_tpu.checkpointing.transport import CheckpointTransport
from torchft_tpu.coordination import (
    ManagerClient,
    ManagerServer,
    StoreClient,
    StoreServer,
    stripe_roster,
    stripe_source_cohort,
)
from torchft_tpu.parallel.process_group import ProcessGroup, REDUCE_AVG, REDUCE_SUM
from torchft_tpu.parallel.work import Work, completed_work
from torchft_tpu.utils import faults as faults
from torchft_tpu.utils import flightrecorder as flightrec
from torchft_tpu.utils import linkstats as linkstats
from torchft_tpu.utils import metrics as metrics
from torchft_tpu.utils import tracing as tracing
from torchft_tpu.utils.env import env_float, env_int, env_str
from torchft_tpu.utils.logging import ReplicaLogger, log_event
from torchft_tpu.utils.retry import RetryPolicy
from torchft_tpu.utils.rwlock import RWLock

logger = logging.getLogger(__name__)

T = TypeVar("T")

MANAGER_ADDR_KEY = "manager_addr"
REPLICA_ID_KEY = "replica_id"

#: Canonical per-step phase vocabulary: the top-level names a
#: ``tracing.phase`` is opened under (``Manager._phase`` here, the heal
#: transport for the striped-heal split).  The quorum_duration histogram
#: labels, flight-recorder phase records, ``torchft.<name>`` profiler
#: annotations and per-phase trace spans all use these names.  The
#: tft-verify protocol model renders its counterexample traces in the same
#: vocabulary (analysis/protocol_model.MODEL_PHASE_OPS), pinned by a tier-1
#: test — add here BEFORE timing a new phase.
PROTOCOL_PHASES = (
    "quorum_wait",
    "quorum_rpc",
    "pg_configure",
    "heal_send",
    "heal_recv",
    # striped-heal receive split (ISSUE 15; order since ISSUE 51): the
    # header's fetch from the primary / the striped fragment wire from
    # there on, and within it the decode into retained buffers and, when
    # the manifest is in, what joining the local digests and holding all
    # against it still costs — heal_recv stays the umbrella total.
    "heal_manifest",
    "heal_diff",
    "heal_wire",
    "heal_decode",
    # the user's load_state_dict of a healed state (back onto the device)
    "heal_apply",
    "reshard",
    "layout_commit",
    "host_sync",
    "ring",
    "commit",
)

#: The parts of those phases, timed where the work is (the PG worker
#: thread, ``checkpointing/fragments.py``).  A name with a dot is contained
#: in the phase before the dot: ``phase_times()`` returns parts beside
#: their wholes, and whatever sums phases skips them (``tracing.is_part``).
PHASE_PARTS = (
    # ProcessGroupTCP.allreduce, on its worker thread
    "ring.queue",  # submit until the worker picks the op up
    "ring.d2h",  # held by the device→host leg: the relayout's dispatch, then a bucket's starts and its wait
    "ring.pack",  # bucket concat, pad-in of a widened leaf or a tail, copy of a host leaf
    "ring.wire",  # the wall of a bucket's two streams of 2(w-1) messages, the first header sought to the last byte in and out, less what stood still for a reduce (ring.reduce); its four parts, the receiving role's:
    "ring.wire.arrive",  # first message of the op: until the previous rank's first byte, i.e. until it reached the ring
    "ring.wire.wait",  # every later message: until it starts (the peer is in the ring, late with a chunk): a stall between two messages
    "ring.wire.recv",  # a message's header and payload coming in, slice by slice; a stall between two slices of a message is here
    "ring.wire.send",  # handing the stream to the sender thread, then what is left of it once the last byte is in
    "ring.reduce",  # the seconds the wire stood still for a reduce or a division: a one-slice chunk's between two messages, the receiver held back for scratch, the wait for the reducer once the last byte is in; attrs slices (a message) and hidden (slices reduced under the wire)
    "ring.reduce.hidden",  # no span: the seconds of reduce and division that ran on the reducer thread while the next slice was coming in
    "ring.unpack",  # cast back of a widened leaf, split, unflatten (the division is the reduce's: each rank divides its own chunk before the allgather)
    # fragments.iter_heal_fragments / stage_heal_checkpoint, per fragment,
    # on the source: each fragment's wire bytes are written ONCE
    "heal_send.snapshot",  # device leaves to host numpy
    "heal_send.encode",  # the one pass: serialization.prepare, then its writer copies each leaf into the buffer the fragment is served from and feeds sha256 the same bytes, block by block
    "heal_send.hash",  # what hashing is left outside that pass: the digest's finalisation
    "heal_send.stage",  # reserving that buffer (the native server's, lent; a bufpool one without it) and publishing it where it lies
    "heal_send.copied",  # no span: BYTES the transport copied beyond the one write; 0 when every fragment was staged in place
    # fragments.iter_local_fragment_digests, per fragment, on the transport's
    # digest thread: begun at the header, beside the stripe, under a
    # heal_diff that opens when the manifest is in, after the stripe (so
    # the parts outweigh their whole)
    "heal_diff.snapshot",
    "heal_diff.hash",  # serialization.prepare's writer into the source's sink with nothing kept: no bytes built
    "heal_diff.hidden",  # no span: of those two, the seconds ended when the manifest came
    # inside fetch_raw: long-poll until the source staged the header
    "heal_manifest.wait",
    "heal_wire.overlapped",  # no span: BYTES of fragments that had landed on the healer when the source made its manifest; 0 when the stripe began behind it
    # one per fragment decoded; heal_decode is their busy sum
    "heal_decode.fragment",
)

TIMEOUT_SEC = env_float("TORCHFT_TIMEOUT_SEC", 60.0)
QUORUM_TIMEOUT_SEC = env_float("TORCHFT_QUORUM_TIMEOUT_SEC", 60.0)
CONNECT_TIMEOUT_SEC = env_float("TORCHFT_CONNECT_TIMEOUT_SEC", 10.0)
QUORUM_RETRIES = env_int("TORCHFT_QUORUM_RETRIES", 0, minimum=0)


def _to_sec(t: "float | timedelta | None", default: float) -> float:
    if t is None:
        return default
    if isinstance(t, timedelta):
        return t.total_seconds()
    return float(t)


def _is_floating(dtype: Any) -> bool:
    """True for float dtypes incl. ml_dtypes (bfloat16/fp8 — the TPU training
    dtypes), which np.issubdtype does not classify as np.floating."""
    return jax.numpy.issubdtype(dtype, jax.numpy.floating)


class WorldSizeMode(Enum):
    """How the quorum world size behaves (reference manager.py:112-127).

    DYNAMIC: the world grows/shrinks with membership; gradients are averaged
    over the live participant count.
    FIXED_WITH_SPARES: the world is capped at min_replica_size; extra healthy
    replicas are warm spares that compute but do not contribute.
    """

    DYNAMIC = 0
    FIXED_WITH_SPARES = 1


class Manager:
    """Fault-tolerance manager for one worker of one replica group.

    Args:
        pg: the replica-dimension process group (reconfigured per quorum).
        min_replica_size: minimum replicas for a commit to count.
        load_state_dict / state_dict: callables for the user training state
            (pytree); more can be registered via register_state_dict_fn.
        use_async_quorum: overlap quorum with the forward pass.
        checkpoint_transport: transport for live healing (HTTPTransport by
            default).
        store_addr: address of this replica group's rendezvous store; if
            None and group_rank == 0, an in-process StoreServer is started.
        replica_id: stable id of this replica group; a ``:uuid`` suffix is
            appended for fast-restart disambiguation (reference :300-306).
    """

    def __init__(
        self,
        pg: ProcessGroup,
        min_replica_size: int,
        load_state_dict: "Optional[Callable[[Any], None]]" = None,
        state_dict: "Optional[Callable[[], Any]]" = None,
        use_async_quorum: bool = True,
        timeout: "float | timedelta" = TIMEOUT_SEC,
        quorum_timeout: "float | timedelta" = QUORUM_TIMEOUT_SEC,
        connect_timeout: "float | timedelta" = CONNECT_TIMEOUT_SEC,
        group_rank: "Optional[int]" = None,
        group_world_size: "Optional[int]" = None,
        world_size_mode: WorldSizeMode = WorldSizeMode.DYNAMIC,
        store_addr: "Optional[str]" = None,
        lighthouse_addr: "Optional[str]" = None,
        replica_id: "Optional[str]" = None,
        port: "Optional[int]" = None,
        checkpoint_transport: "Optional[CheckpointTransport[Any]]" = None,
        init_sync: bool = True,
        max_retries: "Optional[int]" = None,
        quorum_retries: int = QUORUM_RETRIES,
        heartbeat_interval: float = 0.1,
    ) -> None:
        self._pg = pg
        self._min_replica_size = min_replica_size
        self._use_async_quorum = use_async_quorum
        self._timeout = _to_sec(timeout, TIMEOUT_SEC)
        self._quorum_timeout = _to_sec(quorum_timeout, QUORUM_TIMEOUT_SEC)
        self._connect_timeout = _to_sec(connect_timeout, CONNECT_TIMEOUT_SEC)
        self._replica_world_size_mode = world_size_mode
        self._init_sync = init_sync
        self._max_retries = max_retries
        # Real backoff semantics for quorum_retries (previously only a bare
        # loop count inside the native server): connection-level failures of
        # the quorum RPC retry with exponential backoff + full jitter, all
        # inside the quorum timeout budget.  TimeoutError is NOT retried —
        # the budget expiring IS the failure — and RpcError is not either
        # (the server already applied its own lighthouse retries).
        self._quorum_policy = RetryPolicy(
            name="manager.quorum",
            max_attempts=max(quorum_retries, 0) + 1,
            base_delay=0.25,
            multiplier=2.0,
            max_delay=5.0,
            retryable=(ConnectionError,),
        )

        self._group_rank = (
            group_rank if group_rank is not None else env_int("RANK", 0, minimum=0)
        )
        self._group_world_size = (
            group_world_size
            if group_world_size is not None
            else env_int("WORLD_SIZE", 1)
        )

        self._load_state_dict_fns: Dict[str, Callable[[Any], None]] = {}
        self._user_state_dicts: Dict[str, Callable[[], Any]] = {}
        if load_state_dict is not None and state_dict is not None:
            self.register_state_dict_fn("default", load_state_dict, state_dict)

        if checkpoint_transport is None:
            from torchft_tpu.checkpointing.http_transport import HTTPTransport

            checkpoint_transport = HTTPTransport(timeout=self._timeout)
        self._checkpoint_transport: CheckpointTransport[Any] = checkpoint_transport

        self._executor = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="torchft_quorum"
        )
        self._quorum_future: "Optional[concurrent.futures.Future[None]]" = None

        self._state_dict_lock = RWLock(timeout=self._timeout)
        self._pending_state_dict: "Optional[Dict[str, Any]]" = None
        self._errored: "Optional[Exception]" = None
        self._healing = False
        self._recovery_future: "Optional[concurrent.futures.Future[None]]" = None
        self._participating_replica_rank: "Optional[int]" = None
        self._participating_replica_world_size: int = 0

        self._step = 0
        self._batches_committed = 0
        self._commit_failures = 0
        self._quorum_id = -1

        # Wall-clock accumulated per protocol phase — the FT-overhead
        # observability surface (the reference only exposes these as
        # profiler spans, torchft/manager.py:385,591,790); consumers read
        # the non-destructive ``phase_times`` snapshot.  ``_phase`` (the
        # one ``tracing.phase`` primitive) fills it, and additionally feeds
        # the torchft_quorum_duration_seconds histogram and, when a tracer
        # is installed, one child span per phase under the round's root.
        self._phase_acc: Dict[str, float] = {}
        self._summary_lock = threading.Lock()  # the step digest state below
        # Trace context of the in-flight quorum round (None when tracing
        # is off or the step is unsampled).  The trace id is DERIVED FROM
        # THE STEP (tracing.step_trace_id), so every replica group, the
        # lighthouse, and both heal endpoints of one training step share
        # one trace with zero coordination.
        self._round_ctx: "Optional[tracing.TraceContext]" = None
        self._round_start_ns = 0
        self._round_step = 0

        # --- coordination wiring (reference manager.py:277-325) -----------
        lighthouse_addr = lighthouse_addr or env_str("TORCHFT_LIGHTHOUSE") or None
        if lighthouse_addr is None:
            raise ValueError(
                "lighthouse_addr (or TORCHFT_LIGHTHOUSE) is required"
            )

        self._owned_store: "Optional[StoreServer]" = None
        if store_addr is None:
            if self._group_world_size != 1:
                raise ValueError(
                    "store_addr is required when group_world_size > 1"
                )
            self._owned_store = StoreServer()
            store_addr = self._owned_store.address()
        self._store_addr = store_addr
        store = StoreClient(store_addr, connect_timeout=self._connect_timeout)

        self._manager_server: "Optional[ManagerServer]" = None
        if self._group_rank == 0:
            if replica_id is None:
                replica_id = ""
            # uuid suffix: a fast-restarted replica must not be confused with
            # its dead predecessor in lighthouse state.
            new_replica_id = replica_id + ":" + str(uuid.uuid4())
            bind_port = port or env_int("TORCHFT_MANAGER_PORT", 0, minimum=0)
            self._manager_server = ManagerServer(
                replica_id=new_replica_id,
                lighthouse_addr=lighthouse_addr,
                store_address=store_addr,
                world_size=self._group_world_size,
                bind=f":{bind_port}",
                heartbeat_interval=heartbeat_interval,
                connect_timeout=self._connect_timeout,
                quorum_retries=quorum_retries,
            )
            # replica_id BEFORE manager_addr: readers probe the addr and
            # then read the id, so publishing in this order guarantees a
            # live addr is never paired with the previous incarnation's id
            store.set(REPLICA_ID_KEY, new_replica_id)
            store.set(MANAGER_ADDR_KEY, self._manager_server.address())

        # Non-zero ranks discover the group's ManagerServer through the
        # store.  After a whole-group fast restart the store still holds
        # the DEAD incarnation's address until the new rank 0 republishes
        # — probe the endpoint and re-read until a live server answers
        # (bounded by connect_timeout), instead of wiring this Manager to
        # a corpse for its whole lifetime.
        def _probe(budget: "Optional[float]") -> str:
            probe_timeout = (
                self._connect_timeout if budget is None else max(budget, 0.001)
            )
            addr = store.get(MANAGER_ADDR_KEY, timeout=probe_timeout)
            if self._manager_server is None and not self._endpoint_alive(addr):
                raise ConnectionError(
                    f"manager server at {addr} (from store) not accepting "
                    f"connections yet"
                )
            return addr

        try:
            addr = RetryPolicy(
                name="manager.store_probe",
                base_delay=0.25,
                multiplier=1.0,
                max_delay=0.25,
                jitter=False,
                retryable=(ConnectionError,),
            ).run(_probe, timeout=self._connect_timeout)
        except TimeoutError as e:
            raise TimeoutError(
                f"manager server (from store) unreachable within "
                f"connect_timeout={self._connect_timeout}s: {e.__cause__ or e}"
            ) from e
        # read the id AFTER the probe succeeds: rank 0 publishes replica_id
        # before manager_addr, so a live addr implies the matching
        # incarnation's id is already visible
        self._replica_id = store.get(REPLICA_ID_KEY, timeout=self._connect_timeout)
        self._client = ManagerClient(addr, connect_timeout=self._connect_timeout)
        store.close()

        self._logger = ReplicaLogger(self, self._replica_id, self._group_rank)
        # Opt-in per-manager scrape endpoint (TORCHFT_METRICS_PORT);
        # process-wide singleton, so multi-manager tests don't fight.
        metrics.maybe_serve_from_env()
        # Metric labels use the STABLE replica id (the prefix before the
        # ':<uuid>' incarnation suffix): every restart would otherwise mint
        # a fresh label value, growing the process-wide registry without
        # bound across crash-and-heal cycles and resetting each series'
        # counters (breaking rate() continuity).  Events/logs keep the full
        # incarnation id — they are records, not series.
        self._metric_replica_id = (
            self._replica_id.split(":", 1)[0] or self._replica_id
        )
        # Bound metric children cached per replica: the labels() lookup is
        # ~9 us and _observe_phase sits on the step hot path — caching keeps
        # the telemetry cost per phase at the observe() itself (~1 us).
        self._phase_hist: Dict[str, Any] = {}
        self._m_allreduces = metrics.ALLREDUCES.labels(
            replica_id=self._metric_replica_id
        )
        self._m_commits = {
            result: metrics.COMMITS.labels(
                replica_id=self._metric_replica_id, result=result
            )
            for result in ("success", "failure")
        }
        self._m_step = metrics.STEP.labels(replica_id=self._metric_replica_id)
        self._m_participants = metrics.PARTICIPANTS.labels(
            replica_id=self._metric_replica_id
        )
        # Cluster step-timeline digest state (guarded by _summary_lock):
        # phase_times() snapshot at the last digest, plus codec/wire busy
        # seconds accumulated from quantized collectives since then.  The
        # per-step deltas ride the native manager's lighthouse heartbeat
        # (report_summary) into /timeline.json.
        self._summary_phase_snapshot: Dict[str, float] = {}
        self._summary_codec_s = 0.0
        self._summary_wire_s = 0.0
        # Online parallelism switching (parallel/layout.py): optional
        # LayoutController attached via attach_layout().  When present,
        # every quorum entry carries this group's layout epoch + shard
        # manifest, and the async-quorum thread runs the two-phase
        # switch protocol (commit round first, then plan+stage).
        self._layout: "Optional[Any]" = None
        self._weight_publisher: "Optional[Any]" = None
        self._publish_pending: "Optional[int]" = None
        self._publish_executor: (
            "Optional[concurrent.futures.ThreadPoolExecutor]"
        ) = None
        # Durable fragment store (checkpointing/store.py, ISSUE 17):
        # opt-in via TORCHFT_STORE_DIR.  Committed steps spill to disk
        # off the hot path (single-worker spiller) and the store is
        # attached to the checkpoint transport so peers' cold-start
        # restores can stripe-fetch spilled fragments from this rank's
        # disk exactly like a live heal.
        self._frag_store = fragment_store.store_from_env(
            self._metric_replica_id, self._group_rank
        )
        self._spiller: "Optional[Any]" = None
        self._spill_pending: "Optional[int]" = None
        self._last_spill = 0.0
        if self._frag_store is not None:
            attach = getattr(self._checkpoint_transport, "attach_store", None)
            if attach is not None:
                attach(self._frag_store)
            self._spiller = fragment_store.StoreSpiller(self._frag_store)

    @staticmethod
    def _endpoint_alive(addr: str, probe_timeout: float = 1.0) -> bool:
        """True if a TCP listener answers at ``addr`` ("host:port")."""
        from torchft_tpu.coordination import parse_host_port

        try:
            with socket.create_connection(parse_host_port(addr), probe_timeout):
                return True
        except OSError:
            return False

    # ------------------------------------------------------------------
    # state dict registry
    # ------------------------------------------------------------------

    def register_state_dict_fn(
        self,
        key: str,
        load_state_dict_fn: "Callable[[Any], None]",
        state_dict_fn: "Callable[[], Any]",
    ) -> None:
        """Register a named slice of user state for healing
        (reference manager.py:355-366)."""
        self._load_state_dict_fns[key] = load_state_dict_fn
        self._user_state_dicts[key] = state_dict_fn

    def attach_layout(self, controller: Any) -> Any:
        """Attach a :class:`~torchft_tpu.parallel.layout.LayoutController`
        enabling online parallelism switching: on membership change the
        fleet re-plans its (dp, shard, pp) layout under a monotone layout
        epoch, re-shards registered state live over the checkpoint
        transport, and commits the switch at the same quorum round on
        every group or rolls back (docs/architecture.md "Online
        parallelism switching").  Returns the controller for chaining."""
        self._layout = controller
        if hasattr(controller, "bind"):
            controller.bind(self)
        return controller

    def layout_controller(self) -> "Optional[Any]":
        return self._layout

    def attach_weight_publisher(self, publisher: Any) -> Any:
        """Attach a :class:`~torchft_tpu.serving.WeightPublisher`: every
        COMMITTED step's user state is published as weight version
        ``step`` into the serving tier (docs/architecture.md
        "Weight-serving tier").  Timing: the user applies the optimizer
        update AFTER ``should_commit`` returns, so the snapshot is taken
        at the start of the NEXT round (the same point layout updates
        settle) — and flushed at :meth:`shutdown` for the final step.
        Attach to ONE rank per job — typically group 0's rank 0; the
        publisher's versions fan out through the lighthouse-synthesized
        distribution tree.  Publish failures are logged, never allowed
        to fail training.  Returns the publisher for chaining."""
        self._weight_publisher = publisher
        return publisher

    def _flush_pending_publish(self, wait: bool = False) -> None:
        """Publish the last committed step's user state, if one is
        pending (called from the next round's start and from shutdown —
        both points where the user's post-commit optimizer update has
        fully materialized).

        Only the SNAPSHOT runs on the caller (under the state-dict read
        lock, the heal consistency point); the encode + staging + HTTP
        advertise run on a single-worker executor so a multi-GB publish
        never turns the publishing rank into the fleet's straggler at
        every ``start_quorum``.  One worker keeps versions ordered;
        ``wait`` (shutdown) drains the queue so the final version is
        staged before the transports die."""
        version, self._publish_pending = self._publish_pending, None
        pub = self._weight_publisher
        if pub is None:
            return
        if version is not None:
            try:
                with self._state_dict_lock.r_lock():
                    state = {
                        k: fn() for k, fn in self._user_state_dicts.items()
                    }
            except Exception:  # noqa: BLE001 - serving never fails training
                self._logger.exception("weight-publish snapshot failed")
                return
            if self._publish_executor is None:
                self._publish_executor = concurrent.futures.ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="tft_weight_publish"
                )

            def _do_publish() -> None:
                try:
                    pub.publish(state, version=version)
                except Exception:  # noqa: BLE001 - never fails training
                    self._logger.exception(
                        "weight publish failed (serving tier degraded "
                        "this step)"
                    )

            self._publish_executor.submit(_do_publish)
        if wait and self._publish_executor is not None:
            self._publish_executor.shutdown(wait=True)
            self._publish_executor = None

    def _flush_pending_spill(self, wait: bool = False) -> None:
        """Spill the last committed step to the durable fragment store,
        if one is pending and the ``TORCHFT_STORE_SPILL_S`` cadence has
        elapsed (0 = every commit).  Only the snapshot runs on the
        caller (under the state-dict read lock — the exact bytes a live
        replica at this step holds); encode + blob writes + manifest
        publish run on the single spill worker, and a failed spill skips
        the version (counted), never failing or stalling training."""
        version, self._spill_pending = self._spill_pending, None
        spiller = self._spiller
        if spiller is None:
            return
        if wait:
            # shutdown path: drain the in-flight spill FIRST so the final
            # committed version is accepted instead of skipped (the
            # no-backlog rule exists to protect the training loop, which
            # is over by now)
            spiller.flush()
        if version is not None:
            interval = env_float("TORCHFT_STORE_SPILL_S", 0.0, minimum=0.0)
            if time.monotonic() - self._last_spill >= interval:
                try:
                    state = self._manager_state_dict()
                except Exception:  # noqa: BLE001 - spill never fails training
                    self._logger.exception("store spill snapshot failed")
                    state = None
                if state is not None and spiller.submit(version, state):
                    self._last_spill = time.monotonic()
        if wait:
            spiller.flush()

    def _manager_state_dict(self) -> "Dict[str, Any]":
        with self._state_dict_lock.r_lock():
            assert self._user_state_dicts, "user state_dict is not initialized"
            return {
                "user": {k: fn() for k, fn in self._user_state_dicts.items()},
                "torchft": self.state_dict(),
            }

    def state_dict(self) -> "Dict[str, int]":
        return {"step": self._step, "batches_committed": self._batches_committed}

    def load_state_dict(self, state_dict: "Dict[str, int]") -> None:
        self._step = state_dict["step"]
        self._batches_committed = state_dict["batches_committed"]

    # Hooks for callers that mutate user state outside the step protocol
    # (reference local_sgd.py:112-124 toggles these around optimizer
    # mutation): disallow takes the state-dict write lock so a concurrent
    # checkpoint send cannot snapshot mid-mutation.
    def disallow_state_dict_read(self) -> None:
        self._state_dict_lock.acquire_write()

    def allow_state_dict_read(self) -> None:
        self._state_dict_lock.release_write()

    # ------------------------------------------------------------------
    # quorum
    # ------------------------------------------------------------------

    def start_quorum(
        self,
        allow_heal: bool = True,
        shrink_only: bool = False,
        timeout: "float | timedelta | None" = None,
    ) -> None:
        """Begin a new step: compute quorum (possibly async) and ready the PG.

        Reference: torchft/manager.py:534-589.
        """
        if self._quorum_future is not None:
            self._quorum_future.result()

        # Serving tier: the previous round's committed weights are fully
        # materialized by now (the user's optimizer update ran between
        # should_commit and this call) — publish them as that step's
        # weight version before the new round begins.
        self._flush_pending_publish()
        self._flush_pending_spill()

        self._errored = None
        self._healing = False
        # Straggler telemetry: piggyback (step, in-flight op) on the native
        # manager's lighthouse heartbeats, so the lighthouse can compute
        # per-replica step lag and straggler scores while this replica is
        # inside the quorum protocol.
        self._report_progress("quorum")

        tracer = tracing.get_tracer()
        ctx: "Optional[tracing.TraceContext]" = None
        if tracer is not None and tracer.sample_step(self._step):
            # Deterministic per-step trace id: every replica at this step
            # derives the same one (and the same sampling decision), so a
            # sampled step's trace is complete across the whole fleet.
            ctx = tracing.TraceContext(
                tracing.step_trace_id(self._step), tracing.new_span_id()
            )
        self._round_ctx = ctx
        self._round_start_ns = time.time_ns() if ctx is not None else 0
        self._round_step = self._step
        # Bind on the caller thread too: the allreduce submit and the
        # should_commit RPC run here and must inject the same context.
        tracing.set_current(ctx)

        self._quorum_future = self._executor.submit(
            self._async_quorum,
            allow_heal=allow_heal,
            shrink_only=shrink_only,
            quorum_timeout=_to_sec(timeout, self._quorum_timeout),
        )
        if not self._use_async_quorum:
            self.wait_quorum()
            if self._healing:
                # eagerly apply the healed state so the forward pass runs on
                # recovered weights
                self._apply_pending_state_dict()
                self._healing = False

    def wait_quorum(self) -> None:
        assert (
            self._quorum_future is not None
        ), "must call start_quorum before wait_quorum"
        with self._phase("quorum_wait"):
            self._quorum_future.result()

    def _async_quorum(
        self, allow_heal: bool, shrink_only: bool, quorum_timeout: float
    ) -> None:
        # The executor thread is where the quorum RPC, pg configure, and
        # the heal transfers run: bind the round's trace context so every
        # outbound RPC (manager quorum, store barriers) and the heal
        # transports carry it.
        tracing.set_current(self._round_ctx)
        try:
            with self._phase("quorum_rpc"):

                def _quorum_rpc(budget: "Optional[float]") -> Any:
                    # chaos site INSIDE the retry policy: an injected drop
                    # (ConnectionError) exercises the quorum_retries backoff
                    # path; an injected raise escapes to report_error
                    faults.check(
                        "manager.quorum", replica=self._replica_id, step=self._step
                    )
                    return self._client._quorum(
                        group_rank=self._group_rank,
                        step=self._step,
                        checkpoint_metadata=self._checkpoint_transport.metadata(),
                        shrink_only=shrink_only,
                        timeout=budget if budget is not None else quorum_timeout,
                        init_sync=self._init_sync,
                        commit_failures=self._commit_failures,
                        layout_epoch=(
                            0 if self._layout is None else self._layout.wire_epoch()
                        ),
                        layout_data=(
                            "" if self._layout is None else self._layout.wire_data()
                        ),
                    )

                quorum = self._quorum_policy.run(
                    _quorum_rpc, timeout=quorum_timeout, op="manager.quorum"
                )
        except Exception as e:  # noqa: BLE001 - captured into the protocol
            # Graceful capture (the reference leaves this as a TODO,
            # manager.py:566-567): the replica sits out this step and votes
            # False rather than crashing the training loop.
            self._logger.exception(f"got exception in quorum: {e}")
            self._participating_replica_rank = None
            self._participating_replica_world_size = 0
            self.report_error(e if isinstance(e, Exception) else RuntimeError(str(e)))
            return

        # Async quorum participates with the max-step cohort (healing
        # replicas contribute zeros this step); sync quorum heals eagerly so
        # everyone participates (reference manager.py:641-657).
        self._participating_replica_rank, self._participating_replica_world_size = (
            (quorum.max_replica_rank, quorum.max_world_size)
            if self._use_async_quorum or not allow_heal
            else (quorum.replica_rank, quorum.replica_world_size)
        )

        if self._replica_world_size_mode == WorldSizeMode.FIXED_WITH_SPARES:
            self._participating_replica_world_size = min(
                self._participating_replica_world_size, self._min_replica_size
            )
            if (
                self._participating_replica_rank is not None
                and self._participating_replica_rank >= self._min_replica_size
            ):
                self._participating_replica_rank = None

        # Online parallelism switching, two-phase (parallel/layout.py):
        # FIRST resolve the previous round's staged switch (commit when
        # the whole quorum reports the staged epoch, else roll back and
        # burn it), THEN — if the live world no longer fits the active
        # layout — plan the next layout and run the reshard transfers on
        # this thread, where heal runs.  Neither phase may fail the
        # training step: a broken switch degrades to the old layout.
        # Runs BEFORE pg configure and the allow_heal gate: this round's
        # quorum entry already advertised our epoch report, so skipping
        # the commit round here (configure error, heal-less round) would
        # let the rest of the fleet activate without us — the exact
        # mixed-generation split the all-commit-same-epoch invariant
        # forbids.  The transfers ride the checkpoint transport, not the
        # PG, so ordering before configure is safe.
        if self._layout is not None:
            outcome = ""
            with self._phase("layout_commit") as p_commit:
                try:
                    faults.check(
                        "manager.layout_commit",
                        replica=self._replica_id,
                        step=quorum.max_step,
                    )
                    outcome = self._layout.maybe_commit(quorum)
                except Exception as e:  # noqa: BLE001 - degrade, never wedge
                    self._logger.exception(f"layout commit failed: {e}")
                    self._layout.abort_staged(f"layout commit failed: {e}")
                    outcome = "rolled_back"
                if not outcome:
                    p_commit.cancel()  # no staged switch to resolve
            if outcome:
                metrics.LAYOUT_SWITCHES.labels(
                    replica_id=self._metric_replica_id, result=outcome
                ).inc()
                active = self._layout.active_layout()
                metrics.LAYOUT_EPOCH.labels(
                    replica_id=self._metric_replica_id
                ).set(active.epoch if active is not None else 0)
                log_event(
                    "layout",
                    f"layout switch {outcome}",
                    job_id=env_str("JOB_ID", "unknown"),
                    replica_id=self._replica_id,
                    rank=self._group_rank,
                    quorum_id=quorum.quorum_id,
                    step=quorum.max_step,
                    outcome=outcome,
                    layout=str(active.key() if active is not None else None),
                )
            with self._phase("reshard") as p_reshard:
                try:
                    staged = self._layout.maybe_stage(self, quorum)
                except Exception as e:  # noqa: BLE001 - degrade, never wedge
                    self._logger.exception(f"layout staging failed: {e}")
                    self._layout.abort_staged(f"layout staging failed: {e}")
                    staged = True
                if not staged:
                    p_reshard.cancel()  # the live world still fits the layout

        if quorum.quorum_id != self._quorum_id:
            metrics.QUORUM_CHANGES.labels(replica_id=self._metric_replica_id).inc()
            log_event(
                "quorum",
                "quorum changed",
                job_id=env_str("JOB_ID", "unknown"),
                replica_id=self._replica_id,
                rank=self._group_rank,
                quorum_id=quorum.quorum_id,
                step=quorum.max_step,
            )
            store_prefixed_addr = (
                f"{quorum.store_address}/torchft/{quorum.quorum_id}/{self._group_rank}"
            )
            self._logger.info(
                f"reconfiguring for quorum_id={quorum.quorum_id} store={store_prefixed_addr}"
            )
            try:
                with self._phase("pg_configure"):
                    self._pg.configure(
                        store_prefixed_addr,
                        self._replica_id,
                        quorum.replica_rank,
                        quorum.replica_world_size,
                    )
                self._quorum_id = quorum.quorum_id
                log_event(
                    "reconfigure",
                    "pg reconfigured",
                    job_id=env_str("JOB_ID", "unknown"),
                    replica_id=self._replica_id,
                    rank=self._group_rank,
                    quorum_id=quorum.quorum_id,
                    step=quorum.max_step,
                    replica_world_size=quorum.replica_world_size,
                )
            except Exception as e:  # noqa: BLE001 - captured into the protocol
                self._logger.exception(f"got exception in pg configure: {e}")
                self.report_error(e)
                return

        if not allow_heal:
            return

        # Striped heal (ISSUE 15): stream-stage fragments + stripe the
        # receive across every max-step peer when the transport carries
        # the fragment protocol (the flag must be literally True so
        # duck-typed test doubles keep the legacy path).
        streamed_heal = (
            getattr(self._checkpoint_transport, "supports_striped_heal", False)
            is True
        )

        # Whole-fleet cold start (ISSUE 17): nobody in the quorum holds
        # live state (max_step == 0) but disks might — restore the newest
        # complete, consistent spilled cut through the striped heal path
        # with files as stripe sources.  Every replica computes the same
        # deterministic cut from the same fleet catalogs, so a
        # successful restore replaces this round's live init-sync
        # branches entirely; a failed one degrades to fresh init (and a
        # replica whose restore failed alone re-heals live next round
        # once its peers commit) — never a wedge.
        if (
            self._frag_store is not None
            and streamed_heal
            and self._step == 0
            and quorum.max_step == 0
        ):
            if self._maybe_cold_restore(quorum):
                return

        # Proactive stripe-source staging: a max-step participant can
        # tell healers exist this round (the max-step cohort is smaller
        # than the quorum) and stages its own fragment stream so healers
        # aggregate up-to-date uplinks beyond the assigned primary's.
        # Bounded by the SAME pure quorum math the healer's source
        # resolution applies: every healer stripes over the first
        # TORCHFT_HEAL_SOURCES max-step roster entries (minus its
        # primary), so only those participants stage — a 64-replica
        # fleet must not burn 60 full encodes for slots nobody fetches.
        # Degrade-only: a failed proactive stage merely shrinks the
        # healer's stripe back toward the primary.
        if (
            streamed_heal
            and not quorum.recover_dst_replica_ranks
            and not quorum.heal
            and quorum.max_replica_rank is not None
            and quorum.max_world_size < quorum.replica_world_size
            and self._in_stripe_source_set(quorum)
        ):
            try:
                with self._phase("heal_send"):
                    self._checkpoint_transport.send_checkpoint_streamed(
                        dst_ranks=[],
                        step=quorum.max_step,
                        state_dict=self._manager_state_dict(),
                        timeout=self._timeout,
                    )
                log_event(
                    "heal",
                    "staged stripe-source checkpoint for healing peers",
                    job_id=env_str("JOB_ID", "unknown"),
                    replica_id=self._replica_id,
                    rank=self._group_rank,
                    quorum_id=quorum.quorum_id,
                    step=quorum.max_step,
                    direction="send",
                    proactive=True,
                )
            except Exception as e:  # noqa: BLE001 - degrade, never wedge
                self._logger.warning(
                    f"proactive stripe-source staging failed "
                    f"(healers fall back to fewer sources): {e}"
                )

        try:
            if quorum.recover_dst_replica_ranks:
                faults.check(
                    "manager.heal", replica=self._replica_id, step=quorum.max_step
                )
                self._logger.info(
                    f"peers need recovery from us {quorum.recover_dst_replica_ranks}"
                )
                with self._phase("heal_send"):
                    if streamed_heal:
                        self._checkpoint_transport.send_checkpoint_streamed(
                            dst_ranks=quorum.recover_dst_replica_ranks,
                            step=quorum.max_step,
                            state_dict=self._manager_state_dict(),
                            timeout=self._timeout,
                        )
                    else:
                        self._checkpoint_transport.send_checkpoint(
                            dst_ranks=quorum.recover_dst_replica_ranks,
                            step=quorum.max_step,
                            state_dict=self._manager_state_dict(),
                            timeout=self._timeout,
                        )
                metrics.HEALS.labels(
                    replica_id=self._metric_replica_id, direction="send"
                ).inc()
                log_event(
                    "heal",
                    "sent checkpoint to healing peers",
                    job_id=env_str("JOB_ID", "unknown"),
                    replica_id=self._replica_id,
                    rank=self._group_rank,
                    quorum_id=quorum.quorum_id,
                    step=quorum.max_step,
                    direction="send",
                    dst_ranks=quorum.recover_dst_replica_ranks,
                )

            if quorum.heal:
                faults.check(
                    "manager.heal", replica=self._replica_id, step=quorum.max_step
                )
                self._healing = True
                heal_info: "Dict[str, Any]" = {}
                # the step healed TO, on heal_recv and what it opens
                with self._phase("heal_recv", step=quorum.max_step) as p_recv:
                    self._logger.info(
                        f"healing required, fetching checkpoint metadata from "
                        f"{quorum.recover_src_manager_address} max_step={quorum.max_step}"
                    )
                    primary_client = ManagerClient(
                        quorum.recover_src_manager_address,
                        connect_timeout=self._connect_timeout,
                    )
                    checkpoint_metadata = primary_client._checkpoint_metadata(
                        self._group_rank, timeout=self._timeout
                    )
                    primary_client.close()
                    assert (
                        quorum.recover_src_replica_rank is not None
                    ), "must have a recover rank when healing"
                    if streamed_heal:
                        sources = [checkpoint_metadata]
                        # Stripe only when genuinely BEHIND the cohort:
                        # an init-sync force-recover round has every
                        # replica at max_step with unsynchronized state —
                        # only the primary's copy is truth there.
                        if quorum.max_replica_rank is None:
                            sources += self._resolve_stripe_sources(
                                quorum, checkpoint_metadata
                            )
                        # heal_manifest, heal_diff, heal_wire and
                        # heal_decode are timed in there, as they happen
                        (
                            self._pending_state_dict,
                            heal_info,
                        ) = self._checkpoint_transport.recv_checkpoint_striped(
                            sources,
                            step=quorum.max_step,
                            timeout=self._timeout,
                            local_state_fn=self._manager_state_dict,
                        )
                    else:
                        self._pending_state_dict = (
                            self._checkpoint_transport.recv_checkpoint(
                                src_rank=quorum.recover_src_replica_rank,
                                metadata=checkpoint_metadata,
                                step=quorum.max_step,
                                timeout=self._timeout,
                            )
                        )
                    self.load_state_dict(self._pending_state_dict["torchft"])
                    # loading the torchft dict restores the step; set it anyway
                    # to make reasoning (and tests) simpler
                    self._step = quorum.max_step
                    # Phase split (ISSUE 15): heal_recv is what the four
                    # split phases leave (metadata RPC, source resolution,
                    # reassembly), so ledger sums stay exact and never
                    # count a split phase against its umbrella.
                    p_recv.exclude(
                        self._fold_phases(
                            heal_info.get("phases"), heal_info.get("parts")
                        )
                    )
                metrics.HEALS.labels(
                    replica_id=self._metric_replica_id, direction="recv"
                ).inc()
                log_event(
                    "heal",
                    "received checkpoint from peer",
                    job_id=env_str("JOB_ID", "unknown"),
                    replica_id=self._replica_id,
                    rank=self._group_rank,
                    quorum_id=quorum.quorum_id,
                    step=quorum.max_step,
                    direction="recv",
                    src_rank=quorum.recover_src_replica_rank,
                    mode=heal_info.get("mode", "legacy"),
                    stripe_sources=heal_info.get("sources", 1),
                    changed_fragments=heal_info.get("changed"),
                )
        except Exception as e:  # noqa: BLE001 - captured into the protocol
            self._logger.exception(f"got exception in recovery: {e}")
            self.report_error(e)

    def _in_stripe_source_set(self, quorum: Any) -> bool:
        """True when this replica is among the first
        ``TORCHFT_HEAL_SOURCES`` max-step participants in roster order —
        the superset every healer's ``_resolve_stripe_sources`` pick
        (first ``max_sources - 1`` entries after excluding its primary)
        can reach, computed from the same roster on every peer — via
        the one copy of the first-K math (ISSUE 19: ``tft-verify
        --scenario plan`` checks the structure this produces)."""
        max_sources = env_int("TORCHFT_HEAL_SOURCES", 4, minimum=1)
        return self._replica_id in stripe_source_cohort(
            quorum.participants, quorum.max_step, max_sources
        )

    def _resolve_stripe_sources(
        self, quorum: Any, primary_metadata: str
    ) -> "List[str]":
        """Transport addresses of the max-step quorum peers beyond the
        assigned primary — the striped heal's extra sources.

        The participants roster (replica-rank order) carries each peer's
        manager address and step; every peer at ``max_step`` holds
        bitwise-replicated state, so its fragments must hash to the
        primary's manifest digests.  Each candidate's checkpoint
        transport address resolves through its manager's
        ``checkpoint_metadata`` RPC (the same discovery heal and reshard
        use), in parallel and best-effort: an unreachable peer just
        shrinks the stripe.  Bounded by ``TORCHFT_HEAL_SOURCES``
        (total sources including the primary).  The candidate pick is
        :func:`~torchft_tpu.coordination.stripe_roster` — the same math
        the tft-plan verifier and the source-side cohort test consume."""
        max_sources = env_int("TORCHFT_HEAL_SOURCES", 4, minimum=1)
        candidates = stripe_roster(
            quorum.participants,
            quorum.max_step,
            quorum.recover_src_replica_rank,
            max_sources,
        )
        if not candidates:
            return []

        def _resolve(addr: str) -> "Optional[str]":
            client = ManagerClient(
                addr, connect_timeout=self._connect_timeout
            )
            try:
                return client._checkpoint_metadata(
                    self._group_rank, timeout=self._connect_timeout
                )
            except Exception as e:  # noqa: BLE001 - best-effort stripe
                self._logger.info(
                    f"stripe source {addr} unresolvable ({e}); striping "
                    f"without it"
                )
                return None
            finally:
                client.close()

        with ThreadPoolExecutor(
            max_workers=min(len(candidates), 4),
            thread_name_prefix="tft_stripe_resolve",
        ) as pool:
            resolved = list(pool.map(_resolve, candidates))
        return [
            m for m in resolved if m and m != primary_metadata
        ]

    def _resolve_store_bases(self, quorum: Any, own: str) -> "List[str]":
        """Checkpoint-transport addresses of every reachable quorum
        participant plus our own — cold restore canvasses ALL disks
        (everyone is at step 0, so there is no max-step cohort to
        prefer).  Sorted + deduped so every replica that resolves the
        same roster derives the same base list, which keeps cut
        selection deterministic fleet-wide."""
        addrs: "List[str]" = []
        for p in quorum.participants:
            if isinstance(p, dict) and p.get("address"):
                addrs.append(p["address"])

        def _resolve(addr: str) -> "Optional[str]":
            client = ManagerClient(
                addr, connect_timeout=self._connect_timeout
            )
            try:
                return client._checkpoint_metadata(
                    self._group_rank, timeout=self._connect_timeout
                )
            except Exception as e:  # noqa: BLE001 - best-effort discovery
                self._logger.info(
                    f"store base {addr} unresolvable ({e}); restoring "
                    f"without its disk"
                )
                return None
            finally:
                client.close()

        resolved: "List[Optional[str]]" = []
        if addrs:
            with ThreadPoolExecutor(
                max_workers=min(len(addrs), 4),
                thread_name_prefix="tft_store_resolve",
            ) as pool:
                resolved = list(pool.map(_resolve, addrs))
        return sorted({m for m in resolved if m} | {own})

    def _maybe_cold_restore(self, quorum: Any) -> bool:
        """Whole-fleet cold-start restore (ISSUE 17, docs/architecture.md
        "Durable fragment store").

        Discovers spilled catalogs across every reachable disk (own +
        peers' via ``/store/versions``), picks the newest complete,
        consistent cut (:func:`~torchft_tpu.checkpointing.store.
        select_cut` — deterministic, never mixes fragment versions), and
        reassembles it via ``recv_checkpoint_striped`` with disks as
        stripe sources: per-fragment failover across disks, delta reuse
        of surviving local state.  Returns True when restored (state is
        pending; the standard healing application path applies it).
        Any failure returns False — fresh init, never a wedge."""
        try:
            with self._phase("heal_recv") as p_recv:
                faults.check("store.restore", replica=self._replica_id, step=0)
                own = self._checkpoint_transport.metadata()
                bases = self._resolve_store_bases(quorum, own)
                catalogs: "Dict[str, Any]" = {}
                for base in bases:
                    cat = fragment_store.fetch_catalog(
                        base, timeout=self._connect_timeout
                    )
                    if cat:
                        catalogs[base] = cat
                plan = fragment_store.select_cut(catalogs)
                if plan is None:
                    p_recv.cancel()  # nothing spilled: fresh init
                    return False
                version, sources = plan
                p_recv.attrs["step"] = version  # the step restored TO
                self._logger.info(
                    f"cold restore: selected spilled v{version} across "
                    f"{len(sources)} disk(s)"
                )
                self._healing = True
                (
                    self._pending_state_dict,
                    info,
                ) = self._checkpoint_transport.recv_checkpoint_striped(
                    sources,
                    step=version,
                    timeout=self._timeout,
                    local_state_fn=self._manager_state_dict,
                    plane="restore",
                )
                metrics.STORE_RESTORE_BYTES.labels(
                    mode=info.get("mode", "full")
                ).inc(int(info.get("wire_bytes") or 0))
                self.load_state_dict(
                    cast(Dict[str, int], self._pending_state_dict["torchft"])
                )
                p_recv.exclude(
                    self._fold_phases(info.get("phases"), info.get("parts"))
                )
            metrics.HEALS.labels(
                replica_id=self._metric_replica_id, direction="recv"
            ).inc()
            log_event(
                "heal",
                "cold-restored from durable store",
                job_id=env_str("JOB_ID", "unknown"),
                replica_id=self._replica_id,
                rank=self._group_rank,
                quorum_id=quorum.quorum_id,
                step=version,
                direction="recv",
                mode=info.get("mode", "full"),
                stripe_sources=info.get("sources", 1),
                changed_fragments=info.get("changed"),
            )
            self._logger.info(
                f"cold-restored to step {version} from {len(sources)} "
                f"store source(s) mode={info.get('mode')}"
            )
            return True
        except Exception as e:  # noqa: BLE001 - degrade to fresh init
            self._logger.warning(f"cold restore failed (starting fresh): {e}")
            self._healing = False
            self._pending_state_dict = None
            return False

    def _apply_pending_state_dict(self) -> None:
        assert self._healing, "must be in healing state"
        assert self._quorum_future is not None, "must call start_quorum first"
        self._quorum_future.result()

        pending = self._pending_state_dict
        if pending is None:
            assert self.errored() is not None, (
                "checkpoint was not staged and no error occurred"
            )
            return
        self._logger.info("applying pending state dict")
        assert self._load_state_dict_fns, "user load_state_dict is not initialized"
        user_state = cast(Dict[str, Any], pending["user"])
        with self._phase("heal_apply"):
            # the user's load puts the healed host arrays back on the device
            for key, load_fn in self._load_state_dict_fns.items():
                load_fn(user_state[key])
        self._pending_state_dict = None

    # ------------------------------------------------------------------
    # collectives
    # ------------------------------------------------------------------

    def allreduce(
        self,
        value: Any,
        should_quantize: bool = False,
        reduce_op: str = REDUCE_AVG,
        device_quantize: "Optional[bool]" = None,
    ) -> Work:
        """Fault-tolerant allreduce of an array or pytree of arrays.

        Averages over the live participant count; non-participants (healing
        replicas) contribute zeros.  On error the Work completes *cleanly*
        with the input (zeroed) value and the error is tracked for
        ``should_commit`` (reference manager.py:385-467).

        The result is arrays the caller may not write: host arrays as a
        rule, private to the caller for as long as it holds them, never an
        alias of an ``np.ndarray`` that was passed in, and nothing writes
        to them again (a ring buffer returns to the pool only when the last
        view of the result is gone).  A ``jax.Array`` leaf may come back as
        a ``jax.Array``: when the group is alone (world size 1, one
        participant) the average is the leaf, and it comes back as itself,
        still on its devices with its sharding, without crossing to the
        host and back; an ``np.ndarray`` leaf beside it comes back as a
        copy.  Take ``np.array(x)`` of a leaf before writing into it or
        calling an ``ndarray``-only method.  Only the error path above
        hands the input back.

        ``device_quantize`` (quantized path only): quantize on-chip with
        the Pallas kernel before the device→host copy; ``None`` = auto
        (on when every leaf is a jax array on a TPU backend) — forwarded
        to :func:`~torchft_tpu.ops.collectives.allreduce_quantized`.
        """
        if self.errored():
            return completed_work(value)

        self.wait_quorum()
        num_participants = self.num_participants()

        with self._phase("host_sync"):
            leaves, treedef = jax.tree_util.tree_flatten(value)
            # An array leaf is handed over as it is, its memory untouched:
            # the group takes it off the device on its worker thread, so the
            # device→host sync overlaps whatever the caller does next (counted
            # in the ``ring`` phase; the DiLoCo fragment-overlap pattern
            # depends on this submit being non-blocking), and the quantized
            # collective quantizes on the chip, so only the int8 payload and
            # row scales cross to the host.  A leaf that is no array (a
            # Python scalar) is wrapped, for its shape and dtype.
            send_leaves: "List[Any]" = [
                x if isinstance(x, (np.ndarray, jax.Array)) else np.asarray(x)
                for x in leaves
            ]
            if not self.is_participating():
                # a healer's share is zeros, made from the shapes alone
                send_leaves = [np.zeros(x.shape, x.dtype) for x in send_leaves]

        if reduce_op == REDUCE_AVG:
            if not all(_is_floating(x.dtype) for x in send_leaves):
                raise ValueError(
                    "average reduce op is only supported for floating point arrays"
                )
            pg_reduce_op = REDUCE_SUM
        else:
            pg_reduce_op = reduce_op

        self._m_allreduces.inc()
        try:
            faults.check(
                "pg.allreduce", replica=self._replica_id, step=self._step
            )
            # ``ring`` ends in the completion callback, on the PG worker's
            # thread: the explicit form, no annotation of its own.  Its
            # parts (PHASE_PARTS ``ring.*``) are timed there, by the PG;
            # ``under`` makes them ring's, in phase_times() and the trace.
            ring = self._phase("ring").begin()
            # The average is the sum over the live participant count, and
            # the group takes the count: it owns the buffer it reduced into
            # and scales it there (this side cannot tell whose memory a
            # result is).
            divisor = (
                num_participants
                if reduce_op == REDUCE_AVG and num_participants != 1
                else None
            )
            with tracing.under(ring):
                if should_quantize:
                    from torchft_tpu.ops.collectives import allreduce_quantized

                    work = allreduce_quantized(
                        send_leaves, pg_reduce_op, self._pg,
                        average_by=divisor,
                        device_quantize=device_quantize,
                    )
                else:
                    work = self._pg.allreduce(
                        send_leaves, pg_reduce_op, divisor=divisor
                    )

            def _postprocess(reduced: "List[Any]") -> Any:
                with tracing.under(ring), tracing.phase(".unpack"):
                    return jax.tree_util.tree_unflatten(treedef, reduced)

            chained = work.then(_postprocess)

            # Track errors out-of-band: the returned Work must complete
            # cleanly so the training loop proceeds to should_commit.
            out: concurrent.futures.Future = concurrent.futures.Future()
            # One-shot holder: the error path below hands the inputs
            # back, but a COMPLETED Work must not keep pinning them — for
            # device gradients that is a whole extra copy of the model
            # held in HBM across the next forward/backward.  Nor the raw
            # Work: a future keeps its callbacks, so this callback would
            # close a cycle around the raw result, which at world size 1 is
            # the device leaves themselves.
            inputs = [(send_leaves, work)]

            def _done(f: "concurrent.futures.Future[Any]") -> None:
                held, raw = inputs.pop()
                ring.end(ok=f.exception() is None)
                # quantized-pipeline accounting for the step digest: the
                # stats dict is complete once the pipeline finished, i.e.
                # before this callback fires
                qs = getattr(raw, "quant_stats", None)
                if isinstance(qs, dict):
                    with self._summary_lock:
                        self._summary_codec_s += float(qs.get("codec_s") or 0.0)
                        self._summary_wire_s += float(qs.get("wire_s") or 0.0)
                exc = f.exception()
                if exc is not None:
                    self.report_error(
                        exc if isinstance(exc, Exception) else RuntimeError(str(exc))
                    )
                    out.set_result(
                        jax.tree_util.tree_unflatten(treedef, held)
                    )
                else:
                    out.set_result(f.result())

            chained.get_future().add_done_callback(_done)
            managed = Work(out)
            # surface the collective's wire/codec accounting on the
            # returned handle: the quantized pipeline's (wire_bytes set
            # synchronously; codec_s_box/quant_stats written at pipeline
            # completion — read after wait) and the TCP ring's measured
            # wire_bytes on the unquantized path
            for attr in (
                "wire_bytes",
                "unquantized_wire_bytes",
                "device_quantized",
                "wire_dtype",
                "codec_s_box",
                "quant_stats",
            ):
                if hasattr(work, attr):
                    setattr(managed, attr, getattr(work, attr))
            return managed
        except Exception as e:  # noqa: BLE001 - captured into the protocol
            self._logger.exception(f"got exception in allreduce -- skipping: {e}")
            self.report_error(e)
            return completed_work(value)

    # ------------------------------------------------------------------
    # errors & commit
    # ------------------------------------------------------------------

    def report_error(self, e: Exception) -> None:
        """Latch an async error; the current step will not be committed
        (reference manager.py:469-482)."""
        self._errored = e
        metrics.ERRORS.labels(replica_id=self._metric_replica_id).inc()
        log_event(
            "error",
            str(e),
            job_id=env_str("JOB_ID", "unknown"),
            replica_id=self._replica_id,
            rank=self._group_rank,
            quorum_id=self._quorum_id,
            step=self._step,
        )
        # Flight recorder: the latched error plus a crash-durable dump of
        # the ring around it — an unhandled manager error is a dump
        # trigger (utils/flightrecorder.py); no-op without
        # TORCHFT_FLIGHT_FILE.
        flightrec.record(
            "manager.error",
            status="error",
            error=str(e),
            replica_id=self._replica_id,
            rank=self._group_rank,
            quorum_id=self._quorum_id,
            step=self._step,
        )
        flightrec.dump(f"manager error: {e!r}", trigger="manager_error")

    def errored(self) -> "Optional[Exception]":
        return self._errored

    def should_commit(self, timeout: "float | timedelta | None" = None) -> bool:
        """Vote on committing this step; all group workers return the same
        value (reference manager.py:790-878)."""
        # recovery (send/recv checkpoint) must be complete before committing
        if self._quorum_future is not None:
            with self._phase("quorum_wait"):
                try:
                    self._quorum_future.result()
                except Exception as e:  # noqa: BLE001
                    self.report_error(
                        e if isinstance(e, Exception) else RuntimeError(str(e))
                    )

        if (err := self._pg.errored()) is not None:
            self.report_error(err)

        if self._healing:
            self._apply_pending_state_dict()

        enough_replicas = self.num_participants() >= self._min_replica_size
        local_should_commit = enough_replicas and self._errored is None
        with self._phase("commit"):
            try:
                should_commit = self._client.should_commit(
                    self._group_rank,
                    self._step,
                    local_should_commit,
                    timeout=_to_sec(timeout, self._timeout),
                )
            except ConnectionError as e:
                # The vote RPC is non-idempotent (no blind resend — a double-
                # delivered vote could release the barrier with a stale tally),
                # so a broken connection surfaces here.  Abstain: latch the
                # error and treat the step as uncommitted — if the group did
                # commit without us, our step falls behind and the next quorum
                # heals us, the same path as any other failed step.
                self._logger.exception(f"should_commit rpc failed, abstaining: {e}")
                self.report_error(e)
                should_commit = False
        self._m_commits["success" if should_commit else "failure"].inc()
        self._m_participants.set(self.num_participants())
        self._logger.info(
            f"should_commit={should_commit} enough_replicas={enough_replicas}, "
            f"errored={self._errored}"
        )
        log_event(
            "commit",
            "commit vote",
            job_id=env_str("JOB_ID", "unknown"),
            replica_id=self._replica_id,
            rank=self._group_rank,
            quorum_id=self._quorum_id,
            step=self._step,
            commit_result=should_commit,
        )

        # Layout two-phase hook: the barrier outcome decides whether a
        # staged reshard survives into the next quorum's commit round —
        # every local rank observes the same vote, so the whole group
        # either carries the staged epoch or burns it together.
        if self._layout is not None:
            self._layout.on_step_commit(should_commit)

        self._checkpoint_transport.disallow_checkpoint()

        # Raised AFTER the round's root span closes below: the terminally
        # failed round is exactly the one a post-mortem trace needs, and
        # the thread-local context must not leak past the raise.
        retries_exhausted: "Optional[RuntimeError]" = None
        if should_commit:
            self._step += 1
            self._batches_committed += self.num_participants()
            self._commit_failures = 0
            # Serving tier: committed weights become weight version
            # `step` — published at the NEXT round's start / shutdown,
            # after the user's post-commit optimizer update lands
            # (attach_weight_publisher; no-op when unattached).
            self._publish_pending = self._step
            # Durable store: the committed step spills to disk at the
            # NEXT round's start (same timing as publish — the user's
            # post-commit optimizer update must land first so the
            # spilled bytes equal what a live replica at this step
            # holds), off the hot path on the single spill worker.
            self._spill_pending = self._step
        else:
            self._commit_failures += 1
            if (
                self._max_retries is not None
                and self._commit_failures > self._max_retries
            ):
                msg = (
                    f"should_commit failed {self._commit_failures} times "
                    f"consecutively, exceeding max_retries={self._max_retries}"
                )
                self._logger.exception(msg)
                retries_exhausted = RuntimeError(msg)
        self._m_step.set(self._step)
        # step (possibly) advanced: refresh the heartbeat-piggybacked
        # progress so lighthouse step-lag tracking follows commits, not
        # just quorum entries — and ship the step digest (phase deltas +
        # codec/wire busy) for the cluster timeline
        self._report_progress("")
        self._report_step_summary()

        # Close the quorum round's root span (children were emitted per
        # phase by _phase, native rpc.* server spans joined via
        # the shared trace id); the ``step`` attribute is the step the
        # round RAN, matching the trace-id derivation, so the diagnose
        # ledger joins spans, flight dumps, and the lighthouse timeline
        # on one key.
        tracer = tracing.get_tracer()
        ctx, self._round_ctx = self._round_ctx, None
        if tracer is not None and ctx is not None:
            tracer.export_span(
                name="quorum_round",
                trace_id=ctx.trace_id,
                span_id=ctx.span_id,
                start_ns=self._round_start_ns,
                end_ns=time.time_ns(),
                attributes={
                    "replica_id": self._replica_id,
                    "rank": self._group_rank,
                    "quorum_id": self._quorum_id,
                    "step": self._round_step,
                    "commit_result": should_commit,
                },
                ok=self._errored is None and retries_exhausted is None,
            )
        tracing.set_current(None)
        if retries_exhausted is not None:
            raise retries_exhausted
        return should_commit

    # ------------------------------------------------------------------
    # introspection
    # ------------------------------------------------------------------

    def _phase(self, name: str, **attrs: Any) -> tracing.phase:
        """The one way this module times a phase (``PROTOCOL_PHASES``):
        ``tracing.phase`` bound to this Manager's accumulator, histogram
        and identity.  On exit the seconds land in ``phase_times()``, the
        ``torchft_quorum_duration_seconds`` histogram and the flight ring,
        and the span, with its true start and end, under the round's root
        when a tracer is installed; while it is open
        ``torchft.<name>`` shows in a ``jax.profiler`` trace.  Opened on the
        caller thread AND the async quorum thread."""
        return tracing.phase(
            name,
            self._phase_acc,
            observe=self._observe_phase,
            **{
                "replica_id": self._replica_id,
                "quorum_id": self._quorum_id,
                "step": self._step,
                **attrs,
            },
        )

    def _observe_phase(self, name: str, seconds: float) -> None:
        child = self._phase_hist.get(name)
        if child is None:
            # benign race: concurrent creators both resolve to the same
            # underlying child (labels() is keyed), last write wins
            child = metrics.QUORUM_DURATION.labels(
                replica_id=self._metric_replica_id, phase=name
            )
            self._phase_hist[name] = child
        child.observe(seconds)

    def _fold_phases(self, *carried: "Optional[Dict[str, float]]") -> float:
        """Add the seconds the heal transport timed into a dict of its own
        (the heal ``info``'s ``phases`` and ``parts``) to the accumulator.
        Their spans were emitted where they happened: none is emitted
        here.  Returns the seconds of the top-level phases among them."""
        top = 0.0
        for timed in carried:
            for name, seconds in (timed or {}).items():
                tracing.add_seconds(self._phase_acc, name, seconds)
                if not tracing.is_part(name):
                    top += seconds
        return top

    def phase_times(self) -> "Dict[str, float]":
        """Non-destructive snapshot of the cumulative wall-clock seconds
        spent per protocol phase (``PROTOCOL_PHASES``) and per part of one
        (``PHASE_PARTS``).  Safe for any number of concurrent consumers
        (bench takes deltas between snapshots); scrapers should prefer the
        ``torchft_quorum_duration_seconds`` histogram, which the phases
        (not their parts) also feed.

        **A key with a dot is contained in the key before its last dot**
        (``ring.d2h`` is part of ``ring``, ``ring.wire.arrive`` of
        ``ring.wire``): sum the keys without a dot, or
        a part counts against its whole (``tracing.is_part``).

        Caller-thread keys: ``quorum_wait`` (blocked waiting for the async
        quorum work — the part NOT hidden behind the forward pass; includes
        the wait in ``should_commit``), ``host_sync`` (caller-thread
        flatten + zero-fill), ``ring`` (collective submit→completion),
        ``heal_apply`` (the user's ``load_state_dict`` of a healed state:
        host arrays back onto the device), ``commit`` (should_commit RPC
        barrier).

        ``ring`` is opened into its parts on the PG worker thread
        (``ProcessGroupTCP``; they sum to ``ring`` within the thread
        hand-offs): ``ring.queue`` (submit until the worker picks the op
        up), ``ring.d2h`` (the time the worker is held by the
        device→host leg: at world size > 1 the device leaves' copies are
        started ahead of the ring, in the order the buckets ring, and a
        bucket waits only for its own leaves, so it is the sum of those
        waits and not the copies' length; ``overlapped`` = bytes whose copy was complete
        when their bucket asked; ``relaid`` = bytes of leaves laid out flat
        on the device first, because it held them in another order of
        dimensions; at world size 1 ``bytes`` = what left the device, 0 for leaves handed
        back as the ``jax.Array`` they are, and ``kept`` = their bytes),
        ``ring.pack`` (bucket concat, the lease of the ring buffer
        and what is copied into it: a leaf that widens, a zero-padded tail;
        at world size 1 the copy of a leaf the caller passed as host
        memory, a leaf kept on the device counts as handed; its attributes
        say bytes ``copied``, bytes ``handed`` through uncopied and whether
        the buffer was a ``pool`` hit),
        ``ring.wire`` (the 2(w-1) exchanges of each bucket: send + receive
        + waiting for the peer), ``ring.reduce`` (the ufunc between them,
        which is also a chunk's first write into the buffer),
        ``ring.unpack`` (the division by the participant count, in place on
        the ring's buffer, cast back, split, and the unflatten chained
        after the raw collective).

        Async-quorum-thread keys (run inside the executor, so they OVERLAP
        ``quorum_wait`` rather than adding to it — they break down what the
        caller was waiting FOR): ``quorum_rpc`` (the lighthouse-mediated
        quorum round trip), ``pg_configure`` (collective reconfigure on
        quorum change), ``heal_send`` (staging a live checkpoint for a
        recovering peer; per fragment ``heal_send.snapshot`` device→host,
        ``.encode`` the one write of its wire bytes into the buffer that
        serves them, hashed as they land, ``.hash`` the digest's
        finalisation, ``.stage`` reserving and publishing that buffer;
        ``heal_send.copied`` is no seconds but the bytes copied beyond
        that write, 0 when every fragment was staged in place),
        ``heal_manifest`` (fetch of the primary's header, the layout;
        ``heal_manifest.wait`` is the long-poll inside it until the source
        has staged it), ``heal_wire`` (from there until the state is whole:
        the striped fetch, which begins at the header and runs beside the
        sources' encode, the manifest's fetch once it drains, a repair
        pass; the loop's wall less decode and diff;
        ``heal_wire.overlapped`` is no seconds but the fragment bytes that
        had landed when the source made its manifest, 0 when the stripe
        began behind it), ``heal_decode`` (busy
        seconds of fragment decode, one ``heal_decode.fragment`` each),
        ``heal_diff`` (what the healer's digests of its own state, in the
        source's layout, still cost once the manifest is in: they run from
        the header on, a fragment at a time, beside the stripe, on a thread
        of their own, where ``heal_diff.snapshot|hash`` time the work per
        fragment; ``heal_diff.hidden`` is how much of that work had ended
        when the manifest came, 0 where no condition was sent),
        ``heal_recv`` (what those four leave of the receive: metadata RPC,
        source resolution, reassembly; the whole receive on the legacy
        path), ``reshard`` (online-parallelism-switch
        staging: plan + slice-diff transfers into the staged buffer) and
        ``layout_commit`` (the fleet-wide activate/rollback of a staged
        layout at the commit round) — both only with a LayoutController
        attached.
        """
        # tracing.add_seconds is the accumulator's only writer and this its
        # only reader: one copy, atomic under the GIL, and no lock to share
        return dict(self._phase_acc)

    def _report_progress(self, inflight_op: str) -> None:
        """Push (step, in-flight op) to the group's native ManagerServer so
        its lighthouse heartbeats carry per-replica progress (rank 0 only —
        the heartbeat is per replica group).  Best-effort: progress
        telemetry never fails a step."""
        server = self._manager_server
        if server is None:
            return
        try:
            server.report_progress(self._step, inflight_op)
        except Exception:  # noqa: BLE001 - telemetry must not fail the step
            logger.debug("progress report failed", exc_info=True)

    def _report_step_summary(self) -> None:
        """Ship the per-step digest (phase-time deltas since the last
        digest, codec/wire busy seconds from quantized collectives) to the
        native ManagerServer; its next lighthouse heartbeat carries it
        once into the rolling cluster timeline (``/timeline.json``).
        Best-effort like :meth:`_report_progress`."""
        server = self._manager_server
        if server is None:
            return
        acc = self.phase_times()
        with self._summary_lock:
            # the digest is summed by its readers (the timeline's
            # ledger): whole phases only, never a part beside its whole
            phases = {
                k: round((v - self._summary_phase_snapshot.get(k, 0.0)) * 1e3, 3)
                for k, v in acc.items()
                if not tracing.is_part(k)
                and v - self._summary_phase_snapshot.get(k, 0.0) > 0.0
            }
            self._summary_phase_snapshot = acc
            codec_s, self._summary_codec_s = self._summary_codec_s, 0.0
            wire_s, self._summary_wire_s = self._summary_wire_s, 0.0
        try:
            server.report_summary(
                {
                    "step": self._step,
                    "phase_ms": phases,
                    "codec_busy_s": round(codec_s, 6),
                    "wire_busy_s": round(wire_s, 6),
                }
            )
        except Exception:  # noqa: BLE001 - telemetry must not fail the step
            logger.debug("step summary report failed", exc_info=True)
        # Piggyback the fleet link-state digest on the same heartbeat
        # channel (consumed-on-send, like the summary).  maybe_digest
        # rate-limits itself (TORCHFT_LINK_REPORT_S), so this is a no-op
        # on most steps; a faulted or failing report never touches the
        # step path.
        try:
            digest = linkstats.LINKS.maybe_digest(socket.gethostname())
            if digest is not None:
                server.report_links(digest)
        except Exception:  # noqa: BLE001 - telemetry must not fail the step
            logger.debug("link digest report failed", exc_info=True)
        # Same piggyback channel for the fragment provenance digest
        # (ISSUE 18): hand the bounded version-vector digest to the
        # native heartbeat loop, which owns consumed-on-send/restore.
        fdigest = None
        try:
            fdigest = provenance.PROV.maybe_digest(socket.gethostname())
            if fdigest is not None:
                server.report_fragments(fdigest)
        except Exception:  # noqa: BLE001 - telemetry must not fail the step
            provenance.PROV.restore_digest(fdigest)
            logger.debug("fragment digest report failed", exc_info=True)

    def current_step(self) -> int:
        return self._step

    def batches_committed(self) -> int:
        return self._batches_committed

    def participating_rank(self) -> "Optional[int]":
        if self._quorum_future is None:
            return None
        self.wait_quorum()
        return self._participating_replica_rank

    def num_participants(self) -> int:
        if self._quorum_future is None:
            return 0
        self.wait_quorum()
        assert self._participating_replica_world_size >= 0, "internal error"
        return self._participating_replica_world_size

    def is_participating(self) -> bool:
        if self._participating_replica_rank is None:
            return False
        if self._healing:
            assert self._use_async_quorum
            return False
        return True

    def replica_id(self) -> str:
        return self._replica_id

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------

    def shutdown(self, wait: bool = True) -> None:
        """Tear down transport, servers, client and executor.

        The four legs are independent (separate sockets/threads), so they
        shut down CONCURRENTLY: during recovery the replacement replica's
        time-to-healthy includes the dying incarnation's teardown, and the
        serial version's ~40 ms (r4 recovery_phases teardown leg) was the
        second-largest addressable recovery phase.  Reference semantics
        preserved (manager.rs shutdown aborts in one Drop).
        """
        # Final committed step's weight version, if a publisher is
        # attached and the loop ended right after its commit; wait=True
        # drains the publish queue before the transports die.
        self._flush_pending_publish(wait=True)
        # Final committed step spills too (wait=True drains the worker),
        # so a clean shutdown leaves the newest step restorable on disk.
        self._flush_pending_spill(wait=True)
        if self._spiller is not None:
            self._spiller.shutdown()
            self._spiller = None
        legs = [
            lambda: self._checkpoint_transport.shutdown(wait=wait),
            self._client.close,
        ]
        if self._manager_server is not None:
            legs.append(self._manager_server.shutdown)
        if self._owned_store is not None:
            legs.append(self._owned_store.shutdown)
        threads = [
            threading.Thread(target=leg, daemon=True) for leg in legs[1:]
        ]
        for t in threads:
            t.start()
        legs[0]()  # checkpoint transport on the caller thread
        if wait:
            for t in threads:
                t.join(timeout=5.0)
        self._executor.shutdown(wait=wait)

    def __enter__(self) -> "Manager":
        return self

    def __exit__(self, *exc: Any) -> None:
        self.shutdown()
