"""Striped multi-source delta heal (ISSUE 15).

Unit layer: the shared fragment plane's heal encode
(``stage_heal_checkpoint`` — header first, fragments as they encode,
digest manifest last) and the striped receive
(``HTTPTransport.recv_checkpoint_striped`` — disjoint fragment ranges
across every source from the header on, per-fragment failover, a
fragment the healer holds asked for conditionally, ``into=`` buffer
reuse, the manifest last).

Chaos layer: a stripe source killed MID-heal and a poisoned (bitwise-
corrupted) fragment both fail over per-fragment to surviving sources and
the heal completes bitwise — the acceptance property of the striped
rebuild.  The ``transport.heal.frag`` fault site drives the scheduled
variants.

Integration layer: a 3-replica fleet with a mid-run kill heals over the
striped path (multiple stripe sources) and converges bitwise, exactly
like the legacy path it replaced.
"""

import threading
import time

import numpy as np
import pytest

from torchft_tpu.checkpointing import fragments as frags
from torchft_tpu.checkpointing.http_transport import HTTPTransport
from torchft_tpu.utils import faults
from torchft_tpu.utils import metrics as _metrics
from torchft_tpu.utils.faults import FaultRule


@pytest.fixture(autouse=True)
def clean_faults():
    faults.FAULTS.configure([], seed=0)
    yield
    faults.FAULTS.configure([])


def make_state(leaves: int = 12, seed: int = 7) -> dict:
    rng = np.random.default_rng(seed)
    return {
        "user": {
            f"w{i}": rng.standard_normal(257).astype(np.float32)
            for i in range(leaves)
        },
        "torchft": {"step": 5, "batches_committed": 10},
    }


def clone_state(state: dict) -> dict:
    return {
        "user": {k: v.copy() for k, v in state["user"].items()},
        "torchft": dict(state["torchft"]),
    }


def assert_state_equal(a: dict, b: dict) -> None:
    assert a["torchft"] == b["torchft"]
    assert set(a["user"]) == set(b["user"])
    for k in a["user"]:
        np.testing.assert_array_equal(a["user"][k], b["user"][k])


@pytest.fixture
def sources():
    """Three transports, each stream-staging the SAME state at step 5 —
    bitwise-replicated heal sources."""
    state = make_state()
    transports = [HTTPTransport(timeout=10.0) for _ in range(3)]
    threads = [
        threading.Thread(
            target=t.send_checkpoint_streamed,
            args=([1], 5, state, 10.0, 6),
        )
        for t in transports
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    yield state, transports
    for t in transports:
        t.shutdown()


class TestStripedHeal:
    def test_full_heal_striped_bitwise_and_into_reuse(self, sources):
        state, transports = sources
        local = clone_state(state)
        for v in local["user"].values():
            v[:] = 0.0
        local["torchft"] = {"step": 0, "batches_committed": 0}
        retained = {k: v for k, v in local["user"].items()}
        healer = HTTPTransport(timeout=10.0)
        try:
            got, info = healer.recv_checkpoint_striped(
                [t.metadata() for t in transports], 5, timeout=20.0,
                local_state_fn=lambda: local, delta=False,
            )
        finally:
            healer.shutdown()
        assert_state_equal(got, state)
        assert info["mode"] == "full"
        assert info["sources"] == 3
        assert info["changed"] == info["fragments"] == 6
        # decode landed IN the retained buffers (zero-alloc heal path)
        for k, buf in retained.items():
            assert got["user"][k] is buf
        # the phase split is the ledger's heal vocabulary
        assert set(info["phases"]) == {
            "heal_manifest", "heal_diff", "heal_wire", "heal_decode"
        }

    def test_delta_heal_wire_scales_with_changed_fragments(self, sources):
        state, transports = sources
        # rejoiner differs in exactly ONE leaf -> one changed fragment
        local = clone_state(state)
        local["user"]["w3"][:] = -1.0
        before = _metrics.HEAL_WIRE_BYTES.labels(mode="delta").get()
        healer = HTTPTransport(timeout=10.0)
        try:
            got, info = healer.recv_checkpoint_striped(
                [t.metadata() for t in transports], 5, timeout=20.0,
                local_state_fn=lambda: local, delta=True,
            )
        finally:
            healer.shutdown()
        assert_state_equal(got, state)
        assert info["mode"] == "delta"
        # w3's fragment + the torchft scalars' fragment(s) at most; far
        # fewer than all 6 — and the wire carried only those bytes
        assert 1 <= info["changed"] < info["fragments"]
        delta_bytes = (
            _metrics.HEAL_WIRE_BYTES.labels(mode="delta").get() - before
        )
        assert delta_bytes == info["wire_bytes"]
        full_payload = sum(
            v.nbytes for v in state["user"].values()
        )
        assert delta_bytes < full_payload / 2

    def test_delta_identical_state_fetches_nothing(self, sources):
        state, transports = sources
        local = clone_state(state)
        healer = HTTPTransport(timeout=10.0)
        try:
            got, info = healer.recv_checkpoint_striped(
                [t.metadata() for t in transports], 5, timeout=20.0,
                local_state_fn=lambda: local, delta=True,
            )
        finally:
            healer.shutdown()
        assert_state_equal(got, state)
        assert info["changed"] == 0
        assert info["wire_bytes"] == 0

    def test_kill_stripe_source_mid_heal(self, sources):
        state, transports = sources
        # Stretch every fragment fetch well past the kill delay: the
        # victim's in-flight fragments are guaranteed to still be in
        # flight when it dies, so the per-fragment failover MUST fire.
        faults.FAULTS.configure(
            [FaultRule(site="transport.heal.frag", action="delay",
                       delay=0.15, times=100)],
            seed=0,
        )
        local = clone_state(state)
        for v in local["user"].values():
            v[:] = 0.0
        killer = threading.Timer(0.05, transports[2].shutdown)
        killer.start()
        healer = HTTPTransport(timeout=10.0)
        try:
            got, info = healer.recv_checkpoint_striped(
                [t.metadata() for t in transports], 5, timeout=30.0,
                local_state_fn=lambda: local, delta=False,
            )
        finally:
            killer.cancel()
            healer.shutdown()
        assert_state_equal(got, state)
        # the dead source's fragments moved to the survivors
        assert info["failovers"] >= 1
        # the delay pacing guarantees every worker held work before any
        # fetch completed, so BOTH survivors delivered fragments
        assert info["sources_used"] >= 2
        assert _metrics.HEAL_STRIPE_SOURCES.get() >= 2
        assert faults.FAULTS.injected("transport.heal.frag") > 0

    def test_dead_source_from_start_fails_over(self, sources):
        state, transports = sources
        # Six tiny fragments and six stripe workers, started one after the
        # other: on a loaded host the primary's two drain the queue before
        # the dead source's have started, and then nothing ever fails over
        # (36 of 40 runs beside 12 busy processes, none with a fetch sent
        # to the dead address).  Stretch every fetch, as the mid-heal kill
        # above does, so that every worker holds a fragment before any
        # fetch completes: the dead source's two MUST fail over.
        faults.FAULTS.configure(
            [FaultRule(site="transport.heal.frag", action="delay",
                       delay=0.3, times=100)],
            seed=0,
        )
        dead = HTTPTransport(timeout=5.0)
        dead_addr = dead.metadata()
        dead.shutdown()
        local = clone_state(state)
        for v in local["user"].values():
            v[:] = 0.0
        before = _metrics.HEAL_FRAG_FAILOVERS.get()
        healer = HTTPTransport(timeout=10.0)
        try:
            got, info = healer.recv_checkpoint_striped(
                [transports[0].metadata(), dead_addr,
                 transports[1].metadata()],
                5, timeout=30.0,
                local_state_fn=lambda: local, delta=False,
            )
        finally:
            healer.shutdown()
        assert_state_equal(got, state)
        assert info["failovers"] >= 1
        assert _metrics.HEAL_FRAG_FAILOVERS.get() > before

    @pytest.mark.parametrize("delta", [True, False])
    def test_poisoned_fragment_fails_over_and_never_lands(
        self, sources, delta
    ):
        state, transports = sources
        # bitwise-corrupt one fragment's staged bytes on a NON-primary
        # source: its sha256 no longer matches the primary's manifest.
        # Restage through the transport API so the poison lands in BOTH
        # data planes (the Python slot and the native zero-copy mirror).
        victim = transports[1]
        with victim._staged_lock.r_lock():
            raw = bytearray(victim._staged[5].sd["frag:2"])
        raw[len(raw) // 2] ^= 0xFF
        victim.stage_streamed_part(5, "frag:2", bytes(raw))
        local = clone_state(state)
        for v in local["user"].values():
            v[:] = 0.0
        healer = HTTPTransport(timeout=10.0)
        try:
            got, info = healer.recv_checkpoint_striped(
                [t.metadata() for t in transports], 5, timeout=30.0,
                local_state_fn=lambda: local, delta=delta,
            )
        finally:
            healer.shutdown()
        # the healed state is bitwise the fleet's, never the poison
        assert_state_equal(got, state)

    def test_forged_slot_fragment_cannot_contaminate_other_slots(
        self, sources
    ):
        """A corrupt fragment whose bytes DECODE but claim FOREIGN leaf
        slots must not overwrite other fragments' leaves (full mode
        decodes before the deferred verify): the slot-layout check
        rejects it and the repair pass restores it from the primary."""
        from torchft_tpu.checkpointing import serialization as ser

        state, transports = sources
        victim = transports[1]
        # forge EVERY fragment on the victim as a VALID serialized
        # stream claiming slot 0 (fragment 0's territory) with a
        # poisoned value — whatever the dynamic stripe routes to the
        # victim decodes fine but fails the slot-layout check
        forged = ser.serialize({"0": np.full(3, -777.0, dtype=np.float32)})
        for i in range(6):
            # transport API restage: forges Python slot + native mirror
            victim.stage_streamed_part(5, f"frag:{i}", forged)
        # pace fetches so every worker pops before any completes: the
        # victim's workers are guaranteed to hold (forged) fragments
        faults.FAULTS.configure(
            [FaultRule(site="transport.heal.frag", action="delay",
                       delay=0.02, times=100)],
            seed=0,
        )
        local = clone_state(state)
        for v in local["user"].values():
            v[:] = 0.0
        healer = HTTPTransport(timeout=10.0)
        try:
            got, info = healer.recv_checkpoint_striped(
                [t.metadata() for t in transports], 5, timeout=30.0,
                local_state_fn=lambda: local, delta=False,
            )
        finally:
            healer.shutdown()
        # every leaf bitwise — the forged slot-0 writes never survive
        # (rejected fragments repaired digest-verified from the primary)
        assert_state_equal(got, state)
        assert info["failovers"] >= 1

    def test_poisoned_primary_fragment_heals_from_peers(self, sources):
        state, transports = sources
        primary = transports[0]
        with primary._staged_lock.r_lock():
            raw = bytearray(primary._staged[5].sd["frag:1"])
        raw[0] ^= 0xFF
        primary.stage_streamed_part(5, "frag:1", bytes(raw))
        local = clone_state(state)
        healer = HTTPTransport(timeout=10.0)
        try:
            got, info = healer.recv_checkpoint_striped(
                [t.metadata() for t in transports], 5, timeout=30.0,
                local_state_fn=lambda: local, delta=True,
            )
        finally:
            healer.shutdown()
        # delta mode verifies on receipt: the primary's corrupt bytes are
        # rejected against its OWN manifest and the fragment heals from a
        # bitwise-replicated peer
        assert_state_equal(got, state)

    def test_injected_fragment_drop_absorbed_by_retry(self, sources):
        state, transports = sources
        faults.FAULTS.configure(
            [FaultRule(site="transport.heal.frag", action="drop", times=2)],
            seed=0,
        )
        local = clone_state(state)
        healer = HTTPTransport(timeout=10.0)
        try:
            got, info = healer.recv_checkpoint_striped(
                [t.metadata() for t in transports], 5, timeout=30.0,
                local_state_fn=lambda: local, delta=False,
            )
        finally:
            healer.shutdown()
        assert_state_equal(got, state)
        assert faults.FAULTS.injected("transport.heal.frag") == 2


class TestHealStagingLifecycle:
    def test_streamed_slot_survives_one_commit_round(self):
        """Streamed heal slots hold immutable bytes, so they get ONE
        round of disallow_checkpoint grace — a striped healer's
        multi-request window stays open across the sources' commit —
        and retire on the second round (nothing lingers unbounded).
        Legacy slots still retire immediately."""
        state = make_state(leaves=2)
        t = HTTPTransport(timeout=5.0)
        try:
            t.send_checkpoint_streamed([1], 7, state, timeout=5.0)
            t.send_checkpoint([1], 8, state, timeout=5.0)
            assert set(t.staged_steps()) == {7, 8}
            t.disallow_checkpoint()
            assert t.staged_steps() == [7]  # legacy slot retired at once
            t.disallow_checkpoint()
            assert t.staged_steps() == []
        finally:
            t.shutdown()

    def test_header_serves_before_encode_finishes(self):
        """Cut-through contract: the digest-less header (and every
        already-staged fragment) serves while the source is still
        encoding; whole-document reads 503 until the manifest lands."""
        import urllib.error

        state = make_state(leaves=4)
        t = HTTPTransport(timeout=5.0)
        try:
            header, frag_iter = frags.iter_heal_fragments(state, 4)
            t.begin_streamed_checkpoint(
                9, {"frag:header": dict(header, version=9)}
            )
            name, raw, digest = next(frag_iter)
            t.stage_streamed_part(9, f"frag:{name}", raw)

            t0 = time.monotonic()
            hbuf = frags.fetch_raw(t.metadata(), 9, "frag_header", 10.0,
                                   role="heal")
            # at once, not when the budget runs out: the native data plane
            # holds no header and would park the request till the version
            # completes, so the header is never asked of it
            assert time.monotonic() - t0 < 2.0
            got_header = frags.decode_manifest(hbuf)
            assert got_header["fragments"] == ["0", "1", "2", "3"]
            assert "digests" not in got_header
            fbuf = frags.fetch_raw(t.metadata(), 9, "frag_0", 2.0,
                                   role="heal")
            assert bytes(memoryview(fbuf)) == bytes(memoryview(raw))
            with pytest.raises((urllib.error.HTTPError, TimeoutError)):
                frags.fetch_raw(t.metadata(), 9, "full", 0.3, role="heal")
        finally:
            t.shutdown()

    def test_legacy_source_falls_back_to_whole_document(self):
        """A source that staged the legacy whole-document snapshot
        serves a striped healer via the classic full fetch (mixed-config
        fleet): frag_header 404s and the striped receive falls back."""
        state = make_state(leaves=3)
        t = HTTPTransport(timeout=5.0)
        healer = HTTPTransport(timeout=5.0)
        try:
            t.send_checkpoint([1], 4, state, timeout=5.0)
            got, info = healer.recv_checkpoint_striped(
                [t.metadata()], 4, timeout=10.0,
                local_state_fn=None, delta=False,
            )
            assert info["mode"] == "legacy"
            assert_state_equal(got, state)
        finally:
            healer.shutdown()
            t.shutdown()

    def test_into_fallback_is_counted_not_silent(self):
        """Satellite: a failing state_dict_fn no longer silently
        disables the warm-buffer receive — it logs and counts
        torchft_heal_into_fallbacks_total."""
        state = make_state(leaves=2)
        src = HTTPTransport(timeout=5.0)
        before = _metrics.HEAL_INTO_FALLBACKS.get()

        def broken_state():
            raise RuntimeError("user state fn exploded")

        healer = HTTPTransport(timeout=5.0, state_dict_fn=broken_state)
        try:
            src.send_checkpoint_streamed([1], 3, state, timeout=5.0)
            got, info = healer.recv_checkpoint_striped(
                [src.metadata()], 3, timeout=10.0, delta=False,
            )
            assert_state_equal(got, state)
            assert _metrics.HEAL_INTO_FALLBACKS.get() == before + 1
        finally:
            healer.shutdown()
            src.shutdown()

    def test_local_digest_layout_matches_staged(self):
        """local_fragment_digests must produce EXACTLY the digests a
        source stages for the same state — the delta diff's soundness."""
        state = make_state(leaves=5)
        t = HTTPTransport(timeout=5.0)
        try:
            manifest = t.send_checkpoint_streamed([1], 2, state,
                                                  timeout=5.0, fragments=4)
            _n, mine = frags.local_fragment_digests(state, 4)
            assert mine == manifest["digests"]
        finally:
            t.shutdown()


    @pytest.mark.parametrize(
        "case",
        [
            "float32", "bfloat16", "zero_d", "empty", "non_contiguous",
            "jax_array", "python_leaf", "more_fragments_than_leaves",
        ],
    )
    def test_streamed_digest_is_the_wire_bytes_digest(self, case):
        """The healer hashes its state in place: per fragment, the digest
        streamed through ``serialization.prepare``'s writer is that of the
        bytes ``serialize`` would have built, and the one a source stages."""
        import hashlib

        import jax
        import jax.numpy as jnp

        from torchft_tpu.checkpointing import serialization as ser

        rng = np.random.default_rng(3)
        w = rng.standard_normal((6, 40)).astype(np.float32)
        state = {f"w{i}": w * (i + 1) for i in range(5)}
        fragments = 3
        if case == "bfloat16":
            state["w1"] = np.asarray(jnp.asarray(w, jnp.bfloat16))
            assert state["w1"].dtype.name == "bfloat16"
        elif case == "zero_d":
            state["w1"] = np.float32(2.5) * np.ones((), np.float32)
            assert state["w1"].shape == ()
        elif case == "empty":
            state["w1"] = np.empty((0, 7), np.float32)
        elif case == "non_contiguous":
            state["w1"] = w.T  # a view in the other order of dimensions
            state["w2"] = w[:, ::3]
            assert not state["w1"].flags.c_contiguous
        elif case == "jax_array":
            state["w1"] = jnp.asarray(w)
            state["w3"] = jnp.asarray(w, jnp.bfloat16)
            assert isinstance(state["w1"], jax.Array)
        elif case == "python_leaf":
            state.update(step=5, name="replica", lr=1e-3)
        elif case == "more_fragments_than_leaves":
            fragments = 9

        n, mine = frags.local_fragment_digests(state, fragments)
        leaves = jax.tree_util.tree_flatten(state)[0]
        assert n == len(leaves)
        names = frags.heal_fragment_names(n, fragments)
        assert list(mine) == names and len(names) == min(fragments, n)
        for name in names:
            frag = {
                str(slot): (
                    np.asarray(leaves[slot])
                    if isinstance(leaves[slot], jax.Array)
                    else leaves[slot]
                )
                for slot in frags.fragment_slots(name, n, len(names))
            }
            assert mine[name] == hashlib.sha256(ser.serialize(frag)).hexdigest()
        t = HTTPTransport(timeout=5.0)
        try:
            manifest = t.send_checkpoint_streamed(
                [1], 2, state, timeout=5.0, fragments=fragments
            )
            assert mine == manifest["digests"]
        finally:
            t.shutdown()


def _one_write_state(kind: str) -> dict:
    """A state of four leaves with the leaf under test among them."""
    import jax.numpy as jnp
    import ml_dtypes

    rng = np.random.default_rng(3)
    w = rng.standard_normal(1031).astype(np.float32)
    leaf = {
        "float32": w,
        "bfloat16": jnp.asarray(w, jnp.bfloat16),  # a jax.Array: snapshotted
        "float8": w.astype(ml_dtypes.float8_e4m3fn),
        "zero_d": np.float32(2.5) * np.ones((), np.float32),
        "object": {"note": "inline in the header", "lr": 1e-3},
        "empty": np.zeros((0, 7), np.float32),
        # not a whole number of the sink's blocks, so a short last one
        "over_a_block": rng.standard_normal(
            frags._SINK_BLOCK // 4 + 12_345
        ).astype(np.float32),
        # as a TPU hands over a leaf whose last dimension is no multiple
        # of 128: each matrix column by column (strides, not a copy); the
        # first over a block, so re-ordered in several stretches of rows
        "device_order": rng.standard_normal((577, 3001)).astype(np.float32).T,
        "device_order_stack": rng.standard_normal((3, 37, 130)).astype(
            ml_dtypes.bfloat16
        ).transpose(0, 2, 1),
        # some other order of memory: no kernel for it, numpy's copy
        "strided": rng.standard_normal((40, 50)).astype(np.float32)[::2, ::5],
    }[kind]
    return {"a": w[:100].copy(), "b": leaf, "c": np.arange(9), "step": 3}


def _fragments_by_hand(state: dict, fragments: int) -> dict:
    """``{name: serialize(fragment)}``: the wire bytes as the old road
    built them, through ``ser.serialize``'s ``BytesIO``."""
    import jax

    from torchft_tpu.checkpointing import serialization as ser

    leaves = jax.tree_util.tree_flatten(state)[0]
    names = frags.heal_fragment_names(len(leaves), fragments)
    return {
        name: ser.serialize({
            str(slot): (
                np.asarray(leaves[slot])
                if isinstance(leaves[slot], jax.Array)
                else leaves[slot]
            )
            for slot in frags.fragment_slots(name, len(leaves), len(names))
        })
        for name in names
    }


class TestOneWrite:
    """ISSUE 45: a source writes each fragment's wire bytes once, into the
    buffer they are served from (the native server's, lent; a ``bufpool``
    buffer with no native plane), and hashes them as they land.  Nothing
    about the bytes or the digests may differ from ``ser.serialize``."""

    KINDS = (
        "float32", "bfloat16", "float8", "zero_d", "object", "empty",
        "over_a_block", "device_order", "device_order_stack", "strided",
    )

    @pytest.mark.parametrize("plane", ["native", "python"])
    @pytest.mark.parametrize("kind", KINDS)
    def test_digest_and_served_bytes_are_serialize(
        self, kind, plane, monkeypatch
    ):
        import hashlib

        from torchft_tpu.checkpointing import fragdata

        assert fragdata.available(), "the native library could not be loaded"
        if plane == "python":
            # no native library at all: numpy re-orders what lies otherwise
            monkeypatch.setattr(fragdata, "available", lambda: False)
        fragdata.reset_port_cache()
        state = _one_write_state(kind)
        if kind.startswith("device_order"):
            assert frags._stored_swapped(state["b"]) is not None
            assert not state["b"].flags.c_contiguous
        want = _fragments_by_hand(state, 2)
        t = HTTPTransport(timeout=5.0)
        try:
            assert (t._frag_native is not None) == (plane == "native")
            manifest = t.send_checkpoint_streamed(
                [1], 4, state, timeout=5.0, fragments=2
            )
            assert list(manifest["digests"]) == list(want)
            # a healer's digests of the same state: the same sink, no bytes
            assert frags.local_fragment_digests(state, 2)[1] == manifest["digests"]
            for name, raw in want.items():
                assert manifest["digests"][name] == hashlib.sha256(
                    raw
                ).hexdigest()
                # the slot holds the ONE buffer: no bytes object beside it
                held = t._staged[4].sd[f"frag:{name}"]
                assert isinstance(held, np.ndarray) and held.dtype == np.uint8
                assert bytes(memoryview(held)) == raw
                buf = frags.fetch_raw(
                    t.metadata(), 4, f"frag_{name}", 5.0, role="heal"
                )
                assert bytes(memoryview(buf)) == raw
            if plane == "native":
                c = t._frag_native.counters()
                assert c["stage_copy_bytes"] == 0
                assert c["stage_inplace_bytes"] == sum(map(len, want.values()))
        finally:
            t.shutdown()
            fragdata.reset_port_cache()

    @pytest.mark.parametrize("engaged", [True, False])
    def test_copied_counts_what_was_not_staged_in_place(
        self, engaged, monkeypatch
    ):
        """The counter that says the mechanism engaged: over a streamed
        heal the native server copies nothing and ``heal_send.copied``
        reads 0; with no buffer to reserve there every byte is copied
        once more and both say so."""
        from torchft_tpu.checkpointing import fragdata
        from torchft_tpu.utils import tracing

        assert fragdata.available(), "the native library could not be loaded"
        if not engaged:
            monkeypatch.setattr(
                fragdata.FragDataServer, "reserve", lambda *a, **k: None
            )
        state = make_state()
        t = HTTPTransport(timeout=5.0)
        healer = HTTPTransport(timeout=5.0)
        sink: dict = {}
        try:
            with tracing.phase("heal_send", sink):
                manifest = t.send_checkpoint_streamed(
                    [1], 6, state, timeout=5.0, fragments=4
                )
            wire = sum(
                memoryview(t._staged[6].sd[f"frag:{n}"]).nbytes
                for n in manifest["fragments"]
            )
            c = t._frag_native.counters()
            assert c["stage_copy_bytes"] == (0 if engaged else wire)
            assert c["stage_inplace_bytes"] == (wire if engaged else 0)
            assert sink["heal_send.copied"] == (0 if engaged else wire)
            # the names the layer metrics read are all still there
            assert {
                "heal_send.snapshot", "heal_send.encode", "heal_send.hash",
                "heal_send.stage",
            } <= set(sink)
            assert sink["heal_send.encode"] > sink["heal_send.hash"]
            # either way a healer gets the source's bytes
            got, _info = healer.recv_checkpoint_striped(
                [t.metadata()], 6, timeout=10.0
            )
            assert_state_equal(got, state)
        finally:
            healer.shutdown()
            t.shutdown()

    @pytest.mark.parametrize("itemsize", [1, 2, 4, 8])
    def test_the_kernel_reorders_any_stretch_of_rows(self, itemsize):
        """``fragdata.copy_transposed`` against numpy, for every item size
        it has a kernel for, on stretches that start and end inside tiles;
        and what ``_stored_swapped`` takes for the device's order."""
        from torchft_tpu.checkpointing import fragdata

        assert fragdata.available(), "the native library could not be loaded"
        dtype = {1: np.uint8, 2: np.uint16, 4: np.float32, 8: np.float64}[itemsize]
        rng = np.random.default_rng(itemsize)
        arr = (rng.random((2, 3, 45, 203)) * 250).astype(dtype).transpose(0, 1, 3, 2)
        assert frags._stored_swapped(arr) == (6, 203, 45)
        want = np.ascontiguousarray(arr).reshape(6, 203, 45)
        for matrix, row, rows in [(0, 0, 203), (5, 0, 1), (3, 64, 64), (4, 7, 190)]:
            dst = np.zeros(rows * 45 * itemsize, np.uint8)
            fragdata.copy_transposed(dst, arr, matrix, row, rows)
            assert dst.tobytes() == want[matrix, row:row + rows].tobytes()
        with pytest.raises(ValueError):
            fragdata.copy_transposed(dst, arr, 6, 0, 190)  # no such matrix
        with pytest.raises(ValueError):
            fragdata.copy_transposed(dst[:-1], arr, 0, 0, 190)  # short
        for other in (
            want,  # C order
            want[:, ::2],  # rows skipped
            arr[:, :, :, ::3],  # columns skipped
            np.zeros((0, 4), dtype).T,  # nothing
            np.arange(5, dtype=dtype),  # one dimension
        ):
            assert frags._stored_swapped(other) is None

    def test_iterator_hands_the_store_a_buffer_it_round_trips(self, tmp_path):
        """``iter_heal_fragments``' second consumer: the durable store
        writes the ``uint8`` buffer (no ``bytes`` any more) to disk and
        loads the state back bitwise."""
        import hashlib

        from torchft_tpu.checkpointing.store import FragmentStore

        state = _one_write_state("bfloat16")
        header, it = frags.iter_heal_fragments(state, 3)
        for name, raw, digest in it:
            assert isinstance(raw, np.ndarray) and raw.dtype == np.uint8
            assert raw.ndim == 1 and raw.flags.c_contiguous
            assert digest == hashlib.sha256(raw).hexdigest()
        store = FragmentStore(str(tmp_path))
        manifest = store.put_state(11, state, fragments=3)
        want = _fragments_by_hand(state, 3)
        for name, raw in want.items():
            assert store.fragment(11, name) == raw
        back = FragmentStore(str(tmp_path)).load_state(store.manifest(11))
        assert back["step"] == 3 and manifest["digests"].keys() == want.keys()
        for k in ("a", "b", "c"):
            assert np.asarray(back[k]).tobytes() == np.asarray(state[k]).tobytes()
            assert np.asarray(back[k]).dtype == np.asarray(state[k]).dtype

    def test_a_healer_that_knows_only_the_wire_format_heals(self):
        """Wire and manifest are the parent commit's: a healer built
        before this source changed reads the header, the manifest and the
        fragments with ``deserialize`` and ``sha256`` alone (plain HTTP
        against the control server, nothing of this tree's fetch plane)
        and holds the source's state."""
        import hashlib
        import urllib.request

        from torchft_tpu.checkpointing import serialization as ser

        state = make_state()
        t = HTTPTransport(timeout=5.0)

        def get(resource: str) -> bytes:
            with urllib.request.urlopen(
                f"{t.metadata()}/checkpoint/8/{resource}", timeout=5.0
            ) as resp:
                return resp.read()

        try:
            t.send_checkpoint_streamed([1], 8, state, timeout=5.0, fragments=5)
            header = ser.deserialize(get("frag_header"))
            manifest = ser.deserialize(get("frag_manifest"))
            assert set(header) == {
                "wire", "fragments", "skeleton", "num_leaves", "version",
            }
            assert set(manifest) == set(header) | {"digests", "created_ns"}
            assert manifest["wire"] == "f32" and manifest["version"] == 8
            leaves = {}
            for name in manifest["fragments"]:
                raw = get(f"frag_{name}")
                assert hashlib.sha256(raw).hexdigest() == manifest["digests"][name]
                leaves.update(
                    {int(k): v for k, v in ser.deserialize(raw).items()}
                )
            assert_state_equal(frags.assemble(manifest, leaves), state)
        finally:
            t.shutdown()


def _stage_by_hand(
    t, step, state, fragments, delay, header_fragments=None, manifest=True
):
    """A source's streamed staging, call by call: the header at once, every
    fragment, and the digest manifest ``delay`` seconds later.
    ``header_fragments``: the header shows another layout than the manifest
    then confirms.  ``manifest=False``: the source never gets that far."""
    header, frag_iter = frags.iter_heal_fragments(state, fragments)
    shown = header
    if header_fragments is not None:
        shown = frags.iter_heal_fragments(state, header_fragments)[0]
    t.begin_streamed_checkpoint(
        step, {f"frag:{frags.HEADER_FRAG}": dict(shown, version=step)}
    )
    digests = {}
    for name, raw, digest in frag_iter:
        t.stage_streamed_part(step, f"frag:{name}", raw, digest=digest)
        digests[name] = digest
    time.sleep(delay)
    if not manifest:
        return
    t.stage_streamed_part(
        step, f"frag:{frags.MANIFEST_FRAG}",
        dict(header, version=step, digests=digests,
             created_ns=time.time_ns()),
    )
    t.finish_streamed_checkpoint(step)


def _call_threads():
    """The live threads but an HTTP server's own (the sources are servers
    in this process: one daemon thread a connection kept alive)."""
    return {
        t for t in threading.enumerate()
        if "process_request_thread" not in t.name
        and "serve_forever" not in t.name
    }


class TestDigestsDuringTheWait:
    """ISSUE 42, in ISSUE 51's order: the healer takes the layout from the
    header, which a source stages before it encodes anything, and hashes
    its own state on a thread of its own, a fragment at a time, beside the
    stripe that begins there too: fragment n is asked for "unless it hashes
    to mine" when its digest is handed over.  The manifest comes last.
    ``heal_diff`` is what the digests still cost once it is in,
    ``heal_diff.hidden`` the digest work that had ended by then (all of
    it: every request waited for its digest)."""

    DELAY = 1.5
    FRAGMENTS = 4

    @staticmethod
    def big_state(seed: int = 11) -> dict:
        rng = np.random.default_rng(seed)
        return {
            "user": {
                f"w{i}": rng.standard_normal(500_000).astype(np.float32)
                for i in range(8)
            },
            "torchft": {"step": 5, "batches_committed": 10},
        }

    def heal(self, state, local, stage_kw=None, timeout=20.0, **recv_kw):
        src = HTTPTransport(timeout=10.0)
        healer = HTTPTransport(timeout=10.0)
        before = _call_threads()
        stager = threading.Thread(
            target=_stage_by_hand,
            args=(src, 5, state, self.FRAGMENTS, self.DELAY),
            kwargs=stage_kw or {},
        )
        stager.start()
        try:
            return healer.recv_checkpoint_striped(
                [src.metadata()], 5, timeout=timeout,
                local_state_fn=lambda: local, **recv_kw,
            )
        finally:
            stager.join(timeout=30.0)
            assert not stager.is_alive()
            # no thread of the call outlives it, however it ended
            left = _call_threads() - before - {stager}
            assert not left, sorted(t.name for t in left)
            healer.shutdown()
            src.shutdown()

    @pytest.mark.parametrize("differing", [0, 1])
    def test_digests_run_beside_the_stripe(self, differing, monkeypatch):
        # digests that take a third of the source's encode at least: they
        # end inside it only if they began with the header
        real = frags.iter_local_fragment_digests

        def slow(state_dict, fragments):
            time.sleep(self.DELAY / 3)
            yield from real(state_dict, fragments)

        monkeypatch.setattr(frags, "iter_local_fragment_digests", slow)
        state = self.big_state()
        local = clone_state(state)
        if differing:
            local["user"]["w3"][:] = -1.0
        got, info = self.heal(state, local, delta=True)
        assert_state_equal(got, state)
        assert info["mode"] == "delta" and info["failovers"] == 0
        # w3's fragment alone crosses the wire, or nothing does
        assert info["changed"] == differing
        assert (info["wire_bytes"] > 0) == bool(differing)
        if differing:
            assert info["wire_bytes"] < sum(
                v.nbytes for v in state["user"].values()
            ) / 2
        parts, phases = info["parts"], info["phases"]
        work = parts["heal_diff.snapshot"] + parts["heal_diff.hash"]
        assert "heal_diff.encode" not in parts
        # the mechanism engaged whole: the work was over when the manifest
        # came, and the recovery paid a small share of the encode for it
        assert info["hidden"] == parts["heal_diff.hidden"]
        assert 0 < info["hidden"] == pytest.approx(work)
        assert phases["heal_diff"] < 0.1 * self.DELAY
        # the new order: the header alone is waited for before the stripe,
        # which then lasts until the source's manifest is staged
        assert phases["heal_manifest"] < 0.5 * self.DELAY
        assert parts["heal_manifest.wait"] <= phases["heal_manifest"]
        assert phases["heal_wire"] >= 0.5 * self.DELAY
        # what crossed had landed before the source made its manifest
        assert info["overlapped"] == parts["heal_wire.overlapped"]
        assert info["overlapped"] == info["wire_bytes"]

    def test_legacy_source_has_no_header_and_hides_nothing(self):
        state = self.big_state()
        src = HTTPTransport(timeout=5.0)
        healer = HTTPTransport(timeout=5.0)
        before = _call_threads()
        try:
            src.send_checkpoint([1], 5, state, timeout=5.0)
            got, info = healer.recv_checkpoint_striped(
                [src.metadata()], 5, timeout=10.0,
                local_state_fn=lambda: clone_state(state), delta=True,
            )
            assert not _call_threads() - before
        finally:
            healer.shutdown()
            src.shutdown()
        assert info["mode"] == "legacy" and info["hidden"] == 0.0
        assert info["phases"] == {} and info["overlapped"] == 0
        assert_state_equal(got, state)

    def test_full_mode_and_no_local_state_hide_nothing(self):
        state = self.big_state()
        got, info = self.heal(state, clone_state(state), delta=False)
        assert_state_equal(got, state)
        assert info["mode"] == "full"
        assert info["hidden"] == info["parts"]["heal_diff.hidden"] == 0.0
        assert "heal_diff.hash" not in info["parts"]

    def test_manifest_of_another_layout_repairs_everything(self):
        """The manifest defines truth: what was taken or kept under a
        layout it does not confirm is thrown away, and every fragment of
        the manifest's layout comes again, verified on receipt."""
        state = self.big_state()
        local = clone_state(state)
        local["user"]["w3"][:] = -1.0
        got, info = self.heal(
            state, local, stage_kw={"header_fragments": 3}, delta=True
        )
        assert_state_equal(got, state)
        assert info["mode"] == "delta"
        assert info["fragments"] == info["changed"] == self.FRAGMENTS
        assert info["failovers"] >= self.FRAGMENTS
        assert info["hidden"] == info["parts"]["heal_diff.hidden"] == 0.0

    def test_an_error_in_the_digests_surfaces(self, monkeypatch):
        def broken(state_dict, fragments):
            yield from ()
            raise RuntimeError("digest worker exploded")

        monkeypatch.setattr(frags, "iter_local_fragment_digests", broken)
        state = self.big_state()
        with pytest.raises(RuntimeError, match="digest worker exploded"):
            self.heal(state, clone_state(state), delta=True)

    def test_primary_dying_during_the_wait_fails_the_call(self):
        """The call raises (the Manager reports the error and the next
        quorum assigns a source), here while it waits for the manifest,
        its stripe drained; the digests are joined, not left behind."""
        state = self.big_state()
        src = HTTPTransport(timeout=10.0)
        healer = HTTPTransport(timeout=10.0)
        before = _call_threads()
        _stage_by_hand(src, 5, state, self.FRAGMENTS, 0.0, manifest=False)
        killer = threading.Timer(0.3, src.shutdown)
        killer.start()
        t0 = time.monotonic()
        try:
            with pytest.raises((OSError, TimeoutError)):
                healer.recv_checkpoint_striped(
                    [src.metadata()], 5, timeout=2.0,
                    local_state_fn=lambda: clone_state(state), delta=True,
                )
            assert time.monotonic() - t0 < 10.0
            killer.join(timeout=10.0)
            left = _call_threads() - before - {killer}
            assert not left, sorted(t.name for t in left)
        finally:
            killer.cancel()
            healer.shutdown()
            src.shutdown()


class GatedSource:
    """A source whose staging the test gates, one fragment at a time: the
    header at once, ``stage()`` the next fragment (or all that are left)
    as ``stage_heal_checkpoint`` would, under its digest, and ``finish()``
    the manifest, last.  ``lie``: ``{name: digest}`` to stage a fragment
    under instead of its own; ``poison``: names whose bytes are corrupted
    (their digest stays the clean one: a source cannot know)."""

    def __init__(self, state, step, fragments, lie=None, poison=()):
        self.t = HTTPTransport(timeout=10.0)
        self.step, self.lie, self.poison = step, lie or {}, set(poison)
        self.header, self._iter = frags.iter_heal_fragments(state, fragments)
        self.header = dict(self.header, version=step)
        self.digests: dict = {}
        self.nbytes: dict = {}
        self.t.begin_streamed_checkpoint(
            step, {f"frag:{frags.HEADER_FRAG}": self.header}
        )

    def stage(self, count=None):
        staged = []
        for name, raw, digest in self._iter:
            self.digests[name], self.nbytes[name] = digest, raw.nbytes
            if name in self.poison:
                raw = raw.copy()
                raw[raw.nbytes // 2] ^= 0xFF
            self.t.stage_streamed_part(
                self.step, f"frag:{name}", raw,
                digest=self.lie.get(name, digest),
            )
            staged.append(name)
            if count is not None and len(staged) >= count:
                break
        return staged

    def finish(self):
        self.stage()
        self.t.stage_streamed_part(
            self.step, f"frag:{frags.MANIFEST_FRAG}",
            dict(self.header, digests=self.digests,
                 created_ns=time.time_ns()),
        )
        self.t.finish_streamed_checkpoint(self.step)

    def shutdown(self):
        self.t.shutdown()


def _plane(plane, monkeypatch):
    """``python``: a fleet without the native data plane (the transports
    built after this hold no native server, and nobody asks for one)."""
    from torchft_tpu.checkpointing import fragdata

    fragdata.reset_port_cache()
    if plane == "python":
        monkeypatch.setattr(fragdata, "enabled", lambda: False)


def _every_worker_holds_a_fragment(delay=0.3):
    """Stretch every fetch so that each stripe worker has taken a fragment
    before any fetch completes (as the kill tests above do)."""
    faults.FAULTS.configure(
        [FaultRule(site="transport.heal.frag", action="delay",
                   delay=delay, times=1000)],
        seed=0,
    )


class TestStripeFromTheHeader:
    """ISSUE 51: one path.  A healer's stripe begins at the header, beside
    its sources' encode, whether or not it has state of its own: with it,
    fragment n is asked for "unless it hashes to my digest of n", and the
    source, which knows n's digest the moment it stages n, answers "same"
    or sends the bytes.  The manifest comes last and defines truth."""

    FRAGMENTS = 4

    @staticmethod
    def state(seed: int = 11, n: int = 100_000) -> dict:
        rng = np.random.default_rng(seed)
        return {
            "user": {
                f"w{i}": rng.standard_normal(n).astype(np.float32)
                for i in range(8)
            },
            "torchft": {"step": 5, "batches_committed": 10},
        }

    @staticmethod
    def recv_in_thread(healer, bases, local, **kw):
        from concurrent.futures import ThreadPoolExecutor

        ex = ThreadPoolExecutor(1)
        fut = ex.submit(
            healer.recv_checkpoint_striped, bases, 5, timeout=30.0,
            local_state_fn=(lambda: local) if local is not None else None,
            **kw,
        )
        ex.shutdown(wait=False)
        return fut

    @pytest.mark.parametrize("plane", ["native", "python"])
    def test_fragment_0_is_decoded_while_the_source_encodes(
        self, plane, monkeypatch
    ):
        """(a) A delta healer whose state differs has decoded fragment 0
        before the source stages fragment 1, and long before a manifest
        exists; what landed so is counted in ``heal_wire.overlapped``."""
        _plane(plane, monkeypatch)
        state = self.state()
        local = clone_state(state)
        for v in local["user"].values():
            v[:] = 0.0
        local["torchft"] = {"step": 0, "batches_committed": 0}
        decoded = threading.Event()
        real = frags.decode_fragment

        def spy(buf, into=None):
            out = real(buf, into=into)
            decoded.set()
            return out

        monkeypatch.setattr(frags, "decode_fragment", spy)
        src = GatedSource(state, 5, self.FRAGMENTS)
        healer = HTTPTransport(timeout=10.0)
        try:
            fut = self.recv_in_thread(healer, [src.t.metadata()], local)
            time.sleep(0.3)  # parked: nothing but the header is staged
            assert not decoded.is_set() and not fut.done()
            assert src.stage(1) == ["0"]
            assert decoded.wait(10.0), "fragment 0 was not decoded"
            # the source has staged neither fragment 1 nor a manifest
            assert set(src.t.streamed_parts(5)) == {"frag:header", "frag:0"}
            assert not fut.done()
            src.finish()
            got, info = fut.result(timeout=30.0)
        finally:
            healer.shutdown()
            src.shutdown()
        assert_state_equal(got, state)
        assert info["mode"] == "delta" and info["failovers"] == 0
        assert info["changed"] == info["fragments"] == self.FRAGMENTS
        assert info["wire_bytes"] == sum(src.nbytes.values())
        # fragment 0 for certain had landed when the manifest was made
        assert info["overlapped"] == info["parts"]["heal_wire.overlapped"]
        assert src.nbytes["0"] <= info["overlapped"] <= info["wire_bytes"]

    @pytest.mark.parametrize("plane", ["native", "python"])
    @pytest.mark.parametrize("differing", [("w3",), ("w0", "w5", "w6")])
    def test_a_streaming_source_moves_the_differing_fragments_alone(
        self, differing, plane, monkeypatch
    ):
        """(b) With a source that is still staging, a healer equal in k of
        n fragments moves the bytes of n - k and ends bitwise equal: no
        body crosses the wire for an equal fragment."""
        _plane(plane, monkeypatch)
        state = self.state()
        local = clone_state(state)
        for k in differing:
            local["user"][k][:] = -1.0
        truth = frags.local_fragment_digests(state, self.FRAGMENTS)[1]
        mine = frags.local_fragment_digests(local, self.FRAGMENTS)[1]
        moved = [n for n in truth if truth[n] != mine[n]]
        assert 0 < len(moved) < self.FRAGMENTS
        src = GatedSource(state, 5, self.FRAGMENTS)
        healer = HTTPTransport(timeout=10.0)
        books = (
            src.t._frag_native.counters if plane == "native" else dict
        )
        before = books()
        try:
            fut = self.recv_in_thread(healer, [src.t.metadata()], local)
            for _ in range(self.FRAGMENTS):
                time.sleep(0.1)
                src.stage(1)
            time.sleep(0.1)
            assert not fut.done()  # the manifest is still to come
            src.finish()
            got, info = fut.result(timeout=30.0)
            # a serve is booked after its last byte went out, which the
            # healer that holds the bytes does not wait for
            until = time.monotonic() + 5.0
            after = books()
            while plane == "native" and time.monotonic() < until and (
                after["serve_bytes"] - before["serve_bytes"]
                < info["wire_bytes"]
            ):
                time.sleep(0.01)
                after = books()
        finally:
            healer.shutdown()
            src.shutdown()
        assert_state_equal(got, state)
        assert info["mode"] == "delta" and info["failovers"] == 0
        assert info["changed"] == len(moved)
        assert info["wire_bytes"] == sum(src.nbytes[n] for n in moved)
        if plane == "native":
            # the source's own books: a "same" for each equal fragment,
            # bytes for the others and for no one else
            assert after["same_replies"] - before["same_replies"] == (
                self.FRAGMENTS - len(moved)
            )
            assert after["serve_bytes"] - before["serve_bytes"] == (
                info["wire_bytes"]
            )

    def test_a_source_still_staging_is_not_failed_over(self):
        """(c) The trap: a non-primary source gets ``HEAL_FAILOVER_S`` a
        fragment, and one that stages its fragments 3 s after the header
        has not died: its "streaming, not yet" is progress."""
        from torchft_tpu.checkpointing import http_transport

        assert http_transport.HEAL_FAILOVER_S < 3.0
        state = self.state(n=1000)
        local = clone_state(state)
        for v in local["user"].values():
            v[:] = 0.0
        primary = GatedSource(state, 5, self.FRAGMENTS)
        late = GatedSource(state, 5, self.FRAGMENTS)
        healer = HTTPTransport(timeout=10.0)
        _every_worker_holds_a_fragment()
        try:
            primary.stage()
            t0 = time.monotonic()
            fut = self.recv_in_thread(
                healer, [primary.t.metadata(), late.t.metadata()], local
            )
            time.sleep(3.0)
            assert not fut.done()  # two fragments are parked at `late`
            late.finish()
            primary.finish()
            got, info = fut.result(timeout=30.0)
            assert time.monotonic() - t0 >= 3.0
        finally:
            healer.shutdown()
            primary.shutdown()
            late.shutdown()
        assert_state_equal(got, state)
        assert info["failovers"] == 0 and info["sources_used"] == 2
        assert info["changed"] == self.FRAGMENTS

    @pytest.mark.parametrize("how", ["refuses", "staged_nothing"])
    def test_a_dead_source_still_costs_the_failover_bound(self, how):
        """(c) ... while a refused connection, or the 503 of a node that
        has staged nothing, is no sign of life: that source is given up
        at the bound, beside a primary that is still staging."""
        from torchft_tpu.checkpointing import http_transport

        state = self.state(n=1000)
        local = clone_state(state)
        for v in local["user"].values():
            v[:] = 0.0
        primary = GatedSource(state, 5, self.FRAGMENTS)
        other = HTTPTransport(timeout=5.0)
        other_addr = other.metadata()
        if how == "refuses":
            other.shutdown()
        healer = HTTPTransport(timeout=10.0)
        _every_worker_holds_a_fragment()
        try:
            t0 = time.monotonic()
            fut = self.recv_in_thread(
                healer, [primary.t.metadata(), other_addr], local
            )
            primary.stage(2)  # still staging when the other is given up
            time.sleep(http_transport.HEAL_FAILOVER_S + 1.0)
            primary.finish()
            got, info = fut.result(timeout=30.0)
            took = time.monotonic() - t0
        finally:
            healer.shutdown()
            primary.shutdown()
            if how != "refuses":
                other.shutdown()
        assert_state_equal(got, state)
        # its two fragments moved to the primary, at the bound and not at
        # the deadline (30 s): the heal ended with the primary's staging
        assert info["failovers"] >= 1 and info["sources_used"] == 1
        assert took < http_transport.HEAL_FAILOVER_S + 3.0

    @pytest.mark.parametrize("plane", ["native", "python"])
    @pytest.mark.parametrize("fault", ["false_same", "poisoned_bytes"])
    def test_a_lying_stripe_source_is_repaired_from_the_manifest(
        self, fault, plane, monkeypatch
    ):
        """(d) A stripe source that answers "same" of a fragment that is
        not, and one that sends poisoned bytes, are found out when the
        manifest comes and repaired, verified on receipt: neither reaches
        the returned state."""
        _plane(plane, monkeypatch)
        state = self.state(n=1000)
        local = clone_state(state)
        for v in local["user"].values():
            v[:] = 0.5
        mine = frags.local_fragment_digests(local, self.FRAGMENTS)[1]
        names = list(mine)
        primary = GatedSource(state, 5, self.FRAGMENTS)
        liar = GatedSource(
            state, 5, self.FRAGMENTS,
            # whatever it is asked for: "you hold that already"
            lie=mine if fault == "false_same" else None,
            poison=names if fault == "poisoned_bytes" else (),
        )
        healer = HTTPTransport(timeout=10.0)
        _every_worker_holds_a_fragment(0.1)
        try:
            fut = self.recv_in_thread(
                healer, [primary.t.metadata(), liar.t.metadata()], local
            )
            liar.finish()
            primary.finish()
            got, info = fut.result(timeout=30.0)
        finally:
            healer.shutdown()
            primary.shutdown()
            liar.shutdown()
        assert_state_equal(got, state)
        # the liar held two fragments: both were taken again
        assert info["failovers"] >= 1
        assert info["changed"] == self.FRAGMENTS


    def test_a_decode_gives_the_interpreters_lock_up(self):
        """A healer decodes beside its sources' encode, whose one pass
        takes the lock back for every block: a leaf's copy out of the wire
        buffer must not hold it for the whole leaf (a ``memoryview`` slice
        assignment did: 100 ms for 100 MB, and the source's pass lost a
        third of its speed on the chip's host)."""
        from torchft_tpu.checkpointing import serialization as ser

        leaf = np.ones(64_000_000, np.float32)  # one leaf of 256 MB
        wire = np.frombuffer(ser.serialize({"0": leaf}), np.uint8)
        stop, gaps = threading.Event(), [0.0]

        def ticker():
            last = time.perf_counter()
            while not stop.is_set():
                time.sleep(0.0005)  # wakes only with the lock in hand
                now = time.perf_counter()
                gaps[0] = max(gaps[0], now - last)
                last = now

        th = threading.Thread(target=ticker, daemon=True)
        th.start()
        time.sleep(0.05)
        gaps[0] = 0.0
        t0 = time.perf_counter()
        got = frags.decode_fragment(wire)
        took = time.perf_counter() - t0
        stop.set()
        th.join(timeout=5)
        np.testing.assert_array_equal(got[0], leaf)
        # holding the lock, the ticker's longest silence is the copy itself
        # (all of it, to the percent; a fifth of slack is for a loaded host)
        assert gaps[0] < 0.8 * took, (gaps[0], took)


class TestStripedHealInteg:
    """Fleet-level: a killed replica heals over the striped path and
    the fleet converges bitwise (Runner/lighthouse idiom of
    test_manager_integ)."""

    def test_striped_recovery_bitwise(self):
        from test_manager_integ import (
            Runner,
            assert_bitwise_equal,
            fail_at,
            run_replicas,
        )

        from torchft_tpu.coordination import LighthouseServer

        lighthouse = LighthouseServer(
            min_replicas=2, join_timeout_ms=100, heartbeat_timeout_ms=1000
        )
        wire_before = (
            _metrics.HEAL_WIRE_BYTES.labels(mode="full").get()
            + _metrics.HEAL_WIRE_BYTES.labels(mode="delta").get()
        )
        try:
            faults.FAULTS.configure([fail_at(replica=1, step=2)])
            runners = [
                Runner(i, lighthouse.address(), total_steps=5,
                       min_replica_size=1)
                for i in range(3)
            ]
            results = run_replicas(runners)
        finally:
            lighthouse.shutdown()
        assert all(r["manager_state"]["step"] == 5 for r in results)
        assert_bitwise_equal(results)
        # the heal actually rode the striped fragment plane
        wire_after = (
            _metrics.HEAL_WIRE_BYTES.labels(mode="full").get()
            + _metrics.HEAL_WIRE_BYTES.labels(mode="delta").get()
        )
        assert wire_after > wire_before
        # the heal fetched over the fragment plane (the gauge reports
        # sources that DELIVERED; with a tiny 4-fragment state on
        # loopback one source can win every pop race, so >= 1 — the
        # deterministic >= 2 assertion lives in the delay-paced
        # TestStripedHeal.test_kill_stripe_source_mid_heal)
        assert _metrics.HEAL_STRIPE_SOURCES.get() >= 1


class TestHealOpened:
    """ISSUE 24: the heal, opened.  A three-replica fleet with a kill,
    read back from ``Manager.phase_times()`` and the span file: the
    source-side parts per fragment, the healer's wait for the source, the
    apply, and the split phases emitted WHEN they happen (they used to be
    recorded in a row after the heal, with starts rebuilt as end minus
    duration: four spans that all ended together)."""

    N = 200_000  # a state big enough for the hashing to take milliseconds

    def _replica(self, rid: int, addr: str, steps: int, out: dict) -> None:
        from torchft_tpu.manager import Manager
        from torchft_tpu.parallel.process_group import ProcessGroupTCP
        from torchft_tpu.utils.faults import InjectedFault

        for attempt in range(3):
            params = {f"w{i}": np.zeros(self.N, np.float32) for i in range(6)}

            def load_state_dict(sd, params=params):
                time.sleep(0.01)  # a device_put would go here
                params.update({k: np.array(v) for k, v in sd.items()})

            manager = Manager(
                pg=ProcessGroupTCP(timeout=20.0),
                min_replica_size=1,
                load_state_dict=load_state_dict,
                state_dict=lambda params=params: dict(params),
                lighthouse_addr=addr,
                replica_id=f"replica_{rid}",
                group_rank=0,
                group_world_size=1,
                timeout=30.0,
                quorum_timeout=30.0,
                init_sync=False,
            )
            # a replica the others finished without can never reach a quorum
            # of two again: it fails here instead of asking for one forever
            alone_by = time.monotonic() + 60.0
            try:
                if attempt == 0 and out.get("all_built") is not None:
                    # under the whole suite's load a replica whose thread
                    # starts 100 ms late misses the first quorum of two and is
                    # healed into the fleet: a survivor that has applied a
                    # heal.  Every manager exists before any asks for a quorum.
                    out["all_built"].wait(timeout=60)
                while manager.current_step() < steps:
                    step = manager.current_step()
                    if time.monotonic() > alone_by:
                        raise TimeoutError(f"replica {rid} still at step {step}")
                    faults.check("train.step", replica=f"replica_{rid}", step=step)
                    manager.start_quorum()
                    grads = {k: np.full_like(v, step + 1.0) for k, v in params.items()}
                    avg = manager.allreduce(grads).wait(timeout=30)
                    if manager.should_commit():
                        for k in params:
                            params[k] = params[k] - 0.1 * avg[k]
                out.setdefault(rid, []).append(manager.phase_times())
                return
            except InjectedFault:
                out.setdefault(rid, []).append(manager.phase_times())
            finally:
                manager.shutdown()
        raise RuntimeError(f"replica {rid} exhausted its attempts")

    def test_heal_parts_and_span_order(self, tmp_path):
        import json
        from concurrent.futures import ThreadPoolExecutor

        from torchft_tpu.coordination import LighthouseServer
        from torchft_tpu.manager import PHASE_PARTS
        from torchft_tpu.utils import tracing

        from torchft_tpu.utils import flightrecorder, metrics

        flightrecorder.RECORDER.clear()
        decode_hist = metrics.QUORUM_DURATION.labels(
            replica_id="replica_1", phase="heal_decode"
        )
        decodes_before = decode_hist.get()["count"]
        path = tmp_path / "spans.jsonl"
        tracing.uninstall_tracer()
        tracing.install_tracer(
            tracing.Tracer(sink=tracing.FileSpanSink(str(path)))
        )
        lighthouse = LighthouseServer(
            min_replicas=2, join_timeout_ms=100, heartbeat_timeout_ms=1000
        )
        import threading

        out: dict = {"all_built": threading.Barrier(3)}
        try:
            faults.FAULTS.configure(
                [FaultRule(site="train.step", replica="replica_1", step=2)]
            )
            with ThreadPoolExecutor(max_workers=3) as ex:
                futs = [
                    ex.submit(self._replica, i, lighthouse.address(), 5, out)
                    for i in range(3)
                ]
                for f in futs:
                    f.result(timeout=120)
        finally:
            lighthouse.shutdown()
            tracing.uninstall_tracer()

        # -- the sources: per-fragment parts inside heal_send ------------
        send_parts = [p for p in PHASE_PARTS if p.startswith("heal_send.")]
        sources = [
            ph for rid in (0, 2) for ph in out[rid] if ph.get("heal_send", 0) > 0
        ]
        assert sources, "no survivor staged a heal"
        for ph in sources:
            assert set(send_parts) <= set(ph), sorted(ph)
            # the bytes a source copied beyond its one write, a counter
            # among the parts (as ``heal_diff.hidden`` is): none
            assert ph["heal_send.copied"] == 0
            opened = sum(ph[p] for p in send_parts)
            assert 0 < opened <= ph["heal_send"]
            assert ph.get("heal_apply", 0.0) == 0.0

        # -- the healer: the new incarnation of replica 1 ----------------
        healer = out[1][-1]
        assert 0 < healer["heal_manifest.wait"] <= healer["heal_manifest"]
        # the healer's digests: hashed in place (nothing is encoded), from
        # the header on, so heal_diff is what they still cost after the
        # stripe, when the manifest is in
        digest_work = healer["heal_diff.snapshot"] + healer["heal_diff.hash"]
        assert "heal_diff.encode" not in healer
        assert 0 < healer["heal_diff.hidden"] == pytest.approx(digest_work)
        assert 0 < digest_work and 0 < healer["heal_diff"]
        # every fragment of a fresh incarnation crossed, all beside the
        # sources' encode or not, but counted either way
        assert healer["heal_wire.overlapped"] >= 0
        assert healer["heal_apply"] >= 0.01  # the user's load, timed at last
        assert healer["heal_wire"] > 0 and healer["heal_recv"] >= 0
        assert "heal_send" not in healer

        # -- the trace: emitted when they happen -------------------------
        spans = [json.loads(l) for l in path.read_text().splitlines() if l]
        by = {}
        for s in spans:
            by.setdefault(s["name"], []).append(s)
        split = [by[n][0] for n in ("heal_manifest", "heal_wire")]
        assert all(len(by[n]) == 1 for n in ("heal_manifest", "heal_diff", "heal_wire"))
        starts = [s["start_ns"] for s in split]
        ends = [s["end_ns"] for s in split]
        # one after the other: the header, then the stripe from it on
        assert split[0]["end_ns"] <= split[1]["start_ns"]
        # heal_diff opens when the manifest is in, which is inside the
        # stripe's phase and after its last fragment's decode
        (diff,) = by["heal_diff"]
        assert split[1]["start_ns"] < diff["start_ns"]
        assert diff["end_ns"] <= split[1]["end_ns"]
        # all three, and heal_recv around them, hang off the healer's root
        (recv,) = by["heal_recv"]
        assert {s["parent_span_id"] for s in split + [diff]} == {
            recv["parent_span_id"]
        }
        assert recv["start_ns"] <= starts[0] and ends[-1] <= recv["end_ns"]
        # heal_recv books what the split leaves, and says so on its span
        assert recv["attributes"]["seconds"] == pytest.approx(
            healer["heal_recv"], abs=1e-6
        )
        assert healer["heal_recv"] < (recv["end_ns"] - recv["start_ns"]) / 1e9
        # heal_decode: one span a heal, inside heal_wire, and in it a part
        # per fragment that moved; one observation and flight record in all
        (dec,) = by["heal_decode"]
        assert split[1]["start_ns"] <= dec["start_ns"]
        assert dec["end_ns"] <= diff["start_ns"]
        assert dec["attributes"]["seconds"] == pytest.approx(
            healer["heal_decode"], abs=1e-6
        )
        assert by["heal_decode.fragment"]
        for d in by["heal_decode.fragment"]:
            assert d["parent_span_id"] == dec["span_id"]
            assert dec["start_ns"] <= d["start_ns"] and d["end_ns"] <= dec["end_ns"]
            assert "fragment" in d["attributes"]
        assert 0 < healer["heal_decode.fragment"] <= healer["heal_decode"]
        assert decode_hist.get()["count"] == decodes_before + 1
        flight = [
            r for r in flightrecorder.snapshot()
            if r.get("kind") == "phase" and r["replica_id"].startswith("replica_1")
        ]
        assert [r["op"] for r in flight].count("heal_decode") == 1
        assert not [r for r in flight if "." in r["op"]]
        # heal_recv and what it opened say which step was healed TO
        healed_to = {
            r["step"] for r in flight if r["op"].startswith("heal_")
            and r["op"] != "heal_apply"
        }
        assert len(healed_to) == 1 and recv["attributes"]["step"] in healed_to
        assert recv["attributes"]["step"] >= 2  # the kill was at step 2
        # source-side parts are children of a heal_send span, per fragment
        send_ids = {s["span_id"] for s in by["heal_send"]}
        assert "heal_send.copied" not in by  # a counter: bytes, no span
        for name in send_parts:
            if name == "heal_send.copied":
                continue
            assert by[name], name
            assert {s["parent_span_id"] for s in by[name]} <= send_ids
            assert all("fragment" in s["attributes"] for s in by[name])
        (apply_span,) = by["heal_apply"]
        assert apply_span["start_ns"] >= recv["end_ns"]
