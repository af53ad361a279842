"""Checkpoint transport round-trip tests.

Mirrors reference torchft/checkpointing/{http_transport_test,
pg_transport_test, transport_test}.py: full + chunked HTTP fetch, RWLock
serving guarantees, PG transport incl. in-place receive.
"""

import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from torchft_tpu.checkpointing import HTTPTransport, PGTransport
from torchft_tpu.checkpointing import serialization as ser
from torchft_tpu.coordination import StoreServer
from torchft_tpu.parallel.process_group import ProcessGroupTCP


def sample_state_dict():
    return {
        "user": {
            "params": {
                "w": np.arange(12, dtype=np.float32).reshape(3, 4),
                "b": np.zeros(4, dtype=np.float32),
            },
            "opt": [np.ones(3, dtype=np.float64), 7],
            "label": "hello",
        },
        "torchft": {"step": 5, "batches_committed": 10},
    }


def assert_state_dicts_equal(a, b):
    import jax

    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    assert ta == tb
    for x, y in zip(la, lb):
        if isinstance(x, np.ndarray) or isinstance(y, np.ndarray):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        else:
            assert x == y


class TestSerialization:
    def test_round_trip(self):
        sd = sample_state_dict()
        assert_state_dicts_equal(ser.deserialize(ser.serialize(sd)), sd)

    def test_chunked_round_trip(self):
        sd = sample_state_dict()
        import jax

        n = len(jax.tree_util.tree_flatten(sd)[0])
        chunks = ser.split_chunks(n, 3)
        assert sorted(sum(chunks, [])) == list(range(n))
        merged = {}
        skeleton = None
        for idx in chunks:
            s, leaves, total = ser.deserialize_from(
                __import__("io").BytesIO(ser.serialize(sd, chunk_indices=idx))
            )
            skeleton = s
            merged.update(leaves)
        assert_state_dicts_equal(ser.reassemble(skeleton, merged, n), sd)

    def test_missing_chunk_detected(self):
        sd = sample_state_dict()
        import io

        s, leaves, n = ser.deserialize_from(
            io.BytesIO(ser.serialize(sd, chunk_indices=[0]))
        )
        with pytest.raises(ValueError, match="missing leaf"):
            ser.reassemble(s, leaves, n)

    def test_jax_arrays(self):
        import jax.numpy as jnp

        sd = {"w": jnp.arange(6.0).reshape(2, 3)}
        out = ser.deserialize(ser.serialize(sd))
        np.testing.assert_array_equal(out["w"], np.arange(6.0).reshape(2, 3))


class TestHTTPTransport:
    def test_full_round_trip(self):
        sender = HTTPTransport(timeout=10.0)
        receiver = HTTPTransport(timeout=10.0)
        try:
            sd = sample_state_dict()
            sender.send_checkpoint([1], step=5, state_dict=sd, timeout=10.0)
            out = receiver.recv_checkpoint(
                src_rank=0, metadata=sender.metadata(), step=5, timeout=10.0
            )
            assert_state_dicts_equal(out, sd)
        finally:
            sender.shutdown()
            receiver.shutdown()

    def test_chunked_round_trip(self):
        sender = HTTPTransport(timeout=10.0, num_chunks=3)
        receiver = HTTPTransport(timeout=10.0, num_chunks=3)
        try:
            sd = sample_state_dict()
            sender.send_checkpoint([1], step=2, state_dict=sd, timeout=10.0)
            out = receiver.recv_checkpoint(
                src_rank=0, metadata=sender.metadata(), step=2, timeout=10.0
            )
            assert_state_dicts_equal(out, sd)
        finally:
            sender.shutdown()
            receiver.shutdown()

    def test_inplace_recv_into_live_state(self):
        sd = sample_state_dict()
        import jax

        live = jax.tree_util.tree_map(
            lambda x: np.zeros_like(x) if isinstance(x, np.ndarray) else x, sd
        )
        sender = HTTPTransport(timeout=10.0)
        receiver = HTTPTransport(timeout=10.0, state_dict_fn=lambda: live)
        try:
            sender.send_checkpoint([1], step=7, state_dict=sd, timeout=10.0)
            out = receiver.recv_checkpoint(
                src_rank=0, metadata=sender.metadata(), step=7, timeout=10.0
            )
            assert_state_dicts_equal(out, sd)
            # numpy leaves were filled in place: same buffers as `live`
            out_leaves = jax.tree_util.tree_flatten(out)[0]
            live_leaves = jax.tree_util.tree_flatten(live)[0]
            for o, l in zip(out_leaves, live_leaves):
                if isinstance(l, np.ndarray):
                    assert o is l
        finally:
            sender.shutdown()
            receiver.shutdown()

    def test_read_only_live_state_is_not_received_into(self):
        """`np.asarray(jax.Array)` — what a `state_dict` that hands out
        host copies of device state returns — is a READ-ONLY view; it must
        not be taken as an in-place receive buffer (every fragment would
        fail to decode and the heal would retry forever)."""
        import jax
        import jax.numpy as jnp

        sd = {"w": np.arange(8, dtype=np.float32), "b": np.ones(3, np.float32)}
        live = jax.tree_util.tree_map(
            lambda x: np.asarray(jnp.zeros_like(x)), sd
        )
        assert not live["w"].flags.writeable
        sender = HTTPTransport(timeout=10.0)
        receiver = HTTPTransport(timeout=10.0, state_dict_fn=lambda: live)
        try:
            sender.send_checkpoint([1], step=9, state_dict=sd, timeout=10.0)
            out = receiver.recv_checkpoint(
                src_rank=0, metadata=sender.metadata(), step=9, timeout=10.0
            )
            assert_state_dicts_equal(out, sd)
            assert out["w"] is not live["w"]
        finally:
            sender.shutdown()
            receiver.shutdown()

    def test_inplace_mismatch_falls_back(self):
        sd = sample_state_dict()
        receiver = HTTPTransport(
            timeout=10.0, state_dict_fn=lambda: {"wrong": np.zeros(1)}
        )
        sender = HTTPTransport(timeout=10.0)
        try:
            sender.send_checkpoint([1], step=8, state_dict=sd, timeout=10.0)
            out = receiver.recv_checkpoint(
                src_rank=0, metadata=sender.metadata(), step=8, timeout=10.0
            )
            assert_state_dicts_equal(out, sd)
        finally:
            sender.shutdown()
            receiver.shutdown()

    def test_wrong_step_404(self):
        sender = HTTPTransport(timeout=5.0)
        try:
            sender.send_checkpoint([1], step=5, state_dict={"x": 1}, timeout=5.0)
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(
                    f"{sender.metadata()}/checkpoint/99/full", timeout=5
                )
        finally:
            sender.shutdown()

    def test_disallow_checkpoint(self):
        sender = HTTPTransport(timeout=5.0)
        try:
            sender.send_checkpoint([1], step=1, state_dict={"x": 1}, timeout=5.0)
            sender.disallow_checkpoint()
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(
                    f"{sender.metadata()}/checkpoint/1/full", timeout=5
                )
        finally:
            sender.shutdown()


class TestPGTransport:
    def _pair(self, store, state_dict_fn=None):
        pgs = [ProcessGroupTCP(timeout=10.0) for _ in range(2)]
        threads = [
            threading.Thread(
                target=pgs[r].configure,
                args=(f"{store.address()}/pgt", f"r{r}", r, 2),
            )
            for r in range(2)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(10)
        return (
            PGTransport(pgs[0], timeout=10.0),
            PGTransport(pgs[1], timeout=10.0, state_dict_fn=state_dict_fn),
            pgs,
        )

    def test_round_trip(self):
        with StoreServer() as store:
            sender, receiver, pgs = self._pair(store)
            sd = sample_state_dict()
            out = {}

            def send():
                sender.send_checkpoint([1], step=5, state_dict=sd, timeout=10.0)

            def recv():
                out["sd"] = receiver.recv_checkpoint(
                    src_rank=0, metadata="<n/a>", step=5, timeout=10.0
                )

            ts = [threading.Thread(target=send), threading.Thread(target=recv)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(20)
            assert_state_dicts_equal(out["sd"], sd)
            for pg in pgs:
                pg.shutdown()

    def test_in_place_receive(self):
        with StoreServer() as store:
            target = {
                "user": {
                    "params": {
                        "w": np.zeros((3, 4), dtype=np.float32),
                        "b": np.zeros(4, dtype=np.float32),
                    },
                    "opt": [np.zeros(3, dtype=np.float64), 0],
                    "label": "",
                },
                "torchft": {"step": 0, "batches_committed": 0},
            }
            sender, receiver, pgs = self._pair(store, state_dict_fn=lambda: target)
            sd = sample_state_dict()
            out = {}

            def send():
                sender.send_checkpoint([1], step=5, state_dict=sd, timeout=10.0)

            def recv():
                out["sd"] = receiver.recv_checkpoint(
                    src_rank=0, metadata="<n/a>", step=5, timeout=10.0
                )

            ts = [threading.Thread(target=send), threading.Thread(target=recv)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(20)
            assert_state_dicts_equal(out["sd"], sd)
            # fast path: the result's array leaves ARE the target's buffers
            assert out["sd"]["user"]["params"]["w"] is target["user"]["params"]["w"]
            np.testing.assert_array_equal(
                target["user"]["params"]["w"], sd["user"]["params"]["w"]
            )
            for pg in pgs:
                pg.shutdown()

    def test_step_mismatch(self):
        with StoreServer() as store:
            sender, receiver, pgs = self._pair(store)
            errs = {}

            def send():
                try:
                    sender.send_checkpoint([1], step=5, state_dict={"x": np.ones(2)}, timeout=5.0)
                except Exception as e:  # noqa: BLE001
                    errs["send"] = e

            def recv():
                try:
                    receiver.recv_checkpoint(src_rank=0, metadata="", step=7, timeout=5.0)
                except Exception as e:  # noqa: BLE001
                    errs["recv"] = e

            ts = [threading.Thread(target=send), threading.Thread(target=recv)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(15)
            assert "step mismatch" in str(errs["recv"])
            for pg in pgs:
                pg.shutdown()


class TestBf16AndZeroDim:
    def test_bf16_round_trip(self):
        # TPU's default training dtype must survive serialization (ml_dtypes
        # have no buffer-protocol format char — regression for memoryview.cast)
        import jax.numpy as jnp
        import ml_dtypes

        sd = {
            "w": np.full((4, 3), 1.5, dtype=np.float32).astype(ml_dtypes.bfloat16),
            "step": np.asarray(7, dtype=np.int32),
            "j": jnp.ones((2,), dtype=jnp.bfloat16),
        }
        out = ser.deserialize(ser.serialize(sd))
        assert out["w"].dtype == ml_dtypes.bfloat16
        np.testing.assert_array_equal(
            out["w"].astype(np.float32), np.full((4, 3), 1.5, np.float32)
        )
        assert out["step"].shape == () and out["step"] == 7
        assert out["j"].dtype == ml_dtypes.bfloat16

    def test_bf16_http_transport(self):
        import ml_dtypes

        sender = HTTPTransport(timeout=10.0)
        receiver = HTTPTransport(timeout=10.0)
        try:
            sd = {"w": np.arange(6, dtype=np.float32).astype(ml_dtypes.bfloat16)}
            sender.send_checkpoint([1], step=3, state_dict=sd, timeout=10.0)
            out = receiver.recv_checkpoint(
                src_rank=0, metadata=sender.metadata(), step=3, timeout=10.0
            )
            assert out["w"].dtype == ml_dtypes.bfloat16
        finally:
            sender.shutdown()
            receiver.shutdown()

    def test_version_keyed_staging_retention(self):
        """Serving-tier contract (ISSUE 12): concurrently publishing
        version V+1 while clients still fetch V must not retire V early
        — V survives until it ages out of the staging window."""
        import threading

        tr = HTTPTransport(timeout=10.0, max_staged=3)
        try:
            docs = {
                v: {"w": np.full(2048, float(v), np.float32)}
                for v in range(1, 6)
            }
            tr.send_checkpoint([], step=1, state_dict=docs[1], timeout=5.0)
            tr.send_checkpoint([], step=2, state_dict=docs[2], timeout=5.0)
            # fetch V=1 from many threads WHILE V=3 (and then V=4) stage
            results = {}

            def _fetch(i):
                try:
                    results[i] = tr.recv_checkpoint(
                        src_rank=0, metadata=tr.metadata(), step=1,
                        timeout=10.0,
                    )
                except Exception as e:  # noqa: BLE001 - asserted below
                    results[i] = e

            threads = [
                threading.Thread(target=_fetch, args=(i,)) for i in range(4)
            ]
            for t in threads:
                t.start()
            tr.send_checkpoint([], step=3, state_dict=docs[3], timeout=5.0)
            for t in threads:
                t.join(timeout=20)
                assert not t.is_alive()
            # every concurrent fetch of V=1 completed with V=1's bytes
            for i, out in results.items():
                assert not isinstance(out, Exception), f"fetch {i}: {out}"
                np.testing.assert_array_equal(out["w"], docs[1]["w"])
            # window is 3: V=1 still staged after the concurrent publish
            assert tr.staged_steps() == [1, 2, 3]
            # a FOURTH version finally ages V=1 out (oldest first)
            tr.send_checkpoint([], step=4, state_dict=docs[4], timeout=5.0)
            assert tr.staged_steps() == [2, 3, 4]
        finally:
            tr.shutdown()

    def test_staging_writer_never_starved_by_fetch_storm(self):
        """The writer-priority lock: a continuous 503-poll storm on the
        read side must not starve send_checkpoint (the serving soak's
        failure mode before the turnstile)."""
        import threading
        import time as _time
        import urllib.error
        import urllib.request

        tr = HTTPTransport(timeout=10.0, max_staged=4)
        stop = threading.Event()

        def _poll():
            # hammer an unstaged step: each request takes the read lock
            while not stop.is_set():
                try:
                    urllib.request.urlopen(
                        f"{tr.metadata()}/checkpoint/999/full", timeout=1.0
                    )
                except (urllib.error.HTTPError, OSError):
                    pass

        threads = [
            threading.Thread(target=_poll, daemon=True) for _ in range(8)
        ]
        try:
            for t in threads:
                t.start()
            _time.sleep(0.2)  # let the storm densify
            t0 = _time.monotonic()
            tr.send_checkpoint(
                [], step=1, state_dict={"w": np.ones(4)}, timeout=5.0
            )
            staged_in = _time.monotonic() - t0
            assert staged_in < 5.0, f"staging starved for {staged_in:.1f}s"
            assert 1 in tr.staged_steps()
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=5)
            tr.shutdown()

    def test_fragment_resource(self):
        """frag_<name> serves exactly one staged fragment; an unknown
        fragment is a permanent 404, distinct from the unstaged 503."""
        import urllib.error
        import urllib.request

        from torchft_tpu.checkpointing import serialization as ser

        tr = HTTPTransport(timeout=10.0)
        try:
            doc = {
                "frag:manifest": {"version": 3, "fragments": ["0"]},
                "frag:0": {"w": np.arange(4, dtype=np.float32)},
            }
            tr.send_checkpoint([], step=3, state_dict=doc, timeout=5.0)
            with urllib.request.urlopen(
                f"{tr.metadata()}/checkpoint/3/frag_0", timeout=5.0
            ) as resp:
                skeleton, leaves, n = ser.deserialize_from(resp)
            frag = ser.reassemble(skeleton, leaves, n)
            np.testing.assert_array_equal(frag["w"], doc["frag:0"]["w"])
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(
                    f"{tr.metadata()}/checkpoint/3/frag_nope", timeout=5.0
                )
            assert ei.value.code == 404
            # unstaged version stays the retryable 503
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(
                    f"{tr.metadata()}/checkpoint/99/frag_0", timeout=5.0
                )
            assert ei.value.code == 503
        finally:
            tr.shutdown()

    def test_recv_retries_until_staged(self):
        # healer fetches BEFORE the sender stages: must poll, not fail
        import threading
        import time as _time

        sender = HTTPTransport(timeout=10.0)
        receiver = HTTPTransport(timeout=10.0)
        try:
            sd = {"w": np.ones(3)}

            def stage_late():
                _time.sleep(0.5)
                sender.send_checkpoint([1], step=9, state_dict=sd, timeout=5.0)

            t = threading.Thread(target=stage_late)
            t.start()
            out = receiver.recv_checkpoint(
                src_rank=0, metadata=sender.metadata(), step=9, timeout=10.0
            )
            t.join()
            np.testing.assert_array_equal(out["w"], np.ones(3))
        finally:
            sender.shutdown()
            receiver.shutdown()
