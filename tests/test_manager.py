"""Manager unit tests with mocked coordination client.

Mirrors reference torchft/manager_test.py:84-911: crafted QuorumResults
drive every Manager state — happy path, async/sync heal, not enough
participants, allreduce error, pg.errored, fixed-with-spares, max_retries.
"""

from unittest.mock import MagicMock, patch

import numpy as np
import pytest

from torchft_tpu.coordination import QuorumResult
from torchft_tpu.manager import Manager, WorldSizeMode
from torchft_tpu.parallel.process_group import (
    ErrorSwallowingProcessGroupWrapper,
    FakeProcessGroupWrapper,
    REDUCE_SUM,
    ProcessGroupDummy,
    ProcessGroupTCP,
)


def make_quorum(
    quorum_id=1,
    replica_rank=0,
    replica_world_size=2,
    max_step=0,
    max_replica_rank=0,
    max_world_size=2,
    heal=False,
    **kw,
):
    return QuorumResult(
        quorum_id=quorum_id,
        replica_rank=replica_rank,
        replica_world_size=replica_world_size,
        recover_src_manager_address=kw.get("recover_src_manager_address", ""),
        recover_src_replica_rank=kw.get("recover_src_replica_rank"),
        recover_dst_replica_ranks=kw.get("recover_dst_replica_ranks", []),
        store_address="fakestore:1/",
        max_step=max_step,
        max_replica_rank=max_replica_rank,
        max_world_size=max_world_size,
        heal=heal,
        commit_failures=kw.get("commit_failures", 0),
    )


@pytest.fixture
def manager_ctx():
    """Manager with fully mocked coordination plumbing."""
    patches = [
        patch("torchft_tpu.manager.ManagerServer"),
        patch("torchft_tpu.manager.StoreServer"),
        patch("torchft_tpu.manager.StoreClient"),
        patch("torchft_tpu.manager.ManagerClient"),
    ]
    mocks = [p.start() for p in patches]
    store_client = mocks[2].return_value
    store_client.get.side_effect = lambda key, **kw: {
        "manager_addr": "mock:1",
        "replica_id": "rep0:uuid",
    }[key]
    client = mocks[3].return_value

    transport = MagicMock()
    transport.metadata.return_value = "http://mock"

    def build(pg=None, **kwargs):
        defaults = dict(
            pg=pg or ProcessGroupDummy(),
            min_replica_size=2,
            load_state_dict=lambda sd: None,
            state_dict=lambda: {"w": np.zeros(2)},
            lighthouse_addr="mock-lh:1",
            group_rank=0,
            group_world_size=1,
            checkpoint_transport=transport,
            use_async_quorum=True,
        )
        defaults.update(kwargs)
        return Manager(**defaults)

    yield build, client, transport
    for p in patches:
        p.stop()


def _start_async_heal(manager, client, transport):
    """A quorum in which this replica (rank 1) heals from rank 0 at step 7:
    under the async quorum it is healing, and no participant, until the
    commit applies the state."""
    client._quorum.return_value = make_quorum(
        replica_rank=1,
        max_step=7,
        max_replica_rank=None,
        max_world_size=1,
        heal=True,
        recover_src_replica_rank=0,
        recover_src_manager_address="peer:1",
    )
    transport.recv_checkpoint.return_value = {
        "user": {"default": {"w": 42}},
        "torchft": {"step": 7, "batches_committed": 70},
    }
    with patch("torchft_tpu.manager.ManagerClient") as peer_cls:
        peer_cls.return_value._checkpoint_metadata.return_value = "http://peer"
        manager.start_quorum()
        manager.wait_quorum()


class TestManagerHappyPath:
    def test_step_and_commit(self, manager_ctx):
        build, client, transport = manager_ctx
        manager = build()
        client._quorum.return_value = make_quorum()
        client.should_commit.return_value = True

        manager.start_quorum()
        assert manager.num_participants() == 2
        assert manager.is_participating()
        assert manager.participating_rank() == 0

        result = manager.allreduce(np.full(4, 2.0)).wait(timeout=10)
        np.testing.assert_allclose(result, np.full(4, 1.0))  # / participants

        assert manager.should_commit()
        assert manager.current_step() == 1
        assert manager.batches_committed() == 2
        transport.disallow_checkpoint.assert_called()

    def test_pg_configured_on_quorum_change(self, manager_ctx):
        build, client, _ = manager_ctx
        pg = ProcessGroupDummy()
        manager = build(pg=pg)
        client._quorum.return_value = make_quorum(quorum_id=1)
        client.should_commit.return_value = True

        manager.start_quorum()
        manager.wait_quorum()
        assert pg.configure_count == 1
        manager.should_commit()

        # same quorum id -> no reconfigure
        manager.start_quorum()
        manager.wait_quorum()
        assert pg.configure_count == 1

        # new quorum id -> reconfigure
        client._quorum.return_value = make_quorum(quorum_id=2)
        manager.start_quorum()
        manager.wait_quorum()
        assert pg.configure_count == 2

    def test_pytree_allreduce(self, manager_ctx):
        build, client, _ = manager_ctx
        manager = build()
        client._quorum.return_value = make_quorum()
        manager.start_quorum()
        grads = {"a": np.full(2, 4.0), "b": [np.full(3, 8.0)]}
        out = manager.allreduce(grads).wait(timeout=10)
        np.testing.assert_allclose(out["a"], np.full(2, 2.0))
        np.testing.assert_allclose(out["b"][0], np.full(3, 4.0))

    def test_jax_array_leaves_pass_through_unmaterialized(self, manager_ctx):
        # device arrays go to the PG unconverted (the device→host sync
        # runs on the PG worker, not the submitting thread); mixed
        # jax/numpy/scalar pytrees still average correctly
        import jax.numpy as jnp

        build, client, _ = manager_ctx
        manager = build()
        client._quorum.return_value = make_quorum()
        manager.start_quorum()
        grads = {"j": jnp.full((4,), 6.0), "n": np.full(2, 4.0), "s": 8.0}
        out = manager.allreduce(grads).wait(timeout=10)
        np.testing.assert_allclose(np.asarray(out["j"]), np.full(4, 3.0))
        np.testing.assert_allclose(out["n"], np.full(2, 2.0))
        np.testing.assert_allclose(np.asarray(out["s"]), 4.0)


class _AloneTCP(ProcessGroupTCP):
    """The ring's own group, configured alone (world size 1) whatever the
    quorum says: the Manager's participant count is then not its size."""

    def configure(self, store_addr, replica_id, rank, world_size):
        super().configure("", replica_id, 0, 1)


class _RecordingDummy(ProcessGroupDummy):
    """Keeps what the Manager handed to ``allreduce``."""

    def allreduce(self, arrays, op=REDUCE_SUM, divisor=None):
        self.sent = list(arrays)
        return super().allreduce(arrays, op, divisor)


class TestManagerAverage:
    """The average is one division by the participant count, by the group
    (it owns the reduced buffer), none at all for a divisor of 1; never a
    write into memory the caller passed in.  The Manager hands leaves over
    and touches no leaf's memory."""

    def _mixed(self):
        import jax.numpy as jnp
        import ml_dtypes

        return {
            "host": np.arange(6, dtype=np.float32) * 3,
            "dev": jnp.arange(5, dtype=jnp.float32) * 7,
            "bf16": np.arange(4).astype(ml_dtypes.bfloat16),
        }

    @pytest.mark.parametrize("pg_kind", ["owner", "tcp-alone", "swallowing"])
    @pytest.mark.parametrize("participants", [1, 2, 3])
    def test_matches_numpy_and_never_writes_the_input(
        self, manager_ctx, pg_kind, participants
    ):
        build, client, _ = manager_ctx
        pg = {
            "owner": ProcessGroupDummy,
            "tcp-alone": _AloneTCP,
            "swallowing": lambda: ErrorSwallowingProcessGroupWrapper(
                ProcessGroupDummy()
            ),
        }[pg_kind]()
        manager = build(pg=pg, min_replica_size=1)
        client._quorum.return_value = make_quorum(
            replica_world_size=participants, max_world_size=participants
        )
        manager.start_quorum()
        assert manager.num_participants() == participants
        grads = self._mixed()
        before = {k: np.array(v) for k, v in grads.items()}
        out = manager.allreduce(grads).wait(timeout=10)
        for key, x in before.items():
            # in the leaf's dtype (numpy alone widens bf16 / int to f32)
            want = (x / participants).astype(x.dtype)
            assert out[key].dtype == x.dtype and out[key].shape == x.shape
            assert out[key].tobytes() == want.tobytes(), key
            assert not np.shares_memory(out[key], grads["host"])
            assert np.array(grads[key]).tobytes() == x.tobytes(), key
        assert manager.errored() is None
        pg.shutdown()

    @pytest.mark.parametrize("leaf", ["fortran-order", "bfloat16", "device"])
    def test_a_non_participant_sends_zeros_made_from_shapes(
        self, manager_ctx, leaf
    ):
        import jax.numpy as jnp
        import ml_dtypes

        build, client, transport = manager_ctx
        pg = _RecordingDummy()
        manager = build(pg=pg)
        _start_async_heal(manager, client, transport)
        assert not manager.is_participating()

        if leaf == "fortran-order":
            x = np.asfortranarray(np.arange(12, dtype=np.float32).reshape(3, 4) + 1)
            assert not x.flags.c_contiguous
        elif leaf == "bfloat16":
            x = (np.arange(6).reshape(2, 3) + 1).astype(ml_dtypes.bfloat16)
        else:
            # a leaf whose conversion raises: nothing may take it off the
            # device to make zeros of it
            x = jnp.arange(20, dtype=jnp.float32).reshape(4, 5) + 1
            x.delete()
            with pytest.raises(RuntimeError):
                np.asarray(x)
        out = manager.allreduce({"g": x}).wait(timeout=10)["g"]
        assert manager.errored() is None
        (sent,) = pg.sent
        for zeros in (sent, out):
            assert type(zeros) is np.ndarray and zeros.flags.c_contiguous
            assert zeros.shape == x.shape and zeros.dtype == x.dtype
            assert not zeros.any()
        if leaf != "device":
            assert not np.shares_memory(sent, x) and x.all()

    def test_divisor_one_hands_a_device_leaf_through(self, manager_ctx):
        import jax.numpy as jnp

        build, client, _ = manager_ctx
        manager = build(min_replica_size=1)
        client._quorum.return_value = make_quorum(
            replica_world_size=1, max_world_size=1
        )
        manager.start_quorum()
        dev = jnp.arange(1 << 12, dtype=jnp.float32)
        out = manager.allreduce({"g": dev}).wait(timeout=10)["g"]
        # the leaf itself, still on the device: no copy, no division
        assert out is dev
        np.testing.assert_array_equal(out, np.arange(1 << 12))

    def _alone(self, manager_ctx, pg_kind):
        build, client, _ = manager_ctx
        pg = {"owner": ProcessGroupDummy, "tcp-alone": _AloneTCP}[pg_kind]()
        manager = build(pg=pg, min_replica_size=1)
        client._quorum.return_value = make_quorum(
            replica_world_size=1, max_world_size=1
        )
        manager.start_quorum()
        assert manager.num_participants() == 1
        return manager, pg

    @pytest.mark.parametrize("pg_kind", ["owner", "tcp-alone"])
    def test_alone_a_device_pytree_comes_back_as_its_leaves(
        self, manager_ctx, pg_kind
    ):
        import jax
        import jax.numpy as jnp

        manager, pg = self._alone(manager_ctx, pg_kind)
        key = jax.random.PRNGKey(3)
        grads = {
            "w": jax.random.normal(key, (33, 7), jnp.float32),
            "blocks": [
                jax.random.normal(key, (5,), jnp.float32).astype(jnp.bfloat16),
                {"b": jnp.arange(3, dtype=jnp.float32) / 7},
            ],
        }
        before = jax.tree_util.tree_map(lambda x: np.array(x), grads)
        out = manager.allreduce(grads).wait(timeout=10)
        assert manager.errored() is None
        assert jax.tree_util.tree_structure(out) == jax.tree_util.tree_structure(grads)
        for got, leaf, want in zip(*map(jax.tree_util.tree_leaves, (out, grads, before))):
            assert isinstance(got, jax.Array) and got is leaf
            assert got.dtype == want.dtype and got.shape == want.shape
            assert np.asarray(got).tobytes() == want.tobytes()
        pg.shutdown()

    @pytest.mark.parametrize("pg_kind", ["owner", "tcp-alone"])
    def test_alone_a_mixed_pytree_returns_each_kind(self, manager_ctx, pg_kind):
        """A ``jax.Array`` leaf comes back as itself, an ``np.ndarray`` as a
        copy the caller may write, a Python scalar as a host array."""
        import jax
        import jax.numpy as jnp

        manager, pg = self._alone(manager_ctx, pg_kind)
        grads = {
            "dev": jnp.arange(6, dtype=jnp.float32) * 3,
            "host": np.arange(4, dtype=np.float32) + 1,
            "scalar": 2.5,
        }
        out = manager.allreduce(grads).wait(timeout=10)
        assert isinstance(out["dev"], jax.Array) and out["dev"] is grads["dev"]
        assert type(out["host"]) is np.ndarray and out["host"].flags.writeable
        assert not np.shares_memory(out["host"], grads["host"])
        out["host"] += 1
        np.testing.assert_array_equal(grads["host"], np.arange(4) + 1)
        assert isinstance(out["scalar"], np.ndarray) and out["scalar"] == 2.5
        pg.shutdown()

    @pytest.mark.parametrize("pg_kind", ["owner", "tcp-alone"])
    def test_a_lone_steps_phase_delta_still_holds_d2h_and_pack(
        self, manager_ctx, pg_kind
    ):
        """What ``benchmarks/layer_metrics/d2h_ms.py`` and ``ring_host_ms.py``
        read: a step whose leaves all stayed on the device still reports
        both parts, each above zero, inside ``ring``."""
        import jax.numpy as jnp

        manager, pg = self._alone(manager_ctx, pg_kind)
        before = manager.phase_times()
        for _ in range(2):
            manager.allreduce({"g": jnp.ones((64,), jnp.float32)}).wait(timeout=10)
            now = manager.phase_times()
            delta = {k: v - before.get(k, 0.0) for k, v in now.items()}
            before = now
            assert delta["ring.d2h"] > 0 and delta["ring.pack"] > 0
            assert delta["ring"] >= delta["ring.d2h"] + delta["ring.pack"]
        pg.shutdown()

    @pytest.mark.parametrize("how", ["latched", "op-fails", "swallowed"])
    def test_errored_pass_through_hands_the_input_back_unwritten(
        self, manager_ctx, how
    ):
        build, client, _ = manager_ctx
        inner = FakeProcessGroupWrapper(ProcessGroupDummy())
        pg = ErrorSwallowingProcessGroupWrapper(inner) if how == "swallowed" else inner
        manager = build(pg=pg)
        client._quorum.return_value = make_quorum()
        manager.start_quorum()
        x = np.arange(4, dtype=np.float32) + 1
        if how == "latched":
            manager.report_error(RuntimeError("earlier failure"))
        else:
            inner.report_future_error(RuntimeError("injected"))
        out = manager.allreduce(x).wait(timeout=10)
        # the step is lost either way; what matters is that nobody took the
        # caller's array for a buffer of its own and divided it in place
        np.testing.assert_array_equal(x, np.arange(4) + 1)
        np.testing.assert_array_equal(out, x)
        assert manager.errored() is not None or pg.errored() is not None


class TestManagerHealing:
    def test_async_heal_applies_on_commit(self, manager_ctx):
        build, client, transport = manager_ctx
        loaded = {}
        manager = build(
            load_state_dict=lambda sd: loaded.update(sd),
            state_dict=lambda: {"w": 1},
        )
        client.should_commit.return_value = True
        client._checkpoint_metadata.return_value = "http://peer"
        _start_async_heal(manager, client, transport)

        # healing: not participating this step, contributes zeros
        assert manager._healing
        assert not manager.is_participating()
        result = manager.allreduce(np.full(2, 5.0)).wait(timeout=10)
        np.testing.assert_allclose(result, np.zeros(2))

        # commit applies the healed user state on the main thread
        assert manager.should_commit()
        assert loaded == {"w": 42}
        # step restored from the healed torchft dict then bumped by commit
        assert manager.current_step() == 8

    def test_sync_quorum_heals_eagerly(self, manager_ctx):
        build, client, transport = manager_ctx
        loaded = {}
        manager = build(
            use_async_quorum=False,
            load_state_dict=lambda sd: loaded.update(sd),
            state_dict=lambda: {"w": 0},
        )
        client._quorum.return_value = make_quorum(
            replica_rank=1,
            max_step=3,
            heal=True,
            recover_src_replica_rank=0,
            recover_src_manager_address="peer:1",
        )
        transport.recv_checkpoint.return_value = {
            "user": {"default": {"w": 9}},
            "torchft": {"step": 3, "batches_committed": 6},
        }
        with patch("torchft_tpu.manager.ManagerClient") as peer_cls:
            peer_cls.return_value._checkpoint_metadata.return_value = "meta"
            manager.start_quorum()
        # eager apply: state loaded before returning; participates this step
        assert loaded == {"w": 9}
        assert not manager._healing
        assert manager.is_participating()

    def test_send_checkpoint_to_recovering_peers(self, manager_ctx):
        build, client, transport = manager_ctx
        manager = build()
        client._quorum.return_value = make_quorum(
            recover_dst_replica_ranks=[1, 2], max_step=4
        )
        manager.start_quorum()
        manager.wait_quorum()
        transport.send_checkpoint.assert_called_once()
        kwargs = transport.send_checkpoint.call_args.kwargs
        assert kwargs["dst_ranks"] == [1, 2]
        assert kwargs["step"] == 4
        assert "user" in kwargs["state_dict"] and "torchft" in kwargs["state_dict"]


class TestManagerFailures:
    def test_not_enough_participants_blocks_commit(self, manager_ctx):
        build, client, _ = manager_ctx
        manager = build(min_replica_size=3)
        client._quorum.return_value = make_quorum(max_world_size=2)
        client.should_commit.return_value = False
        manager.start_quorum()
        assert not manager.should_commit()
        assert manager.current_step() == 0
        # the local vote must have been False
        assert client.should_commit.call_args.args[2] is False

    def test_allreduce_error_swallowed_and_blocks_commit(self, manager_ctx):
        build, client, _ = manager_ctx
        pg = FakeProcessGroupWrapper(ProcessGroupDummy())
        manager = build(pg=pg)
        client._quorum.return_value = make_quorum()
        client.should_commit.return_value = False
        manager.start_quorum()
        pg.report_future_error(RuntimeError("injected allreduce failure"))
        # the work completes cleanly (with the input) but the error latches
        result = manager.allreduce(np.full(2, 3.0)).wait(timeout=10)
        np.testing.assert_allclose(result, np.full(2, 3.0))
        assert manager.errored() is not None
        assert not manager.should_commit()
        assert client.should_commit.call_args.args[2] is False
        # after the error, allreduce is a no-op passthrough
        np.testing.assert_allclose(
            manager.allreduce(np.full(2, 9.0)).wait(timeout=10), np.full(2, 9.0)
        )

    def test_pg_errored_blocks_commit(self, manager_ctx):
        build, client, _ = manager_ctx
        pg = ErrorSwallowingProcessGroupWrapper(ProcessGroupDummy())
        manager = build(pg=pg)
        client._quorum.return_value = make_quorum()
        client.should_commit.return_value = False
        manager.start_quorum()
        pg.report_error(RuntimeError("pg broke"))
        assert not manager.should_commit()
        assert manager.errored() is not None

    def test_quorum_failure_captured(self, manager_ctx):
        build, client, _ = manager_ctx
        manager = build()
        client._quorum.side_effect = TimeoutError("lighthouse down")
        client.should_commit.return_value = False
        manager.start_quorum()
        assert not manager.should_commit()
        assert manager.errored() is not None

    def test_max_retries_raises(self, manager_ctx):
        build, client, _ = manager_ctx
        manager = build(max_retries=2, min_replica_size=2)
        client._quorum.return_value = make_quorum(max_world_size=1)
        client.should_commit.return_value = False
        for _ in range(3):
            manager.start_quorum()
            if manager._commit_failures == 2:
                with pytest.raises(RuntimeError, match="max_retries"):
                    manager.should_commit()
            else:
                assert not manager.should_commit()

    def test_commit_failures_reported_to_quorum(self, manager_ctx):
        build, client, _ = manager_ctx
        manager = build(min_replica_size=5)
        client._quorum.return_value = make_quorum()
        client.should_commit.return_value = False
        manager.start_quorum()
        assert not manager.should_commit()
        manager.start_quorum()
        manager.wait_quorum()
        # second quorum call carries commit_failures=1
        assert client._quorum.call_args.kwargs["commit_failures"] == 1


class TestWorldSizeModes:
    def test_fixed_with_spares_caps_world(self, manager_ctx):
        build, client, _ = manager_ctx
        manager = build(
            min_replica_size=2, world_size_mode=WorldSizeMode.FIXED_WITH_SPARES
        )
        client._quorum.return_value = make_quorum(
            max_world_size=4, max_replica_rank=3
        )
        manager.start_quorum()
        assert manager.num_participants() == 2
        # this replica (rank 3) is a spare -> not participating
        assert not manager.is_participating()
        assert manager.participating_rank() is None


class TestStateDict:
    def test_state_dict_round_trip(self, manager_ctx):
        build, client, _ = manager_ctx
        manager = build()
        manager.load_state_dict({"step": 12, "batches_committed": 34})
        assert manager.current_step() == 12
        assert manager.state_dict() == {"step": 12, "batches_committed": 34}

    def test_manager_state_dict_composite(self, manager_ctx):
        build, client, _ = manager_ctx
        manager = build(state_dict=lambda: {"w": 5})
        sd = manager._manager_state_dict()
        assert sd["user"]["default"] == {"w": 5}
        assert sd["torchft"] == {"step": 0, "batches_committed": 0}

    def test_multiple_state_dict_fns(self, manager_ctx):
        build, client, _ = manager_ctx
        manager = build()
        loaded = {}
        manager.register_state_dict_fn(
            "frag0", lambda sd: loaded.update(frag0=sd), lambda: "s0"
        )
        manager.register_state_dict_fn(
            "frag1", lambda sd: loaded.update(frag1=sd), lambda: "s1"
        )
        sd = manager._manager_state_dict()
        assert sd["user"]["frag0"] == "s0" and sd["user"]["frag1"] == "s1"


class TestStaleManagerAddr:
    def test_nonzero_rank_probes_past_dead_incarnation_addr(self):
        """After a whole-group fast restart the store still holds the dead
        incarnation's manager address until the new rank 0 republishes; a
        non-zero rank must probe and re-read instead of wiring itself to
        the corpse (manager.py store-handoff loop)."""
        import socket
        import threading
        import time

        from torchft_tpu.coordination import (
            LighthouseServer,
            ManagerServer,
            StoreClient,
            StoreServer,
        )

        lighthouse = LighthouseServer(min_replicas=1)
        store = StoreServer()
        sc = StoreClient(store.address())
        # a port with no listener = the dead incarnation's endpoint
        with socket.socket() as s:
            s.bind(("", 0))
            dead_port = s.getsockname()[1]
        sc.set("manager_addr", f"127.0.0.1:{dead_port}")
        sc.set("replica_id", "grp:dead-incarnation")

        server_box = {}

        def republish():
            time.sleep(0.7)
            server = ManagerServer(
                replica_id="grp:new-incarnation",
                lighthouse_addr=lighthouse.address(),
                store_address=store.address(),
                world_size=2,
                bind=":0",
                heartbeat_interval=0.1,
                connect_timeout=5.0,
                quorum_retries=0,
            )
            server_box["server"] = server
            # the store-handoff contract: replica_id BEFORE manager_addr
            # (a live addr implies the matching id is already visible)
            sc.set("replica_id", "grp:new-incarnation")
            sc.set("manager_addr", server.address())

        t = threading.Thread(target=republish, daemon=True)
        t.start()
        try:
            manager = Manager(
                pg=ProcessGroupDummy(),
                min_replica_size=1,
                load_state_dict=lambda sd: None,
                state_dict=lambda: {"x": np.zeros(1)},
                lighthouse_addr=lighthouse.address(),
                group_rank=1,
                group_world_size=2,
                store_addr=store.address(),
                connect_timeout=5.0,
            )
            # wired to the LIVE incarnation, not the stale published addr
            assert manager.replica_id() == "grp:new-incarnation"
            manager.shutdown()
        finally:
            t.join(timeout=5)
            if "server" in server_box:
                server_box["server"].shutdown()
            sc.close()
            store.shutdown()
            lighthouse.shutdown()

    def test_nonzero_rank_times_out_when_no_live_server_appears(self):
        import socket

        from torchft_tpu.coordination import LighthouseServer, StoreClient, StoreServer

        lighthouse = LighthouseServer(min_replicas=1)
        store = StoreServer()
        sc = StoreClient(store.address())
        with socket.socket() as s:
            s.bind(("", 0))
            dead_port = s.getsockname()[1]
        sc.set("manager_addr", f"127.0.0.1:{dead_port}")
        sc.set("replica_id", "grp:dead")
        try:
            with pytest.raises(TimeoutError, match="unreachable"):
                Manager(
                    pg=ProcessGroupDummy(),
                    min_replica_size=1,
                    load_state_dict=lambda sd: None,
                    state_dict=lambda: {"x": np.zeros(1)},
                    lighthouse_addr=lighthouse.address(),
                    group_rank=1,
                    group_world_size=2,
                    store_addr=store.address(),
                    connect_timeout=2.0,
                )
        finally:
            sc.close()
            store.shutdown()
            lighthouse.shutdown()
