"""Optimizer wrapper, DDP, and device-mesh unit tests.

Mirrors reference torchft/optim_test.py:19, ddp_test.py:23-39,
device_mesh_test.py.
"""

from unittest.mock import create_autospec

import jax
import numpy as np
import optax
import pytest

from torchft_tpu.ddp import DistributedDataParallel, PureDistributedDataParallel
from torchft_tpu.manager import Manager
from torchft_tpu.optim import OptimizerWrapper
from torchft_tpu.parallel.device_mesh import ft_init_device_mesh
from torchft_tpu.parallel.work import completed_work


def mock_manager():
    manager = create_autospec(Manager, instance=True)
    manager.allreduce.side_effect = lambda v, **kw: completed_work(v)
    return manager


class TestOptimizerWrapper:
    def test_begin_step_starts_quorum(self):
        manager = mock_manager()
        opt = OptimizerWrapper(manager, optax.sgd(0.1))
        opt.begin_step()
        manager.start_quorum.assert_called_once()
        # torch-compatible alias
        opt.zero_grad()
        assert manager.start_quorum.call_count == 2

    def test_step_commits(self):
        manager = mock_manager()
        manager.should_commit.return_value = True
        opt = OptimizerWrapper(manager, optax.sgd(1.0))
        params = {"w": np.full(2, 3.0, dtype=np.float32)}
        state = opt.init(params)
        new_params, state, committed = opt.step(
            params, {"w": np.full(2, 1.0, dtype=np.float32)}, state
        )
        assert committed
        np.testing.assert_allclose(new_params["w"], np.full(2, 2.0))

    def test_step_skipped_on_failed_commit(self):
        manager = mock_manager()
        manager.should_commit.return_value = False
        opt = OptimizerWrapper(manager, optax.sgd(1.0))
        params = {"w": np.full(2, 3.0, dtype=np.float32)}
        state = opt.init(params)
        new_params, new_state, committed = opt.step(
            params, {"w": np.ones(2, dtype=np.float32)}, state
        )
        assert not committed
        np.testing.assert_allclose(new_params["w"], params["w"])
        assert new_state is state


    def test_update_is_one_donated_program_and_does_not_vote(self):
        """`update` is what a loop calls AFTER its own should_commit():
        no vote inside, one jitted optax step, params/opt_state donated."""
        import jax.numpy as jnp

        manager = mock_manager()
        opt = OptimizerWrapper(manager, optax.adamw(0.1))
        params = {"w": jnp.full((4,), 3.0)}
        state = opt.init(params)
        new_params, new_state = opt.update(
            params, {"w": np.ones(4, np.float32)}, state
        )
        manager.should_commit.assert_not_called()
        assert isinstance(new_params["w"], jax.Array)
        assert float(new_params["w"][0]) < 3.0
        assert params["w"].is_deleted()  # donated: rebind, do not reuse
        assert int(new_state[0].count) == 1


class TestDDP:
    def test_allreduce_gradients(self):
        manager = mock_manager()
        manager.allreduce.side_effect = lambda g, **kw: completed_work(
            jax.tree_util.tree_map(lambda x: x * 0.5, g)
        )
        ddp = DistributedDataParallel(manager)
        grads = {"w": np.full(4, 2.0), "b": np.ones(2)}
        avg = ddp.allreduce_gradients(grads).wait(timeout=5)
        np.testing.assert_allclose(avg["w"], np.full(4, 1.0))

    def test_wrap_grad_fn(self):
        manager = mock_manager()
        ddp = DistributedDataParallel(manager)

        def grad_fn(params, batch):
            return 0.5, {"w": params["w"] * batch}

        wrapped = ddp.wrap_grad_fn(grad_fn)
        loss, grads = wrapped({"w": np.ones(2)}, 3.0)
        assert loss == 0.5
        np.testing.assert_allclose(grads["w"], np.full(2, 3.0))
        manager.allreduce.assert_called_once()

    def test_pure_ddp_per_leaf(self):
        manager = mock_manager()
        ddp = PureDistributedDataParallel(manager)
        grads = {"w": np.ones(2), "b": np.ones(3)}
        out = ddp.allreduce_gradients(grads)
        assert manager.allreduce.call_count == 2
        np.testing.assert_allclose(out["w"], np.ones(2))


class TestManagedDeviceMesh:
    def test_composition(self):
        manager = mock_manager()
        manager.num_participants.return_value = 3
        manager.participating_rank.return_value = 1
        mesh = ft_init_device_mesh(
            manager, {"fsdp": 4, "tp": 2}, devices=jax.devices()
        )
        assert mesh.axis_names == ("dp_replicate", "fsdp", "tp")
        assert mesh.shape() == {"dp_replicate": 3, "fsdp": 4, "tp": 2}
        assert mesh.num_participants() == 3
        # batch slice for replica 1 of 3 on a 12-example global batch
        assert mesh.global_batch_slice(12) == (4, 8)

    def test_zero_participants_reports_one(self):
        manager = mock_manager()
        manager.num_participants.return_value = 0
        manager.participating_rank.return_value = None
        mesh = ft_init_device_mesh(manager, {"fsdp": 8}, devices=jax.devices())
        assert mesh.shape()["dp_replicate"] == 1

    def test_non_participating_gets_empty_batch_slice(self):
        """A healing replica must not silently train on rank 0's data."""
        manager = mock_manager()
        manager.num_participants.return_value = 3
        manager.participating_rank.return_value = None
        manager.is_participating.return_value = False
        mesh = ft_init_device_mesh(manager, {"fsdp": 8}, devices=jax.devices())
        assert mesh.global_batch_slice(12) == (0, 0)

    def test_device_count_mismatch(self):
        manager = mock_manager()
        with pytest.raises(ValueError, match="devices"):
            ft_init_device_mesh(manager, {"fsdp": 3}, devices=jax.devices())

    def test_inner_mesh_usable_by_pjit(self):
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        manager = mock_manager()
        mesh = ft_init_device_mesh(manager, {"fsdp": 8}, devices=jax.devices())
        x = jnp.arange(16.0).reshape(8, 2)
        sharding = NamedSharding(mesh.mesh, P("fsdp", None))
        y = jax.device_put(x, sharding)
        out = jax.jit(lambda a: (a * 2).sum())(y)
        assert float(out) == float((x * 2).sum())
