"""Fault-tolerant data parallelism for JAX training loops.

Analog of the reference FT-DDP (reference: torchft/ddp.py:32-105).  The
reference hooks torch's gradient buckets; in JAX gradients are an explicit
pytree, so DDP here is a gradient-averaging step: zero-contribution
participation and live-count division come from ``Manager.allreduce``
(reference trick, manager.py:416-417), which keeps compiled shapes static
across membership changes.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import jax

from torchft_tpu.manager import Manager
from torchft_tpu.parallel.work import Work


class DistributedDataParallel:
    """FT gradient averaging over the elastic replica dimension.

    Usage::

        ddp = DistributedDataParallel(manager)
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        avg_grads = ddp.allreduce_gradients(grads).wait()
    """

    def __init__(self, manager: Manager, should_quantize: bool = False) -> None:
        self._manager = manager
        self._should_quantize = should_quantize

    def allreduce_gradients(self, grads: Any) -> Work:
        """Average a gradient pytree over the live quorum (single fused op —
        bandwidth-optimal for the ring; the reference's bucket hook exists to
        overlap with backward, which JAX expresses via async dispatch).  One
        op to the caller; beneath it the device link is not fused with the
        ring: the group rings bucket i while the leaves of the next
        buckets are still crossing to the host."""
        return self._manager.allreduce(grads, should_quantize=self._should_quantize)

    def wrap_grad_fn(
        self, grad_fn: "Callable[..., Tuple[Any, Any]]"
    ) -> "Callable[..., Tuple[Any, Any]]":
        """Wrap a ``value_and_grad``-style fn so its gradients come back
        pre-averaged (the comm-hook analog, reference ddp.py:67-79)."""

        def wrapped(*args: Any, **kwargs: Any) -> "Tuple[Any, Any]":
            value, grads = grad_fn(*args, **kwargs)
            return value, self.allreduce_gradients(grads).wait()

        return wrapped


class PureDistributedDataParallel:
    """Naive per-leaf allreduce (reference ddp.py:82-105): simpler to reason
    about, one collective per parameter — for tests and small models."""

    def __init__(self, manager: Manager) -> None:
        self._manager = manager

    def allreduce_gradients(self, grads: Any) -> Any:
        leaves, treedef = jax.tree_util.tree_flatten(grads)
        works = [self._manager.allreduce(leaf) for leaf in leaves]
        return jax.tree_util.tree_unflatten(treedef, [w.wait() for w in works])
