"""Fault-tolerant optimizer wrapper (optax).

Analog of the reference OptimizerWrapper (reference: torchft/optim.py:48-55):
the step boundary hooks the FT protocol — ``begin_step`` (the zero_grad
analog) starts the quorum; ``step`` applies the optax update only if
``should_commit`` votes yes.  Functional JAX adaptation: instead of mutating
module parameters, ``step`` returns the (possibly unchanged) new
``(params, opt_state, committed)``.
"""

from __future__ import annotations

from typing import Any, Tuple

import jax
import optax

from torchft_tpu.manager import Manager


class OptimizerWrapper:
    """Wraps an optax GradientTransformation with the Manager protocol.

    Usage::

        opt = OptimizerWrapper(manager, optax.adamw(3e-4))
        opt_state = opt.init(params)
        ...
        opt.begin_step()                       # starts quorum (zero_grad analog)
        grads = grad_fn(params, batch)
        avg = manager.allreduce(grads).wait()
        if manager.should_commit():            # an async heal lands HERE
            params, opt_state = opt.update(params, avg, opt_state)
    """

    def __init__(self, manager: Manager, optimizer: optax.GradientTransformation) -> None:
        self._manager = manager
        self._optimizer = optimizer
        # One program per step instead of one dispatch per leaf-op, with
        # params and opt_state donated: eager optax keeps mu, nu, their
        # bias-corrected copies and the updates alive next to the old
        # state (~8x params for adamw) — at flagship scale that does not
        # fit a 16 GB chip; the donated program peaks at state + grads.
        self._update = jax.jit(self._update_fn, donate_argnums=(0, 2))

    def _update_fn(self, params: Any, grads: Any, opt_state: Any) -> "Tuple[Any, Any]":
        updates, opt_state = self._optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state

    def init(self, params: Any) -> Any:
        return self._optimizer.init(params)

    def begin_step(self) -> None:
        """Start the new step's quorum (reference: zero_grad -> start_quorum)."""
        self._manager.start_quorum()

    # torch-API-compatible alias
    zero_grad = begin_step

    def update(self, params: Any, grads: Any, opt_state: Any) -> "Tuple[Any, Any]":
        """Apply the optax update unconditionally (no vote): one jitted
        program; ``params`` and ``opt_state`` are DONATED — rebind to the
        returned ``(params, opt_state)`` and drop the old references.
        Host (numpy) inputs are transferred by the call."""
        return self._update(params, grads, opt_state)

    def step(
        self, params: Any, grads: Any, opt_state: Any
    ) -> "Tuple[Any, Any, bool]":
        """Vote, then :meth:`update` iff the group commits.

        Returns ``(params, opt_state, committed)`` — unchanged on a failed
        commit so the step is retried on consistent state.

        With an async quorum a live heal is applied inside the vote, i.e.
        AFTER the caller evaluated ``params``/``opt_state`` for this call,
        so the update would run on the pre-heal state.  Loops that can heal
        asynchronously call ``manager.should_commit()`` themselves and pass
        the post-vote state to :meth:`update` (class docstring).
        """
        if not self._manager.should_commit():
            return params, opt_state, False
        new_params, new_opt_state = self.update(params, grads, opt_state)
        return new_params, new_opt_state, True
