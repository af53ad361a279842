"""Device time a grad step spends deciding where the tokens go: the operations
under the program's ``jax.named_scope`` ``moe.route`` (``models/moe.py``
``held_moe_ffn``: the router's product, the scores, the top-k and the weights
under ``moe.route.score``; the ``[N k, held]`` placement, its ``cumsum``s and
the pool's rows under ``moe.route.place``), forward, the forward again under
remat, and backward, all expert layers.  ``moe_experts_ms`` reads this and the
experts' own products together; this one says what the placement costs as the
experts held grow.  The family reads the rows (``scope_ms``)."""


def read(run):
    scope_ms = getattr(run.get("family"), "scope_ms", None)
    return scope_ms(run, ("moe.route",)) if scope_ms else None
