"""Device time a grad step spends in the chunked delta rule of the KDA layers:
the operations of the grad step's program whose ``op_name`` passes through the
program's ``jax.named_scope`` ``kda`` (``models/kimi_linear.py``; forward, the
forward again under remat, and backward, all layers), from the device trace.
The projections, convolutions, gates and output norm are under ``kda.proj``
and not counted.  The family reads the rows (``scope_ms``)."""


def read(run):
    scope_ms = getattr(run.get("family"), "scope_ms", None)
    return scope_ms(run, ("kda",)) if scope_ms else None
