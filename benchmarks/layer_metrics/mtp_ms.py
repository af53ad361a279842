"""Device time a grad step spends in the multi-token-prediction module: the
operations of the grad step's program whose ``op_name`` passes through the
program's ``jax.named_scope`` ``mtp`` (``models/joyai.py``: the next token's
embedding, the two norms and ``W_eh`` (``mtp.merge``), the module's own
latent-attention and expert layer, its final norm and its pass through the
trunk's head with the second depth's loss; forward, the forward again under
remat, and backward), from the device trace.  The scopes nested under it
(``mtp/mla``, ``mtp/moe.experts``, ``mtp/head``) are read by their own
metrics too.  The family reads the rows (``scope_ms``)."""


def read(run):
    scope_ms = getattr(run.get("family"), "scope_ms", None)
    return scope_ms(run, ("mtp",)) if scope_ms else None
