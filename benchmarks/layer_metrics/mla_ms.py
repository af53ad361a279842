"""Device time a grad step spends in latent attention: the operations of the
grad step's program whose ``op_name`` passes through the program's
``jax.named_scope`` ``mla`` (``models/mla.py``: the query's and the key-value
latents with their norms, the projections up to the heads, the rotary of the
query's and the key's rotary dimensions (``mla.rope`` inside it), the causal
flash kernels at 192 / 128 and the output projection; forward, the forward
again under remat, and backward, every block: the trunk's and the
multi-token-prediction module's), from the device trace.  The family reads the
rows (``scope_ms``)."""


def read(run):
    scope_ms = getattr(run.get("family"), "scope_ms", None)
    return scope_ms(run, ("mla",)) if scope_ms else None
