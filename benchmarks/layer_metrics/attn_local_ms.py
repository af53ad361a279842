"""Device time a grad step spends in the window layers' attention: the
operations of the grad step's program whose ``op_name`` passes through the
program's ``jax.named_scope`` ``attn.local`` (``models/afmoe.py``: the norms of
queries and keys per head, the rotary, the windowed flash kernels, the gate's
product; forward, the forward again under remat, and backward, all window
layers), from the device trace.  The five projections are under ``attn.proj``
and not counted.  The family reads the rows (``scope_ms``)."""


def read(run):
    family = run.get("family")
    if not hasattr(family, "FLASH_WINDOW_KERNELS"):
        return None  # a family without window layers
    return family.scope_ms(run, ("attn.local",))
