"""Device time a grad step spends in the global layers' attention: the
operations under the program's ``jax.named_scope`` ``attn.global``
(``models/afmoe.py``: the norms of queries and keys per head, the causal flash
kernels, the gate's product; no rotary; forward, the forward again under remat,
and backward, all global layers), from the device trace: to be read beside
``attn_local_ms``, whose layers differ by the window and the rotary alone.
The family reads the rows (``scope_ms``)."""


def read(run):
    family = run.get("family")
    if not hasattr(family, "FLASH_WINDOW_KERNELS"):
        return None  # a family without window layers beside its global ones
    return family.scope_ms(run, ("attn.global",))
