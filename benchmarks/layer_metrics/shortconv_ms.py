"""Device time a grad step spends in the gated short convolutions, the token
mixer of the ``conv`` layers: the operations of the grad step's program whose
``op_name`` passes through the program's ``jax.named_scope`` ``shortconv``
(``models/lfm2.py`` ``short_conv_mixer``: the two projections under
``shortconv.proj`` and the chain gate, taps, gate under ``shortconv.mix``
inside it; forward, the forward again under remat, and backward, all
convolution layers), from the device trace.  The family reads the rows
(``scope_ms``)."""


def read(run):
    family = run.get("family")
    if not hasattr(family, "shortconv_work") or not hasattr(family, "scope_ms"):
        return None
    return family.scope_ms(run, ("shortconv",))
