"""Device time a grad step spends in the block-diffusion loss: the operations
of the grad step's program whose ``op_name`` passes through the program's
``jax.named_scope``s ``head`` or ``sdar.loss`` (``models/sdar.py``: the final
norm and the untied head over the noised copy's ``T`` positions of the ``2T``
a row runs, the log-sum-exp, the picked logits and their weighting by ``1 /
p_b`` over the masked positions, a row at a time; forward, the forward again
under its checkpoint, and backward), from the device trace.  The family reads
the rows (``scope_ms``)."""


def read(run):
    family = run.get("family")
    if not hasattr(family, "flash_block_work"):
        return None  # a family without the block-diffusion step
    return family.scope_ms(run, ("head", "sdar.loss"))
