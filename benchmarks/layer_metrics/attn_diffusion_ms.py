"""Device time a grad step spends in the block-diffusion attention: the
operations of the grad step's program whose ``op_name`` passes through the
program's ``jax.named_scope`` ``attn.diffusion`` (``models/sdar.py``: the norms
of queries and keys per head, the rotary by token index, the clean copy's
block-causal flash call, the noised copy's strictly block-causal call on the
clean keys, its own block's dense scores and the merge of the two partial
softmaxes; forward and backward, all layers), from the device trace.  The four
projections are under ``attn.proj`` and not counted.  The family reads the
rows (``scope_ms``)."""


def read(run):
    family = run.get("family")
    if not hasattr(family, "flash_block_work"):
        return None  # a family without the block-diffusion step
    return family.scope_ms(run, ("attn.diffusion",))
