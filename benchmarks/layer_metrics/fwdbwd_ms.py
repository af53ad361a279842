"""Device time of the jitted grad step (``models/transformer.py``
``make_grad_step``) per run, from the ``XLA Modules`` line of the trace: mean
over its runs inside the traced steps."""


def read(run):
    runs = run["trace"]["module_seconds"].get(run["grad_module"])
    return 1e3 * sum(runs) / len(runs) if runs else None
