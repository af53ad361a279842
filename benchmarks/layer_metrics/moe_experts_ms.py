"""Device time a grad step spends routing to and computing the routed experts
held here: the operations under the program's ``jax.named_scope``s
``moe.route`` (router, top-k, the rows each held expert gets) and
``moe.experts`` (gather, the batched SwiGLU, the add back by token), forward,
the forward again under remat, and backward, all expert layers.  The shared
expert (``moe.shared``) is not counted.  The family reads the rows
(``scope_ms``)."""


def read(run):
    scope_ms = getattr(run.get("family"), "scope_ms", None)
    return scope_ms(run, ("moe.route", "moe.experts")) if scope_ms else None
