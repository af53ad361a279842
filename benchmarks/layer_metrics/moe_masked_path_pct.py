"""Which of the routed experts' two paths a grad step ran: of the device time
under the program's scopes ``moe.gathered`` (the grouped products over the
assignments that landed here, the path a deployment's load takes) and
``moe.masked`` (every held expert over every token, taken by a layer whose
batch sent more here than its pool holds), the masked path's share.  0: every
expert layer's load fit its pool.  The router learns from a loss that sees the
held experts alone and nothing balances it (PERF.md section 5), so a window
can run either; ``moe_experts_ms`` and ``grad_step_mfu_pct`` are to be read
beside this.  The family reads the rows (``scope_ms``)."""


def read(run):
    scope_ms = getattr(run.get("family"), "scope_ms", None)
    if not scope_ms:
        return None
    masked = scope_ms(run, ("moe.masked",))
    if masked is None:
        return None
    both = scope_ms(run, ("moe.masked", "moe.gathered"))
    return 100.0 * masked / both if both else 0.0
