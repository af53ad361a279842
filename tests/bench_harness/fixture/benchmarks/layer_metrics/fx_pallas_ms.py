"""Device time a grad step spends in Pallas kernels, whatever the family: every
operation of the grad step's program that the compiled executable names as a
``pallas_call``'s kernel (``run["trace"]["ops"][i]["kernel"]``)."""


def read(run):
    runs = run["trace"]["module_seconds"].get(run["grad_module"])
    spent = sum(op["seconds"] for op in run["trace"].get("ops", ())
                if op["module"] == run["grad_module"] and op["kernel"])
    return 1e3 * spent / len(runs) if runs and spent else None
