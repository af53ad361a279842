"""``heal_send.encode`` + ``heal_send.hash`` on the surviving group that spent
longest in ``heal_send``: serialising its state (about three quarters of it on
the v5e) and the sha256 of the bytes, fragment by fragment, summed over the
window.  Timed inside the program."""

from benchmarks.layer_metrics import heal_snapshot_s


def read(run):
    return heal_snapshot_s.read(run, ("heal_send.encode", "heal_send.hash"))
