"""The survivors' side of a recovery inside the ring: on the groups that were
not killed, the largest ``ring.wire.arrive`` of a single measured step, which
is the step whose allreduce waited for the healer to reach the ring.  Timed
inside the program; nothing without a kill or without the part."""


def read(run):
    victims = {k["group"] for k in run["kills"]}
    rows = [r["phases"]["ring.wire.arrive"] for r in run["records"]
            if r["measured"] and r["group"] not in victims and "ring.wire.arrive" in r["phases"]]
    return max(rows) if victims and rows else None
