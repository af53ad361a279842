"""``heal_send`` on the surviving groups that served a heal (device-to-host,
encode, digests): each group's total over the window, the largest."""


def read(run):
    victims = {k["group"] for k in run["kills"]}
    if not victims:
        return None
    totals = {}
    for r in run["records"]:
        if r["measured"] and r["group"] not in victims:
            totals[r["group"]] = totals.get(r["group"], 0.0) + r["phases"].get("heal_send", 0.0)
    return max(totals.values()) if totals else None
