"""``heal_send.snapshot`` on the surviving group that spent longest in
``heal_send``: its state's device-to-host copy, fragment by fragment, summed
over the window.  Timed inside the program, where the copy is made."""


def read(run, parts=("heal_send.snapshot",)):
    victims = {k["group"] for k in run["kills"]}
    send, part = {}, {}
    for r in run["records"]:
        if r["measured"] and r["group"] not in victims and parts[0] in r["phases"]:
            g = r["group"]
            send[g] = send.get(g, 0.0) + r["phases"].get("heal_send", 0.0)
            part[g] = part.get(g, 0.0) + sum(r["phases"].get(k, 0.0) for k in parts)
    return part[max(send, key=send.get)] if victims and send else None
