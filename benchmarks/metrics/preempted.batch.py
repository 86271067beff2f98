"""Requests the engine preempted for pages inside the window: the
difference of ``engine.stats()["preempted"]`` between the window's edges."""


def read(ctx):
    marks = ctx["marks"]
    return float(marks["close"]["preempted"] - marks["open"]["preempted"])
