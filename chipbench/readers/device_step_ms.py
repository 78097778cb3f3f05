"""Device time of one step in ms: the union of the `XLA Ops` intervals
inside the window, over the window's steps (mean over the chips)."""


def read(run):
    t = run["trace"]
    if not t or not t["devices"] or not t["steps"]:
        return None
    return t["busy_ns"] / t["steps"] / 1e6
