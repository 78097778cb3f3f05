"""Programs launched on a device per step: events on the `XLA Modules`
line inside the window over the window's steps (mean over the chips)."""


def read(run):
    t = run["trace"]
    if not t or not t["devices"] or not t["steps"]:
        return None
    return t["programs"] / t["steps"]
