"""Share of the traced window in which no op ran on the device: 1 minus
the busy union over the harness's window span, on the trace's clock."""


def read(run):
    t = run["trace"]
    if not t or not t["devices"]:
        return None
    return 100.0 * (1.0 - t["busy_ns"] / t["window_ns"])
