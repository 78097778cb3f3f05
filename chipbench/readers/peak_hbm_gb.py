"""Peak device memory in GB (1e9 bytes) on the fullest chip: the larger
of `peak_bytes_in_use` and `peak_bytes_reserved` of `memory_stats()`."""


def read(run):
    return run["memory_peak_bytes"] / 1e9 if run["memory_peak_bytes"] else None
