"""Share of the traced window, in %, in which no operation ran on the
device: 1 - (union of device-op intervals) / window."""


def read(run):
    p = run.profile
    if not p or p["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - p["busy_s"] / p["window_s"])
