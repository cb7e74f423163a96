"""Blocking device-to-host readbacks per policy decision: the window's
``host_syncs`` counts, summed over its span records, over its ``decide``
spans.  A program whose span records carry no ``counts`` gives no
reading."""

COUNTERS = ("host_syncs",)


def read(run):
    decisions = sum(1 for s in run.spans if s["name"] == "decide")
    counted = [s["counts"] for s in run.spans if "counts" in s]
    if not decisions or not counted:
        return None
    return sum(c.get(k, 0) for c in counted for k in COUNTERS) / decisions
