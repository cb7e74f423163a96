"""Host-device transfer per policy decision, in KB (1,024 bytes): the
window's ``h2d_bytes`` and ``d2h_bytes`` counts, summed over its span
records, over its ``decide`` spans.  A program whose span records carry
no ``counts`` gives no reading."""

COUNTERS = ("h2d_bytes", "d2h_bytes")


def read(run):
    decisions = sum(1 for s in run.spans if s["name"] == "decide")
    counted = [s["counts"] for s in run.spans if "counts" in s]
    if not decisions or not counted:
        return None
    return sum(c.get(k, 0) for c in counted
               for k in COUNTERS) / decisions / 1024
