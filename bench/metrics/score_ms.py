"""EIrate scoring and argmax per policy decision, in ms: the mean
``score`` span (it ends in host readbacks, so it times execution)."""


def read(run):
    spans = [s["dur_us"] for s in run.spans if s["name"] == "score"]
    return sum(spans) / len(spans) / 1e3 if spans else None
