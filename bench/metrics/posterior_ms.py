"""Posterior flush and upload per policy decision, in ms: the mean
``posterior`` span (it ends in a sync, so it times execution)."""


def read(run):
    spans = [s["dur_us"] for s in run.spans if s["name"] == "posterior"]
    return sum(spans) / len(spans) / 1e3 if spans else None
