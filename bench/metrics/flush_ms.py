"""Posterior flush per policy decision, in ms: the mean ``gp_flush`` span,
the readback of the dirty GP blocks into the host cache (where the host
first waits for the folds' device work)."""


def read(run):
    spans = [s["dur_us"] for s in run.spans if s["name"] == "gp_flush"]
    return sum(spans) / len(spans) / 1e3 if spans else None
