"""Posterior flush per policy decision, in ms: the mean ``gp_flush`` span,
on the device scorers' path one readout dispatch per dirty GP block into
the device-resident pool (nothing read back)."""


def read(run):
    spans = [s["dur_us"] for s in run.spans if s["name"] == "gp_flush"]
    return sum(spans) / len(spans) / 1e3 if spans else None
