"""GP fold per observation, in ms: the mean ``gp_fold`` span.  It ends in
a sync on the fold's outputs, so it times the fold's host work, its
dispatch and its device execution."""


def read(run):
    spans = [s["dur_us"] for s in run.spans if s["name"] == "gp_fold"]
    return sum(spans) / len(spans) / 1e3 if spans else None
