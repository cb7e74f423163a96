"""Stream-engine host self time per event, in ms.

Each window event's ``event`` span less its children that belong to
layers of their own (``decide``: posterior and scoring; ``gp_fold``: the
fold).  What is left is ingest, admission and retirement (with their
mirror rebuilds), the event log and the launch bookkeeping."""

OTHER_LAYERS = ("decide", "gp_fold")


def read(run):
    events = [s for s in run.spans if s["name"] == "event"]
    if not events:
        return None
    child = {}
    for s in run.spans:
        if s["name"] in OTHER_LAYERS:
            key = (s["trace"], s["parent"])
            child[key] = child.get(key, 0.0) + s["dur_us"]
    own = [s["dur_us"] - child.get((s["trace"], s["span"]), 0.0)
           for s in events]
    return sum(own) / len(own) / 1e3
