"""Growth of W per policy decision, in ms: the window's ``gp_grow`` spans
(a block's rows doubled and copied) summed, over its ``decide`` spans; 0 in
a window with no doubling.  A program that counts no ``fold_rows`` (one
without the growing layout) gives no reading."""


def read(run):
    decisions = sum(1 for s in run.spans if s["name"] == "decide")
    if not decisions or not any("fold_rows" in s.get("counts", {})
                                for s in run.spans):
        return None
    grown = sum(s["dur_us"] for s in run.spans if s["name"] == "gp_grow")
    return grown / decisions / 1e3
