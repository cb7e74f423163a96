"""Device time of the GP fold per observed row it loops over, in ns: the
device time of the window's ``_append_step`` executions over the sum of
its ``fold_rows`` counts (the rows, k, each fold looped over).  A program
that counts no ``fold_rows`` gives no reading."""

PROGRAM = "jit__append_step"
COUNT = "fold_rows"


def read(run):
    runs = (run.profile or {}).get("programs", {}).get(PROGRAM)
    rows = sum(s.get("counts", {}).get(COUNT, 0) for s in run.spans)
    return sum(runs) / rows * 1e9 if runs and rows else None
