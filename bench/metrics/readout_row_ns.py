"""Device time of the posterior readout per observed row it loops over, in
ns: the device time of the window's ``_readout_into`` executions (one per
dirty block a decision flushes) over the sum of its ``readout_rows``
counts (each readout's k).  A program that counts no ``readout_rows``
gives no reading."""

PROGRAM = "jit__readout_into"
COUNT = "readout_rows"


def read(run):
    runs = (run.profile or {}).get("programs", {}).get(PROGRAM)
    rows = sum(s.get("counts", {}).get(COUNT, 0) for s in run.spans)
    return sum(runs) / rows * 1e9 if runs and rows else None
