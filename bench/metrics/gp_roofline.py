"""Share, in %, of the GP row loops' device time that the rows they must
read would take at the chip's peak HBM bandwidth.

Each row a fold or a readout loops over (the window's ``fold_rows`` and
``readout_rows`` counts) is one row of W, read once by one ``rows_dot``:
m float32 values, m the block's candidates.  m is counted from the live
pool, not from how the program lays W out: candidates per live tenant at
the window's decisions, which is each block's m where every tenant holds
as many candidates (so in each cell that lists this metric).  The loops do
no matrix product, so the roofline is the bandwidth bound.  The device
time is that of the ``_append_step`` (fold) and ``_readout_into``
(readout) executions."""

PROGRAMS = ("jit__append_step", "jit__readout_into")
COUNTS = ("fold_rows", "readout_rows")
ROW_BYTES = 4       # a float32 of W


def read(run):
    programs = (run.profile or {}).get("programs", {})
    device_s = sum(sum(programs.get(p, ())) for p in PROGRAMS)
    rows = sum(s.get("counts", {}).get(c, 0) for s in run.spans
               for c in COUNTS)
    tenants = sum(t for _, t in run.decide_live)
    if not device_s or not rows or not tenants or run.peaks is None:
        return None
    width = sum(c for c, _ in run.decide_live) / tenants
    least = rows * width * ROW_BYTES / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / device_s
