"""Share, in %, of the scoring program's device time that the bytes one
decision needs would take at the chip's peak HBM bandwidth.

What a decision needs is counted from the live pool, not from how the
program lays it out: per live candidate its posterior mean, sd and cost
(float32 each), its selected flag (one byte) and its owner (an int32
tenant index); per live tenant one float32 incumbent.  Scoring has no
matrix product, so the roofline is the bandwidth bound.  The device time
is that of ``choose_next_fused``, the program every decision runs."""

PROGRAM = "jit_choose_next_fused"
CANDIDATE_BYTES = 4 + 4 + 4 + 1 + 4
TENANT_BYTES = 4


def needed_bytes(candidates: int, tenants: int) -> int:
    return candidates * CANDIDATE_BYTES + tenants * TENANT_BYTES


def read(run):
    runs = (run.profile or {}).get("programs", {}).get(PROGRAM)
    if not runs or not run.decide_live or run.peaks is None:
        return None
    least = sum(needed_bytes(c, t) for c, t in run.decide_live) \
        / run.peaks["hbm_bytes_per_s"]
    # one scoring execution per decision; scale if the trace caught a
    # different number of either
    least *= len(runs) / len(run.decide_live)
    return 100.0 * least / sum(runs)
