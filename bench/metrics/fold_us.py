"""Device time of one GP fold, in us: the mean duration of the
``_append_step`` program's executions in the window's device trace."""

PROGRAM = "jit__append_step"


def read(run):
    runs = (run.profile or {}).get("programs", {}).get(PROGRAM)
    return sum(runs) / len(runs) * 1e6 if runs else None
