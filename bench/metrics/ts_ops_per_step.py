"""ts_ops_per_step: tuple-space puts, takes and reads in the window
(the difference of ts.stats() between its two ends) per committed step."""

KINDS = ("puts", "takes", "reads")


def read(run):
    a, b = run.rec.ts_open, run.rec.ts_close
    return sum(b[k] - a[k] for k in KINDS) / run.window_steps
