"""grad_runs_per_step.faults: in the cell under kills, executions of the
jaxgrad op for the window's steps, duplicates from straggler re-issue
included, per committed step (n_micro is the least). Counted by the
benchmark's span around the op."""


def read(run):
    return sum(g[3] for g in run.window_grads) / run.window_steps
