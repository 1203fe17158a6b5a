"""tokens_per_s: tokens of the steps committed in the window over the
window's length on the host clock (from the commit that opens it to the
first commit at or after its deadline)."""


def read(run):
    return run.window_tokens / run.window_s
