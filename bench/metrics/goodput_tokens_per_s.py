"""goodput_tokens_per_s: as tokens_per_s, in a cell under kills: tokens
of the steps committed in the window over the window's length."""


def read(run):
    return run.window_tokens / run.window_s
