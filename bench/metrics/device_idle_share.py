"""device_idle_share: 1 - the union of the device's op intervals over the
traced window, in %."""


def read(run):
    if run.trace is None:
        return None
    return 100 * (1 - run.trace["busy_s"] / run.trace["window_s"])
