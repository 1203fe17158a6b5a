"""setup_s: from the start of the process to the commit that opens the
window: JAX start, weights, compiles or cache loads, the cloud's start
and the checked steps."""


def read(run):
    return run.setup_s
