"""combine_s: median host-clock length of the window's committing
JAXSGDProgram.combine calls (it ends in device_get, so it is synchronous)."""


def read(run):
    return run.median([c[2] for c in run.window_combines])
