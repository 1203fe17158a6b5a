"""grad_task_s: median host-clock length of the window's jaxgrad op
calls: the device step, the gradient's copy to the host, the host tree."""


def read(run):
    return run.median([g[2] for g in run.window_grads])
