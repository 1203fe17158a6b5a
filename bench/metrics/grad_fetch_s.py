"""grad_fetch_s: median length of one gradient tree's copy from the device
to the host in a handler's op (``acan.jax_sgd.grad.fetch`` spans of the
traced part's steps; the gradient is ready when it starts)."""

from spans import named, program_spans


def read(run):
    return run.median([(s.end_ns - s.start_ns) * 1e-9 for s in
                       named(program_spans(), "acan.jax_sgd.grad.fetch")
                       if run.in_window(s.ids["step"])])
