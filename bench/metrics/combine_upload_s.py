"""combine_upload_s: median length of the combine's upload of the params
and the n_micro gradient trees to the device, blocked until done
(``acan.jax_sgd.combine.upload`` spans of the traced part's steps)."""

from spans import named, program_spans


def read(run):
    return run.median([(s.end_ns - s.start_ns) * 1e-9 for s in
                       named(program_spans(), "acan.jax_sgd.combine.upload")
                       if run.in_window(s.ids["step"])])
