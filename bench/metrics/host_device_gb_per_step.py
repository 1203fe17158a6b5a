"""host_device_gb_per_step: bytes the program moved between host and
device while the traced part ran (the ``bytes`` of its ``acan.jax_sgd.*``
spans that start in it: param uploads, batches, gradient fetches, the
combine's upload and fetch), per step committed in it, in GB."""

from spans import program_spans


def read(run):
    moved = [s.ids["bytes"] for s in program_spans() if "bytes" in s.ids]
    if not moved:
        return None
    return sum(moved) / run.window_steps / 1e9
