"""mfu: model FLOPs of the window's committed tokens (three times the
forward's matrix products plus causal attention or SSD work, no
recomputation) over window length x chips x the bf16 peak of the
device kind, in %."""

from counts import model_flops_per_token
from peaks import peak


def read(run):
    if run.platform != "tpu":
        return None
    flops = model_flops_per_token(run.config, run.seq) * run.window_tokens
    return 100 * flops / (run.window_s * run.chips
                          * peak(run.device_kind)["bf16_flops_per_s"])
