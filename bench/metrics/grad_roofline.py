"""grad_roofline: the least time of one gradient call (the larger of its
model FLOPs over the bf16 peak and its least bytes over HBM bandwidth;
the FLOPs bound it at these shapes) over the mean device time of one
execution of the gradient program (module jit_loss_fn) in the trace,
in %."""

from counts import grad_call_bytes, grad_call_flops, least_time
from peaks import peak

MODULE = "jit_loss_fn"


def read(run):
    times = (run.trace or {}).get("module_s", {}).get(MODULE)
    if not times:
        return None
    t = run.cell.traffic
    least, _ = least_time(grad_call_flops(run.config, t["micro_batch"], t["seq"]),
                          grad_call_bytes(run.config, t["micro_batch"], t["seq"]),
                          peak(run.device_kind))
    return 100 * least / (sum(times) / len(times))
