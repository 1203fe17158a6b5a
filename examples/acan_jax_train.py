"""ACAN-over-JAX: the paper's runtime scheduling *real* JAX model training
(reduced deepseek-v2-lite MoE) — microbatch-gradient tasks flow through
the Tuple Space with timeout/re-issue, handlers crash mid-task at 25%
probability, and the §5.4 sliding window commits each param version
exactly once.

    PYTHONPATH=src python examples/acan_jax_train.py [--ts-backend spec]

The coordination substrate is pluggable: pass ``--ts-backend sharded``
(or set ``$REPRO_TS_BACKEND``) to run the gradient-task traffic over the
sharded high-throughput tuple-space backend.
"""

from _example_args import protocol_audit, ts_backend_arg
from repro.configs import get_config
from repro.core import ACANCloud, CloudConfig, FaultPlan
from repro.core.space import ANY
from repro.programs.jax_sgd import JAXSGDProgram

N_HANDLERS, N_MICRO = 4, 4


def main() -> None:
    cfg = get_config("deepseek_v2_lite_16b", reduced=True)
    prog = JAXSGDProgram(cfg, steps=8, n_micro=N_MICRO, micro_batch=2,
                         seq=32, lr=0.05, handler_crash_prob=0.25, seed=0)
    # handler_batch=1: gradient tasks are heavy, so micro-batches spread
    # across handlers instead of draining into one batch. The crashes
    # are the program's own; the fault plane's interval faults are off.
    cloud = ACANCloud(CloudConfig(
        n_handlers=N_HANDLERS, handler_batch=1, ts_backend=ts_backend_arg(),
        initial_timeout=30.0, fault_plan=FaultPlan(interval=1e9)),
        program=prog)
    print(f"arch: {cfg.name} (reduced, MoE {cfg.period[0].moe.n_experts}e "
          f"top-{cfg.period[0].moe.top_k}); {N_HANDLERS} handlers, "
          f"{N_MICRO} grad tasks/step, 25% crash prob/task, "
          f"ts backend {type(cloud.ts.backend).__name__}\n")
    res = cloud.run()
    losses = [l for _, l in sorted(res.loss_history)]
    for i, l in enumerate(losses):
        print(f"step {i}: loss {l:.4f}")
    (_, versions), _ = cloud.ts.try_read(("params", ANY))
    print(f"\ncrashes: {prog.crashes}  re-issues: {res.reissues}  "
          f"param versions committed: {versions}")
    assert losses[-1] < losses[0]
    print("loss decreased through crashes — ACAN semantics hold for real "
          "JAX training.")
    protocol_audit(cloud.ts.backend, res)


if __name__ == "__main__":
    main()
