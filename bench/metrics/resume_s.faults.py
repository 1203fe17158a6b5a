"""resume_s.faults: in the cell under kills, median time from a firing
that kills the Manager (``acan.fault.fire`` with manager=1, in the traced
part) to the end of the first gradient op (``acan.jax_sgd.grad``) that
starts after the revived Manager's recovery (the first
``acan.manager.recover`` span that starts after the firing) has ended:
the kill, the revivals, the recovery and one gradient of the new
Manager's pouch. A handler may still finish a gradient of the dead
Manager's pouch meanwhile; that one does not count. Firings with no such
gradient in the traced part are left out, and their number is logged on
standard error."""

import sys

from spans import named, program_spans


def read(run):
    spans = program_spans()
    grads = named(spans, "acan.jax_sgd.grad")
    recovers = named(spans, "acan.manager.recover")
    kills = [f for f in named(spans, "acan.fault.fire") if f.ids["manager"]]
    resumes = []
    for f in kills:
        rec = next((r for r in recovers if r.start_ns >= f.start_ns), None)
        first = None if rec is None else next(
            (g for g in grads if g.start_ns >= rec.end_ns), None)
        if first is not None:
            resumes.append((first.end_ns - f.start_ns) * 1e-9)
    if len(resumes) < len(kills):
        print(f"[resume_s.faults] {len(kills) - len(resumes)} of "
              f"{len(kills)} Manager kills had no gradient after the next "
              "recovery in the traced part", file=sys.stderr, flush=True)
    return run.median(resumes)
