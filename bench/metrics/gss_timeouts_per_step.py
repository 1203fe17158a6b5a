"""gss_timeouts_per_step: the Manager's GSS deadlines that fired with
tasks of the pouch still pending (``acan.manager.gss_timeout`` instants)
in the rounds of the traced part's steps, per committed step. Each such
timeout re-issues the pending tasks: the cause of grad_runs_per_step's
duplicates."""

from spans import named, program_spans


def read(run):
    spans = program_spans()
    if not spans:
        return None
    fired = [s for s in named(spans, "acan.manager.gss_timeout")
             if run.in_window(s.ids["rnd"])]
    return len(fired) / run.window_steps
