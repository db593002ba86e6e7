"""Median wait from when a request was due to the start of the step that
admitted it, over the requests due in the window; one still queued when
the window ends counts its wait so far."""
from bench import stats


def read(run):
    rec = run.rec
    waits = [min(rec.steps[r.admit_step].start if r.admit_step is not None
                 else rec.we, rec.we) - r.due
             for r in stats.due_in_window(rec)]
    return 1e3 * stats.percentile(waits, 50) if waits else None
