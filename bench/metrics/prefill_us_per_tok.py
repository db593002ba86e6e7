"""Admission cost per prompt token: the host-clock seconds of each window
step outside its decode tick (the ``dt`` that ``step`` returns), over the
prompt tokens those steps admitted."""
from bench import layer


def read(run):
    steps = [k for k in layer.window_steps(run) if run.rec.steps[k].admitted]
    toks = sum(sum(layer.prefill_lengths(run, k)) for k in steps)
    if not toks:
        return None
    return 1e6 * sum(layer.prefill_seconds(run, k) for k in steps) / toks
