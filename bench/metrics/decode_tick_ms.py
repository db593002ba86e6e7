"""Mean decode tick, from the program's ``serve_decode_tick_seconds``
histogram: its sum over its count, both taken over the window's steps."""
from bench import layer


def read(run):
    steps = [run.rec.steps[k] for k in layer.window_steps(run)]
    n = sum(s.tick_count for s in steps)
    return 1e3 * sum(s.tick_sum for s in steps) / n if n else None
