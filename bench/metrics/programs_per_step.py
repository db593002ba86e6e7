"""Device programs launched per engine step: the ``XLA Modules`` events
in the part of the window the line covers over the program's
``serve.step`` spans that overlap it, mapped onto the trace's clock
(``bench/spans.py``).  A step needs one decode program and one prefill
per admission; the rest is eager work."""
from bench import spans


def read(run):
    iv = spans.on_trace(run, "serve.step")
    if iv is None:
        return None
    w0 = run.trace.window[0]
    per_chip = []
    for p in run.trace.planes:
        line = run.trace.progs.get(p)
        if line is None:
            continue
        end = spans.covered_ns(line, w0)
        steps = int(((iv[:, 0] < end) & (iv[:, 1] > w0)).sum())
        if steps:
            per_chip.append(len(line.start) / steps)
    return sum(per_chip) / len(per_chip) if per_chip else None
