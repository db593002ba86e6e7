"""Device idle time inside the program's ``serve.admit`` spans, as a
share of the part of the window the device's trace covers: the overlap of
the device's idle intervals with the spans mapped onto the trace's clock
(``bench/spans.py``)."""
from bench import spans


def read(run):
    iv = spans.on_trace(run, "serve.admit")
    return None if iv is None else spans.idle_share(run.trace, iv)
