"""Host time of a decode tick: the mean over the window's ``serve.tick``
spans of the span less its ``serve.tick.wait`` child (the wait for the
device), from the program's timed spans (``bench/spans.py``)."""
from bench import spans


def read(run):
    return spans.host_ms(run, "serve.tick", "serve.tick.wait")
