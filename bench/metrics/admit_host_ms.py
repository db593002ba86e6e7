"""Host time of an admission: the mean over the window's ``serve.admit``
spans of the span less its ``serve.admit.wait`` child (the first-token
fetch), from the program's timed spans (``bench/spans.py``)."""
from bench import spans


def read(run):
    return spans.host_ms(run, "serve.admit", "serve.admit.wait")
