"""On-chip benchmark of the serving path (``python bench/run.py``).

Everything here is the yardstick: traffic generation, the weights made
from a seed, the plain float32 reference and the comparison that decides
``correct``, the FLOP and byte counts, the table of chip peaks and the
reduction of a profiler trace to metrics.  From the program it takes only
the system under test (``repro.serve.ServeEngine``), its counters and the
names its kernels carry in a trace.
"""
