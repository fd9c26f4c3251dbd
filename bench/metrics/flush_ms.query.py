"""Median host duration of the ``bench.flush`` spans (``submit`` + ``flush``
of the requests due) in the traced window, in milliseconds."""
import numpy as np


def read(run):
    t = run.trace
    if t is None:
        return None
    d = t.span_durations_s("bench.flush")
    return float(np.median(d)) * 1e3 if len(d) else None
