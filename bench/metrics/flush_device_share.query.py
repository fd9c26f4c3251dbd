"""Share of the time inside ``bench.flush`` spans in which an operation runs
on the device: the descent's device work against its host work (grid
building, pruning, copies back)."""
from bench.trace_view import intersect_len


def read(run):
    t = run.trace
    if t is None:
        return None
    spans = t.span_intervals("bench.flush")
    total = float((spans[:, 1] - spans[:, 0]).sum()) if len(spans) else 0.0
    if total <= 0.0:
        return None
    return 100.0 * intersect_len(spans, t.busy_any()) / total
