"""Share of the time inside ``bench.ingest`` spans (``engine.ingest`` calls)
during which no operation runs on any of the cell's chips: the host-side
ingest work (pool offers, checks, launches) the device waits on."""
from bench.trace_view import intersect_len


def read(run):
    t = run.trace
    if t is None:
        return None
    spans = t.span_intervals("bench.ingest")
    total = float((spans[:, 1] - spans[:, 0]).sum()) if len(spans) else 0.0
    if total <= 0.0:
        return None
    return 100.0 * (1.0 - intersect_len(spans, t.busy_any()) / total)
