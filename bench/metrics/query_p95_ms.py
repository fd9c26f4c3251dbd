"""95th percentile of the latencies of all requests due in the window, each
from its due time to its answer, in milliseconds."""
from bench.loop import percentile


def read(run):
    lat = run.window.latencies_s
    return percentile(lat, 95) * 1e3 if lat else None
