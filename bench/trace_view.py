"""Reduction of a profiler trace (``.xplane.pb``) to what the readers need.

One traced run gives three kinds of events, all on the profiler's clock:

* the benchmark's own spans: ``jax.profiler.TraceAnnotation`` events named
  ``bench.*`` on the host (``bench.window`` spans the measured window);
* device operations: the ``XLA Ops`` line of each ``/device:<kind>:<i>``
  plane (busy time is the union of their intervals), and the ``XLA
  Modules`` line (one event per program run, carrying its ``run_id``);
* program launches: the host's ``DoEnqueueProgram`` events, whose
  ``run_id`` ties a program run on the device to the moment it was
  enqueued, and so to the benchmark span that launched it (the span that
  most recently opened before the enqueue).

Times are kept in nanoseconds and reported in seconds.
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

WINDOW = "bench.window"
LAUNCH = "DoEnqueueProgram"
_DEVICE = re.compile(r"^/device:([A-Z]+):(\d+)$")


def union(iv: np.ndarray) -> np.ndarray:
    """Disjoint sorted union of ``[start, end)`` rows."""
    iv = np.asarray(iv, np.float64).reshape(-1, 2)
    if not len(iv):
        return iv
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    first = np.ones(len(iv), bool)
    first[1:] = iv[1:, 0] > ends[:-1]
    starts = np.flatnonzero(first)
    last = np.append(starts[1:] - 1, len(iv) - 1)
    return np.stack([iv[starts, 0], ends[last]], axis=1)


def measure_upto(b: np.ndarray, t: np.ndarray) -> np.ndarray:
    """Length of the disjoint sorted set ``b`` below each time in ``t``."""
    t = np.asarray(t, np.float64)
    if not len(b):
        return np.zeros_like(t)
    cum = np.concatenate([[0.0], np.cumsum(b[:, 1] - b[:, 0])])
    i = np.searchsorted(b[:, 0], t, side="right")
    j = np.maximum(i - 1, 0)
    part = np.clip(np.minimum(t, b[j, 1]) - b[j, 0], 0.0, None)
    return np.where(i > 0, cum[j] + part, 0.0)


def intersect_len(a: np.ndarray, b: np.ndarray) -> float:
    """Length of the overlap of two disjoint sorted interval sets."""
    if not len(a) or not len(b):
        return 0.0
    return float(np.sum(measure_upto(b, a[:, 1]) - measure_upto(b, a[:, 0])))


def clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    iv = np.clip(iv, lo, hi)
    return iv[iv[:, 1] > iv[:, 0]] if iv.size else iv.reshape(0, 2)


def _stat(event, key: str):
    for k, v in event.stats:
        if k == key:
            return v
    return None


class TraceView:
    """Spans, device operations and launches of one traced window."""

    def __init__(self, spans: Dict[str, np.ndarray],
                 ops: Sequence[Tuple[np.ndarray, List[str]]],
                 modules: Sequence[Tuple[np.ndarray, List[str], np.ndarray]],
                 launches: Dict[int, float]):
        self.spans = spans
        self.ops = list(ops)                 # per device: ([n, 2], names)
        self.modules = list(modules)         # per device: ([n, 2], names, run_id)
        self.launches = launches
        win = spans.get(WINDOW)
        if win is None or len(win) != 1:
            raise ValueError("the trace holds no single bench.window span")
        self.t0, self.t1 = float(win[0, 0]), float(win[0, 1])

    # -- loading -------------------------------------------------------------

    @classmethod
    def from_profile(cls, profile, n_devices: int) -> "TraceView":
        """From a ``jax.profiler.ProfileData`` of the traced window; the
        first ``n_devices`` device planes are the chips the cell used."""
        spans: Dict[str, list] = defaultdict(list)
        launches: Dict[int, float] = {}
        devices = {}
        for plane in profile.planes:
            m = _DEVICE.match(plane.name)
            if m and m.group(1) != "CUSTOM":
                devices[int(m.group(2))] = plane
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        name = e.name
                        if name.startswith("bench."):
                            spans[name].append((e.start_ns, e.end_ns))
                        elif name == LAUNCH:
                            rid = _stat(e, "run_id")
                            if rid is not None:
                                rid = int(rid)
                                launches[rid] = min(launches.get(rid, e.start_ns),
                                                    e.start_ns)
        if len(devices) < n_devices:
            raise ValueError(f"the trace has {len(devices)} device planes, "
                             f"the cell uses {n_devices}")
        ops, modules = [], []
        for d in sorted(devices)[:n_devices]:
            lines = {ln.name: list(ln.events) for ln in devices[d].lines}
            o = lines.get("XLA Ops", [])
            ops.append((np.asarray([(e.start_ns, e.end_ns) for e in o],
                                   np.float64).reshape(-1, 2),
                        [e.name for e in o]))
            mo = lines.get("XLA Modules", [])
            rid = [_stat(e, "run_id") for e in mo]
            modules.append((np.asarray([(e.start_ns, e.end_ns) for e in mo],
                                       np.float64).reshape(-1, 2),
                            [e.name for e in mo],
                            np.asarray([-1 if r is None else int(r)
                                        for r in rid], np.int64)))
        return cls({k: np.asarray(v, np.float64) for k, v in spans.items()},
                   ops, modules, launches)

    @classmethod
    def from_file(cls, path: str, n_devices: int) -> "TraceView":
        from jax.profiler import ProfileData

        return cls.from_profile(ProfileData.from_file(path), n_devices)

    # -- window and busy time -------------------------------------------------

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    def busy(self, device: int) -> np.ndarray:
        """Disjoint intervals in which an operation ran on ``device``."""
        return clip(union(self.ops[device][0]), self.t0, self.t1)

    def busy_any(self) -> np.ndarray:
        """Intervals in which an operation ran on any of the cell's chips."""
        if not self.ops:
            return np.zeros((0, 2))
        return clip(union(np.concatenate([o[0] for o in self.ops])),
                    self.t0, self.t1)

    def busy_s(self) -> float:
        """Busy seconds in the window, averaged over the cell's chips."""
        return float(np.mean([np.sum(b[:, 1] - b[:, 0]) if b.size else 0.0
                              for b in map(self.busy, range(len(self.ops)))])
                     ) * 1e-9

    def span_intervals(self, name: str) -> np.ndarray:
        return clip(union(self.spans.get(name, np.zeros((0, 2)))),
                    self.t0, self.t1)

    def span_durations_s(self, name: str) -> np.ndarray:
        s = self.spans.get(name, np.zeros((0, 2)))
        s = s[(s[:, 0] >= self.t0) & (s[:, 0] < self.t1)]
        return (s[:, 1] - s[:, 0]) * 1e-9

    # -- attribution -----------------------------------------------------------

    def _span_starts(self):
        rows = [(s, name) for name, iv in self.spans.items()
                if name != WINDOW for s in iv[:, 0]]
        rows.sort()
        return np.asarray([r[0] for r in rows]), [r[1] for r in rows]

    def launching_span(self, run_ids: np.ndarray) -> List[Optional[str]]:
        """The benchmark span that enqueued each program run."""
        starts, names = self._span_starts()
        t = np.asarray([self.launches.get(int(r), np.nan) for r in run_ids],
                       np.float64)
        if not len(starts):
            return [None] * len(t)
        i = np.searchsorted(starts, np.nan_to_num(t, nan=-np.inf),
                            side="right") - 1
        return [names[k] if k >= 0 and not np.isnan(x) else None
                for k, x in zip(i, t)]

    def program_seconds(self, span: str) -> float:
        """Device seconds, summed over the cell's chips, of every program
        run in the window that ``span`` launched."""
        total = 0.0
        for iv, _, rid in self.modules:
            inside = (iv[:, 0] >= self.t0) & (iv[:, 0] < self.t1)
            owners = self.launching_span(rid[inside])
            dur = (iv[inside, 1] - iv[inside, 0])
            total += float(sum(d for d, o in zip(dur, owners) if o == span))
        return total * 1e-9

    # -- breakdown --------------------------------------------------------------

    def top_device_ops(self, k: int = 10) -> List[List]:
        """Device time by program and operation, averaged over the chips."""
        acc: Dict[str, float] = defaultdict(float)
        for (iv, names), (miv, mnames, _) in zip(self.ops, self.modules):
            order = np.argsort(miv[:, 0]) if len(miv) else np.zeros(0, int)
            mstart = miv[order, 0] if len(miv) else np.zeros(0)
            sel = clip(iv, self.t0, self.t1)
            keep = (iv[:, 1] > self.t0) & (iv[:, 0] < self.t1)
            for (s, e), name in zip(sel, np.asarray(names, object)[keep]):
                i = int(np.searchsorted(mstart, s, side="right")) - 1
                prog = (mnames[order[i]].split("(")[0] if i >= 0 else "?")
                op = name.split(" = ")[0].lstrip("%").split(".")[0]
                acc[f"{prog}/{op}"] += (e - s) * 1e-9 / len(self.ops)
        return [[n, v] for n, v in sorted(acc.items(), key=lambda x: -x[1])[:k]]

    def idle_gaps(self, k: int = 10) -> List[List]:
        """The longest stretches of the window with chip 0 idle, each named
        by the innermost benchmark span open at its middle."""
        b = self.busy(0)
        edges = np.concatenate([[self.t0], b.reshape(-1), [self.t1]])
        gaps = edges.reshape(-1, 2)
        gaps = gaps[gaps[:, 1] > gaps[:, 0]]
        gaps = gaps[np.argsort(gaps[:, 0] - gaps[:, 1], kind="stable")][:k]
        named = []
        for s, e in gaps:
            mid = 0.5 * (s + e)
            best, best_start = "bench.loop", -np.inf
            for name, iv in self.spans.items():
                if name == WINDOW or not len(iv):
                    continue
                inside = iv[(iv[:, 0] <= mid) & (iv[:, 1] >= mid)]
                if len(inside) and inside[:, 0].max() > best_start:
                    best, best_start = name, inside[:, 0].max()
            named.append([best, (e - s) * 1e-9])
        return named
