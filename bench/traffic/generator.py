"""The one traffic generator: a mix file's parameters -> a seeded schedule.

A mix (``bench/traffic/<mix>.json``) says how rows and requests arrive:

``ingest``
    ``{"mode": "backlog"}`` -- the next block is offered as soon as the
    previous ``ingest`` returns (a collector catching up on a backlog); or
    ``{"mode": "rate", "rows_per_s": r}`` -- block ``j`` is due at
    ``j * block_rows / r`` seconds, open loop.
``requests``
    ``{"mode": "periodic", "period_s": p, "batch": [req, ...]}`` -- the
    whole batch is due every ``p`` seconds (a dashboard refresh); or
    ``{"mode": "poisson", "rate_per_s": l, "mix": [req + "share", ...]}``
    -- open-loop arrivals, ``round(l * seconds)`` of them in the window.
    The gaps between them are the same for every seed: the quantiles of an
    exponential distribution at ``(i + 1/2) / (n + 1)``, scaled to fill the
    window; the seed shuffles their order.  Or
    ``{"mode": "spaced", ...}`` with the same keys -- arrivals evenly
    spaced, ``seconds / (n + 1)`` apart (a fleet of clients polling on
    staggered fixed periods).  Either way the kinds are dealt in exact
    shares and interleaved in the mix's order, each kind every
    ``1 / share`` requests (rounds of one each, for equal shares); the
    seed picks the kind that leads.  Every seed carries the same work,
    spread alike over the window, in another order.
``max_flush_requests``
    the most requests the server loop hands one ``flush``.

A request is ``{"kind": "topk", "k": k}`` or
``{"kind": "heavy_hitters", "phi": phi}`` (threshold ``ceil(phi * mass)``
of the stream mass acknowledged when it is submitted).
"""
from __future__ import annotations

import dataclasses
import math
from typing import List, Optional, Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class Request:
    due: float                 # seconds after the window opens
    kind: str                  # "topk" | "heavy_hitters"
    k: int = 0
    phi: float = 0.0

    def template(self) -> Tuple[str, int, float]:
        return (self.kind, self.k, self.phi)


@dataclasses.dataclass(frozen=True)
class Schedule:
    rows_per_s: Optional[float]        # None: backlogged
    block_rows: int
    requests: Tuple[Request, ...]      # sorted by due time
    max_flush: int

    def block_due(self, j: int) -> float:
        """When block ``j`` of the window is offered (0 when backlogged)."""
        if self.rows_per_s is None:
            return 0.0
        return j * self.block_rows / self.rows_per_s

    def templates(self) -> List[Tuple[str, int, float]]:
        return sorted({r.template() for r in self.requests})


def _request(spec: dict, due: float) -> Request:
    kind = spec["kind"]
    if kind == "topk":
        return Request(due=due, kind=kind, k=int(spec["k"]))
    if kind == "heavy_hitters":
        return Request(due=due, kind=kind, phi=float(spec["phi"]))
    raise ValueError(f"unknown request kind {kind!r}")


def _exact_shares(shares: List[float], n: int) -> List[int]:
    """Largest-remainder split of ``n`` by ``shares``."""
    total = sum(shares)
    raw = [s * n / total for s in shares]
    counts = [math.floor(x) for x in raw]
    order = sorted(range(len(raw)), key=lambda i: counts[i] - raw[i])
    for i in order[: n - sum(counts)]:
        counts[i] += 1
    return counts


def _deal(shares: List[float], n: int,
          rng: np.random.Generator) -> np.ndarray:
    """``n`` kinds in exact shares, interleaved in the mix's order from a
    seeded first kind on: kind ``k``'s ``j``-th request sits at
    ``(j + offset_k) / share_k``."""
    counts = _exact_shares(shares, n)
    total, lead = sum(shares), int(rng.integers(len(shares)))
    keys = np.concatenate([
        (np.arange(c) + ((k - lead) % len(shares) + 0.5) / len(shares))
        * total / s for k, (c, s) in enumerate(zip(counts, shares))])
    kinds = np.repeat(np.arange(len(counts)), counts)
    return kinds[np.argsort(keys, kind="stable")]


def make_schedule(traffic: dict, rng: np.random.Generator, seconds: float,
                  block_rows: int) -> Schedule:
    ing = traffic["ingest"]
    if ing["mode"] == "backlog":
        rate = None
    elif ing["mode"] == "rate":
        rate = float(ing["rows_per_s"])
    else:
        raise ValueError(f"unknown ingest mode {ing['mode']!r}")
    req = traffic["requests"]
    reqs: List[Request] = []
    if req["mode"] == "periodic":
        period = float(req["period_s"])
        for i in range(1, int(seconds / period) + 1):
            due = i * period
            if due < seconds:
                reqs.extend(_request(r, due) for r in req["batch"])
    elif req["mode"] in ("poisson", "spaced"):
        n = int(round(float(req["rate_per_s"]) * seconds))
        if req["mode"] == "poisson":
            gaps = -np.log1p(-(np.arange(n + 1) + 0.5) / (n + 1))
            rng.shuffle(gaps)
        else:
            gaps = np.ones(n + 1)
        dues = np.cumsum(gaps)[:n] * (seconds / gaps.sum())
        kinds = _deal([float(m["share"]) for m in req["mix"]], n, rng)
        reqs = [_request(req["mix"][int(k)], float(d))
                for k, d in zip(kinds, dues)]
    else:
        raise ValueError(f"unknown request mode {req['mode']!r}")
    return Schedule(rows_per_s=rate, block_rows=int(block_rows),
                    requests=tuple(reqs),
                    max_flush=int(traffic["max_flush_requests"]))
