"""Share of the traced window in which an all-reduce runs on the device,
averaged over the cell's chips: the psum sync's collective time.

An all-reduce is a device op whose name holds ``all-reduce`` as its HLO
opcode (``%psum.7 = s32[...] all-reduce(...)``: JAX names the op after
its primitive) or, in a name without the instruction text, as the op's own
name; an operand reference such as ``%all-reduce-done.3`` does not count.
An asynchronous ``all-reduce-start`` and the ``all-reduce-done`` that
follows it on the same chip count as one interval, from the start's
beginning to the done's end.
"""
import re

import numpy as np

from bench.trace_view import clip, union

_OPCODE = re.compile(r"(?:^|\s)(all-reduce(?:-start|-done)?)\(")


def op_kind(name: str):
    """``all-reduce``, ``all-reduce-start``, ``all-reduce-done`` or None."""
    lhs, _, rhs = name.partition(" = ")
    m = _OPCODE.search(rhs)
    if m:
        return m.group(1)
    op = lhs.lstrip("%").split(".")[0]
    return op if op in ("all-reduce", "all-reduce-start",
                        "all-reduce-done") else None


def all_reduce_intervals(iv: np.ndarray, names) -> np.ndarray:
    """``[n, 2]`` intervals of one chip's all-reduce ops, start/done pairs
    joined, in time order (not yet merged)."""
    out, open_start = [], None
    for i in np.argsort(iv[:, 0], kind="stable"):
        op = op_kind(names[i])
        if op is None:
            continue
        if op == "all-reduce-start":
            open_start = iv[i, 0]
        elif op == "all-reduce-done":
            out.append((iv[i, 0] if open_start is None else open_start,
                        iv[i, 1]))
            open_start = None
        else:
            out.append((iv[i, 0], iv[i, 1]))
    return np.asarray(out, np.float64).reshape(-1, 2)


def all_reduce_seconds(t) -> float:
    """All-reduce time in the window, averaged over the chips."""
    per_chip = []
    for iv, names in t.ops:
        u = clip(union(all_reduce_intervals(iv, names)), t.t0, t.t1)
        per_chip.append(float((u[:, 1] - u[:, 0]).sum()) if len(u) else 0.0)
    return float(np.mean(per_chip)) * 1e-9 if per_chip else 0.0


def read(run):
    t = run.trace
    if t is None:
        return None
    return 100.0 * all_reduce_seconds(t) / t.window_s
