"""The ingest fold's share of its memory roofline.

Least bytes the fold needs for the rows of the window: each row's key (4
bytes per module) and 4-byte weight, plus a 4-byte read and a 4-byte write
of every touched cell (width x levels per row).  Divided by the device time
of every program the ``bench.ingest`` spans launched (attributed by launch,
not by kernel name, summed over the chips) at the chip's HBM bandwidth.
"""


def fold_bytes(rows: int, n_modules: int, width: int, levels: int) -> int:
    return rows * (4 * n_modules + 4 + 8 * width * levels)


def read(run):
    t = run.trace
    if t is None or not run.window.rows:
        return None
    sk = run.cell.config["sketch"]
    need = fold_bytes(run.window.rows, len(sk["domains"]), int(sk["width"]),
                      len(sk["partition"]))
    secs = t.program_seconds("bench.ingest")
    if secs <= 0.0:
        return None
    return 100.0 * need / (secs * float(run.peaks["hbm_bytes_per_s"]))
