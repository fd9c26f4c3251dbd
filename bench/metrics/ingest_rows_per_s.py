"""Flow records folded per second over the whole window: every row offered
in the window over the time from its open until the device has finished
them (drained)."""


def read(run):
    w = run.window
    return w.rows / w.elapsed_s if w.rows else None
