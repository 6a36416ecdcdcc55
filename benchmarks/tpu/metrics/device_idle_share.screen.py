"""Device: share of the traced window in which no operation ran, averaged
over the cell's chips (1 - union of op intervals / window)."""


def read(run):
    t = run.trace
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.mean_busy_s / t.window_s)
