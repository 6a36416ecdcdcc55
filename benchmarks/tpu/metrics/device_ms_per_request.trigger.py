"""Device: busy time in the traced window (union of op intervals, summed
over chips) per request completed in it."""


def read(run):
    t = run.trace
    if t is None or run.trace_nodes is None or not len(run.trace_nodes):
        return None
    return sum(t.busy_s.values()) / len(run.trace_nodes) * 1e3
