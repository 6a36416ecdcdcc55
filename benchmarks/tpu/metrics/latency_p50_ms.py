"""Median, over every request due in the window, of the time from when it
was due on the open-loop schedule to its future resolving. A request that
failed or never resolved counts with the time it waited until the grace
period ended. Host clock, client side."""

import numpy as np


def read(run):
    if not len(run.latency_s):
        return None
    return float(np.percentile(run.latency_s, 50)) * 1e3
