"""Placer and executors: graphs served by the busiest chip over the mean
across the cell's chips, over the window's batches
(``StreamStats.by_device``). 1 is an even spread."""


def read(run):
    served = sum(run.graphs_by_device.values())
    if run.chips < 2 or not served:
        return None
    return max(run.graphs_by_device.values()) * run.chips / served
