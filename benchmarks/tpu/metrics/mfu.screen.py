"""Whole step: algorithmic FLOPs of the graphs completed in the window,
over the window's seconds times the chips times the chip's peak FLOP/s."""


def read(run):
    if not run.completed:
        return None
    flops = run.flops(run.completed_nodes, run.completed_edges)
    return 100.0 * flops / (run.seconds * run.chips
                            * run.peak["flops_per_s"])
