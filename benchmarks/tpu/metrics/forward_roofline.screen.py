"""Forward program: least time for the algorithmic work of the graphs
completed in the traced window, at the chip's peaks, over the device time
of the programs launched in it (every module on the TPU planes: the
engine launches no other compute program in the window). The least time
is the larger of FLOPs over peak FLOP/s and bytes over HBM bandwidth;
bytes are the graphs' own plus the weights once per launch."""

import sys


def read(run):
    t = run.trace
    if t is None or run.trace_nodes is None or not len(run.trace_nodes):
        return None
    device_s = sum(t.module_s.values())
    if device_s <= 0:
        return None
    pk = run.peak
    flops = run.flops(run.trace_nodes, run.trace_edges)
    nbytes = (run.bytes_moved(run.trace_nodes, run.trace_edges)
              + sum(t.launches.values())
              * run.cell.counts.weight_bytes(run.cell.config))
    t_flops = flops / pk["flops_per_s"]
    t_bytes = nbytes / pk["hbm_bytes_per_s"]
    bound = "memory" if t_bytes >= t_flops else "compute"
    print(f"forward_roofline: {bound}-bound, {flops!r} FLOP, {nbytes!r} B, "
          f"{device_s!r} s on the device", file=sys.stderr)
    return 100.0 * max(t_flops, t_bytes) / device_s
