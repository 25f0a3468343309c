"""The CSR aggregation kernel's share of its HBM roofline, in %.

Each call reads w (N, T) and the edge buffers (2E, T) and writes w
(N, T), all float32: (2E + 2N) * T * 4 bytes. The kernel does 2 flops
per buffer element, so bytes bound it. Share = calls x bytes / peak
bandwidth over the summed device time of the kernel's events. Nothing
is read where the trace holds no such event (the mesh runtime does not
call the kernel).
"""

KERNEL = "edge_aggregate"


def read(ctx):
    names = [n for n in ctx.device.op_seconds if KERNEL in n]
    if not names:
        return None
    seconds = sum(ctx.device.op_seconds[n] for n in names)
    calls = sum(ctx.device.op_counts[n] for n in names)
    nbytes = (ctx.edges + 2 * ctx.silos) * ctx.params * 4
    return calls * nbytes / ctx.peaks["hbm_bytes_per_s"] / seconds * 100
