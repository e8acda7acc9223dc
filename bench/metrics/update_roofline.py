"""Share of the bandwidth roofline reached by the update programs, in %.

The least time is Algorithm 1's bytes (each stripe dirtied since the
previous pass: its P data blocks read once, its parity block and P
checksums written once; counted by the harness from the rows it wrote)
over the devices' summed HBM bandwidth.  It is divided by the update
programs' device time on the busiest device.
"""
from bench.harness import layer_seconds


def read(ctx, name):
    if ctx.trace is None:
        return None
    moved = ctx.counters.get("update_bytes", 0)
    s = max(layer_seconds(ctx, ["update"]).values(), default=0.0)
    if moved <= 0 or s <= 0:
        return None
    least = moved / (ctx.n_devices * ctx.peaks["hbm_bytes_per_s"])
    return least / s * 100.0
