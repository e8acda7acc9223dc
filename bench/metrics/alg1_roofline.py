"""Share of the bandwidth roofline reached by the update passes, in %.

The least time is Algorithm 1's bytes as the program counted them
(``update.alg1_bytes``: per dirty stripe a pass covered, counted on the
device, its P data blocks read and its parity block and P checksums
written; overflowed passes add nothing) over the devices' summed HBM
bandwidth.  It is divided by the device time, on the busiest device, of
the runs launched inside ``vilamb.tick.dispatch`` (epoch swaps and update
passes).
"""
from bench.program_trace import busiest, has_program_spans


def read(ctx, name):
    moved = ctx.counters.get("store.update.alg1_bytes", 0)
    if not has_program_spans(ctx.trace) or moved <= 0:
        return None
    s = busiest(ctx, "vilamb.tick.dispatch")
    if s <= 0:
        return None
    least = moved / (ctx.n_devices * ctx.peaks["hbm_bytes_per_s"])
    return least / s * 100.0
