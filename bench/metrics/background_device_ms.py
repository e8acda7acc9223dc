"""Device time per loop step (batch) of the programs the scrub patroller
launched (runs launched inside ``vilamb.patrol``: probes, write samples,
cross-shard xor-folds and adoptions), on the busiest device, in ms."""
from bench.program_trace import busiest, has_program_spans


def read(ctx, name):
    if not has_program_spans(ctx.trace) or not ctx.counters.get("steps"):
        return None
    s = busiest(ctx, "vilamb.patrol")
    return s / ctx.counters["steps"] * 1e3 if s > 0 else None
