"""Device time of the Algorithm-1 update programs per loop step (batch),
on the busiest device, in ms."""
from bench.harness import layer_seconds


def read(ctx, name):
    if ctx.trace is None or not ctx.counters.get("steps"):
        return None
    s = max(layer_seconds(ctx, ["update"]).values(), default=0.0)
    return s / ctx.counters["steps"] * 1e3 if s > 0 else None
