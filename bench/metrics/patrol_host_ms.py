"""Mean host time per tick in the scrub patroller (``vilamb.patrol``),
less the time it waited on the device (its ``vilamb.wait.*`` spans), in
ms."""
from bench.program_trace import has_program_spans, per_tick_ms


def read(ctx, name):
    t = ctx.trace
    if not has_program_spans(t):
        return None
    host = t.span_s("vilamb.patrol") - t.nested_s("vilamb.wait",
                                                  "vilamb.patrol")
    return per_tick_ms(t, host)
