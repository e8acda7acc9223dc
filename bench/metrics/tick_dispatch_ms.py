"""Mean host time per tick in ``vilamb.tick.dispatch``: the epoch swap,
the batched update launch and the resolver hand-off, in ms."""
from bench.program_trace import has_program_spans, per_tick_ms


def read(ctx, name):
    t = ctx.trace
    if not has_program_spans(t):
        return None
    return per_tick_ms(t, t.span_s("vilamb.tick.dispatch"))
