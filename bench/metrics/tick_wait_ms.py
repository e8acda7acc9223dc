"""Mean host time per ``ProtectedStore.tick`` that the tick thread spent
blocked on the device or the resolver thread (the library's
``vilamb.wait.*`` spans inside ``vilamb.tick``), in ms."""
from bench.program_trace import has_program_spans, per_tick_ms


def read(ctx, name):
    t = ctx.trace
    if not has_program_spans(t):
        return None
    return per_tick_ms(t, t.nested_s("vilamb.wait", "vilamb.tick"))
