"""Mean host time of one ``ProtectedStore.tick`` call over the window
(the harness's ``tick`` span), in ms."""


def read(ctx, name):
    d = ctx.spans.durations("tick")
    return sum(d) / len(d) * 1e3 if d else None
