"""Share of the traced window in which no operation ran on the device,
averaged over the cell's devices, in %."""


def read(ctx, name):
    t = ctx.trace
    if t is None or not t.devices or t.window_s <= 0:
        return None
    devs = sorted(t.devices)[:ctx.n_devices]
    busy = sum(t.busy_s(d) for d in devs) / len(devs)
    return (1.0 - busy / t.window_s) * 100.0
