"""Model FLOP/s utilization of the whole training step, in %: the model
FLOPs of the window's steps (6 x matmul parameters + 12 x layers x d_model
x seq per token, PaLM appendix B; recomputation not counted) over the
window's seconds and the chips' bf16 peak."""


def read(ctx, name):
    flops = ctx.counters.get("model_flops")
    if not flops or ctx.window_s <= 0:
        return None
    return flops / ctx.window_s / (ctx.n_devices * ctx.peaks["bf16_flops"]) \
        * 100.0
