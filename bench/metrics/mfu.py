"""The whole round step's share of the chip's bf16 peak: 3 x forward
operations per sample x local-SGD samples trained in the traced window,
over the window's length times the peak.  Samples are estimated from the
cohort means the block pull carries (``MetricContext.samples_per_round``);
masked slots the program executes anyway do not count."""


def read(ctx):
    cfg = ctx.cell.config
    fwd = ctx.flops(cfg["model"]).forward_per_sample(cfg)
    samples = ctx.samples_per_round() * ctx.window["rounds"]
    if ctx.trace.window_s <= 0 or samples <= 0:
        return None
    return 100.0 * 3 * fwd * samples / (ctx.trace.window_s
                                         * ctx.peaks["bf16_flops"])
