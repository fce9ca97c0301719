"""mfu (%): the whole round's model FLOPs over the chips' peak: FLOPs per
trained token (the cell's model's ``flops_per_token``, no recompute) x
tokens the islands trained in the traced window / (window x chips x peak
bf16)."""
from bench import peaks


def read(ctx):
    peak = peaks.lookup(ctx.device_kind)["bf16_flops_per_s"]
    f = ctx.model.flops_per_token(ctx.dm, ctx.cell.traffic.seq)
    return 100.0 * f * ctx.tokens / (ctx.window_s * ctx.chips * peak)
