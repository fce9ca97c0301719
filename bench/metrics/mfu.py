"""mfu (%): the whole round's model FLOPs over the chips' peak: FLOPs per
trained token (``dense_lm.flops_per_token``, no recompute) x tokens the
islands trained in the traced window / (window x chips x peak bf16)."""
from bench import peaks
from bench.models.dense_lm import flops_per_token


def read(ctx):
    peak = peaks.lookup(ctx.device_kind)["bf16_flops_per_s"]
    f = flops_per_token(ctx.dm, ctx.cell.traffic.seq)
    return 100.0 * f * ctx.tokens / (ctx.window_s * ctx.chips * peak)
