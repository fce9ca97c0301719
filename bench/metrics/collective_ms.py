"""collective_ms (ms): summed device duration per round, per chip, of the
collective ops (all-to-all, all-gather, all-reduce, reduce-scatter,
collective-permute) in the traced window."""


def read(ctx):
    tr = ctx.trace
    per_dev = tr.op_seconds(lambda o: o.collective)
    if not per_dev or not any(per_dev.values()):
        return None
    return 1e3 * sum(per_dev.values()) / len(per_dev) / ctx.rounds
