"""batch_ms (ms): host time per round of the trainer's batch synthesis
(``round_batches``, span ``bench.round_batches``) in the traced window."""


def read(ctx):
    d = ctx.trace.span_seconds("bench.round_batches")
    return 1e3 * sum(d) / len(d) if d else None
