"""ckpt_stall_ms (ms): host time the trainer's loop spends in one
``CheckpointManager.save`` call (joining the previous writer, then
copying the server to the host), averaged over the saves of the traced
window; the span ``bench.ckpt_save`` that the benchmark wraps round it."""


def read(ctx):
    d = ctx.trace.span_seconds("bench.ckpt_save")
    return 1e3 * sum(d) / len(d) if d else None
