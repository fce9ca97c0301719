"""server_dev_ms (ms): device time per round, per chip, of the ops on the
flat parameter bus: the ops whose result or operands hold a dimension of
at least params / chips elements (``params`` of the cell's model: a whole
bus, or one chip's segment of it).  That is flatten's writes into the bus,
Eq. 2, the redistribution, unflatten's reads and, on four chips, the
exchange of bus segments.  The per-leaf reshapes that feed flatten touch
no such dimension and are not counted; the op events name no JAX scope
that would tell them apart from the training step's (see PERF.md)."""


def read(ctx):
    tr = ctx.trace
    floor = ctx.model.params(ctx.dm) // ctx.chips
    per_dev = tr.op_seconds(lambda o: not o.container
                            and o.largest_dim >= floor)
    if not per_dev or not any(per_dev.values()):
        return None
    return 1e3 * sum(per_dev.values()) / len(per_dev) / ctx.rounds
