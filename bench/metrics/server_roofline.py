"""server_roofline (%): the least time the chip's HBM needs for Eq. 2 and
the redistribution (``bench.work.server_bytes_per_chip`` at the published
bandwidth) over the time the round's ops outside its loops took
(``server_dev_ms``).  Bound by memory: the merge does 2 FLOPs a byte."""
from bench import peaks, work
from bench.metrics import server_dev_ms


def read(ctx):
    ms = server_dev_ms.read(ctx)
    if not ms:
        return None
    t = ctx.cell.traffic
    bw = peaks.lookup(ctx.device_kind)["hbm_bytes_per_s"]
    need = work.server_bytes_per_chip(ctx.model.params(ctx.dm),
                                      t.islands, ctx.chips) / bw
    return 100.0 * need / (ms / 1e3)
