"""ckpt_idle_ms (ms): device time the checkpoint writer costs per round:
each chip's idle time in the traced window (the first ``vc.round`` to the
end of ``vc.ckpt.wait``) that lies inside a ``vc.writer`` span, whatever
the main thread was doing then, averaged over the chips and divided by
the window's ``vc.round`` spans."""
from bench import program_trace as PT


def read(ctx):
    tr = PT.of(ctx)
    if tr is None or not tr.ops or tr.window() is None:
        return None
    idle = sum(tr.writer_idle_s(d) for d in tr.ops) / len(tr.ops)
    return 1e3 * idle / len(tr.named("vc.round"))
