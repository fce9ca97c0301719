"""ckpt_write_ms (ms): the checkpoint writer thread's busy time per save:
the mean duration of the program's ``vc.writer`` spans (copy into bytes,
msgpack framing, the file's write and rename, retention; see
``repro/checkpoint/store.py``) that start in the traced window."""
from bench import program_trace as PT


def read(ctx):
    tr = PT.of(ctx)
    win = tr.window() if tr else None
    if win is None:
        return None
    d = [s.dur for s in tr.named(PT.WRITER) if win[0] <= s.start <= win[1]]
    return 1e3 * sum(d) / len(d) if d else None
