"""window_compiles (count): backend compiles and persistent-cache loads in
the traced window: the sum of the ``compiles`` arg over the program's
``vc.round`` spans, each of which counts those during its round."""
from bench import program_trace as PT


def read(ctx):
    tr = PT.of(ctx)
    rounds = tr.named("vc.round") if tr else []
    if not rounds:
        return None
    return float(sum(int(s.args.get("compiles", 0)) for s in rounds))
