"""Reference models and how the program is configured for them, found by
the name a configuration file gives under ``"model"``.

A configuration that says ``"model": "<model>"`` is run with two modules
of this package, and a new model joins the benchmark as these two new
files, with no edit to the harness:

``bench/models/<model>.py``, the plain reference.  It imports nothing of
the program and provides:

- ``dims(conf) -> dm``: the sizes, from a configuration file's contents.
  ``dm`` is handed back to the functions below unopened, except
  ``dm.vocab``, the vocabulary the traffic draws tokens from
  (``bench/traffic.py``, ``bench/check.py``).  Used by ``bench/run.py``
  and ``bench/control.py``.
- ``make_init(dm) -> init``: ``init(words)`` makes the seeded float32
  weights on the device in one jitted call, from the two 32-bit words of
  ``bench.vc_ref.seed_words(seed)``, laid out as the program's
  parameters.  ``bench/run.py`` writes them as the server's checkpoint
  and gives them to the reference; ``bench/control.py`` likewise.
- ``nll_sum(params, tokens, dm, cdt=None, scale=1.0)``: the summed
  next-token negative log-likelihood of ``tokens`` [rows, seq], times
  ``scale``.  ``cdt`` is None for float32, or ``"bfloat16"`` or
  ``"float8"`` to round what the program keeps in its compute dtype, and
  its gradients, to that precision (the control of ``bench/check.py``).
  Differentiated and jitted by ``bench.vc_ref.Reference``.
- ``BLOCK_ROWS``: how many rows of a batch ``Reference`` gives
  ``nll_sum`` at a time, summing their losses and gradients.  A model
  whose loss is not a sum over rows sets it to the batch.
- ``flops_per_token(dm, seq)``: model FLOPs of one trained token, forward
  and backward, with no recompute; experts that a token does not reach
  are not counted.  Read by ``bench/metrics/mfu.py``.
- ``params(dm)``: the server's parameters, every leaf, counted from the
  sizes alone.  Read by ``bench/metrics/server_dev_ms.py`` and
  ``bench/metrics/server_roofline.py``.

``bench/models/<model>_program.py``, the program's side:

- ``program_config(conf)``: the ``repro.models.common.ModelConfig`` the
  program's trainer runs for the configuration, refusing what it would
  run differently from the reference.  Used by ``bench/run.py``.
"""
from __future__ import annotations

import importlib
from types import ModuleType
from typing import Tuple

REFERENCE = ("dims", "make_init", "nll_sum", "BLOCK_ROWS",
             "flops_per_token", "params")
PROGRAM = ("program_config",)


def _module(conf: dict, name: str, provides) -> ModuleType:
    try:
        mod = importlib.import_module(f"{__name__}.{name}")
    except ModuleNotFoundError as e:
        if e.name != f"{__name__}.{name}":
            raise
        raise FileNotFoundError(
            f"configuration {conf.get('name')!r} names model "
            f"{conf['model']!r}: there is no bench/models/{name}.py"
        ) from None
    missing = [a for a in provides if not hasattr(mod, a)]
    if missing:
        raise AttributeError(f"bench/models/{name}.py lacks {missing}")
    return mod


def load(conf: dict) -> Tuple[ModuleType, ModuleType]:
    """The reference and the program's side of the model ``conf`` names."""
    name = conf.get("model")
    if not isinstance(name, str) or not name.isidentifier():
        raise ValueError(f"configuration {conf.get('name')!r}: \"model\" "
                         f"must name a module of bench/models, not "
                         f"{name!r}")
    return (_module(conf, name, REFERENCE),
            _module(conf, f"{name}_program", PROGRAM))
