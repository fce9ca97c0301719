"""The general traffic generator: one traffic mix = one JSON file of
parameters under ``bench/workloads/``.

A VC round is: every island runs ``local_steps`` Adam steps, each on
``batch`` x ``seq`` tokens; the server merges the islands by Eq. 2 with
the paper's ``var`` schedule and checkpoints every ``ckpt_every`` rounds.
The trainer draws its own tokens from the run's seed, so the mix fixes the
sizes and the learning rate, and this module regenerates the same tokens
for the reference (``tokens``).  The window holds a fixed number of rounds, drawn from the
seconds asked for (``window_rounds``), so that every seed does the same
work and saves the same number of checkpoints.
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass, fields
from pathlib import Path
from typing import Dict, Union

import numpy as np

# a constant rate, or a linear warm-up to ``peak`` over ``warmup_steps``
# Adam steps followed by a cosine decay to ``min_frac`` of it at
# ``total_steps`` (the schedule the trainer's users run)
LR = Union[float, Dict[str, float]]
SCHEDULE_KEYS = {"peak", "warmup_steps", "total_steps", "min_frac"}


@dataclass(frozen=True)
class Traffic:
    name: str
    islands: int            # client islands, one per chip
    local_steps: int        # Adam steps per island per round
    batch: int              # sequences per local step
    seq: int                # tokens per sequence
    lr: LR                  # Adam learning rate: a rate or a schedule
    ckpt_every: int         # rounds between server checkpoints
    first_round: int        # round the run resumes at (set-up round)
    preempt_first_round: bool  # island 0 masked out of the set-up round
    rounds_per_10s: float   # window rounds per 10 s asked for
    check_rounds: int       # rounds the reference follows

    @property
    def tokens_per_round(self) -> int:
        return self.islands * self.local_steps * self.batch * self.seq

    def window_rounds(self, seconds: float) -> int:
        """Rounds in the window: a whole number of checkpoint periods, at
        least one, and at least ``check_rounds - 1`` after set-up."""
        n = math.ceil(seconds * self.rounds_per_10s / 10.0
                      / self.ckpt_every) * self.ckpt_every
        return max(n, self.ckpt_every, self.check_rounds - 1)


def load(path: Path) -> Traffic:
    """The mix in ``path``; its name is the file's stem."""
    raw = json.loads(Path(path).read_text())
    keys = [f.name for f in fields(Traffic) if f.name != "name"]
    missing = [k for k in keys if k not in raw]
    if missing:
        raise ValueError(f"{path}: missing {missing}")
    t = Traffic(name=Path(path).stem, **{k: raw[k] for k in keys})
    if (t.first_round + 1) % t.ckpt_every:
        raise ValueError(f"{path}: the set-up round must end on a "
                         f"checkpoint: (first_round + 1) % ckpt_every")
    if isinstance(t.lr, dict) and (set(t.lr) != SCHEDULE_KEYS
                                   or t.lr["warmup_steps"] < 1):
        raise ValueError(f"{path}: lr is a rate or a schedule with keys "
                         f"{sorted(SCHEDULE_KEYS)} and warmup_steps >= 1")
    if t.first_round < 1:
        raise ValueError(f"{path}: the run resumes from a checkpoint at "
                         f"first_round >= 1")
    return t


class _Source:
    """Zipfian unigrams mixed with an order-1 token chain: the synthetic
    corpus the trainer draws from (same arithmetic, same seeds)."""

    def __init__(self, vocab: int, seed: int, order_dim: int = 64):
        self.vocab, self.seed = vocab, seed
        rng = np.random.default_rng(seed)
        self._mix = rng.integers(1, vocab, size=(order_dim,))
        p = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** 1.1
        self._probs = p / p.sum()

    def sample(self, n_seqs: int, seq_len: int) -> np.ndarray:
        rng = np.random.default_rng(self.seed * 1_000_003)
        base = rng.choice(self.vocab, size=(n_seqs, seq_len), p=self._probs)
        mix = self._mix[base[:, :-1] % len(self._mix)]
        coin = rng.random((n_seqs, seq_len - 1)) < 0.35
        base[:, 1:] = np.where(coin, (base[:, :-1] + mix) % self.vocab,
                               base[:, 1:])
        return base.astype(np.int32)


def tokens(t: Traffic, vocab: int, seed: int, rnd: int, island: int,
           step: int) -> np.ndarray:
    """The [batch, seq] tokens of one local step, as the trainer draws
    them for ``seed``: round seed ``seed * 7919 + rnd``, then
    ``* 31 + island * 7 + step``."""
    s = (seed * 7919 + rnd) * 31 + island * 7 + step
    return _Source(vocab, s).sample(t.batch, t.seq)
