"""The comparison that decides ``correct``.

The reference (``bench/vc_ref.py`` round the cell's model, a module of
``bench/models``) follows the run's first ``check_rounds`` rounds from the
same seeded weights and the same tokens, in float32.  The numbers
compared, each against the cell's limit in ``bench/limits/``:

- ``loss_gap``: the widest gap, in nats, between a round's loss as the
  trainer returns it and the reference's, over the rounds followed;
- ``update_gap``: by the worst leaf, the gap between the norm of the
  server's change since the start, as the checkpoint written in the window
  holds it, and the reference's norm of that change, over the norm of the
  reference's change of that leaf or of the median leaf, whichever is
  larger.  Leaves whose first gradient in the reference is under a
  thousandth of the median leaf's are left out (none is, in the dense
  models here);
- ``ckpt_bits``: leaves of the last checkpoint that differ, in any bit,
  from the server the trainer returned (limit 0);
- ``tokens_bad``: tokens of the followed rounds' batches, as the trainer
  produced them, that differ from the tokens the traffic generator draws
  for the seed (limit 0).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import traffic as TR
from bench import vc_ref as V

EXCLUDE_BELOW = 1e-3


@dataclass
class Readout:
    """What a run (or a stand-in for the program) produced in the rounds
    that the reference follows."""
    losses: List[float]
    norms: Dict[int, List[float]]        # step -> per-leaf |S_k - S_0|
    grad_norms: Optional[List[float]] = None
    steps: List[List[float]] = field(default_factory=list)  # per island
    tokens_bad: int = 0


def leaf_norms(a, b=None) -> List[float]:
    if b is None:
        return [float(jnp.linalg.norm(x.ravel())) for x in
                jax.tree.leaves(a)]
    return [float(jnp.linalg.norm((x - y).ravel())) for x, y in
            zip(jax.tree.leaves(a), jax.tree.leaves(b))]


def follow(model, dm, t: TR.Traffic, seed: int, weights0, *, cdt=None,
           half_batch: bool = False, alter_token: bool = False,
           no_exchange: bool = False, devices: Sequence = ()) -> Readout:
    """The reference's (or a planted fault's) first ``t.check_rounds``
    rounds from ``weights0``, with the loss of ``model`` (the cell's
    module of ``bench/models``) at sizes ``dm``.  Island p runs on
    ``devices[p % len]``."""
    ref = V.Reference(model, dm, t.lr, cdt=cdt,
                      rows=t.batch // 2 if half_batch else None)
    devices = list(devices) or [V._device_of(weights0)]
    dev = [devices[p % len(devices)] for p in range(t.islands)]
    server = weights0
    opts = [V.adam_init(jax.device_put(server, d)) for d in dev]
    out = Readout(losses=[], norms={})
    for i in range(t.check_rounds):
        rnd = t.first_round + i
        alpha = (rnd + 1) / (rnd + 2)
        alive = [not (t.preempt_first_round and i == 0 and j == 0)
                 for j in range(t.islands)]
        isl = [jax.device_put(server, d) for d in dev]
        losses = [[] for _ in dev]
        for s in range(t.local_steps):
            for p in range(t.islands):
                toks = TR.tokens(t, dm.vocab, seed, rnd, p, s)
                if alter_token:
                    toks = altered(toks, dm.vocab)
                isl[p], opts[p], loss, g = ref.step(isl[p], opts[p], toks)
                losses[p].append(loss)
                if out.grad_norms is None:
                    out.grad_norms = leaf_norms(g)
                del g
        steps = [[float(x) for x in ls] for ls in losses]
        out.steps.extend(steps)
        out.losses.append(float(np.mean(steps)))
        w, w_s = V.eq2_weights(t.islands, alpha, alive)
        if no_exchange:
            server = _eq2_no_exchange(server, isl, w, w_s)
        else:
            server = V.eq2(server, isl, w, w_s)
        del isl
        if (rnd + 1) % t.ckpt_every == 0:
            out.norms[rnd + 1] = leaf_norms(server, weights0)
    return out


def altered(toks: np.ndarray, vocab: int) -> np.ndarray:
    """A planted fault: one token of every sequence changed where the
    batch is produced."""
    toks = toks.copy()
    mid = toks.shape[1] // 2
    toks[:, mid] = (toks[:, mid] + 1) % vocab
    return toks


def _eq2_no_exchange(server, islands, w, w_s):
    """A planted fault for the sharded bus: each island's share of the
    leaves merges only that island, as if the chips never exchanged their
    segments."""
    leaves, tdef = jax.tree.flatten(server)
    isl = [jax.tree.leaves(jax.device_put(x, V._device_of(server)))
           for x in islands]
    n = len(islands)
    out = []
    for k, s in enumerate(leaves):
        j = min(k * n // len(leaves), n - 1)
        out.append((w_s + sum(w) - w[j]) * s + w[j] * isl[j][k])
    return jax.tree.unflatten(tdef, out)


def tokens_bad(t: TR.Traffic, vocab: int, seed: int, batches) -> int:
    """Tokens of the first ``t.check_rounds`` rounds' batches (each
    ``{"tokens": [islands, local_steps, batch, seq]}``) that differ from
    the generator's; a round that is missing counts all its tokens."""
    bad = 0
    for i in range(t.check_rounds):
        want = np.stack([np.stack([TR.tokens(t, vocab, seed,
                                             t.first_round + i, p, s)
                                   for s in range(t.local_steps)])
                         for p in range(t.islands)])
        if i >= len(batches):
            bad += want.size
            continue
        got = np.asarray(batches[i]["tokens"])
        bad += want.size if got.shape != want.shape else int(
            np.sum(got != want))
    return bad


def readings(program: Readout, ref: Readout, ckpt_bits: int = 0
             ) -> Dict[str, float]:
    """The numbers compared: program (or a stand-in) against reference."""
    n = min(len(program.losses), len(ref.losses))
    gaps = [abs(program.losses[i] - ref.losses[i]) for i in range(n)]
    loss_gap = max(gaps) if gaps and len(program.losses) >= len(ref.losses) \
        else float("inf")
    if not all(np.isfinite(program.losses[:n])):
        loss_gap = float("inf")
    g = np.asarray(ref.grad_norms)
    keep = g >= EXCLUDE_BELOW * np.median(g)
    update_gap = 0.0
    for step, rn in ref.norms.items():
        pn = program.norms.get(step)
        if pn is None or len(pn) != len(rn):
            return {"loss_gap": loss_gap, "update_gap": float("inf"),
                    "ckpt_bits": ckpt_bits,
                    "tokens_bad": program.tokens_bad}
        rn, pn = np.asarray(rn)[keep], np.asarray(pn)[keep]
        denom = np.maximum(rn, np.median(rn))
        gap = np.abs(pn - rn) / denom
        update_gap = max(update_gap, float(np.max(gap)) if
                         np.all(np.isfinite(gap)) else float("inf"))
    return {"loss_gap": loss_gap, "update_gap": update_gap,
            "ckpt_bits": ckpt_bits, "tokens_bad": program.tokens_bad}


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    return all(k in numbers and np.isfinite(numbers[k])
               and numbers[k] <= limits[k] for k in limits)
