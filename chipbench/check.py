"""The comparison that decides a serving run's ``correct``.

Once the window has closed, a sample of the requests it finished, drawn
from the seed and always holding the longest and one request of every slot
that finished one, is run through the float32 reference: each prompt followed by the tokens the engine served for it.
Two kinds of position are read, and at each the number is the gap by
which the program's token there lies below the reference's best logit:

* each served position, with the token the decode step sampled;
* the last position of each prefill chunk, with the token the chunk's
  program put first there (its ``probe``, the argmax of that position's
  logits): the chunked prefill checked directly, not only through the
  cache the decode steps read.

The run's reading is the widest such gap. Greedy decoding in bfloat16
puts first the reference's best token or one within rounding of it, so
the gap stays small; a wrong token, a stale cache or a lower precision
widens it.

The control reads the same sequences with the reference computed in fp8:
at each of the same positions it takes the token the fp8 reference puts
first, and reads that token's gap in the float32 reference.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

SAMPLE_TOKENS = 384  # served tokens compared per run, at the least
_STREAM_SAMPLE = 4


@dataclass
class Served:
    """One finished request: its prompt, the tokens it was served, the
    prefill chunks' first tokens as (position, token) pairs, and the slot
    it was served in (``None`` where the run did not see it)."""

    prompt: list[int]
    served: list[int]
    prefilled: list[tuple[int, int]] = field(default_factory=list)
    slot: int | None = None


def sample(finished: list[Served], seed: int,
           want: int | None = None) -> list[Served]:
    """The longest finished request (prompt plus served tokens); then, in
    an order drawn from ``seed``, one request from each slot that finished
    one and is not in yet, so that a fault in some of the batch's slots
    cannot hide behind the slot of the longest; then others in that order
    until ``want`` served tokens (``SAMPLE_TOKENS`` unless given) are in
    the sample or every finished request is."""
    want = SAMPLE_TOKENS if want is None else want
    done = [r for r in finished if r.served]
    if not done:
        return []
    longest = max(range(len(done)),
                  key=lambda i: (len(done[i].prompt) + len(done[i].served), -i))
    order = np.random.default_rng([int(seed), _STREAM_SAMPLE]).permutation(
        len(done)).tolist()
    picked = [longest]
    slots = {done[longest].slot}
    for i in order:
        if done[i].slot not in slots:
            picked.append(i)
            slots.add(done[i].slot)
    total = sum(len(done[i].served) for i in picked)
    for i in order:
        if total >= want:
            break
        if i not in picked:
            picked.append(i)
            total += len(done[i].served)
    return [done[i] for i in picked]


def positions(req: Served) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """The sequence the reference reads (prompt then served tokens but the
    last), the rows it reads, and the program's token at each row: the
    prefill chunks' ends, then the rows whose next-token logits predict the
    served tokens."""
    seq = np.asarray(req.prompt + req.served[:-1], np.int32)
    p = len(req.prompt)
    rows = [r for r, _ in req.prefilled] + list(range(p - 1, p - 1 + len(req.served)))
    ids = [t for _, t in req.prefilled] + list(req.served)
    return seq, np.asarray(rows, np.int32), np.asarray(ids, np.int32)


def widest_gap(reference, requests: list[Served], control=None) -> dict:
    """The widest gap over ``requests``: of the served tokens, and, where a
    ``control`` reference is given, of the tokens it puts first."""
    served_gap = control_gap = 0.0
    tokens = prefilled = 0
    for req in requests:
        seq, rows, ids = positions(req)
        xn = reference.hidden(seq, rows)
        _, best_ids = reference.best(xn)
        best = reference.logit_at(xn, best_ids)
        at = reference.logit_at(xn, ids)
        served_gap = max(served_gap, float(np.max(best - at)))
        tokens += len(req.served)
        prefilled += len(req.prefilled)
        if control is not None:
            _, ctl_ids = control.best(control.hidden(seq, rows))
            at = reference.logit_at(xn, ctl_ids)
            control_gap = max(control_gap, float(np.max(best - at)))
    out = {"served_tokens": tokens, "prefill_positions": prefilled,
           "requests": len(requests),
           "slots": len({r.slot for r in requests}),
           "max_logit_gap": served_gap}
    if control is not None:
        out["control_max_logit_gap"] = control_gap
    return out
