"""The comparison that decides ``correct``.

Every kept answer of a run is compared with the plain reference's answer
to the same query (``bench/reference``).  The answers are exact: cycle
counts are integers and every float of the energy model is the same
float64 expression, so an answer either equals the reference's or it
does not.

The number compared is ``answers_wrong``: the kept answers in which the
best candidate, the worst candidate, the within-15% frontier (a list in
grid order), the (cycles, energy) Pareto set or the number of candidates
differs from the reference's.  Its limit is 0.  The same count per
field goes to the log only.
"""
from __future__ import annotations

import importlib
from typing import Dict, List, Tuple

LIMITS = {"answers_wrong": 0}
FIELDS = ("best", "worst", "points", "pareto", "n_candidates")


def as_tuple(p) -> tuple:
    """A program ``DSEPoint`` as the reference's plain tuple."""
    return (tuple(p.sizes_kb), tuple(p.bws), int(p.cycles))


def reference(cfg: dict, precision: str = "exact"):
    """The configuration's plain reference (``bench/reference/<name>.py``)."""
    mod = importlib.import_module(f"bench.reference.{cfg['reference']}")
    return mod.Reference(cfg, precision=precision)


def compare(kept: List[Tuple[tuple, dict]], ref
            ) -> Tuple[Dict[str, int], Dict[str, int]]:
    """(the numbers compared, the answers wrong per field).  ``kept``
    holds (query key, answer) pairs, the key being (size budget, bw
    budget, objective, batch) and the answer a dict of ``FIELDS``, with
    points as plain tuples.  Each distinct key is priced once."""
    fields = dict.fromkeys(FIELDS, 0)
    answers = 0
    want: Dict[tuple, object] = {}
    for key, got in kept:
        if key not in want:
            want[key] = ref.answer(*key)
        wrong = [f for f in FIELDS if got[f] != getattr(want[key], f)]
        for f in wrong:
            fields[f] += 1
        answers += bool(wrong)
    return {"answers_wrong": answers}, fields


def within_limits(numbers: Dict[str, int]) -> bool:
    return all(numbers[k] <= lim for k, lim in LIMITS.items())
