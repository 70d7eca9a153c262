"""The one traffic generator: a mix file plus a configuration and a seed
give the sequence of queries a run sends.

A mix (``bench/traffic/<name>.json``) states its parameters only:

``clients``        closed-loop callers, each waiting for its reply
``objectives``     per budget pair of the configuration (its ``budgets``),
                   how many queries of each objective one block holds;
                   every block holds the same multiset, so each seed
                   sends the same work in another order
``check``          ``fraction`` of answers (by position in the sequence,
                   drawn from the seed) kept for the comparison, at most
                   ``max``

Every query prices the configuration's own network at its own batch and
sequence length (``bench/harness.py`` ``workload_of``).
"""
from __future__ import annotations

import json
import random
from dataclasses import dataclass
from pathlib import Path
from typing import Iterator, List, Tuple

ROOT = Path(__file__).resolve().parent.parent


@dataclass(frozen=True)
class Query:
    index: int            # position in the run's sequence
    size_budget_kb: int
    bw_budget: int
    objective: str


def load_mix(name: str, root: Path = ROOT) -> dict:
    path = root / "bench" / "traffic" / f"{name}.json"
    return json.loads(path.read_text())


def block_multiset(mix: dict, cfg: dict) -> List[Tuple[int, int, str]]:
    """The (size budget, bw budget, objective) multiset of one block."""
    return [(kb, bw, obj)
            for kb, bw in (tuple(p) for p in cfg["budgets"])
            for obj, n in mix["objectives"].items()
            for _ in range(int(n))]


def queries(mix: dict, cfg: dict, seed: int) -> Iterator[Query]:
    """The run's endless query sequence: block after block, each in an
    order drawn from the seed."""
    rng = random.Random(f"bench-traffic|{seed}")
    base = block_multiset(mix, cfg)
    i = 0
    while True:
        blk = list(base)
        rng.shuffle(blk)
        for kb, bw, obj in blk:
            yield Query(i, kb, bw, obj)
            i += 1


def checked_positions(mix: dict, seed: int, n: int) -> List[int]:
    """Positions (among the first ``n``) whose answers the comparison
    keeps, drawn from the seed."""
    rng = random.Random(f"bench-check|{seed}")
    frac = float(mix["check"]["fraction"])
    picked = [i for i in range(n) if rng.random() < frac]
    return picked[:int(mix["check"]["max"])]


def warmup(mix: dict, cfg: dict) -> List[Tuple[int, int, str, int]]:
    """(size budget, bw budget, objective, group size) bursts of set-up:
    every query of a block, in every group size its clients can make
    (requests of one budget pair and objective that meet in one
    micro-batch are priced as one group)."""
    most = min(int(mix["clients"]), int(cfg["service"]["max_batch"]))
    return [(kb, bw, obj, n)
            for kb, bw, obj in dict.fromkeys(block_multiset(mix, cfg))
            for n in range(1, most + 1)]
