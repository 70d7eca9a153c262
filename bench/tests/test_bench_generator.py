"""The traffic generator: the same seed gives the same queries, every
seed the same work in another order, and each configuration's mix sends
the query its source names."""
import itertools
import json
import sys
from collections import Counter
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import generator  # noqa: E402

MIXES = sorted(p.stem for p in (ROOT / "bench" / "traffic").glob("*.json"))
CONFIGS = sorted(p.stem for p in (ROOT / "bench" / "configs").glob("*.json"))
BIG = 2 ** 31 + 12345

# a mix and a configuration with several pairs and objectives, so that
# the order a seed draws can differ
WIDE_CFG = {"budgets": [[2048, 2048], [1024, 2048], [4096, 1024]],
            "network": {"batch": 32}, "service": {"max_batch": 16}}
WIDE_MIX = {"clients": 2, "objectives": {"cycles": 2, "energy": 1},
            "check": {"fraction": 0.1, "max": 40}}


def config(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())


def take(mix, cfg, seed, n):
    return list(itertools.islice(generator.queries(mix, cfg, seed), n))


@pytest.mark.parametrize("name", MIXES)
@pytest.mark.parametrize("cfg_name", CONFIGS)
def test_deterministic_per_seed(name, cfg_name):
    mix, cfg = generator.load_mix(name), config(cfg_name)
    assert take(mix, cfg, BIG, 300) == take(mix, cfg, BIG, 300)
    pos = generator.checked_positions(mix, BIG, 1000)
    assert pos == generator.checked_positions(mix, BIG, 1000)
    assert 0 < len(pos) <= mix["check"]["max"]
    assert [q.index for q in take(mix, cfg, BIG, 50)] == list(range(50))


@pytest.mark.parametrize("cfg_name", CONFIGS)
def test_warm_sweep_sends_the_table_viii_query(cfg_name):
    cfg = config(cfg_name)
    qs = take(generator.load_mix("warm-sweep"), cfg, BIG, 100)
    assert {(q.size_budget_kb, q.bw_budget, q.objective) for q in qs} == \
        {(2048, 2048, "cycles")}


@pytest.mark.parametrize("seed", [3, 4, BIG])
def test_every_seed_sends_the_same_work(seed):
    n = len(generator.block_multiset(WIDE_MIX, WIDE_CFG))
    assert n == 3 * 3
    got = Counter((q.size_budget_kb, q.bw_budget, q.objective)
                  for q in take(WIDE_MIX, WIDE_CFG, seed, 4 * n))
    assert got == Counter({(kb, bw, obj): 4 * k
                           for kb, bw in WIDE_CFG["budgets"]
                           for obj, k in WIDE_MIX["objectives"].items()})


def test_seeds_change_the_order():
    assert take(WIDE_MIX, WIDE_CFG, BIG, 90) != \
        take(WIDE_MIX, WIDE_CFG, BIG + 1, 90)


def test_checked_positions_take_the_fraction_up_to_the_cap():
    few = dict(WIDE_MIX, check={"fraction": 0.5, "max": 1000})
    pos = generator.checked_positions(few, BIG, 2000)
    assert 800 < len(pos) < 1200 and pos == sorted(set(pos))
    assert len(generator.checked_positions(WIDE_MIX, BIG, 10 ** 5)) == 40


def test_warmup_covers_every_query_and_group_size():
    bursts = generator.warmup(WIDE_MIX, WIDE_CFG)
    assert len(bursts) == len(set(bursts)) == 3 * 2 * 2
    assert {(kb, bw, obj) for kb, bw, obj, _ in bursts} == \
        set(generator.block_multiset(WIDE_MIX, WIDE_CFG))
    assert {n for *_, n in bursts} == {1, 2}


@pytest.mark.parametrize("cfg_name", CONFIGS)
def test_warm_sweep_warms_its_one_query_once(cfg_name):
    assert generator.warmup(generator.load_mix("warm-sweep"),
                            config(cfg_name)) == [(2048, 2048, "cycles", 1)]
