"""The control of the correctness check, run apart from the benchmark::

    python bench/control.py --workload <cell> --seeds 1,2,3 --queries 250

It puts the plain reference computed one precision below the one the
configuration states (float32 grids, energy and scores in place of int64
cycles and float64 energy) in the program's place: for each seed it
answers the queries a run of the cell would keep for the comparison (the
seeded positions among the first ``--queries`` of the cell's sequence)
and compares them with the exact reference, as ``bench/check.py``
compares a run's answers.
The control has to come out not correct.  It needs no chip.
"""
import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import check, generator, harness  # noqa: E402


def control_numbers(cfg: dict, mix: dict, seed: int, n: int) -> dict:
    seq = generator.queries(mix, cfg, seed)
    qs = [q for q, _ in zip(seq, range(n))]
    picked = [qs[i] for i in generator.checked_positions(mix, seed, n)]
    batch = int(cfg["network"]["batch"])
    keys = [(q.size_budget_kb, q.bw_budget, q.objective, batch)
            for q in picked]
    low = check.reference(cfg, precision="float32")
    kept = [(k, vars(low.answer(*k))) for k in keys]
    return check.compare(kept, check.reference(cfg))


def main(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--queries", type=int, required=True,
                   help="queries a run of the cell answers in its window")
    a = p.parse_args(argv)
    _, _, cfg, mix = harness.load_cell(a.workload)
    failed_all = True
    for seed in (int(s) for s in a.seeds.split(",")):
        t = time.perf_counter()
        numbers, wrong = control_numbers(cfg, mix, seed, a.queries)
        ok = check.within_limits(numbers)
        failed_all &= not ok
        print(f"control {a.workload} seed {seed}: "
              + " ".join(f"{k}={v}" for k, v in numbers.items())
              + " (fields: " + " ".join(f"{k}={v}" for k, v in wrong.items())
              + f") -> {'correct' if ok else 'not correct'} "
              f"({time.perf_counter() - t:.1f} s)", flush=True)
    return 0 if failed_all else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
