"""One benchmark run of one cell: set-up, a closed-loop window, the
correctness check, and the result line.

Everything that belongs to a cell comes from files found by name: the
cell in ``BENCHMARK.json``, its configuration (``bench/configs``), its
traffic mix (``bench/traffic``, read by ``bench/generator.py``), the
plain reference the configuration names (``bench/reference``) and one
reader per metric (``bench/metrics/<metric>.py``).  There is no per-cell
code here.

The window drives the program the way its users do: closed-loop clients
send ``DSERequest``s through ``DSEClient`` to one ``DSEService`` over one
``Study`` (grid search on the device backend), then read the answer's
frontier (``points``) and Pareto set.  Latency is taken on the client
side, from submit until both are in hand.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import threading
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Dict, List, Optional, Tuple

from bench import check, generator, spans
from bench import trace as trace_mod

ROOT = Path(__file__).resolve().parent.parent
PLATFORM = "tpu"
POSITIONS = 100_000          # sequence positions the check sample draws from
NETWORK_KEYS = ("net", "training", "batch", "seq")   # keys of `network`


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclass
class Record:
    t_submit: float
    t_result: float = 0.0        # the service's answer is in hand
    t_done: float = 0.0          # frontier and Pareto set are in hand
    n_candidates: int = 0
    wait_s: Optional[float] = None   # latency to the answer minus its search
    failed: bool = False


@dataclass
class Run:
    """What a metric reader reads (see ``bench/metrics``)."""
    setup_s: float
    t_start: float
    t_end: float
    records: List[Record]
    stats: Tuple[object, object]            # ServiceStats at start, end
    tables: Tuple[dict, dict]               # table_cache_stats at start, end
    recorder: Optional[spans.Recorder] = None
    device: Optional[trace_mod.DeviceSummary] = None
    peaks: Optional[dict] = None

    @property
    def window_s(self) -> float:
        return self.t_end - self.t_start

    @property
    def completed(self) -> List[Record]:
        """Queries answered inside the window."""
        return [r for r in self.records
                if not r.failed and r.t_done <= self.t_end]


# ---------------------------------------------------------------------------
# Files found by name
# ---------------------------------------------------------------------------

def load_cell(name: str, root: Path = ROOT) -> Tuple[dict, dict, dict, dict]:
    """(BENCHMARK.json, cell entry, configuration, traffic mix)."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    files = {c["name"]: c["file"] for c in bench["configs"]}
    cfg = json.loads((root / files[cell["config"]]).read_text())
    return bench, cell, cfg, generator.load_mix(cell["traffic"], root)


def cell_metrics(bench: dict, cell: str, traced: bool) -> List[dict]:
    group = bench["per_layer"] if traced else bench["end_to_end"]
    return [m for m in group if "workloads" not in m or cell in m["workloads"]]


def metric_module(name: str, root: Path = ROOT):
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name: str, root: Path = ROOT) -> Callable[[Run], Optional[float]]:
    return metric_module(name, root).read


def calls_of(bench: dict, cell: str, root: Path = ROOT
             ) -> List[Tuple[str, str]]:
    """The program functions, as (module, attribute path), whose calls
    the cell's per-layer readers name in their ``CALLS``: the recorder
    wraps them in every run, traced or not, so both do the same work."""
    return sorted({tuple(c) for m in cell_metrics(bench, cell, True)
                   for c in getattr(metric_module(m["name"], root),
                                    "CALLS", ())})


def workload_of(cfg: dict, name: Optional[str] = None):
    """The configuration's ``network`` entry as the program's ``Workload``:
    ``net``, ``training``, ``batch`` and, for an LLM configuration, an
    optional ``seq``.  Any other key raises: a misspelt key is never
    priced at the program's default."""
    net = cfg["network"]
    unknown = sorted(set(net) - set(NETWORK_KEYS))
    if unknown:
        raise ValueError(f"{cfg.get('name')}: unknown network key(s) "
                         f"{unknown}; known: {list(NETWORK_KEYS)}")
    from repro.core.study import Workload
    seq = net.get("seq")
    return Workload(net["net"], training=bool(net["training"]),
                    batch=int(net["batch"]), name=name,
                    seq=None if seq is None else int(seq))


def peaks_for(kind: str, root: Path = ROOT) -> dict:
    table = json.loads((root / "bench" / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table["devices"][kind]


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------

def _devices(chips: int, require_chip: bool) -> dict:
    import jax
    devs = jax.devices()
    if require_chip and (devs[0].platform != PLATFORM or len(devs) < chips):
        raise NoChip(f"need {chips} {PLATFORM} chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _compile_counter() -> List[Tuple[float, str]]:
    """Every trace, lowering or compile JAX reports, with its time."""
    import jax
    seen: List[Tuple[float, str]] = []

    def on_duration(event: str, _secs: float, **_kw) -> None:
        if event.startswith("/jax/core/compile/"):
            seen.append((time.perf_counter(), event))

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    return seen


def run(cell_name: str, seed: int, seconds: float, traced: bool, *,
        t0: float, root: Path = ROOT, require_chip: bool = True,
        log: Callable[[str], None] = lambda s: print(s, file=sys.stderr,
                                                     flush=True)) -> dict:
    """One run; returns the result line's object."""
    bench, cell, cfg, mix = load_cell(cell_name, root)
    device = _devices(int(cell["chips"]), require_chip)
    sys.path.insert(0, str(root / "src"))
    import jax
    from repro.core import gridax                  # noqa: F401 (device path)
    from repro.core.energy import EnergyModel
    from repro.core.hardware import HardwareSpec
    from repro.core.study import Study
    from repro.serve import DSEClient, DSERequest, DSEService, ServiceError

    wl = workload_of(cfg)               # an unknown key raises here

    def workload(name: Optional[str] = None):
        return dataclasses.replace(wl, name=name)

    # the spans are kept in every run (their cost is a few clock reads a
    # query); the per-layer metrics read them in traced runs only
    recorder = spans.Recorder(        # raises on a missing program name
        calls=calls_of(bench, cell_name, root))
    recorder.install()
    compiles = _compile_counter()
    lat = cfg["lattice"]
    study = Study(HardwareSpec(**cfg["hardware"]), sizes=lat["sizes_kb"],
                  bws=lat["bws"], tol=lat["tol"],
                  energy_model=EnergyModel(**cfg["energy"]),
                  workers=int(cfg["workers"]), store=None,
                  backend=cfg["backend"])
    svc = DSEService(study, **cfg["service"])
    client = DSEClient(svc)
    try:
        # set-up: every budget pair, objective and group size the window
        # will use
        for kb, bw, obj, n in generator.warmup(mix, cfg):
            reqs = [DSERequest(workload(f"warmup{i}" if n > 1 else None),
                               kb, bw, objective=obj)
                    for i in range(n)]
            for res in study.search_requests(reqs):
                res.points, res.pareto()
        kb, bw = cfg["budgets"][0]
        res = client.query(workload(), kb, bw)
        res.points, res.pareto()
        queries = generator.queries(mix, cfg, seed)
        keep = set(generator.checked_positions(mix, seed, POSITIONS))
        lock = threading.Lock()
        records: List[Record] = []
        kept: Dict[int, tuple] = {}
        n_compiles = len(compiles)
        gc.collect()
        stats0, tables0 = svc.stats(), study.cache_stats()

        def client_loop(deadline: float) -> None:
            while True:
                with lock:
                    q = next(queries)
                if time.perf_counter() >= deadline:
                    return
                key = (q.size_budget_kb, q.bw_budget, q.objective, wl.batch)
                req = DSERequest(workload(), q.size_budget_kb,
                                 q.bw_budget, objective=q.objective)
                rec = Record(time.perf_counter())
                try:
                    with spans.annotate("bench.client", traced):
                        res = client.submit(req).result()
                except ServiceError as e:
                    rec.failed = True
                    log(f"query {q.index} {key} failed: {e}")
                    with lock:
                        records.append(rec)
                    continue
                rec.t_result = time.perf_counter()
                with spans.annotate("bench.float_steps", traced):
                    points, pareto = res.points, res.pareto()
                rec.t_done = time.perf_counter()
                rec.n_candidates = res.n_candidates
                s = spans.search_span(recorder, req)
                if s is not None:
                    rec.wait_s = rec.t_result - rec.t_submit - s
                parts = (res.best, res.worst, points, pareto,
                         res.n_candidates)
                with lock:
                    records.append(rec)
                    if q.index in keep:
                        kept[q.index] = (key, parts)

        log_dir = None
        if traced:
            log_dir = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(log_dir, profiler_options=opts)
            recorder.annotate = True
        t_start = time.perf_counter()
        deadline = t_start + seconds
        threads = [threading.Thread(target=client_loop, args=(deadline,),
                                    name=f"bench-client-{i}", daemon=True)
                   for i in range(int(mix["clients"]))]
        for t in threads:
            t.start()
        with spans.annotate(trace_mod.WINDOW, traced):
            time.sleep(max(0.0, deadline - time.perf_counter()))
        t_end = time.perf_counter()
        if traced:
            recorder.annotate = False
            jax.profiler.stop_trace()
        for t in threads:
            t.join()
        in_window = [c for c in compiles[n_compiles:]
                     if t_start <= c[0] <= t_end]
        print(f"compiles in window: {len(in_window)} "
              f"{sorted({e for _, e in in_window})}", flush=True)
        stats1, tables1 = svc.stats(), study.cache_stats()
        mem = jax.devices()[0].memory_stats() or {}
        device["memory_peak_bytes"] = int(mem.get("peak_bytes_in_use", 0))
    finally:
        svc.close()
        recorder.uninstall()

    r = Run(setup_s=t_start - t0, t_start=t_start, t_end=t_end,
            records=records, stats=(stats0, stats1),
            tables=(tables0, tables1),
            recorder=recorder if traced else None)
    n_done = len(r.completed)
    if n_done:
        log(f"dispatcher search ms per answered query "
            f"{recorder.total('search', t_start, t_end) / n_done * 1e3}")
    result: dict = {}
    if traced:
        summary = trace_mod.summarize(trace_mod.latest_xplane(log_dir))
        shutil.rmtree(log_dir, ignore_errors=True)
        r.device, r.peaks = summary, peaks_for(device["kind"], root)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in summary.ops],
            "idle_gaps": [[n, s] for n, s in summary.idle_gaps],
            "device_by_span": sorted(
                ([n, s] for n, s in summary.op_s_by_span.items()),
                key=lambda x: -x[1])[:10]}
    metrics = {}
    for m in cell_metrics(bench, cell_name, traced):
        v = reader(m["name"], root)(r)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    # the check runs once the program's state is freed
    study.clear_caches()
    del study, svc, client
    answers = [kept[i] for i in sorted(kept)]
    gc.collect()
    t_check = time.perf_counter()
    numbers, wrong = check.compare(
        [(key, _answer_parts(parts)) for key, parts in answers],
        check.reference(cfg))
    log(f"reference check of {len(answers)} answers "
        f"({len({k for k, _ in answers})} queries): "
        f"{time.perf_counter() - t_check:.1f} s; fields wrong: "
        + " ".join(f"{k}={v}" for k, v in wrong.items()))
    failed = sum(rec.failed for rec in records)
    correct = bool(answers) and failed == 0 and check.within_limits(numbers)
    for k, v in numbers.items():
        log(f"check {k} {v} limit {check.LIMITS[k]}")
    log(f"check answers_compared {len(answers)} limit >=1")
    result = {"correct": correct, "attempted": len(records),
              "failed": failed, "metrics": metrics, "device": device,
              **result,
              "check": {**{k: {"value": v, "limit": check.LIMITS[k]}
                           for k, v in numbers.items()},
                        "answers_compared": {"value": len(answers),
                                             "limit": ">=1"}}}
    return result


def _answer_parts(parts) -> dict:
    best, worst, points, pareto, n = parts
    return {"best": check.as_tuple(best), "worst": check.as_tuple(worst),
            "points": [check.as_tuple(p) for p in points],
            "pareto": [check.as_tuple(p) for p in pareto],
            "n_candidates": int(n)}


def main(argv: List[str], t0: float) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = p.parse_args(argv)
    # JAX's persistent compilation cache lives at a fixed path inside the
    # checkout; the program takes the directory this variable names, and
    # every program is kept, however short its compile
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        result = run(a.workload, a.seed, a.seconds, bool(a.trace), t0=t0)
    except NoChip as e:
        print(f"no chip: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result))
    return 0
