"""One run of one benchmark cell: set-up, the measured window, the
correctness check against the plain reference, and the result line.

Everything that belongs to one cell is found by name: the cell in
``BENCHMARK.json``, its configuration in ``bench/configs/<config>.json``,
its traffic mix in ``bench/traffic/<traffic>.json`` and each metric's
reader in ``bench/metrics/<metric>.py``. The entry the window drives is
replay validation: ``FleetEngine(plane_backend="jax").run_many`` over C
candidate config-maps and one arrival stream, called in a closed loop by
one caller.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
import traceback
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
#: JAX's persistent compile cache: a fixed path inside the checkout
#: (the path is part of the cache key, so it must never move)
CACHE_DIR = ROOT / ".jax_cache"
CACHE_SETTINGS = {
    "jax_compilation_cache_dir": str(CACHE_DIR),
    "jax_persistent_cache_min_compile_time_secs": 0.0,
    "jax_persistent_cache_min_entry_size_bytes": 0,
    "jax_compilation_cache_max_size": -1,
}

from bench import generate, profile, reference  # noqa: E402


# --------------------------------------------------------------------------
# finding a cell by name
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    root: Path
    chips: int
    config: Dict
    mix: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]


def _applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    found = [w for w in spec["workloads"] if w["name"] == name]
    if not found:
        raise SystemExit(f"no workload named {name!r} in BENCHMARK.json")
    work = found[0]
    conf = [c for c in spec["configs"] if c["name"] == work["config"]][0]
    config = json.loads((root / conf["file"]).read_text())
    mix = json.loads((root / "bench" / "traffic"
                      / f"{work['traffic']}.json").read_text())
    return Cell(name=name, root=root, chips=int(work["chips"]),
                config=config, mix=mix,
                end_to_end=[m for m in spec["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in spec["per_layer"]
                           if _applies(m, name)])


def reader(metric: str, root: Path = ROOT) -> Callable:
    """``read(run) -> float | None`` from ``bench/metrics/<metric>.py``."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


# --------------------------------------------------------------------------
# the device
# --------------------------------------------------------------------------

def accelerator(chips: int) -> Dict:
    """The chips the cell runs on. Where JAX finds no TPU, or fewer
    chips than the cell asks for, the run ends with code 2 and prints
    no result: it never falls back to the CPU."""
    import jax

    devices = jax.devices()
    problem = None
    if devices[0].platform != "tpu":
        problem = (f"no TPU: JAX's first device is {devices[0].platform} "
                   f"({devices[0].device_kind})")
    elif len(devices) < chips:
        problem = (f"the cell asks for {chips} chips, JAX finds "
                   f"{len(devices)}")
    if problem:
        print(problem, file=sys.stderr)
        raise SystemExit(2)
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": chips}


class CompileMeter:
    """Sums JAX's backend-compile seconds and counts compiles and
    persistent-cache hits, from JAX's own monitoring events."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def use_compile_cache() -> None:
    """Keep every compiled program, however small, in the checkout's
    cache, so that only a cell's first run in a checkout compiles. The
    cache is never evicted: it holds a few small programs, and an
    evicting cache cannot read entries written without eviction."""
    import jax

    CACHE_DIR.mkdir(exist_ok=True)
    for key, value in CACHE_SETTINGS.items():
        jax.config.update(key, value)


# --------------------------------------------------------------------------
# the system under test
# --------------------------------------------------------------------------

class SurfaceTimer:
    """Delegates to the backend handed to ``FleetEngine`` and times its
    response-surface calls (``invoke_config_batch``) on the host clock,
    inside a ``bench.surface`` span."""

    def __init__(self, inner):
        self._inner = inner
        self.seconds = 0.0

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def invoke_config_batch(self, nodes, cpu, mem):
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.surface"):
            out = self._inner.invoke_config_batch(nodes, cpu, mem)
        self.seconds += time.perf_counter() - t0
        return out


@dataclasses.dataclass
class CallRecord:
    start: float
    end: float
    instances: int
    surface_s: float

    @property
    def wall(self) -> float:
        return self.end - self.start


class System:
    """The program's objects for one cell: the template built from the
    configuration file, and the engines a validation call drives."""

    def __init__(self, cell: Cell):
        import repro.core  # noqa: F401  (repro.core before repro.serverless)
        from repro.core.cost import PricingModel
        from repro.core.dag import Workflow
        from repro.core.engine import (ClusterModel, ColdStartModel,
                                       FleetEngine)
        from repro.core.resources import ResourceConfig
        from repro.serverless.function import FunctionSpec
        from repro.serverless.platform import AnalyticBackend

        config, mix = cell.config, cell.mix
        if config["backend"] != "analytic":
            raise ValueError(f"unknown backend {config['backend']!r}")
        self.config_type = ResourceConfig
        self.template = Workflow(config["workflow"])
        for f in config["functions"]:
            self.template.add_function(f["name"], payload=FunctionSpec(**f))
        for a, b in config["edges"]:
            self.template.add_edge(a, b)
        self.names = [f["name"] for f in config["functions"]]
        self.slo = float(config["slo_s"])
        p = config["pricing"]
        pricing = PricingModel(mu0=p["mu0_per_vcpu_s"], mu1=p["mu1_per_mb_s"],
                               mu2=p["mu2_per_invocation"])
        plain = AnalyticBackend(input_scale=config["input_scale"])
        self.backend = SurfaceTimer(plain)

        def engine(backend, **kw):
            return FleetEngine(backend, pricing=pricing, plane_backend="jax",
                               **kw)

        constraints = {}
        if mix.get("cluster"):
            c = mix["cluster"]
            constraints["cluster"] = ClusterModel(
                total_cpu=float(c["total_cpu"]),
                total_mem_mb=float(c["total_mem_mb"]))
        if mix.get("cold_start"):
            c = mix["cold_start"]
            constraints["cold_start"] = ColdStartModel(
                delay_s=float(c["delay_s"]),
                keep_alive_s=float(c["keep_alive_s"]))
        #: the one replay of a call: on the constrained plane where the
        #: mix names a cluster or cold starts, else on the fast plane
        self.engine = engine(self.backend, **constraints)
        # the plane with the timed backend and without it (they must
        # agree for surface_ms to stand)
        got = self.engine.batch_eligibility(self.template, [{}])
        want = engine(plain, **constraints).batch_eligibility(
            self.template, [{}])
        if (got["plane"], got["reasons"]) != (want["plane"],
                                              want["reasons"]):
            raise RuntimeError(f"the timed backend moves the replay from "
                               f"{want} to {got}")
        self.plane = (got["plane"], got["reasons"])

    def configs(self, inputs: generate.CallInputs) -> List[Dict]:
        make = self.config_type
        names = self.names
        return [{n: make(cpu=c, mem=m) for n, c, m in zip(names, cr, mr)}
                for cr, mr in zip(inputs.cpu.tolist(), inputs.mem.tolist())]

    def call(self, configs: List[Dict], arrivals: np.ndarray) -> list:
        """One validation, and the numbers a caller decides on read from
        each report. Returns the reports."""
        reports = self.engine.run_many(self.template, configs, [arrivals])
        for r in reports:
            r.slo_attainment(self.slo)
            r.p99
            r.total_cost
        return reports


def answers(reports, slo: float) -> List[reference.Answer]:
    """What the program's reports say, in the reference's terms."""
    return [reference.Answer(
        finish=r.finishes.copy(), latency=r.latencies.copy(),
        queue=r.queue_delays.copy(), cold=r.cold_delays.copy(),
        cost=r.costs.copy(), failed=r.failed_mask.copy(), p99=r.p99,
        hits=int(round(r.slo_attainment(slo) * len(r))),
        total_cost=r.total_cost) for r in reports]


# --------------------------------------------------------------------------
# correctness
# --------------------------------------------------------------------------

def _rel(got: np.ndarray, want: np.ndarray, scale: np.ndarray) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if got.shape != want.shape:
        return math.inf
    if not got.size:
        return 0.0
    same = (got == want)             # equal infinities included
    diff = np.where(same, 0.0, np.abs(got - want))
    ratio = diff / np.maximum(np.abs(np.asarray(scale, np.float64)),
                              np.finfo(np.float64).tiny)
    return float(np.nan_to_num(ratio, nan=math.inf).max())


def compare(got: List[reference.Answer],
            want: List[reference.Answer]) -> Dict[str, float]:
    """The numbers ``correct`` is decided on, for one replay:

    * ``time_rel``: the widest gap in finish, latency, queue delay or
      cold delay of any instance, relative to its latency (its finish
      for the finish), or in a candidate's p99, relative to it;
    * ``cost_rel``: the widest relative gap in an instance's cost or a
      candidate's total cost;
    * ``mismatch``: answers that differ exactly: candidates or instances
      missing or extra, failure flags, and instances within the SLO.
    """
    out = {"time_rel": 0.0, "cost_rel": 0.0, "mismatch": 0}
    out["mismatch"] += abs(len(got) - len(want))
    for g, w in zip(got, want):
        if g.finish.shape != w.finish.shape:
            out["mismatch"] += abs(g.finish.size - w.finish.size) or 1
            out["time_rel"] = out["cost_rel"] = math.inf
            continue
        t = max(_rel(g.finish, w.finish, w.finish),
                _rel(g.latency, w.latency, w.latency),
                _rel(g.queue, w.queue, w.latency),
                _rel(g.cold, w.cold, w.latency),
                _rel([g.p99], [w.p99], [w.p99]))
        c = max(_rel(g.cost, w.cost, w.cost),
                _rel([g.total_cost], [w.total_cost], [w.total_cost]))
        out["time_rel"] = max(out["time_rel"], t)
        out["cost_rel"] = max(out["cost_rel"], c)
        out["mismatch"] += int(np.count_nonzero(g.failed != w.failed))
        out["mismatch"] += abs(g.hits - w.hits)
    return out


def merge_checks(parts: List[Dict[str, float]]) -> Dict[str, float]:
    out = {"time_rel": 0.0, "cost_rel": 0.0, "mismatch": 0}
    for p in parts:
        out["time_rel"] = max(out["time_rel"], p["time_rel"])
        out["cost_rel"] = max(out["cost_rel"], p["cost_rel"])
        out["mismatch"] += p["mismatch"]
    return out


@dataclasses.dataclass
class Sampled:
    """One call kept for the check: its inputs and what the program
    answered."""

    inputs: generate.CallInputs
    got: List[reference.Answer]


def want(cell: Cell, graph: reference.Graph, inputs: generate.CallInputs,
         dtype=np.float64) -> List[reference.Answer]:
    """The reference's answers for one call."""
    return reference.validate(cell.config, graph, inputs.cpu, inputs.mem,
                              inputs.arrivals, cell.mix.get("cluster"),
                              cell.mix.get("cold_start"), dtype)


def check(cell: Cell, sampled: List[Sampled],
          dtype=np.float64) -> Dict[str, float]:
    """The numbers over every sampled call (the reference in ``dtype``)."""
    graph = reference.Graph.of(cell.config)
    return merge_checks([compare(s.got, want(cell, graph, s.inputs, dtype))
                         for s in sampled])


# --------------------------------------------------------------------------
# one run
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Run:
    """What the metric readers read."""

    cell: Cell
    device: Dict
    setup_s: float
    calls: List[CallRecord]
    trace: Optional[profile.Trace] = None


def peaks(kind: str) -> Dict:
    """The chip's published peaks; an unknown device kind is an error."""
    table = json.loads((BENCH / "peaks.json").read_text())
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in "
                       f"bench/peaks.json")
    return table[kind]


def window(cell: Cell, system: System, seed: int, seconds: float,
           sample_rng: np.random.Generator):
    """The measured window: calls back to back until ``seconds`` have
    passed. Returns the call records, the sampled calls and the count
    of calls that raised."""
    import jax

    k = int(cell.mix["check_calls"])
    calls: List[CallRecord] = []
    sampled: List[Sampled] = []
    failed = 0
    n = 0
    t_end = time.perf_counter() + seconds
    while time.perf_counter() < t_end:
        n += 1
        with jax.profiler.TraceAnnotation("bench.inputs"):
            inputs = generate.draw_call(cell.mix, cell.config, seed, n)
            configs = system.configs(inputs)
        surface0 = system.backend.seconds
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation("bench.call"):
                out = system.call(configs, inputs.arrivals)
        except Exception:                      # a validation that never came
            traceback.print_exc()
            failed += 1
            continue
        t1 = time.perf_counter()
        calls.append(CallRecord(t0, t1, inputs.cpu.shape[0]
                                * inputs.arrivals.size,
                                system.backend.seconds - surface0))
        # reservoir sample of the window's calls, drawn from the seed
        slot = n - 1 if n <= k else int(sample_rng.integers(0, n))
        if slot < k:
            with jax.profiler.TraceAnnotation("bench.record"):
                item = Sampled(inputs, answers(out, system.slo))
            if slot < len(sampled):
                sampled[slot] = item
            else:
                sampled.append(item)
    return calls, sampled, failed


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             device: Dict, t_start: float) -> Dict:
    """Set up, measure, check; returns the result line's object."""
    import jax

    use_compile_cache()
    meter = CompileMeter()
    system = System(cell)
    print(f"plane {system.plane[0]} {system.plane[1]} "
          f"device={device['kind']}", file=sys.stderr)
    # warm-up: one call at the cell's own shapes, from its own stream
    warm = generate.draw_call(cell.mix, cell.config, seed, 0)
    system.call(system.configs(warm), warm.arrivals)
    # what set-up left on the heap stays out of the window's collections
    gc.collect()
    gc.freeze()
    setup_compiles = meter.compiles
    setup_s = time.perf_counter() - t_start

    sample_rng = np.random.default_rng([seed % 2**64, 0, 1])
    log_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(log_dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation(profile.WINDOW_SPAN):
            calls, sampled, failed = window(cell, system, seed, seconds,
                                            sample_rng)
        if trace:
            jax.profiler.stop_trace()
            recorded = profile.read(log_dir)
    finally:
        if log_dir:
            shutil.rmtree(log_dir, ignore_errors=True)
    print(f"programs compiled or loaded from the cache: set-up "
          f"{setup_compiles} ({meter.cache_hits} loaded), inside the "
          f"window {meter.compiles - setup_compiles} "
          f"device={device['kind']}", file=sys.stderr)

    stats = jax.devices()[0].memory_stats() or {}
    mem_peak = int(stats.get("peak_bytes_in_use", 0))
    run = Run(cell=cell, device=device, setup_s=setup_s, calls=calls,
              trace=recorded if trace else None)
    del system
    gc.collect()

    numbers = check(cell, sampled)
    limits = cell.config["check_limits"]
    correct = (failed == 0 and bool(calls)
               and all(numbers[k] <= limits[k] for k in limits))

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = reader(m["name"], cell.root)(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = dict(device, memory_peak_bytes=mem_peak)
    result = {"correct": correct, "attempted": len(calls) + failed,
              "failed": failed, "metrics": metrics, "device": dev}
    if trace:
        dev["busy_s"] = profile.busy_ns(run.trace) * 1e-9
        dev["window_s"] = profile.window_ns(run.trace) * 1e-9
        result["breakdown"] = profile.breakdown(run.trace)
    result["checks"] = {k: {"value": _finite(numbers[k]),
                            "limit": limits[k]} for k in limits}
    for k in limits:
        print(f"check {k} {numbers[k]!r} limit {limits[k]!r} "
              f"device={device['kind']}", file=sys.stderr)
    return result


def _finite(x: float) -> float:
    """JSON has no infinity: an infinite gap prints as the largest
    float."""
    return x if math.isfinite(x) else sys.float_info.max


def main(argv: Optional[List[str]] = None, t_start: Optional[float] = None
         ) -> int:
    import argparse

    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    device = accelerator(cell.chips)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      device, t_start)
    print(json.dumps(result))
    return 0
