"""Fleet engine throughput: instances/sec and engine-step wall time.

Runs 100 concurrent chatbot instances (Poisson arrivals) through the
discrete-event engine on a capacity-constrained cluster, plus a
1k-node generated layered DAG as a single instance, plus the batched
replay plane (C candidate config-maps × S arrival seeds through
``FleetEngine.run_many`` vs the looped scalar ``run``, on both the
contention-free fast plane and the finite-cluster + cold-start
constrained plane), and reports

  * simulation wall time + simulated instances per wall-second,
  * invocations evaluated per wall-second (vectorized batch path),
  * queuing/latency percentiles of the constrained run,
  * C×S batched-replay speedup over the scalar loop for both planes,
    with every cell verified bit-identical,
  * an informational ``jax_scan_fleet`` row timing the jitted
    rank-by-rank sweep against the numpy sweep.

Emits ``BENCH_fleet.json`` under artifacts/bench/ so regressions in
the engine hot path surface in CI diffs. ``--smoke`` gates the
``replay_batch`` AND ``constrained_replay_batch`` acceptance bars
(≥5× at bit-identical reports) without overwriting the artifact.
"""
from __future__ import annotations

import sys
import time
from typing import List, Optional

import numpy as np

from repro.core.engine import (ClusterModel, ColdStartModel, FleetEngine,
                               PoissonArrivals, run_fleet)
from repro.core.resources import ResourceConfig
from repro.serverless.generator import (layered_workflow, suggest_slo)
from repro.serverless.platform import SimulatedPlatform
from repro.serverless.workloads import chatbot, workload_slo

from benchmarks.common import emit

N_INSTANCES = 100
CLUSTER = ClusterModel(total_cpu=60.0, total_mem_mb=61440.0)
COLD = ColdStartModel(delay_s=0.5, keep_alive_s=300.0)

#: replay_batch grid: C candidates × S arrival seeds × N instances
REPLAY_C, REPLAY_S, REPLAY_N = 6, 4, 40
#: the smoke bar: batched replays at least this much faster than the
#: looped scalar path, bit-identical on every compared cell
REPLAY_SPEEDUP_BAR = 5.0


def _run_fleet_case():
    platform = SimulatedPlatform()
    env = platform.environment()
    t0 = time.perf_counter()
    report = run_fleet(env, chatbot(),
                       PoissonArrivals(rate=0.1, n=N_INSTANCES, seed=0),
                       cluster=CLUSTER, cold_start=COLD)
    wall = time.perf_counter() - t0
    return {
        "case": "chatbot_fleet100",
        "n_instances": N_INSTANCES,
        "wall_s": wall,
        "instances_per_s": N_INSTANCES / wall,
        "invocations": platform.invocations,
        "invocations_per_s": platform.invocations / wall,
        "p50_s": report.p50,
        "p99_s": report.p99,
        "total_queue_delay_s": report.total_queue_delay,
        "cpu_utilization": report.cpu_utilization,
        "slo_attainment": report.slo_attainment(workload_slo("chatbot")),
        "total_cost": report.total_cost,
    }


def _run_big_dag_case():
    wf = layered_workflow(1000, n_layers=25, p_edge=0.05, seed=0)
    slo = suggest_slo(wf)
    platform = SimulatedPlatform()
    env = platform.environment()
    t0 = time.perf_counter()
    sample = env.execute(wf, slo=slo)
    wall = time.perf_counter() - t0
    return {
        "case": "layered1000_single",
        "n_nodes": len(wf),
        "wall_s": wall,
        "invocations_per_s": platform.invocations / wall,
        "e2e_s": sample.e2e_runtime,
        "feasible": sample.feasible,
    }


def _reports_identical(a, b) -> bool:
    return (np.array_equal(a.latencies, b.latencies)
            and np.array_equal(a.costs, b.costs)
            and np.array_equal(a.queue_delays, b.queue_delays)
            and np.array_equal(a.finishes, b.finishes)
            and np.array_equal(a.failed_mask, b.failed_mask)
            and a.makespan == b.makespan
            and a.total_cost == b.total_cost)


def _replay_grid(n_candidates: int, n_seeds: int, n_instances: int):
    """The shared C×S×N replay grid every replay row benchmarks."""
    template = layered_workflow(12, n_layers=4, seed=7)
    rng = np.random.default_rng(1)
    candidates = []
    for _ in range(n_candidates):
        candidates.append({
            n.name: ResourceConfig(cpu=float(rng.uniform(1.0, 8.0)),
                                   mem=float(rng.uniform(2048.0, 8192.0)))
            for n in template})
    seeds = [PoissonArrivals(0.5, n_instances, seed=s).times()
             for s in range(n_seeds)]
    return template, candidates, seeds


def _time_batch_vs_loop(case: str, n_candidates: int, n_seeds: int,
                        n_instances: int, **engine_kw):
    """Time ``run_many`` against the looped scalar path on one engine
    configuration and verify every cell bit-identical."""
    template, candidates, seeds = _replay_grid(n_candidates, n_seeds,
                                               n_instances)
    env = SimulatedPlatform().environment()
    engine = FleetEngine(env.backend, pricing=env.pricing, **engine_kw)

    t0 = time.perf_counter()
    batched = engine.run_many(template, candidates, seeds)
    batch_wall = time.perf_counter() - t0

    t0 = time.perf_counter()
    looped = []
    for configs in candidates:
        for times in seeds:
            wfs = []
            for _ in range(len(times)):
                wf = template.copy()
                wf.apply_configs(configs)
                wfs.append(wf)
            looped.append(engine.run(wfs, times))
    loop_wall = time.perf_counter() - t0

    identical = all(_reports_identical(a, b)
                    for a, b in zip(batched, looped))
    return {
        "case": case,
        "n_candidates": n_candidates,
        "n_seeds": n_seeds,
        "n_instances": n_instances,
        "n_fleets": n_candidates * n_seeds,
        "batch_wall_s": batch_wall,
        "loop_wall_s": loop_wall,
        "speedup_x": loop_wall / batch_wall if batch_wall > 0
        else float("inf"),
        "bit_identical": identical,
    }


def _run_replay_batch_case(n_candidates: int = REPLAY_C,
                           n_seeds: int = REPLAY_S,
                           n_instances: int = REPLAY_N):
    """C×S batched replays (``run_many``) vs the looped scalar path on
    the contention-free fast plane — the campaign/adaptive/online
    validation hot path at benchmark scale. Every cell is verified
    bit-identical; the row carries the realized speedup."""
    return _time_batch_vs_loop("replay_batch", n_candidates, n_seeds,
                               n_instances)


def _run_constrained_replay_case(n_candidates: int = REPLAY_C,
                                 n_seeds: int = REPLAY_S,
                                 n_instances: int = REPLAY_N):
    """The production-shaped grid: finite CPU+mem cluster AND cold
    starts, replayed through the table-driven constrained plane vs the
    looped scalar event loop — the case that used to serialize
    entirely. Same bit-identity bar as the fast plane."""
    return _time_batch_vs_loop("constrained_replay_batch", n_candidates,
                               n_seeds, n_instances,
                               cluster=CLUSTER, cold_start=COLD)


def _run_jax_scan_case(n_candidates: int = REPLAY_C,
                       n_seeds: int = REPLAY_S,
                       n_instances: int = REPLAY_N):
    """Informational row: the fast plane's longest-path sweep as a
    jitted program (``FleetEngine(plane_backend="jax")``) vs the
    numpy sweep, bit-identity included."""
    template, candidates, seeds = _replay_grid(n_candidates, n_seeds,
                                               n_instances)

    def fresh(plane):
        env = SimulatedPlatform().environment()
        return FleetEngine(env.backend, pricing=env.pricing,
                           plane_backend=plane)

    jax_engine = fresh("jax")
    jax_engine.run_many(template, candidates, seeds)   # jit warm-up
    t0 = time.perf_counter()
    jax_reports = jax_engine.run_many(template, candidates, seeds)
    jax_wall = time.perf_counter() - t0
    numpy_engine = fresh("numpy")
    t0 = time.perf_counter()
    numpy_reports = numpy_engine.run_many(template, candidates, seeds)
    numpy_wall = time.perf_counter() - t0
    identical = all(_reports_identical(a, b)
                    for a, b in zip(jax_reports, numpy_reports))
    return {
        "case": "jax_scan_fleet",
        "n_candidates": n_candidates,
        "n_seeds": n_seeds,
        "n_instances": n_instances,
        "jax_wall_s": jax_wall,
        "numpy_wall_s": numpy_wall,
        "jax_vs_numpy_x": numpy_wall / jax_wall if jax_wall > 0
        else float("inf"),
        "bit_identical": identical,
    }


def check_replay_acceptance(row) -> List[str]:
    """The bar the smoke lane enforces: ≥5× batched replay throughput
    with ``run_many`` bit-identical to the scalar loop everywhere —
    on the fast plane AND the constrained (finite cluster + cold
    start) plane."""
    errors = []
    if not row["bit_identical"]:
        errors.append(f"{row['case']}: run_many reports diverged from "
                      f"the scalar loop")
    if row["speedup_x"] < REPLAY_SPEEDUP_BAR:
        errors.append(f"{row['case']} speedup {row['speedup_x']:.1f}x "
                      f"< {REPLAY_SPEEDUP_BAR:.0f}x")
    return errors


#: the rows the smoke lane gates (jax row is informational only and
#: must not run there — the smoke job installs numpy alone)
SMOKE_CASES = (_run_replay_batch_case, _run_constrained_replay_case)


def main(verbose: bool = True, argv: Optional[List[str]] = None):
    argv = sys.argv[1:] if argv is None else argv
    smoke = "--smoke" in argv
    if smoke:
        # the gate only needs the replay grids; re-time a failing case
        # up to 3 times before failing so a noisy CI neighbor cannot
        # flake the bar (bit-identity must hold on every attempt)
        all_failures: List[str] = []
        for case_fn in SMOKE_CASES:
            failures: List[str] = []
            for _ in range(3):
                row = case_fn()
                failures = check_replay_acceptance(row)
                if verbose:
                    print(f"fleet,{row['case']}_speedup_x,"
                          f"{row['speedup_x']},")
                    print(f"fleet,{row['case']}_bit_identical,"
                          f"{row['bit_identical']},")
                if not failures or not row["bit_identical"]:
                    break
            for f in failures:
                print(f"FAIL {f}")
            if not failures:
                print(f"OK   fleet_throughput         "
                      f"{row['case']} {row['speedup_x']:.1f}x "
                      f"(bar {REPLAY_SPEEDUP_BAR:.0f}x, bit-identical)")
            all_failures.extend(failures)
        return 1 if all_failures else 0

    rows = [_run_fleet_case(), _run_big_dag_case(),
            _run_replay_batch_case(), _run_constrained_replay_case(),
            _run_jax_scan_case()]
    if verbose:
        for r in rows:
            for k, v in r.items():
                if k == "case":
                    continue
                print(f"fleet,{r['case']}_{k},{v},")
    emit(rows, "BENCH_fleet")
    return rows


if __name__ == "__main__":
    out = main()
    sys.exit(out if isinstance(out, int) else 0)
