"""The replay path's spans and counters (``repro.core.telemetry``).

Under a JAX profiler trace on the CPU, each ``run_many`` records one
``fleet.run_many`` span whose phases nest inside it and carry its
``call``; the counters name the plane ``batch_eligibility`` reports;
tracing changes no bit of any report; and a numpy-only deployment never
loads jax.
"""
import glob
import os
import shutil
import subprocess
import sys
import tempfile
import warnings

import numpy as np
import pytest

from repro.core import telemetry
from repro.core.backend import CallableBackend
from repro.core.cost import PricingModel
from repro.core.engine import (ClusterModel, ColdStartModel, FleetEngine,
                               PoissonArrivals)
from repro.core.resources import ResourceConfig
from repro.serverless.generator import fan_workflow, layered_workflow
from repro.serverless.platform import SimulatedPlatform, StochasticBackend

CONSTRAINED_KW = dict(cluster=ClusterModel(total_cpu=12.0,
                                           total_mem_mb=16384.0),
                      cold_start=ColdStartModel(delay_s=1.0,
                                                keep_alive_s=30.0))


class _ScalarPricing(PricingModel):
    """The same prices through a scalar override with no matching
    ``cost_batch``: the fast plane fills its cost table entry by
    entry."""

    def function_cost(self, runtime_s, config):
        return super().function_cost(runtime_s, config)


def _engine(plane: str) -> FleetEngine:
    env = SimulatedPlatform().environment()
    if plane == "fast":
        return FleetEngine(env.backend, pricing=env.pricing,
                           plane_backend="jax")
    if plane == "constrained":
        return FleetEngine(env.backend, pricing=env.pricing,
                           **CONSTRAINED_KW)
    if plane == "scalar_pricing":
        return FleetEngine(env.backend, pricing=_ScalarPricing())
    if plane == "stochastic":
        # replay noise: the fast plane with the numpy sweep
        return FleetEngine(StochasticBackend(noise_sigma=0.05, seed=3),
                           pricing=env.pricing, plane_backend="jax")
    return FleetEngine(CallableBackend(lambda node: node.config.cpu * 0.1),
                       pricing=env.pricing)


def _inputs(n_cand=3, n_seeds=2, n=6):
    template = layered_workflow(8, n_layers=3, seed=14)
    rng = np.random.default_rng(5)
    cands = [{node.name: ResourceConfig(cpu=float(rng.uniform(1.0, 8.0)),
                                        mem=float(rng.uniform(1024.0,
                                                              8192.0)))
              for node in template} for _ in range(n_cand)]
    seeds = [PoissonArrivals(0.25, n, seed=s).times()
             for s in range(n_seeds)]
    return template, cands, seeds


def _distinct_runtimes(plane, template, cands):
    """The distinct runtimes (by bits) of every candidate's functions,
    one scalar ``invoke_batch`` per candidate: what the noise-off ledger
    folds once per call."""
    backend = _engine(plane).backend
    seen = set()
    for configs in cands:
        wf = template.copy()
        wf.apply_configs(configs)
        runtimes, _ = backend.invoke_batch(list(wf))
        seen.update(float(x).hex() for x in runtimes)
    return len(seen)


def _traced(fn):
    """Run ``fn`` under a profiler trace; returns its result and the
    trace's ``fleet.*`` spans as (start, end, name, stats)."""
    import jax
    from jax.profiler import ProfileData

    log_dir = tempfile.mkdtemp()
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        try:
            out = fn()
        finally:
            jax.profiler.stop_trace()
        path, = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
        data = ProfileData.from_file(path)
        spans = []
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            for plane in data.planes:
                for line in plane.lines:
                    for e in line.events:
                        if e.name.startswith("fleet."):
                            a = float(e.start_ns)
                            stats = {k: v for k, v in e.stats}
                            spans.append((a, a + float(e.duration_ns),
                                          e.name, stats))
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    return out, spans


def _inside(inner, outer) -> bool:
    return outer[0] <= inner[0] and inner[1] <= outer[1]


@pytest.mark.parametrize("plane", ["fast", "constrained"])
def test_each_run_many_is_one_span_with_its_phases_inside(plane):
    template, cands, seeds = _inputs()
    engine = _engine(plane)
    engine.run_many(template, cands, seeds)          # compile outside

    def two_calls():
        engine.run_many(template, cands, seeds)
        engine.run_many(template, cands, seeds)

    _, spans = _traced(two_calls)
    roots = [s for s in spans if s[2] == "fleet.run_many"]
    assert len(roots) == 2
    assert roots[0][3]["call"] != roots[1][3]["call"]
    for root in roots:
        assert root[3]["plane"] == plane
        assert root[3]["candidates"] == 3 and root[3]["arrival_sets"] == 2
        assert root[3]["instances"] == 3 * 2 * 6
        kids = [s for s in spans if s[2] != "fleet.run_many"
                and s[3]["call"] == root[3]["call"]]
        assert kids and all(_inside(k, root) for k in kids)
        names = [k[2] for k in kids]
        made, = [k for k in kids if k[2] == "fleet.candidates"]
        assert made[3]["cells"] == 3 * 8
        assert names.count("fleet.surface") == 1
        assert names.count("fleet.price") == 1
        if plane == "fast":
            assert names.count("fleet.sweep") == 1
            assert names.count("fleet.fetch") == 1
            assert names.count("fleet.assemble") == 1
            assert names.count("fleet.ledger") == 3 * 2
            # the noise-off ledger folds each distinct runtime once per
            # call, before the cells read their sums
            assert names.count("fleet.ledger.fold") == 1
            sweep = next(k for k in kids if k[2] == "fleet.sweep")
            assert sweep[3]["cells"] == 3 * 2
            assemble = next(k for k in kids if k[2] == "fleet.assemble")
            fold = next(k for k in kids if k[2] == "fleet.ledger.fold")
            assert _inside(fold, assemble)
            assert fold[3]["distinct"] == _distinct_runtimes(plane,
                                                             template, cands)
            for k in kids:
                if k[2] == "fleet.fetch":
                    assert _inside(k, sweep)
                if k[2] == "fleet.ledger":
                    assert _inside(k, assemble)
                    assert fold[1] <= k[0]
            assert sorted(k[3]["cand"] for k in kids
                          if k[2] == "fleet.ledger") == [0, 0, 1, 1, 2, 2]
        else:
            cells = [k for k in kids if k[2] == "fleet.cell"]
            assert len(cells) == 3 * 2
            assert {k[3]["plane"] for k in cells} == {"constrained"}


@pytest.mark.parametrize("plane", ["fast", "constrained", "scalar_pricing",
                                   "serial", "stochastic"])
def test_counters_name_the_plane_batch_eligibility_reports(plane,
                                                         fresh_memo):
    template, cands, seeds = _inputs()
    # the quantized values a plane rebuilds configs from, seen once
    # before the call: it then misses no memo of them
    for configs in cands:
        for cfg in configs.values():
            cfg.copy()
    engine = _engine(plane)
    routed = "fast" if plane in ("stochastic", "scalar_pricing") else plane
    assert engine.batch_eligibility(template, cands)["plane"] == routed
    before = telemetry.counters()
    engine.run_many(template, cands, seeds)
    after = telemetry.counters()
    delta = {k: v - before.get(k, 0) for k, v in after.items()
             if v != before.get(k, 0)}
    assert delta.pop(f"fleet.calls.{routed}") == 1
    assert delta.pop("fleet.instances") == 3 * 2 * 6
    cells = 3 * 2
    if routed == "fast":
        assert delta.pop("fleet.cells.swept") == cells
        # one busy-ledger row per function of each swept cell, in the
        # fold its replay takes: repeated runtimes without noise,
        # start-ordered ones with it
        fold = "ordered" if plane == "stochastic" else "repeat"
        assert delta.pop(f"fleet.ledger.rows.{fold}") == cells * 8
        if fold == "repeat":
            # served from one fold per distinct runtime of the call
            assert delta.pop("fleet.ledger.rows.folded") == \
                _distinct_runtimes(plane, template, cands)
        if plane == "fast":
            # the jitted sweep (the stochastic plane sweeps in numpy):
            # one step per rank, each padded to its largest in-degree
            ranks = _ranks(template)
            preds = [len(template.predecessors(n)) for n in template.nodes]
            assert delta.pop("fleet.sweep.steps") == len(ranks)
            # each rank after the sources fits one gather block
            assert delta.pop("fleet.sweep.blocks") == len(ranks) - 1
            assert delta.pop("fleet.sweep.slots") == sum(
                len(r) * max(len(template.predecessors(n)) for n in r)
                for r in ranks)
            assert delta.pop("fleet.sweep.edges") == sum(preds)
    else:
        assert delta.pop("fleet.cells.per_cell") == cells
    # the sweep's shape may have been seen by an earlier test here
    assert delta.pop("fleet.sweep.shapes", 0) in (0, 1)
    assert delta == {}


def test_a_new_sweep_shape_is_counted_once():
    # 37 instances in one arrival set: a shape no other test sweeps
    template, cands, seeds = _inputs(n_seeds=1, n=37)
    engine = _engine("fast")
    before = telemetry.counters().get("fleet.sweep.shapes", 0)
    engine.run_many(template, cands, seeds)
    engine.run_many(template, cands, seeds)
    assert telemetry.counters()["fleet.sweep.shapes"] == before + 1


def test_a_quantized_value_is_missed_once_then_memoized(fresh_memo):
    """``resources.quantize.misses`` counts a float's first sight by
    each resource, not a repeat; over two ``run_many`` calls of the
    same ``video.fast``-shaped candidates (the incumbent and challengers
    moving 2 of 6 functions on the lattice) the second adds none."""
    def misses():
        return telemetry.counters().get("resources.quantize.misses", 0)

    before = misses()
    ResourceConfig(cpu=2.5, mem=2048.0)
    assert misses() == before + 2
    ResourceConfig(cpu=2.5, mem=2048.0)
    ResourceConfig(cpu=2.5, mem=2.5)        # a value seen by cpu only
    assert misses() == before + 3
    ResourceConfig(cpu=2, mem=np.float64(2048.0))   # kept by no memo
    assert misses() == before + 3

    template = fan_workflow(4, seed=12)
    names = list(template.nodes)
    rng = np.random.default_rng(7)
    cpu_k = np.full((8, len(names)), 80)
    mem_k = np.full((8, len(names)), 80)
    for c in range(1, 8):
        moved = rng.choice(len(names), size=2, replace=False)
        cpu_k[c, moved] += rng.integers(-10, 11, size=2)
        mem_k[c, moved] += rng.integers(-8, 9, size=2)
    cpu, mem = (cpu_k * 0.1).tolist(), (mem_k * 64.0).tolist()
    _, _, seeds = _inputs(n_seeds=1, n=64)
    engine = _engine("fast")
    added = []
    for _ in range(2):
        before = misses()
        cands = [{n: ResourceConfig(cpu=c, mem=m)
                  for n, c, m in zip(names, cr, mr)}
                 for cr, mr in zip(cpu, mem)]
        engine.run_many(template, cands, seeds)
        added.append(misses() - before)
    distinct = len(set(sum(cpu, []))) + len(set(sum(mem, [])))
    assert added == [distinct, 0]


def _ranks(template):
    """The functions grouped by their longest hop count from a
    source."""
    rank = {}
    for name in template.topological_order():
        rank[name] = 1 + max((rank[p] for p in template.predecessors(name)),
                             default=-1)
    return [[n for n in rank if rank[n] == r]
            for r in range(max(rank.values()) + 1)]


SWEEP_COUNTERS = ("fleet.sweep.steps", "fleet.sweep.blocks",
                  "fleet.sweep.slots", "fleet.sweep.edges")


def _sweep_counts(engine, template, seeds):
    before = telemetry.counters()
    engine.run_many(template, [{}, {}], seeds)
    after = telemetry.counters()
    return {k: after[k] - before.get(k, 0) for k in SWEEP_COUNTERS}


def test_sweep_counters_count_steps_padded_slots_and_edges():
    """A fan-in, three ranks deep: the source gathers nothing, each of
    the 5 branches gathers its one predecessor, and the join gathers
    all 5 — 10 slots in two blocks, every slot an edge."""
    template = fan_workflow(5, seed=2)
    _, _, seeds = _inputs()
    engine = _engine("fast")
    for _ in range(2):
        assert _sweep_counts(engine, template, seeds) == {
            "fleet.sweep.steps": 3, "fleet.sweep.blocks": 2,
            "fleet.sweep.slots": 5 + 5, "fleet.sweep.edges": 10}


def test_sweep_blocks_follow_slots_not_an_in_degree():
    """A fan of 1,100 branches: the branches' rank is wider than a
    block of 1,024 slots, and the join's in-degree is larger than one;
    each is cut into two even blocks of 550, with no padding."""
    from repro.core.engine import _SWEEP_BLOCK
    assert _SWEEP_BLOCK < 1100 <= 2 * _SWEEP_BLOCK
    template = fan_workflow(1100, seed=2)
    _, _, seeds = _inputs(n=3)
    assert _sweep_counts(_engine("fast"), template, seeds) == {
        "fleet.sweep.steps": 3, "fleet.sweep.blocks": 2 + 2,
        "fleet.sweep.slots": 1100 + 1100, "fleet.sweep.edges": 2200}


def test_rank_tables_are_built_once_per_structure_in_a_span():
    """``fleet.sweep.tables`` wraps the first sweep of a template's
    structure, inside the call's ``fleet.sweep``, with the ranks, the
    edges and the largest in-degree; a second call keeps the tables."""
    template, cands, seeds = _inputs()
    engine = _engine("fast")

    def two_calls():
        engine.run_many(template, cands, seeds)
        engine.run_many(template, cands, seeds)

    _, spans = _traced(two_calls)
    tables, = [s for s in spans if s[2] == "fleet.sweep.tables"]
    sweep = next(s for s in spans if s[2] == "fleet.sweep"
                 and s[3]["call"] == tables[3]["call"])
    assert _inside(tables, sweep)
    preds = [len(template.predecessors(n)) for n in template.nodes]
    assert {k: tables[3][k] for k in ("ranks", "edges", "fanin")} == {
        "ranks": len(_ranks(template)), "edges": sum(preds),
        "fanin": max(preds)}


def _equations(jaxpr) -> int:
    """Equations of a jaxpr, those of the jaxprs inside it included."""
    count = 0
    for eqn in jaxpr.eqns:
        count += 1
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) \
                    else (value,):
                if hasattr(sub, "eqns"):
                    count += _equations(sub)
    return count


def _sweep_equations(ranks, c=8, n=64) -> int:
    """Equations of the jitted sweep traced for ``ranks``, a (width,
    in-degree) per rank, the sources first."""
    import jax
    import jax.numpy as jnp
    from repro.core.engine import _block_shape, _jax_sweep_fn

    shapes = [_block_shape(w, k) for w, k in ranks[1:]]
    width = ranks[0][0] + sum(nw * bw for nw, _, _, bw in shapes)
    with jax.enable_x64(True):
        return _equations(jax.make_jaxpr(_jax_sweep_fn())(
            jax.ShapeDtypeStruct((n,), jnp.float64),
            jax.ShapeDtypeStruct((c, width), jnp.float64),
            tuple(jax.ShapeDtypeStruct(s, jnp.int32) for s in shapes)))


def test_sweep_program_does_not_grow_with_fan_in():
    """The same three ranks with a join of in-degree 8, 930 (Montage's
    ``mConcatFit``), 3,000 or 6,000 (three or six slot blocks): one
    program size while each rank fits one block, and one more, by the
    loops' own few equations, once its blocks take a loop, whatever its
    trip count. A rank more adds the same equations each time."""
    sizes = {k: _sweep_equations([(k, 0), (k, 1), (1, k)])
             for k in (8, 930, 3000, 6000)}
    assert sizes[8] == sizes[930] and sizes[3000] == sizes[6000], sizes
    assert 0 < sizes[3000] - sizes[930] < sizes[930], sizes
    deeper = [_sweep_equations([(4, 0)] + [(4, 2)] * r) for r in (1, 2, 3)]
    assert deeper[2] - deeper[1] == deeper[1] - deeper[0] > 0


@pytest.mark.parametrize("plane", ["fast", "constrained"])
def test_reports_are_bit_identical_with_the_profiler_on(plane):
    template, cands, seeds = _inputs()
    off = _engine(plane).run_many(template, cands, seeds)
    on, spans = _traced(lambda: _engine(plane).run_many(template, cands,
                                                        seeds))
    assert spans
    assert len(on) == len(off)
    for a, b in zip(on, off):
        for field in ("arrivals", "finishes", "latencies", "queue_delays",
                      "cold_delays", "costs", "failed_mask"):
            assert np.array_equal(getattr(a, field), getattr(b, field))
        assert a.makespan == b.makespan and a.p99 == b.p99
        assert a.busy_by_function == b.busy_by_function
        assert a.total_cost == b.total_cost


def test_a_collection_is_a_span_while_tracing():
    import gc

    import jax
    from jax.profiler import ProfileData

    log_dir = tempfile.mkdtemp()
    try:
        jax.profiler.start_trace(log_dir)
        gc.collect(1)
        jax.profiler.stop_trace()
        path, = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
        data = ProfileData.from_file(path)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            stats = [{k: v for k, v in e.stats} for p in data.planes
                     for line in p.lines for e in line.events
                     if e.name == "py.gc"]
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    assert {"generation": 1} in stats


def test_numpy_plane_never_loads_jax():
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    code = (
        "import sys, gc\n"
        "import repro.core\n"
        "from repro.core import telemetry\n"
        "from repro.core.engine import FleetEngine, PoissonArrivals\n"
        "from repro.serverless.generator import chain_workflow\n"
        "from repro.serverless.platform import SimulatedPlatform\n"
        "env = SimulatedPlatform().environment()\n"
        "wf = chain_workflow(4, seed=1)\n"
        "engine = FleetEngine(env.backend, pricing=env.pricing)\n"
        "times = PoissonArrivals(0.5, 8, seed=0).times()\n"
        "reports = engine.run_many(wf, [{}, {}], [times])\n"
        "gc.collect()\n"
        "assert len(reports) == 2\n"
        "assert telemetry.counters()['fleet.calls.fast'] == 1\n"
        "print('jax' in sys.modules)\n")
    env = dict(os.environ, PYTHONPATH=os.path.abspath(src))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "False"
