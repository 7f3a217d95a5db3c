"""`FleetEngine.run_many` equivalence bar + SoA report memoization.

The batched replay plane (C candidate config-maps × S arrival seeds
over a shared topology) must be **bit-identical** to the looped scalar
path — ``run([template.copy() + configs, ...], times)`` per cell — on
every compared field, across topology families, finite and infinite
clusters, cold starts + keep-alive expiry, the carry/backlog path the
online challenger gate uses (input carries and ``collect_carry``
output), unbounded-failure candidates, mixed batches, and — under the
paired replay-stream contract — stochastic backends, where the
vectorized planes must match the exact event loop replaying the same
noise plan.
"""
import copy
import dataclasses
import math
import pickle
from decimal import Decimal

import numpy as np
import pytest

from repro.core import engine as engine_module
from repro.core import resources, telemetry
from repro.core.backend import BaseBackend, CallableBackend
from repro.core.cost import PricingModel
from repro.core.engine import (ClusterModel, ColdStartModel, FleetCarry,
                               FleetEngine, PoissonArrivals, _fold_repeats,
                               _fold_rows)
from repro.core.resources import (BASE_CONFIG, CPU_STEP, MEM_STEP_MB,
                                  ResourceConfig, quantize_cpu, quantize_mem)
from repro.serverless.generator import (chain_workflow, diamond_workflow,
                                        fan_workflow, layered_workflow)
from repro.serverless.platform import (AnalyticBackend, SimulatedPlatform,
                                       StochasticBackend)

TOPOLOGIES = {
    "chain": lambda: chain_workflow(5, seed=11),
    "fan": lambda: fan_workflow(4, seed=12),
    "diamond": lambda: diamond_workflow(2, seed=13),
    "layered": lambda: layered_workflow(10, n_layers=3, seed=14),
}


def make_engine(**kw):
    env = SimulatedPlatform().environment()
    return FleetEngine(env.backend, pricing=env.pricing, **kw)


def candidate_sets(template, n_cand, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n_cand):
        out.append({n.name: ResourceConfig(cpu=float(rng.uniform(1.0, 8.0)),
                                           mem=float(rng.uniform(1024.0,
                                                                 8192.0)))
                    for n in template})
    return out


def arrival_sets(n_seeds, n=6, rate=0.25, start=0.0):
    return [PoissonArrivals(rate, n, seed=s, start=start).times()
            for s in range(n_seeds)]


def scalar_cell(engine, template, configs, times, carry=None):
    wfs = []
    for _ in range(len(times)):
        wf = template.copy()
        wf.apply_configs(configs)
        wfs.append(wf)
    return engine.run(wfs, times, carry=carry)


def assert_reports_identical(got, want):
    """Every compared field exact — the acceptance-criteria bar."""
    assert np.array_equal(got.arrivals, want.arrivals)
    assert np.array_equal(got.finishes, want.finishes)
    assert np.array_equal(got.latencies, want.latencies)
    assert np.array_equal(got.queue_delays, want.queue_delays)
    assert np.array_equal(got.cold_delays, want.cold_delays)
    assert np.array_equal(got.costs, want.costs)
    assert np.array_equal(got.failed_mask, want.failed_mask)
    assert got.makespan == want.makespan
    assert got.queue_delay_by_function == want.queue_delay_by_function
    assert got.busy_by_function == want.busy_by_function
    assert got.total_cost == want.total_cost
    assert got.total_queue_delay == want.total_queue_delay
    assert got.p50 == want.p50 and got.p99 == want.p99


def assert_grid_identical(engine, template, cands, seeds, carry=None):
    reports = engine.run_many(template, cands, seeds, carry=carry)
    assert len(reports) == len(cands) * len(seeds)
    k = 0
    for configs in cands:                    # candidate-major ordering
        for times in seeds:
            assert_reports_identical(
                reports[k], scalar_cell(engine, template, configs, times,
                                        carry=carry))
            k += 1
    return reports


@pytest.mark.parametrize("kind", list(TOPOLOGIES))
def test_run_many_bit_identical_infinite_cluster(kind):
    """Vectorized plane == looped scalar run on every topology family."""
    template = TOPOLOGIES[kind]()
    engine = make_engine()
    assert_grid_identical(engine, template,
                          candidate_sets(template, 3, seed=1),
                          arrival_sets(2))


@pytest.mark.parametrize("kind", list(TOPOLOGIES))
def test_run_many_bit_identical_finite_cluster(kind):
    """Finite capacity routes onto the table-driven constrained plane,
    which must reproduce the looped run bit-for-bit (queuing
    included)."""
    template = TOPOLOGIES[kind]()
    engine = make_engine(cluster=ClusterModel(total_cpu=12.0,
                                              total_mem_mb=16384.0))
    cands = [{n.name: ResourceConfig(cpu=4.0, mem=4096.0) for n in template},
             {n.name: ResourceConfig(cpu=6.0, mem=6144.0) for n in template}]
    reports = assert_grid_identical(engine, template, cands,
                                    arrival_sets(2, rate=2.0))
    assert any(r.total_queue_delay > 0.0 for r in reports)


def test_run_many_bit_identical_with_cold_starts():
    template = TOPOLOGIES["chain"]()
    engine = make_engine(cold_start=ColdStartModel(delay_s=1.5,
                                                   keep_alive_s=60.0))
    reports = assert_grid_identical(engine, template,
                                    candidate_sets(template, 2, seed=2),
                                    arrival_sets(2))
    assert all(r.cold_delays.sum() > 0.0 for r in reports)


def test_run_many_bit_identical_from_live_backlog():
    """The online-challenger path: replay from a carried fleet state
    (warm pool + in-flight reservations of a previous epoch)."""
    template = TOPOLOGIES["layered"]()
    # epoch 0 on a tight cluster leaves work in flight at the boundary
    engine = make_engine(cluster=ClusterModel(total_cpu=14.0,
                                              total_mem_mb=20480.0),
                         cold_start=ColdStartModel(delay_s=0.5,
                                                   keep_alive_s=500.0))
    first = engine.run(
        [template.copy() for _ in range(6)],
        PoissonArrivals(1.0, 6, seed=7).times(), collect_carry=True)
    boundary = 30.0
    carry = first.carry.pruned(boundary)
    assert carry.warm                       # the backlog is real
    cands = candidate_sets(template, 2, seed=3)
    seeds = [PoissonArrivals(1.0, 6, seed=8, start=boundary).times()]
    assert_grid_identical(engine, template, cands, seeds, carry=carry)


def test_run_many_busy_carry_on_infinite_cluster_stays_exact():
    """An inert busy reservation still extends the measured makespan;
    the vectorized plane must reproduce it."""
    template = TOPOLOGIES["chain"]()
    engine = make_engine()
    carry = FleetCarry(clock=0.0, warm={},
                       busy=[(900.0, 2.0, 512.0), (0.1, 1.0, 128.0)])
    reports = assert_grid_identical(engine, template,
                                    candidate_sets(template, 2, seed=4),
                                    arrival_sets(1), carry=carry)
    assert all(r.makespan > 800.0 for r in reports)


def test_run_many_empty_candidate_and_seed_sets():
    template = TOPOLOGIES["chain"]()
    engine = make_engine()
    assert engine.run_many(template, [], arrival_sets(2)) == []
    assert engine.run_many(template, candidate_sets(template, 2), []) == []
    # an empty arrival process yields the well-defined empty report
    reports = engine.run_many(template, candidate_sets(template, 2),
                              [np.empty(0)])
    assert len(reports) == 2
    for rep in reports:
        assert len(rep) == 0 and rep.instances == []
        assert rep.p99 == 0.0 and rep.slo_attainment(1.0) == 1.0


def test_run_many_unknown_function_name_raises_keyerror():
    template = TOPOLOGIES["chain"]()
    engine = make_engine()
    bad = {"no-such-function": ResourceConfig()}
    with pytest.raises(KeyError):
        engine.run_many(template, [bad], arrival_sets(1))


def test_run_many_uses_the_vectorized_plane():
    """On an infinite cluster with a deterministic surface the C×S grid
    must be ONE invoke_config_batch call — zero invoke_batch rounds."""
    template = TOPOLOGIES["fan"]()
    env = SimulatedPlatform().environment()
    calls = {"config_batch": 0, "batch": 0}
    real_cfg = env.backend.invoke_config_batch
    env.backend.invoke_config_batch = \
        lambda *a, **k: (calls.__setitem__("config_batch",
                                           calls["config_batch"] + 1)
                         or real_cfg(*a, **k))
    env.backend.invoke_batch = \
        lambda *a, **k: pytest.fail("scalar invoke_batch on the "
                                    "vectorized plane")
    engine = FleetEngine(env.backend, pricing=env.pricing)
    reports = engine.run_many(template, candidate_sets(template, 4, seed=5),
                              arrival_sets(3))
    assert calls["config_batch"] == 1
    assert len(reports) == 12


# -- stochastic paired replay-stream contract --------------------------

class _ScalarMirrorPricing(PricingModel):
    """Overrides scalar ``function_cost`` with the *same* values but no
    matching ``cost_batch``: replays route as with any pricing model,
    and the cost table is filled entry by entry through scalar
    ``function_cost`` — without changing any number."""

    def function_cost(self, runtime_s, config):
        return super().function_cost(runtime_s, config)


def _stochastic_engine(seed, *, sigma=0.05, pricing=None, **kw):
    return FleetEngine(StochasticBackend(noise_sigma=sigma, seed=seed),
                       pricing=pricing or SimulatedPlatform().pricing, **kw)


CONSTRAINED_KW = dict(cluster=ClusterModel(total_cpu=12.0,
                                           total_mem_mb=16384.0),
                      cold_start=ColdStartModel(delay_s=1.0,
                                                keep_alive_s=30.0))


@pytest.mark.parametrize("engine_kw", [{}, CONSTRAINED_KW],
                         ids=["fast_plane", "constrained_plane"])
def test_run_many_stochastic_same_config_scores_identically(engine_kw):
    """The paired replay-stream contract: one (instance, function)
    noise tensor per plane, shared across candidates — so the same
    configuration in two candidate slots is the same experiment and
    must score bit-identically (a per-candidate stream would break
    the challenger gate's paired comparison)."""
    template = TOPOLOGIES["layered"]()
    cfg = candidate_sets(template, 1, seed=6)[0]
    reports = _stochastic_engine(123, **engine_kw).run_many(
        template, [cfg, cfg], arrival_sets(2))
    assert_reports_identical(reports[0], reports[2])
    assert_reports_identical(reports[1], reports[3])


class _PlanBackend(BaseBackend):
    """Replays a fixed ``(runtime, failed)`` per node object, so
    ``FleetEngine.run``'s event loop can be driven off a plan drawn
    elsewhere (here: one paired replay-noise draw)."""

    deterministic = True

    def __init__(self, plan):
        self._plan = plan

    def invoke_batch(self, nodes):
        rows = [self._plan[id(node)] for node in nodes]
        return (np.array([rt for rt, _ in rows], dtype=np.float64),
                np.array([bad for _, bad in rows], dtype=bool))


@pytest.mark.parametrize("engine_kw", [{}, CONSTRAINED_KW],
                         ids=["fast_plane", "constrained_plane"])
def test_run_many_stochastic_matches_planned_event_loop(engine_kw):
    """Cross-plane bit-identity under noise: the vectorized planes must
    reproduce ``FleetEngine.run``'s event loop over template copies
    replaying the same plan — the noise-free surface times ONE
    same-seed ``replay_noise`` draw (failures keep their thrash time),
    instance by instance — on every compared field, bit for bit."""
    template = TOPOLOGIES["layered"]()
    cands = candidate_sets(template, 3, seed=7)
    seeds = arrival_sets(2)
    vec = _stochastic_engine(99, **engine_kw).run_many(
        template, cands, seeds)
    backend = StochasticBackend(noise_sigma=0.05, seed=99)
    nodes = list(template)
    cpu = np.array([[c[n.name].cpu for n in nodes] for c in cands])
    mem = np.array([[c[n.name].mem for n in nodes] for c in cands])
    runtimes, failed = backend.config_surface(nodes, cpu, mem)
    noise = backend.replay_noise(sum(len(t) for t in seeds), len(nodes))
    k = 0
    for ci, configs in enumerate(cands):
        row = 0
        for times in seeds:
            plan, wfs = {}, []
            for _ in times:
                wf = template.copy()
                wf.apply_configs(configs)
                rt = np.where(failed[ci], runtimes[ci],
                              runtimes[ci] * noise[row])
                for v, node in enumerate(wf):
                    plan[id(node)] = (float(rt[v]), bool(failed[ci, v]))
                wfs.append(wf)
                row += 1
            ref = FleetEngine(_PlanBackend(plan),
                              pricing=SimulatedPlatform().pricing,
                              **engine_kw).run(wfs, times)
            assert_reports_identical(vec[k], ref)
            k += 1
    assert k == len(vec) == 6


def test_run_many_stochastic_replay_is_reproducible_and_noisy():
    template = TOPOLOGIES["chain"]()
    cands = candidate_sets(template, 2, seed=8)
    seeds = arrival_sets(2)
    a = _stochastic_engine(7).run_many(template, cands, seeds)
    b = _stochastic_engine(7).run_many(template, cands, seeds)
    for ra, rb in zip(a, b):                 # same seed => same plane
        assert_reports_identical(ra, rb)
    exact = make_engine().run_many(template, cands, seeds)
    assert any(not np.array_equal(ra.finishes, re.finishes)
               for ra, re in zip(a, exact))  # noise is actually applied
    # sigma=0 declares an exact surface: bitwise the analytic plane
    silent = _stochastic_engine(7, sigma=0.0).run_many(
        template, cands, seeds)
    for rs, re in zip(silent, exact):
        assert_reports_identical(rs, re)


def test_run_many_stochastic_consumes_one_noise_draw_per_plane():
    """The plane must advance the backend's RNG exactly once
    (replay_noise), never per cell/candidate — that is what makes
    batched replays paired AND reproducible."""
    template = TOPOLOGIES["fan"]()
    backend = StochasticBackend(noise_sigma=0.05, seed=11)
    draws = {"n": 0}
    real = backend.replay_noise

    def counting(n_instances, n_nodes):
        draws["n"] += 1
        return real(n_instances, n_nodes)

    backend.replay_noise = counting
    backend.invoke_batch = lambda *a, **k: pytest.fail(
        "per-cell invoke_batch on the batched replay plane")
    engine = FleetEngine(backend, pricing=SimulatedPlatform().pricing,
                         **CONSTRAINED_KW)
    reports = engine.run_many(template, candidate_sets(template, 3, seed=9),
                              arrival_sets(2))
    assert draws["n"] == 1
    assert len(reports) == 6


class _NoClampBackend(AnalyticBackend):
    """Deterministic surface whose failures are unbounded (+inf): a
    dead instance never runs its downstream nodes, which the fast
    plane's longest-path sweep cannot see — those candidates replay
    per-cell off the precomputed tables (the constrained plane handles
    them natively)."""

    has_clamped = False

    def _surface(self, cpu, mem, spec_arrays):
        rt, failed = super()._surface(cpu, mem, spec_arrays)
        return np.where(failed, np.inf, rt), failed


def test_run_many_serializes_unbounded_failure_candidates():
    template = TOPOLOGIES["fan"]()
    healthy = {n.name: ResourceConfig(cpu=4.0, mem=8192.0)
               for n in template}
    dying = {n.name: ResourceConfig(cpu=4.0, mem=128.0)    # below floors
             for n in template}
    engine = FleetEngine(_NoClampBackend(),
                         pricing=SimulatedPlatform().pricing)
    reports = assert_grid_identical(engine, template, [healthy, dying],
                                    arrival_sets(2))
    assert not reports[0].failed_mask.any()
    assert reports[2].failed_mask.all()
    assert math.isinf(reports[2].p99)


def test_run_many_mixed_unbounded_failures_on_finite_cluster():
    """The production-shaped mixed batch: finite CPU+mem cluster, cold
    starts, one healthy and one unbounded-failure candidate — the
    constrained plane replays dead instances natively (slot release +
    same-instant re-admission round) and must stay bit-identical."""
    template = TOPOLOGIES["fan"]()
    healthy = {n.name: ResourceConfig(cpu=4.0, mem=8192.0)
               for n in template}
    dying = {n.name: ResourceConfig(cpu=4.0, mem=128.0)
             for n in template}
    engine = FleetEngine(_NoClampBackend(),
                         pricing=SimulatedPlatform().pricing,
                         cluster=ClusterModel(total_cpu=10.0,
                                              total_mem_mb=20480.0),
                         cold_start=ColdStartModel(delay_s=0.5,
                                                   keep_alive_s=20.0))
    reports = assert_grid_identical(engine, template, [healthy, dying],
                                    arrival_sets(2, rate=2.0))
    assert not reports[0].failed_mask.any()
    assert reports[2].failed_mask.all()


def test_opaque_callable_backend_falls_back_and_matches():
    """Backends without a config-batch surface (bare oracles) keep the
    exact looped semantics."""
    template = TOPOLOGIES["chain"]()
    engine = FleetEngine(CallableBackend(lambda node: node.config.cpu * 0.1),
                         pricing=SimulatedPlatform().pricing)
    assert_grid_identical(engine, template,
                          candidate_sets(template, 2, seed=8),
                          arrival_sets(2))


@pytest.mark.parametrize("carry", [
    None, FleetCarry(clock=0.0, warm={}, busy=[(900.0, 2.0, 512.0)])],
    ids=["no_carry", "live_reservation"])
def test_run_many_single_instance_cell_matches_degenerate_path(carry):
    """A fleet of one with no carry goes through ``run``'s degenerate
    fast path, whose float associations differ from the absolute-time
    plane — run_many builds that cell's report through the same
    degenerate path to stay bit-identical; with a carry, ``run`` takes
    its event loop and so does the cell. Healthy candidates and one
    with an unbounded failure, on a template whose insertion order
    differs from topological order so any accumulation-order
    divergence would surface."""
    from repro.core.dag import Workflow
    from repro.serverless.generator import random_spec

    rng = np.random.default_rng(5)
    template = Workflow("scrambled")
    for name in ("f2", "f0", "f1"):          # non-topological insertion
        template.add_function(name, payload=random_spec(name, rng))
    template.add_edge("f0", "f1")
    template.add_edge("f1", "f2")
    engine = FleetEngine(_NoClampBackend(),
                         pricing=SimulatedPlatform().pricing)
    healthy = {n.name: ResourceConfig(cpu=4.0, mem=8192.0) for n in template}
    dying = {n.name: ResourceConfig(cpu=4.0, mem=128.0) for n in template}
    cands = [healthy, *candidate_sets(template, 2, seed=10), dying]
    # nonzero arrival: the degenerate path computes e2e relative and
    # shifts by the arrival, unlike the absolute event-time chain
    reports = assert_grid_identical(engine, template, cands,
                                    [np.array([13.7])], carry=carry)
    assert not reports[0].failed_mask.any()
    assert reports[-1].failed_mask.all() and math.isinf(reports[-1].p99)
    # the live reservation releases inside the run and ends it
    assert all((r.makespan > 800.0) == (carry is not None) for r in reports)


def test_custom_pricing_overrides_are_honored():
    """A pricing model that customizes only scalar function_cost must
    not be silently priced with the base mu-formula (neither by the
    admission rounds nor by the run_many plane)."""
    from repro.core.cost import PricingModel

    class DoubledPricing(PricingModel):
        def function_cost(self, runtime_s, config):
            return 2.0 * super().function_cost(runtime_s, config)

    template = TOPOLOGIES["chain"]()
    env = SimulatedPlatform().environment()
    base = FleetEngine(env.backend)
    doubled = FleetEngine(env.backend, pricing=DoubledPricing())
    assert not doubled._pricing_vectorized     # falls back to scalar
    cands = candidate_sets(template, 1, seed=11)
    times = arrival_sets(1)[0]
    got = doubled.run_many(template, cands, [times])[0]
    ref = base.run_many(template, cands, [times])[0]
    assert got.total_cost == pytest.approx(2.0 * ref.total_cost)
    # a custom *vectorized* implementation is trusted as-is
    class VectorizedDoubled(DoubledPricing):
        def cost_batch(self, runtime_s, cpu, mem):
            return 2.0 * super().cost_batch(runtime_s, cpu, mem)

    vec = FleetEngine(env.backend, pricing=VectorizedDoubled())
    assert vec._pricing_vectorized
    got_vec = vec.run_many(template, cands, [times])[0]
    assert got_vec.total_cost == pytest.approx(got.total_cost)
    # scalar pricing fills the cost table entry by entry, on the fast
    # and the constrained plane alike: bit for bit the serial reference
    for pricing in (_ScalarMirrorPricing(), DoubledPricing()):
        for engine_kw in ({}, CONSTRAINED_KW):
            engine = FleetEngine(env.backend, pricing=pricing, **engine_kw)
            assert_grid_identical(engine, template,
                                  candidate_sets(template, 2, seed=11),
                                  arrival_sets(2))


def test_online_stochastic_validation_stays_paired():
    """On a stochastic backend the challenger gate must remain a
    *paired* comparison: every candidate validated under identical
    noise draws. The same configuration in both slots must therefore
    score identically (a shared noise stream would break this)."""
    from repro.core.campaign import PortfolioSpec, ReplaySpec
    from repro.core.online import OnlineController, OnlineSpec
    from repro.serverless.generator import EpochConditions
    from repro.serverless.platform import make_env

    spec = OnlineSpec(
        portfolio=PortfolioSpec(n_workflows=1, size=4, slo_slacks=(2.0,)),
        replay=ReplaySpec(n_instances=6, rate=0.5), n_epochs=1)
    ctl = OnlineController(
        spec, env_factory=lambda: make_env(noise_sigma=0.05, seed=17))
    tasks = ctl._campaign.tasks()
    cells = ctl._deploy(tasks, ctl._campaign.arrival_seeds(len(tasks)))
    cond = EpochConditions()
    cfg = cells[0].configs
    a, b = ctl._validate_many(cells[0], [cfg, cfg], cond, seed=3)
    assert a == b


def test_run_many_cold_start_keep_alive_expiry_bit_identical():
    """Warm containers must expire mid-replay: a keep-alive shorter
    than the arrival gaps makes later instances pay the cold delay
    again, and the table-driven plane must mirror the scalar pool
    bookkeeping exactly."""
    template = TOPOLOGIES["chain"]()
    engine = make_engine(cold_start=ColdStartModel(delay_s=2.0,
                                                   keep_alive_s=0.75))
    reports = assert_grid_identical(engine, template,
                                    candidate_sets(template, 2, seed=12),
                                    arrival_sets(2, rate=0.05))
    # sparse arrivals + fast expiry: every instance provisions cold
    assert all((r.cold_delays >= 2.0).all() for r in reports)


def test_run_many_collect_carry_matches_scalar():
    """``collect_carry=True`` routes onto the constrained plane; each
    cell's report AND emitted carry (clock, warm pool, reservation log)
    must equal the scalar run's exactly."""
    template = TOPOLOGIES["layered"]()
    engine = make_engine(cluster=ClusterModel(total_cpu=14.0,
                                              total_mem_mb=20480.0),
                         cold_start=ColdStartModel(delay_s=0.5,
                                                   keep_alive_s=120.0))
    cands = candidate_sets(template, 2, seed=13)
    seeds = arrival_sets(2, rate=1.0)
    reports = engine.run_many(template, cands, seeds, collect_carry=True)
    k = 0
    for configs in cands:
        for times in seeds:
            wfs = []
            for _ in range(len(times)):
                wf = template.copy()
                wf.apply_configs(configs)
                wfs.append(wf)
            want = engine.run(wfs, times, collect_carry=True)
            assert_reports_identical(reports[k], want)
            assert reports[k].carry == want.carry
            assert reports[k].carry.busy       # the backlog is real
            k += 1


@pytest.mark.parametrize("vectorized", [True, False],
                         ids=["cost_batch", "scalar_pricing"])
def test_run_many_one_surface_one_pricing_call_on_constrained_plane(
        vectorized):
    """The constrained plane's whole C×S grid must cost ONE
    ``invoke_config_batch`` and ONE ``cost_batch`` — the per-cell event
    loops run off the precomputed tables with zero backend/pricing
    dispatch. A pricing model that does not vectorize fills the same
    table by one scalar ``function_cost`` per (candidate, function)."""
    calls = {"cost": 0, "scalar": 0}

    class CountingPricing(PricingModel):
        def cost_batch(self, runtime_s, cpu, mem):
            calls["cost"] += 1
            return super().cost_batch(runtime_s, cpu, mem)

    class CountingScalarPricing(PricingModel):
        def function_cost(self, runtime_s, config):
            calls["scalar"] += 1
            return super().function_cost(runtime_s, config)

    template = TOPOLOGIES["layered"]()
    env = SimulatedPlatform().environment()
    surface = {"n": 0}
    real_cfg = env.backend.invoke_config_batch
    env.backend.invoke_config_batch = \
        lambda *a, **k: (surface.__setitem__("n", surface["n"] + 1)
                         or real_cfg(*a, **k))
    env.backend.invoke_batch = \
        lambda *a, **k: pytest.fail("scalar invoke_batch on the "
                                    "constrained plane")
    pricing = CountingPricing() if vectorized else CountingScalarPricing()
    engine = FleetEngine(env.backend, pricing=pricing,
                         cluster=ClusterModel(total_cpu=14.0,
                                              total_mem_mb=20480.0),
                         cold_start=ColdStartModel(delay_s=0.5,
                                                   keep_alive_s=60.0))
    assert engine.batch_eligibility(template, [])["plane"] == "constrained"
    reports = engine.run_many(template, candidate_sets(template, 4, seed=14),
                              arrival_sets(3, rate=1.0))
    assert surface["n"] == 1
    assert calls == ({"cost": 1, "scalar": 0} if vectorized
                     else {"cost": 0, "scalar": 4 * len(template)})
    assert len(reports) == 12
    assert any(r.total_queue_delay > 0.0 for r in reports)


# -- batch_eligibility diagnostic --------------------------------------

def test_batch_eligibility_reports_plane_routing():
    template = TOPOLOGIES["chain"]()

    fast = make_engine().batch_eligibility(template, [])
    assert fast == {"plane": "fast", "vectorized": True, "reasons": [],
                    "serial_candidates": None}

    constrained = make_engine(**CONSTRAINED_KW).batch_eligibility(
        template, [])
    assert constrained["plane"] == "constrained"
    assert constrained["vectorized"]
    joined = " ".join(constrained["reasons"])
    assert "finite cluster" in joined and "cold starts" in joined

    carry_plane = make_engine().batch_eligibility(template, [],
                                                  collect_carry=True)
    assert carry_plane["plane"] == "constrained"
    assert any("collect_carry" in r for r in carry_plane["reasons"])

    # the pricing model never routes a replay
    env = SimulatedPlatform().environment()
    scalar = FleetEngine(env.backend,
                         pricing=_ScalarMirrorPricing()).batch_eligibility(
        template, [])
    assert scalar == {"plane": "fast", "vectorized": True, "reasons": [],
                      "serial_candidates": None}

    opaque = FleetEngine(CallableBackend(lambda node: 0.1),
                         pricing=env.pricing).batch_eligibility(template, [])
    assert opaque["plane"] == "serial"
    assert not opaque["vectorized"]
    assert any("batch_safe" in r for r in opaque["reasons"])

    from repro.core.dag import Workflow
    empty = make_engine().batch_eligibility(Workflow("empty"), [])
    assert empty["plane"] == "serial"
    assert any("empty template" in r for r in empty["reasons"])

    # a batch_safe stochastic backend rides the plane
    stoch = _stochastic_engine(0, **CONSTRAINED_KW).batch_eligibility(
        template, [])
    assert stoch["plane"] == "constrained" and stoch["vectorized"]


def test_batch_eligibility_probes_unbounded_failure_candidates():
    template = TOPOLOGIES["fan"]()
    healthy = {n.name: ResourceConfig(cpu=4.0, mem=8192.0)
               for n in template}
    dying = {n.name: ResourceConfig(cpu=4.0, mem=128.0)
             for n in template}
    engine = FleetEngine(_NoClampBackend(),
                         pricing=SimulatedPlatform().pricing)
    elig = engine.batch_eligibility(template, [healthy, dying],
                                    probe_candidates=True)
    assert elig["plane"] == "fast"
    assert elig["serial_candidates"] == [1]
    assert any("unbounded" in r for r in elig["reasons"])
    # without probing, no backend call is made and no verdict is given
    assert engine.batch_eligibility(template, [healthy, dying])[
        "serial_candidates"] is None


def test_campaign_logs_batched_replay_fallback(caplog):
    """Silent serialization must be visible: Campaign.replay_configs_many
    logs the eligibility verdict once per distinct cause."""
    import logging

    from repro.core.campaign import Campaign
    from repro.core.env import Environment

    campaign = Campaign()
    task = campaign.tasks()[0]
    configs = {name: ResourceConfig() for name in task.template.nodes}
    env = Environment(CallableBackend(lambda node: 0.1))
    with caplog.at_level(logging.INFO, logger="repro.core.campaign"):
        campaign.replay_configs_many(task, [configs], 3, env=env,
                                     n_instances=2)
        campaign.replay_configs_many(task, [configs], 4, env=env,
                                     n_instances=2)
    hits = [r for r in caplog.records if "serial plane" in r.message]
    assert len(hits) == 1                     # logged once per cause
    assert "batch_safe" in hits[0].message
    caplog.clear()
    with caplog.at_level(logging.INFO, logger="repro.core.campaign"):
        campaign.replay_configs_many(task, [configs], 5, n_instances=2)
    assert not [r for r in caplog.records if "plane" in r.message]


# -- pricing re-detection (per-pricing-object cache) -------------------

def test_pricing_vectorization_redetects_after_swap_and_mutation():
    env = SimulatedPlatform().environment()
    engine = FleetEngine(env.backend)
    assert engine._pricing_vectorized

    class Mutant(PricingModel):
        pass

    # swapping the pricing object on a cached engine re-detects
    engine.pricing = Mutant()
    assert engine._pricing_vectorized          # nothing overridden yet
    # mutating the *class* after the verdict was cached re-detects too
    Mutant.function_cost = lambda self, runtime_s, config: 0.0
    assert not engine._pricing_vectorized
    del Mutant.function_cost
    assert engine._pricing_vectorized

    # and the verdict is honored end to end: the zero-cost mutant
    # prices every replay at exactly zero through the cost table
    Mutant.function_cost = lambda self, runtime_s, config: 0.0
    template = TOPOLOGIES["chain"]()
    report = engine.run_many(template, candidate_sets(template, 1, seed=15),
                             arrival_sets(1))[0]
    assert report.total_cost == 0.0


# -- the jitted fleet step ----------------------------------------------

def test_jax_plane_backend_matches_numpy_bitwise():
    pytest.importorskip("jax")
    template = TOPOLOGIES["layered"]()
    cands = candidate_sets(template, 3, seed=16)
    seeds = arrival_sets(2)
    carry = FleetCarry(clock=0.0, warm={}, busy=[(700.0, 2.0, 512.0)])
    numpy_reports = make_engine().run_many(template, cands, seeds,
                                           carry=carry)
    jax_engine = make_engine(plane_backend="jax")
    jax_reports = jax_engine.run_many(template, cands, seeds, carry=carry)
    for got, want in zip(jax_reports, numpy_reports):
        assert_reports_identical(got, want)
    # the sweep really ran as a jax program, on JAX's default device
    import jax
    assert jax_engine.sweep_device.platform == jax.default_backend()


def test_jax_sweep_follows_a_structural_edit_of_the_template():
    """The jitted sweep keeps its rank tables between calls; an edge
    added to the template deepens it, and the next call sweeps the new
    structure, still bit-identical to the numpy plane."""
    pytest.importorskip("jax")
    template = TOPOLOGIES["fan"]()
    cands = candidate_sets(template, 2, seed=19)
    seeds = arrival_sets(1)
    jax_engine = make_engine(plane_backend="jax")
    before = jax_engine.run_many(template, cands, seeds)
    # one branch of the fan now waits for another
    source, = [n for n in template.nodes if not template.predecessors(n)]
    first, second = template.successors(source)[:2]
    template.add_edge(first, second)
    jax_reports = jax_engine.run_many(template, cands, seeds)
    assert any(a.finishes.tobytes() != b.finishes.tobytes()
               for a, b in zip(jax_reports, before))
    for got, want in zip(jax_reports,
                         make_engine().run_many(template, cands, seeds)):
        assert_reports_identical(got, want)


def mixed_fan_in(k: int, heavy: str):
    """Sources, then one rank of four functions with in-degrees 1, 2,
    10 and ``k``, then a join of the four and a chain after it. Each of
    the four reads the sources just before the last; the ``k``-wide one
    reads the last too, as its last slot. One source does a hundred
    times the others' work: with ``heavy="last"`` the last, so the
    ``k``-wide function's last slot (in its last slot block) decides
    every finish; with ``heavy="first"`` the first, which nothing reads,
    so a padding slot that read its column and not the -inf one would
    move a narrow function past it."""
    from repro.core.dag import Workflow
    from repro.serverless.function import FunctionSpec

    template = Workflow(f"fan_in_{k}_{heavy}")

    def add(name, i, work=5.0):
        template.add_function(name, payload=FunctionSpec(
            name=name, cpu_work=work + (i * 37) % 23,
            parallel_frac=0.5 + (i % 7) * 0.05, mem_floor=256.0,
            mem_knee=1024.0, mem_penalty=1.5, io_time=0.5 + (i % 5) * 0.25))

    sources = [f"s{i:04d}" for i in range(max(k, 12) + 1)]
    big = sources[-1] if heavy == "last" else sources[0]
    for i, name in enumerate(sources):
        add(name, i, 500.0 if name == big else 5.0)
    mids = []
    for j, degree in enumerate((1, 2, 10, k)):
        add(f"m{j}", j)
        mids.append(f"m{j}")
        reads = sources[-k:] if j == 3 else sources[-degree - 1:-1]
        for name in reads:
            template.add_edge(name, f"m{j}")
    add("join", 1)
    for name in mids:
        template.add_edge(name, "join")
    add("tail", 2)
    template.add_edge("join", "tail")
    return template


@pytest.mark.parametrize("heavy", ["last", "first"])
@pytest.mark.parametrize("k", [1, 2, 10, 300, 1100])
def test_jax_sweep_matches_numpy_and_scalar_at_any_fan_in(k, heavy):
    """Ranks of mixed in-degree (1, 2, 10 and ``k`` side by side, so
    the narrow rows are padded to ``k`` slots, and at 1,100 cut into two
    slot blocks): the jitted sweep's reports are the numpy plane's and
    the scalar loop's, bit for bit."""
    pytest.importorskip("jax")
    template = mixed_fan_in(k, heavy)
    assert max(len(template.predecessors(n)) for n in template.nodes) \
        == max(k, 10)
    cands = candidate_sets(template, 2, seed=23)
    seeds = arrival_sets(2, n=4)
    jax_reports = make_engine(plane_backend="jax").run_many(template, cands,
                                                            seeds)
    numpy_reports = make_engine().run_many(template, cands, seeds)
    engine = make_engine()
    k_cell = 0
    for configs in cands:
        for times in seeds:
            want = scalar_cell(engine, template, configs, times)
            assert_reports_identical(jax_reports[k_cell], want)
            assert_reports_identical(numpy_reports[k_cell], want)
            k_cell += 1


#: values whose running sums round half to even, reach the subnormals,
#: stay huge, or start from -0.0 (the scalar loop starts from +0.0)
FOLD_VALUES = [0.1, 3.0000000000000004, 2.0 ** -1074, 1e300, -0.0]


@pytest.mark.parametrize("m", [2, 3, 4096, 100_000])
@pytest.mark.parametrize("branch", ["repeat", "ordered"])
def test_busy_ledger_fold_matches_the_python_loop_bit_for_bit(branch, m):
    """The fast plane's busy-ledger fold against the scalar event loop's
    ``acc += x``: one value repeated m times (noise off), or per-instance
    values admitted in start order, stable on ties (noise on)."""
    rng = np.random.default_rng(m)
    if branch == "repeat":
        rows = np.broadcast_to(np.array(FOLD_VALUES)[:, None],
                               (len(FOLD_VALUES), m))
        admitted = [[x] * m for x in FOLD_VALUES]
    else:
        # few distinct start times, shuffled: most instances tie
        starts = rng.integers(0, max(m // 8, 2), size=m).astype(float)
        vals = np.array([np.where(rng.random(m) < 0.5, x,
                                  rng.uniform(0.0, 10.0, m))
                         for x in FOLD_VALUES])
        rows = vals[:, np.argsort(starts, kind="stable")]
        order = sorted(range(m), key=starts.__getitem__)  # stable
        admitted = [[float(row[i]) for i in order] for row in vals]
    want = []
    for xs in admitted:
        acc = 0.0
        for x in xs:
            acc += x
        want.append(acc)
    got = _fold_rows(rows)
    assert all(type(x) is float for x in got)
    assert [x.hex() for x in got] == [x.hex() for x in want]


def _loop_sum(x: float, m: int) -> float:
    acc = 0.0
    for _ in range(m):
        acc += x
    return acc


@pytest.mark.parametrize("block", [1, 2, 7])
def test_distinct_runtime_fold_matches_the_python_loop_bit_for_bit(block):
    """Each distinct value folded once and read at every fleet size,
    against ``acc += x`` and ``_fold_rows`` of the repeated row, in
    blocks that split the values unevenly."""
    values = np.array(FOLD_VALUES + [0.0, math.inf, 1.7e308, 2.5])
    sizes = [2, 3, 4096]
    with np.errstate(over="ignore"):         # 1.7e308 reaches inf
        got = _fold_repeats(values, sizes, block)
        assert got.shape == (values.size, len(sizes))
        for j, m in enumerate(sizes):
            rows = np.broadcast_to(values[:, None], (values.size, m))
            want = [_loop_sum(float(x), m) for x in values]
            assert [x.hex() for x in got[:, j].tolist()] \
                == [x.hex() for x in want] \
                == [x.hex() for x in _fold_rows(rows)]


class _TableBackend(AnalyticBackend):
    """A deterministic surface whose runtime is ``table[k]`` for a
    function given k lattice steps of vCPU: a test chooses each cell's
    runtimes, shared or distinct, down to the bit."""

    def __init__(self, table):
        super().__init__()
        self.table = np.asarray(table, dtype=np.float64)

    def _surface(self, cpu, mem, spec_arrays):
        k = np.rint(np.asarray(cpu) / CPU_STEP).astype(int)
        return self.table[k], np.zeros(k.shape, dtype=bool)


class _AccumulateSpy:
    """numpy, recording the shape of every ``np.add.accumulate``."""

    def __init__(self):
        shapes = self.shapes = []

        class _Add:
            def __getattr__(self, name):
                return getattr(np.add, name)

            def __call__(self, *args, **kw):
                return np.add(*args, **kw)

            def accumulate(self, array, *args, **kw):
                shapes.append(np.shape(array))
                return np.add.accumulate(array, *args, **kw)

        self.add = _Add()

    def __getattr__(self, name):
        return getattr(np, name)


#: runtime table rows by case: index k is the runtime at k vCPU steps
LEDGER_TABLES = {
    # four values shared across candidates and functions
    "shared": [0.1, 3.0000000000000004, 7.25, 0.1 + 0.2],
    # signed zeros, the least subnormal, a huge value
    "edge_values": [-0.0, 0.0, 2.0 ** -1074, 1e300, 0.1, 3.0],
    # every (candidate, function) its own runtime: C blocks of V
    "all_distinct": [1.0 + 0.37 * k for k in range(20)],
}


@pytest.mark.parametrize("case", list(LEDGER_TABLES))
def test_noise_off_ledger_folds_each_distinct_runtime_once(case,
                                                           monkeypatch):
    """The noise-off busy ledger of one call with arrival sets of
    different sizes: each function's busy time is its runtime added m
    times from 0.0 (``acc += x`` and ``_fold_rows`` of the repeated row),
    bit for bit; the cells of up to 3 instances equal the scalar loop
    outright; each distinct runtime is folded once, with no temporary
    beyond one cell's (V, max m) row block."""
    template = TOPOLOGIES["chain"]()
    names = list(template.nodes)
    n_cand, n_fn = 4, len(names)
    table = LEDGER_TABLES[case]
    rng = np.random.default_rng(31)
    if case == "all_distinct":
        picks = np.arange(n_cand * n_fn).reshape(n_cand, n_fn)
    else:
        picks = rng.integers(0, len(table), size=(n_cand, n_fn))
    # lattice steps 1..len(table): k vCPU steps give table[k - 1]
    backend = _TableBackend([math.nan] + list(table))
    cands = [{name: ResourceConfig(cpu=float(picks[c, v] + 1) * CPU_STEP,
                                   mem=2048.0)
              for v, name in enumerate(names)} for c in range(n_cand)]
    sizes = [3, 4096, 2]
    seeds = [PoissonArrivals(0.25, m, seed=s).times()
             for s, m in enumerate(sizes)]
    engine = FleetEngine(backend, pricing=SimulatedPlatform().pricing)
    spy = _AccumulateSpy()
    monkeypatch.setattr(engine_module, "np", spy)
    before = telemetry.counters()
    reports = engine.run_many(template, cands, seeds)
    after = telemetry.counters()
    monkeypatch.undo()
    delta = {k: v - before.get(k, 0) for k, v in after.items()}
    runtime = np.array(table)[picks]
    distinct = len({x.hex() for x in runtime.ravel().tolist()})
    if case == "all_distinct":
        assert distinct == n_cand * n_fn
    assert delta["fleet.ledger.rows.folded"] == distinct
    assert delta["fleet.ledger.rows.repeat"] == \
        n_cand * len(sizes) * n_fn
    # the fold's blocks: ceil(distinct / V), none beyond (V, max m)
    assert sum(shape[1:] == (max(sizes),) for shape in spy.shapes) \
        == -(-distinct // n_fn)
    assert all(shape[0] <= n_fn and shape[1] <= max(sizes)
               for shape in spy.shapes)
    k = 0
    for c, configs in enumerate(cands):
        for times, m in zip(seeds, sizes):
            keys = [f"{template.identity}/{name}" for name in names]
            rows = np.broadcast_to(runtime[c][:, None], (n_fn, m))
            got = {key: x.hex()
                   for key, x in reports[k].busy_by_function.items()}
            assert got == {key: _loop_sum(float(x), m).hex()
                           for key, x in zip(keys, runtime[c])}
            assert got == {key: x.hex()
                           for key, x in zip(keys, _fold_rows(rows))}
            if m <= 3:
                assert_reports_identical(
                    reports[k], scalar_cell(engine, template, configs,
                                            times))
            k += 1


def test_candidate_arrays_quantize_as_each_config_copy_does():
    """The (C, V) config arrays against ``ResourceConfig.copy`` per
    cell, bit for bit: configs moved off the lattice after they were
    made, exact halves of a step (rounded to even), values beyond
    either end, and functions a candidate leaves at the template's."""
    template = TOPOLOGIES["layered"]()
    names = list(template.nodes)
    rng = np.random.default_rng(23)
    config_sets = []
    for c in range(6):
        configs = {}
        for name in names[c % 2::2]:
            cfg = ResourceConfig(cpu=1.0, mem=1024.0)
            k = rng.integers(-3, 110)
            cfg.cpu = float(rng.choice([(k + 0.5) * 0.1, k * 0.1 + 0.03,
                                        rng.uniform(-1.0, 12.0)]))
            cfg.mem = float(rng.choice([(k + 0.5) * 64.0,
                                        rng.uniform(0.0, 11000.0)]))
            configs[name] = cfg
        config_sets.append(configs)
    engine = make_engine()
    nodes, _, cpu, mem = engine._candidate_arrays(template, config_sets)
    for ci, configs in enumerate(config_sets):
        for vi, node in enumerate(nodes):
            want = configs.get(node.name, node.config).copy()
            assert cpu[ci, vi].hex() == float(want.cpu).hex()
            assert mem[ci, vi].hex() == float(want.mem).hex()


_CPU_LATTICE = [k * CPU_STEP for k in range(1, 101)]
_MEM_LATTICE = [k * MEM_STEP_MB for k in range(2, 161)]
#: (vCPU values, MB values) per case
_QUANTIZE_GRID = {
    # built the way the benchmark's generator builds them
    "lattice": (_CPU_LATTICE, _MEM_LATTICE),
    "half_steps": ([(k + 0.5) * CPU_STEP for k in range(0, 101)],
                   [(k + 0.5) * MEM_STEP_MB for k in range(1, 161)]),
    "off_lattice": ([k * CPU_STEP + 0.03 for k in range(1, 100)]
                    + np.random.default_rng(31).uniform(0.1, 10.0,
                                                        64).tolist(),
                    [k * MEM_STEP_MB + 7.5 for k in range(2, 160)]
                    + np.random.default_rng(32).uniform(128.0, 10240.0,
                                                        64).tolist()),
    "beyond_ends": ([-5.0, 0.0, 0.04, 0.0999, 10.04, 10.06, 1e300],
                    [-64.0, 0.0, 95.9, 127.9, 10271.9, 10272.1, 1e300]),
    "infinities": ([math.inf, -math.inf], [math.inf, -math.inf]),
    # -0.0 == 0.0 shares a key with 0.0: both clamp to the floor
    "negative_zero": ([-0.0, 0.0, -0.0], [-0.0, 0.0, -0.0]),
    "ints": ([-3, 0, 1, 3, 7, 10, 11, 2**70],
             [-1, 0, 100, 128, 1000, 1024, 10240, 20000, 2**70]),
    "bools": ([True, False], [True, False]),
    "np_float64": ([np.float64(x) for x in (-1.0, 0.05, 0.25, 2.0, 5.6,
                                            9.95, 12.0, math.inf)],
                   [np.float64(x) for x in (-1.0, 96.0, 160.0, 3072.0,
                                            3103.9, 10400.0, math.inf)]),
}


@pytest.mark.parametrize("case", list(_QUANTIZE_GRID))
def test_resource_config_holds_what_quantize_returns(case, fresh_memo):
    """Every value is ``quantize_cpu`` / ``quantize_mem`` of what the
    caller gave, bit for bit and of the same type: when first seen (a
    memo miss) and when seen again (a hit). Only floats are kept."""
    cpus, mems = _QUANTIZE_GRID[case]
    pairs = [(c, mems[i % len(mems)]) for i, c in enumerate(cpus)] + \
        [(cpus[i % len(cpus)], m) for i, m in enumerate(mems)]
    for _ in range(2):
        for c, m in pairs:
            cfg = ResourceConfig(c, m)
            for got, want in ((cfg.cpu, quantize_cpu(c)),
                              (cfg.mem, quantize_mem(m))):
                assert type(got) is type(want)
                assert float(got).hex() == float(want).hex()
    floats = case not in ("ints", "bools", "np_float64")
    assert bool(resources._CPU_MEMO) == floats
    assert bool(resources._MEM_MEMO) == floats


@pytest.mark.parametrize("resource", ["cpu", "mem"])
def test_resource_config_nan_raises_and_is_not_kept(resource, fresh_memo):
    # the other resource an int, which no memo keeps
    args = {"cpu": 2, "mem": 1024, resource: math.nan}
    before = telemetry.counters().get("resources.quantize.misses", 0)
    for _ in range(2):
        with pytest.raises(ValueError):
            ResourceConfig(**args)
    assert resources._CPU_MEMO == {} and resources._MEM_MEMO == {}
    # a value that raises is looked up, and missed, each time
    assert telemetry.counters()["resources.quantize.misses"] == before + 2



@pytest.mark.parametrize("resource", ["cpu", "mem"])
def test_resource_config_decimal_behaves_as_quantize_does(resource,
                                                          fresh_memo):
    """A ``Decimal`` in range raises, as ``quantize_*`` does; one
    clamped to a float bound is that bound. No memo keeps either."""
    quantize = {"cpu": quantize_cpu, "mem": quantize_mem}[resource]
    before = telemetry.counters().get("resources.quantize.misses", 0)
    for x in ("-1", "2", "2048", "1e9"):
        args = {"cpu": 2, "mem": 1024, resource: Decimal(x)}
        try:
            want = quantize(Decimal(x))
        except TypeError:
            with pytest.raises(TypeError):
                ResourceConfig(**args)
        else:
            got = getattr(ResourceConfig(**args), resource)
            assert type(got) is type(want) and got.hex() == want.hex()
    assert resources._CPU_MEMO == {} and resources._MEM_MEMO == {}
    assert telemetry.counters().get("resources.quantize.misses", 0) == before


def test_resource_config_type_surface():
    """A slotted dataclass: the generated comparison, repr, fields,
    asdict, replace and match args; copies and pickles; no
    ``__dict__``; built by position or keyword."""
    cfg = ResourceConfig(2.0, 1024.0)
    assert cfg == ResourceConfig(cpu=2.0, mem=1024.0) == \
        ResourceConfig(2.04, 1040.0)
    assert cfg != ResourceConfig(2.1, 1024.0)
    assert repr(cfg) == "ResourceConfig(cpu=2.0, mem=1024.0)"
    assert str(cfg) == "(2.0 vCPU, 1024 MB)"
    assert ResourceConfig() == BASE_CONFIG == ResourceConfig(10.0, 10240.0)
    assert [f.name for f in dataclasses.fields(cfg)] == ["cpu", "mem"]
    assert dataclasses.asdict(cfg) == {"cpu": 2.0, "mem": 1024.0}
    moved = dataclasses.replace(cfg, cpu=3.33)
    assert moved.cpu == quantize_cpu(3.33) and moved.mem == 1024.0
    assert ResourceConfig.__match_args__ == ("cpu", "mem")
    match cfg:
        case ResourceConfig(c, m):
            assert (c, m) == (2.0, 1024.0)
    assert not hasattr(cfg, "__dict__")
    with pytest.raises(AttributeError):
        cfg.gpu = 1
    for twin in (copy.copy(cfg), copy.deepcopy(cfg),
                 pickle.loads(pickle.dumps(cfg)), cfg.copy()):
        assert twin == cfg and twin is not cfg
    assert cfg.__hash__ is None                 # mutable, as before


def test_resource_config_attributes_set_later_are_not_quantized():
    cfg = ResourceConfig(2.0, 1024.0)
    cfg.cpu, cfg.mem = 2.03, 1000.5
    assert (cfg.cpu, cfg.mem) == (2.03, 1000.5)
    assert pickle.loads(pickle.dumps(cfg)).as_tuple() == (2.03, 1000.5)
    assert copy.deepcopy(cfg).as_tuple() == (2.03, 1000.5)
    # a copy is built anew, so it is quantized
    assert cfg.copy().as_tuple() == (quantize_cpu(2.03), quantize_mem(1000.5))


def test_quantize_memo_never_grows_past_its_bound(fresh_memo):
    """Fed more distinct floats than it holds, each memo stops at its
    bound; the values past it are still right, and each of their
    lookups is a miss."""
    n = resources._MEMO_ENTRIES + 300
    cpus = np.linspace(0.0, 11.0, n).tolist()
    mems = np.linspace(0.0, 11000.0, n).tolist()

    def counted():
        return telemetry.counters()["resources.quantize.misses"]

    for _ in range(2):
        for c, m in zip(cpus, mems):
            cfg = ResourceConfig(c, m)
            assert cfg.cpu.hex() == quantize_cpu(c).hex()
            assert cfg.mem.hex() == quantize_mem(m).hex()
        assert len(resources._CPU_MEMO) == resources._MEMO_ENTRIES
        assert len(resources._MEM_MEMO) == resources._MEMO_ENTRIES
    before = counted()
    ResourceConfig(cpus[0], mems[0])            # stored
    assert counted() == before
    ResourceConfig(cpus[-1], mems[-1])          # past the bound
    assert counted() == before + 2
    assert cpus[-1] not in resources._CPU_MEMO


def test_jax_plane_names_numpy_sweep_under_replay_noise():
    """Replay noise keeps the fast plane's sweep in numpy even where the
    jax sweep was asked for; the diagnostic names it, and the plane
    records no device sweep."""
    template = TOPOLOGIES["chain"]()
    engine = _stochastic_engine(0, plane_backend="jax")
    elig = engine.batch_eligibility(template, [])
    assert elig["plane"] == "fast" and elig["vectorized"]
    assert any("numpy" in r for r in elig["reasons"])
    engine.run_many(template, candidate_sets(template, 2, seed=17),
                    arrival_sets(2))
    assert engine.sweep_device is None
    assert _stochastic_engine(0).batch_eligibility(template, [])[
        "reasons"] == []


def test_unknown_plane_backend_rejected():
    env = SimulatedPlatform().environment()
    with pytest.raises(ValueError, match="plane_backend"):
        FleetEngine(env.backend, plane_backend="tpu")


# -- SoA report memoization (accessor-waste satellite) -----------------

def test_report_accessors_are_memoized():
    template = TOPOLOGIES["chain"]()
    engine = make_engine(cluster=ClusterModel(total_cpu=12.0,
                                              total_mem_mb=16384.0))
    rep = scalar_cell(engine, template,
                      candidate_sets(template, 1, seed=9)[0],
                      PoissonArrivals(1.0, 8, seed=1).times())
    assert rep.latencies is rep.latencies            # no rebuild per call
    assert rep.instances is rep.instances
    assert rep.total_cost == rep.total_cost
    assert rep.total_cost == sum(r.cost for r in rep.instances)
    assert rep.total_queue_delay == \
        sum(r.queue_delay for r in rep.instances)
    assert rep.slo_attainment(5.0) == rep.slo_attainment(5.0)
    # object view agrees with the arrays
    for i, r in enumerate(rep.instances):
        assert r.uid == i
        assert r.e2e == rep.latencies[i]
        assert r.cost == rep.costs[i]
        assert r.failed == rep.failed_mask[i]


def test_report_legacy_instances_constructor_roundtrips():
    from repro.core.engine import FleetReport, InstanceResult

    rows = [InstanceResult(uid=0, arrival=0.0, finish=2.0, e2e=2.0,
                           queue_delay=0.5, cold_delay=0.0, cost=1.25,
                           failed=False),
            InstanceResult(uid=1, arrival=1.0, finish=math.inf, e2e=math.inf,
                           queue_delay=0.0, cold_delay=0.0, cost=0.0,
                           failed=True)]
    rep = FleetReport(instances=rows, makespan=2.0,
                      cpu_utilization=0.0, mem_utilization=0.0,
                      queue_delay_by_function={})
    assert rep.instances == rows
    assert np.array_equal(rep.latencies, [2.0, math.inf])
    assert rep.slo_attainment(3.0) == 0.5
    assert rep.total_cost == 1.25
    assert rep.p50 == math.inf or rep.p50 == 2.0   # interpolation defined
    assert not math.isnan(rep.p99)
