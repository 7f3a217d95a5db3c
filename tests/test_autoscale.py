"""Joint autoscaling: replica-pool engine semantics + the scale plane.

The engine-level contract (pinned here, relied on by the online
controller and the autoscale benchmark):

  * a replica pool bounds its function's *admission concurrency* —
    R invocations run at once, the rest queue FIFO;
  * warm-container pools shard per replica (a pool never serves more
    than R live containers) and cold starts are charged per replica
    spin-up;
  * a carried-in warm pool from an epoch with a larger R is trimmed to
    the R latest-expiring containers at load (the mid-sequence
    replica-change handoff);
  * an *ample* pool at zero provisioning price is **bit-identical** to
    ``scale=None`` on all three replay planes (fast / constrained /
    serial) and under a scalar-only pricing model — the actuator is
    purely additive;
  * provisioned replica-seconds are billed, so scale-out is never free.

Plus the joint-search surface (:class:`ScaleSearcher` speaks the
``Searcher`` protocol; the grid plane serializes it explainably) and
the online control plane with the scale actuator enabled (ledger
conservation, payload shape, determinism, and the autoscale-off
bit-identity guard).
"""
import dataclasses
import math

import numpy as np
import pytest

from repro.core.autoscale import (AutoscaleSpec, ScaleResult, ScaleSearcher,
                                  classify_saturation, grant_replicas,
                                  pool_capacity_factor)
from repro.core.backend import CallableBackend
from repro.core.campaign import PortfolioSpec, ReplaySpec
from repro.core.cost import PricingModel
from repro.core.dag import Workflow
from repro.core.engine import (ClusterModel, ColdStartModel, FleetEngine,
                               PoissonArrivals, ReplicaModel)
from repro.core.online import OnlineSpec, run_online
from repro.core.resources import ResourceConfig
from repro.core.search import make_searcher
from repro.serverless.generator import (DriftEvent, DriftSchedule,
                                        chain_workflow)
from repro.serverless.platform import SimulatedPlatform

# -- replica-pool engine semantics --------------------------------------

#: zero-price pools: semantics only, no replica-second billing
def _pool(replicas=None, default=1):
    return ReplicaModel(replicas=replicas or {}, default=default,
                        provision_frac=0.0, provision_floor=0.0)


def _one_fn():
    wf = Workflow("w")
    wf.add_function("f")
    return wf


def _unit_engine(**kw):
    """One function, exactly 1 s runtime — queueing is exact arithmetic."""
    return FleetEngine(CallableBackend(lambda node: 1.0), **kw)


def test_replica_pool_bounds_admission_concurrency():
    """R=1 serializes two simultaneous arrivals (the second waits a
    full service time); R=2 runs them concurrently."""
    r1 = _unit_engine(scale=_pool({"f": 1})).run(
        [_one_fn(), _one_fn()], [0.0, 0.0])
    assert r1.total_queue_delay == 1.0
    assert sorted(i.e2e for i in r1.instances) == [1.0, 2.0]

    r2 = _unit_engine(scale=_pool({"f": 2})).run(
        [_one_fn(), _one_fn()], [0.0, 0.0])
    assert r2.total_queue_delay == 0.0
    assert [i.e2e for i in r2.instances] == [1.0, 1.0]


def test_warm_pools_shard_per_replica_and_cold_charges_per_spinup():
    """R=1: the second arrival waits, then claims the first's warm
    container — ONE spin-up. R=2: both admitted cold — TWO spin-ups
    (each replica pays its own cold start), no queueing."""
    cold = ColdStartModel(delay_s=0.5, keep_alive_s=100.0)
    r1 = _unit_engine(cold_start=cold, scale=_pool({"f": 1})).run(
        [_one_fn(), _one_fn()], [0.0, 0.0])
    sat1 = r1.saturation()["w/f"]
    assert sat1["spinups"] == 1
    assert sorted(r1.cold_delays.tolist()) == [0.0, 0.5]
    assert r1.total_queue_delay == 1.5        # cold + service of inst 1

    r2 = _unit_engine(cold_start=cold, scale=_pool({"f": 2})).run(
        [_one_fn(), _one_fn()], [0.0, 0.0])
    sat2 = r2.saturation()["w/f"]
    assert sat2["spinups"] == 2
    assert r2.cold_delays.tolist() == [0.5, 0.5]
    assert r2.total_queue_delay == 0.0


def test_carry_handoff_trims_warm_pool_to_new_replica_count():
    """A warm pool carried from an R=3 epoch into an R=1 epoch is
    trimmed to the single latest-expiring container at load: the one
    arrival claims it (no spin-up) and the end-of-epoch carry holds
    exactly one container, not three."""
    cold = ColdStartModel(delay_s=0.5, keep_alive_s=1000.0)
    ep1 = _unit_engine(cold_start=cold, scale=_pool({"f": 3})).run(
        [_one_fn() for _ in range(3)], [0.0, 0.0, 0.0],
        collect_carry=True)
    assert len(ep1.carry.warm[("w", "f")]) == 3

    ep2 = _unit_engine(cold_start=cold, scale=_pool({"f": 1})).run(
        [_one_fn()], [10.0], carry=ep1.carry.pruned(10.0),
        collect_carry=True)
    assert ep2.cold_delays.tolist() == [0.0]          # claimed warm
    assert ep2.saturation()["w/f"]["spinups"] == 0
    assert len(ep2.carry.warm[("w", "f")]) == 1       # trimmed to R


def test_provisioned_replicas_are_billed_replica_seconds():
    """Scale-out is never free: the same fleet at R=2 with a non-zero
    provisioning price costs strictly more than unbounded serving, and
    a floor price adds on top."""
    base = _unit_engine().run([_one_fn(), _one_fn()], [0.0, 0.0])
    priced = _unit_engine(scale=ReplicaModel(
        replicas={"f": 2}, provision_frac=0.25)).run(
        [_one_fn(), _one_fn()], [0.0, 0.0])
    floored = _unit_engine(scale=ReplicaModel(
        replicas={"f": 2}, provision_frac=0.25, provision_floor=0.1)).run(
        [_one_fn(), _one_fn()], [0.0, 0.0])
    assert priced.total_cost > base.total_cost
    assert floored.total_cost > priced.total_cost


def test_saturation_reports_pool_diagnostics():
    """Satellite: per-function saturation rows carry the pool size,
    busy seconds, pool-relative utilization, and queue share."""
    rep = _unit_engine(scale=_pool({"f": 2})).run(
        [_one_fn() for _ in range(4)], [0.0] * 4)
    row = rep.saturation()["w/f"]
    assert row["replicas"] == 2
    assert row["busy_s"] == 4.0               # 4 invocations x 1 s
    assert row["utilization"] == pytest.approx(4.0 / (2 * rep.makespan))
    assert row["queue_share"] == 1.0          # the only queued function


# -- ample-pool bit-identity on all three replay planes -----------------

class _ScalarMirrorPricing(PricingModel):
    """Same numbers, no vectorized ``cost_batch``: the cost table is
    filled entry by entry on whichever plane the replay routes to
    (mirrors the idiom pinned in test_replay_batch)."""

    def function_cost(self, runtime_s, config):
        return super().function_cost(runtime_s, config)


#: an admission bound no fleet here ever reaches + zero provisioning
#: price: the ReplicaModel must be a bit-exact no-op
_AMPLE = ReplicaModel(default=1_000_000, provision_frac=0.0,
                      provision_floor=0.0)


def _plane_engine(plane, scale):
    env = SimulatedPlatform().environment()
    if plane == "fast":
        return FleetEngine(env.backend, pricing=env.pricing, scale=scale)
    if plane == "constrained":
        return FleetEngine(env.backend, pricing=env.pricing, scale=scale,
                           cluster=ClusterModel(total_cpu=12.0,
                                                total_mem_mb=16384.0),
                           cold_start=ColdStartModel(delay_s=1.0,
                                                     keep_alive_s=30.0))
    if plane == "scalar_pricing":
        return FleetEngine(env.backend, pricing=_ScalarMirrorPricing(),
                           scale=scale)
    assert plane == "serial"
    return FleetEngine(CallableBackend(lambda node: 2.0 / node.config.cpu),
                       pricing=env.pricing, scale=scale)


def _assert_reports_identical(got, want):
    assert np.array_equal(got.arrivals, want.arrivals)
    assert np.array_equal(got.finishes, want.finishes)
    assert np.array_equal(got.latencies, want.latencies)
    assert np.array_equal(got.queue_delays, want.queue_delays)
    assert np.array_equal(got.cold_delays, want.cold_delays)
    assert np.array_equal(got.costs, want.costs)
    assert got.makespan == want.makespan
    assert got.total_cost == want.total_cost
    assert got.queue_delay_by_function == want.queue_delay_by_function


@pytest.mark.parametrize("plane", ["fast", "constrained", "scalar_pricing",
                                   "serial"])
def test_ample_pool_is_bit_identical_to_scale_none_on_every_plane(plane):
    """The acceptance bar: an ample zero-price ReplicaModel reproduces
    the pre-replica engine bit-for-bit on each replay plane. The
    with-scale engine routes through the event loop (replica bounds are
    an event-loop concept), so this is also a cross-plane check."""
    template = chain_workflow(4, seed=11)
    cands = [{n.name: ResourceConfig(cpu=float(c), mem=2048.0 * c)
              for n in template} for c in (2, 5)]
    seeds = [PoissonArrivals(1.0, 6, seed=s).times() for s in (0, 1)]
    base = _plane_engine(plane, None).run_many(template, cands, seeds)
    scaled = _plane_engine(plane, _AMPLE).run_many(template, cands, seeds)
    assert len(base) == len(scaled) == 4
    for got, want in zip(scaled, base):
        _assert_reports_identical(got, want)


def test_replica_pools_route_run_many_to_the_event_loop():
    """``batch_eligibility`` must name the replica bound as the reason
    a fast-plane replay lands on the constrained plane."""
    template = chain_workflow(3, seed=1)
    elig = _plane_engine("fast", _AMPLE).batch_eligibility(template, [])
    assert elig["plane"] == "constrained"
    assert any("replica pools" in r for r in elig["reasons"])


def test_replica_model_rejects_bad_pools():
    with pytest.raises(ValueError, match="must be >= 1"):
        ReplicaModel(replicas={"f": 0})
    with pytest.raises(ValueError, match="default pool"):
        ReplicaModel(default=0)
    with pytest.raises(ValueError, match="provision_frac"):
        ReplicaModel(provision_frac=-0.1)


# -- policy helpers -----------------------------------------------------

def test_classify_saturation_queue_share():
    sat = {"a/f": {"queue_delay_s": 3.0}, "a/g": {"queue_delay_s": 1.0}}
    bound, share = classify_saturation(sat, cold_delay_s=4.0)
    assert bound and share == pytest.approx(0.5)
    assert classify_saturation({}, 0.0) == (False, 0.0)
    # pure queueing, no cold component: fully capacity-attributed
    _, share = classify_saturation(sat, 0.0)
    assert share == 1.0


def test_grant_replicas_follows_critical_path_queue_delay():
    sat = {"w/f": {"queue_delay_s": 0.5}, "w/g": {"queue_delay_s": 2.0}}
    replicas = {"f": 1, "g": 1}
    grown = grant_replicas(replicas, sat, ["f", "g"], width=2,
                           max_replicas=2)
    assert grown == {"f": 2, "g": 2}          # g first (more queue), then f
    assert replicas == {"f": 1, "g": 1}       # input untouched (a copy)
    # every pool capped: the grant is a no-op
    assert grant_replicas(replicas, sat, ["f", "g"], width=2,
                          max_replicas=1) == replicas
    # off-path functions are a fallback once the path is capped
    sat2 = {"w/f": {"queue_delay_s": 0.0}, "w/h": {"queue_delay_s": 3.0}}
    assert grant_replicas({"f": 1, "h": 1}, sat2, ["f"], width=1,
                          max_replicas=4) == {"f": 1, "h": 2}


def test_pool_capacity_factor_tracks_provisioned_demand():
    base = ClusterModel(total_cpu=20.0, total_mem_mb=1e6)
    cfg = {"f": ResourceConfig(cpu=10.0, mem=1024.0)}
    # 4 replicas x 10 cpu = 40 cpu on a 20-cpu base -> x2
    assert pool_capacity_factor({"f": 4}, cfg, base,
                                max_scale=8.0) == pytest.approx(2.0)
    # never shrunk below the floor, always capped at max_scale
    assert pool_capacity_factor({"f": 4}, cfg, base, max_scale=8.0,
                                floor=3.0) == pytest.approx(3.0)
    assert pool_capacity_factor({"f": 4}, cfg, base,
                                max_scale=1.5) == pytest.approx(1.5)
    # an infinite base dimension needs no growth
    from repro.core.engine import INFINITE_CLUSTER
    assert pool_capacity_factor({"f": 100}, cfg, INFINITE_CLUSTER,
                                max_scale=8.0) == 1.0


def test_autoscale_spec_validation():
    with pytest.raises(ValueError, match="actuators"):
        AutoscaleSpec(actuators=("config", "warp"))
    with pytest.raises(ValueError, match="actuators"):
        AutoscaleSpec(actuators=())
    with pytest.raises(ValueError, match="max_replicas"):
        AutoscaleSpec(max_replicas=0)
    with pytest.raises(ValueError, match="deploy_utilization"):
        AutoscaleSpec(deploy_utilization=0.0)
    with pytest.raises(ValueError, match="max_cluster_scale"):
        AutoscaleSpec(max_cluster_scale=0.5)


# -- ScaleSearcher protocol ---------------------------------------------

_SEARCH_SPEC = AutoscaleSpec(rate=0.05, n_instances=12, max_rounds=4,
                             config_grant=4, max_replicas=4,
                             provision_frac=0.0)


def _search_once():
    env = SimulatedPlatform().environment()
    searcher = make_searcher("scale", env, spec=_SEARCH_SPEC)
    wf = chain_workflow(3, seed=2)
    return searcher, searcher.search(wf, 120.0), wf


def test_make_searcher_scale_lazy_registers():
    """``make_searcher("scale")`` resolves via the lazy autoscale
    import and refuses a self-referential inner searcher."""
    env = SimulatedPlatform().environment()
    s = make_searcher("scale", env)
    assert isinstance(s, ScaleSearcher) and s.name == "scale"
    with pytest.raises(ValueError, match="inner"):
        make_searcher("scale", env, inner="scale")


def test_scale_search_returns_joint_action():
    searcher, res, wf = _search_once()
    assert isinstance(res, ScaleResult)
    assert set(res.replicas) <= set(wf.nodes)
    assert all(1 <= r <= _SEARCH_SPEC.max_replicas
               for r in res.replicas.values())
    assert res.cluster_scale >= 1.0
    assert res.fleet_evals >= 1
    assert math.isfinite(res.fleet_cost)
    summary = res.summary()
    assert summary["total_replicas"] == sum(res.replicas.values())
    assert {"replicas", "cluster_scale", "fleet_attainment",
            "fleet_evals"} <= set(summary)
    # ~1.85 erlangs offered per R=1 pool: the loop must scale out
    assert sum(res.replicas.values()) > len(res.replicas)


def test_scale_resume_zero_budget_is_a_noop():
    searcher, res, _ = _search_once()
    assert res.state is not None
    assert res.state.payload["replicas"] == res.replicas
    assert searcher.resume(res.state, 0) is res


def test_grid_plane_serializes_scale_searcher_with_reason():
    """No plan(): the lockstep grid must serialize the joint searcher
    explainably, not silently."""
    from repro.core.gridsearch import grid_eligibility
    env = SimulatedPlatform().environment()
    searcher = make_searcher("scale", env, spec=_SEARCH_SPEC)
    (cell,) = grid_eligibility([(searcher, chain_workflow(3, seed=2),
                                 60.0)])
    assert not cell.eligible
    assert any("no plan()" in r for r in cell.reasons)


# -- online control plane with the scale actuator -----------------------

def _autoscale_spec(seed=0, **kw):
    """A small capacity-bound load step: deploy-sized pools saturate at
    3x rate, so the scale actuator must fire."""
    base = dict(
        portfolio=PortfolioSpec(n_workflows=2, size=4, kinds=("chain",),
                                slo_slacks=(1.6,)),
        replay=ReplaySpec(n_instances=12, rate=0.015,
                          cluster=ClusterModel(total_cpu=60.0,
                                               total_mem_mb=61440.0)),
        n_epochs=6,
        drift=DriftSchedule((DriftEvent(2, "load", 3.0),)),
        seed=seed, total_budget=256, cooldown_epochs=0,
        autoscale=AutoscaleSpec(provision_floor=0.02, max_replicas=8,
                                max_cluster_scale=6.0))
    base.update(kw)
    return OnlineSpec(**base)


def test_online_autoscale_ledger_is_conserved():
    report = run_online(_autoscale_spec())
    b = report.budget
    assert b["total"] == b["spent"] + b["remaining"]
    assert b["spent"] == sum(c.spent for c in report.cells)
    assert b["spent"] == sum(r.spent for r in report.reconfigs)


def test_online_autoscale_payload_exposes_pools():
    report = run_online(_autoscale_spec())
    payload = report.to_payload()
    for cell, row in zip(report.cells, payload["cells"]):
        assert cell.replicas is not None
        assert set(cell.replicas) == set(cell.task.template.nodes)
        assert row["replicas"] == sorted(cell.replicas.items())
        assert row["cluster_scale"] == cell.cluster_scale >= 1.0
    for row in payload["epochs"]:
        assert {"total_replicas", "cluster_scale"} <= set(row)
    # the load step forced scale-out past one-replica pools
    assert any(sum(c.replicas.values()) > len(c.replicas)
               for c in report.cells)
    assert any(r.accepted for r in report.reconfigs)


def test_online_autoscale_payload_is_deterministic():
    spec = _autoscale_spec(seed=7)
    assert run_online(spec).to_payload() == run_online(spec).to_payload()


def test_autoscale_off_keeps_payload_free_of_replica_keys():
    """The bit-identity guard: without an AutoscaleSpec no ReplicaModel
    exists and no replica key leaks into BENCH_online payloads."""
    spec = _autoscale_spec(autoscale=None)
    payload = run_online(spec).to_payload()
    for row in payload["cells"]:
        assert "replicas" not in row and "cluster_scale" not in row
    for row in payload["epochs"]:
        assert "total_replicas" not in row


def test_autoscale_bench_row_is_deterministic():
    """The emitted BENCH_autoscale.json row (minus wall-clock keys) is
    identical across runs and clears its pinned bars."""
    bench = pytest.importorskip(
        "benchmarks.autoscale",
        reason="benchmarks namespace needs the repo root on sys.path")
    first = bench.deterministic_payload(
        bench.autoscale_case("compound_shift", bench.COMPOUND_SHIFT))
    second = bench.deterministic_payload(
        bench.autoscale_case("compound_shift", bench.COMPOUND_SHIFT))
    assert first == second
    assert not any(k.endswith("_s") for k in first)
    assert bench.check_acceptance([first]) == []
