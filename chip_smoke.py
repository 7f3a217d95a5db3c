#!/usr/bin/env python3
"""Smoke run of the system's device paths on one TPU chip.

    python3 chip_smoke.py

Runs in one process and refuses to start unless JAX's first device is
a TPU. Three phases run in order; each prints one JSON line naming the
phase, the device kind, the seconds spent compiling (JAX's backend
compiles, persistent-cache reads included), the phase's wall seconds
and its own correctness check:

  A  fleet replay on the device: ``FleetEngine(plane_backend="jax")
     .run_many`` over a 256-function fan-out, 64 candidate config-maps
     x 4 Poisson arrival seeds of 2,560 instances, compared report by
     report with the numpy plane; then one ``run_campaign`` (16
     workflows; AARC, BO and MAFF).
  B  the Pallas kernels at published widths -- flash attention at
     qwen3-0.6b, fused RMSNorm at d=1024, the SSD scan at zamba2-1.2b --
     each compiled for the chip and compared with its ``ref.py``.
  C  ``ServeEngine`` over qwen3-0.6b at full width (random weights from
     ``jax.random.key(0)``): 8 greedy requests with Pallas prefill
     attention, then again with XLA attention on the same parameters.

The last line is ``{"ok": true, "device": {...}}`` and is printed only
when every phase passed; otherwise the exit code is non-zero.
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys
import time
import traceback
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What each phase runs at; the defaults are the chip run's."""

    fan_width: int = 254            # + scatter and gather = 256 functions
    n_candidates: int = 64
    n_seeds: int = 4
    n_instances: int = 2560         # per arrival seed
    arrival_rate: float = 2.0       # Poisson arrivals / second
    campaign_workflows: int = 16
    campaign_size: int = 8
    attn: tuple = (1, 2048, 16, 8, 128)       # b, s, h, kv heads, head_dim
    norm: tuple = (4096, 1024)                # rows, d
    ssd: tuple = (1, 2048, 64, 64, 64, 128)   # b, s, heads, head_dim, state, chunk
    serve_arch: str = "qwen3-0.6b"
    n_requests: int = 8
    n_slots: int = 4
    prompt_len: tuple = (4, 64)     # inclusive range
    max_new: int = 16
    max_len: int = 128
    agree_steps: int = 8


#: tolerances, fixed before the first chip run
REPLAY_RTOL = 1e-12                        # device sweep vs numpy plane
TOL_BF16 = dict(atol=6e-2, rtol=6e-2)      # as tests/test_kernels.py
SSD_STATE_TOL = dict(atol=1e-3, rtol=1e-2)  # as tests/test_kernels.py
#: a greedy divergence between the Pallas and XLA serving runs passes
#: only as a near-tie: the reference's logit gap between the two tokens
#: is at most this multiple of the measured prefill logit deviation
NEAR_TIE_FACTOR = 2.0


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

    def snapshot(self):
        return self.seconds, self.compiles, self.cache_hits


def _max_rel_dev(got: np.ndarray, want: np.ndarray) -> float:
    scale = np.maximum(np.abs(want), np.finfo(np.float64).tiny)
    return float((np.abs(got - want) / scale).max()) if want.size else 0.0


def phase_fleet(sizes: Sizes) -> dict:
    """A: the jitted rank-by-rank replay sweep on the device, against the
    numpy plane, then the campaign entry point."""
    import jax

    from repro.core.campaign import CampaignSpec, PortfolioSpec, run_campaign
    from repro.core.engine import FleetEngine, PoissonArrivals
    from repro.core.resources import ResourceConfig
    from repro.serverless.generator import generate
    from repro.serverless.platform import SimulatedPlatform

    template = generate("fan", width=sizes.fan_width, seed=0)
    rng = np.random.default_rng(0)
    cands = [{n.name: ResourceConfig(cpu=float(rng.uniform(1.0, 8.0)),
                                     mem=float(rng.uniform(1024.0, 8192.0)))
              for n in template} for _ in range(sizes.n_candidates)]
    seeds = [PoissonArrivals(sizes.arrival_rate, sizes.n_instances,
                             seed=s).times() for s in range(sizes.n_seeds)]

    def engine(plane):
        env = SimulatedPlatform().environment()
        return FleetEngine(env.backend, pricing=env.pricing,
                           plane_backend=plane)

    dev = engine("jax")
    elig = dev.batch_eligibility(template, cands, probe_candidates=True)
    t0 = time.perf_counter()
    got = dev.run_many(template, cands, seeds)
    t_cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    again = dev.run_many(template, cands, seeds)
    t_warm = time.perf_counter() - t0
    t0 = time.perf_counter()
    want = engine("numpy").run_many(template, cands, seeds)
    t_numpy = time.perf_counter() - t0

    dev_fin = max(_max_rel_dev(g.finishes, w.finishes)
                  for g, w in zip(got, want))
    dev_lat = max(_max_rel_dev(g.latencies, w.latencies)
                  for g, w in zip(got, want))
    bitwise = all(np.array_equal(g.finishes, w.finishes)
                  and np.array_equal(g.latencies, w.latencies)
                  for g, w in zip(got, want))
    repeat = all(np.array_equal(g.finishes, a.finishes)
                 for g, a in zip(got, again))
    sweep_platform = (dev.sweep_device.platform
                      if dev.sweep_device is not None else None)

    t0 = time.perf_counter()
    report = run_campaign(CampaignSpec(
        portfolio=PortfolioSpec(n_workflows=sizes.campaign_workflows,
                                size=sizes.campaign_size),
        searchers=("aarc", "bo", "maff")))
    t_campaign = time.perf_counter() - t0
    totals = report.totals()
    per = report.summary()
    replays = [r.replay for r in report.results]
    replays_sane = all(
        r is not None and 0.0 <= r.slo_attainment <= 1.0
        and np.isfinite(r.total_cost) for r in replays)
    n_cells = sizes.campaign_workflows * 3

    check = {
        "functions": len(template),
        "instances": int(sum(len(t) for t in seeds)),
        "reports": len(got),
        "plane": elig["plane"], "plane_reasons": elig["reasons"],
        "sweep_platform": sweep_platform,
        "max_rel_dev_finishes": dev_fin, "max_rel_dev_latencies": dev_lat,
        "rtol": REPLAY_RTOL, "bitwise_equal": bitwise,
        "repeat_identical": repeat,
        "run_many_jax_cold_s": t_cold, "run_many_jax_warm_s": t_warm,
        "run_many_numpy_s": t_numpy,
        "default_backend": jax.default_backend(),
        "campaign_s": t_campaign, "campaign_results": totals["n_results"],
        "campaign_mean_slo_attainment": totals["mean_slo_attainment"],
        "campaign_feasible_rate": {k: v["feasible_rate"]
                                   for k, v in per.items()},
    }
    check["ok"] = (
        len(got) == sizes.n_candidates * sizes.n_seeds
        and elig["plane"] == "fast" and not elig["reasons"]
        and elig["serial_candidates"] == []
        and sweep_platform == jax.default_backend()
        and max(dev_fin, dev_lat) <= REPLAY_RTOL and repeat
        and totals["n_results"] == n_cells and replays_sane)
    return check


def _aot(fn, *args):
    """Compile ``fn`` for ``args`` ahead of time, then run it once."""
    import jax

    t0 = time.perf_counter()
    compiled = fn.lower(*args).compile()
    t_compile = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    return out, t_compile, time.perf_counter() - t0


def _close(got, want, tol) -> dict:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return {"max_abs_err": float(np.abs(got - want).max()),
            "ok": bool(np.isfinite(got).all()
                       and np.allclose(got, want, **tol))}


def phase_kernels(sizes: Sizes) -> dict:
    """B: each Pallas kernel once at its published widths on bf16
    inputs, against its pure-jnp oracle run on the same values in fp32
    at highest matmul precision."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import attention_ref
    from repro.kernels.rmsnorm.ops import fused_rmsnorm
    from repro.kernels.rmsnorm.ref import fused_rmsnorm_ref
    from repro.kernels.ssd_scan.ops import ssd_scan
    from repro.kernels.ssd_scan.ref import ssd_scan_ref

    def oracle(fn, *args, **static):
        f32 = [a.astype(jnp.float32) for a in args]
        with jax.default_matmul_precision("highest"):
            return jax.jit(fn, static_argnames=tuple(static))(*f32, **static)

    ks = jax.random.split(jax.random.key(0), 7)
    out = {}

    b, s, h, hkv, d = sizes.attn
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, s, hkv, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, s, hkv, d), jnp.bfloat16)
    o, tc, tw = _aot(flash_attention, q, k, v)
    out["flash_attention"] = dict(_close(o, oracle(attention_ref, q, k, v),
                                         TOL_BF16),
                                  compile_s=tc, wall_s=tw)

    rows, d = sizes.norm
    x = jax.random.normal(ks[3], (rows, d), jnp.bfloat16)
    r = jax.random.normal(ks[4], (rows, d), jnp.bfloat16)
    w = jax.random.normal(ks[5], (d,), jnp.bfloat16)
    (y, nr), tc, tw = _aot(fused_rmsnorm, x, r, w)
    yr, nrr = oracle(fused_rmsnorm_ref, x, r, w)
    cy, cr = _close(y, yr, TOL_BF16), _close(nr, nrr, TOL_BF16)
    out["fused_rmsnorm"] = {"max_abs_err": max(cy["max_abs_err"],
                                               cr["max_abs_err"]),
                            "ok": cy["ok"] and cr["ok"],
                            "compile_s": tc, "wall_s": tw}

    b, s, h, p, n, chunk = sizes.ssd
    kx = jax.random.split(ks[6], 5)
    xh = jax.random.normal(kx[0], (b, s, h, p), jnp.bfloat16)
    bm = jax.random.normal(kx[1], (b, s, n), jnp.bfloat16)
    cm = jax.random.normal(kx[2], (b, s, n), jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(kx[3], (b, s, h)))
    log_a = -dt * jnp.exp(jax.random.normal(kx[4], (b, s, h)) * 0.3)
    (y, hf), tc, tw = _aot(ssd_scan, xh, bm, cm, log_a, dt)
    yr, hr = oracle(ssd_scan_ref, xh, bm, cm, log_a, dt, chunk=chunk)
    cy, ch = _close(y, yr, TOL_BF16), _close(hf, hr, SSD_STATE_TOL)
    out["ssd_scan"] = {"max_abs_err_y": cy["max_abs_err"],
                       "max_abs_err_state": ch["max_abs_err"],
                       "ok": cy["ok"] and ch["ok"],
                       "compile_s": tc, "wall_s": tw}

    out["tolerances"] = {"bf16": TOL_BF16, "ssd_state": SSD_STATE_TOL}
    out["ok"] = all(v["ok"] for k, v in out.items() if k != "tolerances")
    return out


def phase_serve(sizes: Sizes, cfg) -> dict:
    """C: continuous-batching serving at full width, Pallas prefill
    attention against XLA attention on the same parameters."""
    import jax

    from repro.models.model import Model
    from repro.serving import RequestQueue, ServeEngine

    t0 = time.perf_counter()
    params = jax.block_until_ready(
        jax.jit(Model(cfg).init)(jax.random.key(0)))
    t_init = time.perf_counter() - t0
    leaves = jax.tree.leaves(params)
    rng = np.random.default_rng(0)
    lo, hi = sizes.prompt_len
    prompts = [rng.integers(0, cfg.vocab, size=int(rng.integers(lo, hi + 1)))
               for _ in range(sizes.n_requests)]
    models = {impl: Model(dataclasses.replace(cfg, attn_impl=impl))
              for impl in ("pallas", "xla")}

    def serve(impl):
        engine = ServeEngine(models[impl], params, n_slots=sizes.n_slots,
                             max_len=sizes.max_len, temperature=0.0)
        queue = RequestQueue()
        for p in prompts:
            queue.submit(p, max_new_tokens=sizes.max_new)
        t0 = time.perf_counter()
        results = engine.run(queue)
        return ({r.uid: list(r.tokens) for r in results},
                time.perf_counter() - t0)

    def last_logits(impl, tokens):
        batch = {"tokens": jax.numpy.asarray(tokens, jax.numpy.int32)[None]}
        logits, _ = models[impl].prefill(params, batch,
                                         max_len=sizes.max_len)
        # the padded tail of the vocabulary is masked to -1e30
        return np.asarray(logits, np.float32)[0, -1, :cfg.vocab]

    toks = {}
    walls = {}
    for impl in models:
        toks[impl], walls[impl] = serve(impl)

    # the kernel only changes prefill: measure that deviation directly,
    # held to the bf16 tolerance relative to the logits' scale
    prefill_dev, logit_scale = 0.0, 0.0
    for p in prompts:
        lp, lx = last_logits("pallas", p), last_logits("xla", p)
        prefill_dev = max(prefill_dev, float(np.abs(lp - lx).max()))
        logit_scale = max(logit_scale, float(np.abs(lx).max()))

    first = None
    for uid in sorted(toks["xla"]):
        a = toks["pallas"].get(uid, [])[:sizes.agree_steps]
        b = toks["xla"][uid][:sizes.agree_steps]
        step = next((i for i, (ta, tb) in enumerate(zip(a, b)) if ta != tb),
                    None)
        if step is not None:
            ctx = np.concatenate([prompts[uid], b[:step]]).astype(np.int32)
            lx = last_logits("xla", ctx)
            first = {"request": uid, "step": step,
                     "token_pallas": a[step], "token_xla": b[step],
                     "logit_gap": float(lx[b[step]] - lx[a[step]])}
            break

    complete = all(len(toks[i].get(u, [])) == sizes.max_new
                   for i in toks for u in range(sizes.n_requests))
    in_vocab = all(0 <= t < cfg.vocab for i in toks for ts in toks[i].values()
                   for t in ts)
    n_tokens = sum(len(ts) for ts in toks["pallas"].values())
    check = {
        "arch": cfg.name, "layers": cfg.n_layers, "d_model": cfg.d_model,
        "vocab": cfg.vocab, "dtype": cfg.dtype,
        "params": int(sum(x.size for x in leaves)),
        "params_platform": leaves[0].device.platform,
        "init_s": t_init,
        "requests": sizes.n_requests, "slots": sizes.n_slots,
        "tokens_per_run": n_tokens,
        "serve_pallas_s": walls["pallas"], "serve_xla_s": walls["xla"],
        "prefill_logit_max_abs_dev": prefill_dev,
        "logit_scale": logit_scale,
        "agree_steps": sizes.agree_steps,
        "greedy_agree": first is None, "first_divergence": first,
    }
    prefill_tol = TOL_BF16["atol"] + TOL_BF16["rtol"] * logit_scale
    check["prefill_logit_tol"] = prefill_tol
    check["ok"] = (complete and in_vocab and prefill_dev <= prefill_tol
                   and (first is None or abs(first["logit_gap"])
                        <= NEAR_TIE_FACTOR * prefill_dev))
    return check


def run_phases(sizes: Sizes, serve_cfg, device_kind: str) -> bool:
    """Run A, B and C in order, one JSON line each; True iff all pass.
    A phase that raises is reported as failed, traceback on stderr,
    and the remaining phases still run."""
    meter = CompileMeter()
    phases = [("A_fleet_replay", lambda: phase_fleet(sizes)),
              ("B_kernels", lambda: phase_kernels(sizes)),
              ("C_serving", lambda: phase_serve(sizes, serve_cfg))]
    all_ok = True
    for name, fn in phases:
        s0, n0, h0 = meter.snapshot()
        t0 = time.perf_counter()
        try:
            check = fn()
            ok = bool(check.pop("ok"))
        except Exception as exc:   # reported, and fails the run below
            traceback.print_exc()
            check, ok = {"error": f"{type(exc).__name__}: {exc}"}, False
        wall = time.perf_counter() - t0
        s1, n1, h1 = meter.snapshot()
        print(json.dumps({"phase": name, "device_kind": device_kind,
                          "compile_s": s1 - s0, "compiles": n1 - n0,
                          "cache_hits": h1 - h0, "wall_s": wall,
                          "ok": ok, "check": check}, default=str),
              flush=True)
        all_ok = all_ok and ok
    return all_ok


def main() -> int:
    # libtpu would otherwise write its logs under /tmp, outside the
    # checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform!r}",
              file=sys.stderr)
        return 2

    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    from repro.configs.registry import get_config

    sizes = Sizes()
    cfg = get_config(sizes.serve_arch, attn_impl="pallas")
    if not run_phases(sizes, cfg, devices[0].device_kind):
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
