#!/usr/bin/env python3
"""Write ``bench/configs/1000genome.json``: the 1000 Genomes analysis
workflow as one deployment of the benchmark.

    python3 scripts/make_1000genome_config.py

The shape is the Pegasus 1000Genome workflow's, as SeBS-Flow runs it
as serverless functions. Per chromosome: ``SHARDS`` ``individuals``
shards, each feeding ``individuals_merge``; ``sifting``, which depends
on nothing; and for each population one ``mutation_overlap`` and one
``frequency``, each depending on ``individuals_merge`` and ``sifting``.
All 22 autosomes are in one instance, functions chromosome-major.

The response surfaces (one per task type: the shards process equal
slices), the shard count and the SLO are set here, not measured, and
the file lists each under ``assumed``. The incumbent is derived: the
cheapest uniform ``(cpu, mem)`` on the lattice whose latency meets the
SLO. Nothing here is random.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path
from typing import Dict, List, Tuple

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
OUT = ROOT / "bench" / "configs" / "1000genome.json"
sys.path.insert(0, str(ROOT))

from bench import reference  # noqa: E402

CHROMOSOMES = range(1, 23)
SHARDS = 10
POPULATIONS = ("ALL", "AFR", "AMR", "EAS", "EUR", "GBR", "SAS")
SLO_S = 212.5
#: what the traffic's challengers may move (``bench/traffic/``): the
#: SLO must lie clear of every latency they can reach
MOVE_CPU, MOVE_MEM = 1.0, 512.0

CHECK_LIMITS = {"time_rel": 1e-08, "cost_rel": 1e-09, "mismatch": 0}
PRICING = {"mu0_per_vcpu_s": 0.512, "mu1_per_mb_s": 0.001,
           "mu2_per_invocation": 0.0}
LATTICE = {"cpu_min": 0.1, "cpu_max": 10.0, "cpu_step": 0.1,
           "mem_min_mb": 128.0, "mem_max_mb": 10240.0, "mem_step_mb": 64.0}

#: per task type: vCPU-seconds of work, parallel fraction, working-set
#: floor and knee (MB), paging penalty at the floor, I/O seconds
SURFACES: Dict[str, Dict[str, float]] = {
    "individuals": dict(cpu_work=300.0, parallel_frac=0.9, mem_floor=1536,
                        mem_knee=3072, mem_penalty=2.5, io_time=12.0),
    "individuals_merge": dict(cpu_work=40.0, parallel_frac=0.5,
                              mem_floor=1024, mem_knee=2048,
                              mem_penalty=1.5, io_time=20.0),
    "sifting": dict(cpu_work=90.0, parallel_frac=0.6, mem_floor=512,
                    mem_knee=1024, mem_penalty=2.0, io_time=6.0),
    "mutation_overlap": dict(cpu_work=150.0, parallel_frac=0.85,
                             mem_floor=2048, mem_knee=3072, mem_penalty=3.0,
                             io_time=5.0),
    "frequency": dict(cpu_work=240.0, parallel_frac=0.85, mem_floor=2048,
                      mem_knee=3072, mem_penalty=3.0, io_time=5.0),
}

ASSUMED = [
    f"{SHARDS} individuals shards per chromosome (the source leaves the "
    "count to the run; measured per-function profiles are not in the "
    "repository)",
    "one response surface per task type, shared by its shards and "
    "populations, since each processes an equal slice",
    "individuals: 300 vCPU-s, parallel 0.9, working set 1536-3072 MB, "
    "penalty 2.5, 12 s I/O: parses a tenth of a chromosome's VCF",
    "individuals_merge: 40 vCPU-s, parallel 0.5, 1024-2048 MB, penalty "
    "1.5, 20 s I/O: gathers and re-archives the shards' outputs",
    "sifting: 90 vCPU-s, parallel 0.6, 512-1024 MB, penalty 2.0, 6 s "
    "I/O: filters one chromosome's SIFT annotations",
    "mutation_overlap: 150 vCPU-s, parallel 0.85, 2048-3072 MB, penalty "
    "3.0, 5 s I/O: per population, off the critical path at the "
    "incumbent",
    "frequency: 240 vCPU-s, parallel 0.85, 2048-3072 MB, penalty 3.0, "
    "5 s I/O: per population, the longest task, so on the critical path",
    f"SLO {SLO_S:g} s: the incumbent's latency (211.2 s) sits 0.6% under "
    "it, so a challenger that slows a critical-path function can miss it; "
    "no latency a challenger can reach lies within 1e-06 of it",
    "incumbent: the cheapest uniform (cpu, mem) on the lattice that meets "
    "the SLO under these surfaces (derived by "
    "scripts/make_1000genome_config.py)",
    "pricing and lattice as the AARC paper (section IV-A)",
]


def functions() -> List[Tuple[str, str]]:
    """(name, task type) in the file's order, chromosome-major."""
    out = []
    for c in CHROMOSOMES:
        out += [(f"individuals_chr{c}_s{i:02d}", "individuals")
                for i in range(SHARDS)]
        out.append((f"individuals_merge_chr{c}", "individuals_merge"))
        out.append((f"sifting_chr{c}", "sifting"))
        out += [(f"mutation_overlap_chr{c}_{p}", "mutation_overlap")
                for p in POPULATIONS]
        out += [(f"frequency_chr{c}_{p}", "frequency") for p in POPULATIONS]
    return out


def edges() -> List[Tuple[str, str]]:
    out = []
    for c in CHROMOSOMES:
        merge = f"individuals_merge_chr{c}"
        out += [(f"individuals_chr{c}_s{i:02d}", merge)
                for i in range(SHARDS)]
        for task in ("mutation_overlap", "frequency"):
            for p in POPULATIONS:
                out.append((merge, f"{task}_chr{c}_{p}"))
                out.append((f"sifting_chr{c}", f"{task}_chr{c}_{p}"))
    return out


def runtimes(cpu, mem) -> Dict[str, np.ndarray]:
    """Each task type's runtime at ``cpu`` vCPU and ``mem`` MB (arrays
    of one shape) by the reference's response surface; NaN under the
    working-set floor."""
    conf = {"input_scale": 1.0,
            "functions": [dict(name=t, **s, scale_mem=True)
                          for t, s in SURFACES.items()]}
    rt, failed = reference.surface(conf, np.asarray(cpu, float)[..., None],
                                   np.asarray(mem, float)[..., None],
                                   np.float64)
    rt = np.where(failed, np.nan, rt)
    return {t: rt[..., k] for k, t in enumerate(SURFACES)}


def _paths() -> List[Tuple[str, ...]]:
    """Each source-to-sink path of one chromosome, as task types."""
    return [(head, *mid, tail)
            for head, mid in (("individuals", ("individuals_merge",)),
                              ("sifting", ()))
            for tail in ("mutation_overlap", "frequency")]


def latency(rt: Dict[str, np.ndarray]) -> np.ndarray:
    """An instance's latency under per-type runtimes: every chromosome
    has the same paths, so the longest of them."""
    return np.max([sum(rt[t] for t in path) for path in _paths()], axis=0)


def incumbent() -> Tuple[float, float, float]:
    """The cheapest uniform (cpu, mem) on the lattice that meets the
    SLO, and its latency."""
    lat = LATTICE
    cpu = np.arange(round(lat["cpu_min"] / lat["cpu_step"]),
                    round(lat["cpu_max"] / lat["cpu_step"]) + 1) \
        * lat["cpu_step"]
    mem = np.arange(round(lat["mem_min_mb"] / lat["mem_step_mb"]),
                    round(lat["mem_max_mb"] / lat["mem_step_mb"]) + 1) \
        * lat["mem_step_mb"]
    cc, mm = np.meshgrid(cpu, mem, indexing="ij")
    rt = runtimes(cc, mm)
    count = {t: sum(task == t for _, task in functions()) for t in SURFACES}
    rate = PRICING["mu0_per_vcpu_s"] * cc + PRICING["mu1_per_mb_s"] * mm
    cost = sum(count[t] * rt[t] for t in SURFACES) * rate
    ok = np.isfinite(cost) & (latency(rt) <= SLO_S)
    i, j = np.unravel_index(np.argmin(np.where(ok, cost, np.inf)), cc.shape)
    return (round(float(cpu[i]), 6), round(float(mem[j]), 6),
            float(latency(rt)[i, j]))


def reachable_latencies(cpu: float, mem: float) -> np.ndarray:
    """Every path length a challenger can make: up to two functions of
    one path moved by up to ``MOVE_CPU`` and ``MOVE_MEM`` on the
    lattice, the rest at the incumbent. An instance's latency is the
    longest of its paths, so it is always one of these."""
    lat = LATTICE
    dc = np.arange(-round(MOVE_CPU / lat["cpu_step"]),
                   round(MOVE_CPU / lat["cpu_step"]) + 1) * lat["cpu_step"]
    dm = np.arange(-round(MOVE_MEM / lat["mem_step_mb"]),
                   round(MOVE_MEM / lat["mem_step_mb"]) + 1) \
        * lat["mem_step_mb"]
    cc, mm = np.meshgrid(np.clip(cpu + dc, lat["cpu_min"], lat["cpu_max"]),
                         np.clip(mem + dm, lat["mem_min_mb"],
                                 lat["mem_max_mb"]), indexing="ij")
    at_incumbent, at_moved = runtimes(cpu, mem), runtimes(cc, mm)
    out = []
    for path in _paths():
        base = [float(at_incumbent[t]) for t in path]
        moved = [at_moved[t].ravel() for t in path]
        out.append([sum(base)])
        for a in range(len(path)):
            out.append(sum(base) - base[a] + moved[a])
            for b in range(a + 1, len(path)):
                rest = sum(base) - base[a] - base[b]
                out.append((rest + moved[a][:, None]
                            + moved[b][None, :]).ravel())
    return np.concatenate(out)


def config() -> Dict:
    cpu, mem, lat = incumbent()
    if not 0.95 * SLO_S <= lat <= SLO_S:
        raise ValueError(f"the incumbent's latency {lat} is not within 5% "
                         f"under the SLO {SLO_S}")
    reach = reachable_latencies(cpu, mem)
    if np.isnan(reach).any():
        raise ValueError("a challenger can move a function under its "
                         "working-set floor")
    gap = float(np.min(np.abs(reach - SLO_S))) / SLO_S
    if gap < 100 * CHECK_LIMITS["time_rel"]:
        raise ValueError(f"a reachable latency lies {gap:.1e} of the SLO "
                         f"from it: a gap inside time_rel could decide a "
                         f"hit")
    names = functions()
    return {
        "name": "1000genome",
        "source": "Pegasus 1000Genome workflow (github.com/pegasus-isi/"
                  "1000genome-workflow) as serverless functions in "
                  "SeBS-Flow (Schmid, Copik et al.)",
        "deployment": f"one 1000 Genomes analysis over all 22 autosomes, "
                      f"{SHARDS} individuals shards per chromosome "
                      f"({len(names)} functions, {len(edges())} edges, 22 "
                      f"components), replayed on one chip; incumbent "
                      f"{cpu:g} vCPU, {mem:g} MB per function",
        "workflow": "genome_1000",
        "backend": "analytic",
        "precision": "float64",
        "pricing": PRICING,
        "lattice": LATTICE,
        "input_scale": 1.0,
        "slo_s": SLO_S,
        "incumbent": {"cpu": cpu, "mem_mb": mem},
        "assumed": ASSUMED,
        "reduced": [],
        "check_limits": CHECK_LIMITS,
        "functions": [dict(name=n, **SURFACES[t], scale_mem=True)
                      for n, t in names],
        "edges": [list(e) for e in edges()],
    }


def render() -> str:
    """The file's text: one function and one edge a line."""
    conf = config()
    listed = {k: conf.pop(k) for k in ("functions", "edges")}
    head = json.dumps(conf, indent=1)[:-2]
    parts = [head + ","]
    for key, items in listed.items():
        rows = ",\n".join(f"  {json.dumps(x)}" for x in items)
        parts.append(f' "{key}": [\n{rows}\n ]')
    return parts[0] + "\n" + ",\n".join(parts[1:]) + "\n}\n"


if __name__ == "__main__":
    OUT.write_text(render())
