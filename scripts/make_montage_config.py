#!/usr/bin/env python3
"""Write ``bench/configs/montage.json``: the Montage astronomical mosaic
workflow as one deployment of the benchmark.

    python3 scripts/make_montage_config.py

The shape is Pegasus Montage's (Bharathi et al., "Characterization of
Scientific Workflows", WORKS 2008), as DEWE v3 and HyperFlow run it as
serverless functions. Per band, for the images of a ``GRID`` x ``GRID``
grid: one ``mProjectPP`` per image; one ``mDiffFit`` per overlapping
pair (a cell and each of its 8 neighbours), after both images'
projections; ``mConcatFit``, which gathers every ``mDiffFit``;
``mBgModel`` after it; one ``mBackground`` per image, after
``mBgModel`` and the image's projection; ``mImgtbl``, which gathers
every ``mBackground``; then ``mAdd`` and ``mShrink``. The three 2MASS
bands join in one ``mJPEG``, the colour product of Montage's own
tutorial. Functions are band-major.

The response surfaces (one per task type), the grid and the SLO are set
here, not measured, and the file lists each under ``assumed``. The
incumbent is derived: the cheapest uniform ``(cpu, mem)`` on the lattice
whose latency meets the SLO. Nothing here is random.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path
from typing import Dict, List, Tuple

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
OUT = ROOT / "bench" / "configs" / "montage.json"
sys.path.insert(0, str(ROOT))

from bench import reference  # noqa: E402

BANDS = ("J", "H", "K")
GRID = 16
SLO_S = 1000.0
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
    "mProjectPP": dict(cpu_work=24.0, parallel_frac=0.5, mem_floor=512,
                       mem_knee=1024, mem_penalty=1.5, io_time=3.0),
    "mDiffFit": dict(cpu_work=6.0, parallel_frac=0.3, mem_floor=256,
                     mem_knee=512, mem_penalty=1.0, io_time=2.0),
    "mConcatFit": dict(cpu_work=90.0, parallel_frac=0.1, mem_floor=512,
                       mem_knee=1024, mem_penalty=1.5, io_time=40.0),
    "mBgModel": dict(cpu_work=360.0, parallel_frac=0.8, mem_floor=1024,
                     mem_knee=2048, mem_penalty=2.0, io_time=4.0),
    "mBackground": dict(cpu_work=12.0, parallel_frac=0.5, mem_floor=512,
                        mem_knee=1024, mem_penalty=1.5, io_time=3.0),
    "mImgtbl": dict(cpu_work=20.0, parallel_frac=0.1, mem_floor=256,
                    mem_knee=512, mem_penalty=1.0, io_time=10.0),
    "mAdd": dict(cpu_work=600.0, parallel_frac=0.8, mem_floor=2048,
                 mem_knee=4096, mem_penalty=3.0, io_time=60.0),
    "mShrink": dict(cpu_work=120.0, parallel_frac=0.6, mem_floor=1024,
                    mem_knee=2048, mem_penalty=2.0, io_time=10.0),
    "mJPEG": dict(cpu_work=15.0, parallel_frac=0.3, mem_floor=512,
                  mem_knee=1024, mem_penalty=1.5, io_time=5.0),
}

ASSUMED = [
    f"{len(BANDS)} 2MASS bands ({', '.join(BANDS)}) joined in one mJPEG, "
    "the colour mosaic of Montage's own tutorial",
    f"a {GRID}x{GRID} grid of input images per band: the mosaic's size is "
    "the user's choice, not a cut; this one gives the largest in-degree "
    "(mConcatFit) of 930",
    "two images overlap where their grid cells are neighbours, the 8 "
    "around a cell (edges and corners): one mDiffFit per such pair",
    "one response surface per task type, shared by its images and pairs, "
    "since each processes one image or one overlap of the same size",
    "mProjectPP: 24 vCPU-s, parallel 0.5, working set 512-1024 MB, "
    "penalty 1.5, 3 s I/O: reprojects one image",
    "mDiffFit: 6 vCPU-s, parallel 0.3, 256-512 MB, penalty 1.0, 2 s I/O: "
    "differences and fits one overlap",
    "mConcatFit: 90 vCPU-s, parallel 0.1, 512-1024 MB, penalty 1.5, 40 s "
    "I/O: a serial single task that reads every fit",
    "mBgModel: 360 vCPU-s, parallel 0.8, 1024-2048 MB, penalty 2.0, 4 s "
    "I/O: a serial single task, the global background fit",
    "mBackground: 12 vCPU-s, parallel 0.5, 512-1024 MB, penalty 1.5, 3 s "
    "I/O: corrects one image",
    "mImgtbl: 20 vCPU-s, parallel 0.1, 256-512 MB, penalty 1.0, 10 s I/O: "
    "a serial single task that reads every corrected image's header",
    "mAdd: 600 vCPU-s, parallel 0.8, 2048-4096 MB, penalty 3.0, 60 s I/O: "
    "a serial single task that co-adds the band, the largest working set",
    "mShrink: 120 vCPU-s, parallel 0.6, 1024-2048 MB, penalty 2.0, 10 s "
    "I/O: a serial single task",
    "mJPEG: 15 vCPU-s, parallel 0.3, 512-1024 MB, penalty 1.5, 5 s I/O: a "
    "serial single task that joins the three bands",
    f"SLO {SLO_S:g} s: the incumbent's latency (997.9 s) sits 0.2% under "
    "it, so a challenger that slows a serial task on the critical path "
    "can miss it; no latency a challenger can reach lies within 1e-06 of "
    "it",
    "incumbent: the cheapest uniform (cpu, mem) on the lattice that meets "
    "the SLO under these surfaces (derived by "
    "scripts/make_montage_config.py)",
    "pricing and lattice as the AARC paper (section IV-A)",
]


def _image(band: str, r: int, c: int) -> str:
    return f"{band}_r{r:02d}c{c:02d}"


def _pairs() -> List[Tuple[Tuple[int, int], Tuple[int, int]]]:
    """Each overlapping pair of grid cells once: a cell and each of its
    neighbours to the right, below, below-right and below-left."""
    out = []
    for r in range(GRID):
        for c in range(GRID):
            for dr, dc in ((0, 1), (1, 0), (1, 1), (1, -1)):
                rr, cc = r + dr, c + dc
                if 0 <= rr < GRID and 0 <= cc < GRID:
                    out.append(((r, c), (rr, cc)))
    return out


def _cells() -> List[Tuple[int, int]]:
    return [(r, c) for r in range(GRID) for c in range(GRID)]


def _diff(band: str, p: Tuple[int, int], q: Tuple[int, int]) -> str:
    return f"mDiffFit_{_image(band, *p)}_{q[0]:02d}{q[1]:02d}"


def functions() -> List[Tuple[str, str]]:
    """(name, task type) in the file's order: band-major, each band in
    topological order, then the join."""
    out = []
    for b in BANDS:
        out += [(f"mProjectPP_{_image(b, r, c)}", "mProjectPP")
                for r, c in _cells()]
        out += [(_diff(b, p, q), "mDiffFit") for p, q in _pairs()]
        out += [(f"mConcatFit_{b}", "mConcatFit"), (f"mBgModel_{b}",
                                                    "mBgModel")]
        out += [(f"mBackground_{_image(b, r, c)}", "mBackground")
                for r, c in _cells()]
        out += [(f"mImgtbl_{b}", "mImgtbl"), (f"mAdd_{b}", "mAdd"),
                (f"mShrink_{b}", "mShrink")]
    out.append(("mJPEG", "mJPEG"))
    return out


def edges() -> List[Tuple[str, str]]:
    out = []
    for b in BANDS:
        for p, q in _pairs():
            diff = _diff(b, p, q)
            out += [(f"mProjectPP_{_image(b, *p)}", diff),
                    (f"mProjectPP_{_image(b, *q)}", diff),
                    (diff, f"mConcatFit_{b}")]
        out.append((f"mConcatFit_{b}", f"mBgModel_{b}"))
        for r, c in _cells():
            image = _image(b, r, c)
            out += [(f"mBgModel_{b}", f"mBackground_{image}"),
                    (f"mProjectPP_{image}", f"mBackground_{image}"),
                    (f"mBackground_{image}", f"mImgtbl_{b}")]
        out += [(f"mImgtbl_{b}", f"mAdd_{b}"), (f"mAdd_{b}", f"mShrink_{b}"),
                (f"mShrink_{b}", "mJPEG")]
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


#: the tail every source-to-sink path shares, after the background pass
_TAIL = ("mBackground", "mImgtbl", "mAdd", "mShrink", "mJPEG")


def _paths() -> List[Tuple[str, ...]]:
    """Each source-to-sink path of one band, as task types: through the
    global fit, or from a projection straight to its correction."""
    return [("mProjectPP", "mDiffFit", "mConcatFit", "mBgModel", *_TAIL),
            ("mProjectPP", *_TAIL)]


def latency(rt: Dict[str, np.ndarray]) -> np.ndarray:
    """An instance's latency under per-type runtimes: every band has the
    same paths, so the longest of them."""
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
        "name": "montage",
        "source": "Montage mosaic workflow (Pegasus; Bharathi et al., WORKS "
                  "2008) as serverless functions: DEWE v3 (Jiang et al., "
                  "2017), HyperFlow on AWS Lambda (Malawski et al., FGCS "
                  "2020)",
        "deployment": f"one {len(BANDS)}-band colour mosaic of {GRID}x{GRID} "
                      f"images per band, overlaps between 8-neighbours "
                      f"({len(names)} functions, {len(edges())} edges, one "
                      f"component), replayed on one chip; incumbent "
                      f"{cpu:g} vCPU, {mem:g} MB per function",
        "workflow": "montage",
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
