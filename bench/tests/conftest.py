"""The benchmark's own tests run on the CPU at tiny sizes; they steer
the harness's device check themselves (``CPU_DEVICE``)."""
import copy
import json
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402

from bench import harness  # noqa: E402

#: what ``harness.accelerator`` would return; tests hand it to the
#: harness in place of the chip check
CPU_DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}


#: traffic mixes kept under ``bench/traffic/`` for a later cell, with the
#: configuration each is meant for: (configuration file, mix)
KEPT = {"video.capacity": ("video-analysis", "video.capacity")}


def cell_of(name: str) -> harness.Cell:
    """A cell of ``BENCHMARK.json``, or one made of a kept mix and its
    configuration, measured by the end-to-end metrics every cell has."""
    if name not in KEPT:
        return harness.load_cell(name)
    config, traffic = KEPT[name]
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    return harness.Cell(
        name=name, root=ROOT, chips=1,
        config=json.loads((ROOT / "bench" / "configs"
                           / f"{config}.json").read_text()),
        mix=json.loads((ROOT / "bench" / "traffic"
                        / f"{traffic}.json").read_text()),
        end_to_end=[m for m in spec["end_to_end"] if "workloads" not in m],
        per_layer=[])


def tiny(name: str, candidates: int = 3, instances: int = 24,
         check_calls: int = 2) -> harness.Cell:
    """A cell with its mix cut to a size a CPU test holds."""
    cell = cell_of(name)
    mix = copy.deepcopy(cell.mix)
    mix["candidates"]["count"] = candidates
    mix["arrivals"]["count"] = instances
    mix["check_calls"] = check_calls
    cell.mix = mix
    return cell


@pytest.fixture(autouse=True)
def _jax_cache_settings():
    """The harness sets JAX's compile-cache options for its process;
    put them back, so that other tests in this worker compile as they
    would alone."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    saved = {k: getattr(jax.config, k) for k in harness.CACHE_SETTINGS}
    yield
    for key, value in saved.items():
        jax.config.update(key, value)
    compilation_cache.reset_cache()
