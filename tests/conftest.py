import os
import sys

# smoke tests and benches must see ONE device — the 512-device forcing
# belongs exclusively to launch/dryrun.py (see the assignment).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture
def fresh_memo(monkeypatch):
    """Empty memos of quantized values (``repro.core.resources``),
    whatever earlier tests stored."""
    from repro.core import resources

    monkeypatch.setattr(resources, "_CPU_MEMO", {})
    monkeypatch.setattr(resources, "_MEM_MEMO", {})
