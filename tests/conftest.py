"""Suite-wide fixtures/shims.

Prefers the real ``hypothesis`` (declared in requirements.txt); in
environments where it cannot be installed, registers the deterministic
fallback from ``_hypothesis_stub`` so the property-based tests still run
instead of failing collection."""

from __future__ import annotations

import importlib.util
import os
import sys
from pathlib import Path

import pytest

sys.path.insert(0, os.path.dirname(__file__))

try:
    import hypothesis  # noqa: F401  (the real thing, when available)
except ModuleNotFoundError:
    import _hypothesis_stub

    _hypothesis, _strategies = _hypothesis_stub._as_modules()
    sys.modules["hypothesis"] = _hypothesis
    sys.modules["hypothesis.strategies"] = _strategies


@pytest.fixture(scope="session")
def chip_smoke():
    """The repo-root ``chip_smoke.py`` script, imported as a module."""
    path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
