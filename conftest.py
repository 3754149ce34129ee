"""Pytest bootstrap: import paths + optional-dependency gating.

- Puts `src/` (the package) and the repo root (for `benchmarks.*`) on
  sys.path, so `PYTHONPATH=src` is no longer load-bearing (mirrors the
  `pythonpath` pytest config in pyproject.toml for older runners).
- If `pytest-timeout` is not installed, registers the watchdog fallback
  in `tests/_pytest_timeout_fallback.py` (same ini/CLI/marker surface),
  so a deadlocked engine test aborts the run in minutes — with all
  thread stacks dumped — instead of hanging CI to its job timeout.
"""

from __future__ import annotations

import importlib.util
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

try:
    import pytest_timeout  # noqa: F401  (the real plugin wins when present)

    _timeout_fallback = None
except ImportError:
    _tspec = importlib.util.spec_from_file_location(
        "_repro_pytest_timeout_fallback",
        ROOT / "tests" / "_pytest_timeout_fallback.py",
    )
    _timeout_fallback = importlib.util.module_from_spec(_tspec)
    sys.modules["_repro_pytest_timeout_fallback"] = _timeout_fallback
    _tspec.loader.exec_module(_timeout_fallback)


def pytest_addoption(parser):
    if _timeout_fallback is not None:
        _timeout_fallback.add_options(parser)


def pytest_configure(config):
    if _timeout_fallback is not None:
        config.pluginmanager.register(
            _timeout_fallback.TimeoutFallbackPlugin(config),
            "repro-timeout-fallback")
