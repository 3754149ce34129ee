"""Minimal stand-in for `pytest-timeout` (used when the real plugin is
unavailable — this repo must run without network installs).

Implements the surface the suite relies on: the ``timeout`` ini option,
the ``--timeout`` command-line option, and the ``@pytest.mark.timeout(N)``
marker (marker > command line > ini).  Each test runs under a daemon
`threading.Timer`; on expiry the watchdog prints the offending test id,
dumps every thread's stack via `faulthandler` (so a deadlocked
`ClusterEngine` names the threads holding it up), and hard-exits the
process — a hung chaos test fails CI in minutes instead of stalling the
job until its 45-minute kill.  A hard exit (`os._exit`) is the point,
not a shortcut: a thread wedged on an un-interruptible lock can never be
unwound into a polite test failure.

``conftest.py`` registers this plugin only when ``import pytest_timeout``
fails, so installing the real plugin transparently takes over.
"""

from __future__ import annotations

import faulthandler
import os
import sys
import threading

import pytest

_DEFAULT = 0.0          # 0 = no timeout unless configured


def add_options(parser) -> None:
    """Register the ini/CLI options the real plugin would own."""
    parser.addini("timeout",
                  "per-test timeout in seconds (0 = disabled); "
                  "vendored pytest-timeout fallback", default=str(_DEFAULT))
    parser.addoption("--timeout", action="store", dest="timeout",
                     default=None,
                     help="per-test timeout in seconds (0 = disabled); "
                          "vendored pytest-timeout fallback")


def _configured_timeout(item) -> float:
    marker = item.get_closest_marker("timeout")
    if marker is not None and marker.args:
        return float(marker.args[0])
    cli = item.config.getoption("timeout", default=None)
    if cli is not None:
        return float(cli)
    try:
        return float(item.config.getini("timeout") or 0.0)
    except ValueError:
        return _DEFAULT


def _expired(item, seconds: float) -> None:
    # pytest's fd-level capture would swallow the diagnostics; suspend it
    # (same move the real pytest-timeout makes) so the dump reaches CI.
    capman = item.config.pluginmanager.getplugin("capturemanager")
    if capman is not None:
        try:
            capman.suspend_global_capture(in_=True)
        except Exception:
            pass
    sys.stderr.write(
        f"\n+++ repro timeout watchdog: {item.nodeid!r} exceeded "
        f"{seconds:g}s; dumping all thread stacks and aborting the run "
        "+++\n")
    sys.stderr.flush()
    faulthandler.dump_traceback(file=sys.stderr, all_threads=True)
    sys.stderr.flush()
    os._exit(70)


class TimeoutFallbackPlugin:
    """Per-test watchdog timer (vendored pytest-timeout substitute)."""

    def __init__(self, config):
        config.addinivalue_line(
            "markers",
            "timeout(seconds): per-test timeout (vendored pytest-timeout "
            "fallback; the real plugin takes over when installed)")

    @pytest.hookimpl(hookwrapper=True)
    def pytest_runtest_protocol(self, item, nextitem):
        seconds = _configured_timeout(item)
        if seconds <= 0:
            yield
            return
        timer = threading.Timer(seconds, _expired, args=(item, seconds))
        timer.daemon = True
        timer.start()
        try:
            yield
        finally:
            timer.cancel()
