# NOTE: deliberately no XLA_FLAGS here — smoke tests must see the real
# (1-device) topology.  Multi-device tests spawn subprocesses that set
# --xla_force_host_platform_device_count themselves (tests/_scripts/).
import os
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)


@pytest.fixture(scope="session")
def smoke_mesh():
    from repro.core import compat
    return compat.make_mesh((1, 1), ("data", "model"))


def subprocess_env():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.abspath(SRC)
    return env


SCRIPTS = os.path.join(os.path.dirname(__file__), "_scripts")


def run_script(name, timeout=900):
    """Run ``tests/_scripts/<name>`` on 8 virtual devices; assert it exits
    cleanly and reports at least one PASS and no FAIL line; return the
    PASS/FAIL lines."""
    import subprocess
    p = subprocess.run([sys.executable, os.path.join(SCRIPTS, name)],
                       capture_output=True, text=True, timeout=timeout,
                       env=subprocess_env())
    out = p.stdout + p.stderr
    assert p.returncode == 0, out[-3000:]
    lines = [ln for ln in p.stdout.splitlines()
             if ln.startswith(("PASS", "FAIL"))]
    assert lines, out[-2000:]
    bad = [ln for ln in lines if ln.startswith("FAIL")]
    assert not bad, "\n".join(lines)
    return lines


def optional_hypothesis():
    """``(given, settings, st)`` from hypothesis, or decoration-safe stubs
    whose ``given`` marks the decorated test skipped — so missing the
    optional dep skips ONLY the property tests, not the module's plain
    tests (a module-level importorskip would take those down too)."""
    try:
        from hypothesis import given, settings, strategies as st
        return given, settings, st
    except ModuleNotFoundError:
        def given(*_a, **_k):
            return lambda f: pytest.mark.skip(
                reason="needs hypothesis (pip install -r "
                       "requirements-dev.txt)")(f)

        def settings(*_a, **_k):
            return lambda f: f

        class _Strategies:
            def __getattr__(self, _name):
                return lambda *a, **k: None

        return given, settings, _Strategies()
