"""``correct`` at a size a test run holds, on the CPU: a sound run passes;
the control (the reference in float8) and every planted fault fail, under
each cell's own limits.  The chip readings that set those limits come from
``bench/readings.py`` at the cells' own sizes.

    JAX_PLATFORMS=cpu python -m pytest -q tests/bench
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import bench_tiny as tiny  # noqa: E402

import faults as FL  # noqa: E402
import harness as H  # noqa: E402

TRAIN = "gpt-h1024.train.b16"
SERVE = "internlm2-1.8b.serve.chat"
TMP4 = "gpt-h2048.tmp4.train.b16"
CPU_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


@pytest.fixture(autouse=True)
def cpu_run(monkeypatch, tmp_path):
    """Runs on the CPU: a compile cache of the test's own, and stand-in
    peaks (the table has none for a CPU, and must not)."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    monkeypatch.setattr(H, "peaks", lambda kind: CPU_PEAKS)


def _run(name, **kw):
    return tiny.run(tiny.tiny_cell(name), **kw)


def test_sound_training_run_is_correct():
    r = _run(TRAIN)
    assert r["correct"], r["checks"]


def test_sound_serving_run_is_correct():
    r = _run(SERVE, seconds=3.0)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["served_compared"] > 0


def test_training_control_fails():
    import train_cell as C
    cell = tiny.tiny_cell(TRAIN)
    devs = __import__("jax").devices()[:1]
    ref = C.reference_readings(cell, 11, devs)
    ctrl = C.reference_readings(cell, 11, devs, prec="fp8")
    gaps = C.compare(ctrl, ref)
    assert any(v > cell.limits[k] for k, v in gaps.items()), gaps


def test_serving_control_fails():
    import serve_cell as C
    cell = tiny.tiny_cell(SERVE)
    r = C.run(cell, seed=12, seconds=3.0, trace=False,
              devs=__import__("jax").devices()[:1], clog=H.CompileLog(),
              control=True)
    assert r["control_widest"] > cell.limits["widest_logit_gap"], r


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_training_fault_fails(fault):
    with FL.FAULTS[fault]():
        r = _run(TRAIN)
    assert not r["correct"], r["checks"]


def test_serving_token_altered_fails():
    with FL.token():
        r = _run(SERVE, seconds=3.0)
    assert not r["correct"], r["checks"]


_FOUR = r"""
import json, sys
sys.path.insert(0, {here!r})
import bench_tiny as tiny, harness as H, faults as FL
H.peaks = lambda kind: {peaks!r}
cell = tiny.tiny_cell({name!r})
cell.config.update(mesh="1x4")
cell.chips = 4
fault = {fault!r}
with (FL.FAULTS[fault]() if fault else __import__("contextlib").nullcontext()):
    r = tiny.run(cell)
print("RESULT", json.dumps({{"correct": r["correct"], "checks": r["checks"]}}))
"""


@pytest.mark.parametrize("fault", [None, "exchange"])
def test_four_chip_training(fault, tmp_path):
    """The four-chip cell, cut to a tiny size, on four CPU devices with
    its reference split over them: sound, and with the TMP all-reduce
    left out."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jc"))
    src = _FOUR.format(here=str(HERE), peaks=CPU_PEAKS, name=TMP4,
                       fault=fault)
    p = subprocess.run([sys.executable, "-c", src], env=env,
                       capture_output=True, text=True, timeout=900)
    line = [x for x in p.stdout.splitlines() if x.startswith("RESULT")]
    assert p.returncode == 0 and line, p.stderr[-3000:]
    r = json.loads(line[0][len("RESULT"):])
    assert r["correct"] == (fault is None), r["checks"]


def _synthetic_trace(monkeypatch, name):
    """A CPU has no device plane to trace: the run's own profile is
    swapped for a synthetic one."""
    import test_bench_harness as TH
    TR = H._load(H.BENCH / "trace.py", "bench_trace")
    real = TR.Trace.from_dir

    def synthetic(trace_dir):
        real(trace_dir)               # the run did write a profile
        tr = TH._trace()
        if name == SERVE:
            tr.modules = {}
        return tr

    monkeypatch.setattr(TR.Trace, "from_dir", staticmethod(synthetic))


@pytest.mark.parametrize("name", [TRAIN, SERVE])
def test_traced_run_reads_its_per_layer_metrics(name, monkeypatch):
    """Every per-layer metric of the cell that has something to read in
    the synthetic trace comes out, with the breakdown."""
    _synthetic_trace(monkeypatch, name)
    cell = tiny.tiny_cell(name)
    r = tiny.run(cell, seconds=3.0, trace=1)
    want = {m["name"] for m in cell.per_layer()}
    if name == TRAIN:
        want -= {"train.matmul_roofline", "train.step_mfu",
                 "train.step_device_ms"}     # the synthetic trace's module
    assert want <= set(r["per_layer"]), (want, r["per_layer"])
    assert r["busy_s"] > 0 and r["window_s"] > 0
    assert len(r["breakdown"]["device_ops"]) <= 10
    assert r["ctx"]["config"] == cell.config


@pytest.mark.parametrize("name", [TRAIN, SERVE])
def test_phase_report_reads_the_runs_phases(name, monkeypatch):
    """``phase_report.py`` reads the phase split that the run handed its
    readers: its numbers are the result line's."""
    import jax
    import phase_report as PR
    _synthetic_trace(monkeypatch, name)
    out = PR.main(["--workload", name, "--seed", "5", "--seconds", "3"],
                  cell=tiny.tiny_cell(name), devices=jax.devices()[:1])
    shared = set(out["phases"]) & set(out["per_layer"])
    assert len(shared) >= 3
    assert {k: out["phases"][k] for k in shared} == \
        {k: out["per_layer"][k] for k in shared}
    if name == TRAIN:
        assert sum(out["busy_ms_by_pass"].values()) == \
            pytest.approx(out["step_device_ms"])
    else:
        assert out["finished"] > 0 and out["stamps_ordered"]
