"""A new configuration enters the benchmark through new files alone: a
configuration file with a key outside the sizes every dense file states, a
reference module with its own FLOP count, a limits file, and a per-layer
reader of the configuration and of the program's named scopes.  They are
written beside a copy of ``bench/``'s own files, none of which changes, and
a tiny cell built from them runs on the CPU.

    JAX_PLATFORMS=cpu python -m pytest -q tests/bench/test_bench_new_config.py
"""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import bench_tiny as tiny  # noqa: E402

import flops as F  # noqa: E402
import harness as H  # noqa: E402
import phases as PH  # noqa: E402

NAME = "tiny-window.train"
CPU_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
SCOPE = "tmp.oases.sub0"

REFERENCE = '''"""The dense decoder, with a FLOP count of its own: three times the
dense one, as a model whose count differs from the dense formula has."""
import harness as H

_D = H.reference({"reference": "dense_decoder"})
globals().update({k: v for k, v in vars(_D).items()
                  if not k.startswith("__")})


def model_flops_per_token(model, seq_len):
    return 3.0 * _D.model_flops_per_token(model, seq_len)
'''

READER = '''"""The configuration's FLOPs per traced step over the chip's peak and
chip 0's time in the scope the configuration names."""
import harness as H


def read(ctx):
    cfg, ph = ctx.get("config"), ctx.get("phases")
    if not cfg or not ph or not ctx.get("steps"):
        return None
    t = ph["busy_s_by_scope"].get(cfg["scope"])
    if not t:
        return None
    flops = H.count(cfg, "model_flops_per_token")(
        cfg["model"], cfg["seq_len"]) * cfg["global_batch"] \\
        * cfg["seq_len"] * ctx["steps"] / ctx["chips"]
    return 100.0 * flops / (t * ctx["peak_flops"])
'''


@pytest.fixture
def new_files(tmp_path, monkeypatch):
    """A checkout holding ``bench/`` as it is, the new files beside its
    own, and ``BENCHMARK.json`` with the new configuration, cell and
    metric added."""
    bench = tmp_path / "bench"
    shutil.copytree(H.BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    cfg = tiny.tiny_cell("gpt-h1024.train.b16").config
    cfg.update(name="tiny-window", reference="tiny_counted", scope=SCOPE)
    cfg["model"]["window"] = 16
    new = {"configs/tiny-window.json": json.dumps(cfg),
           "references/tiny_counted.py": REFERENCE,
           "metrics/tiny.scope_flops_pct.py": READER,
           f"limits/{NAME}.json":
               (bench / "limits" / "gpt-h1024.train.b16.json").read_text()}
    for rel, text in new.items():
        assert not (H.BENCH / rel).exists()
        (bench / rel).write_text(text)
    bm = H.benchmark()
    bm["configs"].append({"name": "tiny-window", "source": "test",
                          "file": "bench/configs/tiny-window.json",
                          "reduced": [], "why": "test"})
    bm["workloads"].append({"name": NAME, "config": "tiny-window",
                            "traffic": "train.zipf.s1024", "chips": 1,
                            "why": "test"})
    for m in bm["end_to_end"] + bm["per_layer"]:
        if "gpt-h1024.train.b16" in m.get("workloads", []):
            m["workloads"].append(NAME)
    bm["per_layer"].append({"name": "tiny.scope_flops_pct", "unit": "%",
                            "better": "higher", "source": "device_trace",
                            "layer": "train step", "moves": "train_mfu",
                            "workloads": [NAME]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))
    before = {p: p.read_bytes() for p in H.BENCH.rglob("*")
              if p.is_file() and "__pycache__" not in p.parts}
    monkeypatch.setattr(H, "BENCH", bench)
    monkeypatch.setattr(H, "CHECKOUT", tmp_path)
    monkeypatch.setattr(H, "peaks", lambda kind: CPU_PEAKS)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    yield cfg
    after = {p: p.read_bytes() for p in before}
    assert after == before


def test_new_configuration_reaches_the_program(new_files):
    a = H.program_config(H.Cell(NAME).config)
    assert (a.window, a.num_layers, a.d_model) == (16, 2, 64)


def test_new_reference_counts_the_mfu(new_files):
    """``train_mfu`` comes from the new reference's own count."""
    cfg = new_files
    r = tiny.run(H.Cell(NAME), seconds=2.0)
    assert r["correct"], r["checks"]
    e = r["e2e"]
    per_token = 3.0 * F.model_flops_per_token(cfg["model"], cfg["seq_len"])
    assert e["train_mfu"] == pytest.approx(
        100.0 * per_token * e["train_tokens_per_s"]
        / CPU_PEAKS["bf16_flops_per_s"], rel=1e-12)


def test_new_reader_sees_config_and_scopes(new_files, monkeypatch):
    """A traced run hands the new reader the configuration and chip 0's
    time by named scope.  A CPU has no device plane to trace: the run's
    own profile is swapped for a synthetic one, whose ops the step's
    attribution is given as well."""
    import test_bench_harness as TH
    import test_bench_phases as TP
    real_from_dir, real_paths = PH.TR.Trace.from_dir, PH.scope_paths

    def synthetic(trace_dir):
        real_from_dir(trace_dir)          # the run did write a profile
        return TH._trace()

    monkeypatch.setattr(PH.TR.Trace, "from_dir", staticmethod(synthetic))
    monkeypatch.setattr(PH, "scope_paths",
                        lambda text: {**real_paths(text), **TP.PATHS})
    cfg = new_files
    r = tiny.run(H.Cell(NAME), seconds=3.0, trace=1)
    ctx = r["ctx"]
    assert ctx["config"] == cfg and ctx["steps"] == 1
    t = ctx["phases"]["busy_s_by_scope"][SCOPE]
    assert t == pytest.approx(40e-6)      # fusion.7 in the synthetic trace
    flops = 3.0 * F.model_flops_per_token(cfg["model"], cfg["seq_len"]) \
        * cfg["global_batch"] * cfg["seq_len"]
    assert r["per_layer"]["tiny.scope_flops_pct"]["value"] == \
        pytest.approx(100.0 * flops / (t * CPU_PEAKS["bf16_flops_per_s"]))
    for name in ("train.fwd_ms", "train.bwd_ms", "train.opt_ms"):
        assert r["per_layer"][name]["value"] == pytest.approx(
            PH.READERS[name](ctx))
