"""The program's own phases in a trace, on the CPU: the pass and TMP scope
of each compiled instruction, the split of a synthetic trace by pass, scope
and program span, and the per-step and per-tick numbers read from it.

    JAX_PLATFORMS=cpu python -m pytest -q tests/bench/test_bench_phases.py
"""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "bench"))

import flops as F  # noqa: E402
import phases as P  # noqa: E402
from test_bench_harness import AR, CP, EW, MM, _xspace  # noqa: E402

TR = P.TR
US = 1e-6


# --------------------------------------------------------------------------
# attribution
# --------------------------------------------------------------------------
@pytest.mark.parametrize("path,pass_,scope", [
    ("jit(train_step)/jvp()/shard_map/while/body/closed_call/"
     "tmp.oases.sub0/tmp.oases.row_matmul/tmp.reduce/psum", "fwd",
     "tmp.oases.sub0/tmp.oases.row_matmul/tmp.reduce"),
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/tmp.oases.sub1/dot_general", "bwd",
     "tmp.oases.sub1"),
    ("jit(train_step)/optim.adamw/sqrt", "opt", ""),
    ("jit(train_step)/train.grad_accum/convert_element_type", "other", ""),
    ("params[\\'embed\\']", "other", ""),
])
def test_pass_and_scope_of_a_path(path, pass_, scope):
    assert P.pass_of(path) == pass_
    assert P.tmp_scope_of(path) == scope


def test_fusion_without_metadata_takes_its_root():
    text = """HloModule jit_step, entry_computation_layout={()->()}

%fused_computation.3 (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %neg.1 = f32[8]{0} negate(%p0), metadata={op_name="jit(step)/jvp()/neg"}
  ROOT %mul.1 = f32[8]{0} multiply(%neg.1, %p0), metadata={op_name="jit(step)/transpose(jvp())/tmp.megatron.sub0/mul;jit(step)/jvp()/mul"}
}

ENTRY %main (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %fusion.3 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation.3
  ROOT %add.4 = f32[8]{0} add(%fusion.3, %x), metadata={op_name="jit(step)/optim.adamw/add"}
}
"""
    att = P.attribute(text)
    assert att["fusion.3"] == ("bwd", "tmp.megatron.sub0")
    assert att["neg.1"] == ("fwd", "")
    assert att["add.4"] == ("opt", "")
    assert att["x"] == ("other", "")


@pytest.fixture(scope="module")
def tiny_step_hlo():
    """The compiled text of a tiny ``build_train_step`` on one CPU
    device."""
    import jax
    from repro.configs.base import TrainHParams
    from repro.configs.registry import get_config
    from repro.core import compat
    from repro.launch import steps
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    cfg = get_config("gpt-h1024").reduced()
    hp = TrainHParams(schedule="oases")
    fn, _ = steps.build_train_step(cfg, mesh, hp, global_batch=4,
                                   seq_len=32)
    args = steps.train_abstract_inputs(cfg, mesh, hp, global_batch=4,
                                       seq_len=32)
    with jax.set_mesh(mesh):
        return jax.jit(fn).lower(*args).compile().as_text()


def test_tiny_train_step_passes(tiny_step_hlo):
    """Every matmul, and every op that calls one, is forward or backward;
    the optimizer's fusions are ``opt``; ``other`` runs no matmul."""
    att = P.attribute(tiny_step_hlo)
    dots = F.HloDots(tiny_step_hlo)
    mm = dots.matmul_computations()

    def calls(rest):
        m = TR._CALLS_RE.search(rest)
        return m.group(1) if m else None

    matmuls = [n for ops in dots.comps.values()
               for n, kind, _, rest in ops
               if kind in ("dot", "convolution")
               or (kind == "fusion" and calls(rest) in mm)]
    assert matmuls
    assert {att[n][0] for n in matmuls} == {"fwd", "bwd"}
    entry = {n: kind for n, kind, _, _ in dots.comps[dots.entry]}
    opt = [n for n, kind in entry.items()
           if kind == "fusion" and att[n][0] == "opt"]
    assert len(opt) >= 4, opt
    assert all(att[n][0] != "opt" for n in matmuls)


# --------------------------------------------------------------------------
# the split of a trace
# --------------------------------------------------------------------------
PASSES = {"fusion.7": ("fwd", "tmp.oases.sub0"),
          "all-reduce.1": ("bwd", "tmp.oases.sub1/tmp.reduce"),
          "add.2": ("opt", ""),
          "collective-permute-start.1": ("fwd", "")}
PROGRAM = [("serve.admit", 0, 12), ("serve.dispatch", 28, 42),
           ("serve.sync", 48, 62), ("serve.emit", 88, 100)]


def _profile(program=()):
    from jax.profiler import ProfileData
    device = [(MM, 10, 30), (AR, 40, 50), (MM, 60, 70), (EW, 70, 75),
              (MM, 80, 90), (EW, 200, 210)]
    asyncs = [(CP, 65, 85)]
    modules = [("jit_train_step(123)", 10, 90), ("jit_other(9)", 200, 210)]
    spans = [("bench.window", 0, 100), ("bench.data", 30, 38),
             ("bench.read", 90, 100), ("bench.dispatch", 0, 9)]
    return ProfileData.from_text_proto(
        _xspace(device, asyncs, modules, spans + list(program)))


def _split():
    pd = _profile(PROGRAM)
    tr = TR.Trace.from_profile(pd)
    return tr, P.reduce(tr, P.program_spans(pd), PASSES,
                        module="jit_train_step")


def test_split_by_pass_adds_up_to_busy():
    tr, ph = _split()
    red = TR.reduce(tr, module="jit_train_step")
    by = ph["busy_s_by_pass"]
    assert by["fwd"] == pytest.approx(40 * US)      # 10-30, 60-70, 80-90
    assert by["bwd"] == pytest.approx(10 * US)      # the all-reduce
    assert by["opt"] == pytest.approx(5 * US)       # 70-75
    assert by["other"] == pytest.approx(0.0)
    assert sum(by.values()) == pytest.approx(red["busy_s_dev0"])


def test_collectives_by_pass_and_scope():
    tr, ph = _split()
    red = TR.reduce(tr, module="jit_train_step")
    x = ph["exposed_s_by_pass"]
    assert x["bwd"] == pytest.approx(10 * US)       # all of 40-50
    assert x["fwd"] == pytest.approx(5 * US)        # 75-80 of the permute
    assert sum(x.values()) == pytest.approx(red["exposed_collective_s"])
    assert sum(ph["collective_s_by_pass"].values()) == \
        pytest.approx(red["collective_s"])
    rows = {(p, s): (c, e) for p, s, c, e in ph["collective_by_scope"]}
    assert rows[("fwd", P.NO_SCOPE)] == pytest.approx((20 * US, 5 * US))
    assert rows[("bwd", "tmp.oases.sub1/tmp.reduce")] == \
        pytest.approx((10 * US, 10 * US))


def test_idle_by_program_span():
    tr, ph = _split()
    idle = dict(ph["idle_by_program_span"])
    assert idle == pytest.approx({"serve.admit": 10 * US,
                                  "serve.dispatch": 10 * US,
                                  "serve.sync": 10 * US,
                                  "serve.emit": 10 * US,
                                  P.NO_SPAN: 5 * US})
    red = TR.reduce(tr, module="jit_train_step")
    assert ph["idle_s_dev0"] == pytest.approx(
        red["window_s"] - red["busy_s_dev0"])
    gaps = ph["longest_idle_gaps"]
    assert [round(v / US) for _, v in gaps] == [10, 10, 10, 10, 5]
    assert gaps[-1][0] == P.NO_SPAN


def test_program_spans_leave_the_old_reduction_alone():
    """The program's host spans add keys of their own: every key the
    reduction had, ``bench.*`` idle labels included, reads as before."""
    before = TR.reduce(TR.Trace.from_profile(_profile()),
                       module="jit_train_step",
                       matmul_computations={"fused_computation.7"})
    after = TR.reduce(TR.Trace.from_profile(_profile(PROGRAM)),
                      module="jit_train_step",
                      matmul_computations={"fused_computation.7"})
    assert after == before
    assert P.program_spans(_profile()) == []
    assert [n for n, _, _ in P.program_spans(_profile(PROGRAM))] == \
        [n for n, _, _ in PROGRAM]


def test_innermost_nested_and_overlapping():
    pieces = P.innermost([(0, 10, "loop"), (2, 4, "a"), (3, 6, "b"),
                          (12, 13, "c")])
    assert pieces == [(0, 2, "loop"), (2, 3, "a"), (3, 6, "b"),
                      (6, 10, "loop"), (12, 13, "c")]


# --------------------------------------------------------------------------
# per-step and per-tick numbers
# --------------------------------------------------------------------------
def _contexts():
    tr, ph = _split()
    red = TR.reduce(tr, module="jit_train_step")
    train = {"kind": "train", "trace": red, "phases": ph, "steps": 2}
    serve = {"kind": "serve", "trace": red, "phases": ph,
             "ticks_traced": 4, "slot_ticks": 30, "ticks": 10, "slots": 4,
             "request_times": [(0.0, 0.01, 0.5), (0.1, 0.1, 0.3),
                               (0.2, None, None)]}
    return train, serve


TRAIN_READERS = {"train.fwd_ms", "train.bwd_ms", "train.opt_ms",
                 "tmp.fwd_exposed_ms", "tmp.bwd_exposed_ms"}


@pytest.mark.parametrize("name", sorted(P.READERS))
def test_reader_reads_only_its_kind_of_cell(name):
    train, serve = _contexts()
    mine, other = (train, serve) if name in TRAIN_READERS else (serve, train)
    assert P.READERS[name](mine) is not None
    assert P.READERS[name](other) is None
    assert P.READERS[name]({}) is None


def test_reader_values():
    train, serve = _contexts()
    got = P.read_all(train)
    assert got == pytest.approx({
        "train.fwd_ms": 40 * US * 1e3 / 2, "train.bwd_ms": 10 * US * 1e3 / 2,
        "train.opt_ms": 5 * US * 1e3 / 2,
        "tmp.fwd_exposed_ms": 5 * US * 1e3 / 2,
        "tmp.bwd_exposed_ms": 10 * US * 1e3 / 2})
    got = P.read_all(serve)
    assert got["serve.slot_occupancy_pct"] == pytest.approx(75.0)
    assert got["serve.engine_idle_ms"] == pytest.approx(40 * US * 1e3 / 4)
    # the unfinished request is left out; p95 of two values interpolates
    assert got["serve.queue_wait_p95_ms"] == pytest.approx(0.95 * 10.0)
    assert got["serve.prefill_p95_ms"] == pytest.approx(
        200.0 + 0.95 * (490.0 - 200.0))
