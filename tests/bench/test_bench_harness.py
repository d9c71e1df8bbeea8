"""The benchmark's yardstick on the CPU: the trace reduction on a synthetic
trace in the profiler's format, the FLOP counts, the peaks table, the
traffic generator and the files ``BENCHMARK.json`` names.

    JAX_PLATFORMS=cpu python -m pytest -q tests/bench
"""
from __future__ import annotations

import json
import re
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[2] / "bench"
sys.path.insert(0, str(BENCH))

import flops as F  # noqa: E402
import harness as H  # noqa: E402
import traffic as T  # noqa: E402

TR = H._load(BENCH / "trace.py", "bench_trace")


# --------------------------------------------------------------------------
# trace reduction
# --------------------------------------------------------------------------
def _xspace(device_events, async_events, modules, spans) -> str:
    """An XSpace text proto: times in microseconds."""
    meta, lines = {}, []

    def mid(name):
        return meta.setdefault(name, len(meta) + 1)

    def line(lid, name, events):
        evs = "".join(
            f"events {{ metadata_id: {mid(n)} offset_ps: {int(s * 1e6)} "
            f"duration_ps: {int((e - s) * 1e6)} }}\n" for n, s, e in events)
        return f"lines {{ id: {lid} name: \"{name}\" timestamp_ns: 0\n{evs}}}\n"

    def metadata():
        return "".join(
            f"event_metadata {{ key: {i} value {{ id: {i} name: "
            f"{json.dumps(n)} }} }}\n" for n, i in meta.items())

    dev = (line(1, "XLA Ops", device_events)
           + line(2, "Async XLA Ops", async_events)
           + line(3, "XLA Modules", modules))
    dev_meta = metadata()
    meta.clear()
    host = line(1, "python", spans)
    return (f"planes {{ id: 1 name: \"/device:TPU:0\"\n{dev}{dev_meta}}}\n"
            f"planes {{ id: 2 name: \"/host:CPU\"\n{host}{metadata()}}}\n")


MM = ("%fusion.7 = bf16[8,8]{1,0} fusion(bf16[8,8]{1,0} %a), kind=kOutput, "
      "calls=%fused_computation.7")
EW = "%add.2 = f32[8]{0} add(f32[8]{0} %x, f32[8]{0} %y)"
AR = "%all-reduce.1 = f32[8]{0} all-reduce(f32[8]{0} %x), to_apply=%sum"
CP = ("%collective-permute-start.1 = (f32[8]{0}, f32[8]{0}) "
      "collective-permute-start(f32[8]{0} %x), source_target_pairs={{0,1}}")


def _trace():
    from jax.profiler import ProfileData
    device = [(MM, 10, 30), (AR, 40, 50), (MM, 60, 70), (EW, 70, 75),
              (MM, 80, 90), (EW, 200, 210)]
    asyncs = [(CP, 65, 85)]
    modules = [("jit_train_step(123)", 10, 90), ("jit_other(9)", 200, 210)]
    spans = [("bench.window", 0, 100), ("bench.data", 30, 38),
             ("bench.read", 90, 100), ("bench.dispatch", 0, 9)]
    pd = ProfileData.from_text_proto(_xspace(device, asyncs, modules, spans))
    return TR.Trace.from_profile(pd)


def test_trace_busy_idle_and_window():
    red = TR.reduce(_trace(), module="jit_train_step",
                    matmul_computations={"fused_computation.7"})
    us = 1e-6
    assert red["window_s"] == pytest.approx(100 * us)
    # busy: 10-30, 40-50, 60-75, 80-90; the op at 200 lies outside
    assert red["busy_s"] == pytest.approx(55 * us)
    assert red["module_runs"] == 1


def test_trace_collectives_and_exposure():
    red = TR.reduce(_trace(), module="jit_train_step",
                    matmul_computations={"fused_computation.7"})
    us = 1e-6
    # collectives: the all-reduce 40-50 and the async permute 65-85
    assert red["collective_s"] == pytest.approx(30 * us)
    # exposed: all of 40-50, and 75-80 of the permute (60-75, 80-90 busy)
    assert red["exposed_collective_s"] == pytest.approx(15 * us)
    assert red["matmul_s"] == pytest.approx(40 * us)
    names = dict(red["device_ops"])
    assert names["fusion.7 [matmul]"] == pytest.approx(40 * us)
    assert names["all-reduce.1 [collective]"] == pytest.approx(10 * us)


def test_trace_idle_gaps_labelled_by_host_span():
    red = TR.reduce(_trace(), module="jit_train_step",
                    matmul_computations={"fused_computation.7"})
    us = 1e-6
    gaps = {round(s / us): label for label, s in red["idle_gaps"]}
    assert gaps[10] in ("bench.read", "bench.dispatch")   # 0-10 and 90-100
    assert gaps[5] == "outside bench spans"               # 75-80
    assert [g[1] for g in red["idle_gaps"]] == sorted(
        (g[1] for g in red["idle_gaps"]), reverse=True)
    labels = {label for label, _ in red["idle_gaps"]}
    assert "bench.data" in labels                         # 30-40


def test_trace_matmul_only_inside_the_module():
    red = TR.reduce(_trace(), module="jit_other",
                    matmul_computations={"fused_computation.7"})
    assert red["matmul_s"] == 0.0


def test_op_parse():
    o = TR.Op(MM, 0.0, 1.0)
    assert (o.name, o.kind, o.calls) == ("fusion.7", "fusion",
                                         "fused_computation.7")
    assert TR.Op(CP, 0, 1).collective() and not TR.Op(EW, 0, 1).collective()
    assert TR.Op(AR, 0, 1).kind == "all-reduce"


START = ("%async-collective-start = (bf16[8]{0}, bf16[8]{0}) "
         "fusion(bf16[8]{0} %x), kind=kCustom, calls=%fused_computation.738")
CARRY = ("%fusion.1056 = (bf16[8]{0}, bf16[8]{0}) fusion(bf16[8]{0} %a), "
         "kind=kOutput, calls=%async_collective_fusion.1056")
DONE = ("%async-collective-done = bf16[8]{0} fusion(bf16[8]{0} %b), "
        "kind=kCustom, calls=%fused_computation.740")
ARS = ("%all-reduce-start.3 = f32[8]{0} all-reduce-start(f32[8]{0} %x), "
       "to_apply=%sum")
ARD = ("%all-reduce-done.3 = f32[8]{0} all-reduce-done(f32[8]{0} "
       "%all-reduce-start.3)")


def test_async_all_reduce_ends_are_exposed_collectives():
    """The TPU's asynchronous all-reduce: its start and done are
    collectives, exposed where nothing runs beside them; the fusion that
    carries the reduction's steps is a matmul kernel, not a collective."""
    from jax.profiler import ProfileData
    device = [(MM, 10, 30), (START, 30, 32), (CARRY, 32, 60),
              (DONE, 60, 70), (ARS, 75, 76), (EW, 76, 80), (ARD, 80, 82)]
    pd = ProfileData.from_text_proto(_xspace(
        device, [], [("jit_train_step(1)", 10, 82)],
        [("bench.window", 0, 100)]))
    red = TR.reduce(TR.Trace.from_profile(pd), module="jit_train_step",
                    matmul_computations={"fused_computation.7",
                                         "async_collective_fusion.1056"})
    us = 1e-6
    assert red["collective_s"] == pytest.approx((2 + 10 + 1 + 2) * us)
    assert red["exposed_collective_s"] == pytest.approx(15 * us)
    assert red["matmul_s"] == pytest.approx((20 + 28) * us)
    names = dict(red["device_ops"])
    assert names["async-collective-done [collective]"] == \
        pytest.approx(10 * us)
    assert names["fusion.1056 [matmul]"] == pytest.approx(28 * us)
    assert not TR.Op(CARRY, 0, 1).collective()
    assert all(TR.Op(x, 0, 1).collective() for x in (START, DONE, ARS, ARD))


# --------------------------------------------------------------------------
# FLOP counts
# --------------------------------------------------------------------------
@pytest.mark.parametrize("config,gflop", [("gpt-h1024", 3.03),
                                          ("gpt-h2048-tmp4", 10.88)])
def test_model_flops_per_token(config, gflop):
    cfg = H.load_json(BENCH / "configs" / f"{config}.json")
    f = F.model_flops_per_token(cfg["model"], cfg["seq_len"])
    assert abs(f / 1e9 - gflop) < 0.01


@pytest.mark.parametrize("config", ["gpt-h1024", "gpt-h2048-tmp4",
                                    "internlm2-1.8b"])
def test_counts_come_from_the_reference(config):
    """The dense decoder's reference states its counts as ``flops.py``'s,
    so each cell's FLOPs and bytes are those of the dense formula."""
    cfg = H.load_json(BENCH / "configs" / f"{config}.json")
    assert H.count(cfg, "model_flops_per_token") is F.model_flops_per_token
    assert H.count(cfg, "serve_tick") is F.serve_tick


def _module(body: str) -> str:
    return ("HloModule jit_m, entry_computation_layout={()->()}\n\n"
            + body)


def test_dot_and_convolution_flops():
    text = _module("""
%fused_computation.1 (p0: bf16[64,32], p1: bf16[32,16]) -> bf16[64,16] {
  %p0 = bf16[64,32]{1,0} parameter(0)
  %p1 = bf16[32,16]{1,0} parameter(1)
  ROOT %convolution.1 = bf16[64,16]{1,0} convolution(%p0, %p1), dim_labels=bf_io->bf
}

ENTRY %main (a: bf16[64,32], b: bf16[32,16], c: bf16[8,4,5], d: bf16[8,5,3], e: bf16[8,4,5,1]) -> bf16[8,4,3,4] {
  %a = bf16[64,32]{1,0} parameter(0)
  %b = bf16[32,16]{1,0} parameter(1)
  %c = bf16[8,4,5]{2,1,0} parameter(2)
  %d = bf16[8,5,3]{2,1,0} parameter(3)
  %e = bf16[8,4,5,1]{3,2,1,0} parameter(4)
  %fusion.1 = bf16[64,16]{1,0} fusion(%a, %b), kind=kOutput, calls=%fused_computation.1
  %dot.2 = f32[64,16]{1,0} dot(%a, %b), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %convolution.3 = bf16[8,4,3]{2,1,0} convolution(%c, %d), window={size=8 stride=7 lhs_dilate=8}, dim_labels=0bf_0io->0bf
  ROOT %convolution.4 = bf16[8,4,3,4]{3,2,1,0} convolution(%e, %d), window={size=1x4 pad=0_0x3_3 rhs_reversal=0x1}, dim_labels=0bf1_1io0->0bf1
}
""")
    d = F.HloDots(text)
    mm = 2 * 64 * 16 * 32
    batched = 2 * 8 * 4 * 3 * 5          # 8 batch, contraction 5
    padded = 2 * 8 * 4 * 4 * 3 * 5       # the padded dim meets one input
    assert d.executed_flops() == pytest.approx(2 * mm + batched + padded)
    assert "fused_computation.1" in d.matmul_computations()
    assert d.module == "jit_m"


def test_loop_trips_from_condition():
    text = _module("""
%body.1 (p: (s32[], bf16[16,16])) -> (s32[], bf16[16,16]) {
  %p = (s32[], bf16[16,16]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = bf16[16,16]{1,0} get-tuple-element(%p), index=1
  %dot.1 = bf16[16,16]{1,0} dot(%x, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  ROOT %t = (s32[], bf16[16,16]{1,0}) tuple(%i, %dot.1)
}

%cond.1 (p: (s32[], bf16[16,16])) -> pred[] {
  %p = (s32[], bf16[16,16]{1,0}) parameter(0)
  %constant.9 = s32[] constant(24)
  %i = s32[] get-tuple-element(%p), index=0
  ROOT %lt = pred[] compare(%i, %constant.9), direction=LT
}

ENTRY %main (x: bf16[16,16]) -> (s32[], bf16[16,16]) {
  %x = bf16[16,16]{1,0} parameter(0)
  %z = s32[] constant(0)
  %t = (s32[], bf16[16,16]{1,0}) tuple(%z, %x)
  ROOT %while.1 = (s32[], bf16[16,16]{1,0}) while(%t), condition=%cond.1, body=%body.1
}
""")
    assert F.HloDots(text).executed_flops() == 24 * 2 * 16 * 16 * 16


def test_serve_tick_bytes_count_only_the_live_cache():
    cfg = H.load_json(BENCH / "configs" / "internlm2-1.8b.json")
    m = cfg["model"]
    _, empty = F.serve_tick(m, [])
    _, two = F.serve_tick(m, [9, 19])
    kv = F.kv_bytes_per_token(m)
    assert kv == 98304
    assert two - empty == pytest.approx(kv * (10 + 20 + 2) + 2 * 2 * 2048)


# --------------------------------------------------------------------------
# peaks, traffic, files
# --------------------------------------------------------------------------
def test_missing_device_kind_raises():
    assert H.peaks("TPU v5 lite")["bf16_flops_per_s"] == 1.97e14
    with pytest.raises(KeyError):
        H.peaks("cpu")


def test_serve_schedule_same_work_every_seed():
    tr = H.load_json(BENCH / "traffic" / "serve.chat.poisson.json")
    a = T.serve_schedule(tr, seed=2**31 + 5, seconds=20, vocab=1000)
    b = T.serve_schedule(tr, seed=11, seconds=20, vocab=1000)
    assert len(a) == len(b) == round(tr["arrivals"]["rate_per_s"] * 20)
    for key in (lambda r: len(r.prompt), lambda r: r.max_new):
        assert sorted(map(key, a)) == sorted(map(key, b))
        assert list(map(key, a)) != list(map(key, b))
    # the same gaps in another order (each leaves out its own first one)
    gaps = lambda s: set(np.round(np.diff([r.due_s for r in s]), 9))
    assert len(gaps(a) ^ gaps(b)) <= 2
    assert all(0 <= r.due_s < 20 for r in a)
    lo, hi = tr["prompt_len"]["min"], tr["prompt_len"]["max"]
    assert all(lo <= len(r.prompt) <= hi for r in a)
    again = T.serve_schedule(tr, seed=2**31 + 5, seconds=20, vocab=1000)
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, again))


def test_train_batches_from_the_seed():
    tr = H.load_json(BENCH / "traffic" / "train.zipf.s1024.json")
    kw = dict(batch=2, seq_len=64, vocab=1000)
    a = T.train_batch(tr, seed=2**33 + 1, step=0, **kw)
    assert a.shape == (2, 65) and a.min() >= 2 and a.max() < 1000
    assert (a == T.train_batch(tr, seed=2**33 + 1, step=0, **kw)).all()
    assert (a != T.train_batch(tr, seed=2**33 + 1, step=1, **kw)).any()
    assert (a != T.train_batch(tr, seed=7, step=0, **kw)).any()


def test_traffic_law_is_a_file_found_by_name(tmp_path, monkeypatch):
    """A mix names its laws; a new law is a new file beside the others,
    and a law with no file is refused."""
    import shutil
    laws = tmp_path / "laws"
    shutil.copytree(T.LAWS, laws)
    (laws / "same_start.py").write_text(
        "import numpy as np\n"
        "def prompts(part, lengths, stream, ids):\n"
        "    head = ids(stream(5), part['prefix'])\n"
        "    return [np.concatenate([head, ids(stream(3, i), int(n))])\n"
        "            for i, n in enumerate(lengths)]\n")
    monkeypatch.setattr(T, "LAWS", laws)
    tr = H.load_json(BENCH / "traffic" / "serve.chat.poisson.json")
    plain = T.serve_schedule(tr, seed=3, seconds=5, vocab=1000)
    tr["prompts"] = {"law": "same_start", "prefix": 7}
    shared = T.serve_schedule(tr, seed=3, seconds=5, vocab=1000)
    assert all((a.prompt[:7] == shared[0].prompt[:7]).all() for a in shared)
    assert all((a.prompt == b.prompt[7:]).all()
               for a, b in zip(plain, shared))
    tr["prompts"] = {"law": "no_such_law"}
    with pytest.raises(ValueError, match="no_such_law"):
        T.serve_schedule(tr, seed=3, seconds=5, vocab=1000)


def test_cell_runner_by_traffic_kind():
    import serve_cell
    import train_cell
    assert H.Cell("gpt-h1024.train.b16").runner() is train_cell
    assert H.Cell("internlm2-1.8b.serve.chat").runner() is serve_cell
    cell = H.Cell("gpt-h1024.train.b16")
    cell.traffic = dict(cell.traffic, kind="serve")
    with pytest.raises(SystemExit):
        cell.runner()


class _Req:
    def __init__(self, rid, served, slot):
        self.rid, self.out_tokens, self._slot = rid, [0] * served, slot


def test_serving_sample_spans_admissions_and_slots():
    """The longest request, then one from each stretch of the rest in the
    order they were due, on a fresh slot where the stretch has one."""
    import serve_cell as C
    reqs = [_Req(i, 8 + (i * 37) % 50, i % 8) for i in range(150)]
    reqs[90].out_tokens = [0] * 256
    picked = C.sample(reqs, seed=2**31 + 9)
    assert len(picked) == C.SAMPLE_ROWS and picked[0] is reqs[90]
    rest = [r.rid for r in reqs if r is not reqs[90]]
    stretches = np.array_split(np.array(rest), C.SAMPLE_ROWS - 1)
    assert [int(np.searchsorted(rest, r.rid, side="right") - 1)
            // len(stretches[0]) for r in picked[1:]] == list(range(15))
    assert len({r._slot for r in picked[:8]}) == 8
    assert [r.rid for r in C.sample(reqs, seed=2**31 + 9)] == \
        [r.rid for r in picked]
    assert [r.rid for r in C.sample(reqs, seed=5)] != [r.rid for r in picked]
    assert len(C.sample(reqs[:4], seed=1)) == 4


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_benchmark_files():
    bm = H.benchmark()
    for c in bm["configs"]:
        cfg = H.load_json(H.CHECKOUT / c["file"])
        assert cfg["name"] == c["name"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert (BENCH / "references" / f"{cfg['reference']}.py").exists()
    for w in bm["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        mix = H.load_json(BENCH / "traffic" / f"{w['traffic']}.json")
        parts = [v for v in mix.values() if isinstance(v, dict)] \
            + mix.get("rewrite", [])
        for part in parts:
            assert (T.LAWS / f"{part['law']}.py").exists(), part
        assert H.Cell(w["name"]).limits
    names = [m["name"] for m in bm["end_to_end"] + bm["per_layer"]]
    assert len(names) == len(set(names)) and all(map(NAME.match, names))
    e2e = {m["name"] for m in bm["end_to_end"]}
    for m in bm["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").exists()
        assert m["moves"] in e2e
    for m in bm["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("config", ["gpt-h1024", "gpt-h2048-tmp4",
                                    "internlm2-1.8b"])
def test_program_config_as_before(config):
    """Every key of the three files reaches the program as the ten named
    sizes always did."""
    from repro.configs.registry import get_config
    cfg = H.load_json(BENCH / "configs" / f"{config}.json")
    m = cfg["model"]
    before = get_config(cfg["registry"]).replace(
        num_layers=m["num_layers"], d_model=m["d_model"],
        num_heads=m["num_heads"], num_kv_heads=m["num_kv_heads"],
        head_dim=m["head_dim"], d_ff=m["d_ff"], vocab_size=m["vocab_size"],
        rope_theta=m["rope_theta"], norm_eps=m["norm_eps"],
        dtype=m["dtype"], tie_embeddings=False)
    assert H.program_config(cfg) == before


def test_program_config_takes_every_field():
    """A key outside the old ten reaches the program: an object for a
    dataclass field is applied to the entry's own, a list is a tuple."""
    from repro.configs.base import MoEConfig
    from repro.configs.registry import get_config
    cfg = {"name": "granite-moe-share", "registry": "granite-moe-3b-a800m",
           "model": {"moe": {"num_experts": 8}, "window": 256,
                     "layer_pattern": ["global", "local"],
                     "padded_vocab": 49408, "tie_embeddings": True}}
    a = H.program_config(cfg)
    assert a.moe == MoEConfig(num_experts=8, top_k=8, sharding="tmp")
    assert (a.window, a.layer_pattern, a.tie_embeddings) == \
        (256, ("global", "local"), True)
    cfg["model"] = {"moe": {"num_experts": 4, "top_k": 2}}
    cfg["registry"] = "gpt-h1024"
    assert get_config("gpt-h1024").moe is None
    assert H.program_config(cfg).moe == MoEConfig(num_experts=4, top_k=2)
    assert H.program_config(cfg).tie_embeddings is False


@pytest.mark.parametrize("model,error", [
    ({"d_model": 64, "num_experts": 8}, KeyError),
    ({"moe": {"experts": 8}}, KeyError),
    ({"window": {"size": 8}}, TypeError),
])
def test_program_config_refuses_unknown_keys(model, error):
    cfg = {"name": "bad", "registry": "granite-moe-3b-a800m", "model": model}
    with pytest.raises(error):
        H.program_config(cfg)


def test_reference_layout_is_the_programs():
    """The reference draws each weight by the path the program's tree gives
    it: the two trees must name and shape the same leaves."""
    import jax
    import bench_tiny as tiny
    from repro.core import compat
    from repro.core.axes import mesh_info
    from repro.models import params as prm
    cell = tiny.tiny_cell("gpt-h1024.train.b16")
    acfg = H.program_config(cell.config)
    info = mesh_info(compat.make_mesh((1, 1), ("data", "model")))
    prog = prm.model_specs(acfg, info, max_pos=32)
    ref = H.reference(cell.config)

    def layout(tree, is_leaf=None):
        return {jax.tree_util.keystr(p): (tuple(x.shape),
                                          str(np.dtype(x.dtype)))
                for p, x in jax.tree_util.tree_flatten_with_path(
                    tree, is_leaf=is_leaf)[0]}

    mine = layout(ref.abstract(cell.config["model"]))
    theirs = layout(prog, prm.is_spec)
    assert mine == theirs
