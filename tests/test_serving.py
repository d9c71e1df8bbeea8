"""Serving engine on the 1-device mesh: continuous batching semantics."""
import jax
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.serving import Request, ServingEngine


@pytest.fixture(scope="module")
def engine():
    from repro.core import compat
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    cfg = get_config("internlm2-1.8b").reduced().replace(dtype="float32")
    eng = ServingEngine(cfg, mesh, slots=2, max_seq=48)
    eng.load(seed=0)
    return eng


def test_more_requests_than_slots(engine):
    for i in range(5):
        engine.submit(Request(rid=i, prompt=np.arange(3, 8, dtype=np.int32),
                              max_new_tokens=4))
    stats = engine.run_until_drained()
    assert stats["admitted"] == 5
    assert stats["decoded_tokens"] >= 5          # eos may end early
    assert all(a is None for a in engine.active)


def test_greedy_determinism():
    from repro.core import compat
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    cfg = get_config("internlm2-1.8b").reduced().replace(dtype="float32")

    def decode_once():
        eng = ServingEngine(cfg, mesh, slots=1, max_seq=32)
        eng.load(seed=0)
        r = Request(rid=0, prompt=np.arange(3, 8, dtype=np.int32),
                    max_new_tokens=6)
        eng.submit(r)
        eng.run_until_drained()
        return r.out_tokens

    assert decode_once() == decode_once()


def _mk_engine(**kw):
    from repro.core import compat
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    cfg = get_config("internlm2-1.8b").reduced().replace(dtype="float32")
    eng = ServingEngine(cfg, mesh, **{"slots": 2, "max_seq": 48, **kw})
    eng.load(seed=0)
    return eng


def test_slot_exhaustion_backs_up_admission_queue():
    """More requests than slots: the surplus waits in the admission queue
    (not dropped, not over-admitted) and drains as slots free up."""
    eng = _mk_engine()
    for i in range(5):
        eng.submit(Request(rid=i, prompt=np.arange(3, 6, dtype=np.int32),
                           max_new_tokens=3))
    eng.step()
    assert eng.stats["admitted"] == 2          # slot pool is the limit
    assert eng.queued == 3                     # backlog intact
    assert all(a is not None for a in eng.active)
    stats = eng.run_until_drained()
    assert stats["admitted"] == 5
    assert eng.queued == 0
    assert all(a is None for a in eng.active)


def test_eos_mid_batch_frees_slot_for_queued_request():
    """A sequence hitting EOS mid-batch releases its slot; the next queued
    request is admitted into it while the other slot keeps decoding."""
    # probe run: learn the greedy continuation, then re-run with eos_id
    # set to the second decoded token of request 0
    probe = _mk_engine()
    reqs = [Request(rid=i, prompt=np.arange(3 + i, 8 + i, dtype=np.int32),
                    max_new_tokens=8) for i in range(2)]
    for r in reqs:
        probe.submit(r)
    probe.run_until_drained()
    eos = reqs[0].out_tokens[1]
    if eos in (reqs[1].out_tokens or [eos]):
        # extremely unlikely on the random-init model; fall back to a
        # token only request 0 produces second
        eos = next((t for t in reqs[0].out_tokens
                    if t not in reqs[1].out_tokens), eos)

    eng = _mk_engine(eos_id=int(eos))
    r0 = Request(rid=0, prompt=np.arange(3, 8, dtype=np.int32),
                 max_new_tokens=8)
    r1 = Request(rid=1, prompt=np.arange(4, 9, dtype=np.int32),
                 max_new_tokens=8)
    r2 = Request(rid=2, prompt=np.arange(5, 10, dtype=np.int32),
                 max_new_tokens=8)
    for r in (r0, r1, r2):
        eng.submit(r)
    stats = eng.run_until_drained()
    assert r0.done and r0.out_tokens[-1] == eos
    assert len(r0.out_tokens) < 8              # EOS cut generation short
    assert stats["admitted"] == 3              # r2 took the freed slot
    assert r1.done and r2.done


def test_prompt_longer_than_prefill_len_rejected_at_submit():
    eng = _mk_engine(prefill_len=8)
    assert eng.prefill_len == 8
    with pytest.raises(ValueError, match="prefill_len"):
        eng.submit(Request(rid=0,
                           prompt=np.arange(3, 12, dtype=np.int32)))
    with pytest.raises(ValueError, match="empty"):
        eng.submit(Request(rid=1, prompt=np.zeros((0,), np.int32)))
    # boundary prompt admits and decodes fine
    r = Request(rid=2, prompt=np.arange(3, 11, dtype=np.int32),
                max_new_tokens=2)
    eng.submit(r)
    eng.run_until_drained()
    assert r.done and len(r.out_tokens) == 2


def test_prefill_len_derived_and_validated():
    eng = _mk_engine(max_seq=48)
    assert eng.prefill_len == 24               # derived: max_seq // 2
    with pytest.raises(ValueError, match="max_seq"):
        _mk_engine(max_seq=32, prefill_len=32)
    with pytest.raises(ValueError, match="max_seq"):
        _mk_engine(max_seq=32, prefill_len=0)


def test_donate_argnums_backend_branch(monkeypatch):
    """The KV cache is donated on accelerators only: the CPU backend
    ignores donation (and would warn every step), so the engine keys the
    donate_argnums off jax.default_backend()."""
    eng_cpu = _mk_engine()
    assert eng_cpu.donate_argnums == ()

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    eng_tpu = _mk_engine()
    assert eng_tpu.donate_argnums == (1,)
    monkeypatch.undo()

    # both engines decode the same tokens (donation is a memory
    # optimization, not a semantic change; XLA:CPU ignores the aliasing)
    def run(eng):
        r = Request(rid=0, prompt=np.arange(3, 8, dtype=np.int32),
                    max_new_tokens=4)
        eng.submit(r)
        eng.run_until_drained()
        return r.out_tokens

    assert run(eng_cpu) == run(eng_tpu)


def test_request_stamps_and_queue_wait():
    """The engine stamps every request at submit, admission and first
    token, in that order; with more requests than slots the late ones wait
    in the queue, and ``slot_ticks`` sums the active slots over ticks."""
    from repro.obs import Recorder
    eng = _mk_engine(telemetry=Recorder(), eos_id=-1)
    reqs = [Request(rid=i, prompt=np.arange(3, 6, dtype=np.int32),
                    max_new_tokens=3) for i in range(4)]
    for r in reqs:
        eng.submit(r)
    ticks = 0
    while eng.queued or any(a is not None for a in eng.active):
        eng.step()
        ticks += 1
    for r in reqs:
        assert r.done
        assert r.t_submit <= r.t_admit <= r.t_first
    waits = [r.t_admit - r.t_submit for r in reqs]
    assert min(waits[2:]) > max(waits[:2]) > 0
    # 3 prompt tokens then 3 outputs: each request holds a slot 5 ticks
    assert eng.stats["slot_ticks"] == 4 * 5
    assert eng.stats["steps"] == ticks
    ttft = list(eng.rec.hists["serving.ttft_s"])[-4:]
    assert ttft == [r.t_first - r.t_submit for r in reqs]


def test_tick_phases_on_profiler_trace(tmp_path):
    """One tick under ``jax.profiler`` shows each of the engine's phases as
    a host span, in order, and no other engine span."""
    import glob
    import os

    from jax.profiler import ProfileData
    eng = _mk_engine()
    eng.submit(Request(rid=0, prompt=np.arange(3, 6, dtype=np.int32),
                       max_new_tokens=2))
    eng.step()                      # compiles
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.step()
    finally:
        jax.profiler.stop_trace()
    f = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                  recursive=True)[0]
    spans = sorted((e.start_ns, e.name)
                   for p in ProfileData.from_file(f).planes
                   if p.name.startswith("/host:") for ln in p.lines
                   for e in ln.events if e.name.startswith("serve."))
    assert [n for _, n in spans] == ["serve.admit", "serve.prepare",
                                     "serve.dispatch", "serve.sync",
                                     "serve.emit"]
