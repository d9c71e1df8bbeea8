"""Fault taxonomy, monitors, failure injection, checkpoint integrity,
restart hardening, and elastic-supervisor units (runtime/elastic.py).

The full replan -> relayout -> loss-continuity path runs as a multidevice
subprocess test (tests/_scripts/elastic_replan.py via test_distributed.py);
these are the fast single-device units around it.
"""
import json
import os
import tempfile

import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import store
from repro.configs.base import ShapeConfig, TrainHParams
from repro.configs.registry import get_config
from repro.core.plan import ParallelPlan
from repro.core.planner import costmodel as cm
from repro.core.planner import ilp
from repro.runtime import elastic as el
from repro.runtime.trainer import (FailureInjector, StragglerDetector,
                                   corrupt_checkpoint, run_with_restarts)


def _reduced():
    return get_config("internlm2-1.8b").reduced().replace(dtype="float32")


# ---------------- straggler detection ----------------
def test_straggler_warmup_gate():
    det = StragglerDetector()
    det.observe(0, 1.0)
    # a 1000x outlier inside the warmup window must NOT flag: the EWMA
    # has no baseline yet
    for i in range(1, det.warmup):
        assert not det.observe(i, 1000.0)
    assert det.n == det.warmup


def test_straggler_ewma_tracks_mean():
    det = StragglerDetector()
    for i in range(100):
        det.observe(i, 2.0)
    assert abs(det.mean - 2.0) < 1e-3     # geometric convergence from 0
    assert det.var < 1e-3


def test_straggler_flags_and_records():
    det = StragglerDetector()
    for i in range(20):
        assert not det.observe(i, 1.0 + 0.01 * (i % 3))
    assert det.observe(20, 10.0)
    assert det.slow_steps[0][0] == 20


class _AlwaysSlow:
    """Stand-in detector: every step flags slow against a 1.0s baseline."""
    mean = 1.0

    def observe(self, step, dt):
        return True


class _NeverSlow:
    mean = 1.0

    def observe(self, step, dt):
        return False


def test_straggler_escalation_consecutive():
    esc = el.StragglerEscalation(detector=_AlwaysSlow(), escalate_after=3)
    assert esc.observe_step(0, 4.0) is None
    assert esc.observe_step(1, 4.0) is None
    ev = esc.observe_step(2, 4.0)
    assert ev is not None and ev.kind == "straggler"
    assert abs(ev.slowdown - 4.0) < 1e-9
    # the counter resets after escalating — no immediate re-fire
    assert esc.observe_step(3, 4.0) is None


def test_straggler_escalation_resets_on_healthy_step():
    class Alternating:
        mean = 1.0
        _n = 0

        def observe(self, step, dt):
            self._n += 1
            return self._n % 2 == 1       # slow, healthy, slow, ...

    esc = el.StragglerEscalation(detector=Alternating(), escalate_after=2)
    for i in range(10):                   # never 2 consecutive slow steps
        assert esc.observe_step(i, 5.0) is None


def test_straggler_escalation_never_fires_when_healthy():
    esc = el.StragglerEscalation(detector=_NeverSlow(), escalate_after=1)
    for i in range(5):
        assert esc.observe_step(i, 1.0) is None


# ---------------- heartbeat monitor ----------------
def test_heartbeat_monitor_stale_and_missing():
    now = [1000.0]
    with tempfile.TemporaryDirectory() as d:
        fresh, stale = os.path.join(d, "hb0"), os.path.join(d, "hb1")
        for path, t in ((fresh, 995.0), (stale, 100.0)):
            with open(path, "w") as f:
                json.dump({"step": 1, "time": t}, f)
        mon = el.HeartbeatMonitor(
            paths={0: fresh, 1: stale, 2: os.path.join(d, "never_written")},
            timeout_s=60.0, clock=lambda: now[0])
        evs = [mon.poll(7), mon.poll(7), mon.poll(7)]
        hosts = {e.host for e in evs if e is not None}
        assert hosts == {1, 2}            # stale + missing, each ONCE
        assert all(e.kind == "heartbeat-stale" for e in evs
                   if e is not None)
        assert mon.poll(8) is None        # already reported


def test_heartbeat_monitor_tolerates_torn_write():
    now = [1000.0]
    with tempfile.TemporaryDirectory() as d:
        torn = os.path.join(d, "hb0")
        with open(torn, "w") as f:
            f.write('{"step": 3, "ti')    # half-written JSON
        mon = el.HeartbeatMonitor(paths={0: torn}, timeout_s=60.0,
                                  clock=lambda: now[0])
        ev = mon.poll(0)                  # stale, not a crash
        assert ev is not None and ev.host == 0


# ---------------- failure injection ----------------
def test_injector_one_shot_per_mode():
    inj = FailureInjector(fail_at_steps=(3,), host_loss=((5, 1),),
                          link_degrade=((7, 2e9),))
    inj.check(0)
    with pytest.raises(RuntimeError):
        inj.check(3)
    inj.check(3)                          # consumed: resume revisits safely
    with pytest.raises(el.HostLossError) as ei:
        inj.check(5)
    assert ei.value.event.host == 1 and ei.value.event.step == 5
    inj.check(5)
    with pytest.raises(el.LinkDegradedError) as ei:
        inj.check(7)
    assert ei.value.event.link_bw == 2e9
    inj.check(7)


def test_injector_wrap_save_transient_then_ok():
    inj = FailureInjector(ckpt_fail_saves=2)
    wrapped = inj.wrap_save()
    tree = {"a": np.arange(4.0)}
    with tempfile.TemporaryDirectory() as d:
        for _ in range(2):
            with pytest.raises(OSError):
                wrapped(d, 1, tree)
        wrapped(d, 1, tree)               # third attempt lands
        assert store.latest_step(d) == 1


def test_injector_wrap_save_corrupts_committed_step():
    inj = FailureInjector(corrupt_at_steps=(2,))
    wrapped = inj.wrap_save()
    tree = {"a": np.arange(64.0)}
    with tempfile.TemporaryDirectory() as d:
        wrapped(d, 1, tree)
        wrapped(d, 2, tree)
        assert store.verify(d, 1)
        assert not store.verify(d, 2)
        assert store.latest_intact_step(d) == 1


def test_injector_passthrough_when_no_ckpt_faults():
    inj = FailureInjector(fail_at_steps=(1,))
    assert inj.wrap_save(store.save) is store.save


# ---------------- checkpoint integrity ----------------
def test_corrupt_checkpoint_detected_on_restore():
    tree = {"a": jnp.arange(64.0), "b": {"c": jnp.ones((8,))}}
    with tempfile.TemporaryDirectory() as d:
        store.save(d, 1, tree)
        path = store.save(d, 2, tree)
        corrupt_checkpoint(path)
        with pytest.raises(store.CorruptCheckpointError):
            store.restore(d, 2, tree)
        assert store.latest_intact_step(d) == 1
        out, _ = store.restore(d, 1, tree)   # intact neighbor still loads
        np.testing.assert_array_equal(out["a"], tree["a"])


def test_crc_mismatch_detected_even_with_valid_zip():
    # rewrite the shard as a VALID npz with different values: only the
    # manifest crc32 can catch this class of corruption
    tree = {"a": jnp.arange(16.0)}
    with tempfile.TemporaryDirectory() as d:
        path = store.save(d, 5, tree)
        np.savez(os.path.join(path, "shard_0.npz"),
                 a0=np.arange(16.0) + 1.0)
        assert not store.verify(d, 5)
        with pytest.raises(store.CorruptCheckpointError) as ei:
            store.restore(d, 5, tree)
        assert "integrity" in str(ei.value)


def test_garbled_manifest_is_corrupt_not_crash():
    tree = {"a": jnp.zeros((4,))}
    with tempfile.TemporaryDirectory() as d:
        path = store.save(d, 1, tree)
        with open(os.path.join(path, "manifest.json"), "w") as f:
            f.write("{not json")
        with pytest.raises(store.CorruptCheckpointError):
            store.restore(d, 1, tree)
        assert store.latest_intact_step(d) is None


def test_async_checkpointer_retries_transient_oserror():
    calls = {"n": 0}

    def flaky(ckpt_dir, step, tree, **kw):
        calls["n"] += 1
        if calls["n"] == 1:
            raise OSError("transient")
        return store.save(ckpt_dir, step, tree, **kw)

    tree = {"a": jnp.arange(4.0)}
    with tempfile.TemporaryDirectory() as d:
        ck = store.AsyncCheckpointer(d, retries=2, backoff_s=0.0,
                                     save_fn=flaky)
        ck.save(1, tree)
        ck.wait()                          # retry succeeded: no raise
        assert ck.failed_saves == 1
        assert store.latest_step(d) == 1


def test_async_checkpointer_surfaces_exhausted_retries():
    def broken(ckpt_dir, step, tree, **kw):
        raise OSError("disk on fire")

    with tempfile.TemporaryDirectory() as d:
        ck = store.AsyncCheckpointer(d, retries=1, backoff_s=0.0,
                                     save_fn=broken)
        ck.save(1, {"a": jnp.zeros(2)})
        with pytest.raises(OSError):
            ck.wait()
        assert ck.failed_saves == 2        # initial attempt + 1 retry


# ---------------- run_with_restarts hardening ----------------
class _FakeTrainer:
    def __init__(self, outcomes):
        self.outcomes = outcomes           # shared mutable list
        self.log = lambda *a, **k: None

    def train(self, total_steps, **kw):
        out = self.outcomes.pop(0)
        if isinstance(out, BaseException):
            raise out
        return out


def _factory(outcomes, calls):
    def make():
        calls.append(1)
        return _FakeTrainer(outcomes)
    return make


def test_restarts_recover_then_return():
    calls = []
    ok = {"losses": [1.0], "final_step": 2, "slow_steps": []}
    make = _factory([RuntimeError("boom"), ok], calls)
    res = run_with_restarts(make, 2, backoff_s=0.001)
    assert res["final_step"] == 2 and len(calls) == 2


def test_restarts_never_catch_keyboard_interrupt():
    calls = []
    make = _factory([KeyboardInterrupt()], calls)
    with pytest.raises(KeyboardInterrupt):
        run_with_restarts(make, 2)
    assert len(calls) == 1                 # no restart attempt


def test_restarts_never_catch_system_exit():
    calls = []
    make = _factory([SystemExit(3)], calls)
    with pytest.raises(SystemExit):
        run_with_restarts(make, 2)
    assert len(calls) == 1


def test_restarts_respect_restartable_tuple():
    # default tuple: a ValueError is a code defect, not a fault
    calls = []
    make = _factory([ValueError("bug")], calls)
    with pytest.raises(ValueError):
        run_with_restarts(make, 2)
    assert len(calls) == 1
    # opting ValueError in makes it restartable
    calls = []
    ok = {"losses": [], "final_step": 1, "slow_steps": []}
    make = _factory([ValueError("flaky"), ok], calls)
    res = run_with_restarts(make, 1, restartable=(ValueError,),
                            backoff_s=0.0)
    assert res["final_step"] == 1 and len(calls) == 2


def test_restarts_bounded():
    calls = []
    make = _factory([RuntimeError(i) for i in range(10)], calls)
    with pytest.raises(RuntimeError):
        run_with_restarts(make, 2, max_restarts=2, backoff_s=0.0)
    assert len(calls) == 3                 # initial + 2 restarts


def test_restarts_refuse_topology_faults():
    # a FaultError IS a RuntimeError, but restarting the same mesh cannot
    # bring a lost host back — must escalate, not loop
    calls = []
    make = _factory([el.HostLossError(4, 1)], calls)
    with pytest.raises(el.HostLossError):
        run_with_restarts(make, 2, max_restarts=5)
    assert len(calls) == 1


# ---------------- degraded HWConfig ----------------
def test_hwconfig_degrade_clamps():
    hw = cm.V5E
    d = hw.degrade(lost_chips=hw.n_chips + 5)
    assert d.n_chips == 1 and d.node_size <= 1
    d = hw.degrade(n_chips=3)
    assert d.n_chips == 3 and d.node_size <= 3
    assert hw.n_chips != 3                 # original untouched (frozen)


def test_hwconfig_degrade_link_floor_and_scale():
    hw = cm.COMMODITY_25GBE
    d = hw.degrade(link_bw_y=0.0)
    assert d.link_bw_y == 1.0              # floored: never divide by zero
    d = hw.degrade(bw_scale=0.5)
    assert d.link_bw == hw.link_bw * 0.5
    # 0.0 sentinel fields (fall back to link_bw) stay 0.0 under scaling
    if hw.link_bw_x == 0.0:
        assert d.link_bw_x == 0.0


def test_topology_degraded_hw():
    topo = el.Topology(n_hosts=4, chips_per_host=2)
    hw = topo.lose(3).degraded_hw(cm.V5E)
    assert hw.n_chips == 6 and hw.node_size == 2


# ---------------- topology ----------------
def test_topology_lose_and_refuse_last():
    t = el.Topology(n_hosts=2, chips_per_host=4)
    assert t.n_chips == 8
    t2 = t.lose(1)
    assert t2.alive_hosts == (0,) and t2.n_chips == 4
    with pytest.raises(ValueError):
        t2.lose(1)                         # already dead
    with pytest.raises(ValueError):
        t2.lose(0)                         # cannot lose the last host
    with pytest.raises(ValueError):
        t.lose(7)                          # not a host


def test_topology_devices_contiguous_slices():
    devs = list("abcdefgh")                # stand-in device list
    t = el.Topology(n_hosts=4, chips_per_host=2).lose(1)
    assert t.devices(devs) == ["a", "b", "e", "f", "g", "h"]


def test_topology_link_degrade_floor():
    t = el.Topology(n_hosts=2, chips_per_host=1).degrade_link(0.0)
    assert t.link_bw_y == 1.0


# ---------------- replanning ----------------
def test_replan_clamps_options_and_is_executable():
    cfg = _reduced()
    shape = ShapeConfig("t", 64, 4, "train")
    hp = TrainHParams(schedule="oases")
    hw = cm.V5E.degrade(n_chips=4, node_size=4)
    pr = ilp.replan(cfg, shape, hp, hw, options=(2, 4, 8, 16),
                    time_limit=2.0)
    plan = pr.plan.validate_for(cfg)       # executable, right layer count
    assert plan.mesh_shape and int(np.prod(plan.mesh_shape)) <= 4
    # uniform mesh-following form: runs on a plain (data, model) mesh
    assert plan.planned_degrees is None
    assert len(set(plan.schedules)) == 1


def test_replan_single_chip_limit():
    cfg = _reduced()
    shape = ShapeConfig("t", 64, 4, "train")
    hp = TrainHParams(schedule="oases")
    hw = cm.V5E.degrade(n_chips=1, node_size=1)
    pr = ilp.replan(cfg, shape, hp, hw, time_limit=2.0)
    assert int(np.prod(pr.plan.mesh_shape)) == 1


def test_supervisor_fallback_plan_clamps_to_survivors():
    cfg = _reduced()
    hp = TrainHParams(schedule="oases")
    sup = el.ElasticSupervisor(
        make_trainer=None, topology=el.Topology(n_hosts=2, chips_per_host=2),
        cfg=cfg, shape=ShapeConfig("t", 64, 4, "train"), hp=hp,
        log_fn=lambda *a: None)
    big = ParallelPlan.from_hparams(hp, cfg.num_layers,
                                    mesh_shape=(2, 4),
                                    mesh_axes=("data", "model"))
    fb = sup._fallback_plan(big)           # 8-chip plan, 4 survivors
    assert int(np.prod(fb.mesh_shape)) <= 4
    assert fb.primary_schedule == big.primary_schedule
    small = ParallelPlan.from_hparams(hp, cfg.num_layers, mesh_shape=(1, 2),
                                      mesh_axes=("data", "model"))
    assert sup._fallback_plan(small) is small   # still fits: unchanged
    assert sup._fallback_plan(None) is None


def test_fault_event_roundtrip_through_errors():
    ev = el.FaultEvent("host-loss", step=9, host=2, detail="nic down")
    err = el.fault_from_event(ev)
    assert isinstance(err, el.HostLossError)
    assert err.event.host == 2 and err.event.step == 9
    assert isinstance(el.fault_from_event(
        el.FaultEvent("link-degraded", step=1, link_bw=5e9)),
        el.LinkDegradedError)
    generic = el.fault_from_event(el.FaultEvent("heartbeat-stale", host=1))
    assert type(generic) is el.FaultError


def test_train_step_compiler_options_need_tpu_and_model_group(smoke_mesh):
    """Async all-reduce options follow from the platform and the mesh:
    a CPU mesh compiles the train step with none."""
    from repro.launch import steps
    assert steps.train_compiler_options(smoke_mesh) is None
