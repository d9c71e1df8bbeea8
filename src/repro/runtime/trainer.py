"""Fault-tolerant training driver.

Responsibilities at 1000+-node scale (all exercised by tests on CPU):
* checkpoint/restart — async sharded checkpoints with integrity checksums
  and bounded write retry; resume from the newest INTACT checkpoint on
  (re)start, including after injected failures and corrupted shards;
* straggler detection — per-step wall-time EWMA + z-score; slow steps are
  logged, and pluggable monitors (runtime/elastic.py) can escalate
  persistent stragglers into replanning faults;
* elastic re-mesh — on resume the runner may bring a different mesh (e.g. a
  pod dropped); restore re-shards parameters and the data pipeline seeks to
  the restored step (no replay).  The ElasticSupervisor additionally carries
  live state device-to-device across mid-run plan changes (export_state /
  import_state) so a topology fault doesn't cost a checkpoint round-trip;
* heartbeats — a liveness file an external supervisor can watch, written
  atomically (tmp+rename) so a watcher never reads a half-written JSON.
"""
from __future__ import annotations

import json
import math
import os
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax
import numpy as np

from repro import obs
from repro.checkpoint import store
from repro.configs.base import ArchConfig, TrainHParams
from repro.core.axes import mesh_info
from repro.data.pipeline import DataConfig, Prefetcher
from repro.launch import steps as steps_mod
from repro.models import params as prm
from repro.optim import adamw
from repro.runtime import elastic as el


@dataclass
class StragglerDetector:
    alpha: float = 0.1
    z_threshold: float = 3.0
    mean: float = 0.0
    var: float = 0.0
    n: int = 0
    warmup: int = 5                  # steps before the z-test arms
    slow_steps: list = field(default_factory=list)

    def observe(self, step: int, dt: float) -> bool:
        if self.n >= self.warmup:
            sd = math.sqrt(self.var) if self.var > 0 else 1e-9
            z = (dt - self.mean) / sd
            slow = z > self.z_threshold
        else:
            slow = False
        delta = dt - self.mean
        self.mean += self.alpha * delta
        self.var = (1 - self.alpha) * (self.var + self.alpha * delta * delta)
        self.n += 1
        if slow:
            self.slow_steps.append((step, dt))
        return slow


@dataclass
class FailureInjector:
    """Deterministic failure injection for FT/elastic tests and CI chaos.

    Modes (all one-shot: a fired event is consumed so the post-fault
    continuation does not re-trip it when it revisits the step):

    * ``fail_at_steps``       — generic worker failure (RuntimeError), the
                                legacy restart-from-checkpoint path;
    * ``host_loss``           — ``(step, host)`` pairs raising
                                :class:`~repro.runtime.elastic.HostLossError`;
    * ``link_degrade``        — ``(step, bytes_per_s)`` pairs raising
                                :class:`~repro.runtime.elastic.LinkDegradedError`
                                with the measured degraded bandwidth;
    * ``ckpt_fail_saves``     — the first N checkpoint writes raise a
                                transient ``OSError`` (exercises the
                                AsyncCheckpointer retry path);
    * ``corrupt_at_steps``    — checkpoints at these steps are bit-flipped
                                AFTER the atomic commit (exercises the
                                integrity-verify + intact-fallback path).
    """
    fail_at_steps: tuple = ()
    host_loss: tuple = ()            # ((step, host), ...)
    link_degrade: tuple = ()         # ((step, bytes_per_s), ...)
    ckpt_fail_saves: int = 0
    corrupt_at_steps: tuple = ()
    _fired: set = field(default_factory=set)
    _saves_failed: int = 0

    def _once(self, tag) -> bool:
        if tag in self._fired:
            return False
        self._fired.add(tag)
        return True

    def check(self, step: int):
        if step in self.fail_at_steps and self._once(("fail", step)):
            raise RuntimeError(f"injected failure at step {step}")
        for s, host in self.host_loss:
            if step == s and self._once(("host", s)):
                raise el.HostLossError(step, int(host), "injected")
        for s, bw in self.link_degrade:
            if step == s and self._once(("link", s)):
                raise el.LinkDegradedError(step, float(bw), "injected")

    def wrap_save(self, save_fn=store.save):
        """A ``store.save``-compatible callable with this injector's
        checkpoint-write faults applied (wired into AsyncCheckpointer)."""
        if not (self.ckpt_fail_saves or self.corrupt_at_steps):
            return save_fn

        def wrapped(ckpt_dir, step, tree, **kw):
            if self._saves_failed < self.ckpt_fail_saves:
                self._saves_failed += 1
                raise OSError(
                    f"injected transient checkpoint-write error "
                    f"({self._saves_failed}/{self.ckpt_fail_saves})")
            path = save_fn(ckpt_dir, step, tree, **kw)
            if step in self.corrupt_at_steps and self._once(("corrupt",
                                                             step)):
                corrupt_checkpoint(path)
            return path

        return wrapped


def corrupt_checkpoint(path: str):
    """Bit-flip the committed shard of a checkpoint directory — the
    deterministic stand-in for torn writes / bit rot.  The flip lands in
    the member-data region of the npz so ``store.restore`` sees a crc32
    (or zip-CRC) mismatch, not a missing file."""
    shard = os.path.join(path, "shard_0.npz")
    size = os.path.getsize(shard)
    with open(shard, "r+b") as f:
        f.seek(size // 2)
        b = f.read(1)
        f.seek(size // 2)
        f.write(bytes([b[0] ^ 0xFF]))


class Trainer:
    def __init__(self, cfg: ArchConfig, mesh, hp: TrainHParams, *,
                 global_batch: int, seq_len: int, ckpt_dir: str,
                 injector: Optional[FailureInjector] = None,
                 monitors: Sequence[el.FaultMonitor] = (),
                 log_fn: Callable[[str], None] = print,
                 degrees=None, plan=None,
                 telemetry=None, host_id: int = 0):
        from repro.core.plan import ParallelPlan
        from repro.launch.mesh import mesh_signature
        self.cfg = cfg
        self.mesh = mesh
        info = mesh_info(mesh)
        schedules = None
        if plan is not None:
            hp, degrees, schedules = steps_mod.unpack_plan(cfg, hp, plan,
                                                           degrees)
        else:
            # legacy callers: desugar the loose (hp, degrees) threading so
            # the checkpoint manifest ALWAYS records an executable plan
            mshape, maxes = mesh_signature(mesh)
            plan = ParallelPlan.from_hparams(
                hp, cfg.num_layers, degrees=degrees, mesh_shape=mshape,
                mesh_axes=maxes, pp=info.pp)
        self.plan = plan
        # one shared resolution with build_train_step: planner mode sees the
        # extra-dp-adjusted microbatcher; a pipeline mesh folds gradient
        # accumulation into the 1F1B schedule (hp.microbatch = n_micro)
        self.hp = steps_mod.resolve_for_mesh(cfg, info, hp, global_batch,
                                             seq_len, degrees)
        self.degrees = degrees
        self.schedules = schedules
        self.global_batch = global_batch
        self.seq_len = seq_len
        self.ckpt_dir = ckpt_dir
        self.injector = injector or FailureInjector()
        self.monitors = tuple(monitors)
        self.log = log_fn
        # structured telemetry (repro.obs).  Default: an in-memory recorder
        # whose console sink is log_fn, so the familiar "[trainer] ..."
        # lines keep printing while structured payloads ride along; pass
        # obs.NULL to disable entirely, or a JSONL-sinking Recorder
        # (launch/train.py --telemetry <dir>) to persist the run.
        self.rec = (telemetry if telemetry is not None
                    else obs.Recorder(console=log_fn))
        self.host_id = host_id
        self.straggler = StragglerDetector()
        base_save = self.injector.wrap_save()

        def _timed_save(ckpt_dir, step, tree, **kw):
            # runs on the AsyncCheckpointer worker thread — Recorder's file
            # buffer is lock-protected for exactly this caller
            t0 = time.perf_counter()
            path = base_save(ckpt_dir, step, tree, **kw)
            self.rec.observe("trainer.ckpt_write_s",
                             time.perf_counter() - t0, step=step)
            return path

        self.checkpointer = store.AsyncCheckpointer(
            ckpt_dir, save_fn=_timed_save)
        self.run_losses: list = []       # losses of the current train() call
        self._live_state = None          # (params, opt, next_step) on device

        self.step_fn, self.specs = steps_mod.build_train_step(
            cfg, mesh, self.hp, global_batch=global_batch, seq_len=seq_len,
            degrees=degrees, schedules=schedules, plan=self.plan)
        # buffer donation deadlocks XLA:CPU's intra-process collective
        # rendezvous (execution only — the dry-run donates at compile time);
        # enable it on real accelerators.
        donate = (0, 1) if jax.default_backend() != "cpu" else ()
        self.step_fn = steps_mod.jit_train_step(self.step_fn, mesh, donate)
        self.info = info

    # ---- state ----
    def _shardings(self):
        psh = prm.shardings_tree(self.specs, self.mesh)
        osp = adamw.opt_state_specs(self.specs, self.info,
                                    zero1=self.hp.zero1)
        osh = {
            "master": prm.shardings_tree(osp["master"], self.mesh),
            "m": prm.shardings_tree(osp["m"], self.mesh),
            "v": prm.shardings_tree(osp["v"], self.mesh),
            "step": jax.sharding.NamedSharding(
                self.mesh, jax.sharding.PartitionSpec()),
            "err": None,
        }
        return psh, osh

    def init_state(self, seed: int = 0):
        """Fresh (params, opt) made in place: one jitted init whose outputs
        land on their shardings, so no device ever holds the whole
        unsharded state, and every leaf is its own (donatable) buffer."""
        def init(key):
            params = prm.init_params(self.specs, key)
            return params, adamw.init_opt_state(params, self.specs, self.info,
                                                zero1=self.hp.zero1)

        params, opt = jax.jit(init, out_shardings=self._shardings())(
            jax.random.PRNGKey(seed))
        return params, opt, 0

    def _plan_remap(self, metadata: Dict):
        """Cross-plan elastic resume: when the checkpoint's recorded plan
        trains under a different parameter-tree grouping than the current
        one (grouped planner layouts vs the stacked layout, including
        mixed-schedule -> global-schedule transitions), return a
        flat-leaf remap that restacks the canonical layer order into the
        current layout.  Stacked -> stacked pp changes keep the existing
        pure-reshape path (store.restore)."""
        from repro.core.plan import ParallelPlan
        cur_sig = self.plan.grouping_signature()
        saved_d = metadata.get("plan")
        if saved_d is not None:
            saved = ParallelPlan.from_dict(saved_d)
            src_sig = saved.grouping_signature()
            src_meta = el.plan_layout(saved)
        else:                       # pre-plan checkpoint: stacked layout
            pp = metadata.get("pp", 1)
            v = metadata.get("virtual_stages", 1) if pp > 1 else 1
            src_sig = ("stacked", pp, v)
            src_meta = {"pp": pp, "virtual_stages": v}
        if src_sig == cur_sig:
            return None, None
        if src_sig[0] == "stacked" and cur_sig[0] == "stacked":
            return None, src_sig    # pure [v, pp, n/S] reshape suffices
        remap = el.state_remap(self.cfg, src_meta,
                               el.plan_layout(self.plan))
        return remap, src_sig

    def restore_or_init(self, seed: int = 0):
        """Resume from the newest INTACT checkpoint: a corrupted or torn
        write (store.CorruptCheckpointError) falls back to the previous
        step instead of crashing — or silently loading garbage."""
        params, opt, start = self.init_state(seed)
        psh, osh = self._shardings()
        for last in reversed(store.all_steps(self.ckpt_dir)):
            try:
                remap, src_sig = self._plan_remap(
                    store.read_manifest(self.ckpt_dir, last)
                    .get("metadata", {}))
                (params, opt), meta = store.restore(
                    self.ckpt_dir, last, (params, opt),
                    shardings=(psh, osh), remap=remap)
            except store.CorruptCheckpointError as e:
                self.rec.event(
                    "trainer.ckpt_corrupt", step=last,
                    msg=f"[trainer] checkpoint step {last} corrupt "
                        f"({e}); falling back to previous intact "
                        f"checkpoint")
                continue
            src = meta.get("mesh_axes")
            self.rec.event(
                "trainer.restore", step=last,
                relayout=remap is not None,
                msg=f"[trainer] restored step {last} "
                    f"(elastic mesh={tuple(self.mesh.shape.values())}"
                    f" pp={self.info.pp}"
                    + (f" <- {src} pp={meta.get('pp', 1)}" if src else "")
                    + (f", plan relayout {src_sig[0]} -> "
                       f"{self.plan.grouping_signature()[0]}"
                       if remap is not None else "")
                    + ")")
            return params, opt, last
        return params, opt, 0

    # ---- live-state carry (ElasticSupervisor) ----
    def export_state(self) -> Optional[Dict]:
        """Flat host snapshot of the live (params, opt) state for an
        in-memory carry across a topology change: ``{"flat": {keystr:
        np.ndarray | None}, "step": next_step, "sig"/"layout": the source
        plan's grouping}``.  None when no step has completed yet (the
        supervisor then restores from checkpoint)."""
        if self._live_state is None:
            return None
        params, opt, next_step = self._live_state
        leaves, _ = jax.tree_util.tree_flatten_with_path(
            (params, opt), is_leaf=lambda x: x is None)
        flat = {jax.tree_util.keystr(kp):
                (None if v is None else np.asarray(jax.device_get(v)))
                for kp, v in leaves}
        return {"flat": flat, "step": next_step,
                "sig": self.plan.grouping_signature(),
                "layout": el.plan_layout(self.plan)}

    def import_state(self, exported: Dict):
        """Land an exported live state on THIS trainer's mesh/plan:
        relayout the flat leaves across the plan-layout change (grouped
        <-> stacked <-> pipeline stacks), then device_put against this
        trainer's shardings.  Returns the ``state=`` tuple for
        :meth:`train`."""
        flat = exported["flat"]
        if exported["sig"] != self.plan.grouping_signature():
            remap = el.state_remap(self.cfg, exported["layout"],
                                   el.plan_layout(self.plan))
            flat = remap(flat)
        params, opt, _ = self.init_state()
        psh, osh = self._shardings()
        leaves, treedef = jax.tree_util.tree_flatten_with_path(
            (params, opt), is_leaf=lambda x: x is None)
        shard_leaves = jax.tree_util.tree_leaves(
            (psh, osh), is_leaf=lambda x: x is None)
        out = []
        for (kp, like), sh in zip(leaves, shard_leaves):
            key = jax.tree_util.keystr(kp)
            arr = flat.get(key)
            if arr is None:
                out.append(None)
                continue
            like_shape = tuple(getattr(like, "shape", arr.shape))
            if tuple(arr.shape) != like_shape:
                arr = arr.reshape(like_shape)   # [v,pp,n/S] <-> [n] stacks
            out.append(jax.device_put(arr, sh) if sh is not None
                       else jax.device_put(arr))
        params, opt = jax.tree_util.tree_unflatten(treedef, out)
        return params, opt, exported["step"]

    def _heartbeat(self, step: int, dt: Optional[float] = None,
                   loss: Optional[float] = None):
        """Atomic liveness write: tmp + rename, so a watching supervisor
        (HeartbeatMonitor) never reads a half-written JSON.

        Beyond liveness the file now carries per-host step metrics
        (step_time_s / step_time_ewma_s / loss) so a cross-host watcher
        (elastic.StragglerEscalation with peer heartbeats) can localize
        WHICH host is slow, not just that somebody is."""
        hb: Dict = {"step": step, "time": time.time(), "host": self.host_id}
        if dt is not None:
            hb["step_time_s"] = dt
            hb["step_time_ewma_s"] = self.straggler.mean
        if loss is not None:
            hb["loss"] = loss
        path = el.heartbeat_path(self.ckpt_dir)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(hb, f)
        os.replace(tmp, path)

    # ---- main loop ----
    def train(self, total_steps: int, *, ckpt_every: int = 50,
              seed: int = 0, state: Optional[Tuple] = None) -> Dict:
        """Run to ``total_steps``.  ``state=(params, opt, start)`` skips
        the checkpoint restore — the ElasticSupervisor's in-memory
        continuation path (import_state's return value)."""
        os.makedirs(self.ckpt_dir, exist_ok=True)
        if state is not None:
            params, opt, start = state
        else:
            params, opt, start = self.restore_or_init(seed)
        # on a pipeline mesh the batch stays flat — the 1F1B schedule slices
        # its own microbatches inside the step (steps.py)
        dcfg = DataConfig(global_batch=self.global_batch,
                          seq_len=self.seq_len,
                          vocab_size=self.cfg.vocab_size,
                          microbatch=(self.hp.microbatch
                                      if self.info.pp == 1 else 0))
        ctx_shape = ((self.global_batch, self.cfg.context_len,
                      self.cfg.context_dim or self.cfg.d_model)
                     if self.cfg.context_len else None)
        data = Prefetcher(dcfg, self.mesh, start_step=start,
                          ctx_shape=ctx_shape)
        self.run_losses = []
        losses = self.run_losses
        step = start
        try:
            for step, batch in data:
                if step >= total_steps:
                    break
                # perf_counter: the step timer feeds the straggler
                # detector — time.time() is non-monotonic and an NTP slew
                # mid-step reads as a phantom straggler
                t0 = time.perf_counter()
                self.injector.check(step)
                with obs.trace_annotation("train.step"):
                    params, opt, metrics = self.step_fn(params, opt, batch)
                    loss = float(metrics["loss"])
                dt = time.perf_counter() - t0
                self.rec.observe("trainer.step_time_s", dt, step=step)
                self.rec.gauge("trainer.tokens_per_s",
                               self.global_batch * self.seq_len / dt,
                               step=step)
                self.rec.gauge("trainer.loss", loss, step=step)
                if self.straggler.observe(step, dt):
                    self.rec.event(
                        "trainer.straggler", step=step,
                        dt_s=round(dt, 4),
                        ewma_s=round(self.straggler.mean, 4),
                        msg=f"[straggler] step {step} took {dt:.2f}s "
                            f"(ewma {self.straggler.mean:.2f}s)")
                losses.append(loss)
                self._live_state = (params, opt, step + 1)
                self._heartbeat(step, dt, loss)
                for mon in self.monitors:
                    ev = mon.observe_step(step, dt) or mon.poll(step)
                    if ev is not None:
                        raise el.fault_from_event(ev)
                if (step + 1) % ckpt_every == 0 or step + 1 == total_steps:
                    # plan-aware manifest: the executable ParallelPlan (and
                    # the source mesh/pp) travel with the checkpoint so
                    # elastic restores validate/relayout across plan
                    # changes (PP <-> pure TMP, grouped <-> stacked,
                    # mixed-schedule <-> global-schedule)
                    self.checkpointer.save(
                        step + 1, (params, opt),
                        metadata={"loss": loss,
                                  "mesh_axes": {k: int(v) for k, v in
                                                self.mesh.shape.items()},
                                  "pp": self.info.pp,
                                  "virtual_stages": self.hp.virtual_stages,
                                  "plan": self.plan.to_dict()})
                if step % 10 == 0:
                    self.rec.event(
                        "trainer.step", step=step,
                        msg=f"[trainer] step {step} loss {loss:.4f} "
                            f"{dt*1e3:.0f} ms")
        finally:
            data.close()
            try:
                self.checkpointer.wait()
            except OSError as e:
                # an exhausted-retry async write must not mask the loop's
                # own (more informative) fault — surface it as a log +
                # counter the supervisor inspects
                self.rec.counter("trainer.ckpt_write_failed")
                self.rec.event(
                    "trainer.ckpt_write_failed",
                    msg=f"[trainer] checkpoint write failed after "
                        f"retries: {e}")
            self.rec.flush()
        return {"losses": losses, "final_step": step + 1,
                "slow_steps": self.straggler.slow_steps}


def run_with_restarts(make_trainer: Callable[[], Trainer], total_steps: int,
                      *, max_restarts: int = 3, ckpt_every: int = 5,
                      restartable: Tuple = (RuntimeError,),
                      backoff_s: float = 0.0,
                      backoff_factor: float = 2.0) -> Dict:
    """Supervisor loop: restart-from-checkpoint on worker failure.  On a real
    cluster this is the job scheduler; here it doubles as the FT test
    harness (tests inject failures and assert loss continuity).

    ``restartable`` is the exception tuple worth a same-mesh restart
    (default: RuntimeError only — an AssertionError or a shape bug is a
    code defect, not a fault).  ``KeyboardInterrupt``/``SystemExit`` are
    never restartable, and neither is a topology fault
    (:class:`~repro.runtime.elastic.FaultError`): a mesh that lost a host
    cannot be restarted into existence — that is the ElasticSupervisor's
    job.  Restarts back off exponentially (``backoff_s *
    backoff_factor**attempt``) so a crash-looping worker doesn't hammer
    shared checkpoint storage."""
    attempts = 0
    while True:
        trainer = make_trainer()
        # duck-typed: FT tests drive this loop with fake trainers that
        # only expose .train/.log
        rec = getattr(trainer, "rec", None) \
            or obs.Recorder(console=trainer.log)
        try:
            return trainer.train(total_steps, ckpt_every=ckpt_every)
        except (KeyboardInterrupt, SystemExit):
            raise
        except el.FaultError as e:
            rec.event(
                "supervisor.fault", kind=e.event.kind,
                msg=f"[supervisor] topology fault ({e}) is not "
                    f"restartable on the same mesh — use "
                    f"runtime.elastic.ElasticSupervisor")
            raise
        except restartable as e:
            attempts += 1
            rec.counter("supervisor.restarts")
            rec.event(
                "supervisor.restart", attempt=attempts,
                msg=f"[supervisor] worker failed ({e}); "
                    f"restart {attempts}/{max_restarts}")
            if attempts > max_restarts:
                raise
            if backoff_s > 0:
                time.sleep(backoff_s * backoff_factor ** (attempts - 1))
