"""Online elastic training: fault detection -> mid-run ILP replanning ->
in-memory relayout -> continue loss-continuously.

Commodity servers lose hosts, degrade NICs, and grow stragglers as a
matter of course; the paper's planner already knows how to cost a
heterogeneous topology (``HWConfig``), and PR 5's ``relayout_flat`` can
restack parameters exactly across arbitrary plan changes.  This module
closes the loop with a supervisory state machine:

    monitor ──fault──> degrade HWConfig ──> ilp.replan() ──> new mesh
        ^                                                       │
        │                 in-memory relayout (or ckpt restore)  │
        └────────────────── continue training <─────────────────┘

Pieces:

* **Fault taxonomy** — :class:`FaultEvent` + typed :class:`FaultError`
  subclasses (``HostLossError``, ``LinkDegradedError``) the trainer's
  step loop raises, either from the deterministic
  :class:`~repro.runtime.trainer.FailureInjector` (tests/CI chaos) or
  from a pluggable :class:`FaultMonitor`.
* **Monitors** — :class:`HeartbeatMonitor` (staleness of peer liveness
  files) and :class:`StragglerEscalation` (persistent slow steps via the
  existing :class:`~repro.runtime.trainer.StragglerDetector` escalate to
  a replanning fault with the measured slowdown).
* **Topology** — the supervisor's view of surviving hosts/chips and
  measured link health; maps to a degraded ``HWConfig`` for the ILP and
  to the surviving jax device list for the relaunch mesh.
* **ElasticSupervisor** — the loop: bounded replan budget, exponential
  restart backoff, device-to-device state carry via
  ``models/params.relayout_flat`` when the surviving mesh overlaps the
  old one, checkpoint-restore fallback otherwise, and graceful
  degradation to the last-known-good plan when the ILP fails or emits
  something inexecutable.
"""
from __future__ import annotations

import json
import math
import os
import time
from dataclasses import dataclass, field, replace
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from repro import obs
from repro.core.plan import ParallelPlan

# --------------------------------------------------------------------------
# fault taxonomy
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class FaultEvent:
    """One detected fault: what kind, when, and the measurements the
    supervisor needs to degrade the HWConfig for replanning."""
    kind: str                    # 'host-loss' | 'link-degraded' |
    #                              'straggler' | 'heartbeat-stale' |
    #                              'worker-failure'
    step: int = -1
    host: Optional[int] = None   # lost/stale host index (host-loss kinds)
    link_bw: Optional[float] = None   # measured bytes/s (link-degraded)
    slowdown: float = 1.0        # step-time inflation factor (straggler)
    detail: str = ""

    def describe(self) -> str:
        bits = [self.kind, f"step={self.step}"]
        if self.host is not None:
            bits.append(f"host={self.host}")
        if self.link_bw is not None:
            bits.append(f"bw={self.link_bw / 1e9:.2f}GB/s")
        if self.slowdown != 1.0:
            bits.append(f"slowdown={self.slowdown:.1f}x")
        if self.detail:
            bits.append(self.detail)
        return " ".join(bits)


class FaultError(RuntimeError):
    """A detected topology/health fault.  Carries the :class:`FaultEvent`
    so the supervisor can dispatch on kind; deliberately a RuntimeError
    subclass so legacy ``run_with_restarts`` callers fail loudly with a
    pointer to the elastic supervisor instead of restart-looping a mesh
    that no longer exists."""

    def __init__(self, event: FaultEvent):
        super().__init__(event.describe())
        self.event = event


class HostLossError(FaultError):
    def __init__(self, step: int, host: int, detail: str = ""):
        super().__init__(FaultEvent("host-loss", step=step, host=host,
                                    detail=detail))


class LinkDegradedError(FaultError):
    def __init__(self, step: int, link_bw: float, detail: str = ""):
        super().__init__(FaultEvent("link-degraded", step=step,
                                    link_bw=link_bw, detail=detail))


def fault_from_event(event: FaultEvent) -> FaultError:
    """The typed error a monitor-detected event escalates as."""
    if event.kind == "host-loss":
        return HostLossError(event.step, event.host or 0, event.detail)
    if event.kind == "link-degraded":
        return LinkDegradedError(event.step, event.link_bw or 0.0,
                                 event.detail)
    return FaultError(event)


# --------------------------------------------------------------------------
# pluggable fault monitors
# --------------------------------------------------------------------------
class FaultMonitor:
    """Interface the trainer polls every step.  ``observe_step`` sees each
    completed step's wall time; ``poll`` checks out-of-band state
    (heartbeat files, NIC counters).  Return a :class:`FaultEvent` to
    escalate — the trainer raises it as a :class:`FaultError` for the
    supervisor."""

    def observe_step(self, step: int, dt: float) -> Optional[FaultEvent]:
        return None

    def poll(self, step: int) -> Optional[FaultEvent]:
        return None


@dataclass
class HeartbeatMonitor(FaultMonitor):
    """Watches peer-worker heartbeat files (the atomic JSON the trainer
    writes each step) and escalates hosts whose heartbeat goes stale —
    the supervisor treats a stale host as lost.

    ``paths`` maps host index -> heartbeat file; ``clock`` is injectable
    for deterministic tests."""
    paths: Dict[int, str] = field(default_factory=dict)
    timeout_s: float = 60.0
    clock: Callable[[], float] = time.time
    recorder: object = None          # None -> the process-global recorder
    _reported: set = field(default_factory=set)

    def read(self, path: str) -> Optional[dict]:
        """Parsed heartbeat, or None when missing/half-written (a torn
        non-atomic write must look stale, not crash the monitor)."""
        return read_heartbeat(path)

    def poll(self, step: int) -> Optional[FaultEvent]:
        now = self.clock()
        rec = self.recorder if self.recorder is not None \
            else obs.get_recorder()
        for host, path in self.paths.items():
            if host in self._reported:
                continue
            hb = self.read(path)
            age = now - hb["time"] if hb and "time" in hb else float("inf")
            if age != float("inf"):
                rec.gauge("elastic.heartbeat_age_s", age, host=host,
                          step=step)
                if isinstance(hb.get("step_time_ewma_s"), (int, float)):
                    rec.gauge("elastic.peer_step_ewma_s",
                              hb["step_time_ewma_s"], host=host, step=step)
            if age > self.timeout_s:
                self._reported.add(host)
                return FaultEvent(
                    "heartbeat-stale", step=step, host=host,
                    detail=(f"age={age:.1f}s" if age != float("inf")
                            else "missing"))
        return None


@dataclass
class StragglerEscalation(FaultMonitor):
    """Escalates the existing per-step EWMA/z-score straggler detection
    (:class:`~repro.runtime.trainer.StragglerDetector`) into a replanning
    fault once ``escalate_after`` consecutive steps flag slow — transient
    hiccups stay log lines, a persistently slow peer becomes a measured
    ``slowdown`` the supervisor replans against (AMP-style: the collective
    runs at the slowest peer's pace, so the ILP should re-cost links at
    ``bw / slowdown``).

    With ``peer_paths`` (host index -> heartbeat file, the enriched
    per-host files the trainer writes) the escalation also LOCALIZES the
    straggler: each peer's ``step_time_ewma_s`` is compared, and a host
    whose EWMA exceeds ``slow_factor`` x the median of the others is
    named in the escalated event's ``host`` field — so the supervisor can
    tell a slow host from a globally slow cluster."""
    detector: object = None          # StragglerDetector (default: fresh)
    escalate_after: int = 3
    peer_paths: Dict[int, str] = field(default_factory=dict)
    slow_factor: float = 1.25
    _consecutive: int = 0

    def __post_init__(self):
        if self.detector is None:
            from repro.runtime.trainer import StragglerDetector
            self.detector = StragglerDetector()

    def localize(self) -> Tuple[Optional[int], str]:
        """(slow host, per-host detail) from the peer heartbeats' step-time
        EWMAs; (None, "") when no host stands out (or <2 peers report)."""
        ewma = {}
        for host, path in self.peer_paths.items():
            hb = read_heartbeat(path)
            if hb and isinstance(hb.get("step_time_ewma_s"), (int, float)):
                ewma[host] = float(hb["step_time_ewma_s"])
        if len(ewma) < 2:
            return None, ""
        slow = max(ewma, key=ewma.get)
        rest = sorted(v for h, v in ewma.items() if h != slow)
        peers_med = rest[len(rest) // 2]
        detail = " per-host ewma: " + " ".join(
            f"h{h}={v * 1e3:.1f}ms" for h, v in sorted(ewma.items()))
        if ewma[slow] > self.slow_factor * max(peers_med, 1e-9):
            return slow, detail
        return None, detail

    def observe_step(self, step: int, dt: float) -> Optional[FaultEvent]:
        # mean BEFORE this observation: the healthy baseline the slow
        # step is compared against
        baseline = self.detector.mean or dt
        if self.detector.observe(step, dt):
            self._consecutive += 1
        else:
            self._consecutive = 0
        if self._consecutive >= self.escalate_after:
            self._consecutive = 0
            host, where = self.localize()
            return FaultEvent("straggler", step=step, host=host,
                              slowdown=max(dt / max(baseline, 1e-9), 1.0),
                              detail=f"{self.escalate_after} consecutive "
                                     f"slow steps" + where)
        return None


# --------------------------------------------------------------------------
# topology
# --------------------------------------------------------------------------
@dataclass(frozen=True)
class Topology:
    """The supervisor's current view of the cluster: hosts x chips, which
    hosts are lost, and the measured inter-node bandwidth (None = the
    HWConfig's configured value)."""
    n_hosts: int
    chips_per_host: int
    lost_hosts: frozenset = frozenset()
    link_bw_y: Optional[float] = None

    @property
    def alive_hosts(self) -> Tuple[int, ...]:
        return tuple(h for h in range(self.n_hosts)
                     if h not in self.lost_hosts)

    @property
    def n_chips(self) -> int:
        return len(self.alive_hosts) * self.chips_per_host

    def lose(self, host: int) -> "Topology":
        if host not in range(self.n_hosts) or host in self.lost_hosts:
            raise ValueError(f"host {host} is not an alive host of "
                             f"{self.n_hosts}x{self.chips_per_host}")
        lost = frozenset(self.lost_hosts | {host})
        if len(lost) >= self.n_hosts:
            raise ValueError("cannot lose the last host")
        return replace(self, lost_hosts=lost)

    def degrade_link(self, bw: float) -> "Topology":
        return replace(self, link_bw_y=max(float(bw), 1.0))

    def devices(self, all_devices: Optional[Sequence] = None) -> List:
        """Surviving jax devices: host h owns the contiguous slice
        ``[h*cph, (h+1)*cph)`` of the launch-time device list."""
        if all_devices is None:
            import jax
            all_devices = jax.devices()
        cph = self.chips_per_host
        out: List = []
        for h in self.alive_hosts:
            out.extend(all_devices[h * cph:(h + 1) * cph])
        return out

    def degraded_hw(self, hw) -> "object":
        """The ILP's view of what survived (``HWConfig.degrade``)."""
        return hw.degrade(n_chips=self.n_chips,
                          node_size=min(self.chips_per_host, self.n_chips),
                          link_bw_y=self.link_bw_y)


# --------------------------------------------------------------------------
# plan layout descriptors + state carry
# --------------------------------------------------------------------------
def plan_layout(plan: ParallelPlan) -> Dict:
    """The relayout descriptor (models/params.relayout_flat) of the
    parameter-tree layout a plan trains under."""
    if plan.grouping_signature()[0] == "grouped":
        layout = {"degrees": list(plan.degrees),
                  "schedules": list(plan.schedules)}
        if plan.has_seq_layers:
            # ring-attention seq shards break scan groups exactly like a
            # schedule change does (models/params.plan_groups)
            layout["seqs"] = list(plan.seqs)
        return layout
    # interleaving depth only stacks the params under a pipe axis —
    # normalize v to 1 at pp == 1, mirroring grouping_signature()
    return {"pp": plan.pp,
            "virtual_stages": plan.virtual_stages if plan.pp > 1 else 1}


# every params-like subtree of the (params, opt) state tuple: the three
# optimizer moments AND the grad-compress error-feedback buffers (a
# params-shaped tree when compression is on; the plain None leaf passes
# through the relayout as static either way)
STATE_PREFIXES = ("[0]", "[1]['master']", "[1]['m']", "[1]['v']",
                  "[1]['err']")


def state_remap(cfg, src_meta: Dict, dst_meta: Dict):
    """A flat-leaf ``{keystr: array} -> {keystr: array}`` transform that
    relayouts every params-like subtree of a (params, opt) state tuple
    from the ``src_meta`` plan layout to ``dst_meta`` — shared by the
    checkpoint-restore path (``Trainer._plan_remap``) and the in-memory
    elastic state carry (:meth:`ElasticSupervisor._carry_state`)."""
    from repro.models import params as prm

    def remap(by_key: Dict) -> Dict:
        out = {k: v for k, v in by_key.items()
               if not any(k.startswith(p) for p in STATE_PREFIXES)}
        for p in STATE_PREFIXES:
            sub = {k[len(p):]: v for k, v in by_key.items()
                   if k.startswith(p)}
            if not sub:
                continue
            for k2, v2 in prm.relayout_flat(cfg, sub, src_meta,
                                            dst_meta).items():
                out[p + k2] = v2
        return out

    return remap


def mesh_for(topology: Topology, plan: Optional[ParallelPlan] = None,
             *, default_tp: int = 0, devices: Optional[Sequence] = None):
    """A launch mesh over the surviving devices.

    A plan whose recorded ``mesh_shape`` fits the surviving chip count is
    honored exactly — including a shape using only a SUBSET of the
    survivors (the replanning ILP may decide 4 well-connected chips beat
    6 with a straggler; the first ``prod(mesh_shape)`` surviving devices
    are used).  Otherwise a plain ``(data, model)`` mesh with
    ``tp = default_tp`` (or the largest power of two <= the survivors)
    and everything else data-parallel."""
    from repro.core import compat

    devs = topology.devices(devices)
    n = len(devs)
    if plan is not None and plan.mesh_shape \
            and math.prod(plan.mesh_shape) <= n:
        return compat.make_mesh(
            tuple(plan.mesh_shape), tuple(plan.mesh_axes),
            devices=devs[:math.prod(plan.mesh_shape)])
    tp = default_tp or 2 ** int(math.log2(n))
    tp = min(tp, n)
    while n % tp:
        tp //= 2
    return compat.make_mesh((n // tp, tp), ("data", "model"),
                            devices=devs)


# --------------------------------------------------------------------------
# the supervisor
# --------------------------------------------------------------------------
@dataclass
class ElasticConfig:
    """Supervisor knobs."""
    max_replans: int = 3         # bounded replan budget per run
    max_restarts: int = 3        # plain worker-failure restarts
    backoff_s: float = 0.05      # restart backoff base (exponential)
    backoff_factor: float = 2.0
    replan_options: Tuple[int, ...] = (2, 4, 8, 16)
    replan_time_limit: float = 5.0
    restartable: Tuple = (RuntimeError,)


class ElasticSupervisor:
    """The fault-handling training loop.

    ``make_trainer(topology, plan)`` builds a Trainer for the surviving
    topology under ``plan`` (None = the caller's launch-time default);
    the supervisor owns WHEN to rebuild, with what plan, and how state
    crosses the boundary.  ``hw`` is the healthy-cluster HWConfig the
    degraded views derive from; ``shape``/``hp`` describe the workload
    for the replanning ILP.
    """

    def __init__(self, make_trainer, *, topology: Topology, cfg, shape,
                 hp, hw=None, econfig: Optional[ElasticConfig] = None,
                 log_fn: Callable[[str], None] = print,
                 telemetry=None):
        from repro.core.planner import costmodel as cm
        self.make_trainer = make_trainer
        self.topology = topology
        self.cfg = cfg
        self.shape = shape
        self.hp = hp
        self.hw = hw or cm.V5E.degrade(
            n_chips=topology.n_chips, node_size=topology.chips_per_host)
        self.ec = econfig or ElasticConfig()
        self.log = log_fn
        # same convention as Trainer: structured events with log_fn as the
        # console sink, so "[elastic] ..." lines keep printing by default
        self.rec = (telemetry if telemetry is not None
                    else obs.Recorder(console=log_fn))
        self.plan: Optional[ParallelPlan] = None  # None = launch default
        self.events: List[FaultEvent] = []
        self.replans = 0
        self.restarts = 0
        # after a fault the successor trainer is built eagerly (the state
        # relayout needs its specs); the next loop iteration reuses it
        # instead of compiling twice
        self._prebuilt = None

    # ---- replanning ------------------------------------------------------
    def _replan(self, event: FaultEvent,
                last_good: Optional[ParallelPlan]) -> None:
        """Re-run the ILP against the degraded topology; on failure (or
        budget exhaustion) degrade gracefully to the last-known-good plan
        clamped to the survivors."""
        from repro.core.planner import ilp

        hw_d = self.topology.degraded_hw(self.hw)
        if event.kind == "straggler" and event.slowdown > 1.0:
            hw_d = hw_d.degrade(bw_scale=1.0 / event.slowdown)
        if self.replans >= self.ec.max_replans:
            self.rec.event(
                "elastic.replan_exhausted", budget=self.ec.max_replans,
                msg=f"[elastic] replan budget exhausted "
                    f"({self.ec.max_replans}); keeping last-known-good")
            self.plan = self._fallback_plan(last_good)
            return
        t0 = time.perf_counter()
        try:
            pr = ilp.replan(self.cfg, self.shape, self.hp, hw_d,
                            options=self.ec.replan_options,
                            time_limit=self.ec.replan_time_limit)
            new_plan = pr.plan.validate_for(self.cfg)
            if math.prod(new_plan.mesh_shape or (0,)) > self.topology.n_chips:
                raise ValueError(
                    f"replanned mesh {new_plan.mesh_shape} exceeds the "
                    f"{self.topology.n_chips} surviving chips")
            self.replans += 1
            self.plan = new_plan
            dur = time.perf_counter() - t0
            self.rec.observe("elastic.replan_s", dur, step=event.step)
            self.rec.event(
                "elastic.replan", kind=event.kind, step=event.step,
                dur_s=round(dur, 4), plan=new_plan.summary(),
                msg=f"[elastic] replanned after {event.kind}: "
                    f"{pr.summary()} -> {new_plan.summary()}")
        except Exception as e:
            self.rec.observe("elastic.replan_s",
                             time.perf_counter() - t0, step=event.step)
            self.rec.event(
                "elastic.replan_failed", kind=event.kind, step=event.step,
                msg=f"[elastic] replan failed ({e!r}); degrading to "
                    f"last-known-good plan")
            self.plan = self._fallback_plan(last_good)

    def _fallback_plan(self, last_good: Optional[ParallelPlan]
                       ) -> Optional[ParallelPlan]:
        """Last-known-good, clamped to the surviving chip count: keep the
        schedules, shrink tp to the largest power of two that fits."""
        n = self.topology.n_chips
        if last_good is None:
            return None
        tp = 2 ** int(math.log2(n))
        if last_good.mesh_shape and math.prod(last_good.mesh_shape) <= n:
            return last_good
        return ParallelPlan.from_hparams(
            self.hp, last_good.num_layers,
            schedules=[last_good.primary_schedule] * last_good.num_layers,
            mesh_shape=(n // tp, tp), mesh_axes=("data", "model"))

    # ---- state carry -----------------------------------------------------
    def _carry_state(self, trainer, dst_trainer):
        """Device-to-device continuation: export the faulted trainer's
        live state, relayout it into the new trainer's parameter layout,
        and land it on the surviving mesh.  Returns the (params, opt,
        step) tuple for ``dst_trainer.train(state=...)``, or None when
        there is nothing to carry / the relayout fails (-> checkpoint
        restore)."""
        exported = trainer.export_state()
        if exported is None:
            return None
        try:
            with self.rec.span("elastic.state_carry_s"):
                state = dst_trainer.import_state(exported)
            self.rec.event(
                "elastic.state_carry", step=exported["step"],
                msg=f"[elastic] carried live state in-memory to step "
                    f"{exported['step']} "
                    f"({exported['sig'][0]} -> "
                    f"{dst_trainer.plan.grouping_signature()[0]})")
            return state
        except Exception as e:
            self.rec.event(
                "elastic.state_carry_failed",
                msg=f"[elastic] in-memory relayout failed ({e!r}); "
                    f"falling back to checkpoint restore")
            return None

    # ---- the loop --------------------------------------------------------
    def run(self, total_steps: int, *, ckpt_every: int = 50,
            seed: int = 0) -> Dict:
        losses: List[float] = []
        state = None
        while True:
            if self._prebuilt is not None:
                trainer, self._prebuilt = self._prebuilt, None
            else:
                trainer = self.make_trainer(self.topology, self.plan)
            if self.plan is None:
                # launch default = first last-known-good
                self.plan = trainer.plan
            try:
                res = trainer.train(total_steps, ckpt_every=ckpt_every,
                                    seed=seed, state=state)
                losses.extend(res["losses"])
                return {"losses": losses, "final_step": res["final_step"],
                        "slow_steps": res["slow_steps"],
                        "events": list(self.events),
                        "replans": self.replans,
                        "restarts": self.restarts,
                        "plan": self.plan,
                        "topology": self.topology}
            except (KeyboardInterrupt, SystemExit):
                raise
            except FaultError as e:
                ev = e.event
                self.events.append(ev)
                losses.extend(trainer.run_losses)
                self.rec.counter("elastic.faults", kind=ev.kind)
                self.rec.event(
                    "elastic.fault", kind=ev.kind, step=ev.step,
                    host=ev.host, slowdown=round(ev.slowdown, 3),
                    msg=f"[elastic] fault: {ev.describe()}")
                last_good = self.plan
                if ev.kind in ("host-loss", "heartbeat-stale"):
                    try:
                        self.topology = self.topology.lose(ev.host or 0)
                    except ValueError as te:
                        self.rec.event(
                            "elastic.unsurvivable",
                            msg=f"[elastic] unsurvivable: {te}")
                        raise e from None
                elif ev.kind == "link-degraded" and ev.link_bw:
                    self.topology = self.topology.degrade_link(ev.link_bw)
                self._replan(ev, last_good)
                new_trainer = self.make_trainer(self.topology, self.plan)
                state = self._carry_state(trainer, new_trainer)
                # hand the already-built trainer to the next iteration
                self._prebuilt = new_trainer
            except self.ec.restartable as e:
                self.restarts += 1
                losses.extend(trainer.run_losses)
                self.events.append(FaultEvent("worker-failure",
                                              detail=repr(e)))
                self.rec.counter("elastic.restarts")
                if self.restarts > self.ec.max_restarts:
                    raise
                wait = self.ec.backoff_s * \
                    self.ec.backoff_factor ** (self.restarts - 1)
                self.rec.event(
                    "elastic.restart", attempt=self.restarts,
                    msg=f"[elastic] worker failed ({e}); restart "
                        f"{self.restarts}/{self.ec.max_restarts} "
                        f"after {wait * 1e3:.0f} ms backoff")
                time.sleep(wait)
                state = None                 # restore from checkpoint
            if trainer.checkpointer.failed_saves:
                n_failed = trainer.checkpointer.failed_saves
                self.rec.event(
                    "elastic.ckpt_write_failures", count=n_failed,
                    msg=f"[elastic] note: {n_failed} failed "
                        f"checkpoint-write attempts so far")


def heartbeat_path(ckpt_dir: str) -> str:
    """Where a trainer writes its liveness file (atomic tmp+rename)."""
    return os.path.join(ckpt_dir, "heartbeat.json")


def read_heartbeat(path: str) -> Optional[dict]:
    """Parsed heartbeat JSON, or None when missing/half-written (a torn
    non-atomic write must look stale, not crash the reader)."""
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError):
        return None
