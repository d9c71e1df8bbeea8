"""A training cell: the trainer's compiled step, driven from the seed.

Set-up builds one ``Trainer`` as ``launch/train.py`` does, gives it weights
made from the seed, compiles its step, and drives that same step through
its first three steps on the traffic's first three batches: those steps are
what the reference checks.  The window then goes on with the same step and
state, on the following batches, for ``seconds``.
"""
from __future__ import annotations

import gc
import math
import shutil
import time
from typing import Dict

import numpy as np

import faults as FL
import flops as F
import harness as H
import traffic as T
import weights as W

CONFIG_KIND = "train"
CHECK_STEPS = 3


def worst_gap(prog: Dict[str, np.ndarray], ref: Dict[str, np.ndarray],
              keep=None) -> float:
    """Largest gap between the program's and the reference's norm of a
    leaf (per layer for stacked leaves), over the larger of that leaf's
    reference norm and the median leaf's."""
    keys = sorted(ref)
    if sorted(prog) != keys:
        raise ValueError(f"leaves differ: {sorted(set(prog) ^ set(keys))}")
    p = np.concatenate([np.asarray(prog[k], np.float64).ravel()
                        for k in keys])
    r = np.concatenate([np.asarray(ref[k], np.float64).ravel()
                        for k in keys])
    med = float(np.median(r))
    gap = np.abs(p - r) / np.maximum(r, med)
    if keep is not None:
        gap = gap[keep]
    return float(np.max(gap))


def moved_leaves(ref_grad: Dict[str, np.ndarray]) -> np.ndarray:
    """Leaves whose reference gradient is above a thousandth of the median
    leaf's: the others move under Adam by round-off alone."""
    r = np.concatenate([np.asarray(ref_grad[k], np.float64).ravel()
                        for k in sorted(ref_grad)])
    return r >= 1e-3 * float(np.median(r))


def compare(prog: dict, ref: dict) -> Dict[str, float]:
    loss = max(abs(a - b) / abs(b)
               for a, b in zip(prog["losses"], ref["losses"]))
    if not all(math.isfinite(x) for x in prog["losses"]):
        loss = float("inf")
    return {"loss_gap": loss,
            "grad_gap": worst_gap(prog["grad"], ref["grad"]),
            "change_gap": worst_gap(prog["change"], ref["change"],
                                    moved_leaves(ref["grad"]))}


def ref_sharding(devs):
    """Each reference leaf split over the chips along its largest dim."""
    if len(devs) == 1:
        return None
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = jax.sharding.Mesh(np.array(devs), ("r",))

    def place(shape):
        spec = [None] * len(shape)
        dims = [i for i in range(len(shape)) if shape[i] % len(devs) == 0]
        if dims:
            spec[max(dims, key=lambda i: shape[i])] = "r"
        return NamedSharding(mesh, P(*spec))
    return place


def build(cfg: dict, devs):
    """The trainer as ``launch/train.py`` builds it."""
    from repro.configs.base import TrainHParams
    from repro.launch.mesh import resolve_launch
    from repro.runtime import Trainer

    o = cfg["optimizer"]
    hp = TrainHParams(schedule=cfg["schedule"],
                      learning_rate=o["learning_rate"],
                      weight_decay=o["weight_decay"],
                      warmup_steps=o["warmup_steps"],
                      total_steps=o["total_steps"], grad_clip=o["grad_clip"],
                      microbatch=cfg["microbatch"])
    acfg = H.program_config(cfg)
    mesh, plan, hp = resolve_launch(acfg, hp, mesh=cfg["mesh"], devices=devs)
    return Trainer(acfg, mesh, hp, global_batch=cfg["global_batch"],
                   seq_len=cfg["seq_len"],
                   ckpt_dir=str(H.CHECKOUT / ".bench_tmp" / "ckpt"),
                   plan=plan, log_fn=H.log)


def setup(cell: H.Cell, seed: int, devs) -> dict:
    """The trainer's compiled step with its state, driven through its first
    ``CHECK_STEPS`` steps on the feed the window goes on with; what the
    check compares is read from those steps as they pass."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.models import params as prm
    from repro.optim import adamw

    cfg, model = cell.config, cell.config["model"]
    B, S = cfg["global_batch"], cfg["seq_len"]
    trainer = build(cfg, devs)
    mesh = trainer.mesh
    micro = trainer.hp.microbatch if trainer.hp.microbatch > 1 else 1
    abstract = prm.abstract_params(trainer.specs, mesh)
    params = W.make(abstract, seed, H.reference(cfg).leaf_std(model))
    _, osh = trainer._shardings()
    opt = jax.jit(lambda p: adamw.init_opt_state(
        p, trainer.specs, trainer.info, zero1=trainer.hp.zero1),
        out_shardings=osh)(params)
    data = ("data",) if "data" in mesh.axis_names else None
    bsh = NamedSharding(mesh, P(None, data) if micro > 1 else P(data))
    feed = T.TrainFeed(cell.traffic, seed=seed, batch=B, seq_len=S,
                       vocab=model["vocab_size"], sharding=bsh,
                       microbatch=micro)
    norms = jax.jit(W.leaf_norms)
    b1 = cfg["optimizer"]["beta1"]
    try:
        _, batch = next(feed)
        step = trainer.step_fn.lower(params, opt, batch).compile()
        prog = {"losses": []}
        for i in range(CHECK_STEPS):
            if i:
                _, batch = next(feed)
            params, opt, met = step(params, opt, batch)
            prog["losses"].append(float(met["loss"]))
            if i == 0:
                prog["grad"] = {k: np.asarray(v) / (1 - b1)
                                for k, v in norms(opt["m"]).items()}
        w0 = W.make(abstract, seed, H.reference(cfg).leaf_std(model))
        prog["change"] = {k: np.asarray(v) for k, v in jax.jit(
            lambda a, b: norms(jax.tree_util.tree_map(
                lambda x, y: x - y.astype(x.dtype), a, b)))(
            opt["master"], w0).items()}
        del w0
    except BaseException:
        feed.close()
        raise
    return {"trainer": trainer, "step": step, "params": params, "opt": opt,
            "feed": feed, "prog": prog}


def reference_readings(cell: H.Cell, seed: int, devs,
                       prec: str = "f32") -> dict:
    """The reference's losses, first gradient and change over the same
    first steps, on the same batches, from the same seed."""
    cfg, model = cell.config, cell.config["model"]
    batches = []
    for i in range(CHECK_STEPS):
        toks = T.train_batch(cell.traffic, seed=seed, step=i,
                             batch=cfg["global_batch"],
                             seq_len=cfg["seq_len"],
                             vocab=model["vocab_size"])
        batches.append((toks[:, :-1], toks[:, 1:]))
    return H.reference(cfg).run_training(
        model, cfg["optimizer"], seed, batches, prec=prec,
        rows=cfg["reference_rows"], sharding=ref_sharding(devs))


def run(cell: H.Cell, *, seed: int, seconds: float, trace: bool, devs,
        clog: H.CompileLog) -> dict:
    import jax

    cfg, model = cell.config, cell.config["model"]
    B, S = cfg["global_batch"], cfg["seq_len"]
    st = setup(cell, seed, devs)
    step, params, opt, feed, prog = (st["step"], st["params"], st["opt"],
                                     st["feed"], st["prog"])
    del st
    setup_s = time.perf_counter() - H.T0
    setup_compile_s = clog.seconds
    try:
        # the window
        trace_dir = H.CHECKOUT / ".bench_tmp" / f"trace-{cell.name}"
        shutil.rmtree(trace_dir, ignore_errors=True)
        clog.mark()
        waits, losses, traced = [], [], None
        pending = None
        t_start = time.perf_counter()
        while True:
            now = time.perf_counter()
            if trace and traced is None and now - t_start >= 0.3 * seconds:
                if pending is not None:
                    losses.append(float(pending["loss"]))
                    pending = None
                traced = H.start_trace(trace_dir)
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.data"):
                _, batch = next(feed)
            waits.append(time.perf_counter() - t0)
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                params, opt, met = step(params, opt, batch)
            if traced is not None and not traced["done"]:
                traced["steps"] += 1
            if pending is not None:
                with jax.profiler.TraceAnnotation("bench.read"):
                    losses.append(float(pending["loss"]))
            pending = met
            if traced is not None and not traced["done"] and (
                    traced["steps"] >= 5
                    or time.perf_counter() - traced["t"] >= 4.0):
                with jax.profiler.TraceAnnotation("bench.read"):
                    losses.append(float(pending["loss"]))
                pending = None
                H.stop_trace(traced)
            if time.perf_counter() - t_start >= seconds:
                break
        if pending is not None:
            losses.append(float(pending["loss"]))
        t_end = time.perf_counter()
        window_compiles = clog.since_mark()
    finally:
        feed.close()
    steps = len(losses)
    H.log(f"window: {steps} steps in {t_end - t_start} s, {window_compiles} "
          f"compiles, input wait {sum(waits)} s, losses {losses[0]} -> "
          f"{losses[-1]}")
    peak = H.memory_peak(devs)
    hlo = step.as_text() if trace else None
    del params, opt, met, batch, step, pending
    gc.collect()

    # the reference, once the program's state is gone
    t0 = time.perf_counter()
    ref = reference_readings(cell, seed, devs)
    H.log(f"reference: {time.perf_counter() - t0} s; losses program "
          f"{prog['losses']} reference {ref['losses']}")
    gaps = compare(prog, ref)
    limits = cell.limits
    checks = {k: {"value": v, "limit": limits[k]} for k, v in gaps.items()}
    correct = all(v <= limits[k] for k, v in gaps.items())

    peaks = H.peaks(devs[0].device_kind)
    tokens_per_s = steps * B * S / (t_end - t_start)
    mf_tok = F.model_flops_per_token(model, S)
    e2e = {"setup_s": setup_s, "train_tokens_per_s": tokens_per_s,
           "train_mfu": 100.0 * mf_tok * tokens_per_s
           / (len(devs) * peaks["bf16_flops_per_s"])}
    result = {"correct": correct, "attempted": steps,
              "failed": sum(not math.isfinite(x) for x in losses),
              "checks": checks, "memory_peak_bytes": peak,
              "e2e": e2e}
    if trace:
        result.update(_traced(cell, traced, hlo, waits, setup_compile_s, peaks,
                              mf_tok * B * S, len(devs)))
    return result


def _traced(cell, traced, hlo, waits, setup_compile_s, peaks,
            model_flops_step, chips) -> dict:
    TR = H._load(H.BENCH / "trace.py", "bench_trace")
    dots = F.HloDots(hlo)
    red = TR.reduce(TR.Trace.from_dir(str(traced["dir"])),
                    module=dots.module,
                    matmul_computations=dots.matmul_computations())
    shutil.rmtree(traced["dir"], ignore_errors=True)
    ctx = {"kind": "train", "trace": red, "steps": red["module_runs"],
           "setup_compile_s": setup_compile_s, "input_wait_s": waits,
           "executed_dot_flops_per_step": dots.executed_flops(),
           "model_flops_per_step": model_flops_step, "chips": chips,
           "peak_flops": peaks["bf16_flops_per_s"]}
    H.log(f"trace: {red['module_runs']} step runs, busy {red['busy_s']} s "
          f"of {red['window_s']} s, matmul {red['matmul_s']} s, "
          f"collective {red['collective_s']} s")
    return {"per_layer": H.read_per_layer(cell, ctx),
            "busy_s": red["busy_s"], "window_s": red["window_s"],
            "breakdown": {"device_ops": red["device_ops"],
                          "idle_gaps": red["idle_gaps"]}}


def readings(cell: H.Cell, args, faults, *, devs, clog, emit):
    """For ``bench/readings.py``: the first steps' gaps of the program on
    each seed, of the control and of each fault, with no window."""
    refs = {}

    def reference(seed):
        if seed not in refs:
            refs[seed] = reference_readings(cell, seed, devs)
        return refs[seed]

    def program(seed):
        st = setup(cell, seed, devs)
        st["feed"].close()
        prog = st["prog"]
        del st
        gc.collect()
        return prog

    for seed in args.seeds:
        prog = program(seed)
        emit(kind="program", seed=seed, losses=prog["losses"],
             **compare(prog, reference(seed)))
    for seed in args.control_seeds:
        ctrl = reference_readings(cell, seed, devs, prec="fp8")
        emit(kind="control", seed=seed, losses=ctrl["losses"],
             **compare(ctrl, reference(seed)))
    for f in faults:
        for seed in args.fault_seeds:
            with FL.FAULTS[f]():
                prog = program(seed)
            emit(kind=f, seed=seed, losses=prog["losses"],
                 **compare(prog, reference(seed)))
