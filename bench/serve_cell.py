"""A serving cell: ``ServingEngine.step()`` under open-loop arrivals.

Set-up builds the engine as ``launch/serve.py`` does, loads weights made
from the seed and runs one tick to compile the decode step (the only shape
the engine runs).  The window then submits each request when it is due and
ticks the engine while it has work; at the close arrivals stop and the
requests already due drain, for at most ``drain_s`` more seconds.
Every request is timed from when it was due.
"""
from __future__ import annotations

import gc
import math
import shutil
import time
from typing import Dict, List

import numpy as np

import faults as FL
import flops as F
import harness as H
import phases as PH
import traffic as T
import weights as W

CONFIG_KIND = "serve"
SAMPLE_ROWS = 16        # requests the reference reads
CHUNK = 256


def build(cfg: dict, devs):
    """The engine as ``launch/serve.py`` builds it, EOS off."""
    from repro.configs.base import TrainHParams
    from repro.launch.mesh import resolve_launch
    from repro.serving import ServingEngine

    acfg = H.program_config(cfg)
    mesh, plan, hp = resolve_launch(acfg, TrainHParams(
        schedule=cfg["schedule"]), mesh=cfg["mesh"], devices=devs)
    return ServingEngine(acfg, mesh, slots=cfg["slots"],
                         max_seq=cfg["max_seq"], hp=hp, plan=plan,
                         eos_id=-1)


def p95(xs: List[float]) -> float:
    if not xs:
        return float("inf")
    xs = sorted(xs)
    if math.isinf(xs[-1]):
        # nearest rank on the tail, so a missing request reads missing
        return xs[max(math.ceil(0.95 * len(xs)) - 1, 0)]
    return float(np.percentile(np.asarray(xs), 95))


def sample(finished, seed: int):
    """Requests for the reference, every served token of each: the one
    with most served tokens, then one from each of ``SAMPLE_ROWS - 1``
    stretches of the rest in the order they were due, drawn from the seed,
    on a slot the sample does not hold yet where the stretch has one."""
    if not finished:
        return []
    first = max(finished, key=lambda r: (len(r.out_tokens), -r.rid))
    rest = sorted((r for r in finished if r is not first),
                  key=lambda r: r.rid)
    g = T.rng(seed, 4)
    out, slots = [first], {first._slot}
    for part in np.array_split(np.arange(len(rest)), SAMPLE_ROWS - 1):
        if not len(part):
            continue
        drawn = [rest[int(i)] for i in g.permutation(part)]
        pick = next((r for r in drawn if r._slot not in slots), drawn[0])
        out.append(pick)
        slots.add(pick._slot)
    return out


def reference_gaps(cfg: dict, traffic: dict, seed: int, reqs, *,
                   control: bool = False, devs=None):
    """Gaps of the served tokens (and of the control's) in the reference,
    over the sampled requests padded to one fixed shape."""
    import jax.numpy as jnp
    ref = H.reference(cfg)
    model = cfg["model"]
    width = traffic["prompt_len"]["max"] + traffic["output_len"]["max"]
    toks = np.zeros((SAMPLE_ROWS, width), np.int32)
    rows, cols, served = [], [], []
    for j, r in enumerate(reqs):
        seq = np.concatenate([r.prompt, np.asarray(r.out_tokens, np.int32)])
        toks[j, :len(seq)] = seq
        p = len(r.prompt)
        for k, t in enumerate(r.out_tokens):
            rows.append(j)
            cols.append(p - 1 + k)
            served.append(t)
    n = len(served)
    k = SAMPLE_ROWS * traffic["output_len"]["max"]
    chunk = min(CHUNK, k)
    pad = -(-k // chunk) * chunk - n
    rows, cols, served = (np.asarray(x + [0] * pad, np.int32)
                          for x in (rows, cols, served))
    w = ref.make_weights(model, seed)
    import jax
    fn = jax.jit(lambda w, t, a, b, s: ref.served_gaps(
        model, w, t, a, b, s, control=control, chunk=chunk))
    g, gc_ = fn(w, jnp.asarray(toks), jnp.asarray(rows), jnp.asarray(cols),
                jnp.asarray(served))
    return np.asarray(g)[:n], np.asarray(gc_)[:n]


def run(cell: H.Cell, *, seed: int, seconds: float, trace: bool, devs,
        clog: H.CompileLog, rate_override: float = 0.0,
        control: bool = False) -> dict:
    import jax

    from repro.models import params as prm
    from repro.serving import Request

    cfg, model, traffic = cell.config, cell.config["model"], cell.traffic
    if rate_override:
        traffic = dict(traffic, arrivals=dict(traffic["arrivals"],
                                              rate_per_s=rate_override))
    eng = build(cfg, devs)
    params = W.make(prm.abstract_params(eng.specs, eng.mesh), seed,
                    H.reference(cfg).leaf_std(model))
    eng.load(params=params)
    del params
    arrivals = T.serve_schedule(traffic, seed=seed, seconds=seconds,
                                vocab=model["vocab_size"])
    # the first tick compiles the decode step; the next may compile it
    # again for the state the first returned (placed, no longer fresh)
    for _ in range(3):
        before = clog.compiles
        eng.step()
        if clog.compiles == before:
            break
    steps0, prompt0, slot0 = (eng.stats[k] for k in (
        "steps", "prompt_tokens", "slot_ticks"))
    setup_s = time.perf_counter() - H.T0
    setup_compile_s = clog.seconds

    trace_dir = H.CHECKOUT / ".bench_tmp" / f"trace-{cell.name}"
    shutil.rmtree(trace_dir, ignore_errors=True)
    clog.mark()
    reqs: Dict[int, Request] = {}
    due: Dict[int, float] = {}
    times: Dict[int, List[float]] = {}
    live: List[Request] = []
    late, ticks, tick_pos, traced_ticks = [], [], [], []
    traced = None
    backlog = {}
    i = 0
    t0 = time.perf_counter()
    close = t0 + seconds
    cap = close + traffic["drain_s"]
    while True:
        now = time.perf_counter()
        if now > cap:
            break
        with jax.profiler.TraceAnnotation("bench.submit"):
            while i < len(arrivals) and t0 + arrivals[i].due_s <= now:
                a = arrivals[i]
                r = Request(rid=a.rid, prompt=a.prompt,
                            max_new_tokens=a.max_new)
                reqs[a.rid], due[a.rid], times[a.rid] = r, t0 + a.due_s, []
                r._seen, r._slot = 0, None
                eng.submit(r)
                live.append(r)
                late.append(now - due[a.rid])
                i += 1
        for mark in (0.5, 1.0):
            if mark not in backlog and now >= t0 + mark * seconds:
                backlog[mark] = eng.queued
        if trace and traced is None and now >= t0 + 0.4 * seconds:
            traced = H.start_trace(trace_dir)
        if traced is not None and not traced["done"] \
                and now >= traced["t"] + 3.0:
            H.stop_trace(traced)
        if live:
            in_trace = traced is not None and not traced["done"]
            if in_trace:
                tick_pos.append([int(eng.pos[s]) for s in range(eng.slots)
                                 if eng.active[s] is not None])
            ts = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.tick"):
                eng.step()
            te = time.perf_counter()
            ticks.append(te - ts)
            if in_trace:
                traced_ticks.append(te - ts)
            still = []
            if any(r._slot is None for r in live):
                for slot, q in enumerate(eng.active):
                    if q is not None and q._slot is None:
                        q._slot = slot
            for r in live:
                n = len(r.out_tokens)
                if n > r._seen:
                    times[r.rid].extend([te] * (n - r._seen))
                    r._seen = n
                if not r.done:
                    still.append(r)
            live = still
        elif i < len(arrivals):
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(max(t0 + arrivals[i].due_s
                               - time.perf_counter(), 0.0))
        else:
            break
    if traced is not None and not traced["done"]:
        H.stop_trace(traced)
    t_end = time.perf_counter()
    window_compiles = clog.since_mark()

    ttft, itl = [], []
    for rid, r in reqs.items():
        ts = times[rid]
        ttft.append(ts[0] - due[rid] if r.done and ts else float("inf"))
        itl.extend(b - a for a, b in zip(ts, ts[1:]))
    unfinished = sum(not r.done for r in reqs.values())
    ticks_n = eng.stats["steps"] - steps0
    prompt_tok = eng.stats["prompt_tokens"] - prompt0
    slot_ticks = eng.stats["slot_ticks"] - slot0
    H.log(f"window: {len(reqs)} requests due, {unfinished} unfinished, "
          f"{ticks_n} ticks, {sum(len(t) for t in times.values())} tokens, "
          f"ran {t_end - t0} s (close at {seconds} s), generator late "
          f"median {np.median(late) if late else 0} s max "
          f"{max(late) if late else 0} s, {window_compiles} compiles")
    peak = H.memory_peak(devs)
    # the decode step's compiled text, for the phase split (from the cache)
    hlo = eng.decode_fn.lower(
        eng.params, eng.state, jax.numpy.asarray(eng.cur_tok),
        jax.numpy.asarray(eng.pos)).compile().as_text() if trace else None
    slots = eng.slots
    eng.params = eng.state = None
    del eng
    gc.collect()

    finished = [r for r in reqs.values() if r.done]
    picked = sample(finished, seed)
    t1 = time.perf_counter()
    gaps, ctrl = reference_gaps(cfg, traffic, seed, picked, control=control)
    widest = float(np.max(gaps)) if len(gaps) else float("inf")
    H.log(f"reference: {time.perf_counter() - t1} s over {len(picked)} "
          f"requests on {len({r._slot for r in picked})} slots, "
          f"{len(gaps)} served tokens")
    limits = cell.limits
    checks = {"widest_logit_gap": {"value": widest,
                                   "limit": limits["widest_logit_gap"]},
              "unfinished": {"value": unfinished, "limit": 0}}
    correct = widest <= limits["widest_logit_gap"] and unfinished == 0

    cap_ms = (cap - t0) * 1e3
    e2e = {"setup_s": setup_s,
           "serve_ttft_p95_ms": min(p95(ttft) * 1e3, cap_ms),
           "serve_itl_p95_ms": p95(itl) * 1e3}
    result = {"correct": correct, "attempted": len(reqs),
              "served_compared": len(gaps),
              "sampled": len(picked),
              "sampled_slots": len({r._slot for r in picked}),
              "backlog": [backlog.get(0.5), backlog.get(1.0)],
              "ttft_p50_ms": 1e3 * float(np.median(ttft)) if ttft else None,
              "tokens": sum(len(t) for t in times.values()),
              "ran_s": t_end - t0,
              "control_widest": float(np.max(ctrl)) if control and len(ctrl)
              else None,
              "failed": unfinished, "checks": checks,
              "memory_peak_bytes": peak, "e2e": e2e}
    if trace:
        result.update(_traced(cell, trace_dir, hlo, {
            "kind": "serve", "config": cfg,
            "setup_compile_s": setup_compile_s, "tick_s": ticks,
            "ticks": ticks_n, "prompt_tokens": prompt_tok,
            "slot_ticks": slot_ticks, "slots": slots,
            "ticks_traced": len(tick_pos), "traced_tick_s": traced_ticks,
            "request_times": [(r.t_submit, r.t_admit, r.t_first)
                              for r in finished]},
            tick_pos, H.peaks(devs[0].device_kind)))
    return result


def _traced(cell, trace_dir, hlo, ctx, tick_pos, peaks) -> dict:
    """The per-layer metrics of the traced ticks, with everything their
    readers may read, taken from the trace before it is deleted."""
    module = F.HloDots(hlo).module
    tr = PH.TR.Trace.from_dir(str(trace_dir))
    red = PH.TR.reduce(tr)
    ph = PH.reduce(tr, PH.scope_paths(hlo), module=module)
    shutil.rmtree(trace_dir, ignore_errors=True)
    tick_counts = H.count(cell.config, "serve_tick")
    bound = 0.0
    for pos in tick_pos:
        fl, nb = tick_counts(cell.config["model"], pos)
        bound += max(fl / peaks["bf16_flops_per_s"],
                     nb / peaks["hbm_bytes_per_s"])
    ctx.update(trace=red, phases=ph, roofline_s=bound)
    H.log(f"trace: {len(tick_pos)} ticks, busy {red['busy_s']} s of "
          f"{red['window_s']} s, roofline bound {bound} s")
    return {"per_layer": H.read_per_layer(cell, ctx), "ctx": ctx,
            "busy_s": red["busy_s"], "window_s": red["window_s"],
            "breakdown": {"device_ops": red["device_ops"],
                          "idle_gaps": red["idle_gaps"]}}


def readings(cell: H.Cell, args, faults, *, devs, clog, emit):
    """For ``bench/readings.py``: a window of ``args.seconds`` on each
    seed, its widest gap, and the control's over the same sampled
    requests; and each fault's."""
    for seed in args.seeds:
        r = run(cell, seed=seed, seconds=args.seconds, trace=False,
                devs=devs, clog=clog, control=seed in args.control_seeds)
        emit(kind="program", seed=seed,
             widest=r["checks"]["widest_logit_gap"]["value"],
             control=r["control_widest"], unfinished=r["failed"],
             compared=r["served_compared"], sampled=r["sampled"],
             slots=r["sampled_slots"], **r["e2e"])
    for f in faults:
        for seed in args.fault_seeds:
            with FL.FAULTS[f]():
                r = run(cell, seed=seed, seconds=args.seconds, trace=False,
                        devs=devs, clog=clog)
            emit(kind=f, seed=seed,
                 widest=r["checks"]["widest_logit_gap"]["value"],
                 unfinished=r["failed"])
