"""Which part of the program each device op and each idle gap belongs to.

The reduction in ``trace.py`` splits a traced window by op kind (matmul,
collective) and labels idle gaps by the benchmark's own ``bench.*`` spans.
This module splits it by what the program says about itself:

* **pass**, from the compiled module: each instruction's scope path is the
  ``op_name`` of its metadata (for a fusion with none, that of the root of
  its fused computation; of several paths joined by ``;``, the first).  An
  instruction the compiler made with no path at all, such as a layout copy
  inside a loop, takes the path of the one instruction that calls its
  computation (the loop, for its body).  JAX writes ``jvp(`` into the
  forward pass's paths and ``transpose(`` into the backward pass's,
  recompute inside the backward pass included; the optimizer stages its
  update under ``optim.adamw``.  So an op is ``bwd`` if its path holds
  ``transpose(``, else ``fwd`` if it holds ``jvp(``, else ``opt`` if it
  holds ``optim.``, else ``other``;
* **TMP scope**: the ``tmp.*`` components of the path (``core/tmp.py`` and
  ``core/schedule.py`` name the schedule phases), such as
  ``tmp.oases.sub0/tmp.reduce``;
* **named scopes**: every component of the path that the program named
  itself (``jax.named_scope``, ``obs.phase_scope``): a dotted name whose
  parts start with a letter, such as ``tmp.oases.sub0``, ``tmp.reduce``,
  ``optim.adamw`` or ``moe.dispatch``; not one of JAX's own
  (``jit(step)``, ``transpose(jvp())``, ``while``, ``dot_general``) nor an
  instruction's name the compiler wrote there (``reshape.799``);
* **program spans**: the host events the program opens itself
  (``obs.trace_annotation``), named ``serve.*`` or ``train.*``, on the same
  clock as the device ops (``trace.Trace.program_spans``).

Chip 0's busy time is split among the passes by the innermost op running at
each instant, so the passes add up to ``busy_s_dev0``; among the named
scopes the same way, each piece counted once under every scope of its op's
path, so a scope's time holds that of the scopes inside it; its collective
and exposed time (as ``trace.reduce`` defines them) by the innermost
collective, so they add up to ``collective_s`` and ``exposed_collective_s``;
its idle gaps by the innermost program span over each gap's middle.
``READERS`` turns these into per-step and per-tick numbers.
"""
from __future__ import annotations

import bisect
import math
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Tuple

import flops as F
import harness as H

TR = H._load(H.BENCH / "trace.py", "bench_trace")

PASSES = ("fwd", "bwd", "opt", "other")
NO_SPAN = "outside program spans"
NO_SCOPE = "(no tmp scope)"

_OP_NAME_RE = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_ROOT_RE = re.compile(r"^\s*ROOT\s+%?([\w\.\-]+)\s*=")
_CALLED_RE = re.compile(r"(?:body|condition|to_apply|calls)=%?([\w\.\-]+)")
_SCOPE_RE = re.compile(r"^[A-Za-z_]\w*(?:\.[A-Za-z_]\w*)+$")

Labelled = Tuple[float, float, object]
Where = Tuple[str, str, Tuple[str, ...]]   # (pass, TMP scope, named scopes)
OTHER: Where = ("other", "", ())


# --------------------------------------------------------------------------
# attribution from the compiled module
# --------------------------------------------------------------------------
def pass_of(path: str) -> str:
    if "transpose(" in path:
        return "bwd"
    if "jvp(" in path:
        return "fwd"
    if "optim." in path:
        return "opt"
    return "other"


def tmp_scope_of(path: str) -> str:
    return "/".join(c for c in path.split("/") if c.startswith("tmp."))


def scopes_of(path: str) -> Tuple[str, ...]:
    """The named scopes of a path, outermost first, each once."""
    return tuple(dict.fromkeys(c for c in path.split("/")
                               if _SCOPE_RE.match(c)))


def scope_paths(text: str) -> Dict[str, str]:
    """{instruction name: scope path} for every instruction of a compiled
    module's text (``compiled.as_text()``)."""
    comps, _, _, _ = F.parse_hlo(text)
    root_names = {m.group(1) for m in map(_ROOT_RE.match, text.splitlines())
                  if m}
    roots = {c: n for c, ops in comps.items() for n, _, _, _ in ops
             if n in root_names}
    paths: Dict[str, Dict[str, str]] = {}
    calls: Dict[str, Dict[str, Optional[str]]] = {}
    callers: Dict[str, List[Tuple[str, str]]] = defaultdict(list)
    for comp, ops in comps.items():
        paths[comp], calls[comp] = {}, {}
        for name, kind, _, rest in ops:
            m = _OP_NAME_RE.search(rest)
            paths[comp][name] = m.group(1).split(";")[0] if m else ""
            c = TR._CALLS_RE.search(rest) if kind == "fusion" else None
            calls[comp][name] = c.group(1) if c else None
            for sub in _CALLED_RE.findall(rest):
                callers[sub].append((comp, name))

    def own(comp: str, name: str) -> str:
        p, sub = paths[comp][name], calls[comp][name]
        return p or (own(sub, roots[sub]) if sub in roots else "")

    def path(comp: str, name: str) -> str:
        p, seen = own(comp, name), set()
        while not p and len(callers[comp]) == 1 and comp not in seen:
            seen.add(comp)
            comp, name = callers[comp][0]
            p = own(comp, name)
        return p

    return {name: path(comp, name)
            for comp, names in paths.items() for name in names}


def attribute(text: str) -> Dict[str, Tuple[str, str]]:
    """{instruction name: (pass, TMP scope)} for every instruction of a
    compiled module's text."""
    return {name: (pass_of(p), tmp_scope_of(p))
            for name, p in scope_paths(text).items()}


# --------------------------------------------------------------------------
# the trace
# --------------------------------------------------------------------------
def innermost(items: Iterable[Labelled]) -> List[Labelled]:
    """Split the union of labelled intervals into pieces, each carrying
    the label of the interval that started last among those covering it
    (the innermost, where intervals nest)."""
    out: List[Labelled] = []
    stack: List[Tuple[float, object]] = []
    t = -math.inf

    def advance(to: float):
        nonlocal t
        while stack and t < to:
            end, label = stack[-1]
            stop = min(end, to)
            if stop > t:
                out.append((t, stop, label))
                t = stop
            if end <= to:
                stack.pop()
            else:
                break
        t = max(t, to)

    for s, e, label in sorted(items, key=lambda x: (x[0], -x[1])):
        if e <= s:
            continue
        advance(s)
        stack.append((e, label))
    advance(math.inf)
    return out


def _sum(pieces: Iterable[Labelled]) -> Dict[object, float]:
    out: Dict[object, float] = defaultdict(float)
    for s, e, label in pieces:
        out[label] += e - s
    return out


def reduce(tr, paths: Optional[Dict[str, str]] = None, *,
           module: Optional[str] = None, top: int = 10) -> dict:
    """Chip 0's time in the traced window by pass, named scope, TMP scope
    and program span, and its ``top`` longest idle gaps with their program
    span.  ``paths`` is ``scope_paths()`` of the module named ``module``;
    ops of other programs, and any op without a path, count as ``other``
    with no scope."""
    lo, hi = tr.window()
    d0 = sorted(tr.ops)[0]
    where = {p: (pass_of(p), tmp_scope_of(p), scopes_of(p))
             for p in set((paths or {}).values())}
    passes = {name: where[p] for name, p in (paths or {}).items()}
    runs = sorted((s, e) for n, s, e in tr.modules.get(d0, [])
                  if e > lo and s < hi
                  and (module is None or n.split("(")[0] == module))

    def label(o) -> Where:
        if module is not None and not any(s <= o.start < e for s, e in runs):
            return OTHER
        return passes.get(o.name, OTHER)

    ops = [(max(o.start, lo), min(o.end, hi), o) for o in tr.ops[d0]
           if o.end > lo and o.start < hi]
    busy = innermost((s, e, label(o)) for s, e, o in ops)

    # collective and exposed time as ``trace.reduce`` counts them: control
    # ops (loops, calls) are neither
    work = [(s, e, o) for s, e, o in ops if o.kind not in TR._CONTROL]
    colls = [(s, e, label(o)[:2]) for s, e, o in work if o.collective()]
    colls += [(s, e, label(o)[:2]) for s, e, o in (
        (max(o.start, lo), min(o.end, hi), o)
        for o in tr.async_ops.get(d0, []) if o.collective())
        if e > s]
    comp = TR.union((s, e) for s, e, o in work if not o.collective())
    coll = innermost(colls)
    exposed = _exposed(coll, comp)

    gaps, prev = [], lo
    for s, e in TR.union((s, e) for s, e, _ in ops) + [(hi, hi)]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    idle: Dict[str, float] = defaultdict(float)
    labelled = []
    for s, e in gaps:
        mid = 0.5 * (s + e)
        cover = [(e2 - s2, n) for n, s2, e2 in tr.program_spans
                 if s2 <= mid <= e2]
        labelled.append([min(cover)[1] if cover else NO_SPAN, e - s])
        idle[labelled[-1][0]] += e - s

    by_pass: Dict[str, float] = defaultdict(float)
    by_scope: Dict[str, float] = defaultdict(float)
    for (p, _, named), t in _sum(busy).items():
        by_pass[p] += t
        for scope in named:
            by_scope[scope] += t
    cs, xs = _sum(coll), _sum(exposed)
    scopes = sorted(set(cs), key=lambda k: (-cs[k], k))
    return {
        "busy_s_by_pass": {p: by_pass.get(p, 0.0) for p in PASSES},
        "busy_s_by_scope": dict(sorted(by_scope.items(),
                                       key=lambda kv: (-kv[1], kv[0]))),
        "collective_s_by_pass": {p: sum(v for k, v in cs.items()
                                        if k[0] == p) for p in PASSES},
        "exposed_s_by_pass": {p: sum(v for k, v in xs.items()
                                     if k[0] == p) for p in PASSES},
        "collective_by_scope": [[k[0], k[1] or NO_SCOPE, cs[k],
                                 xs.get(k, 0.0)] for k in scopes],
        "idle_s_dev0": sum(e - s for s, e in gaps),
        "idle_by_program_span": sorted(([k, v] for k, v in idle.items()),
                                       key=lambda kv: -kv[1]),
        "longest_idle_gaps": sorted(labelled, key=lambda g: -g[1])[:top],
    }


def _exposed(coll: List[Labelled], comp) -> List[Labelled]:
    """The pieces of ``coll`` in which no op of ``comp`` (merged, sorted)
    runs."""
    ends = [e for _, e in comp]
    out = []
    for a, b, label in coll:
        t = a
        for i in range(bisect.bisect_right(ends, a), len(comp)):
            s, e = comp[i]
            if s >= b:
                break
            if s > t:
                out.append((t, s, label))
            t = max(t, e)
        if b > t:
            out.append((t, b, label))
    return out


# --------------------------------------------------------------------------
# per-step and per-tick numbers
# --------------------------------------------------------------------------
def _train(ctx) -> bool:
    return ctx.get("kind") == "train" and bool(ctx.get("phases")) \
        and bool(ctx.get("steps"))


def _per_step(key: str, p: str):
    def read(ctx):
        if not _train(ctx):
            return None
        return 1e3 * ctx["phases"][key][p] / ctx["steps"]
    return read


def _exposed_per_step(p: str):
    def read(ctx):
        if not _train(ctx) or not ctx["trace"]["collective_s"]:
            return None
        return 1e3 * ctx["phases"]["exposed_s_by_pass"][p] / ctx["steps"]
    return read


def p95(xs: List[float]) -> Optional[float]:
    if not xs:
        return None
    import numpy as np
    return float(np.percentile(np.asarray(xs), 95))


def _served(ctx) -> List[Tuple[float, float, float]]:
    """(t_submit, t_admit, t_first) of the window's finished requests."""
    if ctx.get("kind") != "serve":
        return []
    return [t for t in ctx.get("request_times") or ()
            if None not in t]


def _queue_wait(ctx):
    waits = [a - s for s, a, _ in _served(ctx)]
    return None if not waits else 1e3 * p95(waits)


def _prefill(ctx):
    spans = [f - a for _, a, f in _served(ctx)]
    return None if not spans else 1e3 * p95(spans)


def _occupancy(ctx):
    if ctx.get("kind") != "serve" or not ctx.get("ticks") \
            or ctx.get("slot_ticks") is None:
        return None
    return 100.0 * ctx["slot_ticks"] / (ctx["ticks"] * ctx["slots"])


def _engine_idle(ctx):
    ph = ctx.get("phases")
    if ctx.get("kind") != "serve" or not ph or not ctx.get("ticks_traced"):
        return None
    idle = sum(v for k, v in ph["idle_by_program_span"]
               if k.startswith("serve."))
    return 1e3 * idle / ctx["ticks_traced"]


READERS = {
    "train.fwd_ms": _per_step("busy_s_by_pass", "fwd"),
    "train.bwd_ms": _per_step("busy_s_by_pass", "bwd"),
    "train.opt_ms": _per_step("busy_s_by_pass", "opt"),
    "tmp.fwd_exposed_ms": _exposed_per_step("fwd"),
    "tmp.bwd_exposed_ms": _exposed_per_step("bwd"),
    "serve.queue_wait_p95_ms": _queue_wait,
    "serve.prefill_p95_ms": _prefill,
    "serve.slot_occupancy_pct": _occupancy,
    "serve.engine_idle_ms": _engine_idle,
}


def read_all(ctx) -> Dict[str, float]:
    """Each number of ``READERS`` that finds something to read in ctx."""
    out = {}
    for name, fn in READERS.items():
        v = fn(ctx)
        if v is not None:
            out[name] = float(v)
    return out


def tables(ph: dict, per: int, unit: str) -> str:
    """The per-scope and idle-by-span tables, in ms per ``unit``."""
    n = max(per, 1)
    rows = [f"chip-0 busy ms per {unit} by named scope:"]
    for scope, t in ph["busy_s_by_scope"].items():
        rows.append(f"  {scope:40s} {1e3 * t / n:10.4f}")
    rows.append(f"collective ms per {unit} by pass and TMP scope "
                f"(collective, exposed):")
    for p, scope, c, x in ph["collective_by_scope"]:
        rows.append(f"  {p:5s} {scope:40s} {1e3 * c / n:10.4f} "
                    f"{1e3 * x / n:10.4f}")
    rows.append(f"chip-0 idle ms per {unit} by program span "
                f"(total {1e3 * ph['idle_s_dev0'] / n:.4f}):")
    for k, v in ph["idle_by_program_span"]:
        rows.append(f"  {k:40s} {1e3 * v / n:10.4f}")
    rows.append("longest chip-0 idle gaps, ms:")
    for k, v in ph["longest_idle_gaps"]:
        rows.append(f"  {k:40s} {1e3 * v:10.4f}")
    return "\n".join(rows)
