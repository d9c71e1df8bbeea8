"""The one reduction from a profiler trace to device metrics.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes.  On a TPU each chip
is a plane ``/device:TPU:<n>`` whose line ``XLA Ops`` holds one event per
executed HLO instruction (its text is the instruction: ``%name = shape
kind(...), ..., calls=%computation``), ``Async XLA Ops`` the spans of
asynchronous ones, and ``XLA Modules`` one event per program run.  Host
threads are planes ``/host:...``; the benchmark's own spans are events named
``bench.<what>``, the program's own ``serve.<what>`` or ``train.<what>``, on
the same clock.

The traced window is the benchmark's span ``bench.window``.  Within it:

* busy: the union of the ``XLA Ops`` intervals of a chip; idle is the rest;
* per-op time, summed by instruction name;
* collective time: the union of the intervals in which a collective runs
  (its ops, and the spans of asynchronous ones); exposed: the part of it in
  which no other op runs on that chip.  The TPU compiler's asynchronous
  all-reduce is an ``async-collective-start`` and an
  ``async-collective-done`` (both ``fusion`` instructions) with the
  reduction's steps fused into the compute between them: the two ends are
  collectives, the fusion that carries the steps (it calls an
  ``async_collective_fusion.*`` computation) is compute;
* matmul time: the ops that call a computation holding a matmul (the set
  comes from the compiled module, see ``flops.HloDots``);
* idle gaps, each labelled with the innermost ``bench.*`` span that covers
  its middle: what the host was doing while the chip waited.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Set, Tuple

COLLECTIVE_WORDS = ("all-reduce", "all-gather", "reduce-scatter",
                    "collective-permute", "all-to-all", "ragged-all-to-all",
                    "collective-broadcast", "send", "recv",
                    "async-collective-start", "async-collective-done")
PROGRAM_SPAN_PREFIXES = ("serve.", "train.")
_KIND_RE = re.compile(r" ([a-z][\w\-]*)\(")
_CALLS_RE = re.compile(r"calls=%?([\w\.\-]+)")
_CONTROL = ("while", "call", "conditional")

Interval = Tuple[float, float]


class Op:
    __slots__ = ("name", "kind", "calls", "start", "end")

    def __init__(self, text: str, start: float, end: float):
        head, _, tail = text.partition(" = ")
        self.name = head.strip().lstrip("%")
        m = _KIND_RE.search(" " + tail) if tail else None
        self.kind = m.group(1) if m else re.sub(r"\.\d+$", "", self.name)
        c = _CALLS_RE.search(tail)
        self.calls = c.group(1) if c else None
        self.start, self.end = start, end

    def collective(self) -> bool:
        words = (self.kind, self.name, self.calls or "")
        return any(w in x for w in COLLECTIVE_WORDS for x in words)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def measure(intervals: List[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def intersect(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Both inputs merged and sorted."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def clip(intervals: Iterable[Interval], lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


class Trace:
    """Device ops and host spans of one trace, in seconds: the
    benchmark's spans in ``spans``, the program's in ``program_spans``."""

    def __init__(self, ops: Dict[int, List[Op]],
                 async_ops: Dict[int, List[Op]],
                 modules: Dict[int, List[Tuple[str, float, float]]],
                 spans: List[Tuple[str, float, float]],
                 program_spans: List[Tuple[str, float, float]] = ()):
        self.ops, self.async_ops, self.modules = ops, async_ops, modules
        self.spans, self.program_spans = spans, list(program_spans)

    @classmethod
    def from_dir(cls, trace_dir: str) -> "Trace":
        files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
        from jax.profiler import ProfileData
        return cls.from_profile(ProfileData.from_file(max(
            files, key=os.path.getmtime)))

    @classmethod
    def from_profile(cls, pd) -> "Trace":
        ops, async_ops, modules = (defaultdict(list), defaultdict(list),
                                   defaultdict(list))
        spans, program = [], []
        for plane in pd.planes:
            m = re.match(r"/device:[A-Z]+:(\d+)$", plane.name)
            if m:
                dev = int(m.group(1))
                for line in plane.lines:
                    for e in line.events:
                        s = e.start_ns * 1e-9
                        t = s + e.duration_ns * 1e-9
                        if line.name == "XLA Ops":
                            ops[dev].append(Op(e.name, s, t))
                        elif line.name == "Async XLA Ops":
                            async_ops[dev].append(Op(e.name, s, t))
                        elif line.name == "XLA Modules":
                            modules[dev].append((e.name, s, t))
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name.startswith("bench."):
                            into = spans
                        elif e.name.startswith(PROGRAM_SPAN_PREFIXES):
                            into = program
                        else:
                            continue
                        s = e.start_ns * 1e-9
                        into.append((e.name, s, s + e.duration_ns * 1e-9))
        return cls(dict(ops), dict(async_ops), dict(modules), spans,
                   program)

    def window(self) -> Interval:
        w = [(s, e) for n, s, e in self.spans if n == "bench.window"]
        if not w:
            raise ValueError("the trace has no bench.window span")
        return w[0]


def reduce(tr: Trace, *, module: Optional[str] = None,
           matmul_computations: Set[str] = frozenset(),
           top: int = 10) -> dict:
    """Device metrics of the traced window.  ``module`` restricts the
    matmul and step counts to runs of the program whose HLO module has that
    name; ``matmul_computations`` names the computations holding a matmul
    in that module."""
    lo, hi = tr.window()
    devs = sorted(tr.ops)
    if not devs:
        raise ValueError("the trace holds no device ops")
    busy = {}
    for d in devs:
        busy[d] = union(clip(((o.start, o.end) for o in tr.ops[d]), lo, hi))
    d0 = devs[0]
    ops0 = [o for o in tr.ops[d0] if o.end > lo and o.start < hi]

    # the runs of the step's module on chip 0, for matmul and step counts
    runs = [(s, e) for n, s, e in tr.modules.get(d0, [])
            if e > lo and s < hi and (module is None
                                      or n.split("(")[0] == module)]
    starts = [s for s, _ in sorted(runs)]
    ends = [e for _, e in sorted(runs)]

    def in_module(o: Op) -> bool:
        i = bisect.bisect_right(starts, o.start) - 1
        return i >= 0 and o.start < ends[i]

    coll, comp, matmul_s = [], [], 0.0
    by_op: Dict[str, float] = defaultdict(float)
    for o in ops0:
        s, e = max(o.start, lo), min(o.end, hi)
        if o.kind in _CONTROL:
            continue
        is_coll = o.collective()
        is_mm = (not is_coll and (o.kind in ("convolution", "dot")
                                  or o.calls in matmul_computations)
                 and (module is None or in_module(o)))
        tag = " [collective]" if is_coll else " [matmul]" if is_mm else ""
        by_op[o.name + tag] += e - s
        (coll if is_coll else comp).append((s, e))
        if is_mm:
            matmul_s += e - s
    for o in tr.async_ops.get(d0, []):
        if o.collective():
            coll.extend(clip([(o.start, o.end)], lo, hi))
    coll, comp = union(coll), union(comp)
    exposed = measure(coll) - measure(intersect(coll, comp))

    # idle gaps on chip 0, labelled by the innermost host span over them
    gaps, prev = [], lo
    for s, e in busy[d0] + [(hi, hi)]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    spans = [(n, s, e) for n, s, e in tr.spans if n != "bench.window"]

    def label(s: float, e: float) -> str:
        mid = 0.5 * (s + e)
        cover = [(e2 - s2, n) for n, s2, e2 in spans if s2 <= mid <= e2]
        return min(cover)[1] if cover else "outside bench spans"

    gaps.sort(key=lambda g: g[0] - g[1])
    window = hi - lo
    return {
        "window_s": window,
        "busy_s": sum(measure(b) for b in busy.values()) / len(devs),
        "busy_s_dev0": measure(busy[d0]),
        "chips": len(devs),
        "module_runs": len(runs),
        "collective_s": measure(coll),
        "exposed_collective_s": exposed,
        "matmul_s": matmul_s,
        "device_ops": sorted(([k, v] for k, v in by_op.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": [[label(s, e), e - s] for s, e in gaps[:top]],
    }
