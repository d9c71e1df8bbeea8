"""What every cell shares: finding its files by name, the chip, the compile
cache, compile counts, per-layer metric readers, and the result line."""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import math
import os
import sys
import time
import typing
from pathlib import Path
from typing import Dict, List, Optional

BENCH = Path(__file__).resolve().parent
CHECKOUT = BENCH.parent
T0 = time.perf_counter()        # process start, as near as Python sees it


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(CHECKOUT / "BENCHMARK.json")


class Cell:
    """One workload of ``BENCHMARK.json`` with its configuration and
    traffic mix loaded from the files it names."""

    def __init__(self, name: str, bm: Optional[dict] = None):
        bm = bm if bm is not None else benchmark()
        wl = [w for w in bm["workloads"] if w["name"] == name]
        if not wl:
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
        self.name, self.entry, self.bm = name, wl[0], bm
        cfg = [c for c in bm["configs"] if c["name"] == wl[0]["config"]][0]
        self.config = load_json(CHECKOUT / cfg["file"])
        self.traffic = load_json(BENCH / "traffic"
                                 / f"{wl[0]['traffic']}.json")
        self.chips = int(wl[0]["chips"])
        lim = BENCH / "limits" / f"{name}.json"
        self.limits = load_json(lim)["limits"] if lim.exists() else None

    def runner(self):
        """The module that drives the cell, ``bench/<kind>_cell.py``, by
        the kind its traffic mix names; it states the kind of
        configuration it runs as ``CONFIG_KIND``."""
        mod = importlib.import_module(f"{self.traffic['kind']}_cell")
        if self.config["kind"] != mod.CONFIG_KIND:
            raise SystemExit(f"{self.name}: a {self.config['kind']} "
                             f"configuration under {self.traffic['kind']} "
                             f"traffic")
        return mod

    def end_to_end(self) -> List[dict]:
        return [m for m in self.bm["end_to_end"]
                if self.name in m.get("workloads", [self.name])]

    def per_layer(self) -> List[dict]:
        return [m for m in self.bm["per_layer"]
                if self.name in m.get("workloads", [self.name])]


# --------------------------------------------------------------------------
# the chip and JAX
# --------------------------------------------------------------------------
def require_chips(n: int):
    """The first ``n`` TPU chips, or exit non-zero with no result."""
    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        raise SystemExit(f"bench: needs TPU chips; JAX's backend is "
                         f"{backend!r}")
    devs = jax.devices()
    if len(devs) < n:
        raise SystemExit(f"bench: the cell needs {n} chips, JAX sees "
                         f"{len(devs)}")
    return devs[:n]


def device_info(devs) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak(devs) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


def enable_compile_cache() -> str:
    """JAX's persistent cache: where ``JAX_COMPILATION_CACHE_DIR`` places
    it, else at the fixed path ``<checkout>/.jax_cache``.  Every program is
    kept, however short its compile, so a second run compiles nothing."""
    import jax
    where = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not where:
        where = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", where)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


class CompileLog:
    """Backend compile seconds and compile-cache hits and misses, from
    JAX's monitoring events (after ``chip_smoke.CompileLog``).  ``mark()``
    starts a count of compiles, such as those inside a timed window."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        self._mark = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def mark(self):
        self._mark = self.compiles

    def since_mark(self) -> int:
        return self.compiles - self._mark


# keys of a configuration's ``model`` that are the benchmark's own and name
# no field of the program's configuration
BENCH_MODEL_KEYS = ("padded_vocab",)


def program_config(cfg: dict):
    """The program's ``ArchConfig`` for a configuration file: the registry
    entry it names, with every key of the file's ``model`` applied to the
    field of that name.  A field that holds a dataclass (``moe``) takes an
    object of that dataclass's fields, applied to the entry's own where it
    has one; a list becomes a tuple.  The output head is untied unless the
    file says otherwise.  A key that names no field raises."""
    sys.path.insert(0, str(CHECKOUT / "src"))
    from repro.configs.registry import get_config
    base = get_config(cfg["registry"])
    model = {k: v for k, v in cfg["model"].items()
             if k not in BENCH_MODEL_KEYS}
    return dataclasses.replace(
        base, **{"tie_embeddings": False,
                 **_fields(type(base), model, f"{cfg['name']}: model",
                           base)})


def _fields(kind, values: dict, where: str, base=None) -> dict:
    """``values`` as fields of the dataclass ``kind``; an object given for
    a field that holds a dataclass is applied to ``base``'s value of that
    field where it has one."""
    hints = typing.get_type_hints(kind)
    unknown = sorted(set(values) - {f.name for f in dataclasses.fields(kind)})
    if unknown:
        raise KeyError(f"{where}: {unknown} name no field of "
                       f"{kind.__name__}")
    out = {}
    for k, v in values.items():
        if isinstance(v, dict):
            sub = _dataclass_of(hints[k])
            if sub is None:
                raise TypeError(f"{where}.{k}: an object for a field that "
                                f"holds no dataclass")
            inner = getattr(base, k, None)
            kw = _fields(sub, v, f"{where}.{k}", inner)
            v = sub(**kw) if inner is None else dataclasses.replace(inner,
                                                                    **kw)
        elif isinstance(v, list):
            v = tuple(v)
        out[k] = v
    return out


def _dataclass_of(hint):
    """The dataclass a field's type names, alone or in an ``Optional``."""
    for t in (hint, *typing.get_args(hint)):
        if dataclasses.is_dataclass(t):
            return t
    return None


def reference(cfg: dict):
    path = BENCH / "references" / f"{cfg['reference']}.py"
    return _load(path, f"bench_reference_{cfg['reference']}")


def count(cfg: dict, name: str):
    """The configuration's own count ``name``: ``model_flops_per_token(
    model, seq_len)`` or ``serve_tick(model, positions)`` of its reference
    module where that defines it, else ``flops.py``'s for a dense
    decoder."""
    import flops as F
    return getattr(reference(cfg), name, None) or getattr(F, name)


def start_trace(trace_dir) -> dict:
    """Start the profiler and open the ``bench.window`` span."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    span = jax.profiler.TraceAnnotation("bench.window")
    span.__enter__()
    return {"dir": trace_dir, "span": span, "steps": 0, "done": False,
            "t": time.perf_counter()}


def stop_trace(traced: dict):
    import jax
    traced["span"].__exit__(None, None, None)
    jax.profiler.stop_trace()
    traced["done"] = True


# --------------------------------------------------------------------------
# peaks and per-layer readers
# --------------------------------------------------------------------------
def peaks(device_kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device {device_kind!r} in "
                       f"bench/peaks.json")
    return table[device_kind]


def _load(path: Path, modname: str):
    """A module of the benchmark found by its file, loaded once."""
    if modname in sys.modules:
        return sys.modules[modname]
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    spec.loader.exec_module(mod)
    return mod


def read_per_layer(cell: Cell, ctx: dict) -> Dict[str, dict]:
    """Each per-layer metric of the cell, by its reader
    ``bench/metrics/<name>.py``; a reader that finds nothing to read
    returns None and the metric is left out."""
    out = {}
    for m in cell.per_layer():
        mod = _load(BENCH / "metrics" / f"{m['name']}.py",
                    "bench_metric_" + m["name"].replace(".", "_"))
        v = mod.read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


# --------------------------------------------------------------------------
# the result
# --------------------------------------------------------------------------
def emit(*, correct: bool, attempted: int, failed: int, metrics: dict,
         device: dict, checks: Dict[str, dict],
         breakdown: Optional[dict] = None):
    """Each compared number beside its limit as the last lines of stderr,
    and the result as the last line of stdout, checks last."""
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
        if not math.isfinite(c["value"]):
            c["value"] = None       # strict JSON has no inf or nan
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    sys.stdout.flush()
    print(json.dumps(out), flush=True)
