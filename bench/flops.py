"""Operations and bytes, from the configuration and from compiled HLO.

* ``model_flops_per_token``: what one token of training needs, with no
  recompute: 6 x the matmul parameters (the output head included, the
  embedding lookup not) plus 12 x layers x d_model x seq for attention.
* ``executed_dot_flops``: the matmul work a compiled program executes,
  walked from its optimized HLO with every loop body counted as many times
  as it runs (rematerialized work included).  Derived from the program's
  ``launch/hlo_cost.py`` walker, kept here so the yardstick does not move
  with the program; it also reads the TPU compiler's ``convolution`` form
  of matmuls, batch dimensions folded into dilated windows included.
* ``serve_tick``: the operations and bytes one decode tick needs: every
  weight once, and the cache of each active request up to its position.
"""
from __future__ import annotations

import math
import re
from typing import Dict, List, Set, Tuple

import numpy as np

DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
               "s16": 2, "u16": 2, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
               "f32": 4, "s64": 8, "u64": 8, "f64": 8}


# --------------------------------------------------------------------------
# from the configuration
# --------------------------------------------------------------------------
def matmul_params(model: dict) -> int:
    d, f, L = model["d_model"], model["d_ff"], model["num_layers"]
    hq = model["num_heads"] * model["head_dim"]
    hk = model["num_kv_heads"] * model["head_dim"]
    per_layer = d * hq + 2 * d * hk + hq * d + 3 * d * f
    return L * per_layer + d * model["padded_vocab"]


def model_flops_per_token(model: dict, seq_len: int) -> float:
    return 6.0 * matmul_params(model) + 12.0 * model["num_layers"] \
        * model["d_model"] * seq_len


def kv_bytes_per_token(model: dict) -> int:
    return model["num_layers"] * 2 * model["num_kv_heads"] \
        * model["head_dim"] * 2


def serve_tick(model: dict, positions: List[int]) -> Tuple[float, float]:
    """(flops, bytes) one decode tick needs for active requests at
    ``positions`` (the position each writes this tick)."""
    L, d = model["num_layers"], model["d_model"]
    hq = model["num_heads"] * model["head_dim"]
    ctx = sum(p + 1 for p in positions)
    flops = 2.0 * matmul_params(model) * len(positions) + 4.0 * L * hq * ctx
    weights = 2.0 * matmul_params(model) + 4.0 * (2 * L + 1) * d
    kv = kv_bytes_per_token(model)
    nbytes = weights + kv * ctx + kv * len(positions) \
        + 2.0 * d * len(positions)
    return flops, nbytes


# --------------------------------------------------------------------------
# from optimized HLO
# --------------------------------------------------------------------------
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*(\(?[^=]*?\)?)\s+([\w\-]+)\((.*)$")
_CALLED_RE = re.compile(r"(?:body|to_apply|calls)=%?([\w\.\-]+)")
_TRIP_RE = re.compile(r'known_trip_count[\\\"{:n\s]*?(\d+)')


def _dims(shape: str) -> List[int]:
    m = _SHAPE_RE.search(shape)
    if not m:
        return []
    return [int(x) for x in m.group(2).split(",") if x]


def _numel(shape: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape):
        if dt in DTYPE_BYTES:
            total += math.prod(int(x) for x in dims.split(",") if x)
    return total


def parse_hlo(text: str):
    """{computation: [(name, kind, shape, rest)]}, {computation: {op:
    shape}}, entry name, module name."""
    comps: Dict[str, list] = {}
    shapes: Dict[str, Dict[str, str]] = {}
    cur = entry = module = None
    for line in text.splitlines():
        s = line.strip()
        if s.startswith("HloModule "):
            module = s.split()[1].rstrip(",")
            continue
        if not s or s.startswith("//"):
            continue
        s = re.sub(r"/\*.*?\*/", "", s)
        if s.endswith("{") and " = " not in s:
            m = re.match(r"^(ENTRY\s+)?%?([\w\.\-]+)\s*\(", s)
            if m:
                cur = m.group(2)
                comps[cur], shapes[cur] = [], {}
                if m.group(1):
                    entry = cur
            continue
        om = _OP_RE.match(s)
        if om and cur is not None:
            name, shape, kind, rest = om.groups()
            comps[cur].append((name, kind, shape.strip(), rest))
            shapes[cur][name] = shape.strip()
    return comps, shapes, entry, module


def _operands(rest: str) -> List[str]:
    return re.findall(r"%([\w\.\-]+)", rest.split("), ")[0])


def _dot_flops(shape, rest, shp) -> float:
    m = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", rest)
    ops = _operands(rest)
    dims = _dims(shp.get(ops[0], "")) if ops else []
    if not m or not dims:
        raise ValueError(f"cannot read the dot {rest[:120]!r}")
    k = math.prod(dims[int(c)] for c in m.group(1).split(",") if c)
    return 2.0 * _numel(shape) * k


def _window(win: str, key: str, n: int, default: int) -> List[int]:
    m = re.search(key + r"=([\d_x]+)", win)
    if not m:
        return [default] * n
    return [int(x.split("_")[0]) for x in m.group(1).split("x")]


def _real_taps(n: int, out: int, size: int, stride: int, lo: int,
               lhs_dil: int, rhs_dil: int) -> int:
    """Window taps, summed over the output positions of one spatial
    dimension, that land on a real input element (not padding, not a hole
    of the input's dilation)."""
    x = (np.arange(out)[:, None] * stride + np.arange(size)[None, :]
         * rhs_dil - lo)
    real = (x >= 0) & (x <= (n - 1) * lhs_dil) & (x % lhs_dil == 0)
    return int(real.sum())


def _conv_flops(shape, rest, shp) -> float:
    """A matmul in convolution form: 2 x (output elements over its spatial
    positions) x the lhs feature size x, per spatial dimension, the window
    taps that meet real input.  The compiler folds batch dimensions into
    spatial ones, padded or dilated so each output meets one input: those
    count once, as the contraction does."""
    labels = re.search(r"dim_labels=(\w+)_(\w+)->(\w+)", rest)
    ops = _operands(rest)
    lhs = _dims(shp.get(ops[0], "")) if ops else []
    out = _dims(shape)
    if not labels or not lhs or not out:
        raise ValueError(f"cannot read the convolution {rest[:120]!r}")
    lab_in, lab_out = labels.group(1), labels.group(3)
    k = lhs[lab_in.index("f")]
    g = re.search(r"feature_group_count=(\d+)", rest)
    if g:
        k //= int(g.group(1))
    spatial = sorted(c for c in lab_in if c.isdigit())
    win = re.search(r"window=\{([^}]*)\}", rest)
    win = win.group(1) if win else ""
    nd = len(spatial)
    size = _window(win, "size", nd, 1)
    stride = _window(win, "stride", nd, 1)
    ldil = _window(win, "lhs_dilate", nd, 1)
    rdil = _window(win, "rhs_dilate", nd, 1)
    pad = re.search(r"pad=([\d_x]+)", win)
    lows = ([int(x.split("_")[0]) for x in pad.group(1).split("x")]
            if pad else [0] * nd)
    macs = float(k)
    positions = 1
    for i, c in enumerate(spatial):
        o = out[lab_out.index(c)]
        positions *= o
        macs *= _real_taps(lhs[lab_in.index(c)], o, size[i], stride[i],
                           lows[i], ldil[i], rdil[i])
    return 2.0 * macs * _numel(shape) / positions


class HloDots:
    """Matmul work of one compiled module."""

    def __init__(self, text: str):
        self.comps, self.shapes, self.entry, self.module = parse_hlo(text)
        self._memo: Dict[str, float] = {}

    def _inside(self, comp: str) -> float:
        """Matmul flops of one call of ``comp`` (fusion bodies, loops)."""
        if comp in self._memo:
            return self._memo[comp]
        self._memo[comp] = 0.0
        total = 0.0
        shp = self.shapes.get(comp, {})
        for name, kind, shape, rest in self.comps.get(comp, []):
            if kind == "dot":
                total += _dot_flops(shape, rest, shp)
            elif kind == "convolution":
                total += _conv_flops(shape, rest, shp)
            elif kind == "while":
                body = re.search(r"body=%?([\w\.\-]+)", rest)
                if body:
                    total += self._inside(body.group(1)) * self._trips(rest)
            elif kind == "conditional":
                br = re.search(r"branch_computations=\{([^}]*)\}", rest)
                if br:
                    total += max(self._inside(c.strip().lstrip("%"))
                                 for c in br.group(1).split(","))
            else:
                for c in _CALLED_RE.findall(rest):
                    total += self._inside(c)
        self._memo[comp] = total
        return total

    def _trips(self, rest: str) -> int:
        """A loop's trip count: ``known_trip_count`` where the compiler
        states it, else the bound its condition compares the counter with
        (``counter < N`` from 0, as ``lax.scan`` and ``fori_loop`` lower)."""
        t = _TRIP_RE.search(rest)
        if t:
            return int(t.group(1))
        cond = re.search(r"condition=%?([\w\.\-]+)", rest)
        ops = {n: (k, r) for n, k, _, r in self.comps.get(
            cond.group(1) if cond else "", [])}
        for name, (kind, r) in ops.items():
            if kind != "compare":
                continue
            d = re.search(r"direction=(\w+)", r)
            args = _operands(r)
            consts = [i for i, a in enumerate(args)
                      if ops.get(a, ("",))[0] == "constant"]
            if not d or len(consts) != 1:
                continue
            n = int(ops[args[consts[0]]][1].split(")")[0])
            if (d.group(1), consts[0]) in (("LT", 1), ("GT", 0)):
                return n
            if (d.group(1), consts[0]) in (("LE", 1), ("GE", 0)):
                return n + 1
        raise ValueError(f"cannot read the trip count of {rest[:160]!r}")

    def executed_flops(self) -> float:
        return self._inside(self.entry)

    def matmul_computations(self) -> Set[str]:
        """Computations whose call runs a matmul: what an op in the trace
        calls when it is a matmul kernel."""
        return {c for c in self.comps if self._inside(c) > 0}


def executed_dot_flops(text: str) -> float:
    return HloDots(text).executed_flops()

