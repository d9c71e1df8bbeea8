"""Production meshes.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state).  The dry-run entrypoint sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import; smoke tests and benchmarks see the real (1-device) topology.
"""
from __future__ import annotations

import jax

from repro.core import compat


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips when multi_pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return compat.make_mesh(shape, axes)


def make_factored_mesh(*, multi_pod: bool = False):
    """Planner-mode mesh: the 16-way model axis factored into binary
    sub-axes so per-layer TMP degrees in {1,2,4,8,16} — 1D ints or 2D
    ``(dx, dy)`` tuples (x = leading sub-axes, y = the next) — are
    expressible."""
    shape = (2, 16, 2, 2, 2, 2) if multi_pod else (16, 2, 2, 2, 2)
    axes = (("pod", "data", "t1", "t2", "t3", "t4") if multi_pod
            else ("data", "t1", "t2", "t3", "t4"))
    return compat.make_mesh(shape, axes)


def make_2d_mesh(data: int, dx: int, dy: int):
    """Uniform 2D hybrid-partition mesh ``('data','model_x','model_y')``:
    weight width shards over the dx-way intra-node axis, the contraction
    dim over the dy-way inter-node axis (commodity-server placement)."""
    return compat.make_mesh((data, dx, dy), ("data", "model_x", "model_y"))


def make_pipeline_mesh(pp: int, data: int, model: int):
    """``('pipe','data','model')`` — pipeline stages outermost (on
    commodity clusters the stage boundaries ride the thin inter-node
    links), TMP innermost on the fast lanes."""
    return compat.make_mesh((pp, data, model), ("pipe", "data", "model"))


_MESH_HELP = ("expected 'DxM' (data x model, e.g. '32x8') or 'DxMxxMy' "
              "(2D hybrid, e.g. '16x8x2'); a pipeline axis is prepended "
              "with pp= / --pp, giving PxDxM")


def parse_mesh_spec(spec: str, *, pp: int = 0):
    """Pure parser (no device construction): ``spec`` -> (shape, axes).

    Friendly-errors every malformed form instead of crashing deep in mesh
    construction: non-integer components, wrong component counts, and bad
    pipeline degrees all name the offending token and the accepted grammar.
    """
    parts = [t.strip() for t in str(spec).split("x")]
    shape = []
    for tok in parts:
        if not tok.isdigit() or int(tok) <= 0:
            raise ValueError(
                f"bad mesh spec {spec!r}: component {tok!r} is not a "
                f"positive integer — {_MESH_HELP}")
        shape.append(int(tok))
    if len(shape) == 2:
        axes = ("data", "model")
    elif len(shape) == 3:
        axes = ("data", "model_x", "model_y")
    else:
        raise ValueError(
            f"bad mesh spec {spec!r}: {len(shape)} component(s) — "
            f"{_MESH_HELP}")
    if pp:
        if not isinstance(pp, int) or pp < 1:
            raise ValueError(
                f"bad pipeline degree pp={pp!r}: must be a positive int")
        if pp > 1:
            shape = [pp] + shape
            axes = ("pipe",) + axes
    return tuple(shape), axes


def parse_mesh_shape(spec: str, *, pp: int = 0):
    """'dxm' -> 1D ('data','model'); 'dxm1xm2' -> 2D mesh; ``pp > 1``
    prepends a 'pipe' axis (PxDxM)."""
    shape, axes = parse_mesh_spec(spec, pp=pp)
    return compat.make_mesh(shape, axes)


def parse_degrees(spec: str):
    """'8,4x2,16' -> [8, (4, 2), 16]: per-layer TMP degrees, 'AxB' = 2D.

    Validates every token up front (positive power-of-two components) so a
    typo'd plan fails with the grammar instead of a deep axis-algebra
    crash."""
    def _pow2(tok: str, n: int) -> int:
        if n <= 0 or n & (n - 1):
            raise ValueError(
                f"bad degree spec {spec!r}: component {tok!r} — TMP "
                f"degrees must be positive powers of two (paper §4.2)")
        return n

    def _int(tok: str, part: str) -> int:
        if not part.isdigit():
            raise ValueError(
                f"bad degree spec {spec!r}: component {tok!r} is not a "
                f"degree — expected comma-separated entries 'N' (1D) or "
                f"'AxB' (2D), e.g. '8,4x2,16'")
        return int(part)

    out = []
    for tok in (t.strip() for t in str(spec).split(",")):
        if "x" in tok:
            parts = tok.split("x")
            if len(parts) != 2:
                raise ValueError(
                    f"bad degree spec {spec!r}: 2D entry {tok!r} must be "
                    f"exactly 'AxB', e.g. '4x2'")
            out.append((_pow2(tok, _int(tok, parts[0])),
                        _pow2(tok, _int(tok, parts[1]))))
        elif tok:
            out.append(_pow2(tok, _int(tok, tok)))
        else:
            raise ValueError(
                f"bad degree spec {spec!r}: empty entry — expected "
                f"comma-separated 'N' or 'AxB' tokens, e.g. '8,4x2,16'")
    if not out:
        raise ValueError(f"bad degree spec {spec!r}: no entries")
    return out


def make_smoke_mesh(devices=None):
    """1x1 (or all-local-devices) mesh for CPU smoke tests."""
    n = len(devices or jax.devices())
    d = max(1, n // 4) if n >= 4 else 1
    return compat.make_mesh((d, n // d), ("data", "model"))


# --------------------------------------------------------------------------
# plan desugaring — THE place legacy launcher flags become a ParallelPlan
# --------------------------------------------------------------------------
def resolve_mesh_spec(spec: str = "auto", *, pp: int = 1,
                      multi_pod: bool = False, devices=None):
    """One mesh resolution shared by every launcher: named meshes
    (``auto`` / ``production`` / ``multipod`` / ``factored``) or an
    explicit ``DxM`` / ``DxMxxMy`` grid, with ``pp`` prepending the
    ``pipe`` stage axis."""
    pp = max(pp, 1)
    if spec in ("production", "multipod", "factored"):
        if pp > 1:
            raise SystemExit(
                f"--pp does not compose with --mesh {spec} yet — use an "
                f"explicit 'dxm' spec (e.g. --pp {pp} --mesh 8x16) or "
                f"--mesh auto")
        if spec == "factored":
            return make_factored_mesh(multi_pod=multi_pod)
        return make_production_mesh(multi_pod=multi_pod
                                    or spec == "multipod")
    if spec == "auto":
        if pp > 1:
            n = len(devices or jax.devices())
            if n % pp:
                raise SystemExit(f"--pp {pp} does not divide the "
                                 f"{n} available devices")
            return make_pipeline_mesh(pp, max(n // pp, 1), 1)
        return make_smoke_mesh(devices)
    return parse_mesh_shape(spec, pp=pp)


def mesh_signature(mesh):
    """(shape, axes) of a mesh — what a ParallelPlan records."""
    axes = tuple(mesh.axis_names)
    shape = dict(mesh.shape)
    return tuple(int(shape[a]) for a in axes), axes


def resolve_launch(cfg, hp, *, mesh: str = "auto", pp: int = 1,
                   plan_file: str = "", save_plan: str = "",
                   degrees=None, schedules=None, decode_micro: int = 0,
                   devices=None, log=print):
    """The single plan-desugaring path (train/serve/dryrun all call it):

    * ``--plan plan.json``: the file IS the source of truth — its knobs
      override the legacy flags (``hp`` keeps only the non-parallelism
      fields), its recorded mesh is rebuilt when present, and the legacy
      mesh flags resolve it otherwise;
    * legacy flags: the mesh resolves as before and the scattered knobs
      (schedule, tmp-layout, pp, virtual stages, microbatch, split,
      decode-micro, per-layer degrees) desugar into one ParallelPlan.

    ``--save-plan out.json`` writes the resolved plan either way.
    Returns ``(mesh, plan, hp)`` with ``hp`` already projected through
    the plan (``plan.apply``)."""
    from repro.core.plan import ParallelPlan
    if plan_file:
        plan = ParallelPlan.load(plan_file).validate_for(cfg)
        hp = plan.apply(hp)
        if plan.mesh_shape:
            m = compat.make_mesh(plan.mesh_shape, plan.mesh_axes)
        else:
            m = resolve_mesh_spec(mesh, pp=plan.pp, devices=devices)
        log(f"[plan] loaded {plan_file}: {plan.summary()}")
    else:
        m = resolve_mesh_spec(mesh, pp=pp, devices=devices)
        shape, axes = mesh_signature(m)
        plan = ParallelPlan.from_hparams(
            hp, cfg.num_layers, degrees=degrees, schedules=schedules,
            mesh_shape=shape, mesh_axes=axes, pp=max(pp, 1),
            decode_micro=decode_micro)
        hp = plan.apply(hp)
    if save_plan:
        plan.save(save_plan)
        log(f"[plan] wrote {save_plan}: {plan.summary()}")
    return m, plan, hp
