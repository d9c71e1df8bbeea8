"""In-process pipeline-parallel units: the mesh algebra of the 'pipe'
axis, the stage-layout/microbatch validation, the bubble + P2P cost
composition, and the joint PP x TMP planner goldens on the two fixture
HWConfigs (the acceptance shape of the subsystem — execution equivalence
lives in tests/_scripts/pipeline_equivalence.py under the multidevice
tier)."""
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P  # noqa: F401

from repro.configs.base import TrainHParams
from repro.configs.gpt_oases import PAPER_TABLE4, paper_shape
from repro.configs.registry import get_config
from repro.core import pipeline as pl
from repro.core.axes import batch_pspec, mesh_info
from repro.core.planner import (COMMODITY_25GBE, NVLINK_BOX, p2p_hop_seconds,
                                pipeline_time, plan_joint, stage_hw)
from repro.models import params as prm


def _info(*shape_axes):
    names, sizes = zip(*shape_axes)
    return mesh_info(AbstractMesh(sizes, names))


# --------------------------------------------------------------------------
# mesh algebra
# --------------------------------------------------------------------------
def test_mesh_info_detects_pipe_axis():
    info = _info(("pipe", 2), ("data", 2), ("model", 2))
    assert info.pipe_axes == ("pipe",)
    assert info.pp == 2 and info.dp == 2 and info.tp == 2
    assert info.model_axes == ("model",)


def test_pipe_axis_never_carries_the_batch():
    info = _info(("pipe", 4), ("data", 2), ("model", 1))
    assert batch_pspec(info, 8) == P(("data",))
    assert pl.pipeline_batch_axes(info) == ("data", "pipe")


def test_plain_mesh_has_pp_one():
    assert _info(("data", 2), ("model", 4)).pp == 1


# --------------------------------------------------------------------------
# stage layout + microbatch resolution
# --------------------------------------------------------------------------
def test_stage_layout_validation():
    cfg = get_config("internlm2-1.8b").reduced().replace(num_layers=4)
    assert pl.validate_stage_layout(cfg, 4, 0, 2, 2) == 1
    assert pl.validate_stage_layout(cfg, 4, 0, 4, 1) == 1
    with pytest.raises(ValueError, match="equal pipeline stages"):
        pl.validate_stage_layout(cfg, 4, 0, 2, 3)
    with pytest.raises(ValueError, match="tail"):
        pl.validate_stage_layout(cfg, 4, 1, 2, 1)
    enc = get_config("whisper-small").reduced()
    with pytest.raises(ValueError, match="encoder-decoder"):
        pl.validate_stage_layout(enc, 4, 0, 2, 1)


def test_pipeline_specs_flatten_to_canonical_layer_order():
    """The [v, pp, n/S] stacking must be a pure reshape of [n] — the
    property both the oracle-equivalence tests and the elastic checkpoint
    path rely on."""
    cfg = get_config("internlm2-1.8b").reduced().replace(num_layers=4)
    flat = prm.model_specs(cfg, _info(("data", 2), ("model", 2)))
    pipe = prm.model_specs(cfg, _info(("pipe", 2), ("data", 1), ("model", 2)),
                           virtual_stages=2)
    for a, b in zip(prm.tree_map_specs(lambda s: s, flat["blocks"]),
                    prm.tree_map_specs(lambda s: s, pipe["blocks"])):
        for (ka, sa), (kb, sb) in zip(sorted(a.items()), sorted(b.items())):
            assert ka == kb
            assert sb.shape[:3] == (2, 2, 1)
            assert sb.shape[3:] == sa.shape[1:]
            assert tuple(sb.pspec)[:3] == (None, "pipe", None)
    assert pipe["tail"] == []
    # embed/head stay replicated over pipe
    assert "pipe" not in tuple(pipe["embed"].pspec)


def test_pipeline_rejects_planner_degrees():
    cfg = get_config("internlm2-1.8b").reduced()
    with pytest.raises(ValueError, match="planner strategies"):
        prm.model_specs(cfg, _info(("pipe", 2), ("data", 1), ("model", 2)),
                        degrees=[2, 2])


def test_resolve_microbatch():
    assert pl.resolve_microbatch(8, 2) == 4       # 2*pp capped by divisors
    assert pl.resolve_microbatch(8, 4) == 8
    assert pl.resolve_microbatch(6, 2) == 3       # largest divisor <= 4
    assert pl.resolve_microbatch(8, 2, requested=2) == 2
    with pytest.raises(ValueError, match="divisor"):
        pl.resolve_microbatch(8, 2, requested=3)


def test_bubble_fraction():
    assert pl.bubble_fraction(1, 8) == 0.0
    assert pl.bubble_fraction(4, 4) == pytest.approx(3 / 7)
    # interleaving shrinks the bubble
    assert pl.bubble_fraction(4, 4, 2) < pl.bubble_fraction(4, 4, 1)


# --------------------------------------------------------------------------
# cost composition
# --------------------------------------------------------------------------
def test_pipeline_time_degenerates_at_pp1():
    assert pipeline_time(1.0, 1, 8) == (1.0, 0.0, 0.0)


def test_pipeline_time_bubble_and_p2p():
    total, bfrac, p2p = pipeline_time(1.0, 2, 8, 1, 0.0)
    # busy 0.5 + one microbatch-slot bubble 1/(2*8)
    assert total == pytest.approx(0.5 + 1.0 / 16)
    assert bfrac == pytest.approx((1.0 / 16) / total)
    assert p2p == 0.0
    # more microbatches or interleaving shrink the bubble
    assert pipeline_time(1.0, 2, 16)[0] < total
    assert pipeline_time(1.0, 2, 8, 2)[0] < total
    # P2P hops land on the critical path
    t_hop = 0.01
    assert pipeline_time(1.0, 2, 8, 1, t_hop)[2] >= 2 * t_hop


def test_stage_hw_and_hop_bandwidth():
    hw = stage_hw(COMMODITY_25GBE, 2)
    assert hw.n_chips == 8 and hw.node_size == 8
    cfg, _t, _d, gb = PAPER_TABLE4["gpt-h8192"]
    shape = paper_shape(gb)
    # stage == node: the hop crosses the NIC; fewer microbatches = fatter hop
    slow = p2p_hop_seconds(cfg, shape, COMMODITY_25GBE, 2, 4, 8)
    fast = p2p_hop_seconds(cfg, shape, NVLINK_BOX, 2, 4, 8)
    assert slow > fast
    assert p2p_hop_seconds(cfg, shape, COMMODITY_25GBE, 2, 8, 8) < slow


# --------------------------------------------------------------------------
# joint PP x TMP planner goldens (PR acceptance)
# --------------------------------------------------------------------------
def _joint(schedule, hw, **kw):
    cfg, _tmp, _dp, gb = PAPER_TABLE4["gpt-h8192"]
    return plan_joint(cfg, paper_shape(gb), TrainHParams(schedule=schedule),
                      hw, **kw)


@pytest.mark.parametrize("schedule", ["oases", "fused", "megatron"])
def test_joint_plan_spanning_regime_golden(schedule):
    """When the weights must spread over all 16 chips (the spanning
    regime, options=(16,)), the joint search places pipeline stages
    ACROSS the two commodity boxes and keeps TMP rings within a box —
    and its modeled time beats the best TMP-only plan (which must ring
    through the NIC).  On the uniform NVLink box PP buys nothing."""
    r = _joint(schedule, COMMODITY_25GBE, options=(16,))
    assert r.pp == 2, r.summary()
    assert all(d == 8 for d in r.degrees), r.summary()
    assert r.predicted_s <= r.tmp_only_s, r.summary()
    assert r.fits and r.status == "0", r.summary()
    assert 0.0 < r.bubble_fraction < 0.25, r.summary()

    n = _joint(schedule, NVLINK_BOX, options=(16,))
    assert n.pp == 1, n.summary()
    assert n.predicted_s == pytest.approx(n.tmp_only_s)


@pytest.mark.parametrize("fixture", [COMMODITY_25GBE, NVLINK_BOX])
def test_joint_plan_free_space_stays_tmp_only(fixture):
    """With memory to spare PP is pure overhead (bubble + hops): the
    joint search must agree with the TMP-only planner."""
    r = _joint("oases", fixture)
    assert r.pp == 1, r.summary()
    assert r.predicted_s == pytest.approx(r.tmp_only_s)


def test_joint_pp_candidates_are_executable():
    """pp options must divide the scan-GROUP count (num_layers/|pattern|),
    not num_layers — what validate_stage_layout enforces at training
    time."""
    from repro.core.planner.ilp import _default_pp_options
    cfg = get_config("gemma2-9b")            # 42 layers, 2-kind pattern
    groups = cfg.num_layers // len(cfg.layer_pattern)
    for v in (1, 2):
        for p in _default_pp_options(cfg, COMMODITY_25GBE, v):
            if p > 1:
                assert groups % (p * v) == 0, (p, v)
                pl.validate_stage_layout(cfg, groups, 0, p, v)


def test_joint_microbatch_candidates_always_divide_the_batch():
    """The planner must never recommend a microbatch count the runtime
    (pl.resolve_microbatch) would reject."""
    from repro.configs.base import ShapeConfig
    from repro.core.planner.ilp import _default_microbatch_options
    for gb in (8, 12, 6, 7):
        for pp in (2, 4, 8):
            for m in _default_microbatch_options(pp, 1,
                                                 ShapeConfig("t", 64, gb,
                                                             "train")):
                assert m >= 1 and gb % m == 0, (gb, pp, m)


def test_joint_plan_interleaving_shrinks_predicted_time():
    r1 = _joint("oases", COMMODITY_25GBE, options=(16,), virtual_stages=1)
    r2 = _joint("oases", COMMODITY_25GBE, options=(16,), virtual_stages=2)
    assert r2.pp == 2 and r2.bubble_fraction < r1.bubble_fraction


def test_joint_plan_survives_a_one_chip_host():
    """The --calibrate flow runs plan_joint with whatever
    HWConfig.from_measurements saw — on a 1-device host every option
    clamps to degree 1 and the search must still return a plan instead
    of raising."""
    from repro.configs.base import ShapeConfig
    from repro.core.planner.costmodel import HWConfig
    cfg = get_config("internlm2-1.8b")
    r = plan_joint(cfg, ShapeConfig("t", 4096, 256, "train"),
                   TrainHParams(), HWConfig(n_chips=1, node_size=1))
    assert r.pp == 1 and all(d == 1 for d in r.degrees)


def test_pipeline_mem_scales():
    """Weights shrink 1/stages; live activations keep their in-flight
    factor (a 1F1B stage holds min(stages, n_micro) microbatches)."""
    from repro.core.planner.costmodel import pipeline_mem_scales
    assert pipeline_mem_scales(1, 0) == (1.0, 1.0)
    assert pipeline_mem_scales(4, 8) == (0.25, 1.0)     # full in-flight
    assert pipeline_mem_scales(4, 2) == (0.25, 0.5)     # m < stages
    assert pipeline_mem_scales(2, 0) == (0.5, 1.0)      # auto m >= stages


def test_joint_plan_n_micro_divides_the_per_shard_batch():
    """The winning plan must be executable: n_micro must divide the
    per-dp-shard batch under the plan's own degrees (what
    pipeline.resolve_microbatch enforces at launch)."""
    r = _joint("oases", COMMODITY_25GBE, options=(8,), pp_options=[2])
    deg = max(d if isinstance(d, int) else d[0] * d[1] for d in r.degrees)
    dp = (COMMODITY_25GBE.n_chips // r.pp) // deg
    local = PAPER_TABLE4["gpt-h8192"][3] // max(dp, 1)
    assert local % r.n_micro == 0, r.summary()
    pl.resolve_microbatch(local, r.pp, r.virtual_stages, r.n_micro)


# --------------------------------------------------------------------------
# elastic checkpoint restacking guard
# --------------------------------------------------------------------------
def test_restore_reshapes_stage_stacking_but_rejects_transposes(tmp_path):
    import numpy as np

    from repro.checkpoint import store
    n, d1, d2 = 4, 6, 10
    tree = {"w": np.arange(n * d1 * d2, dtype=np.float32
                           ).reshape(n, d1, d2)}
    store.save(str(tmp_path), 1, tree)
    # PP restacking [n, ...] -> [v, pp, n/S, ...]: pure reshape, allowed
    like = {"w": np.zeros((2, 2, 1, d1, d2), np.float32)}
    restored, _meta = store.restore(str(tmp_path), 1, like)
    assert np.array_equal(np.asarray(restored["w"]).reshape(n, d1, d2),
                          tree["w"])
    # PP -> PP with a different (pp, v): also a pure restacking
    store.save(str(tmp_path), 2, {"w": tree["w"].reshape(1, 2, 2, d1, d2)})
    restored, _meta = store.restore(
        str(tmp_path), 2, {"w": np.zeros((2, 2, 1, d1, d2), np.float32)})
    assert np.array_equal(np.asarray(restored["w"]).reshape(n, d1, d2),
                          tree["w"])
    # transposed per-layer dims: same element count, NOT a restacking —
    # must fail loudly instead of restoring scrambled weights
    with pytest.raises(ValueError, match="restacking"):
        store.restore(str(tmp_path), 1, {"w": np.zeros((n, d2, d1),
                                                       np.float32)})
