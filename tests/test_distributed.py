"""Multi-device integration tests — the first-class ``multidevice`` tier.

Each test spawns a subprocess from tests/_scripts/ (all of which import
the shared ``runner`` harness, which sets
XLA_FLAGS=--xla_force_host_platform_device_count=8 before jax loads; the
in-process tests must keep the real 1-device topology).  Run the tier with
``pytest -m multidevice``; the tests are also marked ``slow`` so the
default fast loop can deselect them.
"""
import pytest

from conftest import run_script

multidevice = pytest.mark.multidevice


@multidevice
@pytest.mark.slow
def test_tmp_equivalence_and_schedules():
    lines = run_script("equivalence.py")
    assert len(lines) >= 8          # 7 archs + schedule agreement


@multidevice
@pytest.mark.slow
def test_2d_hybrid_equivalence():
    """2x2 model-mesh 2D forward+grad vs the single-device oracle, plus
    mixed 1D/2D planner degrees on the factored mesh (PR acceptance)."""
    lines = run_script("equivalence_2d.py", timeout=1800)
    assert len(lines) >= 26         # 7 archs x 3 schedules + plan cases


@multidevice
@pytest.mark.slow
def test_plan_equivalence():
    """Executable-ParallelPlan tier: heterogeneous per-layer
    (degree, schedule) strategies — mixed schedules at mesh-uniform
    degrees on a plain mesh, MoE interplay, and mixed (degree, schedule)
    plans on the factored mesh — are loss- AND grad-identical to the
    1-device oracle (PR acceptance)."""
    lines = run_script("plan_equivalence.py", timeout=1800)
    assert len(lines) >= 8


@multidevice
@pytest.mark.slow
def test_fine_remat_removes_recompute_collectives():
    run_script("remat_counts.py")


@multidevice
@pytest.mark.slow
def test_fault_tolerant_restart():
    run_script("ft_restart.py")


@multidevice
@pytest.mark.slow
def test_elastic_remesh_resume():
    run_script("elastic.py")


@multidevice
@pytest.mark.slow
def test_elastic_replan():
    """Online elasticity (PR acceptance): injected mid-run host loss
    triggers ILP replanning + in-memory relayout with loss continuity
    against an uninterrupted oracle; a link-bandwidth fault replans
    without chip loss; a corrupted checkpoint shard resumes from the
    previous intact checkpoint."""
    lines = run_script("elastic_replan.py", timeout=1800)
    assert len(lines) >= 4


@multidevice
@pytest.mark.slow
def test_telemetry_end_to_end():
    """Telemetry tier (PR acceptance): a short TMP training run with a
    JSONL sink yields a schema-valid trace with step-time histograms,
    async-checkpoint write latency, one ``train.step`` profiler span per
    step, and the enriched per-host heartbeat the straggler localizer
    consumes."""
    lines = run_script("telemetry_run.py")
    assert len(lines) >= 7


@multidevice
@pytest.mark.slow
def test_sequence_parallel_equivalence():
    lines = run_script("sp_equivalence.py")
    assert len(lines) >= 5


@multidevice
@pytest.mark.slow
def test_pipeline_equivalence():
    """Interleaved-1F1B PP x TMP vs the single-device oracle: pp in {2,4}
    x tmp in {1,2} x {megatron,oases,fused}, plus virtual stages, a second
    arch family and PP x 2D hybrid (PR acceptance)."""
    lines = run_script("pipeline_equivalence.py", timeout=1800)
    assert len(lines) >= 14


@multidevice
@pytest.mark.slow
def test_serving_equivalence():
    """Sharded greedy decode through the continuous-batching engine is
    token-identical to the single-device oracle: pp in {1,2} x tmp in
    {1,2} x {megatron,oases,fused}, plus the 2D hybrid decode layout,
    explicit micro-group counts, an indivisible slot count, gemma2,
    and the serving-at-scale grid — paged KV (incl. the pp decode
    stream), prefix reuse with COW, speculative decoding vs the
    undrafted oracle, and the combined paged+prefix+spec path
    (PR acceptance)."""
    lines = run_script("serving_equivalence.py", timeout=1800)
    assert len(lines) >= 30
