"""Unit tests for the two-axis (model_x x model_y) mesh algebra — the
degree type (int | (dx, dy)) and the x/y split MeshInfo hands the 2D
TmpCtx.  AbstractMesh keeps these in-process (no devices needed)."""
import pytest
from jax.sharding import AbstractMesh

from repro.core.axes import T_AXES, deg_total, deg_xy, mesh_info


def _info(*shape_axes):
    names, sizes = zip(*shape_axes)
    return mesh_info(AbstractMesh(sizes, names))


def test_degree_helpers():
    assert deg_total(None) is None
    assert deg_total(8) == 8
    assert deg_total((4, 2)) == 8
    assert deg_xy(8) == (8, 1)
    assert deg_xy((2, 4)) == (2, 4)


def test_mesh_info_detects_2d_axes():
    info = _info(("data", 2), ("model_x", 4), ("model_y", 2))
    assert info.model_axes == ("model_x", "model_y")
    assert info.twod and not info.factored
    assert info.tp == 8 and info.dp == 2
    assert info.xy_axes() == (("model_x",), ("model_y",))
    assert info.tp_axes((4, 2)) == ("model_x", "model_y")


def test_uniform_1d_mesh_has_empty_y():
    info = _info(("data", 2), ("model", 4))
    assert not info.twod and not info.factored
    assert info.xy_axes() == (("model",), ())


def test_2d_degree_must_match_mesh_layout():
    info = _info(("data", 1), ("model_x", 4), ("model_y", 2))
    with pytest.raises(ValueError):
        info.xy_axes((2, 4))          # transposed vs the mesh
    assert info.xy_axes((4, 2)) == (("model_x",), ("model_y",))


def test_factored_mesh_prefix_split():
    info = _info(("data", 16), *((t, 2) for t in T_AXES))
    assert info.factored and not info.twod
    assert info.xy_axes(4) == (("t1", "t2"), ())
    assert info.xy_axes((4, 2)) == (("t1", "t2"), ("t3",))
    assert info.xy_axes((1, 4)) == ((), ("t1", "t2"))
    assert info.xy_axes((2, 8)) == (("t1",), ("t2", "t3", "t4"))
    assert info.tp_axes((2, 2)) == ("t1", "t2")
    # extra-dp axes follow the combined group
    assert info.extra_dp_axes((2, 2)) == ("t3", "t4")
    with pytest.raises(ValueError):
        info.xy_axes((4, 8))          # 32 > 16-way model group
    with pytest.raises(ValueError):
        info.xy_axes((3, 2))          # non-power-of-two


def test_uniform_mesh_rejects_per_layer_2d():
    info = _info(("data", 2), ("model", 8))
    with pytest.raises(ValueError):
        info.xy_axes((2, 2))          # needs factored or model_x/model_y
    assert info.xy_axes((8, 1)) == (("model",), ())
