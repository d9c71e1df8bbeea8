"""The compiled train step names its phases on a TMP mesh: the schedule's
all-reduces carry their ``tmp.*`` scope in both passes, and the update its
``optim.adamw`` scope (a subprocess on four virtual devices)."""
import pytest

from conftest import run_script


@pytest.mark.multidevice
@pytest.mark.slow
def test_tmp_collectives_scoped_in_both_passes():
    lines = run_script("phase_scopes.py")
    assert len(lines) == 4          # megatron and oases, scopes and optim
