"""Ring attention across 8 virtual devices
(``tests/_scripts/ring_equivalence.py``).

A file of its own so that, with tests spread over workers file by file,
this long subprocess runs beside the other multi-device tests instead of
after them."""
import pytest

from conftest import run_script


@pytest.mark.multidevice
@pytest.mark.slow
def test_ring_attention_equivalence():
    """Ring attention (PR acceptance, DESIGN.md §12): the 8-way KV-ring
    kernel matches the 1-device oracle forward AND backward (custom-VJP
    reverse ring) across fp32/bf16 x causal/sliding-window/GQA/softcap
    and uneven sequence tiles; the stacked and grouped (mixed per-layer
    seqs) model paths are loss/grad-identical to the unsharded model;
    unsatisfiable shard factors raise instead of silently degrading."""
    lines = run_script("ring_equivalence.py", timeout=1800)
    assert len(lines) >= 18
