"""Each plain reference agrees with its engine at a tiny size on the CPU,
and each control (the reference in bfloat16 in the engine's place) fails
the check."""
import ml_dtypes
import pytest

from chipbench import control, harness

CELLS = ["fig28-transient-w", "fig30-execute-r60", "fig30-execute-r90",
         "fig28-mva-w"]


@pytest.mark.parametrize("cell", CELLS)
def test_engine_matches_its_reference(tiny_root, cell):
    c = harness.find_cell(tiny_root, cell)
    engine = c.engine.Engine(c.config, c.traffic, 2_400_000_011)
    kept = [engine.keep(harness._answer(engine, i)) for i in range(2)]
    gaps = engine.check(kept)
    assert not gaps.failed, gaps.rows()
    for row in gaps.rows():
        assert row["value"] <= row["limit"], row


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_the_check(tiny_root, cell):
    (_, sound, ctl), = control.readings(tiny_root, cell, [3_000_000_019],
                                        require_tpu=False)
    assert all(r["value"] <= r["limit"] for r in sound), sound
    over = [r["name"] for r in ctl if r["value"] > r["limit"]]
    assert over, ctl
    assert control.CONTROL is ml_dtypes.bfloat16
