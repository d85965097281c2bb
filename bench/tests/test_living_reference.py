"""The living channel at its configuration's own budget (1,500 cycles,
300 of warm-up): a whole ``living_drift`` run on the CPU is correct
against the plain reference.  The 300 cycles of ``test_faults.py`` stop
short of the first receiver VC handed on while its old sender still
streams (cycle 620 of a ``fixed:-1`` lane of this seed)."""
import drive


def test_living_drift_is_correct_at_its_own_budget(monkeypatch):
    monkeypatch.setattr(drive, "SMALL", {})
    res = drive.run("living_drift", "none", 1)
    assert res["correct"], res["checks"]
    assert res["checks"]["int_mismatches"]["value"] == 0
    assert res["checks"]["lanes_checked"]["value"] == 4
