"""The neighbourhood cell's check on the CPU at a tiny size: a clean run is
correct, and each fault planted under the timed path makes it incorrect."""
import pytest

import planted_faults as faults
import tiny_tree


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_tree.build(tmp_path_factory.mktemp("bench"), rate=50.0)


def test_clean_run_is_correct(root, capsys):
    rc, out = tiny_tree.run_cell(root, tiny_tree.NBR2, seed=2**33 + 1, capsys=capsys)
    assert rc == 0 and out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] == 50
    assert out["compared"]["wrong_answers"] == {"value": 0, "limit": 0}
    assert set(out["metrics"]) == {"setup_s", "p50_ms", "peak_hbm_gib"}
    assert list(out)[-1] == "compared"


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_fault_makes_run_incorrect(root, capsys, monkeypatch, fault):
    from bench import run

    monkeypatch.setattr(run, "GRACE_S", 0.5)
    faults.after_setup(monkeypatch, faults.FAULTS[fault])
    rc, out = tiny_tree.run_cell(root, tiny_tree.NBR2, seed=11, capsys=capsys)
    assert rc == 0 and out["correct"] is False
    compared = out["compared"]
    assert compared["wrong_answers"]["value"] + compared["missing_answers"]["value"] > 0
