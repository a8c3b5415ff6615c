"""The correctness check's control at a tiny size: the reference with one of
the configuration's guarantees broken must fail the check (limit 0)."""
import json

import pytest

import tiny_tree
from bench import control


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_tree.build(tmp_path_factory.mktemp("bench"), rate=20.0)


@pytest.mark.parametrize("cell", [tiny_tree.NBR2, tiny_tree.REACH])
def test_control_fails_the_check_on_every_seed(root, capsys, cell):
    assert control.main(["--workload", cell, "--seeds", "21,22,23"], root=root) == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.strip().splitlines()]
    assert len(lines) == 3
    for r in lines:
        assert r["compared"] > 0 and r["wrong_answers"] > 0
