"""Rehearsal of ``chip_smoke.py`` on the CPU: the same phases at a tiny
size, with the Pallas kernel interpreted, and the script's refusals."""
import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py"
    )
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod  # dataclasses resolve annotations there
    spec.loader.exec_module(mod)
    return mod


def test_phases_pass_at_tiny_size(smoke, monkeypatch):
    dep = smoke.phase_load(512, 4096, insert_room=64)
    te = dep.eng.traversal
    assert te.interpret  # built on the CPU: the kernel is interpreted
    # steer the auto policy as a TPU would see it; the engine keeps the
    # interpret mode it resolved at construction
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    smoke.run_one_chip(dep, jax.devices()[:1], batch=64)
    assert 12 <= dep.seed_selected <= smoke.LANES
    assert te.stats["backend_pallas_frontier"] == 3  # one per round
    assert te.stats["backend_xla_coo"] == 3 * (smoke.N_REACH + 1)
    assert te.stats["backend_reference"] == dep.reference_calls
    assert dep.answers == 3 * (smoke.N_REACH + 2)
    assert dep.eng.events["traversal_faults"] == 0
    assert dep.eng.events["compactions_merge"] == 1


def test_served_mismatch_fails(smoke, monkeypatch):
    dep = smoke.phase_load(256, 2048, insert_room=64)
    monkeypatch.setattr(smoke, "_same", lambda a, b: False)
    with pytest.raises(smoke.SmokeFailure, match="differs from reference"):
        smoke.phase_single(dep)


def test_main_refuses_a_cpu(smoke, capsys):
    assert smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_script_alone_fails(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=300,
    )
    assert p.returncode != 0
    assert '"ok"' not in p.stdout
