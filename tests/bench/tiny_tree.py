"""A copy of the benchmark tree with tiny cells, for CPU tests.

The tiny configurations and traffic mixes are the real files with only
their sizes cut, added as new files under new names — the way a later
change adds a cell — so the tests drive the same generators, traffic
generator, serving path and reference as a chip run.
"""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
for _p in (str(REPO / "src"), str(REPO)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

NBR2 = "ur-tiny.nbr2"
REACH = "g5-tiny.reach"
TINY = {
    "gap-urand-s22": ("ur-tiny", {"scale": 10}),
    "graph500-s21": ("g5-tiny", {"scale": 10}),
}


def _load(path: Path):
    with open(path) as f:
        return json.load(f)


def _dump(obj, path: Path):
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def build(root: Path, *, rate: float = 200.0, clients: int = 8) -> Path:
    """Copy ``bench/`` and ``BENCHMARK.json`` under ``root`` and add the
    two tiny cells ``NBR2`` and ``REACH``."""
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = _load(REPO / "BENCHMARK.json")
    bench = root / "bench"
    for real, (tiny, sizes) in TINY.items():
        cfg = _load(bench / "configs" / f"{real}.json")
        cfg.update(name=tiny, **sizes)
        _dump(cfg, bench / "configs" / f"{tiny}.json")
    mix = _load(bench / "traffic" / "nbr2_open.json")
    mix["name"] = "nbr2-tiny"
    mix["arrivals"]["rate_per_s"] = rate
    _dump(mix, bench / "traffic" / "nbr2-tiny.json")
    mix = _load(bench / "traffic" / "reach_p2p8.json")
    mix["name"] = "reach-tiny"
    mix["arrivals"]["clients"] = clients
    _dump(mix, bench / "traffic" / "reach-tiny.json")
    spec["workloads"] += [
        {"name": NBR2, "config": "ur-tiny", "traffic": "nbr2-tiny", "chips": 1,
         "why": "CPU test cell"},
        {"name": REACH, "config": "g5-tiny", "traffic": "reach-tiny", "chips": 1,
         "why": "CPU test cell"},
    ]
    twins = {"gap-urand-s22.nbr2_open": NBR2, "graph500-s21.reach_p2p8": REACH}
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] += [twins[w] for w in m["workloads"] if w in twins]
    _dump(spec, root / "BENCHMARK.json")
    return root


def run_cell(root: Path, cell: str, *, seed: int = 7, seconds: float = 1.0,
             trace: int = 0, capsys=None):
    """``bench/run.py``'s main on the CPU (the look for a chip skipped);
    returns (exit code, parsed result line or None)."""
    import jax

    from bench import run

    saved = run.require_accelerator
    run.require_accelerator = lambda chips: jax.devices()[:chips]
    try:
        rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                       str(seconds), "--trace", str(trace)], root=root)
    finally:
        run.require_accelerator = saved
    if capsys is None:
        return rc, None
    lines = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(lines[-1]) if lines else None
