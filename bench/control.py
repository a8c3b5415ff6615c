"""The control of the correctness check: the reference with one of the
configuration's guarantees broken, put where the served answers go.

    python bench/control.py --workload <cell> --seeds 11,12,13

For each seed it generates the cell's deployment and traffic at full size,
takes the requests one run compares (open loop: every request of a
``run_seconds`` window at the cell's rate; closed loop: each client's first
``--per-client`` queries), answers them with the control and checks those
answers exactly as ``run.py`` checks the served ones. It prints one JSON
line per seed with ``wrong_answers``; the check's limit is 0, so every
reading above 0 is the control failing as it must.

The broken guarantee is the configuration's ``control``; its ``break``
names a module under ``controls/`` that returns the broken snapshot. The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path
from typing import Dict

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import traffic  # noqa: E402
from bench.registry import ROOT, Cell, Registry  # noqa: E402


def readings(cell: Cell, reg: Registry, seed: int, seconds: float,
             per_client: int) -> Dict[str, int]:
    dep = reg.generator(cell.config["generator"]).generate(cell.config, seed)
    wl = traffic.make(reg, cell.traffic, dep, seed, seconds)
    q = cell.traffic["query"]
    kind = reg.query(q["kind"])
    take = per_client if len(wl.streams) > 1 else None
    reqs = [p for s in wl.streams for p in s[:take]]
    truth = kind.answers(dep, q, reqs)
    spec = cell.config["control"]
    control = kind.answers(reg.control(spec["break"]).broken(dep, spec), q, reqs)
    wrong = sum(not np.array_equal(a, b) for a, b in zip(truth, control))
    return {"seed": seed, "compared": len(reqs), "wrong_answers": wrong}


def main(argv=None, *, root: Path = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--per-client", type=int, default=2)
    args = ap.parse_args(argv)
    reg = Registry(root)
    cell = reg.cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        r = readings(cell, reg, seed, float(reg.spec["run_seconds"]), args.per_client)
        print(json.dumps({"workload": cell.name, "control": cell.config["control"], **r}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
