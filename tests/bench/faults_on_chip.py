"""Run one benchmark cell at its own size with a fault planted under the
timed path, on the chip, and print the run's result line: ``correct`` has
to come out false.

    python tests/bench/faults_on_chip.py --fault half_batch_dropped \
        --workload graph500-s21.reach_p2p8 --seed <n> --seconds 51

The faults are those of ``planted_faults.py``; the CPU tests plant the same
ones at a tiny size. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1]))

import planted_faults  # noqa: E402
from bench import run  # noqa: E402


class Patch:
    """The one method of pytest's ``monkeypatch`` that the faults use."""

    def setattr(self, obj, name, value):
        setattr(obj, name, value)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--fault", required=True, choices=sorted(planted_faults.FAULTS))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", required=True)
    ap.add_argument("--seconds", required=True)
    args = ap.parse_args(argv)
    planted_faults.after_setup(Patch(), planted_faults.FAULTS[args.fault])
    run.configure_compile_cache(run.ROOT)
    return run.main(["--workload", args.workload, "--seed", args.seed,
                     "--seconds", args.seconds, "--trace", "0"])


if __name__ == "__main__":
    sys.exit(main())
