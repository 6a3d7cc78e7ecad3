"""Readings that a cell's correctness limits are set from, on the chip, in
one process: for each seed, one run of the cell as ``run.py`` makes it,
and on the first ``--control-seeds`` of them also the control — the
reference at 8-bit float activations put in the program's place on the
same requests, judged by the same decision as the program.

    python3 bench/calibrate.py --workload <cell> --seconds <s> --seeds 1,2,3

One JSON line per seed on standard output.  The benchmark's own runs
never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
import run as R  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control-seeds", type=int, default=4,
                   help="how many of the seeds also read the control")
    args = p.parse_args(argv)
    root = R.BENCH.parent
    spec = R.load_cell(root, args.workload)
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        controls = ("fp8",) if i < args.control_seeds else ()
        out = R.run_cell(root, spec, seed, args.seconds, False,
                         controls=controls)
        line = {"seed": seed, "correct": out["correct"],
                "program": out["readings"],
                "controls": out.get("controls", {}),
                "metrics": out["metrics"], "device": out["device"]}
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
