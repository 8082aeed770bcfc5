"""Whole runs of a cell with the timed path replaced or broken, judged by
the harness's own check, on the chip at the cell's own size:

* ``--control``: the control takes the program's place: at the same
  positions of the same served requests, the gap of the token that the
  reference computed in float8 puts first (e4m3 with one scale per tensor
  wherever the program holds bfloat16, the step below the precision the
  configuration serves in; see ``reference.fp8``). The line gives the
  program's widest gap beside it, so one process reads both readings a
  limit is set from;
* ``--fault <name>``: one of ``faults.FAULTS`` planted in the program.

    python3 chipbench/control.py --workload <cell> --seconds <s> --seeds <n> [<n> ...] [--control] [--fault <name>]

Each seed is one run as ``run.py`` makes it (set-up, the window, the
check); its result line is printed with the seed in front. The
benchmark's own runs never run this. One process, all seeds."""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--fault", default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from chipbench.run import open_cell
    cell, devices = open_cell(args.workload)
    from chipbench import faults, harness
    undo = faults.plant(args.fault) if args.fault else (lambda: None)
    try:
        for seed in args.seeds:
            res = harness.run(cell, seed, args.seconds, False,
                              time.monotonic(), devices,
                              ROOT / "chipbench" / "out", args.control)
            print(json.dumps({"seed": seed, "fault": args.fault, **res}),
                  flush=True)
    finally:
        undo()
    return 0


if __name__ == "__main__":
    sys.exit(main())
