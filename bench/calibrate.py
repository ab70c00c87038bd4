"""Readings for the limits of a cell's check: the program's numbers and
its control's, seed by seed, in one process.

    python3 bench/calibrate.py --workload appc.fit --seeds 1,2,3 \
        --seconds 2 [--control]

For each seed: the cell's set-up, a short window at the cell's own load,
the program's state released, then the numbers the check compares; with
``--control``, also the numbers of the control (the reference put in
the program's place at the precision below the configuration's); with
``--fault <kind>``, the numbers of the program with that fault planted
(``faults.py``). One JSON line per seed. The benchmark's own runs do not run this; its
readings set the limits in the configuration files (see PERF.md).
"""
import argparse
import contextlib
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import faults  # noqa: E402
import harness  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--fault", choices=faults.KINDS,
                    help="plant this fault under the timed path")
    args = ap.parse_args(argv)
    sys.path.insert(0, harness.SRC)
    harness.setup_jax()
    spec = harness.load_spec(pending=True)
    cell = harness.Cell(spec, args.workload)
    devs = harness.devices_for(cell.chips)
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        drv = cell.driver().Driver(cell.config, cell.traffic, seed, devs,
                                   log=sys.stderr)
        with (faults.planted(cell.traffic["driver"], args.fault)
              if args.fault else contextlib.nullcontext()):
            drv.setup()
            gc.collect()
            gc.freeze()
            drv.window(args.seconds, None)
            drv.release()
        rep = drv.check()
        line = {"seed": seed, "fault": args.fault,
                "correct": rep["correct"], "failed": rep["failed"],
                "program": {k: c["value"] for k, c in rep["checks"].items()}}
        if args.control:
            line["control"] = drv.control()
        line["seconds"] = time.perf_counter() - t0
        print(json.dumps(line), flush=True)
        del drv
    return 0


if __name__ == "__main__":
    sys.exit(main())
