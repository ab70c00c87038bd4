"""Find the knee of a serving cell: the highest fixed rate at which the
queue does not grow over the window.

    python3 bench/knee.py --workload appc.serve --seed 5 --seconds 10 \
        --rates 250,500,1000,2000

One process, one set-up: the cell's driver serves each rate in turn for
``--seconds``. For each rate it prints the 50th and 99th percentile
latency from when a request was due, the rate completed, and the growth
of the backlog: the mean latency of the last fifth of the requests over
that of the first fifth (about 1 when the queue holds steady). The
benchmark's own runs do not use this; it fixes the rate written into a
traffic file.
"""
import argparse
import gc
import json
import os
import sys
import time

T_START = time.perf_counter()
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, harness.SRC)
    harness.setup_jax()
    import numpy as np

    spec = harness.load_spec(pending=True)
    cell = harness.Cell(spec, args.workload)
    devs = harness.devices_for(cell.chips)
    drv = cell.driver().Driver(cell.config, cell.traffic, args.seed, devs,
                               log=sys.stderr)
    drv.setup()
    gc.collect()
    gc.freeze()
    for rate in [float(r) for r in args.rates.split(",")]:
        drv.traffic = dict(cell.traffic, rate_per_s=rate)
        t0 = time.perf_counter()
        drv.window(args.seconds, None)
        lat = drv.counters["latencies_s"]
        fifth = max(len(lat) // 5, 1)
        done = sum(a is not None for a in drv.answers)
        print(json.dumps({
            "rate_per_s": rate, "requests": len(lat),
            "completed_per_s": done / (time.perf_counter() - t0),
            "p50_ms": float(np.percentile(lat, 50) * 1e3),
            "p99_ms": float(np.percentile(lat, 99) * 1e3),
            "backlog_growth": float(lat[-fifth:].mean()
                                    / lat[:fifth].mean())}), flush=True)
    drv.release()
    return 0


if __name__ == "__main__":
    sys.exit(main())
