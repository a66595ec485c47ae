"""Find a served configuration's knee: the highest submit rate the
daemon holds without a growing backlog.

    python3 bench/knee.py --workload rfold4096.steady --rates 20,40,80 \
        [--seconds 10] [--seed 1] [--out knee.json]

One process on the chip runs the cell's served driver once per rate
(each with a fresh daemon, the same prefill, and no reference check)
and prints one JSON line per rate: the offered submit rate, the ops
completed per second of window, the median and 95th-percentile
latency over the window, the same 95th percentile over the ops due in
the window's first and last quarter, and the ops still unanswered at
the close. A backlog that grows shows as a last quarter far slower than
the first. The knee is recorded in the configuration's file
(``served.knee_submits_per_s``) and in ``PERF.md``; the cells then run
at fixed fractions of it.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))

from benchlib import harness, readers, registry  # noqa: E402
from benchlib.device import device_info, require_chips  # noqa: E402
from benchlib.stats import percentile  # noqa: E402


def summarize(run, rate: float) -> dict:
    start, close = run.extra["start"], run.extra["close"]
    quarter = (close - start) / 4.0
    lat = readers.latency_ms(run)

    def p95_due(lo, hi):
        xs = [x for r, x in zip(run.ops, lat) if lo <= r[2] < hi]
        return percentile(xs, 95) if xs else None

    return {"submits_per_s": rate,
            "ops_per_s": readers.ops_per_s(run),
            "op_p50_ms": readers.op_percentile(run, 50),
            "op_p95_ms": readers.op_percentile(run, 95),
            "p95_first_quarter_ms": p95_due(start, start + quarter),
            "p95_last_quarter_ms": p95_due(close - quarter, close + 1),
            "unanswered_at_close": sum(1 for r in run.ops
                                       if r[4] is None or r[4] > close),
            "ops": len(run.ops), "failed": run.failed}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated submit rates (submits/s)")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    bench = registry.load_benchmark()
    device = device_info()
    cell0 = harness.make_cell(bench, args.workload, args.seed, args.seconds,
                              False, T_START)
    require_chips(device, cell0.chips)
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    driver = registry.load_driver(cell0.mix["driver"])
    rows = []
    for rate in (float(r) for r in args.rates.split(",")):
        cell = harness.make_cell(bench, args.workload, args.seed,
                                 args.seconds, False, time.perf_counter(),
                                 rate=rate, check=False)
        cell.peaks = registry.peaks_for(device["kind"])
        row = summarize(driver.run(cell), rate)
        rows.append(row)
        print(json.dumps(row), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"workload": args.workload, "device": device,
                       "seconds": args.seconds, "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
