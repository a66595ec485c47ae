"""Show that a replicated cell's comparison fails when its standby does.

    python3 bench/control_standby.py --workload rfold4096_ha.replicated \
        --plant pause_standby --ack-mode async --seeds 11 \
        [--seconds 30] [--out control.json]

Runs the cell at its own size on the chip, as ``run.py`` does, once per
seed, with a fault planted in the standby (``served_plus.PLANTS``):

* ``pause_standby``: the standby's process is stopped (SIGSTOP) from
  the window's start until the primary has been killed;
* ``standby_lost_wal``: the standby's journal writes nothing, yet it
  acknowledges every record.

``--ack-mode`` runs the primary in ``async`` or ``sync`` ack mode in
place of the configuration's. Each run prints one JSON line with the
compared numbers; a run whose comparison passes with something planted
is a finding. The benchmark's own runs never run this.
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

from benchlib import harness, registry  # noqa: E402
from benchlib.device import device_info, require_chips  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--plant", required=True)
    ap.add_argument("--ack-mode", choices=("async", "sync"), required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    bench = registry.load_benchmark()
    device = device_info()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        cell = harness.make_cell(bench, args.workload, seed, args.seconds,
                                 False, time.perf_counter())
        require_chips(device, cell.chips)
        cell.peaks = registry.peaks_for(device["kind"])
        cell.config["served"]["ack_mode"] = args.ack_mode
        driver = registry.load_driver(cell.mix["driver"])
        run = driver.run(cell, plant=args.plant)
        row = {"plant": args.plant, "ack_mode": args.ack_mode, "seed": seed,
               "correct": run.correct,
               "checks": {n: v for n, v, _ in run.checks},
               "attempted": run.attempted, "failed": run.failed,
               "failover_s": run.extra.get("failover_s")}
        rows.append(row)
        print(json.dumps(row), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"workload": args.workload, "device": device,
                       "seconds": args.seconds, "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
