"""Show that a cell's comparison fails when it should.

    python3 bench/control.py --workload rfold4096.steady \
        --plant control,half_batch,flip,unchanged,lost_wal \
        --seeds 11,12,13 [--seconds 10] [--out control.json]

Runs the cell at its own size on the chip, as ``run.py`` does, once per
seed and planted thing, with:

* ``control``: the plain reference in the engine's place, answering
  from occupancy one query stale (``benchlib.reference.StaleFitmask``);
* ``half_batch``, ``flip``, ``unchanged``: faults planted in the
  program's engine (``benchlib.reference.FAULTS``);
* ``lost_wal``: the served daemon's journal writes nothing
  (``benchlib.reference.CORE_FAULTS``);
* ``none``: nothing planted, the sound run.

Each run prints one JSON line with the compared numbers; a run whose
comparison passes with something planted is a finding. The benchmark's
own runs never run this.
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

from benchlib import harness, reference, registry  # noqa: E402
from benchlib.device import device_info, require_chips  # noqa: E402


def hooks(plant: str) -> dict:
    if plant == "none":
        return {}
    if plant == "control":
        return {"engine_hook": reference.use_control}
    if plant in reference.FAULTS:
        return {"engine_hook": reference.FAULTS[plant]}
    if plant in reference.CORE_FAULTS:
        return {"core_hook": reference.CORE_FAULTS[plant]}
    raise SystemExit(f"unknown --plant {plant!r}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--plant", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    bench = registry.load_benchmark()
    device = device_info()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    rows = []
    for plant in args.plant.split(","):
        for seed in (int(s) for s in args.seeds.split(",")):
            cell = harness.make_cell(bench, args.workload, seed, args.seconds,
                                     False, time.perf_counter(),
                                     **hooks(plant))
            require_chips(device, cell.chips)
            cell.peaks = registry.peaks_for(device["kind"])
            try:
                run, _ = harness.run_cell(cell, bench, device)
                row = {"plant": plant, "seed": seed, "correct": run.correct,
                       "checks": {n: v for n, v, _ in run.checks},
                       "attempted": run.attempted, "failed": run.failed}
            except Exception as e:  # noqa: BLE001 -- a crash is a failure
                row = {"plant": plant, "seed": seed, "correct": False,
                       "crashed": f"{type(e).__name__}: {e}"[:300]}
            rows.append(row)
            print(json.dumps(row), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"workload": args.workload, "device": device,
                       "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
