"""Small cells for the CPU tests: the served and what-if drivers at a
size a test run holds (512 XPUs as 8 cubes of 4^3, or as an 8^3 torus
for Folding), with the harness's look for a chip skipped. The served cell runs the ``jax`` engine (the
``numpy`` one makes no engine calls on the served path); the what-if
cell runs the ``numpy`` engine through the broker."""
from __future__ import annotations

import copy
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

from benchlib import harness, registry  # noqa: E402

XPUS = 512
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


def small_config() -> dict:
    cfg = copy.deepcopy(registry.load_config(registry.load_benchmark(),
                                             "rfold4096"))
    cfg["num_xpus"] = XPUS
    cfg["policy_kw"] = {"num_xpus": XPUS, "cube_n": 4}
    grid = [4, 4, 4]
    cfg["warm"] = {"served": {"grid": grid, "multibox": [], "free_counts": []},
                   "whatif": {"grid": grid, "multibox": [], "free_counts": []}}
    return cfg


def small_folding_config() -> dict:
    """Folding on a static 8^3 torus (512 XPUs), otherwise as above."""
    cfg = small_config()
    cfg["policy"] = "folding"
    cfg["policy_kw"] = {"dims": [8, 8, 8]}
    grid = [8, 8, 8]
    cfg["warm"] = {"served": {"grid": grid, "multibox": [], "free_counts": []},
                   "whatif": {"grid": grid, "multibox": [], "free_counts": []}}
    return cfg


def served_cell(seed: int, trace: bool = False, **hooks) -> harness.Cell:
    mix = dict(registry.load_mix("steady"), prefill_jobs=60, connections=4,
               grace_s=20.0)
    return harness.Cell(name="small.steady", config=small_config(), mix=mix,
                        seed=seed, seconds=1.5, trace=trace, chips=1,
                        t_start=time.perf_counter(), engine="jax", rate=25.0,
                        peaks=registry.peaks_for("TPU v5 lite"), **hooks)


def whatif_cell(seed: int, folding: bool = False, **hooks) -> harness.Cell:
    """Two RFold simulators per sweep, or (``folding``) one Folding
    simulator per sweep on the 8^3 torus."""
    if folding:
        mix = dict(registry.load_mix("whatif"), sims=1, num_jobs=40)
        config = small_folding_config()
    else:
        mix = dict(registry.load_mix("whatif"), sims=2, num_jobs=40)
        config = small_config()
    return harness.Cell(name="small.whatif", config=config, mix=mix,
                        seed=seed, seconds=0.1, trace=False, chips=1,
                        t_start=time.perf_counter(), engine="numpy",
                        peaks=registry.peaks_for("TPU v5 lite"), **hooks)


def run(cell: harness.Cell):
    driver = registry.load_driver(cell.mix["driver"])
    return driver.run(cell)
