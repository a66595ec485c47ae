"""CPU checks of the benchmark's own arithmetic and lookups: the fitmask
work count, percentiles over every op, lookup by name, the traffic
generator, and the refusal to run without a TPU."""
import json
import math
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import smallcell  # noqa: F401  (puts bench/ and src/ on the path)
from benchlib import philly, readers, reference, registry, stats, work
from benchlib.device import NoAccelerator, require_chips

ROOT = Path(__file__).resolve().parents[2]


# -- fitmask work ------------------------------------------------------

def test_fitmask_work_counts_decisions_and_cells():
    ops, nbytes = work.fitmask_work(64, 10, 4, 4, 4)
    cells = 64 * 64
    assert ops == work.DECISION_OPS * cells * 10 + cells
    assert nbytes == cells + cells * 10 / 8


def test_roofline_seconds_takes_the_binding_peak():
    peaks = registry.peaks_for("TPU v5 lite")
    t, bound = work.roofline_seconds(*work.fitmask_work(64, 10, 4, 4, 4),
                                     peaks)
    assert bound == "bytes"
    assert t == pytest.approx((4096 + 4096 * 10 / 8) / 819e9)
    t, bound = work.roofline_seconds(1e12, 1.0, peaks)
    assert bound == "ops" and t == pytest.approx(1e12 / 393e12)


# -- percentiles -------------------------------------------------------

def test_percentile_matches_numpy_over_all_values():
    rng = np.random.default_rng(0)
    xs = list(rng.exponential(5.0, size=1001))
    for q in (50, 95, 99):
        assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_failed_ops_count_as_missing_every_limit():
    xs = [1.0] * 90 + [math.inf] * 10
    assert stats.percentile(xs, 50) == 1.0
    assert stats.percentile(xs, 95) == math.inf


def test_latency_is_taken_from_the_due_time():
    class Run:
        ops = [["submit", 1, 10.0, 10.5, 10.75, "c:1", {"ok": True}],
               ["done", 1, 11.0, 11.0, 11.001, "c:2", {"ok": True}],
               ["done", 2, 12.0, None, None, None, None]]
    lat = readers.latency_ms(Run)
    assert lat[0] == pytest.approx(750.0)
    assert lat[1] == pytest.approx(1.0)
    assert lat[2] == math.inf


# -- lookup by name ----------------------------------------------------

def test_every_name_in_the_benchmark_has_its_file():
    bench = registry.load_benchmark()
    for cell in bench["workloads"]:
        registry.load_config(bench, cell["config"])
        mix = registry.load_mix(cell["traffic"])
        registry.load_driver(mix["driver"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(registry.load_reader(m["name"]))


def test_every_cell_reports_setup_another_metric_and_a_layer():
    bench = registry.load_benchmark()
    for cell in bench["workloads"]:
        e2e = {m["name"] for m in registry.metrics_for(bench, cell["name"],
                                                       "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = registry.metrics_for(bench, cell["name"], "per_layer")
        assert layer and all(m["moves"] in e2e for m in layer)


def test_unknown_names_are_refused():
    bench = registry.load_benchmark()
    with pytest.raises(registry.UnknownName):
        registry.find_cell(bench, "no.such.cell")
    with pytest.raises(registry.UnknownName):
        registry.load_mix("no_such_mix")
    with pytest.raises(registry.UnknownName):
        registry.load_reader("no_such_metric")
    with pytest.raises(registry.UnknownName):
        registry.peaks_for("TPU v999")


def test_a_new_metric_is_found_by_its_file(tmp_path):
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "new_metric.x.py").write_text(
        "def read(run):\n    return 42.0\n")
    assert registry.load_reader("new_metric.x", tmp_path)(None) == 42.0


# -- traffic -----------------------------------------------------------

def test_seeds_permute_one_pool_of_jobs():
    p = {**registry.load_mix("steady")["philly"], "cluster_xpus": 4096,
         "size_max": 4096}
    a = philly.jobs(p, 300, seed=1, head=100)
    b = philly.jobs(p, 300, seed=2 ** 33 + 5, head=100)
    key = lambda js: sorted((j.duration, j.shape) for j in js)  # noqa: E731
    gaps = lambda js: sorted(np.diff([j.arrival for j in js]))  # noqa: E731
    # The prefill and the window each get the same jobs and gaps from
    # every seed, in another order.
    for part in (slice(0, 100), slice(100, 300)):
        assert key(a[part]) == key(b[part])
        assert [j.shape for j in a[part]] != [j.shape for j in b[part]]
    assert np.allclose(gaps(a[99:]), gaps(b[99:]))
    assert a[99].arrival == pytest.approx(b[99].arrival)
    assert all(j.size <= 4096 for j in a)


def test_drawn_order_gives_every_seed_the_same_arrivals():
    p = {**registry.load_mix("steady")["philly"], "cluster_xpus": 4096,
         "size_max": 4096}
    a = philly.jobs(p, 300, seed=1, head=100, order="drawn")
    b = philly.jobs(p, 300, seed=2 ** 33 + 5, head=100, order="drawn")
    same = lambda js: [(j.arrival, j.duration, j.shape) for j in js]  # noqa: E731,E501
    assert same(a) == same(b)
    assert [j.job_id for j in a] != [j.job_id for j in b]
    assert sorted(j.job_id for j in b) == list(range(300))
    with pytest.raises(ValueError):
        philly.jobs(p, 10, seed=1, order="sorted")


def test_load_generator_never_imports_jax():
    code = ("import sys; sys.path[:0] = ['bench/drivers'];"
            "import loadgen; print('jax' in sys.modules)")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "False"


# -- the plain reference -----------------------------------------------

def test_plain_fitmask_matches_a_loop_over_origins():
    rng = np.random.default_rng(3)
    occ = rng.uniform(size=(3, 4, 5, 6)) < 0.3
    boxes = [(1, 1, 1), (2, 3, 1), (4, 5, 6), (5, 1, 1)]
    got = reference.PlainFitmask().multibox(occ, boxes)
    for b in range(3):
        for k, (a, bb, c) in enumerate(boxes):
            for x in range(4):
                for y in range(5):
                    for z in range(6):
                        inside = x + a <= 4 and y + bb <= 5 and z + c <= 6
                        want = inside and not occ[b, x:x + a, y:y + bb,
                                                  z:z + c].any()
                        assert got[b, k, x, y, z] == want
    assert list(reference.PlainFitmask().free_counts(occ)) == \
        [int((~occ[b]).sum()) for b in range(3)]


# -- no TPU, no result -------------------------------------------------

def test_no_tpu_is_refused():
    with pytest.raises(NoAccelerator):
        require_chips({"platform": "cpu", "kind": "cpu", "count": 1}, 1)
    with pytest.raises(NoAccelerator):
        require_chips({"platform": "tpu", "kind": "TPU v5 lite",
                       "count": 1}, 4)


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run([sys.executable, "bench/run.py", "--workload",
                          "rfold4096.steady", "--seed", "1", "--seconds",
                          "1", "--trace", "0"], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    for line in out.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)
