"""Tests for the parallel evaluation subsystem (repro.eval): seed
derivation stability, pool-vs-serial equivalence, checkpoint
resume-equals-fresh, and fingerprint invalidation."""
import json
import os

import pytest

from repro.eval import (EvalRunner, EvalTask, aggregate_by_label,
                        derive_seed, make_tasks, prune_checkpoints,
                        run_task, table1)
from repro.eval.runner import SHARD_CHARS, iter_checkpoints, shard_dir

# Small matrix: 512-XPU cluster, short traces — seconds, not minutes.
CONFIGS = [
    ("RFold (4^3)", "rfold", dict(num_xpus=512, cube_n=4)),
    ("Reconfig (4^3)", "reconfig", dict(num_xpus=512, cube_n=4)),
]


def _tasks(runs=2, num_jobs=25):
    return make_tasks(CONFIGS, runs=runs, num_jobs=num_jobs, load=1.5,
                      seed0=100)


def _strip_timing(records):
    return [{k: v for k, v in r.items() if k != "sim_s"} for r in records]


# ----------------------------------------------------- seed derivation
def test_derive_seed_depends_only_on_run_idx():
    a = [derive_seed(100, r) for r in range(8)]
    b = [derive_seed(100, r) for r in range(8)]
    assert a == b
    assert len(set(a)) == len(a)        # distinct runs, distinct seeds


def test_task_seeds_paired_across_policies():
    """Every policy sees the same trace seed for run r (paired runs)."""
    tasks = _tasks(runs=3)
    by_run = {}
    for t in tasks:
        by_run.setdefault(t.run_idx, set()).add(t.seed)
    for r, seeds in by_run.items():
        assert len(seeds) == 1, (r, seeds)


def test_records_stable_across_worker_counts(tmp_path):
    """Pool width is an execution detail: identical records for
    workers=0 (inline), 1, and 2 (process pool)."""
    outs = []
    for workers in (0, 1, 2):
        runner = EvalRunner(checkpoint_dir=None, workers=workers)
        outs.append(_strip_timing(runner.run(_tasks())))
    assert outs[0] == outs[1] == outs[2]


# ----------------------------------------------------- checkpoint/resume
def test_resume_from_partial_checkpoint_equals_fresh(tmp_path):
    ckpt = str(tmp_path / "ckpt")
    fresh = EvalRunner(checkpoint_dir=None, workers=0).run(_tasks())

    # populate checkpoints, then delete half of them
    runner = EvalRunner(checkpoint_dir=ckpt, workers=0)
    runner.run(_tasks())
    files = sorted(iter_checkpoints(ckpt))
    assert len(files) == len(_tasks())
    for path in files[::2]:
        os.remove(path)

    resumed_runner = EvalRunner(checkpoint_dir=ckpt, workers=0)
    resumed = resumed_runner.run(_tasks())
    stats = resumed_runner.last_stats
    assert stats["reused_from_checkpoint"] == len(files) - len(files[::2])
    assert stats["executed"] == len(files[::2])
    assert _strip_timing(resumed) == _strip_timing(fresh)

    # aggregates (what the tables are built from) match exactly too
    agg_fresh = aggregate_by_label(fresh)
    agg_resumed = aggregate_by_label(resumed)
    for label in agg_fresh:
        assert agg_fresh[label]["agg"] == agg_resumed[label]["agg"]
        assert table1(agg_fresh) == table1(agg_resumed)


def test_stale_fingerprint_checkpoint_is_rerun(tmp_path):
    """A checkpoint written under a different config (here: num_jobs)
    must not be reused for the new config."""
    ckpt = str(tmp_path / "ckpt")
    EvalRunner(checkpoint_dir=ckpt, workers=0).run(_tasks(num_jobs=20))
    runner = EvalRunner(checkpoint_dir=ckpt, workers=0)
    records = runner.run(_tasks(num_jobs=25))
    assert runner.last_stats["reused_from_checkpoint"] == 0
    assert all(r["summary"]["num_jobs"] == 25 for r in records)


def test_corrupt_checkpoint_is_rerun(tmp_path):
    ckpt = str(tmp_path / "ckpt")
    tasks = _tasks(runs=1)
    EvalRunner(checkpoint_dir=ckpt, workers=0).run(tasks)
    victim = os.path.join(shard_dir(ckpt, tasks[0].fingerprint()),
                          tasks[0].checkpoint_name())
    with open(victim, "w") as f:
        f.write("{not json")
    runner = EvalRunner(checkpoint_dir=ckpt, workers=0)
    runner.run(tasks)
    assert runner.last_stats["executed"] == 1
    with open(victim) as f:
        assert json.load(f)["fingerprint"] == tasks[0].fingerprint()


def test_pool_writes_checkpoints(tmp_path):
    ckpt = str(tmp_path / "ckpt")
    runner = EvalRunner(checkpoint_dir=ckpt, workers=2)
    runner.run(_tasks(runs=1))
    assert sorted(os.path.basename(p) for p in iter_checkpoints(ckpt)) \
        == sorted(t.checkpoint_name() for t in _tasks(runs=1))


# ----------------------------------------------------- sharded store
def test_checkpoints_land_in_fingerprint_shards(tmp_path):
    """Every checkpoint is bucketed under its fingerprint prefix — no
    file sits in the flat root (10k-task sweeps must not pile up in
    one directory)."""
    ckpt = str(tmp_path / "ckpt")
    tasks = _tasks(runs=2)
    EvalRunner(checkpoint_dir=ckpt, workers=0).run(tasks)
    assert not [n for n in os.listdir(ckpt)
                if os.path.isfile(os.path.join(ckpt, n))]
    for t in tasks:
        path = os.path.join(shard_dir(ckpt, t.fingerprint()),
                            t.checkpoint_name())
        assert os.path.exists(path), path
        rel = os.path.relpath(path, ckpt)
        assert rel.split(os.sep)[0] == t.fingerprint()[:SHARD_CHARS]


def test_resume_from_legacy_flat_store(tmp_path):
    """A pre-shard (flat) checkpoint dir keeps resuming: every record
    is reused, nothing re-executes."""
    ckpt = str(tmp_path / "ckpt")
    tasks = _tasks(runs=1)
    EvalRunner(checkpoint_dir=ckpt, workers=0).run(tasks)
    # flatten the store the way the pre-shard runner laid it out
    for path in list(iter_checkpoints(ckpt)):
        os.replace(path, os.path.join(ckpt, os.path.basename(path)))
    for name in os.listdir(ckpt):
        sub = os.path.join(ckpt, name)
        if os.path.isdir(sub):
            os.rmdir(sub)
    runner = EvalRunner(checkpoint_dir=ckpt, workers=0)
    runner.run(tasks)
    assert runner.last_stats["reused_from_checkpoint"] == len(tasks)
    assert runner.last_stats["executed"] == 0


def test_flat_store_cross_label_reuse(tmp_path):
    """The label-independent fingerprint glob also finds legacy flat
    checkpoints written under a different label."""
    ckpt = str(tmp_path / "ckpt")
    t1 = make_tasks([CONFIGS[0]], runs=1, num_jobs=20, load=1.5, seed0=100)
    EvalRunner(checkpoint_dir=ckpt, workers=0).run(t1)
    for path in list(iter_checkpoints(ckpt)):
        os.replace(path, os.path.join(ckpt, os.path.basename(path)))
    relabeled = [("RFold renamed",) + CONFIGS[0][1:]]
    t2 = make_tasks(relabeled, runs=1, num_jobs=20, load=1.5, seed0=100)
    runner = EvalRunner(checkpoint_dir=ckpt, workers=0)
    records = runner.run(t2)
    assert runner.last_stats["reused_from_checkpoint"] == 1
    assert records[0]["label"] == "RFold renamed"


# ----------------------------------------------------- task semantics
def test_run_task_record_shape():
    task = EvalTask(label="RFold (4^3)", policy="rfold",
                    policy_kw=dict(num_xpus=512, cube_n=4),
                    run_idx=0, seed=7, num_jobs=15, load=1.5)
    rec = run_task(task)
    assert rec["fingerprint"] == task.fingerprint()
    assert rec["summary"]["num_jobs"] == 15
    assert 0.0 <= rec["summary"]["jcr"] <= 1.0
    assert len(rec["cdf_levels"]) == len(rec["cdf"]) == 101


def test_sim_kw_reaches_simulator():
    """Tasks carry Simulator kwargs (the ablation driver relies on
    this): backfill=True must change scheduling on a blocking trace."""
    base = EvalTask(label="x", policy="rfold",
                    policy_kw=dict(num_xpus=512, cube_n=4),
                    seed=3, num_jobs=40, load=3.0)
    bf = EvalTask(label="x", policy="rfold",
                  policy_kw=dict(num_xpus=512, cube_n=4),
                  seed=3, num_jobs=40, load=3.0,
                  sim_kw=dict(backfill=True))
    assert base.fingerprint() != bf.fingerprint()
    r_base, r_bf = run_task(base), run_task(bf)
    assert r_bf["summary"]["jct_p50"] <= r_base["summary"]["jct_p50"]


def test_fingerprint_ignores_display_label():
    """Label is display-only: two labels for one config share a
    fingerprint (the ablation arms rely on cross-label reuse)."""
    a = EvalTask(label="RFold (4^3)", policy="rfold", policy_kw={"cube_n": 4})
    b = EvalTask(label="RFold FIFO", policy="rfold", policy_kw={"cube_n": 4})
    assert a.fingerprint() == b.fingerprint()


def test_checkpoint_reused_across_labels(tmp_path):
    """A run checkpointed under one label is reused for the same
    config under a different label, restamped with the new label."""
    ckpt = str(tmp_path / "ckpt")
    t1 = make_tasks([CONFIGS[0]], runs=1, num_jobs=20, load=1.5, seed0=100)
    EvalRunner(checkpoint_dir=ckpt, workers=0).run(t1)
    relabeled = [("RFold renamed",) + CONFIGS[0][1:]]
    t2 = make_tasks(relabeled, runs=1, num_jobs=20, load=1.5, seed0=100)
    runner = EvalRunner(checkpoint_dir=ckpt, workers=0)
    records = runner.run(t2)
    assert runner.last_stats["reused_from_checkpoint"] == 1
    assert records[0]["label"] == "RFold renamed"


def test_fingerprint_covers_every_outcome_field():
    base = EvalTask(label="a", policy="rfold", policy_kw={"cube_n": 4})
    variants = [
        EvalTask(label="a", policy="reconfig", policy_kw={"cube_n": 4}),
        EvalTask(label="a", policy="rfold", policy_kw={"cube_n": 2}),
        EvalTask(label="a", policy="rfold", policy_kw={"cube_n": 4},
                 run_idx=1),
        EvalTask(label="a", policy="rfold", policy_kw={"cube_n": 4},
                 seed=1),
        EvalTask(label="a", policy="rfold", policy_kw={"cube_n": 4},
                 num_jobs=10),
        EvalTask(label="a", policy="rfold", policy_kw={"cube_n": 4},
                 load=2.0),
        EvalTask(label="a", policy="rfold", policy_kw={"cube_n": 4},
                 trace_kw={"size_scale": 128.0}),
        EvalTask(label="a", policy="rfold", policy_kw={"cube_n": 4},
                 sim_kw={"backfill": True}),
    ]
    fps = {t.fingerprint() for t in variants}
    assert base.fingerprint() not in fps
    assert len(fps) == len(variants)


def test_checkpoint_name_is_filesystem_safe():
    t = EvalTask(label="RFold (4^3) / weird:label", policy="rfold")
    name = t.checkpoint_name()
    assert "/" not in name and ":" not in name and " " not in name
    assert name.endswith(f"__{t.fingerprint()}.json")


@pytest.mark.parametrize("fleet_size", ["auto", 0])
def test_device_engine_refuses_worker_processes(fleet_size):
    """A JAX-backed engine claims the accelerator, which belongs to one
    process: the runner raises instead of forking workers for it."""
    runner = EvalRunner(workers=2, fleet_size=fleet_size,
                        fleet_engine="pallas")
    tasks = make_tasks([("RFold (4^3)", "rfold",
                         dict(num_xpus=512, cube_n=4, engine="pallas"))],
                       runs=2, num_jobs=5, load=1.5, seed0=0)
    with pytest.raises(ValueError, match="one process"):
        runner.run(tasks)


def test_workers_default_is_cpu_count():
    assert EvalRunner().workers == os.cpu_count()


# ----------------------------------------------------- store pruning
def _flatten_store(ckpt):
    """Rewrite a sharded store into the legacy flat layout."""
    for path in list(iter_checkpoints(ckpt)):
        os.replace(path, os.path.join(ckpt, os.path.basename(path)))
    for name in os.listdir(ckpt):
        sub = os.path.join(ckpt, name)
        if os.path.isdir(sub):
            os.rmdir(sub)


@pytest.mark.parametrize("flat", [False, True])
def test_prune_drops_stale_keeps_current(tmp_path, flat):
    """Prune removes checkpoints whose fingerprint left the task set
    (here: an old num_jobs) and keeps the current ones resumable —
    on sharded and legacy-flat stores alike."""
    ckpt = str(tmp_path / "ckpt")
    stale = _tasks(runs=1, num_jobs=20)
    current = _tasks(runs=1, num_jobs=25)
    EvalRunner(checkpoint_dir=ckpt, workers=0).run(stale)
    EvalRunner(checkpoint_dir=ckpt, workers=0).run(current)
    if flat:
        _flatten_store(ckpt)
    assert len(list(iter_checkpoints(ckpt))) == len(stale) + len(current)

    stats = prune_checkpoints(ckpt, current)
    assert stats["removed"] == len(stale)
    assert stats["kept"] == len(current)
    assert stats["bytes_freed"] > 0

    runner = EvalRunner(checkpoint_dir=ckpt, workers=0)
    runner.run(current)
    assert runner.last_stats["reused_from_checkpoint"] == len(current)


def test_prune_caps_store_size_evicting_oldest(tmp_path):
    """With max_bytes, survivors beyond the cap are evicted oldest-
    mtime first — the newest checkpoints stay resumable."""
    ckpt = str(tmp_path / "ckpt")
    tasks = _tasks(runs=2)
    EvalRunner(checkpoint_dir=ckpt, workers=0).run(tasks)
    paths = sorted(iter_checkpoints(ckpt), key=os.path.getmtime)
    for age, path in enumerate(paths):   # make mtime order deterministic
        os.utime(path, (1000 + age, 1000 + age))
    newest = max(paths, key=os.path.getmtime)
    cap = os.path.getsize(newest)
    stats = prune_checkpoints(ckpt, tasks, max_bytes=cap)
    survivors = list(iter_checkpoints(ckpt))
    assert survivors == [newest]
    assert stats["removed"] == len(paths) - 1


def test_prune_leaves_foreign_files_and_cleans_empty_shards(tmp_path):
    ckpt = str(tmp_path / "ckpt")
    tasks = _tasks(runs=1, num_jobs=20)
    EvalRunner(checkpoint_dir=ckpt, workers=0).run(tasks)
    foreign = os.path.join(ckpt, "notes.json")
    with open(foreign, "w") as f:
        f.write("{}")
    shards_before = [n for n in os.listdir(ckpt)
                     if os.path.isdir(os.path.join(ckpt, n))]
    stats = prune_checkpoints(ckpt, _tasks(runs=1, num_jobs=25))
    assert stats["removed"] == len(tasks)       # every stale ckpt gone
    assert os.path.exists(foreign)              # never ours to delete
    for name in shards_before:                  # emptied shards removed
        assert not os.path.isdir(os.path.join(ckpt, name))


if __name__ == "__main__":
    pytest.main([__file__, "-q"])
