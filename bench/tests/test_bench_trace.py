"""The reduction from trace to device numbers, on 60 ms of a trace
recorded on one TPU v5e (``fixtures/trace_rfold4096_steady.json``)."""
import json
from pathlib import Path

import numpy as np
import pytest

import smallcell  # noqa: F401  (puts bench/ on the path)
from benchlib import tracefile

FIXTURE = Path(__file__).parent / "fixtures" / "trace_rfold4096_steady.json"


@pytest.fixture(scope="module")
def events():
    return json.loads(FIXTURE.read_text())


def _timeline(events, w0, w1):
    """Busy nanoseconds of the window on a 1 ns grid, the slow way."""
    busy = np.zeros(int(w1 - w0), bool)
    for _, s, d in events["devices"]["/device:TPU:0"]["XLA Ops"]:
        lo, hi = max(s, w0) - w0, min(s + d, w1) - w0
        if hi > lo:
            busy[int(lo):int(hi)] = True
    return busy


def test_busy_and_idle_match_a_timeline(events):
    red = tracefile.reduce_events(events)
    (_, w0, wdur), = [e for e in events["host"] if e[0] == tracefile.WINDOW]
    busy = _timeline(events, w0, w0 + wdur)
    assert red["window_s"] == pytest.approx(wdur / 1e9)
    assert red["busy_s"] == pytest.approx(busy.sum() / 1e9, rel=1e-3)
    idle = sum(t for _, t in red["idle_gaps"])
    assert idle == pytest.approx(red["window_s"] - red["busy_s"], rel=1e-6)
    assert 0 < red["busy_s"] < red["window_s"]


def test_kernel_time_is_the_fitmask_events(events):
    red = tracefile.reduce_events(events)
    ops = events["devices"]["/device:TPU:0"]["XLA Ops"]
    fit = [d for n, _, d in ops if "_fitmask_multibox" in n]
    assert red["kernel_events"] == len(fit) > 0
    assert red["kernel_s"] == pytest.approx(sum(fit) / 1e9)
    names = [n for n, _ in red["device_ops"]]
    assert any(n.startswith("_fitmask_multibox") for n in names)
    assert all(" = " not in n for n in names)


def test_idle_gaps_are_named_by_host_spans(events):
    red = tracefile.reduce_events(events)
    labels = {n for n, _ in red["idle_gaps"]}
    assert labels <= {"bench.apply", "bench.engine", "bench.wal",
                      "bench.snapshot", "host.other"}


def test_no_window_or_no_device_reads_nothing(events):
    assert tracefile.reduce_events({"devices": events["devices"],
                                    "host": []}) is None
    assert tracefile.reduce_events({"devices": {},
                                    "host": events["host"]}) is None
