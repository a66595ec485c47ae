"""The standby replica of a replicated served cell: a child process
that never imports JAX, as a control-plane host without an accelerator.

    python standby.py < params.json

It reads one JSON object of parameters from its first line of standard
input: the primary's ``address``, the placement rules (``policy``,
``policy_kw``, ``backfill``), its own ``engine``, ``fsync`` and
``checkpoint_dir``, and ``plant`` (null in a benchmark run). It starts
a ``repro.api.Scheduler`` with ``role="standby"`` that tails the
primary, prints ``{"event": "ready", "address": [host, port]}``, and
serves until its standard input closes or it is killed. The driver
talks to it over loopback TCP: ``status``, then ``promote``.

``plant`` "lost_wal" plants a fault for the tests: the standby's
journal appends and snapshots write nothing, yet it acknowledges every
record it applies.
"""
from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))                     # bench/
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))

from benchlib import reference  # noqa: E402
from repro import api  # noqa: E402


def main() -> int:
    params = json.loads(sys.stdin.readline())
    config = api.SchedulerConfig(
        policy=params["policy"], policy_kw=dict(params["policy_kw"]),
        backfill=params["backfill"], engine=params["engine"],
        checkpoint_dir=params["checkpoint_dir"], fsync=params["fsync"],
        role=api.ROLE_STANDBY, replicate_from=tuple(params["address"]))
    sched = api.Scheduler(config).start()
    try:
        if params.get("plant") == "lost_wal":
            reference.CORE_FAULTS["lost_wal"](sched._daemon.core)
        elif params.get("plant") is not None:
            raise ValueError(f"no standby plant {params['plant']!r}")
        print(json.dumps({"event": "ready",
                          "address": list(sched.address)}), flush=True)
        sys.stdin.readline()
    finally:
        sched.kill()
    if "jax" in sys.modules:
        raise RuntimeError("the standby imported JAX")
    return 0


if __name__ == "__main__":
    sys.exit(main())
