"""``setup_s``: Seconds from the process's start to the window's: imports,
warm-up (compiles, or loads from the persistent cache) and the prefill
the traffic needs (host clock)."""


def read(run):
    return run.setup_s
