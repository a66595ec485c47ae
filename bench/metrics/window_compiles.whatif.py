"""``window_compiles.whatif``: XLA compiles inside the window (should be
0), what-if cells."""
from benchlib.readers import window_compiles


def read(run):
    return window_compiles(run)
