"""``window_compiles.served``: XLA compiles inside the window (should be
0), served cells."""
from benchlib.readers import window_compiles


def read(run):
    return window_compiles(run)
