"""``repl_serve_ms_per_op.repl``: The primary loop's own time framing
records for the standby (program span ``repl.serve``), summed over the
window, per op of the window, replicated served cells."""
from benchlib import replspans


def read(run):
    return replspans.per_op(run, lambda r: 1e3 * r.seconds)
