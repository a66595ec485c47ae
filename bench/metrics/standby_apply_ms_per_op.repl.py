"""``standby_apply_ms_per_op.repl``: The standby's time applying and
fsyncing the records it pulled, as each pull reports it (the
``standby_apply_s`` tag of ``repl.serve``), summed over the window, per
op of the window, replicated served cells."""
from benchlib import replspans


def read(run):
    return replspans.per_op(run, lambda r: 1e3 * r.tags["standby_apply_s"],
                            tag="standby_apply_s")
