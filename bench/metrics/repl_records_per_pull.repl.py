"""``repl_records_per_pull.repl``: Journal records the primary sent per
standby pull (the ``records`` tag of ``repl.serve``), replicated served
cells: how many ops one round trip to the standby carries."""
from benchlib import replspans


def read(run):
    pulls = replspans.pulls(run, tag="records")
    if not pulls:
        return None
    return sum(r.tags["records"] for r in pulls) / len(pulls)
