"""``k_served_per_needed.whatif``: Box slots the fleet broker sent to the
engine over the distinct boxes its flushes asked for (the ``k_served``
and ``k_needed`` tags of program span ``broker.flush``), what-if cells:
1 is no spare slot."""
from benchlib.progtags import tag_ratio


def read(run):
    return tag_ratio(run, "broker.flush", "k_served", "k_needed")
