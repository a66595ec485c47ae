"""``fitmask_roofline.whatif``: Least time the chip could take for the
fitmask work asked for, over the kernel's device time, what-if cells."""
from benchlib.readers import fitmask_roofline_pct


def read(run):
    return fitmask_roofline_pct(run)
