"""``fitmask_roofline.served``: Least time the chip could take for the
fitmask work asked for, over the kernel's device time, served cells."""
from benchlib.readers import fitmask_roofline_pct


def read(run):
    return fitmask_roofline_pct(run)
