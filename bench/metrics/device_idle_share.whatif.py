"""``device_idle_share.whatif``: Share of the traced window with no
operation on the device, what-if cells."""
from benchlib.readers import device_idle_pct


def read(run):
    return device_idle_pct(run)
