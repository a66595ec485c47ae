"""``device_idle_share.served``: Share of the traced window with no
operation on the device, served cells."""
from benchlib.readers import device_idle_pct


def read(run):
    return device_idle_pct(run)
