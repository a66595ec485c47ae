"""``wire_queue_ms_per_op``: Mean send-to-reply time less the op's apply
span, matched by request id: client, wire and the daemon loop's queue."""
from benchlib.readers import wire_queue_ms_per_op


def read(run):
    return wire_queue_ms_per_op(run)
