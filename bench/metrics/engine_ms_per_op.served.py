"""``engine_ms_per_op.served``: Mean time per op in fitmask engine calls
(dispatch, device, copy back), served cells."""
from benchlib.readers import per_op_ms


def read(run):
    return per_op_ms(run, ("bench.engine",))
