"""decode_step_mfu: the whole decode step's share of the chip's roofline:
the least time of a decode step (the larger of its operations over peak
and its bytes over HBM bandwidth: every weight once, and the keys and
values the active sequences attend, from flops.decode_*), over the
measured device time of a decode program, in the traced window."""
import numpy as np

import flops
from metric_util import DECODE, traced_sched_steps


def read(rec):
    tr = rec.trace
    if rec.kind != "serve" or tr is None:
        return None
    runs = tr.module_runs(DECODE)
    steps = [s for s in traced_sched_steps(rec) if s[2] > 0]
    if not runs or not steps:
        return None
    least = []
    for _, _, ticks, tokens, kv in steps:
        t, _ = flops.least_time(flops.decode_flops(rec.dims, tokens / ticks,
                                                   kv / ticks),
                                flops.decode_bytes(rec.dims, kv / ticks),
                                rec.peak)
        least.append(t)
    measured = float(np.mean([e.dur for e in runs])) / 1e9
    return 100.0 * float(np.mean(least)) / measured
