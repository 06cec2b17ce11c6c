"""prefill_share.serve: device time of the prefill programs over the
device's busy time, in the traced window."""
from metric_util import PREFILL


def read(rec):
    tr = rec.trace
    if rec.kind != "serve" or tr is None:
        return None
    busy = tr.busy_s()
    runs = tr.module_runs(PREFILL)
    if busy <= 0 or not runs:
        return None
    return 100.0 * sum(e.dur for e in runs) / 1e9 / busy
