"""exposed_input_ms.train: device-idle time (first device) that lies
inside the program's ``repro.train.next_batch`` spans, per train-step
program run in the traced window: the part of building the next batch
that the step does not hide."""
import program_spans as ps
from metric_util import TRAIN_STEP


def read(rec):
    tr = rec.trace
    if rec.kind != "train" or tr is None or not tr.devices():
        return None
    cover = ps.union(ps.named(ps.of(rec), ps.TRAIN_NEXT_BATCH))
    runs = tr.module_runs(TRAIN_STEP)
    if not cover or not runs:
        return None
    gaps = tr.idle_gaps(tr.devices()[0])
    return sum(ps.covered(a, b, cover) for a, b in gaps) / len(runs) / 1e6
