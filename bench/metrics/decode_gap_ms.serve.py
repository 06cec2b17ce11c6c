"""decode_gap_ms.serve: mean time between the end of a decode program
and the start of the next one, where no other program runs between them:
the host's share of a decode tick, in the traced window."""
import numpy as np

from metric_util import DECODE


def read(rec):
    tr = rec.trace
    if rec.kind != "serve" or tr is None:
        return None
    runs = tr.module_runs(r".")
    gaps = [b.start - a.end for a, b in zip(runs, runs[1:])
            if tr.matches(DECODE, a) and tr.matches(DECODE, b)]
    if not gaps:
        return None
    return float(np.mean(gaps)) / 1e6
