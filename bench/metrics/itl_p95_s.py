"""itl_p95_s: 95th percentile over every gap between consecutive tokens
of every request due in the window, on the benchmark's clock."""
import numpy as np

from metric_util import pctl


def read(rec):
    if rec.kind != "serve":
        return None
    gaps = []
    for r in rec.requests:
        gaps.extend(np.diff(r["token_t"]).tolist())
    return pctl(gaps, 95)
