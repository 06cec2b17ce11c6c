"""decode_gap_program_ms.serve: of each gap between consecutive decode
programs with no other program between them (the gaps of
decode_gap_ms.serve), the mean time that the engine's own host work
covers: its admission, capacity, decode-dispatch and per-slot
bookkeeping spans (``repro.serve.*``), moved onto the device's clock by
the trace's own offset (``program_spans.clock_offset``).  The rest of the
gap is the wait for the tokens to reach the host and the caller's own
loop."""
import numpy as np

import program_spans as ps
from metric_util import DECODE


def read(rec):
    tr = rec.trace
    if rec.kind != "serve" or tr is None:
        return None
    cover = ps.union(ps.named(ps.of(rec), *ps.SERVE_HOST_WORK))
    runs = tr.module_runs(r".")
    gaps = [ps.covered(a.end, b.start, cover) for a, b in zip(runs, runs[1:])
            if tr.matches(DECODE, a) and tr.matches(DECODE, b)]
    if not cover or not gaps:
        return None
    return float(np.mean(gaps)) / 1e6
