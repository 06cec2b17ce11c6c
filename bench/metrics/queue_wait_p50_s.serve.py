"""queue_wait_p50_s.serve: median, over the window's requests, of the time
from due to the start of the scheduler step that admitted it (the step in
which its first token came): the wait for the scheduler, before its own
prefill."""
import bisect

from metric_util import pctl


def read(rec):
    if rec.kind != "serve" or not rec.sched_steps:
        return None
    starts = [s[0] for s in rec.sched_steps]
    waits = []
    for r in rec.requests:
        if not r["token_t"]:
            continue
        i = bisect.bisect_right(starts, r["token_t"][0]) - 1
        if i >= 0:
            waits.append(max(0.0, starts[i] - r["due"]))
    return pctl(waits, 50)
