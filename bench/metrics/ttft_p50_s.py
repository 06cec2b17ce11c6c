"""ttft_p50_s: median, over every request due in the window, of the time
from its due time to its first token on the host.  (At 0.8 of the knee a
51 s window holds 41 requests: too few for a 95th percentile, which moved
by 16-39% between runs of one seed; PERF.md.)"""
from metric_util import pctl, ttfts


def read(rec):
    if rec.kind != "serve" or not rec.requests:
        return None
    return pctl(ttfts(rec), 50)
