"""Helpers shared by the metric readers in ``bench/metrics/``."""
from __future__ import annotations

from typing import List, Optional

import numpy as np

# programs and kernels as the trace names them today: a program run is
# ``jit_<python function name>(<id>)``; the flash kernels are custom calls
# named after the jitted wrapper, ``%jvp_jit__flash_attention_vjp__.<n>``
# forward and ``%transpose_jvp_jit__flash_attention_vjp___.<n>`` backward
TRAIN_STEP = r"^jit_train_step\("
PREFILL = r"^jit_fn\("
DECODE = r"^jit_fused_decode\("
FLASH_KERNELS = r"flash_attention"


def pctl(values: List[float], q: float) -> Optional[float]:
    if not values:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def ttfts(rec) -> List[float]:
    """Due time to first token of every request due in the window.  One
    that never got a first token counts the whole wait it was given, to
    the end of the run: a lower bound of an infinite wait."""
    end = rec.counters["t_end"]
    return [(r["token_t"][0] if r["token_t"] else end) - r["due"]
            for r in rec.requests]


def traced_sched_steps(rec):
    """The scheduler steps that ran inside the traced window."""
    if rec.traced is None:
        return []
    a, b = rec.traced
    return [s for s in rec.sched_steps if s[0] >= a and s[1] <= b]
