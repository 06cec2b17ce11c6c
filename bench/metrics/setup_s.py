"""setup_s: process start to the start of the window (import, weights
from the seed, compile-cache loads, warm-up of the cell's shapes)."""


def read(rec):
    return rec.setup_s
