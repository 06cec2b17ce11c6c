"""input_wait_ms.train: mean host time per step spent in the program's
data source (next_batch), over the window."""


def read(rec):
    n = rec.span_count("next_batch")
    if rec.kind != "train" or not n:
        return None
    return rec.span_total("next_batch") / n * 1e3
