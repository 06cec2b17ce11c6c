"""prefill_pad_waste.serve: the share of prefilled positions that were
padding: 1 - (real prompt tokens) / (slots x bucket), summed over the
program's ``repro.serve.prefill`` spans in the traced window."""
import program_spans as ps


def read(rec):
    if rec.kind != "serve":
        return None
    spans = ps.named(ps.of(rec), ps.SERVE_PREFILL)
    padded = sum(s.meta["slots"] * s.meta["bucket"] for s in spans)
    if not padded:
        return None
    return 100.0 * (1.0 - sum(s.meta["tokens"] for s in spans) / padded)
