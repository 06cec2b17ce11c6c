"""decode_occupancy.serve: live slots over all slots, summed over the
program's ``repro.serve.decode`` spans in the traced window."""
import program_spans as ps


def read(rec):
    if rec.kind != "serve":
        return None
    spans = ps.named(ps.of(rec), ps.SERVE_DECODE)
    slots = sum(s.meta["slots"] for s in spans)
    if not slots:
        return None
    return 100.0 * sum(s.meta["active"] for s in spans) / slots
