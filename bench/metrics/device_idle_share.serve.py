"""device_idle_share.serve: 1 - (union of device operation intervals)
over the traced window, in a serving cell."""


def read(rec):
    tr = rec.trace
    if rec.kind != "serve" or tr is None or not tr.devices():
        return None
    return 100.0 * (1.0 - tr.busy_s() / tr.window_s)
