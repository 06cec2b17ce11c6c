"""train_mfu: the whole train step's share of the chips' peak: model
operations per step (flops.train_flops_per_step, nothing recomputed)
times steps, over the window, over chips x peak."""
import flops


def read(rec):
    if rec.kind != "train" or not rec.steps:
        return None
    mix = rec.traffic
    work = flops.train_flops_per_step(rec.dims, int(mix["batch"]),
                                      int(mix["seq"])) * rec.steps
    return 100.0 * work / rec.window_s / (
        rec.chips * rec.peak["bf16_flops_per_s"])
