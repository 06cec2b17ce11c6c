"""flash_attn_roofline.train: the least time that causal attention's
forward and backward need in a step (flops.attention_*_train against the
chip's peaks; compute bounds it at these sizes), over the device time of
the flash kernels in a step, from the train-step runs in the trace."""
import flops
from metric_util import FLASH_KERNELS, TRAIN_STEP


def read(rec):
    tr = rec.trace
    if rec.kind != "train" or tr is None:
        return None
    runs = tr.module_runs(TRAIN_STEP)
    kernel_s = sum(tr.kernel_time_s(FLASH_KERNELS, e.start, e.end)
                   for e in runs)
    if not runs or kernel_s <= 0:
        return None
    mix = rec.traffic
    b, s = int(mix["batch"]), int(mix["seq"])
    least, _ = flops.least_time(flops.attention_flops_train(rec.dims, b, s),
                                flops.attention_bytes_train(rec.dims, b, s),
                                rec.peak)
    return 100.0 * least * len(runs) / kernel_s
