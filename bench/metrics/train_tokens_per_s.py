"""train_tokens_per_s: every token trained in the window over the whole
window, which ends when the last step's state is ready."""


def read(rec):
    if rec.kind != "train" or not rec.steps:
        return None
    return rec.tokens / rec.window_s
