"""mfu.train: model FLOPs of the train steps done in the traced window
(``flops.py``, recomputation not counted) over window x chips x the chip's
bf16 peak, in percent.  Moves ``train_tokens_per_s``."""


def read(run):
    if run.kind != "train" or run.window_s <= 0:
        return None
    return 100.0 * run.flops / (run.window_s * run.chips * run.peak["bf16_flops_per_s"])
