"""device_idle.train: share of the traced window in which no op runs on a
device (1 - union of its op intervals over the window), mean over the
cell's devices, in percent.  Moves ``train_tokens_per_s``."""
from chipbench import reduce


def read(run):
    if run.kind != "train" or run.trace is None or run.trace_window is None:
        return None
    share = reduce.idle_share(run.trace, *run.trace_window)
    return None if share is None else 100.0 * share
