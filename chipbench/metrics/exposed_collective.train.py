"""exposed_collective.train: share of the traced window in which a
collective runs on a device and no other op does (``reduce.py``), mean
over the cell's devices, in percent.  Moves ``train_tokens_per_s``."""
from chipbench import reduce


def read(run):
    if run.kind != "train" or run.trace is None or run.trace_window is None:
        return None
    share = reduce.exposed_collective_share(run.trace, *run.trace_window)
    return None if share is None else 100.0 * share
