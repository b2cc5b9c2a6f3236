"""device_unscoped_ms.train: device self time of the ops that carry none
of the program's named scopes (``scopes.py``), or are missing from the
step's HLO, in the traced window, mean over the cell's devices, per train
step done in it, in ms.  Moves ``train_tokens_per_s``."""
from chipbench import scopes


def read(run):
    return scopes.scope_ms(run, "unscoped")
