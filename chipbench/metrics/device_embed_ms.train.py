"""device_embed_ms.train: device self time of the program's ``embed``
scope (``scopes.py``) in the traced window, mean over the cell's
devices, per train step done in it, in ms.  Moves ``train_tokens_per_s``."""
from chipbench import scopes


def read(run):
    return scopes.scope_ms(run, "embed")
