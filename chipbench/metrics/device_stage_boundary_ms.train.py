"""device_stage_boundary_ms.train: device self time of the program's
``stage_boundary`` scope (``scopes.py``) in the traced window, mean over
the cell's devices, per train step done in it, in ms: the ``ppermute``
that hands an activation to the next stage, with its layouts on either
side, forward and backward.  Moves ``train_tokens_per_s``."""
from chipbench import scopes


def read(run):
    return scopes.scope_ms(run, "stage_boundary")
