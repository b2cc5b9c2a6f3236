"""device_pipeline_ms.train: device self time of the program's
``pipeline`` scope (``scopes.py``) in the traced window, mean over the
cell's devices, per train step done in it, in ms: the fill-drain
rotation, the masking of clipped microbatches and the ``pod`` reductions
of the gradients of the parameters every stage holds, less the layer
scopes and ``stage_boundary`` inside it.  Moves ``train_tokens_per_s``."""
from chipbench import scopes


def read(run):
    return scopes.scope_ms(run, "pipeline")
