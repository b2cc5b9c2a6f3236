"""Names of the ``jax.named_scope``s on the train path.

A scope reaches every instruction of the optimized HLO as part of its
``op_name`` metadata, in the forward, the remat recompute and the
backward (``transpose(jvp(...))``) alike, so a device trace of the step
can be split by layer.  Scopes are metadata only: the compiled program is
the same with or without them.

- ``embed``: token gather and cast; its backward scatter-adds into the
  embedding's gradient.
- ``attn``: pre-norm, attention and its residual add.
- ``ffn``: pre-norm, FFN or MoE layer and its residual add.
- ``loss_head``: the chunked head matmul and cross-entropy.
- ``optimizer``: the AdamW update (norm, clip, moments, decay, write).
"""

EMBED, ATTN, FFN, LOSS_HEAD, OPTIMIZER = SCOPES = (
    "embed", "attn", "ffn", "loss_head", "optimizer")
