"""Plain float32 references, one module per architecture family, named by
the ``reference`` key of a configuration file.  Each exposes
``build(sizes, precision)``, the model of the file's sizes in ``"f32"``
or in the float8 control (``"fp8"``), ``make_train_step(model,
optimizer)`` and ``zeros_like_tree(params)``.  They import nothing of the
program under test."""
