"""The train path's named scopes reach the compiled GPT-A train step.

The step is compiled once, on the CPU, at the benchmark's test widths.
Each scope must name instructions of the optimized HLO in every pass it
takes part in (forward ``jvp``, remat recompute, backward ``transpose``);
every matmul must carry a layer's scope; the AdamW update that writes the
parameters and both moments must carry ``optimizer``.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest

from chipbench.tests.tiny import SIZES
from repro.configs import load_config
from repro.models.transformer import build_model
from repro.optim.optimizer import OptimizerConfig, init_opt_state, make_train_step
from repro.scopes import ATTN, EMBED, FFN, LOSS_HEAD, OPTIMIZER, SCOPES

# the passes in which each scope has instructions
PASSES = {EMBED: ("jvp", "transpose("), ATTN: ("jvp", "rematted_computation", "transpose("),
          FFN: ("jvp", "rematted_computation", "transpose("),
          LOSS_HEAD: ("jvp", "transpose("), OPTIMIZER: ("/optimizer/",)}
LAYER_SCOPES = {EMBED, ATTN, FFN, LOSS_HEAD}
INSTRUCTION = re.compile(r"^\s*(ROOT\s+)?%([\w.\-]+) = .*? ([\w\-]+)\((.*)$")
OP_NAME = re.compile(r'op_name="([^"]*)"')


def innermost(path):
    return next((t for t in reversed(re.split(r"[/(),]", path)) if t in SCOPES), None)


@pytest.fixture(scope="module")
def step_hlo():
    """{instruction: (opcode, op_name path)} and the entry's result operands."""
    cfg = dataclasses.replace(load_config("gpt-a"), **SIZES, dtype=jnp.bfloat16,
                              param_dtype=jnp.float32)
    model = build_model(cfg)
    step = jax.jit(make_train_step(model.loss, OptimizerConfig()), donate_argnums=(0, 1))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    batch = {"tokens": jax.ShapeDtypeStruct((2, 64), jnp.int32)}
    text = step.lower(params, jax.eval_shape(init_opt_state, params), batch).compile().as_text()
    ops, root, entry = {}, None, False
    for line in text.splitlines():
        entry = entry or line.startswith("ENTRY")
        m = INSTRUCTION.match(line)
        if m:
            path = OP_NAME.search(line)
            ops[m.group(2)] = (m.group(3), path.group(1) if path else "")
            if entry and m.group(1):
                root = re.findall(r"%([\w.\-]+)", m.group(4))
    return ops, root, len(jax.tree.leaves(params))


@pytest.mark.parametrize("scope", SCOPES)
def test_scope_reaches_the_compiled_step(step_hlo, scope):
    ops, root, n_params = step_hlo
    paths = [p for _, p in ops.values() if innermost(p) == scope]
    assert paths, f"no instruction carries {scope!r}"
    for part in PASSES[scope]:
        assert any(part in p for p in paths), f"{scope!r} has nothing in {part!r}"

    dots = [p for op, p in ops.values() if op in ("dot", "convolution")]
    assert dots and all(innermost(p) in LAYER_SCOPES for p in dots), \
        [p for p in dots if innermost(p) not in LAYER_SCOPES]
    if scope in (ATTN, FFN, LOSS_HEAD):  # its matmuls in every pass
        for part in PASSES[scope]:
            assert any(part in p and innermost(p) == scope for p in dots), (scope, part)
    if scope == OPTIMIZER:  # new parameters, then step, mu and nu, then the metrics
        updated = root[:n_params] + root[n_params + 1: 3 * n_params + 1]
        assert len(updated) == 3 * n_params
        assert all(innermost(ops[name][1]) == OPTIMIZER for name in updated), \
            [(name, ops[name]) for name in updated if innermost(ops[name][1]) != OPTIMIZER]
