"""Cross-pod pipeline tests — run in a subprocess with 8 (or 4) fake
devices (jax locks the device count at first init, so the main pytest
process cannot host these)."""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=900,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


PREAMBLE = """
import warnings; warnings.filterwarnings("ignore")
import jax, jax.numpy as jnp
from repro.configs import get_smoke_config
from repro.models.transformer import build_model
from repro.parallel.pipeline import make_pipeline_loss
from repro.launch.mesh import make_host_mesh
mesh = make_host_mesh(multi_pod=True)
"""


@pytest.mark.slow  # compile-heavy: excluded from tier-1
@pytest.mark.parametrize("arch", ["minitron_4b", "zamba2_2p7b", "deepseek_v2_lite_16b"])
def test_pipeline_matches_reference_loss(arch):
    out = _run(PREAMBLE + f"""
cfg = get_smoke_config("{arch}")
m = build_model(cfg)
params = m.init(jax.random.PRNGKey(0))
batch = {{"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab_size)}}
ref, _ = jax.jit(m.loss)(params, batch)
with jax.set_mesh(mesh):
    s = jax.jit(make_pipeline_loss(cfg, mesh, n_micro=4, boundary="striped"))(params, batch)
    d = jax.jit(make_pipeline_loss(cfg, mesh, n_micro=4, boundary="direct"))(params, batch)
assert abs(float(s) - float(ref)) < 3e-2, (float(s), float(ref))
assert abs(float(s) - float(d)) < 3e-2
print("OK", float(s), float(ref))
""")
    assert "OK" in out


@pytest.mark.slow  # compile-heavy: excluded from tier-1
def test_pipeline_gradients_match_reference():
    out = _run(PREAMBLE + """
cfg = get_smoke_config("minitron_4b")
m = build_model(cfg)
params = m.init(jax.random.PRNGKey(0))
batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab_size)}
with jax.set_mesh(mesh):
    g = jax.jit(jax.grad(make_pipeline_loss(cfg, mesh, n_micro=4)))(params, batch)
g0 = jax.jit(jax.grad(lambda p, b: m.loss(p, b)[0]))(params, batch)
num = sum(float(jnp.sum(jnp.abs(a.astype(jnp.float32)-b.astype(jnp.float32)))) for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(g0)))
den = sum(float(jnp.sum(jnp.abs(b.astype(jnp.float32)))) for b in jax.tree.leaves(g0))
assert num / den < 0.05, num / den
print("OK", num / den)
""")
    assert "OK" in out


@pytest.mark.slow  # compile-heavy: excluded from tier-1
def test_striped_boundary_dcn_bytes():
    """Atlas striping never sends MORE inter-pod bytes than the direct
    boundary — and (EXPERIMENTS.md §Perf B) XLA's partitioner performs
    the striping automatically, so the two often lower identically:
    the paper's transport insight is native to GSPMD."""
    out = _run(PREAMBLE + """
from repro.launch.dryrun import collective_bytes
cfg = get_smoke_config("minitron_4b")
m = build_model(cfg)
params_sds = jax.eval_shape(m.init, jax.random.PRNGKey(0))
batch = {"tokens": jax.ShapeDtypeStruct((8, 32), jnp.int32)}
res = {}
with jax.set_mesh(mesh):
    for mode in ("striped", "direct"):
        lf = make_pipeline_loss(cfg, mesh, n_micro=4, boundary=mode)
        compiled = jax.jit(lf).lower(params_sds, batch).compile()
        res[mode] = collective_bytes(compiled.as_text(), pod_stride=4)
print("striped", res["striped"]["dcn"], "direct", res["direct"]["dcn"])
assert res["striped"]["dcn"] > 0, res  # pod boundary is exercised
assert res["striped"]["dcn"] <= res["direct"]["dcn"], res
""")
    assert "striped" in out


@pytest.mark.slow  # compile-heavy: excluded from tier-1
def test_identity_padding_is_exact():
    """27-layer (deepseek) and 9-group (zamba2) stacks pad to uniform
    stages without changing the function (checked vs reference loss)."""
    out = _run(PREAMBLE + """
from repro.parallel.pipeline import pad_layer_stack, padded_num_layers
assert padded_num_layers(27, 2) == 28
assert padded_num_layers(9, 2) == 10
for arch in ("deepseek_v2_lite_16b", "zamba2_2p7b"):
    cfg = get_smoke_config(arch)
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab_size)}
    ref, _ = jax.jit(m.loss)(params, batch)
    with jax.set_mesh(mesh):
        s = jax.jit(make_pipeline_loss(cfg, mesh, n_micro=4))(params, batch)
    assert abs(float(s) - float(ref)) < 3e-2, (arch, float(s), float(ref))
print("OK")
""")
    assert "OK" in out


@pytest.mark.slow  # compile-heavy: excluded from tier-1
def test_dryrun_smoke_combo_on_host_mesh():
    """A miniature dry-run (host-mesh sized) proves the lowering path."""
    out = _run("""
import warnings; warnings.filterwarnings("ignore")
import jax, jax.numpy as jnp
from repro.launch.dryrun import collective_bytes, _DTYPE_BYTES, _shape_bytes
# parser unit checks
assert _shape_bytes("bf16[4,8]") == 64
assert _shape_bytes("f32[2,2]") == 16
hlo = '''
  %ar = f32[16,16]{1,0} all-reduce(f32[16,16]{1,0} %x), replica_groups={{0,1},{2,3}}
  %cp = bf16[8]{0} collective-permute(bf16[8]{0} %y), source_target_pairs={{0,4},{1,5}}
'''
c = collective_bytes(hlo, pod_stride=4)
assert c["by_op"]["all-reduce"] == 16*16*4*2
assert c["dcn"] == 16, c  # the permute crosses the pod stride
print("OK")
""")
    assert "OK" in out


def test_pipeline_matches_reference_on_four_devices():
    """Tier-1 guard for the four-chip layout (pod=2, data=1, model=2):
    both boundaries give the plain model's loss."""
    out = _run(PREAMBLE + """
assert dict(mesh.shape) == {"pod": 2, "data": 1, "model": 2}, mesh.shape
cfg = get_smoke_config("gpt_a")
m = build_model(cfg)
params = m.init(jax.random.PRNGKey(0))
batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab_size)}
ref, _ = jax.jit(m.loss)(params, batch)
with jax.set_mesh(mesh):
    s = jax.jit(make_pipeline_loss(cfg, mesh, n_micro=4, boundary="striped"))(params, batch)
    d = jax.jit(make_pipeline_loss(cfg, mesh, n_micro=4, boundary="direct"))(params, batch)
assert abs(float(s) - float(ref)) < 3e-2, (float(s), float(ref))
assert abs(float(d) - float(ref)) < 3e-2, (float(d), float(ref))
print("OK", float(s), float(d), float(ref))
""", devices=4)
    assert "OK" in out
