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


def test_constrain_in_manual_region_names_no_manual_axis():
    """Inside a ``shard_map`` manual over ``pod``/``data``, ``constrain``
    leaves those axes out of its spec, so a ``custom_vjp`` whose forward
    constrains (as the loss head's does) traces again under ``jax.grad``
    after ``constraints_disabled`` has closed, still split over ``model``."""
    out = _run("""
import re
import warnings; warnings.filterwarnings("ignore")
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_host_mesh
from repro.parallel.sharding import constrain, constraints_disabled
mesh = make_host_mesh(multi_pod=True)


def sq_fwd(x):
    y = constrain(x, P("data", None, "model"))
    return jnp.sum(y * y), y


@jax.custom_vjp
def sq(x):
    return sq_fwd(x)[0]


sq.defvjp(sq_fwd, lambda y, g: (2 * g * y,))


def body(x):
    def step(tot, xi):
        with constraints_disabled():  # as the pipeline's stages
            return tot + sq(xi), None

    # a scan body is traced to a jaxpr first; differentiating it traces
    # sq_fwd again, outside constraints_disabled, as the rotation does
    tot, _ = jax.lax.scan(step, jnp.float32(0.0), x[None])
    return jax.lax.psum(tot, ("pod", "data"))


loss = jax.shard_map(body, mesh=mesh, in_specs=P("pod"), out_specs=P(),
                     axis_names={"pod", "data"}, check_vma=False)
x = jnp.linspace(-1.0, 1.0, 4 * 2 * 8).reshape(4, 2, 8)
with jax.set_mesh(mesh):
    g = jax.jit(jax.grad(loss))(x)
    jaxpr = str(jax.make_jaxpr(jax.grad(loss))(x))
specs = re.findall(r"sharding_constraint\\[.*?spec=PartitionSpec\\(([^)]*)\\)", jaxpr, re.S)
assert jnp.allclose(g, 2 * x), g
assert specs and all(s == "None, None, 'model'" for s in specs), specs
print("OK", specs)
""", devices=4)
    assert "OK" in out


def test_pipeline_train_step_on_four_devices():
    """The pipelined ``make_train_step`` on ``(pod 2, data 1, model 2)``,
    both boundaries, against the plain step on one device: loss, gradient
    norm, first gradient (Adam's first moment) and updated parameters. It
    hands the state back in the layout it was placed in, with each stage's
    layers on its pod, so two steps compile one program."""
    out = _run(PREAMBLE + """
from jax.sharding import PartitionSpec as P
from repro.optim.optimizer import OptimizerConfig, init_opt_state, make_train_step
from repro.parallel.sharding import make_batch_shardings, make_param_shardings
cfg = get_smoke_config("gpt_a")
m = build_model(cfg)
opt = OptimizerConfig(peak_lr=1e-3, warmup_steps=1, total_steps=10)
batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab_size)}
p0 = jax.device_get(m.init(jax.random.PRNGKey(0)))
p_ref, o_ref, m_ref = jax.device_get(jax.jit(make_train_step(m.loss, opt))(
    p0, init_opt_state(p0), batch))


def gap(a, b, base):
    sq = lambda t: sum(float(jnp.sum(jnp.square(x))) for x in jax.tree.leaves(t))
    return (sq(jax.tree.map(jnp.subtract, a, b)) / sq(base)) ** 0.5


for boundary in ("striped", "direct"):
    with jax.set_mesh(mesh):
        sh = make_param_shardings(jax.eval_shape(lambda: p0), mesh)
        assert sh["layers"]["ffn"]["w_up"].spec == P("pod", None, "model"), sh
        params = jax.device_put(p0, sh)
        opt_state = init_opt_state(params)
        b = jax.device_put(batch, make_batch_shardings(jax.eval_shape(lambda: batch), mesh))
        loss = make_pipeline_loss(cfg, mesh, n_micro=4, boundary=boundary)
        step = jax.jit(make_train_step(loss, opt, loss_has_metrics=False), donate_argnums=(0, 1))
        params, opt_state, met = step(params, opt_state, b)
        p1, mu1 = jax.device_get((params, opt_state.mu))
        params, opt_state, _ = step(params, opt_state, b)
        kept = jax.tree.map(lambda x, s: x.sharding.is_equivalent_to(s, x.ndim),
                            (params, opt_state.mu, opt_state.nu), (sh, sh, sh))
    assert all(jax.tree.leaves(kept)), kept
    assert step._cache_size() == 1, step._cache_size()
    # bf16 compute in both, summed in another order: measured 1.2e-5
    assert abs(float(met["loss"]) - float(m_ref["loss"])) < 1e-3
    # measured 3.4e-6 relative
    assert abs(float(met["grad_norm"]) / float(m_ref["grad_norm"]) - 1) < 1e-3
    # the first gradient, leaf by leaf: measured 9.4e-3 of its norm
    assert gap(mu1, o_ref.mu, o_ref.mu) < 3e-2
    # Adam's first update is g / |g| elementwise: where the two bf16
    # gradients disagree on the sign of a near-zero element the update
    # differs by 2 lr; measured 0.105 of the update's norm, and 1.0 with
    # the activation between the stages zeroed (loss then off by 4.6e-2)
    assert gap(p1, p_ref, jax.tree.map(jnp.subtract, p_ref, p0)) < 0.25
print("OK")
""", devices=4)
    assert "OK" in out


def test_pipeline_scopes_reach_the_compiled_step():
    """The pipelined train step's optimized HLO on ``(pod 2, data 1,
    model 2)``: every ``collective-permute`` is innermost in
    ``stage_boundary``, the ``pod`` reduction of the cotangents that leave
    the stages is in ``pipeline``, and the token gather is in ``embed``."""
    out = _run(PREAMBLE + """
import re
from repro.optim.optimizer import OptimizerConfig, init_opt_state, make_train_step
from repro.parallel.sharding import make_batch_shardings, make_param_shardings
from repro.scopes import ALL
cfg = get_smoke_config("gpt_a")
m = build_model(cfg)
with jax.set_mesh(mesh):
    shapes = jax.eval_shape(m.init, jax.random.PRNGKey(0))
    sds = lambda t, sh: jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s), t, sh)
    params = sds(shapes, make_param_shardings(shapes, mesh))
    batch = {"tokens": jax.ShapeDtypeStruct((4, 32), jnp.int32)}
    batch = sds(batch, make_batch_shardings(batch, mesh))
    loss = make_pipeline_loss(cfg, mesh, n_micro=4, boundary="striped")
    step = jax.jit(make_train_step(loss, OptimizerConfig(), loss_has_metrics=False))
    text = step.lower(params, jax.eval_shape(init_opt_state, params), batch).compile().as_text()
# an op's type is a shape or a tuple of them (an all-reduce of several)
ops = re.findall(r"= (?:\\((?:[^()]|\\([^()]*\\))*\\)|\\S+) ([\\w\\-]+)\\(.*op_name=\\"([^\\"]*)\\"", text)
innermost = lambda path: next(
    (t for t in reversed(re.split(r"[/(),]", path)) if t in ALL), None)
permutes = [p for op, p in ops if op.startswith("collective-permute")]
assert permutes and all(innermost(p) == "stage_boundary" for p in permutes), permutes
pod_sums = [p for op, p in ops if op.startswith("all-reduce") and innermost(p) == "pipeline"]
assert any(p.endswith("shard_map/psum") for p in pod_sums), pod_sums
gathers = [p for op, p in ops if op == "gather"]
assert gathers and all(innermost(p) == "embed" for p in gathers), gathers
print("OK", len(permutes), len(pod_sums))
""", devices=4)
    assert "OK" in out


def test_dryrun_pipelined_train_step_keeps_its_fsdp_layout():
    """The multi-pod train dry run places parameters and moments split
    over ``data`` as well (FSDP); its pipelined step, compiled ahead of
    time on ``(pod 2, data 2, model 2)`` at smoke size with a d_ff wide
    enough for FSDP to split the FFN, hands them back in that layout. The
    pipelined loss given that layout lays its gradients out in it."""
    out = _run("""
import dataclasses
import warnings; warnings.filterwarnings("ignore")
import jax
from repro.configs import get_smoke_config
from repro.launch import dryrun, shapes as shp
from repro.launch.mesh import make_host_mesh
from repro.parallel.pipeline import make_pipeline_loss
dryrun.make_production_mesh = lambda multi_pod: make_host_mesh(multi_pod=multi_pod)
shp.config_for = lambda arch, shape: dataclasses.replace(get_smoke_config(arch), d_ff=4096)
shp.SHAPES["train_4k"] = dict(shp.SHAPES["train_4k"], seq_len=32, global_batch=8)
mesh, fn, args, cfg = dryrun.build_lowerable("gpt_a", "train_4k", multi_pod=True)
assert dict(mesh.shape) == {"pod": 2, "data": 2, "model": 2}, mesh.shape
params, opt_state, _ = args
placed = jax.tree.map(lambda s: s.sharding, (params, opt_state.mu, opt_state.nu))
assert any("data" in str(s.spec) for s in jax.tree.leaves(placed)), placed
with jax.set_mesh(mesh):
    compiled = fn.lower(*args).compile()
p_out, o_out, _ = compiled.output_shardings
kept = jax.tree.map(lambda a, b, x: a.is_equivalent_to(b, len(x.shape)),
                    placed, (p_out, o_out.mu, o_out.nu), (params, params, params))
assert all(jax.tree.leaves(kept)), kept
with jax.set_mesh(mesh):
    loss = make_pipeline_loss(cfg, mesh, fsdp=True)
    g_out = jax.jit(jax.grad(loss)).lower(params, args[2]).compile().output_shardings
kept = jax.tree.map(lambda a, b, x: a.is_equivalent_to(b, len(x.shape)), placed[0], g_out, params)
assert all(jax.tree.leaves(kept)), kept
print("OK")
""")
    assert "OK" in out


@pytest.mark.parametrize("arch", ["gpt_a", "zamba2_2p7b"])
def test_only_what_the_layers_read_is_reduced_over_pod(arch):
    """The pipelined train step on ``(pod 2, data 1, model 2)``, compiled
    at smoke size with a vocabulary no other width shares: no ``pod``
    reduction, and no all-reduce innermost in ``pipeline``, carries an
    operand as long as the vocabulary (or its ``model`` split), and every
    ``pod`` reduction of a parameter-shaped operand is the gradient of a
    parameter the layers read, outside ``io_keys``: none for GPT-A,
    ``shared_attn`` for zamba2."""
    out = _run(PREAMBLE + f"ARCH = {arch!r}" + r"""
import dataclasses, re
from repro.launch.dryrun import _crosses_pod
from repro.models.transformer import build_pipeline_parts
from repro.optim.optimizer import OptimizerConfig, init_opt_state, make_train_step
from repro.parallel.sharding import make_batch_shardings, make_param_shardings
from repro.scopes import ALL
cfg = dataclasses.replace(get_smoke_config(ARCH), vocab_size=640)
m = build_model(cfg)
parts = build_pipeline_parts(cfg)
with jax.set_mesh(mesh):
    shapes = jax.eval_shape(m.init, jax.random.PRNGKey(0))
    sh = make_param_shardings(shapes, mesh)
    sds = lambda t, s: jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s), t, s)
    params = sds(shapes, sh)
    batch = {"tokens": jax.ShapeDtypeStruct((4, 32), jnp.int32)}
    batch = sds(batch, make_batch_shardings(batch, mesh))
    loss = make_pipeline_loss(cfg, mesh, n_micro=4, boundary="striped")
    step = jax.jit(make_train_step(loss, OptimizerConfig(), loss_has_metrics=False))
    text = step.lower(params, jax.eval_shape(init_opt_state, params), batch).compile().as_text()
local = {k: {tuple(s.shard_shape(x.shape)) for x, s in
              zip(jax.tree.leaves(shapes[k]), jax.tree.leaves(sh[k]))} for k in shapes}
shared = set().union(*(local[k] for k in local
                       if k != parts.layer_key and k not in parts.io_keys))
param_shaped = set().union(*local.values()) - {()}
vocab = {cfg.vocab_size, cfg.vocab_size // mesh.shape["model"]}
per_pod = mesh.shape["data"] * mesh.shape["model"]  # device ids are pod-major
innermost = lambda path: next(
    (t for t in reversed(re.split(r"[/(),]", path)) if t in ALL), None)
pod_shapes, pipeline_shapes = [], []
for line in text.splitlines():
    r = re.match(r"\s*%\S+ = (.+?) all-reduce(-start)?\(", line)
    if not r:
        continue
    dims = [tuple(int(d) for d in s.split(",") if d)
            for s in re.findall(r"[a-z][a-z0-9]*\[([0-9,]*)\]", r.group(1))]
    if _crosses_pod(line, per_pod):
        pod_shapes += dims
    if innermost(re.search(r'op_name="([^"]*)"', line).group(1)) == "pipeline":
        pipeline_shapes += dims
assert pod_shapes, "no pod reduction found"
assert not [s for s in pod_shapes + pipeline_shapes if vocab & set(s)], (pod_shapes, pipeline_shapes)
reduced = {s for s in pod_shapes if s in param_shaped}
assert reduced <= shared, (reduced, shared)
assert bool(reduced) == bool(shared), (reduced, shared)
print("OK", sorted(reduced))
""", devices=4)
    assert "OK" in out


def test_pipelined_loss_weighs_each_microbatch_alike():
    """With a mask that differs between microbatches, the pipelined loss is
    the mean of each microbatch's plain ``model.loss``, not the masked mean
    over all their tokens (which the sharpened head sets well apart)."""
    out = _run(PREAMBLE + """
cfg = get_smoke_config("gpt_a")
m = build_model(cfg)
params = m.init(jax.random.PRNGKey(0))
# a sharp head spreads the tokens' CE, so the two weightings differ
params["lm_head"] = params["lm_head"] * 16
tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab_size)
labels = jnp.pad(tokens[:, 1:], ((0, 0), (0, 1)))
lengths = jnp.array([31, 17, 6, 2])  # one row a microbatch
mask = (jnp.arange(32)[None] < lengths[:, None]).astype(jnp.float32)
batch = {"tokens": tokens, "labels": labels, "mask": mask}
plain = jax.jit(lambda p, b: m.loss(p, b)[0])
ref = sum(float(plain(params, {k: v[i:i + 1] for k, v in batch.items()}))
          for i in range(4)) / 4
pooled = float(plain(params, batch))
with jax.set_mesh(mesh):
    s = float(jax.jit(make_pipeline_loss(cfg, mesh, n_micro=4))(params, batch))
# bf16 compute in both, summed in another order (as the train step test)
tol = 1e-3 * abs(ref)
assert abs(s - ref) < tol, (s, ref, pooled)
assert abs(pooled - ref) > 10 * tol, (pooled, ref)
print("OK", s, ref, pooled)
""", devices=4)
    assert "OK" in out


@pytest.mark.parametrize("groups,crosses", [
    ("replica_groups={{0,2},{1,3}}", True),
    ("replica_groups={{0,1},{2,3}}", False),
    ("replica_groups={{0},{1},{2},{3}}", False),
    ("replica_groups=[2,2]<=[4]", False),
    ("replica_groups=[2,2]<=[2,2]T(1,0)", True),
    ("replica_groups=[1,4]<=[4]", True),
    ("source_target_pairs={{0,2},{1,3}}", True),
    ("source_target_pairs={{0,0},{2,1},{1,2},{3,3}}", True),
])
def test_crosses_pod_reads_every_form_of_device_groups(groups, crosses):
    """``collective_bytes`` counts a collective as inter-pod (``dcn``) by
    its device groups on ``(pod 2, data 1, model 2)``, pod-major: the
    transposed iota form ``[2,2]<=[2,2]T(1,0)`` is the groups {0,2} and
    {1,3}, across the pods."""
    from repro.launch.dryrun import _crosses_pod

    line = f"%ar = f32[8]{{0}} all-reduce(f32[8]{{0}} %x), {groups}, to_apply=%add"
    assert _crosses_pod(line, pod_stride=2) == crosses
