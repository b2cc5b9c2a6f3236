"""Geo-distributed training, end to end:

1. Algorithm 1 picks the DC split for a 2-DC fleet (what-if, no hardware).
2. The discrete-event simulator compares Atlas vs Varuna/GPipe on it.
3. The REAL cross-pod pipeline (shard_map + ppermute over the `pod` axis,
   striped Atlas boundary) trains a reduced model on 8 emulated devices.

  PYTHONPATH=src python examples/geo_train.py
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp

from repro.configs import get_smoke_config
from repro.core import topology, wan
from repro.core.dc_selection import JobModel, algorithm1, best_plan
from repro.core.simulator import GeoTopology, simulate, testbed_spec
from repro.data.pipeline import DataConfig, make_batches
from repro.launch.mesh import make_host_mesh
from repro.models.transformer import build_model
from repro.optim.optimizer import OptimizerConfig, init_opt_state, make_train_step
from repro.parallel.pipeline import make_pipeline_loss


def main(steps: int = 30):
    # ---- 1) plan ----
    job = JobModel(
        t_fwd_ms=10.0,
        act_bytes=wan.activation_bytes(1, 4096, 4096),
        partition_param_bytes=412e6 * 2,
        microbatches=16,
    )
    plans = algorithm1(job, {"us-east": 240, "us-west": 240}, P=8)
    plan = best_plan(plans)
    print(f"[plan] best D={plan.D} partitions={plan.partitions} "
          f"throughput={plan.throughput:.4f} gpus={plan.gpus_used}")

    # ---- 2) simulate ----
    stage_dc = []
    for i, dc in enumerate(sorted(plan.partitions)):
        stage_dc += [i] * plan.partitions[dc]
    spec = testbed_spec(
        hidden=4096, seq_len=4096, micro_batch=1, layers_per_stage=1,
        layer_params=412e6, num_stages=len(stage_dc), microbatches=16,
        stage_dc=stage_dc,
    )
    for policy, mt, D in (("gpipe", False, 1), ("varuna", False, 1), ("atlas", True, 2)):
        r = simulate(spec, GeoTopology(wan_latency_ms=40, multi_tcp=mt),
                     policy=policy, n_pipelines=D, validate=True)
        print(f"[sim] {policy:7s} multi_tcp={mt}  iter={r.iteration_ms:8.0f}ms "
              f"util={r.utilization:.0%}")

    # ---- 2b) same job on a heterogeneous (skewed) WAN ----
    for name, topo in (("uniform", GeoTopology(wan_latency_ms=40)),
                       ("skewed", topology.skewed_3dc()),
                       ("azure", topology.azure_testbed())):
        r = simulate(spec, topo, policy="atlas", n_pipelines=2, validate=True)
        print(f"[sim] atlas on {name:8s} iter={r.iteration_ms:8.0f}ms "
              f"util={r.utilization:.0%}")

    # ---- 3) real cross-pod pipeline on emulated devices ----
    mesh = make_host_mesh(multi_pod=True)  # (pod=2, data=2, model=2)
    cfg = get_smoke_config("gpt_a")
    model = build_model(cfg)
    print(f"[pipeline] mesh={dict(mesh.shape)} arch={cfg.name} boundary=striped")
    with jax.set_mesh(mesh):
        params = model.init(jax.random.PRNGKey(0))
        loss_fn = make_pipeline_loss(cfg, mesh, n_micro=4, boundary="striped")
        step_fn = jax.jit(
            make_train_step(loss_fn, OptimizerConfig(peak_lr=3e-3, warmup_steps=5,
                                                     total_steps=steps),
                            loss_has_metrics=False),
            donate_argnums=(0, 1),
        )
        opt_state = init_opt_state(params)
        for i, b in enumerate(
            make_batches(cfg, DataConfig(batch_size=8, seq_len=64), num_steps=steps)
        ):
            params, opt_state, m = step_fn(
                params, opt_state, {k: jnp.asarray(v) for k, v in b.items()}
            )
            if i % 10 == 0 or i == steps - 1:
                print(f"[pipeline] step {i:3d} loss {float(m['loss']):.4f}")
    print("[pipeline] done — PP across pods, DP+TP inside (paper §4.2 layout)")


if __name__ == "__main__":
    main()
