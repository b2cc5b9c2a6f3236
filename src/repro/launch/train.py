"""End-to-end training driver.

Runs on the host mesh built from the visible devices (one chip, a
four-chip host, or CPU virtual devices), or on the production mesh.  The
loop (data pipeline -> sharded train step -> metrics -> async
checkpoints) is the same for the reduced (``--smoke``) config, the full
config, and the full config cut to ``--layers N`` (published widths,
fewer layers: what fits one chip).

  PYTHONPATH=src python -m repro.launch.train --arch gpt-a --steps 200 \
      --batch 8 --seq 128 --smoke --ckpt-dir ckpt
  PYTHONPATH=src python -m repro.launch.train --arch gpt-a --layers 2 \
      --steps 4 --batch 1 --seq 1536      # GPT-A widths, one TPU v5e chip
  PYTHONPATH=src python -m repro.launch.train --arch zamba2-2.7b --smoke \
      --pipeline --steps 20        # cross-pod pipeline path (even device count)
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import load_config
from repro.ckpt.checkpoint import AsyncCheckpointer
from repro.data.pipeline import DataConfig, make_batches
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models.transformer import build_model
from repro.optim.optimizer import OptimizerConfig, init_opt_state, make_train_step
from repro.parallel.pipeline import make_pipeline_loss
from repro.parallel.sharding import make_batch_shardings, make_param_shardings


def print_memory_analysis(tag: str, compiled) -> None:
    """Per-device bytes of a compiled program, as its compiler counts them."""
    m = compiled.memory_analysis()
    gib = lambda b: f"{b / 2**30:.2f}"
    print(f"[{tag}] memory_analysis GiB: args={gib(m.argument_size_in_bytes)} "
          f"out={gib(m.output_size_in_bytes)} temp={gib(m.temp_size_in_bytes)} "
          f"alias={gib(m.alias_size_in_bytes)}", flush=True)


def main(argv=None):
    """Train; returns the per-step metrics (host floats), one dict a step."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU)")
    ap.add_argument("--layers", type=int, default=0,
                    help="replace the config's num_layers (depth cut)")
    ap.add_argument("--pipeline", action="store_true", help="PP over pod axis")
    ap.add_argument("--n-micro", type=int, default=4)
    ap.add_argument("--boundary", default="striped", choices=["striped", "direct"])
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = load_config(args.arch, smoke=args.smoke, layers=args.layers)
    model = build_model(cfg)
    if args.production_mesh:
        mesh = make_production_mesh(multi_pod=args.pipeline)
    else:
        mesh = make_host_mesh(multi_pod=args.pipeline)
    print(f"[train] arch={cfg.name} layers={cfg.num_layers} d_model={cfg.d_model} "
          f"d_ff={cfg.d_ff} vocab={cfg.vocab_size} batch={args.batch} seq={args.seq} "
          f"mesh={dict(mesh.shape)} params={cfg.param_count()/1e6:.1f}M", flush=True)

    opt_cfg = OptimizerConfig(peak_lr=args.lr, warmup_steps=min(20, args.steps // 5 + 1),
                              total_steps=args.steps)
    with jax.set_mesh(mesh):
        params = model.init(jax.random.PRNGKey(args.seed))
        p_sh = make_param_shardings(jax.eval_shape(lambda: params), mesh)
        params = jax.tree.map(lambda x, s: jax.device_put(x, s), params, p_sh)
        opt_state = init_opt_state(params)
        if args.pipeline:
            loss_fn = make_pipeline_loss(cfg, mesh, n_micro=args.n_micro,
                                         boundary=args.boundary)
            step_fn = jax.jit(make_train_step(loss_fn, opt_cfg, loss_has_metrics=False),
                              donate_argnums=(0, 1))
        else:
            step_fn = jax.jit(make_train_step(model.loss, opt_cfg), donate_argnums=(0, 1))

        ckpt = AsyncCheckpointer(args.ckpt_dir) if args.ckpt_dir else None
        data = make_batches(cfg, DataConfig(seed=args.seed, batch_size=args.batch,
                                            seq_len=args.seq), num_steps=args.steps)
        history = []
        t0 = time.time()
        tokens_done = 0
        for step, batch in enumerate(data):
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            b_sh = make_batch_shardings(jax.eval_shape(lambda: batch), mesh)
            batch = jax.tree.map(lambda x, s: jax.device_put(x, s), batch, b_sh)
            if step == 0:
                print_memory_analysis(
                    "train", step_fn.lower(params, opt_state, batch).compile())
            params, opt_state, metrics = step_fn(params, opt_state, batch)
            history.append(metrics)
            tokens_done += args.batch * args.seq
            if step % args.log_every == 0 or step == args.steps - 1:
                loss = float(metrics["loss"])
                dt = time.time() - t0
                print(f"step {step:5d} loss {loss:.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f} "
                      f"lr {float(metrics['lr']):.2e} "
                      f"tok/s {tokens_done/max(dt,1e-9):,.0f}", flush=True)
            if step == 0:  # step 0 compiled, and reading its loss synced: start tok/s here
                t0, tokens_done = time.time(), 0
            if ckpt and step and step % args.ckpt_every == 0:
                ckpt.save(step, {"params": params, "opt": opt_state},
                          {"step": step, "loss": float(metrics["loss"])})
        if ckpt:
            ckpt.save(args.steps, {"params": params, "opt": opt_state},
                      {"step": args.steps})
            ckpt.close()
            print(f"[train] checkpoint at {ckpt.latest_path()}")
    return [{k: float(v) for k, v in m.items()} for m in jax.device_get(history)]


if __name__ == "__main__":
    main()
