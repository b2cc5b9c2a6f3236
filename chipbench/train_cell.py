"""Runner of ``train`` mixes: a closed loop of the program's train step.

The step is built as ``launch/train.py`` builds it: ``make_train_step``
over ``model.loss`` on the host mesh or, where the mix holds
``"pipeline": {"microbatches": n, "boundary": "striped"|"direct"}``,
over ``make_pipeline_loss`` on the host's two-pod mesh; jitted with the
parameters and optimizer state donated.  Set-up makes the weights from
the seed, then drives that same step object through the mix's first
``check_steps`` steps with the feed the window uses; their losses, the first gradient (read from Adam's
first moment after one step) and the parameters' change after them are
the program's readings.  The window continues the same object, with at
most two steps in flight, and ends when the last step dispatched in it is
done.  Once the window has closed and the program's state is freed, the
plain reference that the configuration names follows the same first
steps from the same seed, in float32, on no pipeline, with its
parameters, moments and tokens placed on the program's mesh as the
program places its own.
"""
from __future__ import annotations

import collections
import time
from typing import Dict

import numpy as np

from chipbench import compare, traffic
from chipbench.harness import Context, Run, free_device_arrays, log, memory_peak, span
from chipbench.weights import Weights, block_norms, leaf_norms

OPT_KEYS = ("peak_lr", "min_lr_ratio", "warmup_steps", "total_steps", "b1", "b2",
            "eps", "weight_decay", "clip_norm")


def _abstract(tree, shardings=None):
    """``tree``'s shapes and dtypes with the shardings of its arrays, or
    with ``shardings``."""
    import jax

    if shardings is None:
        shardings = jax.tree.map(lambda x: x.sharding, tree)
    return jax.tree.map(lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh),
                        tree, shardings)


def make_mesh(ctx: Context):
    """The program's host mesh for the cell: two pods where the mix is
    pipelined, and as many devices as the cell has chips."""
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(multi_pod="pipeline" in ctx.mix)
    if mesh.size != ctx.chips:
        raise RuntimeError(f"mesh {dict(mesh.shape)} is not the cell's {ctx.chips} chips")
    return mesh


class Program:
    """The program's train step, its mesh and its feed for one seed."""

    def __init__(self, ctx: Context):
        import jax

        from repro.models.transformer import build_model
        from repro.optim.optimizer import OptimizerConfig, make_train_step
        from repro.parallel.pipeline import make_pipeline_loss
        from repro.parallel.sharding import make_batch_shardings, make_param_shardings

        self.ctx, self.mix = ctx, ctx.mix
        self.cfg = ctx.program_config()
        model = build_model(self.cfg)
        self.mesh = make_mesh(ctx)
        self.opt = OptimizerConfig(**{k: self.mix["optimizer"][k] for k in OPT_KEYS})
        pipe = self.mix.get("pipeline")
        if pipe:
            loss = make_pipeline_loss(self.cfg, self.mesh, n_micro=pipe["microbatches"],
                                      boundary=pipe["boundary"])
            step = make_train_step(loss, self.opt, loss_has_metrics=False)
        else:
            step = make_train_step(model.loss, self.opt)
        self.step = jax.jit(step, donate_argnums=(0, 1))
        with jax.set_mesh(self.mesh):
            self.shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
            self.weights = Weights(self.shapes, make_param_shardings(self.shapes, self.mesh))
        self._batch_shardings = make_batch_shardings
        self.feed = traffic.train_batches(self.mix, self.cfg.vocab_size, ctx.seed)

    def put(self, batch):
        import jax

        with span("batch"):
            sh = self._batch_shardings(jax.eval_shape(lambda: batch), self.mesh)
            return jax.tree.map(jax.device_put, batch, sh)

    def first_steps(self):
        """Set-up: weights, then the mix's first steps with their readings."""
        from repro.optim.optimizer import init_opt_state

        params = self.weights.make(self.ctx.seed)
        opt_state = init_opt_state(params)
        losses, grad_norms = [], None
        for s in range(self.mix["check_steps"]):
            params, opt_state, m = self.step(params, opt_state, self.put(next(self.feed)))
            losses.append(m["loss"])
            if s == 0:  # Adam's first moment after one step is (1 - b1) g
                grad_norms = np.asarray(leaf_norms(opt_state.mu)) / (1.0 - self.opt.b1)
                grad_blocks = np.asarray(block_norms(opt_state.mu)) / (1.0 - self.opt.b1)
        readings = {"losses": [float(x) for x in losses], "grad_norms": grad_norms,
                    "grad_blocks": grad_blocks,
                    "change_norms": self.weights.change_norms(params, self.ctx.seed)}
        self.window_state = _abstract((params, opt_state))
        return params, opt_state, readings

    def step_hlo(self) -> str:
        """The optimized HLO text of the step as the window calls it,
        compiled again from the shapes and shardings of the state that
        set-up hands to the window.  A step may return its state sharded
        otherwise than it took it (the pipelined step puts each stage's
        layers on its pod), so the window can run another program than
        the first step did."""
        import jax

        batch = jax.eval_shape(lambda: traffic.train_batch(self.mix, self.cfg.vocab_size, 0, 0))
        batch = _abstract(batch, self._batch_shardings(batch, self.mesh))
        with jax.set_mesh(self.mesh):
            return self.step.lower(*self.window_state, batch).compile().as_text()

    def window(self, params, opt_state, t_end: float):
        import jax

        inflight = collections.deque()
        steps = 0
        while True:
            batch = self.put(next(self.feed))
            with span("dispatch"):
                params, opt_state, m = self.step(params, opt_state, batch)
            inflight.append(m["loss"])
            steps += 1
            if len(inflight) > 1:
                with span("sync"):
                    inflight.popleft().block_until_ready()
            if time.perf_counter() >= t_end:
                break
        with span("sync"):
            jax.block_until_ready((params, opt_state))
        return params, opt_state, steps


def reference_readings(ctx: Context, shapes, precision: str = "f32",
                       token_share: float = 1.0) -> Dict[str, object]:
    """The plain reference through the mix's first steps from the seed:
    losses, the first clipped gradient's leaf norms, the change's leaf
    norms.  ``token_share`` < 1 leaves out the later positions of every
    row (a planted fault for calibration)."""
    import jax
    import jax.numpy as jnp

    from repro.parallel.sharding import make_batch_shardings, make_param_shardings

    ref = ctx.reference()
    mix, V = ctx.mix, ctx.sizes["vocab_size"]
    step = ref.make_train_step(ref.build(ctx.sizes, precision), mix["optimizer"])
    mesh = make_mesh(ctx)
    p_sh = make_param_shardings(shapes, mesh)
    weights = Weights(shapes, p_sh)
    p = weights.make(ctx.seed)
    zeros = jax.jit(ref.zeros_like_tree, out_shardings=p_sh)
    mu, nu = zeros(p), zeros(p)
    B, T = mix["batch"], mix["seq_len"]
    keep = int(round(token_share * (T - 1)))

    def put(x):
        return jax.device_put(x, make_batch_shardings(jax.eval_shape(lambda: x), mesh))

    mask = np.zeros((B, T - 1), np.float32)
    mask[:, :keep] = 1.0
    mask = put(mask)
    losses, first, blocks = [], None, None
    for s in range(mix["check_steps"]):
        tokens = put(traffic.train_batch(mix, V, ctx.seed, s)["tokens"])
        p, mu, nu, loss, gn = step(p, mu, nu, jnp.int32(s + 1), tokens, mask)
        losses.append(loss)
        if s == 0:  # as the program's: Adam's first moment is (1 - b1) g
            first = np.asarray(gn)
            blocks = np.asarray(block_norms(mu)) / (1.0 - mix["optimizer"]["b1"])
    out = {"losses": [float(x) for x in losses], "grad_norms": first, "grad_blocks": blocks,
           "change_norms": weights.change_norms(p, ctx.seed)}
    del p, mu, nu
    free_device_arrays()
    return out


def run(ctx: Context) -> Run:
    import jax

    prog = Program(ctx)
    mix = ctx.mix
    with jax.set_mesh(prog.mesh):
        params, opt_state, readings = prog.first_steps()
        t0 = ctx.window_begin()
        params, opt_state, steps = prog.window(params, opt_state, t0 + ctx.seconds)
        t1 = time.perf_counter()
        trace = ctx.window_end()
        mem = memory_peak(ctx.devices)
    log(f"set-up {t0 - ctx.t_start:.3f} s; window {t1 - t0:.3f} s, {steps} steps; "
        f"compiles in window: {ctx.compiles_between(t0, t1)}; "
        f"program readings {readings}")
    del params, opt_state
    free_device_arrays()
    shapes = prog.shapes
    t_ref = time.perf_counter()
    ref = reference_readings(ctx, shapes)
    log(f"reference {time.perf_counter() - t_ref:.3f} s; readings {ref}; loss_gap (not "
        f"compared) {compare.loss_gap(readings['losses'], ref['losses'])!r}")
    tokens = steps * mix["batch"] * mix["seq_len"]
    run = Run(kind="train", chips=ctx.chips, peak=ctx.peak, sizes=ctx.sizes, mix=mix,
              metrics={"train_tokens_per_s": (tokens / (t1 - t0), "tokens/s"),
                       "setup_s": (t0 - ctx.t_start, "s")},
              numbers=compare.train_numbers(readings, ref),
              attempted=steps, failed=0, memory_peak_bytes=mem, window_s=t1 - t0,
              flops=steps * ctx.flops().train_step_flops(ctx.sizes, mix["batch"], mix["seq_len"]),
              trace=trace, step_hlo=prog.step_hlo,
              extra={"program": readings, "reference": ref, "shapes": shapes})
    if trace is not None:
        from chipbench import reduce

        run.trace_window = reduce.window(trace)
    return run
