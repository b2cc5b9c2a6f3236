"""Runs one cell of a benchmark root on four virtual CPU devices and
prints its result line as the last line of stdout; the tests of the
pipelined train path start it as a process of its own, since JAX fixes
the number of devices when it starts.

    python four_devices.py <root> [--fault exchange] <run.py arguments>
    python four_devices.py <root> --map <workload>

The program's ``constrain`` (``repro/parallel/sharding.py``) names the
pipeline's manual ``data`` axis when the loss head's custom VJP traces
its forward inside the pipeline's ``shard_map``, and the pipelined train
step then fails to trace (PERF.md, Open questions).  This script puts in
the repair that the program needs, leaving a mesh's manual axes out of a
spec before it is fitted, so that the harness's pipelined path can be
tested end to end; where the program carries the repair, it changes
nothing.  ``--fault exchange`` plants a fault in the timed path: the
activation sent between stages arrives as zeros.  ``--map`` prints
whether the op map of ``train_cell.Program.step_hlo`` is the one of the
step compiled from the real state that set-up hands to the window.
"""
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))


def _leave_out_manual_axes(sharding):
    from jax.sharding import AxisType, PartitionSpec as P

    fit = sharding._fit_spec

    def fit_auto(shape, spec, mesh):
        manual = {n for n, t in zip(mesh.axis_names, mesh.axis_types) if t == AxisType.Manual}

        def keep(entry):
            names = entry if isinstance(entry, tuple) else (entry,)
            left = tuple(n for n in names if n is not None and n not in manual)
            return (left if len(left) > 1 else left[0]) if left else None

        return fit(shape, P(*(keep(e) for e in spec)), mesh)

    sharding._fit_spec = fit_auto


def _drop_the_exchange():
    import jax
    import jax.numpy as jnp

    jax.lax.ppermute = lambda x, axis_name, perm: jnp.zeros_like(x)


def same_map(root, workload):
    import jax

    from chipbench import harness, scopes, train_cell

    ctx = harness.Context(workload, 1, 1.0, False, time.perf_counter(), root=root,
                          require_chip=False)
    prog = train_cell.Program(ctx)
    with jax.set_mesh(prog.mesh):
        params, opt_state, _ = prog.first_steps()
        real = prog.step.lower(params, opt_state, prog.put(next(prog.feed))).compile()
    return scopes.op_scopes(real.as_text()) == scopes.op_scopes(prog.step_hlo())


def main(argv):
    root, argv = argv[0], argv[1:]
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path[:0] = [REPO, os.path.join(REPO, "src")]
    from repro.parallel import sharding

    _leave_out_manual_axes(sharding)
    if argv[0] == "--map":
        print(json.dumps({"same_map": same_map(root, argv[1])}), flush=True)
        return 0
    if argv[:2] == ["--fault", "exchange"]:
        _drop_the_exchange()
        argv = argv[2:]
    from chipbench import harness

    line = harness.run_cell(argv, time.perf_counter(), root=root, require_chip=False)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
