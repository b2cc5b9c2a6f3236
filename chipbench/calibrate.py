"""Readings that the limits in ``limits/<workload>.json`` are set from, on
the chip, at the cell's own size, in one process.

    python3 chipbench/calibrate.py --workload gpt-a-2l.train \
        --seeds 101,102,...,112 --control-seeds 101,102,103 --seconds 1

For every seed in ``--seeds`` it makes a whole run of the cell by its
kind's runner (``harness.runner_module``), with a short window, and
prints the compared numbers of the program: the lower readings.  For
every seed in ``--control-seeds`` it then reads the control and the
planted faults, through that runner's ``reference_readings``, against
the same float32 reference: the reference in float8
(``precision="fp8"``) in the program's place; half of each row's tokens
left out of the loss (the mean taken over the rest);
a state returned unchanged, which reads 1 on ``change_gap`` by
construction and needs no run.

Each line also says whether its numbers pass the committed limits
(``compare.checks``, as a run judges them): the program's have to, and
the control and each fault have to fail at least one.  Each limit lies
between the largest lower reading and the smallest upper one, as
``PERF.md`` records.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args()
    root = os.path.dirname(HERE)
    sys.path[:0] = [root, os.path.join(root, "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(root, ".jax_cache")
    import jax
    import numpy as np

    jax.config.update("jax_compilation_cache_dir", os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from chipbench import compare, harness

    seeds = [int(s) for s in args.seeds.split(",")]
    controls = [int(s) for s in args.control_seeds.split(",") if s]

    def emit(seed, side, numbers, **more):
        ok = compare.all_within(compare.checks(numbers, ctx.limits))
        print(json.dumps({"seed": seed, "side": side, **numbers, **more,
                          "within_limits": ok}), flush=True)

    for seed in seeds:
        ctx = harness.Context(args.workload, seed, args.seconds, False, time.perf_counter())
        cell = harness.runner_module(ctx.mix["kind"])
        run = cell.run(ctx)
        f32 = run.extra["reference"]
        emit(seed, "program", run.numbers, failed=run.failed, attempted=run.attempted,
             loss_gap=compare.loss_gap(run.extra["program"]["losses"], f32["losses"]))
        if seed not in controls:
            continue
        shapes = run.extra["shapes"]
        ctl = cell.reference_readings(ctx, shapes, "fp8")
        emit(seed, "control_fp8", compare.train_numbers(ctl, f32),
             loss_gap=compare.loss_gap(ctl["losses"], f32["losses"]))
        half = cell.reference_readings(ctx, shapes, "f32", token_share=0.5)
        emit(seed, "fault_half_tokens", compare.train_numbers(half, f32),
             loss_gap=compare.loss_gap(half["losses"], f32["losses"]))
        still = dict(run.extra["program"], change_norms=np.zeros_like(f32["change_norms"]))
        emit(seed, "fault_state_unchanged", compare.train_numbers(still, f32))
    return 0


if __name__ == "__main__":
    sys.exit(main())
