"""The control at a size a CPU test holds: the float8 reference put in
the program's place fails the committed limits, judged as a run judges
them (``compare.checks``), while the program passes them.  The train
cell is cut to d_model 512, where the two stand as far apart as at
d_model 1024 (PERF.md gives the readings the limits were set from)."""
import time


import tiny
from chipbench import compare, harness, train_cell

SEED = 2**33 + 101
TRAIN_SIZES = {"num_layers": 2, "d_model": 512, "num_heads": 8, "num_kv_heads": 8,
               "head_dim": 64, "d_ff": 2048, "vocab_size": 8192}


def ctx_for(root, workload):
    return harness.Context(workload, SEED, 0.5, False, time.perf_counter(), root=root,
                           require_chip=False)


def passes(numbers, ctx):
    return compare.all_within(compare.checks(numbers, ctx.limits))


def test_train_control(tmp_path):
    root = tiny.write_root(str(tmp_path), sizes=TRAIN_SIZES, seq_len=256, batch=1)
    ctx = ctx_for(root, "gpt-a-2l.train")
    run = train_cell.run(ctx)
    shapes, f32 = run.extra["shapes"], run.extra["reference"]
    ctl = compare.train_numbers(train_cell.reference_readings(ctx, shapes, "fp8"), f32)
    assert passes(run.numbers, ctx), run.numbers
    assert not passes(ctl, ctx), ctl

