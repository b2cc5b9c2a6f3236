"""The harness end to end without a chip, at a size a CPU test holds:
every cell of BENCHMARK.json loads and runs by name; a new cell, mix,
configuration, reference, FLOP count and metric are new files plus new
entries, shown with a pipelined four-chip cell on four virtual devices;
and a timed path broken underneath makes ``correct`` come out false."""
import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

import tiny
from chipbench import harness, traffic
from chipbench.harness import BENCH_DIR, REPO_ROOT

SEED = 2**35 + 17


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.write_root(str(tmp_path_factory.mktemp("bench")))


def run(root, workload, trace=0, seconds=1.0, seed=SEED):
    argv = ["--workload", workload, "--seed", str(seed), "--seconds", str(seconds),
            "--trace", str(trace)]
    return harness.run_cell(argv, time.perf_counter(), root=root, require_chip=False)


def committed():
    with open(os.path.join(REPO_ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


WORKLOADS = [w["name"] for w in committed()["workloads"]]


@pytest.mark.parametrize("workload", WORKLOADS)
def test_every_cell_loads_by_name(workload):
    ctx = harness.Context(workload, 1, 1.0, False, 0.0, require_chip=False)
    cfg = ctx.program_config()
    sizes = {k: v for k, v in ctx.sizes.items() if isinstance(v, int) and hasattr(cfg, k)}
    assert all(getattr(cfg, k) == v for k, v in sizes.items())
    assert callable(harness.runner(ctx.mix["kind"]))
    assert callable(ctx.reference().build) and callable(ctx.flops().train_step_flops)
    assert set(ctx.limits) == {"grad_gap", "grad_block_gap", "change_gap"}
    for m in ctx.bench["per_layer"]:
        assert callable(harness._load_reader(BENCH_DIR, m["name"]))


@pytest.mark.parametrize("workload", WORKLOADS)
def test_cell_runs_and_is_correct(root, workload):
    line = run(root, workload)
    assert line["correct"], line["checks"]
    names = {m["name"] for m in harness.cell_end_to_end(committed(), workload)}
    assert set(line["metrics"]) == names
    assert list(line)[-1] == "checks"
    assert line["metrics"]["setup_s"]["value"] > 0


def test_traced_run_reports_per_layer_metrics(root):
    line = run(root, "gpt-a-2l.train", trace=1)
    assert "mfu.train" in line["metrics"]
    assert "setup_s" not in line["metrics"]
    assert {"busy_s", "window_s"} <= set(line["device"])


FOUR_DEVICES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "four_devices.py")
PIPELINED = "gpt-a-4l-pp2tp2.train"
TINY_FLOPS = '''"""A new family's FLOP count, as a test states it: twice the dense one."""
from chipbench import flops


def train_step_flops(sizes, batch, seq_len):
    return 2 * flops.train_step_flops(sizes, batch, seq_len)
'''
TINY_REFERENCE = '''"""A new family's reference, as a test states it: the dense one, which
says on stderr that it was built."""
import sys

from chipbench.reference.dense_lm import DenseLM, make_train_step, zeros_like_tree  # noqa: F401


def build(sizes, precision="f32"):
    print("built reference dense_tiny", file=sys.stderr)
    return DenseLM(sizes, precision)
'''


def write(path, text):
    with open(path, "w") as f:
        f.write(text if isinstance(text, str) else json.dumps(text))


@pytest.fixture(scope="module")
def pipelined_root(tmp_path_factory):
    """A root with one more cell, made of new files and entries alone: the
    committed pipelined mix and four-layer configuration at CPU size, on
    four chips; the configuration names a FLOP count and a reference of
    its own; two new metrics."""
    root = tiny.write_root(str(tmp_path_factory.mktemp("bench")))
    cb = os.path.join(root, "chipbench")
    cfg = tiny._load(BENCH_DIR, "configs", "gpt-a-4l.json")
    cfg.update(tiny.SIZES, num_layers=4, flops="flops_tiny", reference="dense_tiny")
    write(os.path.join(cb, "configs", "gpt-a-4l.json"), cfg)
    mix = tiny._load(BENCH_DIR, "traffic", "train_pp2tp2.json")
    mix.update(seq_len=64)  # batch 4: the mix's 4 microbatches of one row
    write(os.path.join(cb, "traffic", "train_pp2tp2.json"), mix)
    shutil.copy(os.path.join(cb, "limits", "gpt-a-2l.train.json"),
                os.path.join(cb, "limits", f"{PIPELINED}.json"))
    for sub in ("metrics", "reference"):
        os.unlink(os.path.join(cb, sub))
        shutil.copytree(os.path.join(BENCH_DIR, sub), os.path.join(cb, sub))
    write(os.path.join(cb, "metrics", "steps_done.train.py"),
          "def read(run):\n    return run.attempted\n")
    write(os.path.join(cb, "metrics", "flops_per_step.train.py"),
          "def read(run):\n    return run.flops / run.attempted\n")
    write(os.path.join(cb, "reference", "dense_tiny.py"), TINY_REFERENCE)
    write(os.path.join(cb, "flops_tiny.py"), TINY_FLOPS)
    b = tiny._load(root, "BENCHMARK.json")
    b["configs"].append({"name": "gpt-a-4l", "source": "https://arxiv.org/abs/2411.14458",
                         "file": "chipbench/configs/gpt-a-4l.json",
                         "reduced": ["num_layers"], "why": "test"})
    b["workloads"].append({"name": PIPELINED, "config": "gpt-a-4l",
                           "traffic": "train_pp2tp2", "chips": 4, "why": "test"})
    for m in b["end_to_end"] + b["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(PIPELINED)
    for name, unit in (("steps_done.train", "steps"), ("flops_per_step.train", "FLOP"),
                       ("exposed_collective.train", "%")):
        b["per_layer"].append({"name": name, "unit": unit, "better": "higher",
                               "source": "host_clock", "layer": "benchmark client",
                               "moves": "train_tokens_per_s", "workloads": [PIPELINED]})
    write(os.path.join(root, "BENCHMARK.json"), b)
    return root


def run_on_four(root, workload, trace=0, fault=None):
    argv = [sys.executable, FOUR_DEVICES, root] + (["--fault", fault] if fault else []) + [
        "--workload", workload, "--seed", str(SEED), "--seconds", "1", "--trace", str(trace)]
    p = subprocess.run(argv, capture_output=True, text=True, timeout=600, cwd=root)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stderr


def test_new_cell_mix_config_and_metric_are_files_plus_entries(pipelined_root):
    from chipbench import flops

    line, err = run_on_four(pipelined_root, PIPELINED, trace=1)
    assert line["correct"], line["checks"]
    assert line["device"]["count"] == 4
    assert line["metrics"]["steps_done.train"]["value"] == line["attempted"] > 0
    cfg = tiny._load(pipelined_root, "chipbench", "configs", "gpt-a-4l.json")
    assert line["metrics"]["flops_per_step.train"]["value"] == 2 * flops.train_step_flops(cfg, 4, 64)
    assert "mfu.train" in line["metrics"]
    assert "built reference dense_tiny" in err


# ---- the timed path broken underneath: correct comes out false ----------


def test_fault_state_unchanged(root, monkeypatch):
    from repro.optim import optimizer

    real = optimizer.make_train_step

    def frozen(*a, **k):
        step = real(*a, **k)

        def bad(params, opt_state, batch):
            _, _, metrics = step(params, opt_state, batch)
            return params, opt_state, metrics
        return bad

    monkeypatch.setattr(optimizer, "make_train_step", frozen)
    line = run(root, "gpt-a-2l.train")
    assert not line["correct"]
    assert line["checks"]["change_gap"]["value"] == pytest.approx(1.0, abs=1e-3)


def test_fault_half_batch_left_out(root, monkeypatch):
    from repro.models import transformer

    real = transformer.build_model

    def halved(cfg):
        model = real(cfg)
        loss = model.loss

        def half(params, batch):
            B = batch["tokens"].shape[0]
            return loss(params, {"tokens": batch["tokens"][: B // 2]})
        model.loss = half
        return model

    monkeypatch.setattr(transformer, "build_model", halved)
    line = run(root, "gpt-a-2l.train")
    assert not line["correct"], line["checks"]


def test_map_of_the_pipelined_step_is_the_windows(pipelined_root):
    """The pipelined step returns its layers sharded over ``pod``, so the
    window runs another program than the first step; the op map is of the
    window's."""
    p = subprocess.run([sys.executable, FOUR_DEVICES, pipelined_root, "--map", PIPELINED],
                       capture_output=True, text=True, timeout=600, cwd=pipelined_root)
    assert p.returncode == 0, p.stderr[-3000:]
    assert json.loads(p.stdout.strip().splitlines()[-1]) == {"same_map": True}


def test_fault_exchange_between_chips_left_out(pipelined_root):
    line, _ = run_on_four(pipelined_root, PIPELINED, fault="exchange")
    assert not line["correct"], line["checks"]


# ---- inputs from the seed; no chip, no result --------------------------------


def test_same_seed_same_inputs_and_every_seed_the_same_work():
    with open(os.path.join(BENCH_DIR, "traffic", "train.json")) as f:
        mix = json.load(f)
    a = traffic.train_batch(mix, 50304, SEED, 3)["tokens"]
    assert np.array_equal(a, traffic.train_batch(mix, 50304, SEED, 3)["tokens"])
    assert not np.array_equal(a, traffic.train_batch(mix, 50304, SEED, 4)["tokens"])
    other = traffic.train_batch(mix, 50304, 7, 3)["tokens"]
    assert a.shape == other.shape == (mix["batch"], mix["seq_len"])
    assert not np.array_equal(a, other)
    assert 0 <= a.min() and a.max() < 50304


def test_no_chip_means_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload",
                        WORKLOADS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=300, cwd=REPO_ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
