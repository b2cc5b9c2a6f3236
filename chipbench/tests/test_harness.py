"""The harness end to end without a chip, at a size a CPU test holds:
every cell of BENCHMARK.json loads and runs by name, a new cell, mix,
configuration and metric are new files plus new entries, and a timed
path broken underneath makes ``correct`` come out false."""
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
    assert set(ctx.limits) == {"grad_gap", "change_gap"}
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


def test_new_cell_mix_config_and_metric_are_files_plus_entries(tmp_path):
    root = tiny.write_root(str(tmp_path))
    cb = os.path.join(root, "chipbench")
    with open(os.path.join(cb, "configs", "gpt-a-2l.json")) as f:
        cfg = json.load(f)
    cfg["num_layers"] = 4
    with open(os.path.join(cb, "configs", "gpt-a-4l.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(cb, "traffic", "train.json")) as f:
        mix = json.load(f)
    mix.update(seq_len=128, batch=1)
    with open(os.path.join(cb, "traffic", "train-long.json"), "w") as f:
        json.dump(mix, f)
    shutil.copy(os.path.join(cb, "limits", "gpt-a-2l.train.json"),
                os.path.join(cb, "limits", "gpt-a-4l.train-long.json"))
    metrics = os.path.join(cb, "metrics")
    os.unlink(metrics)
    shutil.copytree(os.path.join(BENCH_DIR, "metrics"), metrics)
    with open(os.path.join(metrics, "steps_done.train.py"), "w") as f:
        f.write("def read(run):\n    return run.attempted\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        b = json.load(f)
    b["configs"].append({"name": "gpt-a-4l", "source": "https://arxiv.org/abs/2411.14458",
                         "file": "chipbench/configs/gpt-a-4l.json",
                         "reduced": ["num_layers"], "why": "test"})
    b["workloads"].append({"name": "gpt-a-4l.train-long", "config": "gpt-a-4l",
                           "traffic": "train-long", "chips": 1, "why": "test"})
    for m in b["end_to_end"] + b["per_layer"]:
        if "workloads" in m:
            m["workloads"].append("gpt-a-4l.train-long")
    b["per_layer"].append({"name": "steps_done.train", "unit": "steps",
                           "better": "higher", "source": "host_clock", "layer": "benchmark client",
                           "moves": "train_tokens_per_s", "workloads": ["gpt-a-4l.train-long"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)
    line = run(root, "gpt-a-4l.train-long", trace=1)
    assert line["correct"], line["checks"]
    assert line["metrics"]["steps_done.train"]["value"] == line["attempted"]
    assert "mfu.train" in line["metrics"]


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
