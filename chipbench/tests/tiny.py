"""A benchmark root at a size a CPU test holds: the real cells' mixes and
configuration with small widths, written under a temporary directory, so
that the harness runs end to end without a chip.  Its metric readers,
references and FLOP count are the committed files."""
import json
import os
import shutil

from chipbench.harness import BENCH_DIR, REPO_ROOT

SIZES = {"num_layers": 2, "d_model": 128, "num_heads": 4, "num_kv_heads": 4,
         "head_dim": 32, "d_ff": 512, "vocab_size": 512}


def _load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def write_root(tmp, sizes=None, seq_len=64, batch=2):
    """The committed BENCHMARK.json and limits, configuration files cut to
    ``sizes`` (``SIZES`` unless given) and mixes cut to CPU size: train
    batches of ``batch x seq_len``."""
    bench = _load(REPO_ROOT, "BENCHMARK.json")
    for sub in ("configs", "traffic", "limits"):
        os.makedirs(os.path.join(tmp, "chipbench", sub), exist_ok=True)
    for name in ("metrics", "reference", "flops.py"):
        os.symlink(os.path.join(BENCH_DIR, name), os.path.join(tmp, "chipbench", name))
    for c in bench["configs"]:
        cfg = _load(REPO_ROOT, c["file"])
        cfg.update(sizes or SIZES)
        with open(os.path.join(tmp, c["file"]), "w") as f:
            json.dump(cfg, f)
    for w in bench["workloads"]:
        mix = _load(BENCH_DIR, "traffic", f"{w['traffic']}.json")
        mix.update(seq_len=seq_len, batch=batch)
        with open(os.path.join(tmp, "chipbench", "traffic", f"{w['traffic']}.json"), "w") as f:
            json.dump(mix, f)
        shutil.copy(os.path.join(BENCH_DIR, "limits", f"{w['name']}.json"),
                    os.path.join(tmp, "chipbench", "limits"))
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return tmp
