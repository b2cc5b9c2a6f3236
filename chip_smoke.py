"""Smoke run of the training and serving stack on a TPU, through the
entry points a user calls (``repro.launch.serve.main``,
``repro.launch.train.main``) and the Pallas kernels compiled for the chip.

  python3 chip_smoke.py                # one chip: serve, train, kernels
  python3 chip_smoke.py --four-chips   # four chips: cross-pod pipeline only

The model is the paper's GPT-A (``configs/gpt_a.py``) at its published
widths (d_model 4096, d_ff 16384, 32 heads of 128, vocab 50304), cut in
depth to fit one 16 GiB TPU v5e chip; weights are random from a seed.
Every phase checks its outputs and raises on a failure.  With no TPU the
run stops at the device phase with a non-zero exit; there is no CPU
fallback.  The last line of stdout is one JSON object naming the device.

Everything runs in this one process: it holds the chip, and it starts no
child process.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

# Depth x sequence cut for one v5e chip, from the train step's
# compiled.memory_analysis() at GPT-A widths, batch 1 (AOT for v5e):
#   2 layers x 1536 tokens: 9.11 GiB arguments + 5.43 GiB temporaries
#   = 14.54 of 15.75 GiB, 1.22 GiB free.  2 x 1792 leaves 0.78 GiB free,
#   2 x 2048 needs 15.93 GiB, and 2 x 4096 does not fit.
LAYERS = 2
SEQ = 1536
TRAIN_STEPS = 4
TRAIN_ARGV = ["--arch", "gpt-a", "--layers", str(LAYERS), "--steps", str(TRAIN_STEPS),
              "--batch", "1", "--seq", str(SEQ), "--log-every", "1"]
# serving: three requests of unequal prompt length in one batch
MAX_NEW, MAX_LEN = 4, 128
SERVE_ARGV = ["--arch", "gpt-a", "--layers", str(LAYERS), "--requests", "3",
              "--batch", "3", "--max-new", str(MAX_NEW), "--prompt-len", "64",
              "--max-len", str(MAX_LEN)]
# four chips: two pipeline stages of 2 layers, (pod=2, data=1, model=2)
FOUR_CHIP_ARGV = ["--arch", "gpt-a", "--layers", "4", "--steps", "1",
                  "--batch", "4", "--seq", "1024", "--log-every", "1"]
# kernels at GPT-A widths: 4096 tokens, 32 heads of 128, d_model 4096
KERNEL_DIMS = dict(T=4096, H=32, D=128, d_model=4096)
KERNEL_MARK = "tpu_custom_call"  # a compiled Mosaic kernel in the program
PIPELINE_LOSS_ATOL = 3e-2  # as tests/test_pipeline_multidev.py
BF16_TOL = dict(atol=2e-2, rtol=2e-2)  # as tests/test_kernels.py


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(what)


def cache_entries(path: str) -> set:
    """Compiled programs in the cache (``<name>-<key>-cache`` files; a
    size-capped cache also keeps an ``-atime`` file per entry)."""
    if not os.path.isdir(path):
        return set()
    return {e for e in os.listdir(path) if e.endswith("-cache")}


def memory_line(device) -> str:
    stats = device.memory_stats() or {}
    return (f"peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
            f"bytes_limit={stats.get('bytes_limit')}")


def phase_device(four_chips: bool):
    import jax

    devices = jax.devices()
    d = devices[0]
    print(f"[device] platform={d.platform} kind={d.device_kind} count={len(devices)}",
          flush=True)
    if d.platform != "tpu":
        raise SystemExit(f"no TPU found (platform {d.platform!r}); no CPU fallback")
    if four_chips:
        check(len(devices) == 4, f"--four-chips needs 4 devices, found {len(devices)}")
    return devices


def phase_serve(device) -> None:
    import jax
    import numpy as np

    from repro.configs import load_config
    from repro.launch import serve
    from repro.models.transformer import build_model
    from repro.serving.engine import ServingEngine

    cfg = load_config("gpt-a", layers=LAYERS)
    done = serve.main(SERVE_ARGV)
    lengths = [len(r.prompt) for r in done]
    check(len(set(lengths)) > 1, f"prompt lengths not unequal: {lengths}")
    for r in done:
        print(f"[serve] request {r.req_id} prompt_len={len(r.prompt)} tokens={r.generated}")
        check(len(r.generated) == MAX_NEW,
              f"request {r.req_id}: {len(r.generated)} tokens, want {MAX_NEW}")
        check(all(0 <= t < cfg.vocab_size for t in r.generated),
              f"request {r.req_id}: token out of vocabulary: {r.generated}")

    # Neighbour independence: each request's prefill logits in the ragged
    # batch (its pad slots masked by position) match its logits alone,
    # within bf16 tolerance, and so does its greedy token wherever the top
    # two logits are further apart than that tolerance.  The weights are
    # serve.main's (same seed), so the batched argmax is its first token.
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    engine = ServingEngine(cfg, params, len(done), MAX_LEN)
    batched = np.asarray(engine.prefill_logits(done)[0])
    for i, r in enumerate(done):
        alone = np.asarray(engine.prefill_logits([r])[0][0])
        np.testing.assert_allclose(batched[i], alone, err_msg=f"request {r.req_id}",
                                   **BF16_TOL)
        check(r.generated[0] == int(np.argmax(batched[i])),
              f"request {r.req_id}: first token {r.generated[0]} is not the argmax")
        second, first = np.sort(alone)[-2:]
        tie = first - second <= 2 * (BF16_TOL["atol"] + BF16_TOL["rtol"] * abs(first))
        check(tie or np.argmax(alone) == np.argmax(batched[i]),
              f"request {r.req_id}: greedy token differs batched vs alone")
        print(f"[serve] request {r.req_id}: batched vs alone prefill logits "
              f"max_abs_diff={float(np.max(np.abs(batched[i] - alone)))}")
    print(f"[serve] ok: {len(done)} requests; {memory_line(device)}", flush=True)


def phase_train(device) -> None:
    from repro.launch import train

    print(f"[train] cut: {' '.join(TRAIN_ARGV)}", flush=True)
    history = train.main(TRAIN_ARGV)
    check(len(history) == TRAIN_STEPS, f"{len(history)} steps, want {TRAIN_STEPS}")
    losses = [m["loss"] for m in history]
    gnorms = [m["grad_norm"] for m in history]
    check(all(math.isfinite(x) for x in losses + gnorms),
          f"non-finite loss or grad norm: {losses} {gnorms}")
    print(f"[train] ok: losses={losses} grad_norms={gnorms}; {memory_line(device)}",
          flush=True)


def phase_kernels() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref

    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    B, T, H, D, dm = 1, KERNEL_DIMS["T"], KERNEL_DIMS["H"], KERNEL_DIMS["D"], KERNEL_DIMS["d_model"]
    q = jax.random.normal(ks[0], (B, T, H, D), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, T, H, D), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, T, H, D), jnp.bfloat16)
    Bd = 4
    qd = jax.random.normal(ks[3], (Bd, 1, H, D), jnp.bfloat16)
    kd = jax.random.normal(ks[4], (Bd, T, H, D), jnp.bfloat16)
    vd = jax.random.normal(ks[5], (Bd, T, H, D), jnp.bfloat16)
    kv_pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (Bd, T))
    kv_pos = jnp.where(kv_pos < T - T // 40, kv_pos, -1)  # empty tail slots
    q_pos = jnp.full((Bd, 1), T - T // 30, jnp.int32)
    x = jax.random.normal(ks[6], (B, T, dm), jnp.bfloat16)
    scale = 1.0 + 0.1 * jax.random.normal(ks[7], (dm,), jnp.float32)

    cases = {
        "flash_attention": (ops.flash_attention, (q, k, v),
                            lambda: ref.flash_attention_ref(q, k, v, causal=True)),
        "decode_attention": (ops.decode_attention, (qd, kd, vd, q_pos, kv_pos),
                             lambda: ref.decode_attention_ref(qd, kd, vd, q_pos, kv_pos)),
        "rmsnorm": (ops.rmsnorm, (x, scale), lambda: ref.rmsnorm_ref(x, scale)),
    }
    for name, (fn, args, oracle) in cases.items():
        # compiled Mosaic kernel, not the interpreter
        check(KERNEL_MARK in fn.lower(*args).as_text(),
              f"{name}: no {KERNEL_MARK} in the lowered program")
        out = np.asarray(fn(*args), np.float32)
        with jax.default_matmul_precision("highest"):
            want = np.asarray(oracle(), np.float32)
        err = float(np.max(np.abs(out - want)))
        np.testing.assert_allclose(out, want, err_msg=name, **BF16_TOL)
        print(f"[kernels] {name} {tuple(args[0].shape)} ok: max_abs_err={err}", flush=True)


def phase_four_chips(devices) -> None:
    from repro.launch import train

    plain = train.main(FOUR_CHIP_ARGV)[0]["loss"]
    losses = {"plain": plain}
    for boundary in ("striped", "direct"):
        losses[boundary] = train.main(
            FOUR_CHIP_ARGV + ["--pipeline", "--boundary", boundary])[0]["loss"]
    print(f"[four-chips] step-0 losses: {losses}", flush=True)
    for boundary in ("striped", "direct"):
        diff = abs(losses[boundary] - plain)
        check(math.isfinite(losses[boundary]) and diff < PIPELINE_LOSS_ATOL,
              f"{boundary} pipeline loss {losses[boundary]} vs plain {plain}")
    for d in devices:
        print(f"[four-chips] device {d.id}: {memory_line(d)}")
    print("[four-chips] ok: pipelined losses match the plain step", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the cross-pod pipeline on four chips")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    entries_before = cache_entries(cache_dir)
    devices = phase_device(args.four_chips)
    if args.four_chips:
        phase_four_chips(devices)
    else:
        # serve before train: peak_bytes_in_use cannot be reset, so this
        # order gives each phase its own peak
        phase_serve(devices[0])
        phase_train(devices[0])
        phase_kernels()
    # programs this run compiled and wrote to the cache: none on a warm one
    written = sorted(e.split("-")[0] for e in cache_entries(cache_dir) - entries_before)
    print(f"[cache] {cache_dir}: {len(entries_before)} entries before; "
          f"written by this run: {len(written)} {written}", flush=True)
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
