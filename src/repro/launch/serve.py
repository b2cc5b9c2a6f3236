"""Serving driver: batched prefill/decode with the Splitwise-style split
(paper §5) and BubbleTea admission statistics.

  PYTHONPATH=src python -m repro.launch.serve --arch gpt-a --smoke \
      --requests 16 --max-new 8
  PYTHONPATH=src python -m repro.launch.serve --arch gpt-a --layers 2 \
      --requests 4 --max-new 4      # GPT-A widths, one TPU v5e chip
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import load_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models.transformer import build_model
from repro.serving.engine import Request, ServingEngine, SplitwiseCluster


def main(argv=None):
    """Serve seeded random requests; returns them with their tokens."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gpt-a")
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU)")
    ap.add_argument("--layers", type=int, default=0,
                    help="replace the config's num_layers (depth cut)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--splitwise", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = load_config(args.arch, smoke=args.smoke, layers=args.layers)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    rng = np.random.default_rng(args.seed)

    reqs = [
        Request(
            i,
            rng.integers(0, cfg.vocab_size, size=rng.integers(4, args.prompt_len)).astype(np.int32),
            max_new_tokens=args.max_new,
        )
        for i in range(args.requests)
    ]

    if args.splitwise:
        cluster = SplitwiseCluster(cfg, params, args.batch, args.max_len)
        serve = cluster.serve
    else:
        engine = ServingEngine(cfg, params, args.batch, args.max_len)
        serve = engine.generate

    done = []
    t0 = time.time()
    for i in range(0, len(reqs), args.batch):
        done += serve(reqs[i : i + args.batch])
    wall = time.time() - t0

    ttfts = [r.ttft_ms for r in done]
    tbts = [t for r in done for t in r.tbt_ms]
    print(f"[serve] arch={cfg.name} layers={cfg.num_layers} d_model={cfg.d_model} "
          f"requests={len(done)} wall={wall:.2f}s")
    print(f"  TTFT ms: p50={np.percentile(ttfts,50):.1f} p99={np.percentile(ttfts,99):.1f}")
    if tbts:
        print(f"  TBT  ms: p50={np.percentile(tbts,50):.1f} p99={np.percentile(tbts,99):.1f}")
    if args.splitwise:
        print(f"  KV bytes moved: {cluster.kv_bytes_moved/1e6:.2f} MB")
    return done


if __name__ == "__main__":
    main()
