"""Entry point of the chip benchmark: one cell of ``BENCHMARK.json``.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on the machine that holds the cell's
chips.  The last line of stdout is the result as one JSON object; the
compared numbers and their limits are the last lines of stderr.  With no
TPU, or fewer chips than the cell needs, it exits 3 and prints no result.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# JAX's persistent compilation cache, at a fixed path inside the checkout
# (the path is part of the cache key); the program takes it from the
# environment variable.
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def main() -> int:
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # libtpu logs to /tmp otherwise
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from chipbench import harness

    return harness.main(sys.argv[1:], T_START)


if __name__ == "__main__":
    sys.exit(main())
