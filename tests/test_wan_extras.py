"""Fig 7 bandwidth-stability model + §6.7 compression negative result +
launcher CLI smoke tests."""
import os
import subprocess
import sys

import pytest

from repro.core import wan
from repro.core.simulator import GeoTopology, PipelineSpec, simulate
from repro.core.simulator import testbed_spec as make_spec

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_fig7_cov_matches_paper_ordering():
    """Longer WAN path fluctuates LESS (paper: 0.8% Asia vs 2.3% US-West)."""
    west = wan.trace_cov(wan.bandwidth_trace_gbps(34))
    asia = wan.trace_cov(wan.bandwidth_trace_gbps(95))
    assert asia < west
    assert 0.002 < asia < 0.02
    assert 0.01 < west < 0.04


def test_fig7_trace_deterministic():
    a = wan.bandwidth_trace_gbps(34, seed=1)
    b = wan.bandwidth_trace_gbps(34, seed=1)
    assert a == b
    c = wan.bandwidth_trace_gbps(34, seed=2)
    assert a != c


def test_trace_seed_folds_in_bandwidth():
    """Regression (ISSUE 3): two links with the same *integer* latency
    but different bandwidth (multi- vs single-TCP at one RTT) must not
    emit perfectly correlated fluctuation patterns."""
    multi = wan.wan_link(34.0, True)   # 5 Gbps
    single = wan.wan_link(34.0, False)  # cwnd-limited
    assert multi.bw_gbps != single.bw_gbps
    a = wan.bandwidth_trace_for_link(multi, seed=1)
    b = wan.bandwidth_trace_for_link(single, seed=1)
    # normalize out the mean: compare the fluctuation *patterns*
    na = [x / multi.bw_gbps for x in a]
    nb = [x / single.bw_gbps for x in b]
    assert na != nb


def test_trace_seed_uses_full_precision_latency():
    """Latencies 34.2 vs 34.9 ms truncate to the same int — their traces
    must still decorrelate."""
    a = wan.bandwidth_trace_for_link(wan.Link(34.2, 5.0), seed=1)
    b = wan.bandwidth_trace_for_link(wan.Link(34.9, 5.0), seed=1)
    assert a != b
    # and a fixed link stays deterministic
    again = wan.bandwidth_trace_for_link(wan.Link(34.2, 5.0), seed=1)
    assert a == again


def test_sec67_compression_is_net_loss():
    """§6.7: 4× activation compression at 2× same-loss compute is slower
    than Atlas's semantics-preserving transport."""
    spec = make_spec(
        hidden=8192, seq_len=6144, micro_batch=1, layers_per_stage=1,
        layer_params=1.2e9, num_stages=4, microbatches=16, stage_dc=[0, 0, 1, 2],
    )
    t = GeoTopology(wan_latency_ms=40, multi_tcp=True)
    atlas = simulate(spec, t, policy="atlas", n_pipelines=3, validate=True).iteration_ms
    comp_spec = PipelineSpec(**{
        **spec.__dict__,
        "act_bytes": spec.act_bytes * wan.COMPRESSION_RATIO,
        "t_fwd_ms": spec.t_fwd_ms * wan.COMPRESSION_COMPUTE_MULT,
    })
    comp = simulate(comp_spec, t, policy="varuna", validate=True).iteration_ms
    assert comp > 1.3 * atlas  # paper: ~2× slowdown; direction must hold


@pytest.mark.parametrize(
    "argv",
    [
        # tier-1 guard: the train entry point on one device
        ["repro.launch.train", "--arch", "gpt-a", "--smoke", "--steps", "3",
         "--batch", "4", "--seq", "32", "--log-every", "1"],
        pytest.param(
            ["repro.launch.serve", "--arch", "gpt-a", "--smoke", "--requests", "2",
             "--max-new", "3", "--batch", "2"],
            marks=pytest.mark.slow,  # subprocess + full jit compile
        ),
    ],
)
def test_launcher_cli_smoke(argv, tmp_path):
    # the compile cache goes where JAX_COMPILATION_CACHE_DIR says
    cache = tmp_path / "jax_cache"
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_COMPILATION_CACHE_DIR=str(cache),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    out = subprocess.run(
        [sys.executable, "-m", *argv], capture_output=True, text=True,
        env=env, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert any(cache.iterdir()), "no compile-cache entry written"


def test_compile_cache_defaults_to_checkout():
    """Without JAX_COMPILATION_CACHE_DIR the cache sits at a fixed path in
    the checkout (a moving directory would never hit)."""
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    code = ("import jax\n"
            "from repro.launch.compile_cache import enable_compile_cache\n"
            "print(enable_compile_cache(), jax.config.jax_compilation_cache_dir)\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    want = os.path.join(REPO, ".jax_cache")
    assert out.stdout.split() == [want, want]
