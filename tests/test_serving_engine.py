"""serving/engine.py: request lifecycle, metrics, sampling, padding
isolation and the Splitwise KV handoff.  One engine and one cluster are
shared across the module so the prefill/decode jits compile once."""
import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models.transformer import build_model
from repro.serving.engine import (
    Request,
    ServingEngine,
    SplitwiseCluster,
    zeros_cache,
)


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("gpt_a")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = ServingEngine(cfg, params, max_batch=3, max_len=64)
    cluster = SplitwiseCluster(cfg, params, max_batch=3, max_len=64)
    return cfg, model, params, engine, cluster


def test_zeros_cache_marks_empty_slots(setup):
    cfg, model, _, _, _ = setup
    cache = zeros_cache(model, batch=2, max_len=16)
    pos_leaves = [x for x in jax.tree.leaves(cache) if x.dtype == np.int32]
    assert pos_leaves and all((np.asarray(x) == -1).all() for x in pos_leaves)


def test_request_lifecycle_metrics(setup):
    cfg, _, _, engine, _ = setup
    reqs = [
        Request(0, np.arange(5, dtype=np.int32), max_new_tokens=6),
        Request(1, np.arange(8, dtype=np.int32), max_new_tokens=3),
    ]
    out = engine.generate(reqs)
    # every request got exactly its token budget
    assert len(out[0].generated) == 6
    assert len(out[1].generated) == 3
    # TTFT recorded once, TBT once per decode step that produced a token
    for r in out:
        assert r.ttft_ms > 0
        assert len(r.tbt_ms) == len(r.generated) - 1
        assert all(t >= 0 for t in r.tbt_ms)
        assert all(0 <= t < cfg.vocab_size for t in r.generated)


def test_greedy_deterministic(setup):
    cfg, _, _, engine, _ = setup
    r1 = engine.generate([Request(0, np.arange(8, dtype=np.int32), max_new_tokens=6)])
    r2 = engine.generate([Request(0, np.arange(8, dtype=np.int32), max_new_tokens=6)])
    assert r1[0].generated == r2[0].generated
    assert len(r1[0].generated) == 6
    assert r1[0].ttft_ms > 0 and len(r1[0].tbt_ms) == 5


def test_batch_isolation_equal_batch(setup):
    """A request's output must not depend on its batch neighbours."""
    cfg, _, _, engine, _ = setup
    p0 = (np.arange(8) % cfg.vocab_size).astype(np.int32)
    alone = engine.generate([Request(0, p0.copy(), max_new_tokens=4)])[0].generated
    other = (np.arange(6) * 7 % cfg.vocab_size).astype(np.int32)
    together = engine.generate(
        [Request(1, p0.copy(), max_new_tokens=4), Request(2, other, max_new_tokens=4)]
    )[0].generated
    assert alone == together


def test_prefill_right_alignment_batch_padding(setup):
    """Unequal-length prompts batched together must each behave as if
    right-aligned alone: pad slots carry position -1 and are masked, so
    the SHORT prompt's tokens are also neighbour-independent."""
    cfg, _, _, engine, _ = setup
    short = (np.arange(4) % cfg.vocab_size).astype(np.int32)
    long = (np.arange(12) * 5 % cfg.vocab_size).astype(np.int32)
    alone = engine.generate([Request(0, short.copy(), max_new_tokens=4)])[0].generated
    mixed = engine.generate([
        Request(1, short.copy(), max_new_tokens=4),
        Request(2, long, max_new_tokens=4),
    ])[0].generated
    assert alone == mixed


def test_prefill_logits_short_prompt_batched_matches_alone(setup):
    """``prefill_logits`` of a left-padded short prompt equals its logits
    alone to bf16 tolerance (the check chip_smoke.py makes at GPT-A
    widths, where greedy tokens can flip on bf16 near-ties)."""
    cfg, _, _, engine, _ = setup
    short = Request(0, (np.arange(4) % cfg.vocab_size).astype(np.int32))
    long = Request(1, (np.arange(12) * 5 % cfg.vocab_size).astype(np.int32))
    batched, _ = engine.prefill_logits([short, long])
    alone, _ = engine.prefill_logits([short])
    assert batched.shape == (2, cfg.vocab_size)
    np.testing.assert_allclose(np.asarray(batched[0]), np.asarray(alone[0]),
                               atol=2e-2, rtol=2e-2)


def test_dropped_engine_frees_its_weights_without_gc(setup):
    """No reference cycle through the engine: dropping the last reference
    releases it (and the device weights it holds) at once."""
    import gc
    import weakref

    cfg, _, params, _, _ = setup
    gc.disable()
    try:
        engine = ServingEngine(cfg, params, max_batch=2, max_len=16)
        ref = weakref.ref(engine)
        del engine
        assert ref() is None
    finally:
        gc.enable()


def test_ragged_prefill_masked_under_pallas_impl(setup):
    """The pallas flash kernel ignores positions; the engine must pin the
    masking sdpa for ragged batches so pad slots stay invisible even when
    the pallas impl is active."""
    from repro.models import attention

    cfg, _, params, _, _ = setup
    short = (np.arange(4) % cfg.vocab_size).astype(np.int32)
    peer = ((np.arange(4) * 7 + 1) % cfg.vocab_size).astype(np.int32)
    long = (np.arange(12) * 5 % cfg.vocab_size).astype(np.int32)
    engine = ServingEngine(cfg, params, max_batch=2, max_len=64)
    attention.set_attention_impl("pallas")
    try:
        # equal-length batch: no padding, dense fast path
        dense = engine.generate([
            Request(1, short.copy(), max_new_tokens=3),
            Request(2, peer, max_new_tokens=3),
        ])[0].generated
        # ragged batch: 8 pad slots in front of `short`
        ragged = engine.generate([
            Request(3, short.copy(), max_new_tokens=3),
            Request(4, long, max_new_tokens=3),
        ])[0].generated
    finally:
        attention.set_attention_impl("xla")
    assert dense == ragged


def test_temperature_sampling_stays_in_vocab(setup):
    cfg, _, _, engine, _ = setup
    req = Request(5, np.arange(8, dtype=np.int32), max_new_tokens=6,
                  temperature=1.0)
    out = engine.generate([req])[0]
    assert len(out.generated) == 6
    assert all(0 <= t < cfg.vocab_size for t in out.generated)


@pytest.mark.slow  # compiles a second (hybrid ssm+attention) model
def test_recurrent_family_ragged_batches_served_per_request():
    """Mamba/RWKV-style models scan pads into their recurrent state, so
    the engine must split ragged batches instead of left-padding them."""
    cfg = get_smoke_config("zamba2_2p7b")
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    engine = ServingEngine(cfg, params, max_batch=2, max_len=64)
    short = (np.arange(6) % cfg.vocab_size).astype(np.int32)
    long = (np.arange(12) * 5 % cfg.vocab_size).astype(np.int32)
    alone = engine.generate([Request(0, short.copy(), max_new_tokens=3)])[0].generated
    mixed = engine.generate([
        Request(1, short.copy(), max_new_tokens=3),
        Request(2, long, max_new_tokens=3),
    ])[0].generated
    assert alone == mixed


def test_splitwise_matches_monolithic_and_counts_kv_bytes(setup):
    """Prefill/decode disaggregation must not change the tokens (§5),
    and the KV handoff must actually move bytes."""
    cfg, _, _, engine, cluster = setup
    prompt = (np.arange(8) * 3 % cfg.vocab_size).astype(np.int32)
    before = cluster.kv_bytes_moved
    split = cluster.serve([Request(0, prompt.copy(), max_new_tokens=5)])[0]
    mono = engine.generate([Request(1, prompt.copy(), max_new_tokens=5)])[0]
    assert cluster.kv_bytes_moved > before
    assert split.generated == mono.generated


def test_sampling_decorrelated_across_decode_steps(setup):
    """Regression: the PRNG key used to derive from sum(req_id) only, so
    every decode step of a batch reused the identical key — with flat
    logits each step re-drew the same token forever.  The step index is
    now folded into the key: consecutive steps differ, the same step is
    reproducible, and batches whose ids merely share a sum diverge."""
    cfg, _, _, engine, _ = setup
    flat = jax.numpy.zeros((3, cfg.vocab_size))
    reqs = [Request(i, np.zeros(1, np.int32), temperature=1.0) for i in range(3)]
    s1 = engine._sample(flat, reqs, step=1)
    s2 = engine._sample(flat, reqs, step=2)
    assert list(s1) != list(s2)
    assert list(s1) == list(engine._sample(flat, reqs, step=1))
    # sum-collision: ids (0, 3) and (1, 2) hashed identically before
    a = [Request(0, np.zeros(1, np.int32), temperature=1.0),
         Request(3, np.zeros(1, np.int32), temperature=1.0)]
    b = [Request(1, np.zeros(1, np.int32), temperature=1.0),
         Request(2, np.zeros(1, np.int32), temperature=1.0)]
    flat2 = jax.numpy.zeros((2, cfg.vocab_size))
    draws_a = [int(t) for s in range(4) for t in engine._sample(flat2, a, step=s)]
    draws_b = [int(t) for s in range(4) for t in engine._sample(flat2, b, step=s)]
    assert draws_a != draws_b


def test_kv_bytes_moved_counts_only_valid_positions(setup):
    """Regression: the handoff counter summed whole cache leaves, i.e.
    B × max_len ring slots of which all but prompt_len are pads.  It must
    agree with the latency model's kv_bytes_per_token × prompt_tokens
    accounting instead."""
    cfg, model, _, _, cluster = setup
    # gpt_a smoke: k+v leaves (L=2, B, S, H=4, hd=64) bf16
    #   per token = 2 leaves × 2 × 4 × 64 × 2 B = 2048 B
    from repro.serving.engine import (
        kv_cache_bytes_per_token,
        kv_cache_state_bytes_per_seq,
    )
    ring = cluster.prefill_engine.max_len
    cache = zeros_cache(model, 2, ring)
    per_token = kv_cache_bytes_per_token(cache, ring)
    per_seq = kv_cache_state_bytes_per_seq(cache, ring)
    assert per_token == 2 * cfg.num_layers * 4 * 64 * 2
    assert per_seq == 0.0
    lens = (5, 8)
    before = cluster.kv_bytes_moved
    cluster.serve([
        Request(10 + i, (np.arange(L) % cfg.vocab_size).astype(np.int32),
                max_new_tokens=2)
        for i, L in enumerate(lens)
    ])
    moved = cluster.kv_bytes_moved - before
    assert moved == per_token * sum(lens)
    # strictly below the old full-ring accounting
    full_ring = sum(
        x.size * x.dtype.itemsize
        for x in jax.tree.leaves(cache)
        if jax.numpy.issubdtype(x.dtype, jax.numpy.floating)
    )
    assert moved < full_ring
