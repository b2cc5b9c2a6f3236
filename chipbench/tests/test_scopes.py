"""Device time split by the program's named scopes: the HLO map, self
time on a recorded trace in which a ``while`` encloses two ops, a scope
that the program declares inside another, and the
``device_<scope>_ms.train`` readers."""
import os
import random
import time

import pytest

import tiny
from chipbench import harness, reduce, scopes

HERE = os.path.dirname(os.path.abspath(__file__))
READERS = [f"device_{s}_ms.train" for s in scopes.declared() + (scopes.UNSCOPED,)]

HLO = """HloModule jit_train_step, is_scheduled=true

%body (p: (s32[], f32[8,128])) -> (s32[], f32[8,128]) {
  %p = (s32[], f32[8,128]{1,0}) parameter(0)
  %fusion.3 = f32[8,128]{1,0} fusion(%gte.1), kind=kOutput, calls=%fc.3, metadata={op_name="jit(train_step)/transpose(jvp(attn))/while/body/closed_call/mul" source_file="t.py" source_line=3}
  ROOT %convolution.4 = f32[8,128]{1,0} convolution(%a, %b), metadata={op_name="jit(train_step)/jvp()/while/body/checkpoint/rematted_computation/ffn/dot_general"}
}

ENTRY %main.9 (params__ffn__.1: f32[8,128]) -> f32[8,128] {
  %params__ffn__.1 = f32[8,128]{1,0} parameter(0), metadata={op_name="params[\\'layers\\'][\\'ffn\\'][\\'w_up\\']"}
  %fusion.1 = bf16[8,128]{1,0} fusion(%params__ffn__.1), kind=kLoop, metadata={op_name="jit(train_step)/jvp(embed)/convert_element_type"}
  %while.2 = (s32[], f32[8,128]{1,0}) while(%tuple.1), condition=%cond, body=%body, metadata={op_name="jit(train_step)/jvp()/while"}
  %copy.5 = f32[8,128]{1,0} copy(%p.2)
  ROOT %fusion.6 = f32[8,128]{1,0} fusion(%copy.5), metadata={op_name="jit(train_step)/optimizer/sub"}
}
"""
MAP = {"p": "unscoped", "fusion.3": "attn", "convolution.4": "ffn", "params__ffn__.1": "unscoped",
       "fusion.1": "embed", "while.2": "unscoped", "copy.5": "unscoped", "fusion.6": "optimizer"}


@pytest.fixture
def trace():
    with open(os.path.join(HERE, "data", "while_trace.json")) as f:
        return reduce.Trace.from_json(f.read())


def test_scope_names_are_the_programs():
    import repro.scopes

    assert scopes.declared() == tuple(getattr(repro.scopes, "ALL", repro.scopes.SCOPES))
    assert set(repro.scopes.SCOPES) <= set(scopes.declared())


def test_op_scopes_reads_the_innermost_scope_of_each_instruction():
    assert scopes.op_scopes(HLO) == MAP
    assert scopes.scope_of("a/transpose(jvp(loss_head))/b/attn/ffn/dot_general") == "ffn"
    assert scopes.op_key("%while.2 = (s32[], f32[8,128]{1,0}) while(%t)") == "while.2"
    assert scopes.op_key("fusion.3") == "fusion.3"


def test_self_time_leaves_out_what_a_while_encloses(trace):
    # TPU:0: while.2 [200,700] encloses fusion.3 (150) and convolution.4 (200): 150 its own
    ops = scopes.op_self_ns(trace.devices["/device:TPU:0"], 0, 1000)
    assert {scopes.op_key(k): v for k, v in ops.items()} == {"fusion.1": 100, "while.2": 150, "fusion.3": 150, "convolution.4": 200,
                   "copy.5": 100}
    # clipped to [300,600]: while.2 [400,450], fusion.3 [300,400], convolution.4 [450,600]
    ops = scopes.op_self_ns(trace.devices["/device:TPU:0"], 300, 600)
    assert {scopes.op_key(k): v for k, v in ops.items()} == {
        "while.2": 50, "fusion.3": 100, "convolution.4": 150}


def test_scopes_sum_to_busy_time_and_unknown_ops_are_unscoped(trace):
    by = scopes.self_ns(trace, MAP, 0, 1000)
    assert sum(by.values()) == pytest.approx(reduce.busy_ns(trace, 0, 1000))
    # TPU:1: fusion.9 is in no map; it starts inside while.2 and outlasts it,
    # so [880,960] is its own and while.2 keeps nothing
    assert by == pytest.approx({"embed": (100 + 200) / 2, "attn": (150 + 200) / 2,
                                "ffn": (200 + 380) / 2, "loss_head": 0, "optimizer": 0,
                                "unscoped": (150 + 100 + 0 + 80) / 2})


def test_self_time_sums_to_busy_time_on_any_overlap():
    rng = random.Random(5)
    for _ in range(50):
        ops = []
        for i in range(rng.randint(1, 30)):
            s = rng.randint(0, 1000)
            ops.append((s, s + rng.randint(0, 300), f"op.{i % 7}"))
        lo, hi = sorted(rng.sample(range(-100, 1300), 2))
        got = sum(scopes.op_self_ns(ops, lo, hi).values())
        assert got == pytest.approx(reduce.total(reduce.merge(reduce.clip(
            ((s, e) for s, e, _ in ops), lo, hi))))


@pytest.fixture
def router_declared(monkeypatch):
    """The program declaring one more scope, ``router``, which the model
    would open inside ``ffn``."""
    import repro.scopes

    monkeypatch.setattr(repro.scopes, "ALL", repro.scopes.SCOPES + ("router",), raising=False)
    scopes.declared.cache_clear()
    yield
    monkeypatch.undo()
    scopes.declared.cache_clear()


def test_a_declared_sub_scope_is_mapped_innermost(router_declared, trace):
    assert scopes.declared()[-1] == "router"
    hlo = HLO.replace("/ffn/dot_general", "/ffn/router/dot_general")
    op_scope = scopes.op_scopes(hlo)
    assert op_scope == dict(MAP, **{"convolution.4": "router"})
    assert scopes.scope_of("jit(train_step)/transpose(jvp(ffn))/router/dot") == "router"
    assert scopes.scope_of("jit(train_step)/jvp(router)/ffn/dot") == "ffn"
    by = scopes.self_ns(trace, op_scope, 0, 1000)
    assert sum(by.values()) == pytest.approx(reduce.busy_ns(trace, 0, 1000))
    assert by["router"] == pytest.approx((200 + 380) / 2) and by["ffn"] == 0


def test_a_constant_that_is_not_in_all_is_no_scope(monkeypatch):
    """Only ``ALL`` (or ``SCOPES``) declares: another upper-case constant
    of the module names no scope."""
    import repro.scopes

    monkeypatch.setattr(repro.scopes, "ROUTER", "router", raising=False)
    scopes.declared.cache_clear()
    try:
        assert "router" not in scopes.declared()
        assert scopes.scope_of("jit(train_step)/ffn/router/dot") == "ffn"
    finally:
        monkeypatch.undo()
        scopes.declared.cache_clear()


@pytest.fixture(scope="module")
def ctx(tmp_path_factory):
    root = tiny.write_root(str(tmp_path_factory.mktemp("bench")))
    return harness.Context("gpt-a-2l.train", 11, 1.0, False, time.perf_counter(),
                           root=root, require_chip=False)


def tiny_run(ctx, trace=None, window=None, step_hlo=None):
    return harness.Run(kind="train", chips=1, peak=ctx.peak, sizes=ctx.sizes, mix=ctx.mix,
                       metrics={}, numbers={}, attempted=4, failed=0, memory_peak_bytes=0,
                       window_s=1.0, trace=trace, trace_window=window, step_hlo=step_hlo)


def read_all(run):
    return {m: harness._load_reader(harness.BENCH_DIR, m)(run) for m in READERS}


def test_readers_give_none_without_a_trace(ctx):
    assert set(read_all(tiny_run(ctx)).values()) == {None}
    no_device = reduce.Trace({}, [(0, 1000, reduce.WINDOW_SPAN)])
    assert set(read_all(tiny_run(ctx, no_device, (0, 1000))).values()) == {None}
    # a trace, but no step to map
    ops = reduce.Trace({"/device:TPU:0": [(0, 100, "fusion.1")]}, [(0, 1000, reduce.WINDOW_SPAN)])
    assert set(read_all(tiny_run(ctx, ops, (0, 1000))).values()) == {None}


def test_map_of_the_runners_step(ctx):
    """The map made from abstract arguments is the one of the step that
    the window drives with the state set-up hands it and a real batch."""
    import jax

    from chipbench import train_cell

    prog = train_cell.Program(ctx)
    with jax.set_mesh(prog.mesh):
        params, opt_state, _ = prog.first_steps()
        real = prog.step.lower(params, opt_state, prog.put(next(prog.feed)))
        real = scopes.op_scopes(real.compile().as_text())
    run = tiny_run(ctx, reduce.Trace({"/device:TPU:0": []}, []), (0, 1000), prog.step_hlo)
    assert scopes.run_op_scopes(run) == real
    assert set(real.values()) == set(scopes.declared()) | {scopes.UNSCOPED}

    # one op of each scope, 100 ns each, with one op the map lacks
    names = [next(k for k, v in real.items() if v == s) for s in scopes.declared()]
    events = [(100 * i, 100 * i + 100, n) for i, n in enumerate(names + ["unknown.1"])]
    run = tiny_run(ctx, reduce.Trace({"/device:TPU:0": events}, []), (0, 1000), prog.step_hlo)
    run.extra["op_scopes"] = real
    got = read_all(run)
    assert got == {m: pytest.approx(100 / 1e6 / 4) for m in READERS}


def test_readers_give_none_for_a_program_without_scopes(ctx):
    run = tiny_run(ctx, reduce.Trace({"/device:TPU:0": [(0, 100, "fusion.1")]}, []), (0, 1000),
                   lambda: "")
    run.extra["op_scopes"] = {"fusion.1": scopes.UNSCOPED}
    assert set(read_all(run).values()) == {None}
