"""Device time of a step split by the program's named scopes.

The program declares its ``jax.named_scope`` names in ``repro/scopes.py``
(``declared``: ``ALL``, or ``SCOPES`` where it has no ``ALL``; today
``embed``, ``attn``, ``ffn``, ``loss_head``, ``optimizer``); each
reaches every instruction of the optimized HLO as part of its
``op_name`` metadata.  The profiler names a device op by its
HLO instruction, so the compiled step's text maps each op of the trace to
the innermost declared scope around it, and a scope that a later program
declares, inside another or not, is mapped with no change here:

- ``op_scopes`` reads that text into ``{instruction: scope}``;
- ``self_ns`` splits the device time of a trace's window by scope, each
  op counted by its self time (its duration less the part of it that ops
  nested inside it cover: a ``while`` encloses its body's ops on the
  profiler's line), so the values sum to ``reduce.busy_ns``;
- ``scope_ms`` is what the ``device_<scope>_ms.train`` readers return.

The map is made after the window of a traced run, so neither ``setup_s``
nor the window pays for it, from the run's ``step_hlo``: the runner builds
its step again, compiles it with abstract arguments and returns its text.
A run without a trace or without a step to map reads nothing.
"""
from __future__ import annotations

import functools
import re
import time
from typing import Dict, Iterable, Optional, Tuple

UNSCOPED = "unscoped"

_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'\bop_name="((?:[^"\\]|\\.)*)"')
_OP_KEY = re.compile(r"%?([\w.\-]+)")


@functools.lru_cache(maxsize=None)
def declared() -> Tuple[str, ...]:
    """Every scope name the program declares: ``repro.scopes.ALL`` where
    the module has it (the layer scopes and any sub-scope, such as a
    ``router`` inside ``ffn``), else its layer scopes ``SCOPES``."""
    from repro import scopes

    return tuple(getattr(scopes, "ALL", scopes.SCOPES))


def scope_of(path: str) -> str:
    """The innermost scope named in an ``op_name`` path: its last segment
    that is a declared scope name (``transpose(jvp(attn))`` gives
    ``attn``; an argument's path, ``params['ffn']``, gives none)."""
    names = declared()
    for token in reversed(re.split(r"[/(),]", path)):
        if token in names:
            return token
    return UNSCOPED


def op_scopes(hlo_text: str) -> Dict[str, str]:
    """``{instruction name: scope}`` for every instruction of an HLO
    module's text; one without ``op_name`` is ``unscoped``."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            path = _OP_NAME.search(line)
            out[m.group(1)] = scope_of(path.group(1)) if path else UNSCOPED
    return out


def op_key(event_name: str) -> str:
    """The instruction an XLA Ops event names: ``fusion.3`` or the
    instruction's text, ``%fusion.3 = f32[...] ...``."""
    m = _OP_KEY.match(event_name)
    return m.group(1) if m else event_name


def op_self_ns(ops: Iterable[Tuple[float, float, str]], lo: float,
               hi: float) -> Dict[str, float]:
    """Self time in ``[lo, hi]`` per op name of one device's ops: every
    instant some op runs is counted once, for the op that started last
    among those running, which for nested ops is the innermost."""
    out: Dict[str, float] = {}
    stack = []  # [end, name, resume]: an open op, and where its self time resumes

    def pop():
        end, name, resume = stack.pop()
        out[name] = out.get(name, 0.0) + max(0.0, end - resume)
        if stack:
            stack[-1][2] = max(stack[-1][2], end, resume)

    clipped = ((max(s, lo), min(e, hi), n) for s, e, n in ops)
    for s, e, name in sorted((c for c in clipped if c[1] > c[0]),
                             key=lambda c: (c[0], -c[1])):
        while stack and stack[-1][0] <= s:
            pop()
        if stack:
            parent = stack[-1]
            out[parent[1]] = out.get(parent[1], 0.0) + max(0.0, s - parent[2])
            parent[2] = max(parent[2], s)
        stack.append([e, name, s])
    while stack:
        pop()
    return out


def instruction_self_ns(trace, lo: float, hi: float) -> Dict[str, float]:
    """Self time in ``[lo, hi]`` per HLO instruction, mean over the
    trace's devices."""
    out: Dict[str, float] = {}
    for ops in trace.devices.values():
        for name, ns in op_self_ns(ops, lo, hi).items():
            out[op_key(name)] = out.get(op_key(name), 0.0) + ns
    nd = max(len(trace.devices), 1)
    return {k: v / nd for k, v in out.items()}


def self_ns(trace, op_scope: Dict[str, str], lo: float, hi: float) -> Dict[str, float]:
    """Device self time in ``[lo, hi]`` per scope (and ``unscoped``),
    mean over the trace's devices; an op missing from ``op_scope`` is
    ``unscoped``."""
    acc = dict.fromkeys(declared() + (UNSCOPED,), 0.0)
    for key, ns in instruction_self_ns(trace, lo, hi).items():
        acc[op_scope.get(key, UNSCOPED)] += ns
    return acc


def run_op_scopes(run) -> Optional[Dict[str, str]]:
    """The op map of a traced run with a step to map, made once and kept
    on the run."""
    if (run.step_hlo is None or run.trace is None or run.trace_window is None
            or not run.trace.devices):
        return None
    if "op_scopes" not in run.extra:
        from chipbench.harness import log

        t0 = time.perf_counter()
        run.extra["op_scopes"] = op_scopes(run.step_hlo())
        log(f"op scope map: {len(run.extra['op_scopes'])} instructions in "
            f"{time.perf_counter() - t0:.3f} s after the window")
    return run.extra["op_scopes"]


def _report(run, op_scope: Dict[str, str]) -> None:
    """Log how much busy time the map covers and the costliest ops left
    unscoped."""
    from chipbench.harness import log

    per_op = instruction_self_ns(run.trace, *run.trace_window)
    busy = sum(per_op.values())
    found = sum(ns for k, ns in per_op.items() if k in op_scope)
    unscoped = sorted(((ns, k) for k, ns in per_op.items()
                       if op_scope.get(k, UNSCOPED) == UNSCOPED), reverse=True)[:3]
    steps = max(run.attempted, 1)
    log(f"op scope map covers {100.0 * found / max(busy, 1.0):.3f} % of busy time "
        f"({busy / 1e9:.6f} s); largest unscoped ops, ms a step: "
        f"{[(k, ns / 1e6 / steps) for ns, k in unscoped]}")


def scope_ms(run, scope: str) -> Optional[float]:
    """Device self time of ``scope`` per step done in the traced window,
    in ms; None without a trace, or where the program names no such scope
    (``unscoped``: none at all)."""
    op_scope = run_op_scopes(run)
    if not op_scope or run.attempted <= 0:
        return None
    named = set(op_scope.values()) - {UNSCOPED}
    if (scope == UNSCOPED and not named) or (scope != UNSCOPED and scope not in named):
        return None
    if "scope_ns" not in run.extra:
        run.extra["scope_ns"] = self_ns(run.trace, op_scope, *run.trace_window)
        _report(run, op_scope)
    return run.extra["scope_ns"][scope] / 1e6 / run.attempted
