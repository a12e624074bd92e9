"""Device time of the sparse greedy's phases, for the ``greedy`` layer's
metrics.

The program tags its phases with ``jax.named_scope``
(``core/placement.py``): ``greedy.init`` before the loop, then per
iteration ``greedy.pick``, ``greedy.rescore`` and ``greedy.stop_test``,
and ``sigma`` after it. The profiler's trace carries each op's scope as
its ``tf_op``, except for ops the compiler made without metadata: the
custom fusions that scatter pairs into the ``[E, P + 1]`` grid, the sorts
that feed them, loops it built for them. Their scope is read from the
compiled program itself: :func:`scope_map` takes each instruction's
``op_name``, else the one most frequent among the ops fused into it, else
that of its nearest consumer in the data flow. What these leave bare on
the v5e (the bodies of loops the compiler built beside the re-score's
sort, each op under 0.3 % of the program at the metro size) stays
outside the phases, as do the copies the profiler files under the loop.

The compiled text comes from the same jitted function the tick runs,
``repro.workloads.batched.sparse_evaluator(P + 1, True)``, lowered at the
run's shapes in the driver's device context (the driver's ``facts``:
users, edges, implementations, and ``max_impls``, the width of the
candidate pairs, since the tick keeps every eligible implementation). The
map is used only where the program's own metadata names each op the
trace scopes as the trace does (both traced runs on the v5e agreed on
every one); a trace without any ``greedy.*`` scope (a program without
the tags) is left alone and the readers are silent.
"""
from __future__ import annotations

import re
import sys
from collections import Counter, deque
from typing import Dict, List, Optional

import numpy as np

from bench.trace_reduce import Events, leaves

PHASES = ("greedy.init", "greedy.pick", "greedy.rescore",
          "greedy.stop_test", "sigma")
PROGRAM = r"jit_run\b"

_COMP = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s.*\{\s*$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_REF = re.compile(r"%([\w.\-]+)")
_OWN = re.compile(r'^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=.*?op_name="([^"]*)"',
                  re.M)


def _scoped(op_name: Optional[str]) -> Optional[str]:
    """An ``op_name`` that names a scope path (``jit(run)/...``)."""
    return op_name if op_name and "/" in op_name else None


def scope_map(hlo_text: str) -> Dict[str, str]:
    """``{instruction name: scope path}`` for every instruction of a
    compiled HLO module's text whose scope can be found."""
    comps: Dict[str, List[str]] = {}
    info: Dict[str, Dict] = {}
    cur = None
    for line in hlo_text.splitlines():
        m = _COMP.match(line)
        if m:
            cur = m.group(1)
            comps[cur] = []
            continue
        if line.strip() == "}":
            cur = None
            continue
        m = _INSTR.match(line)
        if m is None or cur is None:
            continue
        name, rhs = m.group(1), m.group(2)
        op = _OP_NAME.search(rhs)
        body = _OP_NAME.sub("", rhs)
        comps[cur].append(name)
        info[name] = {"comp": cur, "op_name": op.group(1) if op else None,
                      "refs": _REF.findall(body), "fusion": " fusion(" in body}
    for name, d in info.items():
        d["operands"] = [r for r in d["refs"] if r in info and r != name]
        d["calls"] = [r for r in d["refs"] if r in comps]
    users: Dict[str, List[str]] = {n: [] for n in info}
    for name, d in info.items():
        for o in d["operands"]:
            if info[o]["comp"] == d["comp"]:
                users[o].append(name)

    def fused(comp: str, seen) -> Counter:
        out: Counter = Counter()
        if comp in seen:
            return out
        seen.add(comp)
        for n in comps[comp]:
            s = _scoped(info[n]["op_name"])
            if s:
                out[s] += 1
            for c in info[n]["calls"]:
                out += fused(c, seen)
        return out

    def direct(name: str) -> Optional[str]:
        d = info[name]
        s = _scoped(d["op_name"])
        if s is None and d["fusion"]:
            votes: Counter = Counter()
            for c in d["calls"]:
                votes += fused(c, set())
            if votes:
                s = votes.most_common(1)[0][0]
        return s

    known = {n: s for n in info if (s := direct(n)) is not None}

    def consumer(name: str) -> Optional[str]:
        """The scope of the nearest consumer that has one: an op the
        compiler made (a sort, a copy) serves the ops that read it."""
        seen, queue = {name}, deque([name])
        while queue:
            for nb in users[queue.popleft()]:
                if nb in known:
                    return known[nb]
                if nb not in seen:
                    seen.add(nb)
                    queue.append(nb)
        return None

    out = dict(known)
    for n in info:
        if n not in out and (s := consumer(n)) is not None:
            out[n] = s
    return out


def program_text(facts: Dict) -> Optional[str]:
    """The compiled text of the greedy's jitted function at the tick's
    shapes; None where JAX cannot give it. Lowered with the first device
    as the default, as the driver's ticks run, JAX hands back from its
    in-memory caches the very executable the window ran (in any other
    context it compiles the program anew)."""
    import jax
    import jax.numpy as jnp

    from repro.workloads.batched import sparse_evaluator

    U, E = int(facts["users"]), int(facts["edges"])
    P, M = int(facts["impls"]), int(facts["max_impls"])
    shape = jax.ShapeDtypeStruct
    try:
        with jax.default_device(jax.devices()[0]):
            return sparse_evaluator(P + 1, True).lower(
                shape((U, M), jnp.int32), shape((U, M), jnp.float32),
                shape((U,), jnp.int32), shape((P,), jnp.int32),
                shape((P,), jnp.float32), shape((E,), jnp.float32)
            ).compile().as_text()
    except Exception as e:  # noqa: BLE001 - a reader never raises
        print(f"[bench] greedy_phases: no compiled text: {e!r}",
              file=sys.stderr, flush=True)
        return None


def own_scopes(hlo_text: str) -> Dict[str, str]:
    """``{instruction name: scope path}`` of the instructions whose own
    metadata names a scope path."""
    return {n: s for n, s in _OWN.findall(hlo_text) if _scoped(s)}


def fill(ops: Events, scopes: Dict[str, str],
         own: Dict[str, str]) -> Optional[Events]:
    """``ops`` with each empty scope taken from ``scopes``, or None where
    the program's own metadata (``own``) names any op otherwise than the
    trace does: then the text is not that of the program that ran. (The
    profiler gives an op without metadata the scope of its loop, so only
    ops with metadata of their own are compared.)"""
    pairs = [(n, s.rstrip(":")) for n, s in zip(ops.name, ops.scope)
             if s and n in own]
    if not pairs or any(own[n] != s for n, s in pairs):
        return None
    return Events(ops.start, ops.dur, list(ops.name),
                  [s or scopes.get(n, "") for n, s in zip(ops.name,
                                                           ops.scope)])


_LAST: tuple = (None, None)     # (trace, its ops): one map per trace


def greedy_ops(run) -> Optional[Events]:
    """Device 0's leaf ops inside the window's ``jit_run`` executions,
    scopes filled from the compiled program; None where the trace holds
    no ``greedy.*`` scope or the map does not fit it."""
    global _LAST
    t = run.trace
    if t is None or not t.devices:
        return None
    if _LAST[0] is t:
        return _LAST[1]
    dev = t.devices[0]
    out = None
    if any("/greedy." in s for s in dev.ops.scope):
        text = program_text(run.facts)
        if text is not None:
            runs = t.programs(dev, PROGRAM)
            ops = dev.ops.select(t.in_window(dev.ops))
            inside = np.zeros(len(ops), bool)
            for a, b in zip(runs.start.tolist(), runs.end.tolist()):
                inside |= (ops.start >= a) & (ops.end <= b)
            out = fill(leaves(ops.select(inside)), scope_map(text),
                       own_scopes(text))
    _LAST = (t, out)
    return out


def phase_ns(run, phase: str) -> Optional[int]:
    """Device ns of the leaf ops of ``jit_run`` under ``phase``."""
    ops = greedy_ops(run)
    if ops is None:
        return None
    hit = np.array([f"/{phase}/" in s for s in ops.scope], bool)
    return int(ops.dur[hit].sum()) if hit.any() else None


def counter(name: str) -> Optional[float]:
    """A counter of the program's obs tracer, which the trace's end
    uninstalled (``repro.obs.last_tracer``, until the reduction keeps the
    counters itself); None where the program keeps none, or has no
    ``last_tracer`` to find them by."""
    from repro import obs

    last = getattr(obs, "last_tracer", None)
    tracer = last() if last is not None else None
    if tracer is None or name not in tracer.counters:
        return None
    return float(tracer.counters[name])
