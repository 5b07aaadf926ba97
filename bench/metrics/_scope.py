"""Device time of the model's layers, shared by the ``<scope>_ms.step``
readers, ``recompute_ms.step`` and ``unscoped_share.step``.

The program runs each of its layers under a ``jax.named_scope`` from a
fixed vocabulary (``repro/models/scopes.py``), and JAX writes the scope
into the ``op_name`` of each HLO instruction's metadata:
``jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/
rematted_computation/attention/dot_general``.  The trace names a device
op by its instruction name alone (``%fusion.660``), so the map from
instruction names to op names comes from the compiled text of the
cell's step.  The step is built as the cell's runner builds it and
compiled once, after the window of a traced run: untraced runs never
come here.  That compile keys JAX's persistent cache on metadata too;
with the default key, which strips it, an entry compiled from a tree
without the scopes (the same program) would answer, and its text has
none.

An instruction's path is its ``op_name``.  A fusion the compiler left
without one takes the first of the computation it calls.  An
instruction whose path holds no scope (a layout copy, a cumsum the
compiler rewrote, each made without metadata) takes the path of its
users in its computation when those that have a scope all share the
outermost one: data made only for a layer is that layer's.

Each op's device time (``Reduced.ops``, all chips) is credited to the
outermost scope of the vocabulary in its path, or to no scope: the
scopes and the unscoped time partition the ops' time.  Where the
compiler merged instructions and joined their paths with ';', the first
path that holds a scope decides.  ``within`` counts an op under every
scope its path names at any depth, so an inner scope (``ssd_scan``
inside ``mamba2``) and recompute (``rematted_computation``) are read
across the partition.  A time per step is device milliseconds over
chips x the window's steps.  Every reader returns None where there is
no map, where an op of the trace is no instruction of the map's module
(the map is not the program that ran), where no op carries a scope (a
program without them) or where none is under its own.
"""
from __future__ import annotations

import argparse
import dataclasses
import re
import sys
from typing import Dict, List, Optional

import jax

from bench import harness, steps

RECOMPUTE = "rematted_computation"
#: the runners (``bench/traffic/<kind>.py``) whose step the map rebuilds,
#: and the kind of step each builds
STEP_KINDS = {"train_steps": "train", "prefill_steps": "prefill"}
#: a computation's first line, at the left margin:
#: ``%fused_computation.3 (param_0: f32[4]) -> f32[4] {``
_COMPUTATION = re.compile(r"^(?:ENTRY )?(%\S+) ")
#: an instruction's line: ``  %name = ...``
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?(%[^\s=]+) = ")
_OP_NAME = re.compile(r'\bop_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r"\bcalls=(%[^\s,]+)")
_REFERENCE = re.compile(r"%[^\s,(){}]+")
#: a transformation's wrapper round a scope in a path:
#: ``transpose(jvp(head))``; a ``jit(...)`` names a function, not a scope
_WRAPPED = re.compile(r"(?:(?!p?jit\()\w+\()*([\w.\-]+)\)*")


def vocabulary():
    """The program's scope names, or None for a program that has none."""
    try:
        from repro.models.scopes import VOCABULARY
    except ImportError:
        return None
    return tuple(VOCABULARY)


def _parts(path: str) -> List[str]:
    """The names in ``path``, unwrapped from their transformations."""
    out = []
    for part in re.split(r"[/;]", path):
        m = _WRAPPED.fullmatch(part)
        if m:
            out.append(m.group(1))
    return out


def scope_of(op_name: str, vocab) -> Optional[str]:
    """The outermost scope of ``vocab`` in ``op_name``'s path; of the
    first path that has one where the compiler merged instructions and
    joined their paths with ';'."""
    for path in op_name.split(";"):
        for part in _parts(path):
            if part in vocab:
                return part
    return None


def op_names(hlo_text: str, vocab) -> Dict[str, str]:
    """{instruction name: path} for every instruction of a compiled
    module's text ("" for one that has no path): its op_name, else that
    of the computation a fusion calls, else that of its users (see the
    module's notes)."""
    names: Dict[str, str] = {}
    first: Dict[str, str] = {}               # by computation
    calls: Dict[str, str] = {}               # instruction -> callee
    users: Dict[str, List[str]] = {}         # instruction -> its users
    members: Dict[str, List[str]] = {}       # computation -> instructions
    operands: Dict[str, List[str]] = {}
    computation = None
    for line in hlo_text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            computation = head.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        name = m.group(1)
        op = _OP_NAME.search(line)
        names[name] = op.group(1) if op else ""
        if op:
            first.setdefault(computation, op.group(1))
        callee = _CALLS.search(line)
        if callee:
            calls[name] = callee.group(1)
        members.setdefault(computation, []).append(name)
        operands[name] = _REFERENCE.findall(line[m.end():].split(
            ", metadata=")[0])
    for name, callee in calls.items():
        if not names[name] and callee in first:
            names[name] = first[callee]
    for group in members.values():
        inside = set(group)
        for name in group:
            for o in operands[name]:
                if o in inside and o != name:
                    users.setdefault(o, []).append(name)
    unscoped = [n for n in names if scope_of(names[n], vocab) is None]
    changed = True
    while changed:
        changed = False
        for name in unscoped:
            if scope_of(names[name], vocab) is not None:
                continue
            scoped = [names[u] for u in users.get(name, ())
                      if scope_of(names[u], vocab) is not None]
            if scoped and len({scope_of(p, vocab) for p in scoped}) == 1:
                names[name] = scoped[0]
                changed = True
    return names


@dataclasses.dataclass
class Credit:
    """Device seconds of a trace's ops, all chips."""
    scopes: Dict[str, float]    # by outermost scope
    within: Dict[str, float]    # by each scope, and RECOMPUTE, at any depth
    unscoped: float
    missing: float              # ops that are no instruction of the module
    total: float


def credit(ops: Dict[str, float], names: Dict[str, str], vocab) -> Credit:
    """Credit the op times ``ops`` ({instruction name: seconds}) by the
    map ``names`` ({instruction name: path})."""
    scopes: Dict[str, float] = {}
    within: Dict[str, float] = {}
    unscoped = missing = 0.0
    marks = set(vocab) | {RECOMPUTE}
    for op, seconds in ops.items():
        if op not in names:
            missing += seconds
        path = names.get(op, "")
        s = scope_of(path, vocab)
        if s is None:
            unscoped += seconds
        else:
            scopes[s] = scopes.get(s, 0.0) + seconds
        for mark in set(_parts(path)) & marks:
            within[mark] = within.get(mark, 0.0) + seconds
    return Credit(scopes, within, unscoped, missing, sum(ops.values()))


# ---------------------------------------------------------------- the step
def cell_of_command_line():
    """The cell ``bench/run.py --workload`` names, or None."""
    p = argparse.ArgumentParser(add_help=False, allow_abbrev=False)
    p.add_argument("--workload")
    args, _ = p.parse_known_args(sys.argv[1:])
    return harness.resolve(args.workload) if args.workload else None


def step_text(cell, devices) -> str:
    """The compiled text of ``cell``'s step as its runner
    (``bench/traffic/<kind>.py``) builds it on ``devices``, compiled with
    the persistent cache keyed on metadata too."""
    from repro.configs.base import ShapeConfig
    from repro.runtime import stepfn
    t = cell.traffic
    kind = STEP_KINDS[t["kind"]]
    arch, rt = steps.arch_and_runtime(cell)
    shape = ShapeConfig(cell.traffic_name, t["seq"], t["batch"], kind)
    build = getattr(stepfn, f"build_{kind}_step")
    bundle = build(arch, shape, rt, steps.mesh(t["mesh"], devices))
    key = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, key)
    jax.config.update(key, True)
    try:
        return bundle.lower().compile().as_text()
    finally:
        jax.config.update(key, before)


def _credit(ctx) -> Optional[Credit]:
    """The window's credit, computed once for all the readers of one
    run (they share ``ctx``); None without a map, with an op the map does
    not hold, or without scopes."""
    if "scope_credit" not in ctx:
        ctx["scope_credit"] = None
        vocab = vocabulary()
        cell = cell_of_command_line() if vocab else None
        if cell is not None and cell.traffic["kind"] in STEP_KINDS:
            text = step_text(cell, jax.devices()[:cell.chips])
            c = credit(ctx["trace"].ops, op_names(text, vocab), vocab)
            if c.scopes and not c.missing:
                ctx["scope_credit"] = c
    return ctx["scope_credit"]


def _per_step_ms(ctx, seconds: float) -> Optional[float]:
    n = ctx["counts"].get("steps")
    if not seconds or not n:
        return None
    return 1e3 * seconds / (ctx["chips"] * n)


def ms_per_step(ctx, scope: str) -> Optional[float]:
    """Device ms per step of the ops whose outermost scope is ``scope``."""
    c = _credit(ctx)
    return None if c is None else _per_step_ms(ctx, c.scopes.get(scope, 0.0))


def ms_within_per_step(ctx, mark: str) -> Optional[float]:
    """Device ms per step of the ops whose path names ``mark`` (a scope,
    or ``RECOMPUTE``) at any depth."""
    c = _credit(ctx)
    return None if c is None else _per_step_ms(ctx, c.within.get(mark, 0.0))


def unscoped_share(ctx) -> Optional[float]:
    """Share of the ops' device time that carries no scope, in %."""
    c = _credit(ctx)
    return None if c is None or not c.total else 100.0 * c.unscoped / c.total
