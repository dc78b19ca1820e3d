"""Device time by the program's own phases: which named scope each device op
of a program belongs to.

The step, chunk and eval programs enter ``jax.named_scope``s (``llm.fwd_bwd``,
``llm.mixer.attention.core``, ``fl.local_sgd``, ...; PERF.md section 3 has the
table).  A device profile names each op by its HLO instruction (``fusion.140``)
and nothing else; the executable's optimised HLO text names every instruction
with ``metadata={op_name="jit(..)/llm.fwd_bwd/transpose(jvp(Transformer))/
layer_1/mlp/llm.mlp/w_up/dot_general"}``, the scopes, the transforms and the
flax module path all in it.  This module is the table between the two:

- the timed paths call :func:`note_program` once a program (one slot a name:
  ``llm.step``, ``sim.chunk``, ``sim.eval``; the newest wins).  What is kept
  is the compiled program, or the jitted function with the ABSTRACT arguments
  of the call that ran it: no device array, no trainer, no simulator;
- :func:`scope_map` reads the text the first time somebody asks (a span
  ``obs.scope_map`` records what that cost and that it compiled nothing: jit's
  in-memory cache answers ``lower(..).compile()``) and keeps the result;
- :func:`device_seconds_by` joins a profile's ``{op: seconds}`` with it.

A fusion carries its own instruction's metadata, which XLA takes from the
fusion's root: time inside one fusion that spans two scopes goes to the
root's (XProf reads the same field).  The persistent compilation cache's key
leaves the metadata out (``jax/_src/cache_key.py``), so a scope edit shows in
the map only in a program compiled after it.
"""

from __future__ import annotations

import re
from collections import defaultdict
from typing import Any, Optional, Union

import jax

from .trace import XLA_COUNTERS, traced

__all__ = ["note_program", "scope_map", "device_seconds_by", "parse_op_name", "parse_hlo_text"]

#: a path component of ``op_name`` that is one of the program's named scopes
SCOPE = re.compile(r"^(llm|fl)\.[\w.]+$")
_WRAPPED = re.compile(r"^([\w\-]+)\((.*)\)$")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = ")
_FUSED = re.compile(r" fusion\([^\n]*?calls=%?([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')

_noted: dict[str, tuple] = {}               # name -> (program, abstract args or None)
_maps: dict[str, Optional[dict]] = {}       # name -> what scope_map made of it


def _abstract(x):
    if not isinstance(x, jax.Array):
        return x
    return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding if x.committed else None)


def note_program(name: str, program: Any, args: Optional[tuple] = None) -> None:
    """Publish ``program`` as what runs under ``name``: a
    ``jax.stages.Compiled``, or a jitted function with the arguments of the
    call that ran it (arrays are kept as ``ShapeDtypeStruct``s with their
    shardings, so nothing noted holds device memory)."""
    _noted[name] = (program, None if args is None else jax.tree_util.tree_map(_abstract, args))
    _maps.pop(name, None)


def parse_op_name(op_name: str) -> dict:
    """``op_name`` -> ``{"op_name", "scopes": [outer, .., inner], "scope":
    inner or "", "pass"}``.  A scope is a path component that matches
    ``SCOPE`` once its transform wrappers (``jvp(..)``, ``transpose(..)``,
    ``vmap(..)``) are opened; a repeated one (a rematerialised op keeps its
    forward path behind the backward's) counts once.  ``pass``: ``bwd`` under
    a ``transpose(``, or ``remat`` where a ``rematted_computation`` follows it
    (``jax.checkpoint``'s recomputed forward); ``fwd`` under a ``jvp(`` alone;
    empty outside a gradient."""
    scopes, wrappers = [], set()
    parts = op_name.split("/")
    for part in parts:
        while (m := _WRAPPED.match(part)):
            wrappers.add(m.group(1))
            part = m.group(2)
        if SCOPE.match(part) and part not in scopes:
            scopes.append(part)
    if "transpose" in wrappers:
        which = "remat" if "rematted_computation" in parts else "bwd"
    else:
        which = "fwd" if "jvp" in wrappers else ""
    return {"op_name": op_name, "scopes": scopes, "scope": scopes[-1] if scopes else "", "pass": which}


def parse_hlo_text(text: str) -> dict[str, dict]:
    """Optimised HLO text -> ``{instruction name: parse_op_name(its op_name)}``
    for every instruction that can be a device op of its own: those inside a
    fused computation are their fusion's.  An instruction without metadata
    has an empty ``op_name``."""
    fused = set(_FUSED.findall(text))
    out, skip = {}, False
    for line in text.splitlines():
        if not line.startswith(" "):
            m = _COMPUTATION.match(line)
            skip = bool(m) and m.group(1) in fused
        elif not skip and (m := _INSTRUCTION.match(line)):
            named = _OP_NAME.search(line)
            out[m.group(1)] = parse_op_name(named.group(1) if named else "")
    return out


def scope_map(name: str) -> Optional[dict[str, dict]]:
    """The noted program's instructions by name (``parse_hlo_text``), built on
    the first call and kept; ``None`` where nothing was noted under ``name``
    or the executable gives no text (a program bound from the AOT store)."""
    if name not in _maps and name in _noted:
        program, args = _noted[name]
        with traced("obs.scope_map", counters=XLA_COUNTERS, program=name) as span:
            compiled = program if args is None else program.lower(*args).compile()
            text = compiled.as_text() or ""
            ops = parse_hlo_text(text)
            span.attrs.update(ops=len(ops), scoped_ops=sum(1 for o in ops.values() if o["scope"]),
                              text_bytes=len(text))
        _maps[name] = ops or None
    return _maps.get(name)


def device_seconds_by(op_seconds: dict[str, float], scopes: dict[str, dict],
                      key: Union[str, "re.Pattern"]) -> dict[str, float]:
    """A profile's ``{instruction name: seconds}`` summed by ``key``:
    ``"scope"`` (the innermost scope), ``"pass"``, or a regular expression
    over ``op_name`` (by the text it matched).  What no entry of the map
    names, what has no scope or pass, and what the expression does not match
    are summed under ``""``."""
    pattern = None if key in ("scope", "pass") else re.compile(key)
    out: dict[str, float] = defaultdict(float)
    for op, seconds in op_seconds.items():
        entry = scopes.get(op)
        if entry is None:
            group = ""
        elif pattern is None:
            group = entry[key]
        else:
            m = pattern.search(entry["op_name"])
            group = m.group(0) if m else ""
        out[group] += seconds
    return dict(out)
