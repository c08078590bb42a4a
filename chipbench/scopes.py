"""Device ops tied to the program's named scopes, and the engine's host
spans, for the per-layer readers.

A device op in a trace carries its instruction's HLO text and no name
stack. The compiled program's own text (``Compiled.as_text()``) holds the
same instruction names, each with ``metadata={op_name="..."}``, the scope
path the program traced it under: ``jit(decode)/L027/mlp_wo/unpack/...``
for the bitstream unpack of the policy's ``L027.mlp_wo``. ``op_names``
reads that table; ``scope_of`` looks an op up in it.

The engine emits its host spans (``engine.step``, ``engine.launch``, ...)
as profiler annotations on the host's clock, beside the ``bench.*`` spans.
``engine_spans`` reads them from the run's trace file; ``step_split``
breaks each ``engine.step`` into its launches and its direct children.
Where a program has no such scope or span, both come back empty.
"""
from __future__ import annotations

import os
import re
from typing import Dict, List, Optional, Tuple

import common

_INSTR = re.compile(r"\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_SITE = re.compile(r"L\d{3}$")

STEP = "engine.step"
LAUNCH = "engine.launch"

Span = Tuple[float, float, str]   # start ns, end ns, name


def op_names(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> its ``op_name`` ("" where it has none), over
    every computation of a compiled program's text."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            o = _OP_NAME.search(line)
            out[m.group(1)] = o.group(1) if o else ""
    return out


def scope_of(op, table: Dict[str, str]) -> Optional[str]:
    """The op's scope path, or None where its instruction is not in the
    program's text."""
    m = _INSTR.match(op.name)
    return table.get(m.group(1)) if m else None


def under(path: str, scope: str) -> bool:
    return scope in path.split("/")


def site(path: str) -> Optional[Tuple[str, str]]:
    """(``L027``, ``mlp_wo``): the policy site a scope path runs in."""
    parts = path.split("/")
    for a, b in zip(parts, parts[1:]):
        if _SITE.match(a):
            return a, b
    return None


def decode_text(cell) -> str:
    """The compiled decode program's text, from the same lowering that
    ``Cell.decode_cost`` runs."""
    import jax.numpy as jnp

    eng, n = cell.eng, cell.mix["slots"]
    return eng._decode.lower(eng.params, jnp.zeros((n, 1), jnp.int32),
                             jnp.zeros((n,), jnp.int32),
                             eng.state).compile().as_text()


def engine_spans(r) -> List[Span]:
    """Every ``engine.*`` host span of the run's trace, sorted by start."""
    import reduce_trace
    from jax.profiler import ProfileData

    path = reduce_trace.find(os.path.join(common.BENCH, "out", "trace",
                                          r.cell.run.cell["name"]))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("engine."):
                        out.append((ev.start_ns,
                                    ev.start_ns + ev.duration_ns, ev.name))
    return sorted(out)


def step_split(spans: List[Span], window: Tuple[float, float]):
    """For each ``engine.step`` inside the window: (its ns, the ns of the
    launches inside it, {direct child: ns})."""
    t0, t1 = window
    rest = sorted((s for s in spans if s[2] != STEP),
                  key=lambda s: (s[0], -s[1]))
    rows = []
    for a, b, name in spans:
        if name != STEP or a < t0 or b > t1:
            continue
        launch, children, end = 0.0, {}, a
        for s, e, n in rest:
            if s < a or e > b:
                continue
            if n == LAUNCH:
                launch += e - s
            if s >= end:   # not nested in the last direct child
                children[n] = children.get(n, 0.0) + e - s
                end = e
        rows.append((b - a, launch, children))
    return rows
