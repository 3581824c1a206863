"""Device seconds by ``jax.named_scope``: the join between a compiled
program's text and a device trace.

``nn.Layer.__call__``, the autograd engine (``bwd/<layer path>``), the
optimizer's step and the serving stack (``layer3/scatter_kv``) run their
ops under named scopes. XLA keeps the scope path in every instruction's
``metadata={op_name="jit(step)/jit(main)/gpt/layers.3/attn/dot_general"}``,
and a device trace names each executed instruction (``%copy.12``, not the
bare ``copy``). This module puts the two together, so that a trace whose top
line reads ``fusion`` or ``copy`` can say under which layer that time runs.
"""
from __future__ import annotations

import re
from typing import Dict, Mapping

__all__ = ["scope_seconds", "scope_of", "NO_SCOPE"]

#: where an instruction's seconds go when the text gives it no ``op_name``
#: or when the text does not hold the instruction at all
NO_SCOPE = "(no scope)"

_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s(.*)$", re.M)
_OP_NAME = re.compile(r'\bop_name="([^"]*)"')
_OPERAND = re.compile(r"%([\w.\-]+)")
#: `jit(step)`, `jvp(...)`, `transpose(jvp(...))`: wrappers of the call and of
#: jax's own transformations, not scopes anybody wrote
_WRAPPER = re.compile(r"^\w+\(.*\)$")


def scope_of(op_name: str) -> str:
    """`jit(step)/jit(main)/gpt/layers.3/attn/dot_general` -> `gpt/layers.3/
    attn`: the path less the call wrappers and the primitive at its end;
    `caches[3][0]` -> `(caches[3][0])`."""
    if "/" not in op_name and not _WRAPPER.match(op_name):
        # no path at all: an argument's own name (`caches[3][0]`, on the
        # copy that relays the argument out before any scope's op reads
        # it) or a primitive the compiler expanded outside every scope
        return f"({op_name})"
    parts = [p for p in op_name.split("/") if not _WRAPPER.match(p)][:-1]
    return "/".join(parts) or NO_SCOPE


def scope_seconds(hlo_text: str,
                  instr_seconds: Mapping[str, float]) -> Dict[str, float]:
    """Device seconds by scope, longest first.

    ``hlo_text`` is a compiled program's text (``lowered.compile().
    as_text()``: the instructions the device runs, fusions under the name
    the trace gives them, each with the ``op_name`` of its root).
    ``instr_seconds`` maps an instruction of THAT program as the trace
    names it (``%copy.12`` or ``copy.12``) to its device seconds; the
    caller chooses what goes in — all of a program's instructions, or
    only its ``copy.*`` to learn where the copies run. An instruction with
    no ``op_name`` takes that of the value it reads (first operand, up to 8
    steps back); one the text lacks lands under ``NO_SCOPE``."""
    named, operand = _op_names(hlo_text)
    out: Dict[str, float] = {}
    for instr, secs in instr_seconds.items():
        instr, hops = instr.lstrip("%"), 0
        while instr not in named and instr in operand and hops < 8:
            instr, hops = operand[instr], hops + 1
        op_name = named.get(instr)
        scope = scope_of(op_name) if op_name else NO_SCOPE
        out[scope] = out.get(scope, 0.0) + float(secs)
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def _op_names(hlo_text: str):
    """({instruction: op_name}, {instruction without one: its first
    operand}). The compiler adds instructions of its own with no metadata
    (the copy that puts a scatter's result back into the argument's
    layout): such a one is billed to the scope of the value it moves."""
    named, operand = {}, {}
    for name, rest in _INSTRUCTION.findall(hlo_text):
        m = _OP_NAME.search(rest)
        if m:
            named[name] = m.group(1)
        else:
            m = _OPERAND.search(rest)
            if m:
                operand[name] = m.group(1)
    return named, operand
