"""Device scopes — which layer of the validator each compiled instruction
belongs to.

The fused encode -> score -> top-k step names its parts with
``jax.named_scope``: ``encode`` (the passage encoder), ``score`` (query x
passage scores and the validity mask) and ``merge`` (the running top-k
fold).  Inside an expert trunk's encoder, ``moe`` is the expert layer
(router, dispatch, held and shared experts, combine) and ``experts`` its
grouped products alone; the innermost scope names an instruction.  The compiler keeps the scope in each instruction's
``metadata={op_name="jit(fused_window)/while/body/.../merge/..."}``, but a
TPU profile names a device operation by its HLO text alone: operand types
written out, no metadata, no backend configuration.  :func:`hlo_scopes`
reads a compiled program's text and returns the map a trace reader needs:
the instruction as the profile names it -> its scope.  A profile cuts very
long names short; :func:`op_head` (name, result type and opcode) is the key
to fall back on there.

Instructions the compiler adds (layout copies, bitcasts, tuple plumbing)
often carry no ``op_name``; they take the scope of the first operand that
has one, else of the first user that has one, so a copy of an encoder
output counts as encode.  Parameters, and instructions that touch no scoped
instruction, stay unscoped.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

__all__ = ["SCOPES", "hlo_scopes", "module_name", "op_head",
           "scope_of_op_name"]

#: the fused step's layers, as ``jax.named_scope`` names them
SCOPES: Tuple[str, ...] = ("encode", "score", "merge", "moe", "experts")

_MODULE = re.compile(r"^HloModule ([^\s,]+)", re.M)
_NAME = re.compile(r"(?:ROOT\s+)?(%[^\s=]+) = ")
_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_INDEX = re.compile(r"^/\*index=\d+\*/")
# attributes that the profile leaves out of an operation's name
_DROPPED = re.compile(r",\s*(?:metadata|backend_config|frontend_attributes|"
                      r"sharding|statistics|origin)=")


def scope_of_op_name(op_name: str) -> Optional[str]:
    """The innermost of :data:`SCOPES` named as a component of
    ``op_name``."""
    for part in reversed(op_name.split("/")):
        if part in SCOPES:
            return part
    return None


def module_name(hlo_text: str) -> str:
    """The program's name as a profile's ``XLA Modules`` line gives it
    (``jit_fused_window``): every window variant shares it."""
    m = _MODULE.search(hlo_text)
    return m.group(1) if m else ""


def op_head(text: str) -> str:
    """``%name = type opcode``: an instruction's text up to its operands."""
    m = _NAME.match(text)
    if m is None:
        return text
    end = _close(text, m.end()) if text[m.end():m.end() + 1] == "(" \
        else text.find(" ", m.end())
    op = _OPCODE.match(text, end) if end >= 0 else None
    return text[:op.end() - 1] if op else text


def _close(text: str, i: int) -> int:
    """Index just past the bracket group that opens at ``text[i]``."""
    depth = 0
    for j in range(i, len(text)):
        c = text[j]
        if c in "([{":
            depth += 1
        elif c in ")]}":
            depth -= 1
            if depth == 0:
                return j + 1
    return len(text)


def _split_top(text: str) -> List[str]:
    """``text`` split at the commas outside any bracket."""
    parts, depth, start = [], 0, 0
    for j, c in enumerate(text):
        if c in "([{":
            depth += 1
        elif c in ")]}":
            depth -= 1
        elif c == "," and depth == 0:
            parts.append(text[start:j].strip())
            start = j + 1
    tail = text[start:].strip()
    if tail:
        parts.append(tail)
    return parts


@dataclasses.dataclass
class _Instr:
    name: str
    type: str
    opcode: str
    operands: List[str]
    attrs: str          # what follows the operands, up to the dropped ones
    op_name: str


def _parse(line: str) -> Optional[_Instr]:
    text = line.strip()
    m = _NAME.match(text)
    if m is None:
        return None
    i = m.end()
    end = _close(text, i) if text[i:i + 1] == "(" else text.find(" ", i)
    op = _OPCODE.match(text, end) if end >= 0 else None
    if op is None:
        return None
    close = _close(text, op.end() - 1)
    operands = [_INDEX.sub("", o) for o in
                _split_top(text[op.end():close - 1])]
    rest = text[close:]
    dropped = _DROPPED.search(rest)
    meta = _OP_NAME.search(rest)
    return _Instr(m.group(1), text[i:end], op.group(1), operands,
                  rest[:dropped.start()] if dropped else rest,
                  meta.group(1) if meta else "")


def _profile_text(ins: _Instr, types: Dict[str, str]) -> str:
    """The instruction as a TPU profile names it: each operand preceded by
    its type."""
    ops = ", ".join(f"{types[o]} {o}" if o in types else o
                    for o in ins.operands)
    return f"{ins.name} = {ins.type} {ins.opcode}({ops}){ins.attrs}"


def hlo_scopes(compiled) -> Dict[str, str]:
    """{instruction text as a TPU profile names it: scope} for a compiled
    program (anything with ``as_text()``, or that text itself)."""
    text = compiled if isinstance(compiled, str) else compiled.as_text()
    instrs = [i for i in map(_parse, text.splitlines()) if i is not None]
    types = {i.name: i.type for i in instrs}
    scope = {i.name: scope_of_op_name(i.op_name) for i in instrs}
    users: Dict[str, List[str]] = {}
    for i in instrs:
        for o in i.operands:
            users.setdefault(o, []).append(i.name)
    plumbing = [i for i in instrs if i.opcode != "parameter"]
    for i in plumbing:
        if scope[i.name] is None:
            scope[i.name] = next((scope[o] for o in i.operands
                                  if scope.get(o)), None)
    for i in reversed(plumbing):
        if scope[i.name] is None:
            scope[i.name] = next((scope[u] for u in users.get(i.name, ())
                                  if scope.get(u)), None)
    return {_profile_text(i, types): scope[i.name] for i in instrs
            if scope[i.name] is not None}
