"""Names for device operations that a later reader of the ledger can parse.

A traced device op is named after its HLO instruction.  ``tag()`` turns that
into ``<conv|dot|coll|other>.<hlo-name>.<dtype>-<dims joined by x>`` made of
letters, digits, ``_``, ``.`` and ``-`` alone, for example
``conv.fusion.12.bf16-256x64x56x56``.  Whether a fusion holds a convolution
is not in the trace: ``kinds_from_hlo()`` reads it from the text of the
compiled program (``compiled.as_text()``), by looking into the computation
each fusion calls.  On the TPU a dense layer's ``dot`` is lowered to a
``convolution`` too, so ``conv`` reads "work of the matrix unit".
"""
from __future__ import annotations

import re

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
                "collective-permute", "all-to-all")
_NAME = re.compile(r"^\s*(?:ROOT\s+)?%?([A-Za-z_][\w.\-]*)")
_SHAPE = re.compile(r"\b(pred|[suf]\d+|bf16|c64|c128)\[([\d,]*)\]")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$")
_OPCODE = re.compile(r"=\s*(?:\([^=]*?\)|\S+)\s+([a-z][\w\-]*)\(")


def op_name(text: str) -> str:
    """The instruction's own name: ``%fusion.12 = bf16[..] fusion(..)`` and
    a bare ``fusion.12`` both give ``fusion.12``."""
    m = _NAME.match(text)
    return m.group(1) if m else "unnamed"


def _is_collective(opcode: str) -> bool:
    return opcode.removesuffix("-start").removesuffix("-done") in _COLLECTIVES


def _own_kind(line: str):
    """conv / dot / coll when the instruction itself is one, else None."""
    m = _OPCODE.search(line)
    opcode = m.group(1) if m else ""
    if opcode == "convolution":
        return "conv"
    if opcode == "dot":
        return "dot"
    return "coll" if _is_collective(opcode) else None


def kinds_from_hlo(hlo_text: str) -> dict:
    """``{instruction name: "conv"|"dot"|"coll"}`` for every instruction of
    the compiled program that is one of those, or a fusion (or call) whose
    computation holds one.  Instructions not in the dict are ``other``."""
    inside = {}   # computation -> kind of the strongest op it holds
    calls = {}    # instruction -> computation it calls
    kinds = {}
    current = None
    for line in hlo_text.splitlines():
        head = _COMPUTATION.match(line)
        if head and "=" not in line.split("(", 1)[0]:
            current = head.group(1)
            continue
        if line.strip() == "}":
            current = None
            continue
        if "=" not in line:
            continue
        name = op_name(line)
        kind = _own_kind(line)
        if kind:
            kinds[name] = kind
            if current and inside.get(current) != "conv":
                inside[current] = kind
        called = _CALLS.search(line)
        if called:
            calls[name] = called.group(1)
    for name, comp in calls.items():
        if name not in kinds and comp in inside:
            kinds[name] = inside[comp]
    return kinds


def tag(text: str, kinds: dict | None = None) -> str:
    """The ledger's name for the traced op whose event name is ``text``."""
    name = op_name(text)
    kind = (kinds or {}).get(name) or _own_kind(text)
    if kind is None:
        kind = "coll" if _is_collective(name.split(".")[0]) else "other"
    shape = _SHAPE.search(text.split("=", 1)[1]) if "=" in text else None
    out = "%s.%s" % (kind, name)
    if shape:
        out += ".%s-%s" % (shape.group(1),
                           "x".join(shape.group(2).split(",")) or "scalar")
    return re.sub(r"[^A-Za-z0-9_.\-]", "_", out)
