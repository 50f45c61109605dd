"""Reader ``scope_op``: device time per traced module of the ops that a part
of the program built.

Parameters, in the metric's file: ``scope``, a regex over the op's scope path
(``hlo_scope.scopes_from_hlo``: ``transpose(jvp(step.forward))/.../
MaxPool2D.pool0/op.Pooling/pad``, or ``unscoped``), and ``kind``, a regex over
its tag (``hlo_tag.tag``: ``other.pad.36.bf16-64x64x224x224``), both of which
must match.  The value is the plain sum of the matching ops' device time on
device 0, in ms per traced module; None in a run that was not traced, and
None when nothing matches, as for a program that carries no such scope.

It joins two facts every runner already returns: ``facts["trace"]["ops"]``
(tag, start, duration) and ``facts["programs"][0].as_text()``.  The join
(tag -> instruction name -> scope path) is made once per run, kept in
``facts["scope_op"]`` and logged as the ten heaviest ops with their scope
and what else their fusion holds.
"""
from __future__ import annotations

import re

from perfbench import hlo_scope

_KIND = re.compile(r"^[a-z]+\.")
_SHAPE = re.compile(
    r"\.(?:pred|[suf]\d+|bf16|c64|c128)-(?:scalar|\d+(?:x\d+)*)$")


def instruction(tag: str) -> str:
    """The HLO instruction name inside a tag: ``other.fusion.570.bf16-64``
    gives ``fusion.570``."""
    return _SHAPE.sub("", _KIND.sub("", tag, count=1))


def scope_table(facts: dict) -> dict:
    """``{tag: (scope path, what else its fusion holds)}`` for the ops of the
    traced window, made on first use and kept in ``facts``."""
    if "scope_op" not in facts:
        hlo_text = facts["programs"][0].as_text()
        scopes = hlo_scope.scopes_from_hlo(hlo_text)
        table = {}
        for tag, _, _ in facts["trace"]["ops"]:
            if tag not in table:
                name = instruction(tag)
                table[tag] = (scopes.get(name, hlo_scope.UNSCOPED),
                              hlo_scope.mixed(hlo_text, name))
        facts["scope_op"] = table
        for tag, seconds in facts["trace"]["device_ops"]:
            print("scope_op: %.6fs %s | %s | %s"
                  % (seconds, tag, table[tag][0],
                     " ".join(table[tag][1]) or "-"), flush=True)
    return facts["scope_op"]


def read(spec: dict, facts: dict):
    """The metric's value from ``facts``, or None."""
    trace = facts.get("trace")
    if not trace or not trace["n_modules"]:
        return None
    table = scope_table(facts)
    scope, kind = re.compile(spec["scope"]), re.compile(spec["kind"])
    ns = sum(dur for tag, _, dur in trace["ops"]
             if kind.search(tag) and scope.search(table[tag][0]))
    return ns * 1e-6 / trace["n_modules"] if ns > 0 else None
