"""Which part of the program built each instruction of a compiled step.

The program names itself while it is traced: ``jax.named_scope`` on the
step's phases (``step.forward``, ``step.update``, ...), on every Gluon block
(``<Class>.<name>``) and on every op (``op.<name>``), and JAX adds
``jvp(...)`` on the forward pass and ``transpose(jvp(...))`` on the backward
pass.  The compiler keeps the path on each instruction as
``metadata={op_name="jit(step)/transpose(jvp(step.forward))/.../op.Pooling/pad"}``.
``scopes_from_hlo()`` reads it from ``compiled.as_text()``, beside
``hlo_tag.kinds_from_hlo()``: every instruction gets exactly ONE scope path,
so sums over disjoint patterns of paths partition the step.  A program that
names nothing (an older checkout) still has JAX's own elements in its paths,
and no ``step.*`` one.
"""
from __future__ import annotations

import collections
import functools
import re

from perfbench import hlo_tag

UNSCOPED = "unscoped"

_OP_NAME = re.compile(r'op_name="([^"]*)"')
_JIT = re.compile(r"^p?jit\(.*\)$")
_WRAPPED = re.compile(r"^\w+\((.*)\)$")


def scope_path(op_name: str) -> str:
    """``op_name`` as a scope path: the first entry where XLA joined the
    names of merged instructions with ``;``, without its ``jit(...)``
    elements.  The last element is the primitive (``pad``, ``mul``)."""
    first = op_name.split(";", 1)[0]
    return "/".join(e for e in first.split("/") if e and not _JIT.match(e))


@functools.lru_cache(maxsize=1)  # one program's text is asked about many times
def _parse(hlo_text: str):
    """``(own, members, calls)``: each instruction's own scope path ('' for
    none), the paths of each computation's instructions, and the computation
    each fusion or call calls."""
    own, calls = {}, {}
    members = collections.defaultdict(list)
    current = None
    for line in hlo_text.splitlines():
        head = hlo_tag._COMPUTATION.match(line)
        if head and "=" not in line.split("(", 1)[0]:
            current = head.group(1)
            continue
        if line.strip() == "}":
            current = None
            continue
        if "=" not in line:
            continue
        name = hlo_tag.op_name(line)
        m = _OP_NAME.search(line)
        own[name] = scope_path(m.group(1)) if m else ""
        if current and own[name]:
            members[current].append(own[name])
        called = hlo_tag._CALLS.search(line)
        if called:
            calls[name] = called.group(1)
    return own, members, calls


def scopes_from_hlo(hlo_text: str) -> dict:
    """``{instruction name: scope path}`` for every instruction of the
    compiled program.  An instruction's scope is its own ``op_name``; a
    fusion or call without one takes the commonest path among the
    instructions of the computation it calls; with neither it is
    ``unscoped``."""
    own, members, calls = _parse(hlo_text)
    scopes = {}
    for name, path in own.items():
        inside = members.get(calls.get(name))
        if not path and inside:
            path = collections.Counter(inside).most_common(1)[0][0]
        scopes[name] = path or UNSCOPED
    return scopes


def leaf(path: str) -> str:
    """The innermost named element of a scope path: the path without its
    primitive, cut to its last element (``op.Pooling``,
    ``BatchNorm.stage1_batchnorm0``, ``step.update``), out of the
    ``jvp(...)`` or ``transpose(...)`` JAX may have put around it."""
    last = path.rsplit("/", 1)[0].rsplit("/", 1)[-1]
    while _WRAPPED.match(last):
        last = _WRAPPED.match(last).group(1)
    return last


def mixed(hlo_text: str, name: str) -> list:
    """The distinct leaves inside the computation that the fusion ``name``
    calls, sorted: what else was fused into an op that carries one scope.
    For the log only."""
    _, members, calls = _parse(hlo_text)
    return sorted({leaf(p) for p in members.get(calls.get(name), ())})
