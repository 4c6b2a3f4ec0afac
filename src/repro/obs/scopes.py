"""Named scopes of a compiled program's ops, read from its HLO text.

A profiler trace of a TPU names each device op by its HLO instruction
alone, without the ``op_name`` path that carries the ``jax.named_scope``
it was traced under.  :func:`hlo_scopes` reads that path from the text
of the compiled module (``jax.jit(f).lower(...).compile().as_text()``)
so that a trace's op times can be put down to the program's scopes.
"""
from __future__ import annotations

import re

_HLO_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.-]+) \(")
_HLO_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.-]+) = ")
_HLO_OP_NAME = re.compile(r'op_name="([^"]*)"')
_WRAPPED = re.compile(r"^(?:[\w.-]+\()*(.*?)\)*$")
_HLO_CALLEE = re.compile(
    r"(?:calls|body|condition|to_apply)=%?([\w.-]+)"
    r"|(?:branch|called)_computations=\{([^}]*)\}")


def _scope_of(path: str, scopes):
    """The first component of an ``op_name`` path that names one of
    ``scopes``, unwrapped from the transformations around it
    (``vmap(optimizer)``, ``transpose(jvp(ssd_scan))``); else ``None``."""
    for part in path.split("/"):
        name = _WRAPPED.match(part).group(1)
        if name in scopes:
            return name
    return None


def hlo_scopes(text: str, scopes) -> dict:
    """``{instruction: scope}`` of a compiled HLO module's text: the
    first component of each instruction's ``op_name`` that names one
    of ``scopes`` (:func:`_scope_of`).

    XLA leaves some instructions it builds without metadata (a fusion
    holding the f64 halves of a scatter, the ops of a conditional's
    branch).  Such an instruction takes the one scope named inside the
    computations it calls, else the scope of the instruction that calls
    its own computation."""
    own, comp_of, callees, members = {}, {}, {}, {}
    comp = None
    for line in text.splitlines():
        head = _HLO_COMPUTATION.match(line)
        if head:
            comp = head.group(1)
            continue
        inst = _HLO_INSTRUCTION.match(line)
        if inst is None:
            continue
        name = inst.group(1)
        comp_of[name] = comp
        members.setdefault(comp, []).append(name)
        path = _HLO_OP_NAME.search(line)
        scope = _scope_of(path.group(1), scopes) if path else None
        if scope is not None:
            own[name] = scope
        called = []
        for single, many in _HLO_CALLEE.findall(line):
            called += [single] if single else [
                c.strip().lstrip("%") for c in many.split(",")]
        if called:
            callees[name] = called

    inner: dict = {}

    def named_inside(c):
        """Scopes named in computation ``c`` and everything it calls."""
        if c not in inner:
            inner[c] = set()
            found = set()
            for n in members.get(c, ()):
                if n in own:
                    found.add(own[n])
                for cc in callees.get(n, ()):
                    found |= named_inside(cc)
            inner[c] = found
        return inner[c]

    for name, called in callees.items():
        if name not in own:
            found = set().union(*(named_inside(c) for c in called))
            if len(found) == 1:
                own[name] = found.pop()
    comp_scope: dict = {}
    changed = True
    while changed:
        changed = False
        for name, called in callees.items():
            for c in called:
                if name in own and c not in comp_scope:
                    comp_scope[c] = own[name]
                    changed = True
        for name, c in comp_of.items():
            if name not in own and c in comp_scope:
                own[name] = comp_scope[c]
                changed = True
    return own
