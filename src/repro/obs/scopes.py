"""Device scopes: the layer names every operation of a step carries.

The model, step and sync code open ``jax.named_scope(<scope>)`` around
each layer. A scope costs nothing at run time: it only lands in the
``op_name`` metadata of the HLO instructions traced inside it, forward,
backward (``transpose(jvp(...))``) and rematerialised alike. A profiler
trace names each device operation by its HLO instruction, so joining the
trace's operations to the compiled program's text (:func:`hlo_scopes`)
gives every device-second a layer.

====================  ==================================================
``embed``             the embedding lookup
``attention``         ln1, the GQA / MLA mixer and its residual add
``ssm``               the same for the Mamba mixer
``ssd``               the Mamba mixer's chunked SSD scan, inside ``ssm``
``mlp``               ln2, the dense SwiGLU / MLP and its residual add
``moe``               ln2, the routed-expert FFN and its residual add
``head``              final norm, the head matmul, pad masking and the
                      fp32 logsumexp / cross-entropy of the loss
``grad_accum``        the gradient accumulator: zero-init and ``g_acc + g``
``optimizer``         learning rate, clipping and the AdamW update
``sync``              the gradient all-reduce (per leaf, bucketed, int8)
====================  ==================================================

Only ``ssd`` nests, inside ``ssm``; where a scope nests or a trace
context wraps one (``jvp(head)``), the innermost vocabulary word of the
path is the scope.
"""
from __future__ import annotations

import re
from collections import Counter, defaultdict

__all__ = ["DEVICE_SCOPES", "scope_of", "hlo_scopes"]

DEVICE_SCOPES = ("embed", "attention", "ssm", "ssd", "mlp", "moe", "head",
                 "grad_accum", "optimizer", "sync")

_WORD = re.compile(r"[^/()]+")
_MODULE = re.compile(r"^HloModule ([\w.\-]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%([\w.\-]+)")


def scope_of(op_name: str) -> str | None:
    """The innermost :data:`DEVICE_SCOPES` word of an ``op_name`` path
    (``jit(step)/transpose(jvp())/while/body/attention/dot_general`` ->
    ``attention``), or None."""
    found = None
    for word in _WORD.findall(op_name):
        if word in DEVICE_SCOPES:
            found = word
    return found


def hlo_scopes(hlo_text: str) -> tuple[str | None, dict[str, str | None]]:
    """``(module name, {instruction name: scope or None})`` of a compiled
    module's text (``compiled.as_text()``), over every computation.

    An instruction whose own ``op_name`` names no scope but that calls a
    computation (a fusion whose root carries no metadata, as a bitcast
    does) takes the scope most instructions of that computation name.
    An instruction may span lines: a Pallas kernel's custom call holds
    newlines in its frontend attributes, and its metadata follows them.
    """
    module, comp, name = None, None, None
    scopes: dict[str, str | None] = {}
    calls: dict[str, str] = {}
    inside: dict[str, Counter] = defaultdict(Counter)
    for line in hlo_text.splitlines():
        if module is None:
            m = _MODULE.match(line)
            if m:
                module = m.group(1)
                continue
        m = _COMPUTATION.match(line)
        if m:
            comp, name = m.group(1), None
            continue
        m = _INSTRUCTION.match(line)
        if m:
            name = m.group(1)
            scopes[name] = None
        elif name is None or line.strip() in ("", "}"):
            name = None
            continue
        op = _OP_NAME.search(line)
        if op and scopes[name] is None:
            scopes[name] = scope_of(op.group(1))
            if scopes[name] is not None:
                inside[comp][scopes[name]] += 1
        called = _CALLS.search(line)
        if called:
            calls[name] = called.group(1)
    for name, comp in calls.items():
        if scopes[name] is None and inside[comp]:
            scopes[name] = inside[comp].most_common(1)[0][0]
    return module, scopes
