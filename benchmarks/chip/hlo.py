"""What the compiled program says about its own instructions.

A device trace names each operation by its instruction name in the
compiled HLO module (``fusion.12``, ``all-reduce-start.3``). This module
reads ``compiled.as_text()`` once and gives, for every instruction, its
opcode, the element type and count of its result, and for a fusion or a
custom call what it holds: the collectives it wraps, the custom call's
target, and a Pallas kernel's name. The trace reduction names and
classifies operations with it. The collective parsing follows
``chip_smoke.py::collectives``.
"""
from __future__ import annotations

import base64
import re

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")

_COMP = re.compile(r"^(?:ENTRY\s+)?%?(?P<name>[\w.\-]+)")
_INSTR = re.compile(
    r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=\s*(?P<type>.+?)\s"
    r"(?P<op>[a-z][\w\-]*)\((?P<rest>.*)$"
)
_ARRAY = re.compile(r"\b([a-z]+\d*)\[([\d,]*)\]")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_OPNAME = re.compile(r'op_name="([^"]+)"')
_BODY = re.compile(r'"body":"([A-Za-z0-9+/=]+)"')
_KERNEL_FN = re.compile(rb"[A-Za-z_][A-Za-z0-9_]*_kernel\b")


def _arrays(type_str):
    out = []
    for dtype, dims in _ARRAY.findall(type_str):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        out.append((dtype, n))
    return out


def collective_kind(opcode: str) -> str | None:
    """'all-reduce' for all-reduce, all-reduce-start and all-reduce-done."""
    for kind in COLLECTIVES:
        if opcode == kind or opcode.startswith(kind + "-"):
            return kind
    return None


def _kernel_name(rest: str):
    """A Pallas kernel's function name, from the Mosaic module the custom
    call carries (base64, with the kernel's source locations in it)."""
    b = _BODY.search(rest)
    if not b:
        return None
    try:
        found = _KERNEL_FN.findall(base64.b64decode(b.group(1)))
    except ValueError:
        return None
    return found[0].decode() if found else None


def instructions(text: str) -> dict:
    """{instruction name: info} over every computation of the module.

    info: ``opcode``; ``arrays`` [(dtype, elements)] of the result;
    ``computation`` it lives in; ``op_name``, the source-level name the
    compiler kept; ``calls`` the computation a fusion calls; ``target`` and
    ``kernel`` of a custom call."""
    out, comp = {}, None
    for line in text.splitlines():
        if line[:1].strip() and line.rstrip().endswith("{"):
            comp = _COMP.match(line)["name"]
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        info = {"opcode": m["op"], "arrays": _arrays(m["type"]),
                "computation": comp}
        o = _OPNAME.search(m["rest"])
        if o:
            info["op_name"] = o.group(1)
        c = _CALLS.search(m["rest"])
        if c:
            info["calls"] = c.group(1)
        t = _TARGET.search(m["rest"])
        if t:
            info["target"] = t.group(1)
            k = _kernel_name(m["rest"])
            if k:
                info["kernel"] = k
        out[m["name"]] = info
    return out


def collective_of(name: str, instrs: dict):
    """(kind, dtype, elements) of the collective that instruction `name`
    runs, directly or inside the fusion it calls; None for any other."""
    info = instrs.get(name)
    if info is None:
        return None
    kind = collective_kind(info["opcode"])
    if kind is None and "calls" in info:
        inner = [i for i in instrs.values()
                 if i["computation"] == info["calls"]
                 and collective_kind(i["opcode"])]
        if inner:
            info = inner[0]
            kind = collective_kind(info["opcode"])
    if kind is None:
        return None
    arrays = [a for a in info["arrays"] if a[0] not in ("u32", "s32")
              or a[1] > 1] or info["arrays"]
    big = max(arrays, key=lambda a: a[1]) if arrays else ("", 0)
    return kind, big[0], big[1]
