"""Reading a compiled program's text (``jax.stages.Compiled.as_text()``)
in tests: the arrays it makes outside fused computations, and which of
its parameters share a buffer with an output."""

import re

_COMPUTATION = re.compile(r"^(ENTRY )?%?([\w.\-]+) .*\{$")
_ARRAY = re.compile(r"^\s*(ROOT )?%?([\w.\-]+) = \w+\[([\d,]*)\]\S*\s+([\w\-]+)\(")
_PARAMETER = re.compile(r"^\s*%?[\w.\-]+ = \w+\[([\d,]*)\]\S*\s+parameter\((\d+)\)")
_ALIAS = re.compile(r"\{\d+\}: \((\d+), \{\}")


def _computations(text: str) -> tuple[dict[str, list[str]], str]:
    """Lines of each computation, and the entry computation's name."""
    out, name, entry = {}, None, None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m and not line.startswith(" "):
            name = m.group(2)
            out[name] = []
            if m.group(1):
                entry = name
        elif name is not None:
            out[name].append(line)
    return out, entry


def _dims(s: str) -> tuple[int, ...]:
    return tuple(int(d) for d in s.split(",") if d)


def materialized(text: str) -> list[tuple[str, tuple[int, ...], str]]:
    """(name, dims, kind) of every array an instruction outside fused
    computations makes: its opcode, or for a fusion the opcode of the
    fused computation's root (``fusion:scatter``)."""
    comps, _ = _computations(text)
    fused = set(re.findall(r"calls=%?([\w.\-]+)", text))
    root = {}
    for name, lines in comps.items():
        for line in lines:
            m = _ARRAY.match(line)
            if m and m.group(1):
                root[name] = m.group(4)
    out = []
    for name, lines in comps.items():
        if name in fused:
            continue
        for line in lines:
            m = _ARRAY.match(line)
            if not m:
                continue
            kind = m.group(4)
            if kind == "fusion":
                called = re.search(r"calls=%?([\w.\-]+)", line).group(1)
                kind = "fusion:" + root.get(called, "?")
            out.append((m.group(2), _dims(m.group(3)), kind))
    return out


def aliased_parameter_dims(text: str) -> list[tuple[int, ...]]:
    """Dims of the entry parameters that share a buffer with an output,
    sorted."""
    header = text.split("\n", 1)[0]
    numbers = set()
    if "input_output_alias={" in header:
        rest, depth, end = header.split("input_output_alias=", 1)[1], 0, 0
        for end, ch in enumerate(rest):
            depth += {"{": 1, "}": -1}.get(ch, 0)
            if depth == 0:
                break
        numbers = {int(p) for p in _ALIAS.findall(rest[:end + 1])}
    comps, entry = _computations(text)
    dims = {}
    for line in comps[entry]:
        p = _PARAMETER.match(line)
        if p:
            dims[int(p.group(2))] = _dims(p.group(1))
    return sorted(dims[n] for n in numbers)
