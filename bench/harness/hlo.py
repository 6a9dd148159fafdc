"""Readable labels for device operations, from the HLO text that the TPU
trace gives as the event's name, e.g.
``%pairwise_sq_dists.1 = f32[512,250368]{1,0:T(8,128)} custom-call(
f32[512,128]{...} %a, f32[250368,128]{...} %b, ...), custom_call_target=...``.
"""
from __future__ import annotations

import re

_SHAPE = re.compile(r"\b(pred|[a-z]+\d+)\[([\d,]*)\]")


def op_name(text: str) -> str:
    """``pairwise_sq_dists.1`` for the text above."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def _shapes(text: str) -> list[tuple[str, tuple[int, ...]]]:
    return [(dt, tuple(int(v) for v in dims.split(",") if v))
            for dt, dims in _SHAPE.findall(text)]


def short(text: str) -> str:
    """A readable label: name, output shape and operation kind."""
    _, _, rhs = text.partition(" = ")
    op_at = re.search(r"\s([\w\-]+)\(", rhs)
    outs = _shapes(rhs[:op_at.start()] if op_at else rhs)
    shape = ",".join(f"{dt}[{','.join(map(str, dims))}]" for dt, dims in outs)
    kind = op_at.group(1) if op_at else ""
    return " ".join(p for p in (op_name(text), shape, kind) if p)[:160]
