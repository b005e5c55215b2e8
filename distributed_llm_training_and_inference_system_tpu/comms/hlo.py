"""Which collectives a compiled program holds, and in which loop.

GSPMD decides the collectives, not the code: a sharding rule that puts a
mesh axis on a matmul's contraction makes XLA move the weight where it
could have moved the rows, and inside a ``scan`` it moves it once an
iteration. The partitioned program's text (``compiled.as_text()``) shows
what was decided; this reads it. Nothing here runs or times anything.
"""

from __future__ import annotations

import re
from dataclasses import dataclass

# an async pair is one transfer: the ``-start`` carries the shapes
_OPS = ("all-gather", "all-reduce", "all-reduce-scatter", "reduce-scatter",
        "all-to-all", "collective-permute", "collective-broadcast")
_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
                "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
                "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8}
_SHAPE = re.compile(r"\b(%s)\[([0-9,]*)\]" % "|".join(_DTYPE_BYTES))
_INSTR = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*?)\s((?:[a-z]+-)*[a-z]+)\(")
_COMPUTATION = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s*(?:\(.*)?\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(
    r"\b(calls|body|condition|to_apply|branch_computations|"
    r"called_computations)=(?:%?([\w.\-]+)|\{([^}]*)\})")


@dataclass(frozen=True)
class Collective:
    op: str                       # "all-gather", "all-reduce", ...
    name: str                     # the instruction's name in the text
    shapes: tuple[tuple[str, tuple[int, ...]], ...]   # result (dtype, dims)
    nbytes: int                   # bytes of the result, one device
    loop: str                     # op_name up to the innermost ``while``
    op_name: str                  # the whole op_name ("" if none)
    in_loop: bool                 # inside a ``while`` body, by the call graph
    fusion: str                   # the fusion instruction that holds it, or
                                  # "": the name a device trace shows is
                                  # ``fusion or name``
    overlapped: bool              # in an ``async_collective_fusion``: it
                                  # runs beside that fusion's matmul, whose
                                  # time is what a trace shows for it

    @property
    def widest(self) -> tuple[str, tuple[int, ...]]:
        return max(self.shapes, key=lambda s: _nbytes(*s))

    def has_axis(self, size: int) -> bool:
        return any(size in dims for _, dims in self.shapes)


def _nbytes(dtype: str, dims: tuple[int, ...]) -> int:
    n = _DTYPE_BYTES[dtype]
    for d in dims:
        n *= d
    return n


def _loop_of(op_name: str) -> str:
    """``jit(f)/a/while/body/b/while/body/c`` -> ``jit(f)/a/while/body/b/while``;
    "" for an operation outside every loop."""
    parts = op_name.split("/")
    last = max((i for i, p in enumerate(parts) if p == "while"), default=-1)
    return "/".join(parts[: last + 1])


def collectives(hlo_text: str) -> list[Collective]:
    """Every collective instruction of an optimised HLO module, in the
    order of the text. A TPU program wraps most of them in fusions
    (``async_collective_fusion``, ``all-reduce-scatter``): the instruction
    inside the fused computation is what is listed, under the loop of the
    fusion that calls it."""
    found: list[tuple[str, str, str, str, str]] = []
    # callee -> (caller, how it is called, the calling line's op_name and
    # instruction)
    callers: dict[str, list[tuple[str, str, str, str]]] = {}
    comp = None
    for line in hlo_text.splitlines():
        if not line.startswith(" "):
            m = _COMPUTATION.match(line)
            if m:
                comp = m.group(1)
            continue
        m = _INSTR.match(line)
        if comp is None or not m:
            continue
        name, result, op = m.groups()
        n = _OP_NAME.search(line)
        op_name = n.group(1) if n else ""
        for how, one, many in _CALLED.findall(line):
            for callee in re.findall(r"[\w.\-]+", one or many):
                callers.setdefault(callee, []).append(
                    (comp, how, op_name, name))
        base = op[:-len("-start")] if op.endswith("-start") else op
        if base == "all-reduce" and comp.startswith("all-reduce-scatter"):
            base = "all-reduce-scatter"     # the fusion keeps one shard
        if base in _OPS:
            found.append((comp, name, base, result, op_name))

    def walk_up(c: str, want, seen=frozenset()):
        """The first true ``want(call)`` over the calls that reach ``c``."""
        for caller, how, op_name, _ in callers.get(c, ()):
            got = want(how, op_name) or (
                caller not in seen and walk_up(caller, want, seen | {c}))
            if got:
                return got
        return None

    out = []
    for comp, name, op, result, op_name in found:
        shapes = tuple((d, tuple(int(x) for x in dims.split(",") if x))
                       for d, dims in _SHAPE.findall(result))
        # a fused computation's instructions may carry no metadata of their
        # own: take the calling fusion's
        op_name = op_name or walk_up(comp, lambda _, n: n) or ""
        out.append(Collective(
            op=op, name=name, shapes=shapes,
            nbytes=sum(_nbytes(*s) for s in shapes),
            loop=_loop_of(op_name), op_name=op_name,
            in_loop=bool(walk_up(comp, lambda how, _: how == "body")),
            fusion=next((instr for _, how, _, instr in callers.get(comp, ())
                         if how == "calls"), ""),
            overlapped=comp.startswith("async_collective_fusion")))
    return out
