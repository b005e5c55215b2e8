"""From a profiler trace (``.xplane.pb``) to numbers.

Reads device planes only: the XLA-modules line gives one event per program
execution, the XLA-ops line one per operation. Which plane, which lines and
which module name is which program are DATA (``programs.json``), read from a
trace by hand; a program the file does not name is reported under its own
module name, never dropped.

The reduction works on plain ``(name, start_s, end_s)`` tuples, so the tests
feed it a hand-made trace; ``load`` is the only part that needs a profile.
"""

from __future__ import annotations

import glob
import json
import os
import re
from collections import defaultdict
from pathlib import Path

from benchmark.stats import merge_intervals, subtract_length, union_length

NAMES = json.loads(Path(__file__).with_name("programs.json").read_text())


def find_xplane(trace_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def short_name(text: str) -> str:
    """An operations-line event is named by its whole HLO line (``%fusion.3 =
    (f32[2,512]...) fusion(...)``): keep the left-hand side, and for a
    custom call its target, which is what tells a Pallas kernel apart."""
    name = text.split(" = ", 1)[0].lstrip("%")
    target = re.search(r'custom_call_target="([^"]+)"', text)
    return (f"{name}:{target.group(1)}" if target else name)[:64]


def load(profile, names: dict = NAMES) -> dict:
    """{device plane name: {"modules": [(name, s, e)], "ops": [...]}} with
    times in seconds, from a ``jax.profiler.ProfileData``."""
    out = {}
    for plane in profile.planes:
        if not plane.name.startswith(names["device_plane_prefix"]):
            continue
        lines = {}
        for line in plane.lines:
            for key in ("modules", "ops"):
                if line.name == names[f"{key}_line"]:
                    lines[key] = [
                        (short_name(e.name), e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9)
                        for e in line.events]
        if lines:
            out[plane.name] = {"modules": lines.get("modules", []),
                               "ops": lines.get("ops", [])}
    return out


def listing(profile, top: int = 12) -> dict:
    """Every plane and line of a trace with the events that took most time:
    what one reads by hand before naming programs in ``programs.json``."""
    out = {}
    for plane in profile.planes:
        for line in plane.lines:
            seen: dict = defaultdict(lambda: [0, 0.0])
            for e in line.events:
                seen[e.name][0] += 1
                seen[e.name][1] += e.duration_ns * 1e-9
            rows = sorted(seen.items(), key=lambda kv: -kv[1][1])[:top]
            out[f"{plane.name} | {line.name}"] = [
                [k, n, s] for k, (n, s) in rows]
    return out


def program_of(module_name: str, names: dict = NAMES) -> str:
    """The program a module event belongs to: ``jit_prefill(123...)`` ->
    ``prefill``. Unknown modules keep their own name, without the id."""
    base = re.sub(r"\(.*$", "", module_name).strip()
    for program, prefixes in names["programs"].items():
        if any(base == p or base.startswith(p) for p in prefixes):
            return program
    return base


def program_seconds(modules: list, names: dict = NAMES) -> dict:
    """{program: [executions, device seconds]} from a modules line."""
    out: dict = defaultdict(lambda: [0, 0.0])
    for name, s, e in modules:
        p = out[program_of(name, names)]
        p[0] += 1
        p[1] += e - s
    return {k: tuple(v) for k, v in out.items()}


def leaves(ops: list, names: dict = NAMES) -> list:
    """Operations that contain no other operation and are not containers
    (a ``while`` spans its whole body): what actually occupied the device."""
    containers = tuple(names["container_ops"])
    events = sorted(ops, key=lambda t: (t[1], -t[2]))
    out, stack = [], []          # stack of [event, has_child]
    for ev in events:
        while stack and stack[-1][0][2] <= ev[1]:
            done, had = stack.pop()
            if not had:
                out.append(done)
        if stack:
            stack[-1][1] = True
        stack.append([ev, False])
    out.extend(ev for ev, had in stack if not had)
    return [ev for ev in out
            if not re.sub(r"[.\d]+$", "", ev[0]).startswith(containers)]


def busy_seconds(ops: list) -> float:
    return union_length((s, e) for _, s, e in ops)


def top_ops(ops: list, n: int = 10, names: dict = NAMES) -> list:
    """[[operation, seconds]] of the leaf operations that took most device
    time, instances of one name added up (``fusion.12`` stays itself)."""
    total: dict = defaultdict(float)
    for name, s, e in leaves(ops, names):
        total[name] += e - s
    return [[k, v] for k, v in sorted(total.items(),
                                      key=lambda kv: -kv[1])[:n]]


def idle_gaps(modules: list, n: int = 10, names: dict = NAMES) -> list:
    """[[label, seconds]]: idle time between consecutive program executions
    added up by the programs on either side (``decode->prefill``). With no
    spans inside the program this is all that can be said of a gap."""
    total: dict = defaultdict(float)
    evs = sorted(modules, key=lambda t: t[1])
    end, last = None, None
    for name, s, e in evs:
        prog = program_of(name, names)
        if end is not None and s > end:
            total[f"{last}->{prog}"] += s - end
        if end is None or e > end:
            end, last = e, prog
    return [[k, v] for k, v in sorted(total.items(),
                                      key=lambda kv: -kv[1])[:n]]


def exposed_collective_seconds(ops: list, names: dict = NAMES) -> float:
    """Seconds in which a collective operation runs on the device and no
    other operation does."""
    keys = tuple(names["collective_ops"])
    lv = leaves(ops, names)
    coll = [(s, e) for n, s, e in lv if any(k in n for k in keys)]
    rest = [(s, e) for n, s, e in lv if not any(k in n for k in keys)]
    return subtract_length(coll, rest)


def reduce(planes: dict, window_s: float, names: dict = NAMES) -> dict:
    """Everything the metric readers take from a trace. ``busy_s`` is the
    mean over the device planes; programs, top operations, gaps and
    collectives are device 0's (the first plane by name)."""
    if not planes:
        return {}
    order = sorted(planes)
    first = planes[order[0]]
    busy = [busy_seconds(planes[p]["ops"] or planes[p]["modules"])
            for p in order]
    span = [(min(s for _, s, _ in planes[p]["ops"]),
             max(e for _, _, e in planes[p]["ops"]))
            for p in order if planes[p]["ops"]]
    return {
        "devices": order,
        "window_s": window_s,
        "busy_s": sum(busy) / len(busy),
        "busy_s_per_device": busy,
        "trace_span_s": max(e for _, e in span) - min(s for s, _ in span)
        if span else 0.0,
        "programs": program_seconds(first["modules"], names),
        "device_ops": top_ops(first["ops"], 10, names),
        "idle_gaps": idle_gaps(first["modules"], 10, names),
        "exposed_collective_s": exposed_collective_seconds(first["ops"],
                                                           names),
    }
