"""Where inside each device program a traced run's time went.

    python3 -m benchmark.program_parts [--top N] [trace.xplane.pb]

reads the newest ``.xplane.pb`` under ``.cache/benchmark_out`` (what a
``--trace 1`` run leaves) and prints, per program of the "XLA Modules"
line: its events, its device milliseconds an event, each part's seconds
and share of the program's operations, and the N largest operations of
each part by ``trace_reduce.op_kind``.

The program names its parts with ``jax.named_scope``, each one word of
``PARTS`` (``tpu_pipelines/observability/trace.py DEVICE_PARTS``; a test
holds the two equal).  How an operation is booked:

* **Program.**  An event of the "XLA Ops" line belongs to the program
  whose event on the "XLA Modules" line holds its start (sorted spans,
  one bisect an event).  Programs are added up by name without the
  number in brackets, so every bucket of ``jit_run`` is one program.
* **Self seconds.**  An event counts for its duration less what the
  events nested inside it on that line cover (``trace_reduce.self_times``):
  a ``while``, a ``call`` or a conditional is not counted a second time
  through its children.
* **Part.**  The *innermost* segment of the operation's scope path that
  is a word of ``PARTS``.  The path is the instruction's ``op_name`` in
  the HLO module that the trace carries in its ``/host:metadata`` plane;
  flax's module names, ``jit(...)``, ``while/body`` and the wrappers of
  a transformation (``transpose(jvp(...))``) stand in it too and are
  split at ``/`` and brackets.  Innermost, because flax's own module
  names are segments as well and one of them is a word (a
  ``TransformerBlock`` calls its MLP ``mlp``): the dropout inside that
  module is ``.../mlp/dropout/...`` and is dropout.  The older, finer
  scopes (``eva.attend``, ``moe.experts``, ``attn.full``) are no words
  and lie inside one.  Booked ``own``.
* **Inherited.**  An instruction whose own path holds no word (a copy
  the compiler made, a ``slice-done``, a fusion named after a plain
  root) takes, in this order: the one part that the instructions of the
  computations it calls name by their own paths, if they agree
  (``inside``: a fusion the compiler made of several keeps its members'
  paths where it has none); else the part of the instruction that calls
  its computation (``caller``: the body of a scan inside the attention
  is attention); else the one part that the instructions it feeds and
  is fed by inside its computation agree on, those without a part not
  asked (``neighbours``), repeated until nothing changes so that a
  chain of copies reaches the part at its end.  Where the neighbours
  disagree, or none has a part, the operation is ``unnamed``: a large
  ``unnamed`` share says a site of the program lacks a scope.

Every reader under ``layer_metrics/`` that asks for a part goes through
``newest()``, which parses a trace once a process (cached by path and
modification time).  Nothing here runs in an untraced run.
"""

from __future__ import annotations

import argparse
import bisect
import functools
import glob
import os
import re
import sys
import time
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from benchmark import manifest, trace_reduce

# tpu_pipelines/observability/trace.py DEVICE_PARTS
PARTS = (
    "attention_core", "attention_proj", "mlp", "norm", "embed_head",
    "dropout", "optimizer", "cache_write", "arena", "sample",
)
UNNAMED = "unnamed"
WAYS = ("own", "inside", "caller", "neighbours", "none")
HLO_STAT = "Hlo Proto"
METADATA_PLANE = "/host:metadata"

_SEGMENT = re.compile(r"[^/()]+")
_NUMBER = re.compile(r"\(\d+\)$")
# (program, instruction), start, duration: seconds
Op = Tuple[Tuple[str, str], float, float]


def part_of_path(path: str) -> Optional[str]:
    """The innermost word of ``PARTS`` in a scope path, or None."""
    for segment in reversed(_SEGMENT.findall(path or "")):
        if segment in PARTS:
            return segment
    return None


def program_name(event_name: str) -> str:
    """``jit_run(12923865437516446565)`` -> ``jit_run``."""
    return _NUMBER.sub("", event_name)


# ------------------------------------------------------------ arithmetic


def in_programs(
    ops: Iterable[Tuple[str, float, float]],
    modules: Sequence[Tuple[str, float, float]],
) -> List[Op]:
    """Each ``(instruction, start, duration)`` keyed by the program whose
    event holds its start (``""``: none does)."""
    spans = sorted((s, s + d, name) for name, s, d in modules)
    starts = [a for a, _, _ in spans]
    keyed = []
    for name, start, dur in ops:
        i = bisect.bisect_right(starts, start) - 1
        program = spans[i][2] if i >= 0 and start < spans[i][1] else ""
        keyed.append(((program, name), start, dur))
    return keyed


def resolve(computations) -> Dict[str, Tuple[str, str]]:
    """``{instruction: (part, way)}`` for one HLO module, by the rule of
    the module's docstring.  ``computations``: objects with ``id`` and
    ``instructions``, each instruction with ``name``, ``id``, ``op_name``,
    ``operand_ids`` and ``called_computation_ids`` (``Hlo`` below, or a
    test's stand-ins)."""
    part: Dict[int, Optional[str]] = {}
    way: Dict[int, str] = {}
    caller: Dict[int, int] = {}       # computation id -> calling instruction
    home: Dict[int, int] = {}         # instruction id -> its computation
    near: Dict[int, List[int]] = {}
    names: Dict[int, str] = {}
    members: Dict[int, List[int]] = {}    # computation id -> instructions
    calls: Dict[int, List[int]] = {}
    for comp in computations:
        members[comp.id] = [ins.id for ins in comp.instructions]
        for ins in comp.instructions:
            names[ins.id] = ins.name
            home[ins.id] = comp.id
            part[ins.id] = part_of_path(ins.op_name)
            way[ins.id] = "own" if part[ins.id] else "none"
            calls[ins.id] = list(ins.called_computation_ids)
            for called in ins.called_computation_ids:
                caller.setdefault(called, ins.id)
            for operand in ins.operand_ids:
                near.setdefault(ins.id, []).append(operand)
                near.setdefault(operand, []).append(ins.id)

    def from_caller(i: int) -> Optional[str]:
        seen = set()
        while i in home and home[i] in caller and i not in seen:
            seen.add(i)
            i = caller[home[i]]
            if way[i] in ("own", "inside"):
                return part[i]
        return None

    for i in [i for i, w in way.items() if w == "none" and calls[i]]:
        named = {
            part[j] for c in calls[i] for j in members.get(c, ())
            if way[j] == "own"}
        if len(named) == 1:
            part[i], way[i] = named.pop(), "inside"
    for i in [i for i, w in way.items() if w == "none"]:
        found = from_caller(i)
        if found:
            part[i], way[i] = found, "caller"
    open_ = [i for i, w in way.items() if w == "none"]
    while open_:
        found = {}
        for i in open_:
            around = {
                part[j] for j in near.get(i, ())
                if part.get(j) and home.get(j) == home[i]}
            if len(around) == 1:
                found[i] = around.pop()
        if not found:
            break
        for i, p in found.items():
            part[i], way[i] = p, "neighbours"
        open_ = [i for i in open_ if i not in found]
    return {names[i]: (part[i] or UNNAMED, way[i]) for i in names}


def book(
    ops: Sequence[Op], modules: Sequence[Tuple[str, float, float]],
    parts: Dict[Tuple[str, str], Tuple[str, str]],
    kinds: Optional[Dict[Tuple[str, str], str]] = None,
) -> Dict[str, Dict]:
    """The table by program: ``{program: {"events", "seconds" (of its
    events on the modules line), "op_seconds" (its operations' self
    seconds), "parts": {part: seconds}, "ways": {way: seconds}, "ops":
    {(part, kind): seconds}}}``.  ``parts``: ``(part, way)`` by
    ``(program event name, instruction)``; a key it lacks is unnamed.
    ``kinds``: what to print an instruction as."""
    table: Dict[str, Dict] = {}

    def row(name: str) -> Dict:
        return table.setdefault(program_name(name), {
            "events": 0, "seconds": 0.0, "op_seconds": 0.0, "parts": {},
            "ways": {}, "ops": {}})

    for name, _, dur in modules:
        r = row(name)
        r["events"] += 1
        r["seconds"] += dur
    for key, seconds in trace_reduce.self_times(ops).items():
        if not key[0]:
            continue
        r = row(key[0])
        part, how = parts.get(key, (UNNAMED, "none"))
        kind = (kinds or {}).get(key, key[1])
        r["op_seconds"] += seconds
        r["parts"][part] = r["parts"].get(part, 0.0) + seconds
        r["ways"][how] = r["ways"].get(how, 0.0) + seconds
        r["ops"][(part, kind)] = r["ops"].get((part, kind), 0.0) + seconds
    return table


def share(table: Optional[Dict[str, Dict]], programs: Sequence[str],
          parts: Sequence[str]) -> Optional[float]:
    """Per cent of the self seconds of ``programs``' operations that
    ``parts`` hold; None where the trace holds no such program."""
    rows = [table[p] for p in programs if table and p in table]
    total = sum(r["op_seconds"] for r in rows)
    if not total:
        return None
    return 100.0 * sum(r["parts"].get(p, 0.0) for r in rows for p in parts) \
        / total


def mean_ms(table: Optional[Dict[str, Dict]], program: str
            ) -> Optional[float]:
    """Mean device milliseconds of one event of ``program``."""
    r = (table or {}).get(program)
    return 1e3 * r["seconds"] / r["events"] if r and r["events"] else None


# -------------------------------------------------- the trace's layout


@functools.lru_cache(maxsize=None)
def messages() -> Dict[str, type]:
    """Message classes for the fields read of an ``.xplane.pb`` (tsl's
    ``xplane.proto``) and of the ``HloProto`` in it (xla's ``hlo.proto``),
    described here field by field: JAX's own ``ProfileData`` hands out
    neither an event's metadata nor the metadata plane's HLO."""
    from google.protobuf import (
        descriptor_pb2, descriptor_pool, message_factory)

    f = descriptor_pb2.FieldDescriptorProto
    scalar = {"int64": f.TYPE_INT64, "uint64": f.TYPE_UINT64,
              "string": f.TYPE_STRING, "bytes": f.TYPE_BYTES,
              "double": f.TYPE_DOUBLE}
    layout = {
        "XSpace": [("planes", 1, "XPlane", True)],
        "XPlane": [("name", 2, "string", False),
                   ("lines", 3, "XLine", True),
                   ("event_metadata", 4, "EventEntry", True),
                   ("stat_metadata", 5, "StatEntry", True)],
        "EventEntry": [("key", 1, "int64", False),
                       ("value", 2, "XEventMetadata", False)],
        "StatEntry": [("key", 1, "int64", False),
                      ("value", 2, "XStatMetadata", False)],
        "XLine": [("name", 2, "string", False),
                  ("timestamp_ns", 3, "int64", False),
                  ("events", 4, "XEvent", True)],
        "XEvent": [("metadata_id", 1, "int64", False),
                   ("offset_ps", 2, "int64", False),
                   ("duration_ps", 3, "int64", False)],
        "XStat": [("metadata_id", 1, "int64", False),
                  ("uint64_value", 3, "uint64", False),
                  ("int64_value", 4, "int64", False),
                  ("str_value", 5, "string", False),
                  ("bytes_value", 6, "bytes", False),
                  ("ref_value", 7, "uint64", False)],
        "XEventMetadata": [("id", 1, "int64", False),
                           ("name", 2, "string", False),
                           ("display_name", 4, "string", False),
                           ("stats", 5, "XStat", True)],
        "XStatMetadata": [("id", 1, "int64", False),
                          ("name", 2, "string", False)],
        "HloProto": [("hlo_module", 1, "HloModule", False)],
        "HloModule": [("name", 1, "string", False),
                      ("computations", 3, "HloComputation", True)],
        "HloComputation": [("name", 1, "string", False),
                           ("instructions", 2, "HloInstruction", True),
                           ("id", 5, "int64", False)],
        "HloInstruction": [("name", 1, "string", False),
                           ("opcode", 2, "string", False),
                           ("metadata", 7, "OpMetadata", False),
                           ("id", 35, "int64", False),
                           ("operand_ids", 36, "int64", True),
                           ("called_computation_ids", 38, "int64", True)],
        "OpMetadata": [("op_name", 2, "string", False)],
    }
    package = "benchmark_program_parts"
    file = descriptor_pb2.FileDescriptorProto(
        name=package + ".proto", package=package, syntax="proto3")
    for message, fields in layout.items():
        m = file.message_type.add(name=message)
        for name, number, kind, repeated in fields:
            field = m.field.add(
                name=name, number=number,
                label=f.LABEL_REPEATED if repeated else f.LABEL_OPTIONAL)
            if kind in scalar:
                field.type = scalar[kind]
            else:
                field.type = f.TYPE_MESSAGE
                field.type_name = f".{package}.{kind}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(file)
    return {
        name: message_factory.GetMessageClass(
            pool.FindMessageTypeByName(f"{package}.{name}"))
        for name in layout}


class Hlo:
    """One instruction of a parsed ``HloProto`` as ``resolve`` reads it."""

    __slots__ = ("name", "id", "op_name", "operand_ids",
                 "called_computation_ids")

    def __init__(self, ins):
        self.name, self.id = ins.name, ins.id
        self.op_name = ins.metadata.op_name
        self.operand_ids = ins.operand_ids
        self.called_computation_ids = ins.called_computation_ids


class _Computation:
    __slots__ = ("id", "instructions")

    def __init__(self, comp):
        self.id = comp.id
        self.instructions = [Hlo(i) for i in comp.instructions]


def _module_parts(module) -> Dict[str, Tuple[str, str]]:
    return resolve([_Computation(c) for c in module.computations])


def module_parts(hlo_module: bytes) -> Dict[str, Tuple[str, str]]:
    """``resolve`` over a serialized ``HloModuleProto``."""
    return _module_parts(messages()["HloModule"].FromString(hlo_module))


def read(path: str) -> Optional[Dict[str, Dict]]:
    """``book`` over the device plane of ``path`` that ran most
    operations; None where the trace holds no device operation (the CPU).
    ``took_s`` of the result's ``""`` row is what the reading cost."""
    t0 = time.perf_counter()
    with open(path, "rb") as f:
        space = messages()["XSpace"].FromString(f.read())
    hlo: Dict[str, bytes] = {}
    for plane in space.planes:
        if plane.name != METADATA_PLANE:
            continue
        stat = {e.key: e.value.name for e in plane.stat_metadata}
        for entry in plane.event_metadata:
            for s in entry.value.stats:
                if stat.get(s.metadata_id) == HLO_STAT and s.bytes_value:
                    hlo[entry.value.name] = s.bytes_value
    best = None
    for plane in space.planes:
        if not plane.name.startswith("/device:"):
            continue
        lines = {line.name: line for line in plane.lines}
        n = len(lines[trace_reduce.OP_LINE].events) \
            if trace_reduce.OP_LINE in lines else 0
        if n and (best is None or n > best[0]):
            best = (n, plane, lines)
    if best is None:
        return None
    _, plane, lines = best
    meta = {e.key: e.value for e in plane.event_metadata}

    def events(line):
        t = line.timestamp_ns * 1e-9
        return [(ev.metadata_id, t + ev.offset_ps * 1e-12,
                 ev.duration_ps * 1e-12) for ev in line.events]

    modules = [
        (meta[m].name, s, d)
        for m, s, d in events(lines[trace_reduce.MODULE_LINE])
    ] if trace_reduce.MODULE_LINE in lines else []
    ops = in_programs(events(lines[trace_reduce.OP_LINE]), modules)
    by_program = {
        name: _module_parts(
            messages()["HloProto"].FromString(hlo[name]).hlo_module)
        for name in {m for m, _, _ in modules} if name in hlo}
    parts, kinds = {}, {}
    for key in {key for key, _, _ in ops}:
        program, m = key
        name = meta[m].display_name or meta[m].name.partition(" = ")[0]
        kinds[key] = trace_reduce.op_kind(meta[m].name)
        found = by_program.get(program, {}).get(name.strip().lstrip("%"))
        if found:
            parts[key] = found
    table = book(ops, modules, parts, kinds)
    table[""] = {"events": len(ops), "took_s": time.perf_counter() - t0,
                 "path": path}
    return table


@functools.lru_cache(maxsize=2)
def _read_once(path: str, mtime_ns: int) -> Optional[Dict[str, Dict]]:
    return read(path)


def newest_path() -> Optional[str]:
    found = glob.glob(os.path.join(
        manifest.ROOT, ".cache", "benchmark_out", "*", "trace", "**",
        "*.xplane.pb"), recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def newest() -> Optional[Dict[str, Dict]]:
    """The table of the newest trace under the checkout's
    ``.cache/benchmark_out``, read once a process; None where there is
    no trace or it holds no device operation."""
    path = newest_path()
    return _read_once(path, os.stat(path).st_mtime_ns) if path else None


def for_cell(facts: Dict, kind: str) -> Optional[Dict[str, Dict]]:
    """What a reader of a ``kind`` of cell (``train_windows``,
    ``serve_steps``) reads: the newest trace's table in a traced run of
    such a cell, else None."""
    if facts.get("trace") is None or kind not in facts:
        return None
    return newest()


# ------------------------------------------------------------- the report


def report(table: Dict[str, Dict], top: int) -> List[str]:
    lines = []
    order = sorted(
        (p for p in table if p), key=lambda p: -table[p]["op_seconds"])
    for program in order:
        r = table[program]
        total = r["op_seconds"] or float("nan")
        lines.append(
            f"{program}: {r['events']} events, {r['seconds']:.6f} s of "
            f"events, {r['op_seconds']:.6f} s of operations, "
            f"{1e3 * r['seconds'] / max(1, r['events']):.4f} ms an event")
        lines.append("  booked by: " + ", ".join(
            f"{w} {100 * r['ways'].get(w, 0.0) / total:.2f} %"
            for w in WAYS))
        for part, seconds in sorted(
                r["parts"].items(), key=lambda kv: -kv[1]):
            lines.append(
                f"  {part:<15} {seconds:.6f} s  {100 * seconds / total:5.2f} %")
            ranked = sorted(
                ((k, s) for (p, k), s in r["ops"].items() if p == part),
                key=lambda ks: -ks[1])
            for kind, s in ranked[:top]:
                lines.append(
                    f"      {kind:<58} {s:.6f} s  {100 * s / total:5.2f} %")
    return lines


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("trace", nargs="?", help="an .xplane.pb (default: the "
                   "newest under .cache/benchmark_out)")
    p.add_argument("--top", type=int, default=5)
    args = p.parse_args(argv)
    path = args.trace or newest_path()
    if not path:
        print("program parts: no trace under .cache/benchmark_out",
              file=sys.stderr)
        return 1
    table = read(path)
    if table is None:
        print(f"program parts: {path} holds no device operation",
              file=sys.stderr)
        return 1
    print(f"program parts: {table['']['events']} operation events of "
          f"{path} booked in {table['']['took_s']:.1f} s")
    print("\n".join(report(table, args.top)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
