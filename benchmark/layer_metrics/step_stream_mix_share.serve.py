"""Share of the decode step program's device time that goes to the mixing
of the residual streams (Xing4.0-29B-A4B's mHC path): the self seconds of
the operations whose scope path holds ``mhc.mix`` (the flat norm, the
products with ``phi``, the sigmoids, the 20 normalisations) or
``mhc.apply`` (``u`` and the write-back) over those of all the step
programs' operations, from the run's own trace.  The two scopes lie INSIDE
a word of benchmark/program_parts.py (``attention_proj``, ``mlp``), so this
share is a part of those two and not an eleventh beside them.  The path is
the instruction's ``op_name`` in the HLO that the trace carries; an
operation with no path of its own (a fusion the compiler made) counts
where every instruction it calls that has a path holds a scope.  A share
is a map of the program, not a goal.  Returns nothing in another kind of
cell, in an untraced run, on the CPU, and where no operation of the step
holds either scope (any other model, any commit before the scopes)."""

LAYER = "model step"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"

# serving/generative.py PROGRAM_NAMES
PROGRAMS = ("jit_run",)
SCOPES = ("mhc.mix", "mhc.apply")


def holds(op_name, scopes=SCOPES):
    from benchmark import program_parts

    return any(
        s in scopes for s in program_parts._SEGMENT.findall(op_name or ""))


def in_scope(module, scopes=SCOPES):
    """Names of the instructions of a parsed ``HloModule`` that count."""
    members = {
        c.id: [i.metadata.op_name for i in c.instructions]
        for c in module.computations}
    found = set()
    for comp in module.computations:
        for ins in comp.instructions:
            path = ins.metadata.op_name
            if path:
                if holds(path, scopes):
                    found.add(ins.name)
                continue
            called = [
                p for c in ins.called_computation_ids
                for p in members.get(c, ()) if p]
            if called and all(holds(p, scopes) for p in called):
                found.add(ins.name)
    return found


def shares(path, programs=PROGRAMS, scopes=SCOPES):
    """``(seconds in scope, seconds of all operations)`` of ``programs``
    in the trace at ``path``; None where it holds no device operation."""
    from benchmark import program_parts as pp, trace_reduce

    with open(path, "rb") as f:
        space = pp.messages()["XSpace"].FromString(f.read())
    hlo = {}
    for plane in space.planes:
        if plane.name != pp.METADATA_PLANE:
            continue
        stat = {e.key: e.value.name for e in plane.stat_metadata}
        for entry in plane.event_metadata:
            for s in entry.value.stats:
                if stat.get(s.metadata_id) == pp.HLO_STAT and s.bytes_value:
                    hlo[entry.value.name] = s.bytes_value
    planes = [
        (len(lines[trace_reduce.OP_LINE].events), plane, lines)
        for plane in space.planes if plane.name.startswith("/device:")
        for lines in [{line.name: line for line in plane.lines}]
        if trace_reduce.OP_LINE in lines
        and trace_reduce.MODULE_LINE in lines]
    if not planes:
        return None
    _, plane, lines = max(planes, key=lambda p: p[0])
    meta = {e.key: e.value for e in plane.event_metadata}

    def events(line):
        t = line.timestamp_ns * 1e-9
        return [(ev.metadata_id, t + ev.offset_ps * 1e-12,
                 ev.duration_ps * 1e-12) for ev in line.events]

    modules = [(meta[m].name, s, d)
               for m, s, d in events(lines[trace_reduce.MODULE_LINE])]
    wanted = {name for name, _, _ in modules
              if pp.program_name(name) in programs}
    counted = {
        name: in_scope(
            pp.messages()["HloProto"].FromString(hlo[name]).hlo_module,
            scopes)
        for name in wanted if name in hlo}
    inside = total = 0.0
    ops = pp.in_programs(events(lines[trace_reduce.OP_LINE]), modules)
    for (program, m), seconds in trace_reduce.self_times(ops).items():
        if program not in wanted:
            continue
        total += seconds
        name = meta[m].display_name or meta[m].name.partition(" = ")[0]
        if name.strip().lstrip("%") in counted.get(program, ()):
            inside += seconds
    return inside, total


def read(facts):
    from benchmark import program_parts

    if facts.get("trace") is None or "serve_steps" not in facts:
        return None
    path = program_parts.newest_path()
    found = shares(path) if path else None
    if not found or not found[0] or not found[1]:
        return None
    return 100.0 * found[0] / found[1]
