"""What a profiler trace holds that `jax.profiler.ProfileData` does not show.

An `.xplane.pb` file is one serialized `XSpace` (tsl/profiler/protobuf/
xplane.proto).  `ProfileData` gives each event its name, times and its own
stats.  Two things live elsewhere in the file and are read here, with a
small reader of the protobuf wire format (no generated module, no
tensorflow):

  * op metadata.  On the TPU the stats that describe an op -- `tf_op` (the
    JAX op path, `jit(run)/genie.match/jit(range_count)/pallas_call`),
    `source` (file:line), `program_id` -- belong to the op's event
    *metadata*, shared by every run of the op, and not to its events.
  * programs.  The `/host:metadata` plane holds one event metadata per
    compiled program, named like its `XLA Modules` events
    (`jit_run(16224828801452629536)`), whose `Hlo Proto` stat is the
    optimized HLO (xla/service/hlo.proto).  From it: each instruction's
    name, opcode, `metadata.op_name`, source line (its own, or the
    innermost of its stack frame in the module's stack frame index),
    operands and the computations it calls (fused computation, `while`
    body and condition).
"""
from __future__ import annotations

import dataclasses
import gzip
import struct

# field numbers of the messages read (xplane.proto, hlo.proto, xla_data.proto)
SPACE_PLANES = 1
PLANE_NAME, PLANE_EVENT_METADATA, PLANE_STAT_METADATA = 2, 4, 5
MAP_KEY, MAP_VALUE = 1, 2
EVENT_METADATA_NAME, EVENT_METADATA_STATS = 2, 5
STAT_METADATA_NAME = 2
STAT_METADATA_ID, STAT_DOUBLE, STAT_UINT64, STAT_INT64 = 1, 2, 3, 4
STAT_STR, STAT_BYTES, STAT_REF = 5, 6, 7
HLO_PROTO_MODULE = 1
MODULE_COMPUTATIONS, MODULE_STACK_FRAMES = 3, 17
FRAMES_FILES, FRAMES_LOCATIONS, FRAMES_FRAMES = 1, 3, 4
LOCATION_FILE, LOCATION_LINE = 1, 3
FRAME_LOCATION = 1
COMPUTATION_NAME, COMPUTATION_INSTRUCTIONS = 1, 2
COMPUTATION_ID, COMPUTATION_ROOT_ID = 5, 6
INSTR_NAME, INSTR_OPCODE, INSTR_METADATA = 1, 2, 7
INSTR_ID, INSTR_OPERANDS, INSTR_CALLED = 35, 36, 38
OP_NAME, OP_SOURCE_FILE, OP_SOURCE_LINE, OP_STACK_FRAME = 2, 3, 4, 15

METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"


def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def fields(buf):
    """(field number, wire type, value) of each field of one message, in
    order: an int for varint and fixed-width fields, a memoryview for
    length-delimited ones."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire == 1:
            v, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 5:
            v, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} is not supported")
        yield num, wire, v


def _ints(wire: int, v) -> list[int]:
    """A repeated int64 field's values, packed or not."""
    if wire == 0:
        return [_signed(v)]
    out, i = [], 0
    while i < len(v):
        x, i = _varint(v, i)
        out.append(_signed(x))
    return out


def _str(v) -> str:
    return bytes(v).decode("utf-8", "replace")


@dataclasses.dataclass(frozen=True)
class Instruction:
    name: str
    opcode: str
    op_name: str                 # metadata.op_name, "" when the compiler left none
    source: str                  # metadata file:line, "" when none
    operands: tuple              # operand instruction names
    called: tuple                # called computation ids


@dataclasses.dataclass
class Program:
    """One compiled program's optimized HLO."""

    name: str                    # as its XLA Modules events: `jit_run(<id>)`
    instructions: dict           # name -> Instruction (unique in a module)
    computations: dict           # id -> (name, root instruction name, names)

    def calls(self, ins: Instruction):
        """The instructions of the computations `ins` calls, and of those
        they call, each computation once."""
        seen, out, todo = set(), [], list(ins.called)
        while todo:
            cid = todo.pop(0)
            if cid in seen or cid not in self.computations:
                continue
            seen.add(cid)
            for name in self.computations[cid][2]:
                sub = self.instructions[name]
                out.append(sub)
                todo.extend(sub.called)
        return out


def _stats(buf, stat_names: dict) -> dict:
    out, ref = {}, {}
    for num, wire, v in fields(buf):
        if num == STAT_METADATA_ID:
            ref["id"] = v
        elif num == STAT_DOUBLE:
            ref["v"] = struct.unpack("<d", v.to_bytes(8, "little"))[0]
        elif num == STAT_UINT64:
            ref["v"] = v
        elif num == STAT_INT64:
            ref["v"] = _signed(v)
        elif num == STAT_STR:
            ref["v"] = _str(v)
        elif num == STAT_BYTES:
            ref["v"] = v
        elif num == STAT_REF:
            ref["v"] = stat_names.get(v, "")
    return {stat_names.get(ref.get("id"), str(ref.get("id"))): ref.get("v")}


def _map_entries(buf):
    key = value = None
    for num, wire, v in fields(buf):
        if num == MAP_KEY:
            key = v
        elif num == MAP_VALUE:
            value = v
    return key, value


def _plane(buf):
    """(name, [(event metadata name, stats)]) of one XPlane."""
    name, metas, stat_names = "", [], {}
    for num, wire, v in fields(buf):
        if num == PLANE_NAME:
            name = _str(v)
        elif num == PLANE_STAT_METADATA:
            key, value = _map_entries(v)
            for n2, _, v2 in fields(value or b""):
                if n2 == STAT_METADATA_NAME:
                    stat_names[key] = _str(v2)
        elif num == PLANE_EVENT_METADATA:
            metas.append(_map_entries(v)[1])
    out = []
    for m in metas:
        ev_name, stats = "", {}
        for num, wire, v in fields(m or b""):
            if num == EVENT_METADATA_NAME:
                ev_name = _str(v)
            elif num == EVENT_METADATA_STATS:
                stats.update(_stats(v, stat_names))
        out.append((ev_name, stats))
    return name, out


def program(name: str, hlo_proto) -> Program:
    """Read a serialized `HloProto` into a Program."""
    module = b""
    for num, wire, v in fields(hlo_proto):
        if num == HLO_PROTO_MODULE:
            module = v
    raw = []                      # (computation id, name, root id, [instr])
    frames = []                   # stack frame id - 1 -> "file:line"
    for num, wire, v in fields(module):
        if num == MODULE_STACK_FRAMES:
            frames = _frames(v)
        if num != MODULE_COMPUTATIONS:
            continue
        cname, cid, root, instrs = "", 0, None, []
        for n2, w2, v2 in fields(v):
            if n2 == COMPUTATION_NAME:
                cname = _str(v2)
            elif n2 == COMPUTATION_ID:
                cid = _signed(v2)
            elif n2 == COMPUTATION_ROOT_ID:
                root = _signed(v2)
            elif n2 == COMPUTATION_INSTRUCTIONS:
                instrs.append(_instruction(v2))
        raw.append((cid, cname, root, instrs))
    by_id = {i["id"]: i["name"] for *_, instrs in raw for i in instrs}
    instructions, computations = {}, {}
    for cid, cname, root, instrs in raw:
        for i in instrs:
            frame = i["frame"]
            instructions[i["name"]] = Instruction(
                name=i["name"], opcode=i["opcode"], op_name=i["op_name"],
                source=i["source"] or (frames[frame - 1]
                                       if 0 < frame <= len(frames) else ""),
                operands=tuple(by_id[o] for o in i["operands"] if o in by_id),
                called=tuple(i["called"]))
        computations[cid] = (cname, by_id.get(root, ""),
                             [i["name"] for i in instrs])
    return Program(name=name, instructions=instructions,
                   computations=computations)


def _frames(buf) -> list[str]:
    """The innermost "file:line" of each frame of a stack frame index."""
    files, locations, frames = [], [], []
    for num, wire, v in fields(buf):
        if num == FRAMES_FILES:
            files.append(_str(v))
        elif num == FRAMES_LOCATIONS:
            loc = dict((n, x) for n, _, x in fields(v))
            locations.append((loc.get(LOCATION_FILE, 0), loc.get(LOCATION_LINE, 0)))
        elif num == FRAMES_FRAMES:
            frames.append(dict((n, x) for n, _, x in fields(v)).get(FRAME_LOCATION, 0))
    out = []
    for loc in frames:            # ids are 1-based, 0 is none
        f, line = locations[loc - 1] if 0 < loc <= len(locations) else (0, 0)
        out.append(f"{files[f - 1]}:{line}" if 0 < f <= len(files) else "")
    return out


def _instruction(buf) -> dict:
    out = dict(name="", opcode="", op_name="", source="", id=None,
               operands=[], called=[], frame=0)
    for num, wire, v in fields(buf):
        if num == INSTR_NAME:
            out["name"] = _str(v)
        elif num == INSTR_OPCODE:
            out["opcode"] = _str(v)
        elif num == INSTR_ID:
            out["id"] = _signed(v)
        elif num == INSTR_OPERANDS:
            out["operands"].extend(_ints(wire, v))
        elif num == INSTR_CALLED:
            out["called"].extend(_ints(wire, v))
        elif num == INSTR_METADATA:
            src, line = "", 0
            for n2, _, v2 in fields(v):
                if n2 == OP_NAME:
                    out["op_name"] = _str(v2)
                elif n2 == OP_SOURCE_FILE:
                    src = _str(v2)
                elif n2 == OP_SOURCE_LINE:
                    line = v2
                elif n2 == OP_STACK_FRAME:
                    out["frame"] = v2
            if src:
                out["source"] = f"{src}:{line}"
    return out


@dataclasses.dataclass
class Metadata:
    """Op metadata of every device plane and the programs of the trace."""

    ops: dict        # device plane name -> {op name -> [stats, ...]}
    programs: dict   # program name (`jit_run(<id>)`) -> Program


def read(path: str, device_plane) -> Metadata:
    """Read the op metadata of the planes whose name `device_plane` matches
    (a compiled regex) and the programs of the trace at `path` (an
    `.xplane.pb`, or the same gzipped)."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        space = f.read()
    ops, programs = {}, {}
    for num, wire, v in fields(space):
        if num != SPACE_PLANES:
            continue
        name = ""
        for n2, _, v2 in fields(v):
            if n2 == PLANE_NAME:
                name = _str(v2)
                break
        if device_plane.match(name):
            by_op = ops.setdefault(name, {})
            for op, stats in _plane(v)[1]:
                by_op.setdefault(op, []).append(stats)
        elif name == METADATA_PLANE:
            for prog, stats in _plane(v)[1]:
                if stats.get(HLO_STAT) is not None:
                    programs[prog] = program(prog, stats[HLO_STAT])
    return Metadata(ops=ops, programs=programs)
