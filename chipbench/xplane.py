"""The part of a profiler ``.xplane.pb`` that ``jax.profiler.ProfileData``
does not reach: event metadata and its statistics, such as the HLO
modules a profile carries in its ``/host:metadata`` plane.

A minimal reader of the protobuf wire format, following the field
numbers of ``tsl/profiler/protobuf/xplane.proto`` and
``xla/service/hlo.proto``; it imports nothing beyond the standard
library and skips every field it does not name.
"""

import struct

# xplane.proto
SPACE_PLANES = 1
PLANE_NAME, PLANE_EVENT_METADATA, PLANE_STAT_METADATA = 2, 4, 5
MAP_VALUE = 2  # of a map entry; its key is field 1
EVENT_METADATA_NAME, EVENT_METADATA_STATS = 2, 5
STAT_METADATA_ID, STAT_METADATA_NAME = 1, 2
STAT_ID, STAT_STR, STAT_BYTES = 1, 5, 6
# hlo.proto
HLO_PROTO_MODULE = 1
MODULE_NAME, MODULE_COMPUTATIONS = 1, 3
COMPUTATION_NAME, COMPUTATION_INSTRUCTIONS, COMPUTATION_ID = 1, 2, 5
INSTRUCTION_NAME, INSTRUCTION_OPCODE, INSTRUCTION_METADATA = 1, 2, 7
INSTRUCTION_CALLED = 38
METADATA_OP_NAME = 2


def _varint(buf, i):
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def fields(buf):
    """(field number, value) of each field of one message, in order: an
    int for varint and fixed-width fields, a memoryview for
    length-delimited ones."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 1:
            value = struct.unpack_from("<Q", buf, i)[0]
            i += 8
        elif wire == 2:
            size, i = _varint(buf, i)
            value = buf[i:i + size]
            i += size
        elif wire == 5:
            value = struct.unpack_from("<I", buf, i)[0]
            i += 4
        else:
            raise ValueError("unsupported protobuf wire type {}".format(wire))
        yield key >> 3, value


def _packed_or_single(value):
    if isinstance(value, int):
        return [value]
    out, i = [], 0
    while i < len(value):
        v, i = _varint(value, i)
        out.append(v)
    return out


def _text(value):
    return bytes(value).decode("utf-8", "replace")


def planes(space_bytes):
    """[(plane name, plane bytes)] of a serialized XSpace."""
    out = []
    for num, plane in fields(space_bytes):
        if num != SPACE_PLANES:
            continue
        name = ""
        for pnum, value in fields(plane):
            if pnum == PLANE_NAME:
                name = _text(value)
                break
        out.append((name, plane))
    return out


def metadata_stats(plane):
    """[(event metadata name, stat name, str or bytes value)] of every
    event metadata entry of a plane."""
    stat_names, entries = {}, []
    for num, value in fields(plane):
        if num == PLANE_STAT_METADATA:
            for knum, kv in fields(value):
                if knum == MAP_VALUE:
                    sid, sname = None, ""
                    for snum, sv in fields(kv):
                        if snum == STAT_METADATA_ID:
                            sid = sv
                        elif snum == STAT_METADATA_NAME:
                            sname = _text(sv)
                    stat_names[sid] = sname
        elif num == PLANE_EVENT_METADATA:
            for knum, kv in fields(value):
                if knum == MAP_VALUE:
                    entries.append(kv)
    out = []
    for entry in entries:
        name, stats = "", []
        for num, value in fields(entry):
            if num == EVENT_METADATA_NAME:
                name = _text(value)
            elif num == EVENT_METADATA_STATS:
                stats.append(value)
        for stat in stats:
            sid, val = None, None
            for num, value in fields(stat):
                if num == STAT_ID:
                    sid = value
                elif num in (STAT_STR, STAT_BYTES):
                    val = value
            if val is not None:
                out.append((name, stat_names.get(sid, ""), val))
    return out


def hlo_instructions(hlo_proto):
    """(module name, [(computation, instruction, opcode, op_name,
    [called computation names])]) of a serialized ``HloProto``."""
    module = None
    for num, value in fields(hlo_proto):
        if num == HLO_PROTO_MODULE:
            module = value
    if module is None:
        return "", []
    name, comps = "", []
    for num, value in fields(module):
        if num == MODULE_NAME:
            name = _text(value)
        elif num == MODULE_COMPUTATIONS:
            comps.append(value)
    by_id, rows = {}, []
    for comp in comps:
        cname, cid, instrs = "", None, []
        for num, value in fields(comp):
            if num == COMPUTATION_NAME:
                cname = _text(value)
            elif num == COMPUTATION_ID:
                cid = value
            elif num == COMPUTATION_INSTRUCTIONS:
                instrs.append(value)
        by_id[cid] = cname
        for instr in instrs:
            iname = opcode = op_name = ""
            called = []
            for num, value in fields(instr):
                if num == INSTRUCTION_NAME:
                    iname = _text(value)
                elif num == INSTRUCTION_OPCODE:
                    opcode = _text(value)
                elif num == INSTRUCTION_METADATA:
                    for mnum, mv in fields(value):
                        if mnum == METADATA_OP_NAME:
                            op_name = _text(mv)
                elif num == INSTRUCTION_CALLED:
                    called.extend(_packed_or_single(value))
            rows.append((cname, iname, opcode, op_name, called))
    return name, [(c, i, o, n, [by_id.get(x, "") for x in called])
                  for c, i, o, n, called in rows]
