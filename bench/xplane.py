"""Read a profiler's `.xplane.pb` file with every event's stats.

`jax.profiler.ProfileData` gives each event the stats stored on the event
alone. A chip's op events keep their per-instruction stats (the op's scope
path among them) on the event's metadata, shared by every run of the
instruction, and the link from an event to its metadata is an id that
`ProfileData` does not expose. So this module parses the file itself, with
`protobuf` message classes built from the XPlane schema (the fields read
here, under their numbers in tsl/profiler/protobuf/xplane.proto). Where a
program's HLO is in the trace (its `/host:metadata` plane), `op_names`
reads each instruction's `op_name` from it (xla/service/hlo.proto).
"""
from __future__ import annotations

import functools
from typing import Callable, Collection, Optional

from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

_I64, _U64, _F64 = "TYPE_INT64", "TYPE_UINT64", "TYPE_DOUBLE"
_STR, _BYTES, _MSG = "TYPE_STRING", "TYPE_BYTES", "TYPE_MESSAGE"
# message: [(field, number, type, message type or None, repeated)]
_SCHEMA = {
    "XSpace": [("planes", 1, _MSG, "XPlane", True)],
    "XPlane": [("name", 2, _STR, None, False),
               ("lines", 3, _MSG, "XLine", True),
               ("event_metadata", 4, _MSG, "EventMetadataEntry", True),
               ("stat_metadata", 5, _MSG, "StatMetadataEntry", True)],
    "EventMetadataEntry": [("key", 1, _I64, None, False),
                           ("value", 2, _MSG, "XEventMetadata", False)],
    "StatMetadataEntry": [("key", 1, _I64, None, False),
                          ("value", 2, _MSG, "XStatMetadata", False)],
    "XLine": [("name", 2, _STR, None, False),
              ("timestamp_ns", 3, _I64, None, False),
              ("events", 4, _MSG, "XEvent", True)],
    "XEvent": [("metadata_id", 1, _I64, None, False),
               ("offset_ps", 2, _I64, None, False),
               ("duration_ps", 3, _I64, None, False),
               ("stats", 4, _MSG, "XStat", True)],
    "XStat": [("metadata_id", 1, _I64, None, False),
              ("double_value", 2, _F64, None, False),
              ("uint64_value", 3, _U64, None, False),
              ("int64_value", 4, _I64, None, False),
              ("str_value", 5, _STR, None, False),
              ("bytes_value", 6, _BYTES, None, False),
              ("ref_value", 7, _U64, None, False)],
    "XEventMetadata": [("name", 2, _STR, None, False),
                       ("stats", 5, _MSG, "XStat", True)],
    "XStatMetadata": [("name", 2, _STR, None, False)],
    "HloProto": [("hlo_module", 1, _MSG, "HloModuleProto", False)],
    "HloModuleProto": [("computations", 3, _MSG, "HloComputationProto",
                        True)],
    "HloComputationProto": [("instructions", 2, _MSG, "HloInstructionProto",
                             True)],
    "HloInstructionProto": [("name", 1, _STR, None, False),
                            ("metadata", 7, _MSG, "OpMetadata", False)],
    "OpMetadata": [("op_name", 2, _STR, None, False)],
}
METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"
_PACKAGE = "bench_xplane"
_VALUES = ("double_value", "uint64_value", "int64_value", "str_value",
           "bytes_value", "ref_value")


@functools.lru_cache(maxsize=None)
def _class(message: str):
    f = descriptor_pb2.FieldDescriptorProto
    file = descriptor_pb2.FileDescriptorProto(name="bench_xplane.proto",
                                              package=_PACKAGE,
                                              syntax="proto3")
    for msg, fields in _SCHEMA.items():
        m = file.message_type.add(name=msg)
        for name, number, kind, ref, repeated in fields:
            fd = m.field.add(name=name, number=number,
                             type=getattr(f, kind),
                             label=(f.LABEL_REPEATED if repeated
                                    else f.LABEL_OPTIONAL))
            if ref:
                fd.type_name = f".{_PACKAGE}.{ref}"
            if name in _VALUES:
                fd.oneof_index = 0
        if msg == "XStat":
            m.oneof_decl.add(name="value")
    pool = descriptor_pool.DescriptorPool()
    pool.Add(file)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{_PACKAGE}.{message}"))


def _stat(s, names: dict):
    kind = s.WhichOneof("value")
    if kind is None:
        return None
    value = getattr(s, kind)
    return names.get(value, value) if kind == "ref_value" else value


@functools.lru_cache(maxsize=1)
def _space(path: str):
    space = _class("XSpace")()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    return space


def read(path: str, keep: Callable[[str, str, str], bool],
         stat_names: Optional[Collection[str]] = None) -> list:
    """The planes of an xplane file as plain data:
    [{"name", "lines": [{"name", "events": [[name, start_ns, dur_ns,
    stats]]}]}], each event's stats its metadata's overlaid by its own,
    only those named in `stat_names` if given (a chip's traced query holds
    millions of op events, so the stats dict of an event without stats of
    its own is its metadata's, shared). Only the events for which
    keep(plane, line, event name) holds are kept; a plane with none kept is
    left out."""
    planes = []
    for p in _space(path).planes:
        names = {e.key: e.value.name for e in p.stat_metadata}
        wanted = {k for k, n in names.items()
                  if stat_names is None or n in stat_names}

        def stats_of(stats) -> dict:
            return {names[s.metadata_id]: _stat(s, names) for s in stats
                    if s.metadata_id in wanted}

        meta = {e.key: e.value for e in p.event_metadata}
        lines = []
        for ln in p.lines:
            kept: dict = {}         # metadata id -> (name, stats) or None
            events = []
            for ev in ln.events:
                mid = ev.metadata_id
                if mid not in kept:
                    m = meta.get(mid)
                    name = m.name if m is not None else ""
                    kept[mid] = ((name, stats_of(m.stats) if m is not None
                                  else {})
                                 if keep(p.name, ln.name, name) else None)
                if kept[mid] is None:
                    continue
                name, stats = kept[mid]
                own = stats_of(ev.stats) if ev.stats else None
                if own:
                    stats = {**stats, **own}
                events.append([name, ln.timestamp_ns + ev.offset_ps / 1e3,
                               ev.duration_ps / 1e3, stats])
            if events:
                lines.append({"name": ln.name, "events": events})
        if lines:
            planes.append({"name": p.name, "lines": lines})
    return planes


def op_names(path: str) -> dict:
    """{program id: {instruction name: op_name}} from the HLO of each
    program the trace recorded; empty where it recorded none. The plane
    keys a program by its id as a signed integer, an op event's stats as an
    unsigned one: the ids are taken unsigned."""
    out: dict = {}
    for p in _space(path).planes:
        if p.name != METADATA_PLANE:
            continue
        names = {e.key: e.value.name for e in p.stat_metadata}
        for e in p.event_metadata:
            for s in e.value.stats:
                if names.get(s.metadata_id) != HLO_STAT:
                    continue
                hlo = _class("HloProto")()
                hlo.ParseFromString(s.bytes_value)
                out[e.key % 2**64] = {i.name: i.metadata.op_name
                              for c in hlo.hlo_module.computations
                              for i in c.instructions}
    return out
