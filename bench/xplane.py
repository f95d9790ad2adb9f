"""The name stack of each device op event in an ``.xplane.pb``.

``jax.profiler.ProfileData`` gives an event its own stats only; a TPU op
event's HLO ``op_name`` metadata (the JAX name stack) sits on the event's
metadata, which it does not expose. So this module reads the trace's
``XSpace`` protobuf itself, with message classes built at run time from
the published schema (``tsl/profiler/protobuf/xplane.proto``, the fields
read here), and nothing but ``google.protobuf``.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, List

#: (message, [(field, number, type, label, message type)]): the fields of the
#: schema read here; the parser skips every other field.
_I64, _U64, _STR, _MSG = 3, 4, 9, 11
_OPT, _REP = 1, 3
_SCHEMA = [
    ("XSpace", [("planes", 1, _MSG, _REP, "XPlane")]),
    ("XPlane", [("name", 2, _STR, _OPT, None), ("lines", 3, _MSG, _REP, "XLine"),
                ("event_metadata", 4, _MSG, _REP, "XPlane.EventMetadataEntry"),
                ("stat_metadata", 5, _MSG, _REP, "XPlane.StatMetadataEntry")]),
    ("XLine", [("name", 2, _STR, _OPT, None), ("events", 4, _MSG, _REP, "XEvent")]),
    ("XEvent", [("metadata_id", 1, _I64, _OPT, None)]),
    ("XStat", [("metadata_id", 1, _I64, _OPT, None), ("str_value", 5, _STR, _OPT, None),
               ("ref_value", 7, _U64, _OPT, None)]),
    ("XEventMetadata", [("stats", 5, _MSG, _REP, "XStat")]),
    ("XStatMetadata", [("name", 2, _STR, _OPT, None)]),
]
_MAPS = {"XPlane": [("EventMetadataEntry", "XEventMetadata"),
                    ("StatMetadataEntry", "XStatMetadata")]}


@functools.lru_cache(maxsize=None)
def _space_class():
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    package = "bench_xplane"
    proto = descriptor_pb2.FileDescriptorProto(name="bench_xplane.proto", package=package,
                                               syntax="proto3")
    for name, fields in _SCHEMA:
        message = proto.message_type.add(name=name)
        for key, value in _MAPS.get(name, []):
            entry = message.nested_type.add(name=key)
            entry.options.map_entry = True
            entry.field.add(name="key", number=1, type=_I64, label=_OPT)
            entry.field.add(name="value", number=2, type=_MSG, label=_OPT,
                            type_name=f".{package}.{value}")
        for field, number, kind, label, type_name in fields:
            f = message.field.add(name=field, number=number, type=kind, label=label)
            if type_name:
                f.type_name = f".{package}.{type_name}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(proto)
    return message_factory.GetMessageClass(pool.FindMessageTypeByName(f"{package}.XSpace"))


def stat_value(stat, names: Dict[int, str]) -> str:
    """A string stat's value; a reference value names a stat metadata."""
    return names.get(stat.ref_value, "") if stat.ref_value else stat.str_value


def read_space(path: str):
    """The ``XSpace`` of one trace file."""
    space = _space_class()()
    with open(path, "rb") as fh:
        space.ParseFromString(fh.read())
    return space


def op_stacks(path: str, line_name: str, stat_name: str,
              keep: Callable[[str], bool]) -> Dict[str, List[str]]:
    """For each plane ``keep`` takes, the ``stat_name`` stat of each event's
    metadata on its line ``line_name``, in the events' order (``""`` where
    the metadata has no such stat)."""
    out: Dict[str, List[str]] = {}
    for plane in read_space(path).planes:
        if not keep(plane.name):
            continue
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        wanted = {k for k, v in names.items() if v == stat_name}
        stack = {}
        for key, meta in plane.event_metadata.items():
            for stat in meta.stats:
                if stat.metadata_id in wanted:
                    stack[key] = stat_value(stat, names)
        for line in plane.lines:
            if line.name == line_name:
                out[plane.name] = [stack.get(e.metadata_id, "") for e in line.events]
    return out
