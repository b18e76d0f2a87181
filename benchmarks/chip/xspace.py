"""Read a profiler's ``.xplane.pb`` (an ``XSpace``) with the benchmark's
own copy of the schema's used part.

The field numbers are those of TSL's ``tsl/profiler/protobuf/xplane.proto``
(``XSpace``, ``XPlane``, ``XLine``, ``XEvent``, ``XStat``,
``XEventMetadata``, ``XStatMetadata``); the messages are built at import
under a package of their own, so they never clash with another copy.
``jax.profiler.ProfileData`` shows an op's per-run stats only; the
per-op metadata (its HLO source line, category) is in the event metadata
that this reads.
"""
from __future__ import annotations

import gzip

from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

_PKG = "chipbench.xspace"
_T = descriptor_pb2.FieldDescriptorProto
_OPT, _REP = _T.LABEL_OPTIONAL, _T.LABEL_REPEATED

#: message -> [(field, number, type, label, message type or None, oneof)]
_SCHEMA = {
    "XSpace": [("planes", 1, _T.TYPE_MESSAGE, _REP, "XPlane", None)],
    "XPlane": [
        ("id", 1, _T.TYPE_INT64, _OPT, None, None),
        ("name", 2, _T.TYPE_STRING, _OPT, None, None),
        ("lines", 3, _T.TYPE_MESSAGE, _REP, "XLine", None),
        ("event_metadata", 4, _T.TYPE_MESSAGE, _REP,
         "XPlane.EventMetadataEntry", None),
        ("stat_metadata", 5, _T.TYPE_MESSAGE, _REP,
         "XPlane.StatMetadataEntry", None),
        ("stats", 6, _T.TYPE_MESSAGE, _REP, "XStat", None),
    ],
    "XLine": [
        ("id", 1, _T.TYPE_INT64, _OPT, None, None),
        ("name", 2, _T.TYPE_STRING, _OPT, None, None),
        ("timestamp_ns", 3, _T.TYPE_INT64, _OPT, None, None),
        ("events", 4, _T.TYPE_MESSAGE, _REP, "XEvent", None),
    ],
    "XEvent": [
        ("metadata_id", 1, _T.TYPE_INT64, _OPT, None, None),
        ("offset_ps", 2, _T.TYPE_INT64, _OPT, None, 0),
        ("duration_ps", 3, _T.TYPE_INT64, _OPT, None, None),
        ("stats", 4, _T.TYPE_MESSAGE, _REP, "XStat", None),
        ("num_occurrences", 5, _T.TYPE_INT64, _OPT, None, 0),
    ],
    "XStat": [
        ("metadata_id", 1, _T.TYPE_INT64, _OPT, None, None),
        ("double_value", 2, _T.TYPE_DOUBLE, _OPT, None, 0),
        ("uint64_value", 3, _T.TYPE_UINT64, _OPT, None, 0),
        ("int64_value", 4, _T.TYPE_INT64, _OPT, None, 0),
        ("str_value", 5, _T.TYPE_STRING, _OPT, None, 0),
        ("bytes_value", 6, _T.TYPE_BYTES, _OPT, None, 0),
        ("ref_value", 7, _T.TYPE_UINT64, _OPT, None, 0),
    ],
    "XEventMetadata": [
        ("id", 1, _T.TYPE_INT64, _OPT, None, None),
        ("name", 2, _T.TYPE_STRING, _OPT, None, None),
        ("display_name", 4, _T.TYPE_STRING, _OPT, None, None),
        ("stats", 5, _T.TYPE_MESSAGE, _REP, "XStat", None),
    ],
    "XStatMetadata": [
        ("id", 1, _T.TYPE_INT64, _OPT, None, None),
        ("name", 2, _T.TYPE_STRING, _OPT, None, None),
    ],
}
_ONEOF = {"XEvent": "data", "XStat": "value"}
_MAPS = {"EventMetadataEntry": "XEventMetadata",
         "StatMetadataEntry": "XStatMetadata"}


def _add_fields(msg, fields):
    for name, number, ftype, label, mtype, oneof in fields:
        f = msg.field.add(name=name, number=number, type=ftype, label=label)
        if mtype:
            f.type_name = f".{_PKG}.{mtype}"
        if oneof is not None:
            f.oneof_index = oneof


def _build():
    fdp = descriptor_pb2.FileDescriptorProto(
        name="chipbench_xspace.proto", package=_PKG, syntax="proto3")
    for name, fields in _SCHEMA.items():
        msg = fdp.message_type.add(name=name)
        if name in _ONEOF:
            msg.oneof_decl.add(name=_ONEOF[name])
        _add_fields(msg, fields)
        if name == "XPlane":
            for entry, value in _MAPS.items():
                e = msg.nested_type.add(name=entry)
                e.options.map_entry = True
                _add_fields(e, [("key", 1, _T.TYPE_INT64, _OPT, None, None),
                                ("value", 2, _T.TYPE_MESSAGE, _OPT, value,
                                 None)])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{_PKG}.XSpace"))


XSpace = _build()


def read_xspace(path: str):
    """The ``XSpace`` message stored in ``path`` (gzipped if it ends in
    ``.gz``)."""
    space = XSpace()
    with (gzip.open if str(path).endswith(".gz") else open)(path, "rb") as f:
        space.ParseFromString(f.read())
    return space


def stat_value(stat):
    """An ``XStat``'s value, whichever kind it holds."""
    kind = stat.WhichOneof("value")
    return None if kind is None else getattr(stat, kind)
