"""The profiler's ``.xplane.pb`` read as a protobuf message.

``jax.profiler.ProfileData`` gives each event its own stats but not the
stats of its *metadata*, where the TPU trace keeps an operation's op path
(``tf_op``: ``jit(step_fn)/transpose(jvp(head))/dot_general``, the name
stack ``jax.named_scope`` writes). This module parses the same file with
the ``XSpace`` schema of ``tsl/profiler/protobuf/xplane.proto``, built at
run time from the fields the benchmark reads (unknown fields are skipped
by the parser), so no generated code or extra package is needed.
"""

from __future__ import annotations

import functools


def _message(fdp, name, fields):
    from google.protobuf import descriptor_pb2

    F = descriptor_pb2.FieldDescriptorProto
    m = fdp.message_type.add(name=name)
    for fname, number, typ, repeated, type_name in fields:
        f = m.field.add(name=fname, number=number, type=typ,
                        label=F.LABEL_REPEATED if repeated
                        else F.LABEL_OPTIONAL)
        if type_name:
            f.type_name = type_name
    return m


@functools.lru_cache(maxsize=None)
def schema():
    """The ``XSpace`` message class (fields by xplane.proto's numbers)."""
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)

    F = descriptor_pb2.FieldDescriptorProto
    I64, U64, STR, MSG = F.TYPE_INT64, F.TYPE_UINT64, F.TYPE_STRING, \
        F.TYPE_MESSAGE
    fdp = descriptor_pb2.FileDescriptorProto(
        name="benchmarks_chip_xplane.proto", package="bench_xplane",
        syntax="proto3")
    _message(fdp, "XStat", [
        ("metadata_id", 1, I64, False, None),
        ("double_value", 2, F.TYPE_DOUBLE, False, None),
        ("uint64_value", 3, U64, False, None),
        ("int64_value", 4, I64, False, None),
        ("str_value", 5, STR, False, None),
        ("ref_value", 7, U64, False, None)])
    _message(fdp, "XEventMetadata", [
        ("id", 1, I64, False, None), ("name", 2, STR, False, None),
        ("display_name", 4, STR, False, None),
        ("stats", 5, MSG, True, ".bench_xplane.XStat")])
    _message(fdp, "XStatMetadata", [
        ("id", 1, I64, False, None), ("name", 2, STR, False, None)])
    _message(fdp, "XEvent", [
        ("metadata_id", 1, I64, False, None),
        ("offset_ps", 2, I64, False, None),
        ("duration_ps", 3, I64, False, None)])
    _message(fdp, "XLine", [
        ("id", 1, I64, False, None), ("name", 2, STR, False, None),
        ("timestamp_ns", 3, I64, False, None),
        ("events", 4, MSG, True, ".bench_xplane.XEvent")])
    plane = _message(fdp, "XPlane", [
        ("id", 1, I64, False, None), ("name", 2, STR, False, None),
        ("lines", 3, MSG, True, ".bench_xplane.XLine"),
        ("event_metadata", 4, MSG, True,
         ".bench_xplane.XPlane.EventMetadataEntry"),
        ("stat_metadata", 5, MSG, True,
         ".bench_xplane.XPlane.StatMetadataEntry")])
    for entry, value in (("EventMetadataEntry", "XEventMetadata"),
                         ("StatMetadataEntry", "XStatMetadata")):
        e = plane.nested_type.add(name=entry)
        e.options.map_entry = True
        e.field.add(name="key", number=1, type=I64,
                    label=F.LABEL_OPTIONAL)
        e.field.add(name="value", number=2, type=MSG,
                    label=F.LABEL_OPTIONAL,
                    type_name=f".bench_xplane.{value}")
    _message(fdp, "XSpace", [
        ("planes", 1, MSG, True, ".bench_xplane.XPlane")])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


def load(path: str):
    """The ``XSpace`` in the ``.xplane.pb`` file at ``path``."""
    space = schema()()
    with open(path, "rb") as fh:
        space.ParseFromString(fh.read())
    return space


def metadata_stat(plane, name: str) -> dict:
    """``{event metadata id: string value}`` of the metadata stat called
    ``name`` on one plane (entries without it are left out). A string
    is stored in place or as a reference to a stat metadata entry."""
    names = {k: v.name for k, v in plane.stat_metadata.items()}
    out = {}
    for k, md in plane.event_metadata.items():
        for st in md.stats:
            if names.get(st.metadata_id) == name:
                out[k] = st.str_value or names.get(st.ref_value, "")
    return out
