"""The two facts of a profiler trace that the harness's reduced one
(``benchmarks/lib/trace.py``) leaves out, read from the ``*.xplane.pb`` itself:

* when the profiler session started and stopped, in realtime nanoseconds
  (the ``Task Environment`` plane's ``profile_start_time`` / ``_stop_time``):
  every timestamp in the file has the start subtracted from it;
* each device operation's scope: on this runtime an ``XLA Ops`` event is named
  by its instruction's HLO text, and the ``jax.named_scope`` path (the HLO's
  ``op_name``) is the ``tf_op`` stat of the event's *metadata*, which
  ``jax.profiler.ProfileData`` does not hand out.

The file is protobuf (tsl ``xplane.proto``); the few fields needed are decoded
from the wire format directly, the lines' events skipped unread (the program
has a decoder of its own for event names and times, ``profiling/xplane.py``;
the yardstick reads the file itself and imports none of the program's
internals):

    XSpace.planes = 1
    XPlane { name=2, event_metadata=4 (map<int64, XEventMetadata>), stat_metadata=5 (map<int64, XStatMetadata>), stats=6 }
    XEventMetadata { name=2, stats=5 }     XStatMetadata { name=2 }
    XStat { metadata_id=1, uint64_value=3, int64_value=4, str_value=5, ref_value=7 (a stat_metadata id whose name is the value) }
"""

from __future__ import annotations

import dataclasses

DEVICE_PLANE = "/device:TPU:"
ENVIRONMENT_PLANE = "Task Environment"
SCOPE_STAT = "tf_op"


def _varint(buf, i: int):
    shift = result = 0
    while True:
        b = buf[i]
        i += 1
        result |= (b & 0x7F) << shift
        if not b & 0x80:
            return result, i
        shift += 7


def fields(buf):
    """(field number, wire type, value) of one message; a length-delimited
    value is a ``memoryview`` of its bytes, unread."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            if i + size > n:
                raise ValueError("a length-delimited field runs past the end of its message")
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {wire} does not occur in an xplane")
        yield number, wire, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _map_entry(entry):
    """(key, value message) of one protobuf map entry."""
    key = value = None
    for number, _, v in fields(entry):
        if number == 1:
            key = v
        elif number == 2:
            value = v
    return key, value


def _stat(message, stat_names: dict):
    """(name, value) of one XStat; a string for str / ref values, an int for the integer ones."""
    name = value = None
    for number, _, v in fields(message):
        if number == 1:
            name = stat_names.get(v)
        elif number in (3, 4):
            value = v
        elif number == 5:
            value = _text(v)
        elif number == 7:
            value = stat_names.get(v)
    return name, value


@dataclasses.dataclass
class Session:
    start_ns: int | None  # what the profiler subtracted from every timestamp of the file
    stop_ns: int | None
    scopes: dict  # a device event's name (its instruction's HLO text) -> its tf_op (the op_name with scopes)


def read_session(path: str) -> Session:
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    session = Session(None, None, {})
    for number, _, plane in fields(buf):
        if number != 1:
            continue
        name, stat_names, metadata, stats = "", {}, [], []
        for n, _, v in fields(plane):
            if n == 2:
                name = _text(v)
            elif n == 4:
                metadata.append(v)
            elif n == 5:
                key, message = _map_entry(v)
                stat_names[key] = next((_text(x) for k, _, x in fields(message) if k == 2), "") if message is not None else ""
            elif n == 6:
                stats.append(v)
        if name == ENVIRONMENT_PLANE:
            found = dict(_stat(s, stat_names) for s in stats)
            session.start_ns, session.stop_ns = found.get("profile_start_time"), found.get("profile_stop_time")
        elif name.startswith(DEVICE_PLANE):
            for entry in metadata:
                _, message = _map_entry(entry)
                if message is None:
                    continue
                event_name, scope = "", None
                for n, _, v in fields(message):
                    if n == 2:
                        event_name = _text(v)
                    elif n == 5:
                        stat_name, value = _stat(v, stat_names)
                        if stat_name == SCOPE_STAT:
                            scope = value
                if scope:
                    session.scopes[event_name] = scope
    return session
