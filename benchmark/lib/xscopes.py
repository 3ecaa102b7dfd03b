"""Device time by the program's `jax.named_scope`s.

The profiler keeps, with every XLA operation's metadata, the stat `tf_op`: the
operation's `op_name`, which is jax's name stack when the operation was
traced ("jit(train_step)/transpose(jvp(kda))/kda_backward/while/body/mul").
`jax.profiler.ProfileData` hands out an event's own stats and not its
metadata's, and `lib/tracered.py` keeps an operation's name alone, so the
capture is read here as protobuf wire format: the few fields of
tsl/profiler/protobuf/xplane.proto that this needs, by their numbers.

An operation belongs to the innermost of the scopes asked for that its name
stack holds; to "other" when it holds none, to "unnamed" when the compiler
gave it no `op_name` (copies, some fusions). Times are SELF times on the
"XLA Ops" line (lib/tracered.self_times), seconds, averaged over the chips.
"""

from __future__ import annotations

import re

from lib import tracered


def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: an int for a varint, the bytes
    of anything else."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        else:
            size = {1: 8, 5: 4}.get(wire)
            if size is None:            # 2: length-delimited
                size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        yield key >> 3, value


def _map(entries) -> dict:
    """A protobuf map<int64, message>: entries of key = 1, value = 2."""
    out = {}
    for entry in entries:
        f = dict(_fields(entry))
        out[f.get(1, 0)] = f.get(2, b"")
    return out


def _plane(buf) -> tuple[str, list, dict]:
    """-> (name, [(line name, [(metadata id, start_ps, duration_ps)])],
    {metadata id: tf_op or None})."""
    name, lines, events_meta, stats_meta = "", [], [], []
    for f, v in _fields(buf):
        if f == 2:
            name = bytes(v).decode()
        elif f == 3:
            lines.append(v)
        elif f == 4:
            events_meta.append(v)
        elif f == 5:
            stats_meta.append(v)
    if not name.startswith("/device:TPU:"):
        return name, [], {}
    stat_name = {k: bytes(dict(_fields(v)).get(2, b"")).decode()
                 for k, v in _map(stats_meta).items()}
    tf_op = {}
    for k, meta in _map(events_meta).items():
        tf_op[k] = None
        for f, v in _fields(meta):
            if f != 5:
                continue
            stat = dict(_fields(v))
            if stat_name.get(stat.get(1)) == "tf_op":
                tf_op[k] = (bytes(stat[5]).decode() if 5 in stat
                            else stat_name.get(stat.get(7), ""))
    out = []
    for line in lines:
        f = {}
        events = []
        for num, v in _fields(line):
            if num == 4:
                e = dict(_fields(v))
                events.append((e.get(1, 0), e.get(2, 0), e.get(3, 0)))
            else:
                f[num] = v
        t0 = f.get(3, 0) * 1000
        out.append((bytes(f.get(2, b"")).decode(),
                    [(m, t0 + off, dur) for m, off, dur in events]))
    return name, out, tf_op


def scope_of(tf_op: str | None, scopes) -> str:
    if tf_op is None:
        return "unnamed"
    for word in reversed(re.findall(r"\w+", tf_op)):
        if word in scopes:
            return word
    return "other"


def scope_seconds(path: str, scopes) -> dict | None:
    """{scope: seconds of self time} over the capture at `path`, or None
    where it has no device plane with named operations."""
    with open(path, "rb") as f:
        space = f.read()
    total: dict[str, float] = {}
    chips = 0
    for f, v in _fields(space):
        if f != 1:
            continue
        _, lines, tf_op = _plane(v)
        events = sorted((e for n, evs in lines if n == tracered.OPS_LINE
                         for e in evs), key=lambda e: e[1])
        if not events or not any(tf_op.values()):
            continue
        chips += 1
        own, _ = tracered.self_times([list(e) for e in events])
        for (meta, _, _), ps in zip(events, own):
            key = scope_of(tf_op.get(meta), scopes)
            total[key] = total.get(key, 0.0) + max(ps, 0) * 1e-12
    return {k: v / chips for k, v in total.items()} if chips else None
