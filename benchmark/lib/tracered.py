"""From a profiler trace to numbers. The reduction is the yardstick's: it
lives with the benchmark and is tested on a small recorded trace
(benchmark/tests/small_trace.json).

A trace is read into plain data first,
    [{"name": plane, "lines": [{"name": line, "events": [[name, start_ns, dur_ns], ...]}]}]
and everything else works on that, so the test needs no profiler.

On a TPU each chip is a plane "/device:TPU:<n>"; its line "XLA Ops" has one
event per executed HLO operation, "XLA Modules" one per executed program.
An event's name is the operation's whole HLO text,
    %_dequant_matmul_2d.86 = bf16[16,4096]{..} custom-call(bf16[16,14336]{..} %x, s8[14336,4096]{..} %w, ...), custom_call_target="tpu_custom_call", ...
which `short_name` cuts to the name before " = " and, for a Pallas kernel (a
custom call to tpu_custom_call), the shapes of its operands and result:
    _dequant_matmul_2d.86(bf16[16,14336],s8[14336,4096],f32[1,4096])->bf16[16,4096]
so a kernel can be told by what the compiler calls it and its operations
and bytes computed from the shapes it ran with. Operations nest (a `while`
holds its body's operations): sums are of SELF time, an operation's time
less that of the operations inside it. Busy time is the union of the
"XLA Ops" events; it is averaged over the chips used.

Exposed collective time is likewise taken against LEAVES only: a collective
is hidden only while an operation that encloses no other runs beside it. An
enclosing `while` or call spans its body's collectives and hides nothing.
On the TPU the "XLA Ops" line is serial, so a synchronous all-reduce there,
and the wait inside an asynchronous collective's `-done`, are exposed for
their whole length; what an asynchronous collective does between its
`-start` and `-done` lies on another line, under the compute, and is not
counted.
"""

from __future__ import annotations

import re

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|"
    r"collective-broadcast|send|recv", re.I)


SHAPE = re.compile(r"\b([a-z]+\d*\[[\d,]*\])")


def short_name(text: str) -> str:
    """The operation's name; for a Pallas kernel with its shapes (above)."""
    head, sep, rest = text.partition(" = ")
    name = head.lstrip("%")
    if not sep or "tpu_custom_call" not in rest:
        return name
    result, _, call = rest.partition(" custom-call(")
    operands = call.partition("custom_call_target")[0]
    return (f"{name}({','.join(SHAPE.findall(operands))})"
            f"->{','.join(SHAPE.findall(result))}")


def self_times(events: list[list]) -> tuple[list[int], list[bool]]:
    """Per event (sorted by start) its duration less the events nested in
    it, and whether it is a leaf (encloses no other event)."""
    out = [d for _, _, d in events]
    leaf = [True] * len(events)
    stack: list[int] = []          # indices of the open enclosing events
    for i, (_, s, d) in enumerate(events):
        while stack and events[stack[-1]][1] + events[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            out[stack[-1]] -= d
            leaf[stack[-1]] = False
        stack.append(i)
    return out, leaf


def read_xplane(path: str) -> list[dict]:
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:"):
            continue    # host threads: nothing here reads them yet
        lines = []
        for line in plane.lines:
            if line.name not in (OPS_LINE, MODULES_LINE):
                continue
            lines.append({"name": line.name, "events": [
                [short_name(ev.name), int(ev.start_ns), int(ev.duration_ns)]
                for ev in line.events]})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def device_planes(planes: list[dict]) -> list[dict]:
    """One plane per chip, in order of the chip's number."""
    found = {}
    for p in planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", p["name"])
        if m and any(l["name"] == OPS_LINE and l["events"]
                     for l in p["lines"]):
            found[int(m.group(1))] = p
    return [found[k] for k in sorted(found)]


def line_events(plane: dict, name: str) -> list[list]:
    for l in plane["lines"]:
        if l["name"] == name:
            return sorted(l["events"], key=lambda e: e[1])
    return []


def union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[tuple[int, int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def covered(intervals: list[tuple[int, int]]) -> int:
    return sum(b - a for a, b in union(intervals))


def overlap(a: list[tuple[int, int]], b: list[tuple[int, int]]) -> int:
    """Length of the intersection of two unions."""
    a, b = union(a), union(b)
    i = j = total = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def reduce(planes: list[dict], n_devices: int) -> dict:
    """busy_s, window_s (first to last device event; the caller may put
    its own clock's window in its place), per-operation sums of self time,
    exposed collective time, and the breakdown the result line carries.
    Seconds, averaged over the chips."""
    devs = device_planes(planes)[:n_devices]
    if not devs:
        raise RuntimeError("the trace has no device plane with operations: "
                           f"planes {[p['name'] for p in planes]}")
    ops: dict[str, list] = {}
    busy = exposed = coll = 0
    first, last = None, None
    gaps: dict[str, int] = {}
    for plane in devs:
        events = line_events(plane, OPS_LINE)
        spans = [(s, s + d) for _, s, d in events]
        busy += covered(spans)
        first = spans[0][0] if first is None else min(first, spans[0][0])
        last = max(last or 0, max(b for _, b in spans))
        own_ns, leaf = self_times(events)
        for (name, _, _), own in zip(events, own_ns):
            rec = ops.setdefault(name, [0, 0])
            rec[0] += max(own, 0)
            rec[1] += 1
        cspans = [(s, s + d) for n, s, d in events if COLLECTIVE.search(n)]
        # only a leaf can hide a collective: a `while` spans its own body
        kspans = [(s, s + d) for (n, s, d), is_leaf in zip(events, leaf)
                  if is_leaf and not COLLECTIVE.search(n)]
        coll += covered(cspans)
        exposed += covered(cspans) - overlap(cspans, kspans)
        # idle gaps, by the program the device ran next
        modules = line_events(plane, MODULES_LINE)
        mi = 0
        merged = union(spans)
        for (_, end), (start, _) in zip(merged, merged[1:]):
            while mi < len(modules) and modules[mi][1] + modules[mi][2] <= start:
                mi += 1
            nxt = (modules[mi][0] if mi < len(modules) else "?")
            label = "before " + re.sub(r"\(\d+\)$", "", nxt)[:60]
            gaps[label] = gaps.get(label, 0) + (start - end)
    n = len(devs)
    ns = 1e-9 / n
    top = sorted(ops.items(), key=lambda kv: -kv[1][0])
    return {
        "busy_s": busy * ns,
        "window_s": (last - first) * 1e-9,
        "devices": n,
        "collective_s": coll * ns,
        "collective_exposed_s": exposed * ns,
        "ops": [[k, v[0] * ns, v[1] / n] for k, v in top],
        "breakdown": {
            "device_ops": [[k[:120], v[0] * ns] for k, v in top[:10]],
            "idle_gaps": [[k, v * ns] for k, v in
                          sorted(gaps.items(), key=lambda kv: -kv[1])[:10]]},
    }
