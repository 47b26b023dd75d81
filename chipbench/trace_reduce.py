"""From a profiler trace (`.xplane.pb`) to the numbers the benchmark
reports, with `jax.profiler.ProfileData` and nothing else.

    busy_s     union of the intervals in which an operation ran on a
               device, averaged over the devices traced
    window_s   the traced sub-window: the driver's own span
               `chipbench:traced_window`, put around the traced steps and
               synced at both ends (a trace without one: first operation's
               start to last operation's end, averaged likewise)
    device_ops [[name, seconds], ...] self time by operation, summed over
               devices, longest first
    idle_gaps  [[what the host was doing, seconds], ...]: every gap
               between operations on the first device, and from the
               window's edges to its first and last operation, given to
               the driver's own `chipbench:*` span that covers most of it
               (`other` where none does)
    categories {category: seconds} self time by what the operation is, as
               far as its name tells (`category`)

    python3 chipbench/trace_reduce.py <file-or-dir> [--dump]
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys

OPS_LINE = "XLA Ops"
SPAN_PREFIX = "chipbench:"
WINDOW_SPAN = SPAN_PREFIX + "traced_window"
GAP_FLOOR_NS = 2_000      # shorter gaps are the device's own launch cost


def union_ns(intervals) -> float:
    """Total length covered by (start, end) pairs."""
    total, reach = 0.0, None
    for start, end in sorted(intervals):
        if reach is None or start > reach:
            total += end - start
            reach = end
        elif end > reach:
            total += end - reach
            reach = end
    return total


def gaps_ns(intervals, floor: float = 0.0) -> list:
    """The (start, end) gaps between the merged intervals."""
    out, reach = [], None
    for start, end in sorted(intervals):
        if reach is not None and start - reach > floor:
            out.append((reach, start))
        reach = end if reach is None else max(reach, end)
    return out


def self_times(events) -> list:
    """(name, category, self ns) of every event of one line: its duration
    less that of the events nested inside it."""
    out, stack = [], []
    for ev in sorted(events, key=lambda e: (e["start"], -e["end"])):
        while stack and stack[-1]["end"] <= ev["start"]:
            stack.pop()
        if stack and ev["end"] <= stack[-1]["end"]:
            stack[-1]["self"] -= ev["end"] - ev["start"]
        ev = dict(ev, self=ev["end"] - ev["start"])
        stack.append(ev)
        out.append(ev)
    return [(e["name"], e["category"], max(0.0, e["self"])) for e in out]


_HLO = re.compile(r"^(%[\w.\-]+) = (.*?)\b([a-z][a-z\-]*)\(")


def short_name(text: str) -> str:
    """`%fusion.5 fusion/kOutput bf16[1024,54,54,64]` from the HLO text the
    TPU's trace names an operation by; other names as they are."""
    m = _HLO.match(text)
    if not m:
        return text[:120]
    name, shape, opcode = m.groups()
    kind = re.search(r"kind=(k\w+)", text)
    shapes = re.findall(r"[a-z0-9]+\[[\d,]*\]", shape)
    return " ".join(filter(None, [
        name, opcode + ("/" + kind.group(1) if kind else ""),
        shapes[-1] if shapes else ""]))


def category(text: str) -> str:
    """What an operation is, as far as its name tells: `matmul` for a
    convolution or dot and the fusions built on one (on the TPU an output
    fusion, `kind=kOutput`, or a name that says convolution), the HLO
    opcode (with a fusion's kind) otherwise, and "" where the name is no
    HLO text at all."""
    m = _HLO.match(text)
    if not m:
        return ""
    opcode = m.group(3)
    if opcode in ("convolution", "dot") or "kind=kOutput" in text \
            or "convolution" in m.group(1):
        return "matmul"
    kind = re.search(r"kind=(k\w+)", text)
    return opcode + ("/" + kind.group(1) if kind else "")


def _events(line) -> list:
    return [{"name": ev.name, "start": float(ev.start_ns),
             "end": float(ev.start_ns + ev.duration_ns),
             "category": category(ev.name)} for ev in line.events]


def load(path: str) -> dict:
    """{"devices": {plane: [event]}, "spans": [event]} of one trace."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = _events(line)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [e for e in _events(line)
                          if e["name"].startswith(SPAN_PREFIX)]
    return {"devices": devices, "spans": spans}


def reduce(trace: dict) -> dict:
    devices = {k: v for k, v in trace["devices"].items() if v}
    if not devices:
        raise ValueError("the trace holds no device operation")
    whole = [s for s in trace["spans"] if s["name"] == WINDOW_SPAN]
    spans = [s for s in trace["spans"] if s["name"] != WINDOW_SPAN]
    busy = window = 0.0
    ops: dict = {}
    categories: dict = {}
    edges = {}
    for plane, events in devices.items():
        lo = whole[0]["start"] if whole else min(e["start"] for e in events)
        hi = whole[0]["end"] if whole else max(e["end"] for e in events)
        edges[plane] = (lo, hi)
        busy += union_ns([(max(lo, e["start"]), min(hi, e["end"]))
                          for e in events
                          if e["end"] > lo and e["start"] < hi])
        window += hi - lo
        for name, kind, ns in self_times(events):
            name = short_name(name)
            ops[name] = ops.get(name, 0.0) + ns
            categories[kind] = categories.get(kind, 0.0) + ns
    n = len(devices)
    first = sorted(devices)[0]
    lo, hi = edges[first]
    idle: dict = {}
    for start, end in gaps_ns(
            [(lo, lo)] + [(e["start"], e["end"]) for e in devices[first]]
            + [(hi, hi)], GAP_FLOOR_NS):
        best, cover = "other", 0.0
        for span in spans:
            overlap = min(end, span["end"]) - max(start, span["start"])
            if overlap > cover:
                best, cover = span["name"][len(SPAN_PREFIX):], overlap
        idle[best] = idle.get(best, 0.0) + (end - start)
    ranked = lambda d: [[k, v / 1e9] for k, v in
                        sorted(d.items(), key=lambda kv: -kv[1])]
    return {"busy_s": busy / n / 1e9, "window_s": window / n / 1e9,
            "devices": n, "device_ops": ranked(ops),
            "idle_gaps": ranked(idle),
            "categories": {k: v / 1e9 for k, v in categories.items()}}


def find(path: str) -> str:
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def reduce_dir(path: str) -> dict:
    return reduce(load(find(path)))


def _dump(path: str) -> None:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(find(path))
    for plane in data.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print("  LINE", line.name, len(events))
            for ev in events[:6]:
                print("     ", ev.name[:80], ev.start_ns, ev.duration_ns,
                      {k: str(v)[:60] for k, v in list(ev.stats)[:12]})


if __name__ == "__main__":
    if "--dump" in sys.argv:
        _dump(sys.argv[1])
    else:
        out = reduce_dir(sys.argv[1])
        out["device_ops"] = out["device_ops"][:25]
        print(json.dumps(out, indent=1))
