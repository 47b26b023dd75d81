"""Device time by the program's own names, from a profiler trace.

The program puts `jax.named_scope`s around its phases and function-call
layers, and flax names its modules; XLA carries that name stack through
compilation as each instruction's `op_name`, and the TPU's trace stores it
as the `tf_op` stat of the event's *metadata* (with `hlo_category`, XLA's
own `flops` and `bytes_accessed`, and `source`). `jax.profiler.ProfileData`
shows only the stats of the event itself, so this file reads the
`.xplane.pb` in its wire format: the seven messages it needs, nothing else.

    seconds by scope   self time (`trace_reduce.self_times`) of every
                       `XLA Ops` event, clipped to the driver's
                       `chipbench:traced_window` span where the trace has
                       one, summed over devices
    an event's scope   the innermost name of its stack that `scopes.json`
                       declares (a phase such as `augment`, a function-call
                       layer such as `lrn1`), else its module path below
                       the model (`conv2`, `stage1_block1/bn1`), else ""
    backward           a stack that went through `transpose(`
    idle gaps          as `trace_reduce.reduce` gives them to `chipbench:*`
                       spans, given to the program's own `dvggf:*` spans
                       (`telemetry`'s bridge to the profiler's clock)

A fused operation has one name stack, its root instruction's: what XLA
fused into a convolution is counted under the convolution's layer. What
XLA itself made (a copy, a layout change, the `%reverse` it splits from a
gather) has no name stack and falls to "". The names looked for are the
benchmark's own data (`chipbench/scopes.json`), not an import from the
program. A trace that holds none of the declared phases comes from a
program without scopes, or from a stale executable out of the compile
cache (the cache key leaves names out): the metrics' readers then return
None, never 0.

    python3 chipbench/scope_reduce.py <file-or-dir> [--depth N]
                       the whole table, forward and backward apart
"""

from __future__ import annotations

import json
import os
import re
import struct
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import trace_reduce  # noqa: E402

PROGRAM_SPANS = "dvggf:"
UNNAMED = ""


# ---- the .xplane.pb wire format --------------------------------------------

def _varint(buf, i: int):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: an int for a varint, a
    memoryview for a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {wire} is not in an XSpace")
        yield key >> 3, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _stat(buf):
    """XStat -> (metadata_id, value); a `ref_value` comes back as
    ("ref", id of the stat metadata whose name is the string)."""
    key, value = 0, None
    for num, v in _fields(buf):
        if num == 1:
            key = v
        elif num == 2:
            value = struct.unpack("<d", v)[0]
        elif num in (3, 4):
            value = v
        elif num == 5:
            value = _text(v)
        elif num == 6:
            value = bytes(v)
        elif num == 7:
            value = ("ref", v)
    return key, value


def _event_metadata(buf) -> dict:
    out = {"name": "", "stats": []}
    for num, v in _fields(buf):
        if num == 2:
            out["name"] = _text(v)
        elif num == 5:
            out["stats"].append(_stat(v))
    return out


def _map_entry(buf):
    key, value = 0, b""
    for num, v in _fields(buf):
        if num == 1:
            key = v
        elif num == 2:
            value = v
    return key, value


def _line(buf) -> dict:
    out = {"name": "", "timestamp_ns": 0, "events": []}
    for num, v in _fields(buf):
        if num == 2:
            out["name"] = _text(v)
        elif num == 3:
            out["timestamp_ns"] = v
        elif num == 4:
            meta = offset = duration = 0
            for n, x in _fields(v):
                if n == 1:
                    meta = x
                elif n == 2:
                    offset = x
                elif n == 3:
                    duration = x
            out["events"].append((meta, offset, duration))
    return out


def _plane(buf) -> dict:
    out = {"name": "", "lines": [], "event_metadata": {}, "stat_names": {}}
    for num, v in _fields(buf):
        if num == 2:
            out["name"] = _text(v)
        elif num == 3:
            out["lines"].append(_line(v))
        elif num == 4:
            key, value = _map_entry(v)
            out["event_metadata"][key] = _event_metadata(value)
        elif num == 5:
            key, value = _map_entry(v)
            out["stat_names"][key] = next(
                (_text(x) for n, x in _fields(value) if n == 2), "")
    return out


def read_xspace(path: str) -> list:
    """The planes of an `.xplane.pb`: name, lines (name, `timestamp_ns`,
    events as (metadata id, offset ps, duration ps)), event metadata (name
    and stats) and stat names, all by id."""
    with open(path, "rb") as f:
        data = memoryview(f.read())
    return [_plane(v) for num, v in _fields(data) if num == 1]


def _events(plane: dict, line: dict, wanted: tuple = ()) -> list:
    """A line's events as `trace_reduce` takes them, with the `wanted`
    stats of their metadata, by name, in the place of a category."""
    names, out = plane["stat_names"], []
    resolved: dict = {}
    for meta_id, offset_ps, duration_ps in line["events"]:
        if meta_id not in resolved:
            meta = plane["event_metadata"].get(meta_id, {"name": "",
                                                         "stats": []})
            stats = {}
            for key, value in meta["stats"]:
                name = names.get(key, "")
                if name not in wanted:
                    continue
                if isinstance(value, tuple):
                    value = names.get(value[1], "")
                stats[name] = value
            resolved[meta_id] = (meta["name"], stats)
        name, stats = resolved[meta_id]
        start = line["timestamp_ns"] + offset_ps / 1000.0
        out.append({"name": name, "start": start,
                    "end": start + duration_ps / 1000.0,
                    "category": stats})
    return out


def load(path: str) -> dict:
    """{"devices": {plane: [event]}, "spans": [event], "modules": [name]}:
    the `XLA Ops` of every TPU plane with their metadata's `tf_op` and
    `hlo_category` (as the event's "category"), the host's `chipbench:*`
    and `dvggf:*` spans, and the names on the `XLA Modules` lines."""
    devices, spans, modules = {}, [], set()
    for plane in read_xspace(path):
        if plane["name"].startswith("/device:TPU:"):
            for line in plane["lines"]:
                if line["name"] == trace_reduce.OPS_LINE:
                    devices[plane["name"]] = _events(
                        plane, line, ("tf_op", "hlo_category"))
                elif line["name"] == "XLA Modules":
                    modules |= {re.sub(r"\(\d+\)$", "", e["name"])
                                for e in _events(plane, line)}
        elif plane["name"].startswith("/host:"):
            for line in plane["lines"]:
                spans += [e for e in _events(plane, line)
                          if e["name"].startswith(
                              (trace_reduce.SPAN_PREFIX, PROGRAM_SPANS))]
    return {"devices": devices, "spans": spans, "modules": sorted(modules)}


# ---- from a name stack to a scope ------------------------------------------

def declared(path: str | None = None) -> dict:
    with open(path or os.path.join(ROOT, "chipbench", "scopes.json")) as f:
        return json.load(f)


_WRAPPED = re.compile(r"^([\w.\-]+)\((.*)\)$")


def scope_of(tf_op: str, names: dict) -> tuple:
    """(scope, backward) of one name stack such as
    `jit(train_step)/transpose(jvp(VGGF))/lrn1/dot_general:`."""
    parts = tf_op.rstrip(":").split("/")[:-1]     # the last is the primitive
    backward = any("transpose(" in p for p in parts)
    stack = []                    # (name, is a scope and not a jitted call)
    for part in parts:
        plain = True
        while (m := _WRAPPED.match(part)):
            plain = plain and m.group(1) in names["transforms"]
            part = m.group(2)
        stack.append((part, plain))
    known = set(names["phases"]) | set(names["layers"])
    for name, plain in reversed(stack):
        if plain and name in known:
            return name, backward
    for i, (name, plain) in enumerate(stack):
        if plain and name in names["models"]:
            path = []
            for below, is_scope in stack[i + 1:]:
                if not is_scope:       # a jitted call ends the module path
                    break
                path.append(below)
            return "/".join(path), backward
    return UNNAMED, backward


# ---- the reduction ----------------------------------------------------------

def reduce(trace: dict, names: dict | None = None) -> dict:
    """{"total_s", "scopes": {scope: {"forward": s, "backward": s}},
    "categories": {hlo_category: s}, "unnamed": {name stack less its
    primitive: s} for what fell to "", "phases_found": [...], "idle_gaps":
    [[span, s]], "modules": [...]}; shares of `total_s` sum to one."""
    names = names or declared()
    devices = {k: v for k, v in trace["devices"].items() if v}
    if not devices:
        raise ValueError("the trace holds no device operation")
    first = devices[sorted(devices)[0]]
    window = next(((s["start"], s["end"]) for s in trace["spans"]
                   if s["name"] == trace_reduce.WINDOW_SPAN), None)
    lo, hi = window or (min(e["start"] for e in first),
                        max(e["end"] for e in first))
    scopes: dict = {}
    categories: dict = {}
    unnamed: dict = {}
    total = 0.0
    for events in devices.values():
        # `self_times` hands an event's "category" back untouched: give it
        # the event itself, for its stats and its ends
        for _, ev, ns in trace_reduce.self_times(
                [dict(e, category=e) for e in events]):
            stats = ev["category"]
            inside = min(hi, ev["end"]) - max(lo, ev["start"]) \
                if window else ev["end"] - ev["start"]
            if inside <= 0 or ns <= 0:
                continue
            ns *= min(1.0, inside / (ev["end"] - ev["start"]))
            tf_op = stats.get("tf_op", "")
            scope, backward = scope_of(tf_op, names)
            if scope == UNNAMED:
                stem = tf_op.rpartition("/")[0]
                unnamed[stem] = unnamed.get(stem, 0.0) + ns / 1e9
            cell = scopes.setdefault(scope, {"forward": 0.0, "backward": 0.0})
            cell["backward" if backward else "forward"] += ns / 1e9
            kind = stats.get("hlo_category", "")
            categories[kind] = categories.get(kind, 0.0) + ns / 1e9
            total += ns / 1e9

    ours = [s for s in trace["spans"] if s["name"].startswith(PROGRAM_SPANS)]
    idle: dict = {}
    for start, end in trace_reduce.gaps_ns(
            [(lo, lo)] + [(e["start"], e["end"]) for e in first]
            + [(hi, hi)], trace_reduce.GAP_FLOOR_NS):
        best, cover = "other", 0.0
        for span in ours:
            overlap = min(end, span["end"]) - max(start, span["start"])
            if overlap > cover:
                best, cover = span["name"][len(PROGRAM_SPANS):], overlap
        idle[best] = idle.get(best, 0.0) + (end - start) / 1e9
    return {"total_s": total, "scopes": scopes, "categories": categories,
            "unnamed": unnamed,
            "phases_found": sorted(set(scopes) & set(names["phases"])),
            "idle_gaps": sorted(idle.items(), key=lambda kv: -kv[1]),
            "modules": trace.get("modules", [])}


def reduce_dir(path: str) -> dict:
    return reduce(load(trace_reduce.find(path)))


def of(facts: dict):
    """The reduction of a run's trace, made once; None (and a line on
    standard error) where the run was not traced or its trace holds none
    of the declared phases."""
    if "scopes" not in facts:
        facts["scopes"] = None
        if facts.get("trace_dir"):
            try:
                table = reduce_dir(facts["trace_dir"])
            except (FileNotFoundError, ValueError) as err:
                table, why = None, str(err)
            else:
                why = ("none of the declared phases is in the trace (a "
                       "program without scopes, or a stale executable); "
                       f"modules {table['modules']}")
            if table and table["phases_found"]:
                facts["scopes"] = table
            else:
                print(f"[chipbench] scope_reduce: {why}", file=sys.stderr,
                      flush=True)
    return facts["scopes"]


def share_pct(table: dict, scopes) -> float:
    """What the named scopes, forward and backward, take of `total_s`."""
    spent = sum(sum(table["scopes"].get(s, {}).values()) for s in scopes)
    return 100.0 * spent / table["total_s"]


def read_share(facts: dict, scopes):
    """What a per-layer metric's `read(facts)` returns for `scopes`: their
    share of the run's traced device self time, or None (see `of`)."""
    table = of(facts)
    return None if table is None else share_pct(table, scopes)


def _print(table: dict, depth: int = 0) -> None:
    total = table["total_s"]
    print(f"modules {table['modules']}; device self time {total * 1e3:.3f} ms;"
          f" phases found {table['phases_found']}")
    print(f"{'scope':<34}{'forward ms':>12}{'backward ms':>13}{'share %':>9}")
    merged: dict = {}
    for scope, t in table["scopes"].items():
        key = "/".join(scope.split("/")[:depth]) if depth else scope
        cell = merged.setdefault(key, {"forward": 0.0, "backward": 0.0})
        cell["forward"] += t["forward"]
        cell["backward"] += t["backward"]
    rows = sorted(merged.items(), key=lambda kv: -sum(kv[1].values()))
    for scope, t in rows:
        print(f"{scope or '(unnamed)':<34}{t['forward'] * 1e3:>12.3f}"
              f"{t['backward'] * 1e3:>13.3f}"
              f"{100 * sum(t.values()) / total:>9.2f}")
    print(f"{'sum':<34}{'':>12}{'':>13}"
          f"{100 * sum(sum(t.values()) for _, t in rows) / total:>9.2f}")
    print("by hlo_category: " + ", ".join(
        f"{k or '(none)'} {100 * v / total:.1f} %" for k, v in sorted(
            table["categories"].items(), key=lambda kv: -kv[1])
        if v >= 0.0005 * total))
    print("unnamed, by name stack: " + ", ".join(
        f"{k or '(none)'} {100 * v / total:.1f} %" for k, v in sorted(
            table["unnamed"].items(), key=lambda kv: -kv[1])
        if v >= 0.0005 * total))
    print("idle gaps over 2 us by the program's span: " + (", ".join(
        f"{k} {v * 1e3:.3f} ms" for k, v in table["idle_gaps"]) or "none"))


if __name__ == "__main__":
    import argparse
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("trace", help="an .xplane.pb, or a directory of one")
    parser.add_argument("--depth", type=int, default=0,
                        help="cut module paths to their first N names "
                             "(ResNet-50's 130 rows become its 16 blocks)")
    args = parser.parse_args()
    _print(reduce_dir(args.trace), args.depth)
