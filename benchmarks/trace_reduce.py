"""The reduction from a profiler trace to numbers.

``load`` turns the ``.xplane.pb`` that ``jax.profiler`` wrote into plain rows
``[plane, line, name, start_ns, duration_ns]``; ``reduce`` works on such rows
alone, so that a recorded cut-down trace (``fixtures/``) checks it on the CPU.

On a TPU each chip is a plane ``/device:TPU:<n>`` whose line ``XLA Ops`` holds
one event per executed operation (a ``while`` holds its body's operations
nested inside it) and whose line ``XLA Modules`` holds one event per executed
program, named ``<jit name>(<fingerprint>)``. Host threads are lines of the
plane ``/host:CPU``; the benchmark's own spans are found there by name.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Row = Sequence  # [plane, line, name, start_ns, duration_ns]

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def load(path: str, keep_host: Iterable[str] = ()) -> List[list]:
    """Device rows in full; of the host planes only events named in
    ``keep_host`` (a host plane holds a great many runtime events)."""
    from jax.profiler import ProfileData

    keep = set(keep_host)
    rows = []
    for plane in ProfileData.from_file(path).planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        if not device and not keep:
            continue
        for line in plane.lines:
            for ev in line.events:
                if device or ev.name in keep:
                    rows.append([plane.name, line.name, ev.name,
                                 int(ev.start_ns), int(ev.duration_ns)])
    return rows


def union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    merged: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1] = (merged[-1][0], b)
        else:
            merged.append((a, b))
    return merged


def self_times(events: List[Tuple[str, int, int]]) -> Dict[str, int]:
    """Summed duration by name, a nested event's time taken out of the event
    that holds it (one line's events nest and never cross): a ``while``
    keeps what its body's operations leave, the loop's own overhead."""
    own: Dict[str, int] = {}
    stack: List[list] = []  # [name, end, self]
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and start >= stack[-1][1]:
            done = stack.pop()
            own[done[0]] = own.get(done[0], 0) + done[2]
        if stack:
            stack[-1][2] -= dur
        stack.append([name, start + dur, dur])
    for name, _, rest in stack:
        own[name] = own.get(name, 0) + rest
    return own


def module_name(event_name: str) -> str:
    """``jit_grow_tree(1234567)`` -> ``jit_grow_tree``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def reduce(rows: Iterable[Row], host_spans: Optional[Dict[str, str]] = None,
           top: int = 10) -> dict:
    """Busy and idle time of the traced stretch, per-program device time, the
    operations that took most of it and the longest idle gaps, each gap named
    by the host span its middle lies in (``host_spans``: span name -> what
    the host was doing) or ``elsewhere``.

    The stretch runs from the first device operation's start to the last
    one's end; ``busy_s`` is the union of the operations' intervals (a
    ``while`` counts from its start to its end: the device is running the
    program; what the loop itself costs shows in ``device_ops``, by self
    time), averaged over the chips that ran any. Returns None where no device operation ran."""
    ops: Dict[str, List[Tuple[str, int, int]]] = {}
    modules: Dict[str, List[int]] = {}
    host_spans = host_spans or {}
    spans: List[Tuple[int, int, str]] = []
    for plane, line, name, start, dur in rows:
        if DEVICE_PLANE.match(plane):
            if line == OPS_LINE:
                ops.setdefault(plane, []).append((name, start, dur))
            elif line == MODULES_LINE:
                modules.setdefault(module_name(name), []).append(dur)
        elif name in host_spans:
            spans.append((start, start + dur, host_spans[name]))
    if not ops:
        return None
    busy = window = 0
    gaps: List[Tuple[int, int]] = []
    own: Dict[str, int] = {}
    for events in ops.values():
        merged = union([(s, s + d) for _, s, d in events])
        busy += sum(b - a for a, b in merged)
        window += merged[-1][1] - merged[0][0]
        gaps += [(merged[i][1], merged[i + 1][0]) for i in range(len(merged) - 1)]
        for name, t in self_times(events).items():
            own[name] = own.get(name, 0) + t
    chips = len(ops)

    def host_was(a: int, b: int) -> str:
        mid = (a + b) // 2
        return next((what for s, e, what in spans if s <= mid < e), "elsewhere")

    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "chips": chips,
        "busy_s": busy / chips / 1e9,
        "window_s": window / chips / 1e9,
        "modules": {name: {"count": len(d), "seconds": sum(d) / chips / 1e9}
                    for name, d in modules.items()},
        "device_ops": [[name, t / chips / 1e9] for name, t in
                       sorted(own.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[host_was(a, b), (b - a) / 1e9] for a, b in gaps[:top]],
    }


def dump(rows: List[list], path: str, ops_kept: int = 4000) -> None:
    """Writes a cut-down copy of the rows as JSON: every program execution
    and host span, and the first ``ops_kept`` operations of each chip, with a
    count of what each line held. What a fixture is cut from."""
    import json

    counts: Dict[str, int] = {}
    kept: List[list] = []
    seen: Dict[str, int] = {}
    for row in sorted(rows, key=lambda r: r[3]):
        key = "%s | %s" % (row[0], row[1])
        counts[key] = counts.get(key, 0) + 1
        if row[1] == OPS_LINE:
            seen[row[0]] = seen.get(row[0], 0) + 1
            if seen[row[0]] > ops_kept:
                continue
        kept.append(row)
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "w", encoding="utf-8") as fh:
        json.dump({"lines": counts, "rows": kept}, fh)
