"""Device self time per ``jax.named_scope`` from a raw ``.xplane.pb``, by hand.

PERF.md section 5's per-scope table is this script's output on the profile
of a traced benchmark run (brought back before the harness removes it).
The scope is the ``tf_op`` stat of an op's *metadata*, which
``jax.profiler.ProfileData`` does not expose, so the file is read with the
xplane protobuf that ships beside jax. Not part of the benchmark: PERF.md
7.4 asks a ``benchmark`` PR to fold this into ``benchmarks/trace_reduce.py``.

    python helpers/xplane_scopes.py <file.xplane.pb> [traced_iterations=2]
"""
import collections
import json
import sys

SCOPES = ("hist_build", "partition", "split_find", "apply_split", "oob_leaf",
          "column_take")


def innermost(text):
    """The last scope name on a path such as
    ``jit(grow_tree)/while/body/apply_split/hist_build/dot``."""
    best, at = None, -1
    for s in SCOPES:
        i = text.rfind("/" + s + "/")
        if i < 0 and text.endswith("/" + s):
            i = len(text) - len(s) - 1
        if i > at:
            best, at = s, i
    return best


def self_times(events):
    """[(event, self_ps)] of one line's (id, offset, duration) events, which
    nest and never cross: a ``while`` is charged less its body."""
    out, stack = [], []
    for ev in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and ev[1] >= stack[-1][0][1] + stack[-1][0][2]:
            out.append(tuple(stack.pop()))
        if stack:
            stack[-1][1] -= ev[2]
        stack.append([ev, ev[2]])
    out.extend(tuple(x) for x in stack)
    return out


def main(path, iters=2):
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    with open(path, "rb") as fh:
        space.ParseFromString(fh.read())
    for plane in space.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        key = next(
            (i for i, m in plane.stat_metadata.items() if m.name == "tf_op"), None
        )
        scope_of = {
            mid: innermost(st.str_value)
            for mid, md in plane.event_metadata.items()
            for st in md.stats
            if st.metadata_id == key
        }
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            evs = [(e.metadata_id, e.offset_ps, e.duration_ps) for e in line.events]
            per = collections.Counter()
            names = collections.defaultdict(collections.Counter)
            for ev, own in self_times(evs):
                scope = scope_of.get(ev[0]) or "(no scope)"
                per[scope] += own
                names[scope][plane.event_metadata[ev[0]].name[:72]] += own
            total = sum(per.values())
            print(json.dumps({
                "plane": plane.name,
                "events": len(evs),
                "busy_ms": total / 1e9,
                "stretch_ms": (max(e[1] + e[2] for e in evs) - min(e[1] for e in evs)) / 1e9,
                "ms_per_iter": {s: v / 1e9 / iters for s, v in per.most_common()},
                "share": {s: v / total for s, v in per.most_common()},
            }, indent=1))
            for scope, ops in names.items():
                print(scope, [(n, round(v / 1e9 / iters, 1)) for n, v in ops.most_common(8)])


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 2)
