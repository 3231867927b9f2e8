"""Device self time by phase from a raw ``.xplane.pb``, by hand.

PERF.md section 5's per-phase tables are this script's output on the profile
of a traced benchmark run (brought back before the harness removes it). The
phase of an op is the innermost of ``lightgbm_tpu.obs.trace.SCOPES`` on the
``tf_op`` stat of its *metadata* (its ``jax.named_scope`` path), which
``jax.profiler.ProfileData`` does not expose, so the file is read with the
xplane protobuf that ships beside jax. Each program (``XLA Modules``) gets its
own table: every phase, each outer phase's total (its sub-phases and what
lies under it alone), what lies under none and its dearest ops. Each idle gap
of the device is named by the innermost program span open on the host at its
middle: the program's spans enter ``jax.profiler.TraceAnnotation``
(obs/trace.py), so they lie in the host plane on the profile's clock. Not part
of the benchmark: PERF.md 7.4 asks a ``benchmark`` PR to fold this into
``benchmarks/trace_reduce.py``.

XLA makes instructions of its own that carry no name (the loop it expands a
scatter into, the copies and pads of a layout change), and the profiler
leaves ``tf_op`` off some that have one (a conditional). The profile holds
each program's optimised HLO (plane ``/host:metadata``), so such an op is
named from it, by the first rule that gives a phase (``HloPhases``): its own
``op_name``; a fusion, by the named instructions it holds; an instruction of
a computation XLA made for a named op (the body of an expanded scatter), by
that op; else, but for a loop's or a branch's own time, by its nearest
consumer, then its nearest producer, in its computation. Each table says how
much each rule named; ``unnamed_share`` is what the profile's ``tf_op`` alone
leaves under no phase.

    python helpers/xplane_scopes.py <file.xplane.pb> [traced_iterations=2]
"""
import collections
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lightgbm_tpu.obs.trace import scope_of  # noqa: E402

#: host events that are the program's spans (obs/trace.py's sites, the
#: PhaseTimers phases of utils/timer.py) or the benchmark's (``bench.*``)
PROGRAM_SPAN = re.compile(r"^(?:train|dataset|jit|bench)\.")
PHASES = {"bagging", "boosting(grad)", "chunked boosting", "renew+score update",
          "tree growth", "valid scores"}
UNSCOPED = "(no phase)"
#: what a loop or a branch costs itself is its own op's, no phase's: these
#: take no name from their neighbours
CONTROL = {"while", "conditional", "call"}


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


def outer(phase):
    return phase.split("/")[0]


def host_spans(space):
    """[(start_ps, end_ps, name)] of the program's and the benchmark's spans
    on the host planes, on the profile's clock."""
    spans = []
    for plane in space.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            t0 = line.timestamp_ns * 1000
            for ev in line.events:
                name = plane.event_metadata[ev.metadata_id].name
                if PROGRAM_SPAN.match(name) or name in PHASES:
                    start = t0 + ev.offset_ps
                    spans.append((start, start + ev.duration_ps, name))
    return spans


def innermost_span(spans, t):
    """The name of the shortest span open at ``t``, or ``elsewhere``."""
    open_ = [s for s in spans if s[0] <= t < s[1]]
    return min(open_, key=lambda s: s[1] - s[0])[2] if open_ else "elsewhere"


class HloPhases:
    """The phase of each instruction of one program, from its ``HloProto``
    (module docstring's rules); ``phase(name)`` -> (phase or None, rule)."""

    def __init__(self, proto_bytes):
        from tensorflow.compiler.xla.service import hlo_pb2

        module = hlo_pb2.HloProto.FromString(proto_bytes).hlo_module
        self.comps = {c.id: c for c in module.computations}
        self.ins, self.by_name = {}, {}
        self.users = collections.defaultdict(list)
        self.comp_of, self.callers = {}, collections.defaultdict(list)
        for c in module.computations:
            for i in c.instructions:
                self.ins[i.id], self.by_name[i.name] = i, i
                self.comp_of[i.id] = c.id
                for o in i.operand_ids:
                    self.users[o].append(i.id)
        for i in self.ins.values():
            if i.opcode != "fusion":
                for cid in i.called_computation_ids:
                    self.callers[cid].append(i.id)
        self.memo = {}

    def own(self, i):
        return scope_of(i.metadata.op_name) if i.metadata.op_name else None

    def fused(self, i):
        """The phase of the named instructions a fusion holds, its root's
        first; None for any other instruction."""
        if i.opcode != "fusion":
            return None
        found = collections.Counter()
        for cid in i.called_computation_ids:
            comp = self.comps[cid]
            root = self.ins.get(comp.root_id)
            if root is not None and (self.own(root) or self.fused(root)):
                return self.own(root) or self.fused(root)
            for j in comp.instructions:
                p = self.own(j) or self.fused(j)
                if p:
                    found[p] += 1
        return found.most_common(1)[0][0] if found else None

    def caller(self, i):
        """The phase of the nearest op that runs the computation ``i`` lies
        in (a while, a conditional, a call), climbing while none has one."""
        cid, seen = self.comp_of[i.id], set()
        while cid not in seen and self.callers.get(cid):
            seen.add(cid)
            up = self.ins[self.callers[cid][0]]
            p = self.own(up)
            if p:
                return p
            cid = self.comp_of[up.id]
        return None

    def nearest(self, i, links):
        """The own or fused phase of the nearest instruction along
        ``links`` (consumers or producers) in ``i``'s computation."""
        frontier, seen = [i.id], {i.id}
        for _ in range(16):
            nxt = []
            for x in frontier:
                for y in links(x):
                    if y in seen or self.comp_of.get(y) != self.comp_of[i.id]:
                        continue
                    seen.add(y)
                    j = self.ins[y]
                    p = self.own(j) or self.fused(j)
                    if p:
                        return p
                    nxt.append(y)
            frontier = nxt
        return None

    def phase(self, name):
        if name not in self.memo:
            i = self.by_name.get(name)
            got = (None, "not in the module")
            if i is not None:
                rules = [("own op_name", self.own), ("fused", self.fused),
                         ("caller", self.caller)]
                if i.opcode not in CONTROL:
                    rules += [
                        ("consumer", lambda i: self.nearest(i, self.users.__getitem__)),
                        ("producer", lambda i: self.nearest(
                            i, lambda x: self.ins[x].operand_ids))]
                got = next(((p, rule) for rule, p in
                            ((rule, find(i)) for rule, find in rules) if p),
                           (None, "none"))
            self.memo[name] = got
        return self.memo[name]


def hlo_modules(space):
    """{program event name: its ``HloProto`` bytes} of the profile."""
    out = {}
    for plane in space.planes:
        if plane.name == "/host:metadata":
            for md in plane.event_metadata.values():
                for st in md.stats:
                    if st.bytes_value:
                        out[md.name] = st.bytes_value
    return out


def instruction_name(md):
    """An op event's HLO instruction name: ``%fusion.12 = ...`` -> fusion.12."""
    return md.display_name or re.match(r"%?([^\s=]+)", md.name).group(1)


def by_module(plane, line_name, module_of):
    """{module: [(metadata_id, offset_ps, duration_ps)]} of one line."""
    out = collections.defaultdict(list)
    for line in plane.lines:
        if line.name == line_name:
            for e in line.events:
                out[module_of(line.timestamp_ns * 1000 + e.offset_ps)].append(
                    (e.metadata_id, e.offset_ps + line.timestamp_ns * 1000,
                     e.duration_ps))
    return out


def read(path, iters=2):
    """Each device plane's tables (one a program, then its idle gaps) as
    dicts; ``main`` prints them."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    with open(path, "rb") as fh:
        space.ParseFromString(fh.read())
    spans = host_spans(space)
    protos, hlo = hlo_modules(space), {}
    out = []
    for plane in space.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        key = next(
            (i for i, m in plane.stat_metadata.items() if m.name == "tf_op"), None
        )
        phase_of = {
            mid: scope_of(st.str_value)
            for mid, md in plane.event_metadata.items()
            for st in md.stats
            if st.metadata_id == key
        }
        modules = sorted(
            (line.timestamp_ns * 1000 + e.offset_ps,
             line.timestamp_ns * 1000 + e.offset_ps + e.duration_ps,
             plane.event_metadata[e.metadata_id].name)
            for line in plane.lines if line.name == "XLA Modules"
            for e in line.events)

        def module_of(t):
            return next((m for s, e, m in modules if s <= t < e), "(no module)")

        ops = by_module(plane, "XLA Ops", module_of)
        for module, evs in sorted(ops.items(), key=lambda kv: -sum(e[2] for e in kv[1])):
            if module in protos and module not in hlo:
                hlo[module] = HloPhases(protos[module])
            per = collections.Counter()
            names = collections.defaultdict(collections.Counter)
            rules = collections.Counter()
            unnamed = 0
            for ev, own in self_times(evs):
                phase, rule = phase_of.get(ev[0]), "tf_op"
                if phase is None:
                    unnamed += own
                    if module in hlo:
                        phase, rule = hlo[module].phase(
                            instruction_name(plane.event_metadata[ev[0]]))
                phase = phase or UNSCOPED
                rules[rule if phase != UNSCOPED else "none"] += own
                per[phase] += own
                names[phase][plane.event_metadata[ev[0]].name[:72]] += own
            total = sum(per.values())
            outer_total = collections.Counter()
            for phase, v in per.items():
                outer_total[outer(phase)] += v
            out.append({
                "plane": plane.name,
                "module": re.sub(r"\(\d+\)$", "", module),
                "events": len(evs),
                "self_ms_per_iter": total / 1e9 / iters,
                "ms_per_iter": {s: v / 1e9 / iters for s, v in sorted(per.items())},
                "outer_ms_per_iter": {s: v / 1e9 / iters
                                      for s, v in outer_total.most_common()},
                "unscoped_share": per[UNSCOPED] / total if total else 0.0,
                "unnamed_share": unnamed / total if total else 0.0,
                "named_by_ms_per_iter": {r: v / 1e9 / iters
                                         for r, v in rules.most_common()},
                "ops_ms_per_iter": {
                    phase: [(n, v / 1e9 / iters) for n, v in ops_.most_common(8)]
                    for phase, ops_ in sorted(names.items())},
            })
        # idle: the gaps between the union of every op, named by the host
        merged = []
        for a, b in sorted((s, s + d) for evs in ops.values() for _, s, d in evs):
            if merged and a <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], b)
            else:
                merged.append([a, b])
        gaps = [(merged[i][1], merged[i + 1][0]) for i in range(len(merged) - 1)]
        named = collections.Counter()
        count = collections.Counter()
        for a, b in gaps:
            what = innermost_span(spans, (a + b) // 2)
            named[what] += b - a
            count[what] += 1
        out.append({
            "plane": plane.name,
            "stretch_ms": (merged[-1][1] - merged[0][0]) / 1e9 if merged else 0.0,
            "idle_ms": sum(named.values()) / 1e9,
            "idle_by_span_ms": {s: v / 1e9 for s, v in named.most_common()},
            "gaps_by_span": dict(count.most_common()),
            "longest_gaps_ms": [
                [innermost_span(spans, (a + b) // 2), (b - a) / 1e9]
                for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:10]],
        })
    return out


def main(path, iters=2):
    for table in read(path, iters):
        ops = table.pop("ops_ms_per_iter", {})
        print(json.dumps(table, indent=1))
        for phase, top in ops.items():
            print(" ", phase, [(n, round(v, 2)) for n, v in top])


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 2)
