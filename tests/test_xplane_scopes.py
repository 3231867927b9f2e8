"""The by-hand reader of a profile by phase (helpers/xplane_scopes.py) on a
profile made here: an op's phase is the innermost of ``obs/trace.SCOPES`` on
its ``tf_op``, else what the profile's own HLO module says of it (its
``op_name``, the op XLA expanded it from, its consumer); a ``while`` is
charged less its body; each outer phase totals its sub-phases; each program
gets its own table; an idle gap of the device is named by the innermost
program span open on the host at its middle. Nothing here is a device
number."""
import importlib.util
import os

import pytest

xplane_pb2 = pytest.importorskip("tensorflow.tsl.profiler.protobuf.xplane_pb2")
hlo_pb2 = pytest.importorskip("tensorflow.compiler.xla.service.hlo_pb2")

HERE = os.path.dirname(os.path.abspath(__file__))
SPEC = importlib.util.spec_from_file_location(
    "xplane_scopes", os.path.join(HERE, "..", "helpers", "xplane_scopes.py"))
xplane_scopes = importlib.util.module_from_spec(SPEC)
SPEC.loader.exec_module(xplane_scopes)

MS = 10 ** 9  # picoseconds
BODY = "jit(grow_tree)/while/body/apply_split/"
GROWER = "jit_grow_tree(7)"


def _hlo():
    """The grower's module as the profile holds it: the loop's body runs a
    take (named), a lane pass (named), a copy XLA made for the lane pass (no
    name), the conditional of the lane pass (named; the profiler leaves its
    ``tf_op`` off) and the loop XLA expanded a carry write into (named),
    whose body's fusion has no name."""
    proto = hlo_pb2.HloProto()
    module = proto.hlo_module
    body, wide, main = (module.computations.add(id=k, name=n) for k, n in
                        ((2, "body"), (3, "wide.while_body"), (1, "main")))
    module.entry_computation_id = 1

    def add(comp, iid, name, opcode, op_name="", operands=(), called=()):
        i = comp.instructions.add(id=iid, name=name, opcode=opcode,
                                  operand_ids=list(operands),
                                  called_computation_ids=list(called))
        i.metadata.op_name = op_name

    add(body, 21, "fusion.2", "fusion", BODY + "partition/partition/split_columns/gather")
    add(body, 24, "copy.4", "copy", operands=[21])
    add(body, 22, "fusion.3", "fusion", BODY + "partition/cond/branch_1_fun/partition/lanes/gather",
        operands=[24])
    add(body, 29, "cond.9", "conditional", BODY + "partition/partition/lanes/cond", operands=[22])
    add(body, 28, "while.8", "while", BODY + "carry_write/scatter", called=[3])
    add(body, 25, "hist_pallas_fb.5", "custom-call", BODY + "hist_build/kernel/pallas_call")
    add(wide, 37, "fusion.7", "fusion")
    add(main, 10, "while.1", "while", "jit(grow_tree)/while", called=[2])
    return proto.SerializeToString()


def _profile(path):
    """The grower from 0 to 11 ms (its loop of 10 ms holding a take of 3, a
    lane pass of 2, the copy for it of 1, the expanded scatter's loop of 2
    holding its fusion of 1 and the conditional's 0.5; a kernel call of 1
    after it), a gap of 1 ms while the host waits for the tree, the score
    update from 12 to 13 ms."""
    space = xplane_pb2.XSpace()
    meta = space.planes.add(name="/host:metadata")
    meta.stat_metadata[1].name = "Hlo Proto"
    md = meta.event_metadata[1]
    md.id, md.name = 1, GROWER
    md.stats.add(metadata_id=1, bytes_value=_hlo())
    dev = space.planes.add(name="/device:TPU:0")
    dev.stat_metadata[1].name = "tf_op"
    ops = {
        10: (GROWER, None), 11: ("jit_update(9)", None),
        20: ("%while.1 = (s32[]) while(...)", None),
        21: ("%fusion.2 = u8[8,200] fusion(...)", BODY + "partition/partition/split_columns/gather"),
        22: ("%fusion.3 = s32[200] fusion(...)", BODY + "partition/cond/branch_1_fun/partition/lanes/gather"),
        23: ("%copy.4 = s32[200] copy(...)", None),
        24: ("%hist_pallas_fb.5 = f32[8] custom-call(...)", BODY + "hist_build/kernel/pallas_call"),
        26: ("%while.8 = (s32[]) while(...)", None),
        27: ("%fusion.7 = f32[255] fusion(...)", None),
        28: ("%cond.9 = (s32[200]) conditional(...)", None),
        25: ("%fusion.6 = f32[200] fusion(...)", "jit(update)/score_update/add"),
    }
    for mid, (name, tf_op) in ops.items():
        md = dev.event_metadata[mid]
        md.id, md.name = mid, name
        if tf_op:
            md.stats.add(metadata_id=1, str_value=tf_op)
    modules = dev.lines.add(name="XLA Modules", timestamp_ns=1000)
    for mid, start, dur in ((10, 0, 11), (11, 12, 1)):
        modules.events.add(metadata_id=mid, offset_ps=start * MS, duration_ps=dur * MS)
    line = dev.lines.add(name="XLA Ops", timestamp_ns=1000)
    for mid, start, dur in ((20, 0, 10), (21, 1, 3), (22, 4, 2), (23, 6, 1), (26, 7, 2),
                            (27, 7.5, 1), (28, 9, 0.5), (24, 10, 1), (25, 12, 1)):
        line.events.add(metadata_id=mid, offset_ps=int(start * MS), duration_ps=int(dur * MS))
    host = space.planes.add(name="/host:CPU")
    for mid, name in ((1, "train.iteration"), (2, "train.wait_prev_tree"),
                      (3, "train.boundary"), (4, "PjitFunction(update)")):
        host.event_metadata[mid].id, host.event_metadata[mid].name = mid, name
    py = host.lines.add(name="python", timestamp_ns=1000)
    for mid, start, dur in ((1, 0, 13), (2, 10, 2), (3, 13, 2), (4, 11, 1)):
        py.events.add(metadata_id=mid, offset_ps=start * MS, duration_ps=dur * MS)
    with open(path, "wb") as fh:
        fh.write(space.SerializeToString())


def test_the_phases_the_programs_and_the_gaps(tmp_path):
    path = str(tmp_path / "t.xplane.pb")
    _profile(path)
    grow, update, idle = xplane_scopes.read(path, iters=1)
    assert grow["module"] == "jit_grow_tree" and update["module"] == "jit_update"
    assert grow["ms_per_iter"] == pytest.approx({
        "partition/split_columns": 3.0,
        # the lane pass, the copy made for it, its conditional's own time
        "partition/lanes": 2.0 + 1.0 + 0.5,
        # the expanded scatter's loop and the fusion in its body
        "carry_write": 1.0 + 1.0,
        "hist_build/kernel": 1.0,
        # the grower's loop keeps what its body leaves: 10 - 8.5
        "(no phase)": 1.5})
    assert grow["outer_ms_per_iter"] == pytest.approx({
        "partition": 6.5, "carry_write": 2.0, "hist_build": 1.0, "(no phase)": 1.5})
    assert grow["self_ms_per_iter"] == pytest.approx(11.0)
    assert grow["unscoped_share"] == pytest.approx(1.5 / 11)
    assert grow["unnamed_share"] == pytest.approx(5.0 / 11)
    assert grow["named_by_ms_per_iter"] == pytest.approx({
        "tf_op": 6.0, "own op_name": 1.5, "consumer": 1.0, "caller": 1.0, "none": 1.5})
    assert update["ms_per_iter"] == pytest.approx({"score_update": 1.0})
    # one gap, 11-12 ms: its middle lies in the wait, itself inside the
    # iteration; the runtime's own event there is no program span
    assert idle["stretch_ms"] == pytest.approx(13.0)
    assert idle["idle_by_span_ms"] == pytest.approx({"train.wait_prev_tree": 1.0})
    assert idle["gaps_by_span"] == {"train.wait_prev_tree": 1}


def test_a_gap_outside_every_span_is_elsewhere():
    spans = [(0, 10, "train.iteration"), (2, 4, "train.wait_prev_tree")]
    assert xplane_scopes.innermost_span(spans, 3) == "train.wait_prev_tree"
    assert xplane_scopes.innermost_span(spans, 5) == "train.iteration"
    assert xplane_scopes.innermost_span(spans, 11) == "elsewhere"
