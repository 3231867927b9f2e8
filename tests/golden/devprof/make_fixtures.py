"""Regenerate the checked-in profiler capture fixtures (deterministic gzip).

Two synthetic LIGHTGBM_TPU_PROFILE capture dirs in the XLA profiler's
on-disk layout (``<dir>/plugins/profile/<session>/<host>.trace.json.gz``),
read by tests/test_obs.py's ``trace merge`` tests:

 * ``tpu_capture`` — one host lane with TraceAnnotation spans, one
   ``/device:TPU:0`` lane with nested op events, and H2D/D2H transfer
   events: what ``python -m lightgbm_tpu.obs.trace merge <dir>`` expands
   and gunzips.
 * ``rank_capture.rank0`` / ``rank_capture.rank1`` — a two-rank
   ``maybe_profile`` capture (the base dir does not exist, exactly as the
   rank suffix leaves things) proving find_trace_files folds ranks.

Run from the repo root::

    python tests/golden/devprof/make_fixtures.py
"""
import gzip
import io
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def _write_gz(path, doc):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    buf = io.BytesIO()
    # filename="" + mtime=0: byte-identical output on every regeneration
    with gzip.GzipFile(filename="", mode="wb", fileobj=buf, mtime=0) as gz:
        gz.write(json.dumps(doc, sort_keys=True).encode("utf-8"))
    with open(path, "wb") as fh:
        fh.write(buf.getvalue())
    print("wrote %s (%d bytes)" % (path, len(buf.getvalue())))


def tpu_capture():
    evs = [
        # process/thread metadata
        {"ph": "M", "name": "process_name", "pid": 1,
         "args": {"name": "/host:CPU"}},
        {"ph": "M", "name": "thread_name", "pid": 1, "tid": 10,
         "args": {"name": "python"}},
        {"ph": "M", "name": "process_name", "pid": 100,
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "name": "thread_name", "pid": 100, "tid": 1,
         "args": {"name": "XLA Ops"}},
        # host annotation spans (TraceAnnotation names, us clock)
        {"ph": "X", "name": "train.iteration", "pid": 1, "tid": 10,
         "ts": 500, "dur": 9000},
        {"ph": "X", "name": "tree growth", "pid": 1, "tid": 10,
         "ts": 800, "dur": 6500},
        {"ph": "X", "name": "prof.hist_build", "pid": 1, "tid": 10,
         "ts": 1000, "dur": 4000},
        {"ph": "X", "name": "prof.split_scan", "pid": 1, "tid": 10,
         "ts": 5200, "dur": 1800},
        # a long profiler-internal host frame: must NOT stretch the window
        {"ph": "X", "name": "$profiler.py:91 start_trace", "pid": 1,
         "tid": 10, "ts": 0, "dur": 500000},
        # device ops ("XLA Ops" lane); fusion.123 contains nested.child
        {"ph": "X", "name": "fusion.123", "pid": 100, "tid": 1,
         "ts": 1200, "dur": 2000,
         "args": {"flops": 4e9, "bytes accessed": 1e8}},
        {"ph": "X", "name": "nested.child", "pid": 100, "tid": 1,
         "ts": 1500, "dur": 500},
        {"ph": "X", "name": "scatter-add.7", "pid": 100, "tid": 1,
         "ts": 3400, "dur": 1200},
        {"ph": "X", "name": "cumsum.2", "pid": 100, "tid": 1,
         "ts": 5300, "dur": 900, "args": {"flops": 1e8}},
        # outside every annotation span -> unattributed, never dropped
        {"ph": "X", "name": "loop_unrolled.9", "pid": 100, "tid": 1,
         "ts": 9600, "dur": 700},
        # transfers (host side), byte counts in args
        {"ph": "X", "name": "TransferToDevice", "pid": 1, "tid": 11,
         "ts": 300, "dur": 150, "args": {"bytes": 1048576}},
        {"ph": "X", "name": "TransferFromDevice", "pid": 1, "tid": 11,
         "ts": 10350, "dur": 100, "args": {"bytes": 2048}},
    ]
    doc = {"traceEvents": evs, "displayTimeUnit": "ms"}
    _write_gz(os.path.join(
        HERE, "tpu_capture", "plugins", "profile", "2026_01_01_00_00_00",
        "fixture.trace.json.gz"), doc)


def rank_capture():
    for rank, (op_ts, nbytes) in enumerate(((1000, 4096), (1500, 8192))):
        evs = [
            {"ph": "M", "name": "process_name", "pid": 7,
             "args": {"name": "/device:TPU:%d" % rank}},
            {"ph": "X", "name": "prof.hist_build", "pid": 1, "tid": 2,
             "ts": op_ts - 100, "dur": 1200},
            {"ph": "X", "name": "fusion.%d" % rank, "pid": 7, "tid": 1,
             "ts": op_ts, "dur": 1000},
            {"ph": "X", "name": "TransferToDevice", "pid": 1, "tid": 3,
             "ts": op_ts - 50, "dur": 40, "args": {"bytes": nbytes}},
        ]
        _write_gz(os.path.join(
            HERE, "rank_capture.rank%d" % rank, "plugins", "profile",
            "2026_01_01_00_00_00", "rank%d.trace.json.gz" % rank),
            {"traceEvents": evs})


if __name__ == "__main__":
    tpu_capture()
    rank_capture()
