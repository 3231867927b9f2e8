"""True multi-process distributed loading over the jax.distributed runtime.

tests/test_dist_loading.py proves the mapper-exchange protocol with an
in-process simulation; this test launches REAL separate processes joined
through jax.distributed.initialize (the multi-host path's actual runtime)
and checks that load_two_round + jax_mapper_exchange leaves every rank with
byte-identical BinMappers over its own row shard — the property that makes
cross-rank histogram psums well-defined (reference analogue: the BinMapper
allgather of dataset_loader.cpp:877-944 over sockets/MPI).
"""
import hashlib
import json
import os
import socket
import subprocess
import sys
import textwrap

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKER = textwrap.dedent(
    """
    import os, sys, json, hashlib
    os.environ["JAX_PLATFORMS"] = "cpu"
    rank, world, port, data = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3], sys.argv[4]
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.distributed.initialize(coordinator_address="127.0.0.1:" + port,
                               num_processes=world, process_id=rank)
    sys.path.insert(0, "@REPO@")
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.dist_loader import jax_mapper_exchange, load_two_round
    cfg = Config.from_params({"max_bin": 31, "objective": "binary"})
    binned, rows = load_two_round(data, cfg, rank=rank, num_machines=world,
                                  mapper_exchange=jax_mapper_exchange,
                                  chunk_rows=400)
    blob = json.dumps([m.to_dict() for m in binned.mappers], sort_keys=True)
    print("RESULT " + json.dumps({
        "rank": rank,
        "num_data": int(binned.num_data),
        "digest": hashlib.sha256(blob.encode()).hexdigest(),
        "rows_mod_ok": bool(((rows % world) == rank).all()),
    }), flush=True)
    """
).replace("@REPO@", REPO)


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _launch_world(worker, data, tmp_path, attempt):
    """One coordinated 2-process run; returns results or None on a
    coordinator bind failure (the _free_port close-then-rebind race)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # no virtual devices: one real proc per rank
    port = _free_port()
    results = []
    procs = []
    # stderr to files, not pipes: a worker spewing warnings must not stall
    # on a full pipe while the test waits on its sibling
    errs = [
        open(tmp_path / ("err_a%d_r%d.log" % (attempt, r)), "w+")
        for r in range(2)
    ]
    try:
        procs = [
            subprocess.Popen(
                [sys.executable, str(worker), str(r), "2", str(port), str(data)],
                env=env, stdout=subprocess.PIPE, stderr=errs[r], text=True,
            )
            for r in range(2)
        ]
        for r, p in enumerate(procs):
            out, _ = p.communicate(timeout=240)
            errs[r].seek(0)
            err_text = errs[r].read()
            if p.returncode != 0:
                low = err_text.lower()
                if "address already in use" in low or "failed to bind" in low:
                    return None  # port race: caller retries on a fresh port
                raise AssertionError(err_text[-2000:])
            line = next(l for l in out.splitlines() if l.startswith("RESULT "))
            results.append(json.loads(line[len("RESULT "):]))
        return results
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
        for fh in errs:
            fh.close()


TRAIN_WORKER = textwrap.dedent(
    """
    import os, sys, json, hashlib
    os.environ["JAX_PLATFORMS"] = "cpu"
    rank, world, port, data = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3], sys.argv[4]
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.distributed.initialize(coordinator_address="127.0.0.1:" + port,
                               num_processes=world, process_id=rank)
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    sys.path.insert(0, "@REPO@")
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.dataset import construct_dataset
    from lightgbm_tpu.ops.grow import grow_tree
    from lightgbm_tpu.ops.split import SplitParams
    from lightgbm_tpu.parallel.data_parallel import grow_tree_data_parallel

    raw = np.load(data)
    X, y = raw["X"], raw["y"]
    cfg = Config.from_params({"max_bin": 63, "objective": "binary"})
    ds = construct_dataset(X, cfg, label=y.astype(np.float32))
    F, N = ds.bins.shape
    grad = (0.5 - y).astype(np.float32)
    hess = np.full(N, 0.25, np.float32)
    ones = np.ones(N, np.float32)
    sp = SplitParams(0.0, 0.0, 0.0, 5, 1e-3, 0.0)
    meta_np = ds.feature_meta_arrays()
    kw = dict(num_leaves=15, max_depth=-1, num_bins=ds.max_num_bin, params=sp)

    # ---- global 2-process mesh; every rank contributes its row shard ----
    mesh = Mesh(np.array(jax.devices()), ("data",))
    assert len(jax.devices()) == world and len(jax.local_devices()) == 1
    row_s = NamedSharding(mesh, P("data"))
    col_s = NamedSharding(mesh, P(None, "data"))
    rep_s = NamedSharding(mesh, P())
    shard = slice(rank * N // world, (rank + 1) * N // world)
    bins_g = jax.make_array_from_process_local_data(col_s, np.asarray(ds.bins)[:, shard])
    def row(a):
        return jax.make_array_from_process_local_data(row_s, a[shard])
    def rep(a):
        return jax.make_array_from_process_local_data(rep_s, np.asarray(a))
    meta_g = {k: rep(v) for k, v in meta_np.items()}
    tree, leaf_id = grow_tree_data_parallel(
        mesh, bins_g, row(grad), row(hess), row(ones),
        rep(np.ones(F, bool)), meta_g, **kw,
    )
    def model_arrays(t):
        # the tree itself: its work counters (the last field) say how it
        # was grown, and a shard of the rows is not grown like all of them
        return [np.asarray(x) for x in jax.device_get(t)[:-1]]

    tree_np = model_arrays(tree)
    blob = json.dumps([t.tolist() for t in tree_np], sort_keys=True)
    lid_local = np.asarray(
        [s.data for s in leaf_id.addressable_shards][0]
    )

    # ---- voting-parallel across the same two-process mesh --------------
    # top_k >= F elects every feature; the elected-slice psum then equals
    # the full data-parallel combine, so structure must match serial
    # exactly (values to ULP: shard-local subtraction chains re-order f32)
    from lightgbm_tpu.parallel.voting_parallel import grow_tree_voting_parallel
    tree_vp, _ = grow_tree_voting_parallel(
        mesh, bins_g, row(grad), row(hess), row(ones),
        rep(np.ones(F, bool)), meta_g, top_k=F, **kw,
    )
    vp_np = model_arrays(tree_vp)

    # ---- single-process serial oracle on this rank's own device --------
    meta_l = {k: jnp.asarray(v) for k, v in meta_np.items()}
    tree_s, leaf_s = grow_tree(
        jnp.asarray(ds.bins), jnp.asarray(grad), jnp.asarray(hess),
        jnp.asarray(ones), jnp.ones((F,), bool), meta_l, **kw,
    )
    s_np = model_arrays(tree_s)
    blob_s = json.dumps([t.tolist() for t in s_np], sort_keys=True)
    lid_match = bool(
        (np.asarray(leaf_s)[shard] == lid_local).all()
    )
    # voting vs serial: structure exact, float fields to tolerance
    fields = tree_s._fields
    vp_struct_ok = True
    vp_close_ok = True
    for name, sv, vv in zip(fields, s_np, vp_np):
        if sv.dtype.kind in "iub":
            vp_struct_ok &= bool(np.array_equal(sv, vv))
        else:
            vp_close_ok &= bool(
                np.allclose(sv, vv, rtol=2e-4, atol=1e-5)
            )
    print("RESULT " + json.dumps({
        "rank": rank,
        "digest_dp": hashlib.sha256(blob.encode()).hexdigest(),
        "digest_serial": hashlib.sha256(blob_s.encode()).hexdigest(),
        "num_leaves": int(tree_np[0]),
        "leaf_id_match": lid_match,
        "vp_struct_ok": vp_struct_ok,
        "vp_close_ok": vp_close_ok,
    }), flush=True)
    """
).replace("@REPO@", REPO)


def _launch_world_retrying(worker_src, data, tmp_path, base_attempt, name):
    """Write the worker script and run _launch_world with the port-bind
    retry policy shared by every multi-process test here."""
    worker = tmp_path / name
    worker.write_text(worker_src)
    for attempt in range(2):
        results = _launch_world(worker, data, tmp_path, base_attempt + attempt)
        if results is not None:
            return results
    raise AssertionError("coordinator port bind failed twice")


def test_two_process_mapper_exchange(tmp_path):
    rng = np.random.RandomState(0)
    X = rng.randn(2000, 5)
    y = (X[:, 0] > 0).astype(int)
    data = tmp_path / "mp.train"
    with open(data, "w") as fh:
        for i in range(len(y)):
            fh.write("%d\t%s\n" % (y[i], "\t".join("%.5f" % v for v in X[i])))
    results = _launch_world_retrying(WORKER, data, tmp_path, 0, "worker.py")

    assert results[0]["digest"] == results[1]["digest"], (
        "ranks disagree on BinMappers after the allgather"
    )
    assert all(r["rows_mod_ok"] for r in results)
    assert sum(r["num_data"] for r in results) == 2000


LOAD_TRAIN_WORKER = textwrap.dedent(
    """
    import os, sys, json, hashlib
    os.environ["JAX_PLATFORMS"] = "cpu"
    rank, world, port, data = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3], sys.argv[4]
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.distributed.initialize(coordinator_address="127.0.0.1:" + port,
                               num_processes=world, process_id=rank)
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    sys.path.insert(0, "@REPO@")
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.dist_loader import jax_mapper_exchange, load_two_round
    from lightgbm_tpu.ops.split import SplitParams
    from lightgbm_tpu.parallel.data_parallel import grow_tree_data_parallel

    # the documented multi-host recipe (examples/parallel_learning/README.md):
    # rank-sharded two-round loading, then data-parallel training over the
    # global mesh — composed end-to-end across real processes
    cfg = Config.from_params({"max_bin": 31, "objective": "binary"})
    binned, _rows = load_two_round(data, cfg, rank=rank, num_machines=world,
                                   mapper_exchange=jax_mapper_exchange,
                                   chunk_rows=300)
    F, n_local = binned.bins.shape
    y = np.asarray(binned.metadata.label, np.float32)
    grad = (0.5 - y).astype(np.float32)
    hess = np.full(n_local, 0.25, np.float32)
    ones = np.ones(n_local, np.float32)

    mesh = Mesh(np.array(jax.devices()), ("data",))
    col_s = NamedSharding(mesh, P(None, "data"))
    row_s = NamedSharding(mesh, P("data"))
    rep_s = NamedSharding(mesh, P())
    bins_g = jax.make_array_from_process_local_data(col_s, np.asarray(binned.bins))
    def row(a):
        return jax.make_array_from_process_local_data(row_s, a)
    def rep(a):
        return jax.make_array_from_process_local_data(rep_s, np.asarray(a))
    meta_g = {k: rep(v) for k, v in binned.feature_meta_arrays().items()}
    sp = SplitParams(0.0, 0.0, 0.0, 5, 1e-3, 0.0)
    tree, leaf_id = grow_tree_data_parallel(
        mesh, bins_g, row(grad), row(hess), row(ones), rep(np.ones(F, bool)),
        meta_g, num_leaves=15, max_depth=-1, num_bins=binned.max_num_bin,
        params=sp,
    )
    tree_np = [np.asarray(x) for x in jax.device_get(tree)]
    blob = json.dumps([t.tolist() for t in tree_np], sort_keys=True)
    # the grown tree must reduce the local training loss (recipe sanity)
    lid_local = np.asarray([s.data for s in leaf_id.addressable_shards][0])
    leaf_value = tree_np[9]  # TreeArrays.leaf_value position
    pred = leaf_value[lid_local]
    before = float(np.mean(np.log1p(np.exp(-(2 * y - 1) * 0.0))))
    after = float(np.mean(np.log1p(np.exp(-(2 * y - 1) * pred * 4.0))))
    print("RESULT " + json.dumps({
        "rank": rank,
        "digest": hashlib.sha256(blob.encode()).hexdigest(),
        "num_leaves": int(tree_np[0]),
        "n_local": int(n_local),
        "loss_improves": bool(after < before),
    }), flush=True)
    """
).replace("@REPO@", REPO)


def test_two_process_load_then_train(tmp_path):
    """The documented multi-host recipe end-to-end: load_two_round rank
    sharding + mapper exchange, then data-parallel growth over the same
    two-process mesh — the composition of the two flows proven separately
    above (reference analogue: dataset_loader.cpp:762 rank loading feeding
    data_parallel_tree_learner.cpp training)."""
    rng = np.random.RandomState(5)
    X = rng.randn(1600, 4)
    # two-feature signal: a single-feature label yields pure children after
    # the root split and growth legitimately stops at 2 leaves
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(int)
    data = tmp_path / "lt.train"
    with open(data, "w") as fh:
        for i in range(len(y)):
            fh.write("%d\t%s\n" % (y[i], "\t".join("%.5f" % v for v in X[i])))
    results = _launch_world_retrying(
        LOAD_TRAIN_WORKER, data, tmp_path, 20, "lt_worker.py"
    )
    r0, r1 = sorted(results, key=lambda r: r["rank"])
    assert r0["digest"] == r1["digest"], "ranks grew different trees"
    assert r0["num_leaves"] > 2
    assert r0["n_local"] + r1["n_local"] == 1600
    assert r0["loss_improves"] and r1["loss_improves"]


OBS_WORKER = textwrap.dedent(
    """
    import os, sys, json
    os.environ["JAX_PLATFORMS"] = "cpu"
    rank, world, port, data = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3], sys.argv[4]
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.distributed.initialize(coordinator_address="127.0.0.1:" + port,
                               num_processes=world, process_id=rank)
    sys.path.insert(0, "@REPO@")
    from lightgbm_tpu.obs import dist, registry, trace

    # the rank-suffix fix: an env-derived trace path must never collide
    os.environ[trace.ENV_TRACE] = data + ".trace"
    tr = trace.start()
    trace_ok = tr.path.endswith(".trace.rank%d" % rank)
    with trace.span("obs.worker", cat="test"):
        pass
    trace.stop()

    # distinguishable per-rank instruments, then the pod-wide merge: the
    # host-side allgather where the backend implements multi-process
    # computations, else the documented FILE-BASED fallback (obs/dist.py)
    # — both paths end in one registry whose counters are the rank sums
    registry.REGISTRY.counter("mp_obs_total").inc(10 * (rank + 1))
    registry.REGISTRY.counter("mp_obs_total").inc(1, kind="labeled")
    registry.REGISTRY.gauge("mp_obs_rank").set(float(rank))
    mine = dist.write_snapshot(data + ".snap")
    try:
        snaps = dist.gather_snapshots()
        mode = "allgather"
    except Exception:
        # e.g. "Multiprocess computations aren't implemented on the CPU
        # backend" (container jaxlib): poll for the sibling's snapshot
        import time
        other = data + ".snap.rank%d.json" % (1 - rank)
        snaps = []
        for _ in range(600):
            try:
                snaps = dist.merge_snapshot_files([mine, other])
            except Exception:
                snaps = []
            if len(snaps) == 2:
                break
            time.sleep(0.1)
        mode = "files"
    merged = dist.merge_snapshots(snaps)
    expo = merged.prometheus_text()
    print("RESULT " + json.dumps({
        "rank": rank,
        "mode": mode,
        "gathered": len(snaps),
        "processes": sorted(s.get("process") for s in snaps),
        "merged_total": merged.counter("mp_obs_total").value(),
        "merged_labeled": merged.counter("mp_obs_total").value(kind="labeled"),
        "provenance_ok": ('process="0"' in expo and 'process="1"' in expo),
        "trace_rank_suffix_ok": trace_ok,
    }), flush=True)
    """
).replace("@REPO@", REPO)


def test_two_process_registry_gather_merge(tmp_path):
    """obs/dist.py pod-wide aggregation over a REAL two-process
    jax.distributed world: both ranks merge their registry snapshots —
    via the host-side allgather where the backend supports multi-process
    computations, else via the documented file-based fallback — and the
    merged counters equal the per-process sums (30 = 10+20, labeled
    2 = 1+1), gauges keep per-process provenance labels, and the
    env-derived trace path picks up the .rank<N> suffix so the two ranks
    never clobber one file (the reference analogue: the per-rank timing
    logs the Network layer's ranks kept separately)."""
    results = _launch_world_retrying(
        OBS_WORKER, tmp_path / "obs", tmp_path, 30, "obs_worker.py"
    )
    for r in results:
        assert r["gathered"] == 2
        assert r["processes"] == [0, 1]
        assert r["merged_total"] == 30, "merged != sum of per-process counters"
        assert r["merged_labeled"] == 2
        assert r["provenance_ok"], "gauges lost process provenance labels"
        assert r["trace_rank_suffix_ok"], "trace path missed .rank<N> suffix"
    # both rank trace files exist side by side and merge into one timeline
    t0 = str(tmp_path / "obs") + ".trace.rank0"
    t1 = str(tmp_path / "obs") + ".trace.rank1"
    assert os.path.exists(t0) and os.path.exists(t1)
    sys.path.insert(0, REPO)
    from lightgbm_tpu.obs import trace as trace_mod

    merged = tmp_path / "obs_merged.json"
    stats = trace_mod.merge_traces(str(merged), [t0, t1])
    assert stats["files"] == 2 and stats["pids"] >= 2


def test_two_process_data_parallel_training(tmp_path):
    """grow_tree_data_parallel across TWO real jax.distributed processes
    forming one global mesh: the tree must be identical on both ranks AND
    identical to single-process serial growth — the in-anger multi-host
    proof of the DP collective path (the analogue of training over
    data_parallel_tree_learner.cpp:149-257 + linkers_socket.cpp:165-211;
    here the cross-process psum rides jax.distributed's CPU collectives)."""
    rng = np.random.RandomState(3)
    X = rng.randn(2000, 5)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float32)
    data = tmp_path / "mp_train.npz"
    np.savez(data, X=X, y=y)
    results = _launch_world_retrying(
        TRAIN_WORKER, data, tmp_path, 10, "train_worker.py"
    )

    r0, r1 = sorted(results, key=lambda r: r["rank"])
    assert r0["digest_dp"] == r1["digest_dp"], "ranks grew different trees"
    assert r0["digest_dp"] == r0["digest_serial"], (
        "distributed tree differs from single-process serial"
    )
    assert r0["num_leaves"] > 2
    assert r0["leaf_id_match"] and r1["leaf_id_match"]
    # voting-parallel over the same two-process mesh (top_k = F): identical
    # structure to serial, float fields to ULP tolerance
    assert r0["vp_struct_ok"] and r1["vp_struct_ok"], (
        "multi-process voting tree structure differs from serial"
    )
    assert r0["vp_close_ok"] and r1["vp_close_ok"]


CKPT_COORD_WORKER = textwrap.dedent(
    """
    import os, sys, json, hashlib
    os.environ["JAX_PLATFORMS"] = "cpu"
    # pin the rank-file transport: this container's jaxlib cannot run
    # multi-process CPU collectives (the three device-collective tests in
    # this module skip for the same reason), and its FAILED collective
    # attempts are unstable on repetition — the production path for that
    # situation is exactly this documented fallback
    os.environ["LIGHTGBM_TPU_CKPT_COORD"] = "files"
    rank, world, port, workdir = (int(sys.argv[1]), int(sys.argv[2]),
                                  sys.argv[3], sys.argv[4])
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.distributed.initialize(coordinator_address="127.0.0.1:" + port,
                               num_processes=world, process_id=rank)
    sys.path.insert(0, "@REPO@")
    import lightgbm_tpu as lgb
    from lightgbm_tpu import engine
    from lightgbm_tpu.obs.registry import REGISTRY
    from lightgbm_tpu.resil import coord

    # identical data on every rank: the serial learner trains the SAME
    # model per rank, so the digest barrier must reach consensus and rank 0
    # alone publishes the archive (resil/coord.py). On jaxlibs without
    # multi-process CPU collectives the device allgather raises and the
    # exchange takes the documented rank-file fallback.
    rng = np.random.RandomState(13)
    X = rng.randn(200, 4); y = (X[:, 0] > 0).astype(float)
    params = {"objective": "binary", "num_leaves": 7, "verbosity": -1}
    ck = os.path.join(workdir, "pod.ckpt")
    bst = engine.train(params, lgb.Dataset(X, label=y), 4,
                       checkpoint_path=ck, checkpoint_rounds=2,
                       verbose_eval=False)
    barriers = REGISTRY.counter("resil_ckpt_barriers").value()
    # resume: all ranks verify they loaded the same archive before grafting
    resumed = engine.train(params, lgb.Dataset(X, label=y), 4,
                           resume_from=ck, verbose_eval=False)
    print("RESULT " + json.dumps({
        "rank": rank,
        "barriers": barriers,
        "archive_exists": os.path.exists(ck),
        "hb_self": os.path.exists(coord.heartbeat_path(ck, rank)),
        "stale": coord.stale_ranks(ck, world, max_age_s=300.0),
        "digest": hashlib.sha256(
            resumed.model_to_string().encode()).hexdigest(),
    }), flush=True)
    """
).replace("@REPO@", REPO)


def test_two_process_checkpoint_coordination(tmp_path):
    """Coordinated multi-process checkpointing over a REAL two-process
    jax.distributed world (resil/coord.py): the per-boundary digest
    barrier reaches consensus (via the host allgather where the backend
    supports multi-process computations, else the documented rank-file
    fallback), rank 0 alone publishes the archive, both ranks heartbeat,
    and the resume barrier lets both ranks graft the same bytes."""
    workdir = tmp_path / "ckpt_world"
    workdir.mkdir()
    results = _launch_world_retrying(
        CKPT_COORD_WORKER, workdir, tmp_path, 40, "ckpt_worker.py"
    )
    r0, r1 = sorted(results, key=lambda r: r["rank"])
    assert r0["archive_exists"] and r1["archive_exists"]
    assert r0["digest"] == r1["digest"], "ranks resumed different models"
    for r in (r0, r1):
        assert r["barriers"] >= 1, "digest barrier never ran"
        assert r["hb_self"], "rank %d wrote no heartbeat" % r["rank"]
        assert r["stale"] == [], "fresh heartbeats reported stale: %r" % (
            r["stale"],)
