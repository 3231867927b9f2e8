"""Test configuration: run JAX on a virtual 8-device CPU platform.

Multi-chip TPU hardware is not available in CI; sharding/collective tests use
virtual CPU devices, per the project testing strategy (SURVEY.md §4: in-process
multi-worker simulation the reference lacks). The virtual-device switch lives
in lightgbm_tpu.utils.platform (shared with the CPU dry runs).
"""
import os
import resource

# XLA's recursive HLO passes can blow the default 8MB stack on large programs
# (observed as a flaky SIGSEGV inside backend_compile late in the suite, when
# hundreds of grow_tree variants have been compiled); raise the soft limit
# before the first compile.
_soft, _hard = resource.getrlimit(resource.RLIMIT_STACK)
if _hard == resource.RLIM_INFINITY or _hard >= 256 * 1024 * 1024:
    resource.setrlimit(
        resource.RLIMIT_STACK, (256 * 1024 * 1024, _hard)
    )

from lightgbm_tpu.utils.platform import force_cpu_devices  # noqa: E402

jax = force_cpu_devices(8)
assert len(jax.devices()) == 8, "expected 8 virtual CPU devices for the test mesh"

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# Every compiled XLA executable keeps its JIT code pages mapped; a full-suite
# run accumulates >60k memory maps and segfaults inside backend_compile when
# it crosses the kernel's vm.max_map_count (default 65530). Dropping the
# executable caches periodically bounds the map count at a modest recompile
# cost. (Diagnosed by watching /proc/<pid>/maps grow to ~61k right before a
# deterministic mid-suite SIGSEGV in jax's compiler.)
_TESTS_PER_CACHE_CLEAR = 40
_test_counter = {"n": 0}


@pytest.fixture(autouse=True)
def _bound_xla_map_count():
    yield
    _test_counter["n"] += 1
    if _test_counter["n"] % _TESTS_PER_CACHE_CLEAR == 0:
        jax.clear_caches()


@pytest.fixture
def rng():
    return np.random.RandomState(42)


# ---------------------------------------------------------------------------
# Quick tier: `pytest -m quick` runs a fast, high-signal subset (~3-5 min on
# the 1-core runner) for the edit-test loop; the full 400+ test suite needs
# >15 min there. Membership is by module so new tests
# in these files inherit the tier.
# ---------------------------------------------------------------------------
_QUICK_MODULES = {
    "test_api_surface", "test_binning",
    "test_binning_equiv", "test_chip_smoke", "test_device_chunk",
    "test_dist_obs", "test_elastic",
    "test_errors", "test_feature_importance", "test_flex",
    "test_graftlint",
    "test_hist_modes", "test_irscan", "test_loop", "test_metric_alias",
    "test_micro_exact", "test_model_io", "test_model_obs", "test_native",
    "test_obs",
    "test_ops", "test_parallel_chunk", "test_param_docs", "test_podwatch",
    "test_resil", "test_sanitize",
    "test_serve_drift", "test_serve_packed",
    "test_serve_resil", "test_serve_server", "test_snapshot_timers",
    "test_tune", "test_vfile", "test_warmstart",
}


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "quick: fast high-signal tier for the edit-test loop "
        "(full suite exceeds the 1-core box's patience)",
    )
    config.addinivalue_line(
        "markers",
        "slow: long-tail cases excluded from the tier-1 window "
        "(-m 'not slow'); run them with -m slow when touching their "
        "subsystem. Membership lives in tests/slow_tests.txt (applied at "
        "collection) = measured duration x redundancy, NOT importance: "
        "every listed case is over about 30 s or has a quicker sibling "
        "or a check.sh smoke covering the same seam.",
    )


# ---------------------------------------------------------------------------
# Multi-process CPU collective capability (tests/test_multiprocess_dist.py):
# the three device-collective tests run REAL 2-process jax.distributed worlds
# whose cross-process psum needs jaxlib's multi-process CPU computations —
# some container jaxlibs raise "Multiprocess computations aren't implemented
# on the CPU backend" (noted at the PR 9 seed). Probe once (two tiny
# subprocess ranks psumming over a 2-device global mesh) and skip-with-reason
# instead of failing, so tier-1 reports capability, not availability.
# ---------------------------------------------------------------------------
_MP_COLLECTIVE_TESTS = {
    "test_two_process_mapper_exchange",
    "test_two_process_load_then_train",
    "test_two_process_data_parallel_training",
}
_MP_PROBE_WORKER = """
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
rank, port = int(sys.argv[1]), sys.argv[2]
import jax
jax.config.update("jax_platforms", "cpu")
jax.distributed.initialize(coordinator_address="127.0.0.1:" + port,
                           num_processes=2, process_id=rank)
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map
mesh = Mesh(np.array(jax.devices()), ("data",))
arr = jax.make_array_from_process_local_data(
    NamedSharding(mesh, P("data")), np.ones(1, np.float32))
out = jax.jit(shard_map(lambda x: jax.lax.psum(x, "data"), mesh=mesh,
                        in_specs=P("data"), out_specs=P("data")))(arr)
assert float(out.addressable_shards[0].data[0]) == 2.0
print("MP-COLLECTIVES-OK")
"""
_mp_probe_cache = {}


def _mp_collectives_supported():
    """One cached 2-process psum probe; (supported, reason-if-not)."""
    if "verdict" in _mp_probe_cache:
        return _mp_probe_cache["verdict"]
    import socket
    import subprocess
    import sys as _sys
    import tempfile

    verdict = (False, "probe could not run")
    for _attempt in range(2):  # retry once on a coordinator port race
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        env = dict(__import__("os").environ, JAX_PLATFORMS="cpu")
        env.pop("XLA_FLAGS", None)  # real 1-device procs, no virtual mesh
        with tempfile.TemporaryDirectory() as td:
            worker = td + "/mp_probe.py"
            with open(worker, "w") as fh:
                fh.write(_MP_PROBE_WORKER)
            procs = [
                subprocess.Popen(
                    [_sys.executable, worker, str(r), str(port)], env=env,
                    stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
                )
                for r in range(2)
            ]
            outs = []
            try:
                for p in procs:
                    out, err = p.communicate(timeout=240)
                    outs.append((p.returncode, out, err))
            except subprocess.TimeoutExpired:
                for p in procs:
                    p.kill()
                verdict = (False, "capability probe timed out")
                break
        if all(rc == 0 and "MP-COLLECTIVES-OK" in out for rc, out, _ in outs):
            verdict = (True, "")
            break
        errs = " ".join(e for _, _, e in outs).lower()
        if "address already in use" in errs or "failed to bind" in errs:
            continue  # port race: retry on a fresh port
        tail = next(
            (e for rc, _, e in outs if rc != 0), outs[0][2]
        ).strip().splitlines()
        verdict = (False, tail[-1][:200] if tail else "probe failed")
        break
    _mp_probe_cache["verdict"] = verdict
    return verdict


# ---------------------------------------------------------------------------
# The slow marker's membership lives in
# tests/slow_tests.txt (one node id per line, relative to tests/, with the
# per-block redundancy justification). The tier-1 window runs -m 'not slow';
# run the excluded long tail with -m slow when touching its subsystem.
# ---------------------------------------------------------------------------
_SLOW_LIST = os.path.join(os.path.dirname(__file__), "slow_tests.txt")


def _slow_nodeids():
    try:
        with open(_SLOW_LIST, encoding="utf-8") as fh:
            return {
                line.strip() for line in fh
                if line.strip() and not line.lstrip().startswith("#")
            }
    except OSError:
        return set()


def pytest_collection_modifyitems(config, items):
    mp_items = [
        i for i in items
        if i.module.__name__.rsplit(".", 1)[-1] == "test_multiprocess_dist"
        and i.name.split("[")[0] in _MP_COLLECTIVE_TESTS
    ]
    if mp_items:
        supported, reason = _mp_collectives_supported()
        if not supported:
            marker = pytest.mark.skip(
                reason="jaxlib lacks multi-process CPU collectives "
                       "(probed: %s)" % reason
            )
            for item in mp_items:
                item.add_marker(marker)
    slow_ids = _slow_nodeids()
    matched = set()
    for item in items:
        mod = item.module.__name__.rsplit(".", 1)[-1]
        if mod in _QUICK_MODULES:
            item.add_marker(pytest.mark.quick)
        if slow_ids:
            nodeid = item.nodeid
            if nodeid.startswith("tests/"):
                nodeid = nodeid[len("tests/"):]
            if nodeid in slow_ids:
                item.add_marker(pytest.mark.slow)
                matched.add(nodeid)
    # a renamed/removed test must not silently resurrect a 2000s tier-1 —
    # but only judge entries whose module was FULLY collected: a narrowed
    # invocation (node-id selection, -k, --deselect) legitimately collects
    # a subset, and warning there would spam every targeted run
    narrowed = (
        bool(config.getoption("keyword", ""))
        or bool(config.getoption("deselect", None))
        or any("::" in str(a) for a in config.invocation_params.args)
    )
    collected_mods = {
        i.nodeid.split("::", 1)[0].rsplit("/", 1)[-1] for i in items
    }
    stale = set() if narrowed else {
        s for s in slow_ids - matched
        if s.split("::", 1)[0] in collected_mods
    }
    if stale:
        import warnings

        warnings.warn(
            "tests/slow_tests.txt entries matched no collected test "
            "(renamed? removed?): %s" % ", ".join(sorted(stale)[:8]),
            stacklevel=1,
        )
