"""chip_smoke.py off the chip: it refuses, and says why.

The chip run itself cannot happen here (no accelerator); what CAN be pinned
on a CPU is everything that keeps a run without the chip from looking like
one: the default invocation fails fast naming the missing TPU, ``--rehearse``
is the only way through and brands every line, the compile cache has one
home that does not move with the working directory, and the peak table has
no row for a device it does not know.
"""
import json
import os
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, cwd=REPO, timeout=300, **env_over):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # a real one-device CPU, not the test mesh
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(env_over)
    t0 = time.time()
    proc = subprocess.run(
        [sys.executable] + args, cwd=cwd, env=env, capture_output=True,
        text=True, timeout=timeout,
    )
    return proc, time.time() - t0


def _json_lines(stdout):
    return [ln for ln in stdout.splitlines() if ln.lstrip().startswith("{")]


def test_no_tpu_is_a_fast_named_failure():
    proc, took = _run([os.path.join(REPO, "chip_smoke.py")])
    assert proc.returncode != 0
    assert "TPU" in proc.stderr and "cpu" in proc.stderr, proc.stderr[-400:]
    # no result line, and nothing was trained or made on the way
    assert not _json_lines(proc.stdout), proc.stdout[-400:]
    assert "binned" not in proc.stdout + proc.stderr
    assert took < 60, "refusal took %.0fs" % took


def test_rehearsal_passes_and_brands_every_line():
    proc, _ = _run([os.path.join(REPO, "chip_smoke.py"), "--rehearse"])
    assert proc.returncode == 0, (proc.stdout[-1500:], proc.stderr[-1500:])
    lines = [ln for ln in proc.stdout.splitlines() if "chip_smoke:" in ln]
    assert len(lines) > 10
    assert all(ln.startswith("REHEARSAL [cpu] ") for ln in lines), lines[:3]
    # a rehearsal prints no bare result line a driver could take for a pass
    assert not _json_lines(proc.stdout)
    assert "rehearsal passed" in lines[-1]
    # the result line a chip run ends with: "ok" and "device", nothing else
    # (the driver refuses any other key); the observations ride the line before
    result = json.loads(lines[-2].split("would have printed: ", 1)[1])
    assert sorted(result) == ["device", "ok"] and result["ok"] is True
    assert sorted(result["device"]) == ["count", "kind", "platform"]
    assert isinstance(result["device"]["count"], int)
    report = json.loads(lines[-3].split("report: ", 1)[1])
    assert report["ok"] is True and {"train", "kernels", "serve"} <= set(report)


def test_compile_cache_has_one_home(tmp_path, monkeypatch):
    import jax

    from lightgbm_tpu.utils.platform import place_compile_cache

    before = jax.config.jax_compilation_cache_dir
    expect = os.path.join(REPO, ".jax_cache")
    try:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        for cwd in (REPO, str(tmp_path)):
            monkeypatch.chdir(cwd)
            jax.config.update("jax_compilation_cache_dir", None)
            assert place_compile_cache() == expect
            assert jax.config.jax_compilation_cache_dir == expect
        # placed from outside: the variable wins (jax reads it by itself at
        # import) and the helper sets nothing in code
        elsewhere = str(tmp_path / "cache")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", elsewhere)
        jax.config.update("jax_compilation_cache_dir", "untouched")
        assert place_compile_cache() == elsewhere
        assert jax.config.jax_compilation_cache_dir == "untouched"
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_unknown_device_has_no_peaks():
    from lightgbm_tpu.obs import costs
    from lightgbm_tpu.utils.log import LightGBMError

    with pytest.raises(LightGBMError, match="TPU v9"):
        costs.chip_peaks("TPU v9", platform="tpu")
    with pytest.raises(LightGBMError):
        costs.chip_peaks("cpu", platform="cpu")
    with pytest.raises(LightGBMError):
        costs.vmem_bytes("TPU v9")
    # what the v5e reports under jax 0.9 / libtpu 0.0.34 (PR 21 chip run)
    assert costs.normalize_device_kind("TPU v5 lite") == "v5e"
    v5e = costs.chip_peaks("TPU v5 lite", platform="tpu")
    assert (v5e["peak_flops"], v5e["peak_bw"]) == (197e12, 819e9)
    assert "cpu" not in costs.CHIP_PEAKS
    # off-chip callers ask for a VMEM size by name: the smallest row
    assert costs.vmem_bytes() == min(
        r["vmem_bytes"] for r in costs.CHIP_PEAKS.values()
    )
