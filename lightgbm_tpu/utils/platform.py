"""JAX platform, device-count and compile-cache helpers.

Plain JAX selects the platform: ``JAX_PLATFORMS`` when it is set, otherwise
the best backend installed (a TPU where there is one). Nothing here changes
that for a process that did not ask for virtual CPU devices by name.
"""
from __future__ import annotations

import os


def force_cpu_devices(n_devices: int = 1):
    """Pin jax to ``n_devices`` virtual CPU devices; returns the jax module.

    For tests and CPU dry runs only. Must run before the jax backend
    initializes (before the first array op / ``jax.devices()`` call) —
    afterwards the switch raises and is ignored, and the caller's assert on
    ``len(jax.devices())`` decides.
    """
    os.environ["JAX_PLATFORMS"] = "cpu"  # for children of this process

    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
        if n_devices > 1:
            jax.config.update("jax_num_cpu_devices", n_devices)
    except (RuntimeError, ValueError):
        pass  # backend already up
    return jax


def ensure_virtual_devices(n_devices: int):
    """The jax module with >= ``n_devices`` devices, or an error.

    Virtual CPU devices are used only when ``JAX_PLATFORMS=cpu`` is set
    explicitly (the test and dry-run environment). Anywhere else — an unset
    variable is the normal state of a machine with real chips — the real
    devices are taken, and too few of them is a failure, never a quiet
    switch to the CPU.
    """
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        jax = force_cpu_devices(n_devices)
    else:
        import jax
    have = len(jax.devices())
    if have < n_devices:
        raise RuntimeError(
            "need %d devices, %s reports %d (set JAX_PLATFORMS=cpu for a "
            "virtual-device dry run)" % (n_devices, jax.default_backend(), have)
        )
    return jax


def place_compile_cache() -> str:
    """Where the persistent compilation cache lives; returns the directory.

    ``JAX_COMPILATION_CACHE_DIR`` decides when it is set: jax reads it by
    itself and nothing is set in code. Otherwise ``<checkout>/.jax_cache``,
    derived from this package's own path — the directory is part of the
    cache key, so it must not depend on the working directory, a pid or a
    clock. The one place in the repo that sets ``jax_compilation_cache_dir``;
    called by the entry points that compile the grower (``chip_smoke.py``,
    ``benchmarks/run.py``, the CLI).
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))),
        ".jax_cache",
    )
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def env_int(name: str, default: int, lo: int = None, hi: int = None) -> int:
    """Import-time integer env knob beside env_choice: unparseable values
    warn and fall back (never silently), range-clamped when bounds given."""
    raw = os.environ.get(name, "")
    if not raw:
        return default
    try:
        val = int(raw)
    except ValueError:
        import warnings

        warnings.warn(
            "%s=%r is not an integer; using %d" % (name, raw, default)
        )
        return default
    if lo is not None:
        val = max(lo, val)
    if hi is not None:
        val = min(hi, val)
    return val


def env_choice(name: str, allowed) -> str:
    """Import-time env knob: the env var's lowercased value if in ``allowed``,
    else "" with a warning. Shared by the LIGHTGBM_TPU_* routing knobs
    (histogram impl, bucket lattice) so typos fail loudly and consistently."""
    val = os.environ.get(name, "").lower()
    if val and val not in allowed:
        import warnings

        warnings.warn(
            "%s=%r not recognized (expected one of %s); ignoring"
            % (name, val, "/".join(sorted(allowed)))
        )
        return ""
    return val
