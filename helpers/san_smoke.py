"""graftsan concurrency stress smoke: the real serve stack under full
sanitizer instrumentation (check.sh --san).

With ``LIGHTGBM_TPU_SAN=transfer,nan,locks`` armed BEFORE import (so every
serve/obs lock is an order-recording _SanLock and the bucketed dispatch runs
under the no-implicit-upload guard), this drives everything the PRs 3-9
serve/obs stack does concurrently:

  * N predictor threads hammering ServeApp.predict with mixed row counts
    and kinds (exact + fused), half on drift-shifted traffic;
  * a hot-swap thread alternating two model versions through
    ModelRegistry.load (watchdog disarm/arm window included);
  * a scrape thread pulling prometheus_metrics() + drift_snapshot();
  * a final graceful drain with requests still in flight.

PASS requires: zero sanitizer trips (no implicit transfer, no lock-order
inversion) and zero prediction errors on the real stack — while a seeded
self-check proves each tripwire actually fires (a deliberate inversion and
a deliberate implicit upload must both raise). The sanitizer being CLEAN on
instrumented code is only evidence if the instruments are live.

Run: JAX_PLATFORMS=cpu python helpers/san_smoke.py
"""
from __future__ import annotations

import json
import os
import sys
import tempfile
import threading
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["LIGHTGBM_TPU_SAN"] = "transfer,nan,locks"

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np  # noqa: E402


def main() -> int:
    import lightgbm_tpu as lgb
    from lightgbm_tpu.obs import sanitize
    from lightgbm_tpu.serve.server import ServeApp

    assert sanitize.MODES == frozenset(
        ("transfer", "nan", "locks")
    ), sanitize.MODES

    rng = np.random.RandomState(0)
    F = 6
    X = rng.randn(800, F)
    y = (X[:, 0] + 0.4 * X[:, 1] > 0).astype(float)

    # two model versions (trained UNDER the transfer/nan tripwires — the
    # training dispatch seams are part of the smoke)
    boosters = [
        lgb.train(
            {"objective": "binary", "num_leaves": 15, "verbosity": -1,
             "device_chunk_size": 4, "num_iterations": rounds},
            lgb.Dataset(X, label=y),
        )
        for rounds in (6, 10)
    ]

    failures: list = []
    stop = threading.Event()

    with tempfile.TemporaryDirectory() as td:
        paths = []
        for i, bst in enumerate(boosters):
            p = os.path.join(td, "m%d.txt" % i)
            bst.save_model(p)
            paths.append(p)

        app = ServeApp(
            batch=True, max_delay_ms=1.0, warmup_rows=64, drift=True,
            drift_min_count=64,
        )
        app.registry.load("m", paths[0])
        app.arm_retrace_watchdog()

        # every serve-stack lock must actually be instrumented, or a clean
        # run proves nothing
        for obj, attr in (
            (app.registry, "_lock"), (app.registry, "_load_lock"),
            (app, "_state_lock"), (app.batcher, "_submit_lock"),
        ):
            lk = getattr(obj, attr)
            assert type(lk).__name__ == "_SanLock", (attr, type(lk))

        shifted = X[:64] + np.array([3.0] + [0.0] * (F - 1))

        def predictor(tid: int) -> None:
            r = np.random.RandomState(tid)
            try:
                for i in range(60):
                    n = int(r.choice([1, 7, 16, 33, 64]))
                    rows = X[r.randint(0, len(X), n)]
                    if tid % 2 == 0 and i % 3 == 0:
                        rows = shifted[:n] if n <= 64 else rows
                    out, _served = app.predict(
                        rows, fused=bool(tid % 3 == 0)
                    )
                    if out.shape[0] != n or not np.isfinite(out).all():
                        raise AssertionError(
                            "bad prediction shape/values: %r" % (out.shape,)
                        )
            except Exception as e:  # noqa: BLE001 - collected for the verdict
                failures.append(("predict[%d]" % tid, repr(e)))

        def swapper() -> None:
            try:
                for i in range(6):
                    if stop.is_set():
                        return
                    app.registry.load("m", paths[(i + 1) % 2])
                    time.sleep(0.05)
            except Exception as e:  # noqa: BLE001
                failures.append(("hot-swap", repr(e)))

        def scraper() -> None:
            # counters materialize lazily on first inc, so the early scrapes
            # legitimately lack serve_requests — the final-text assertion
            # below the joins is the real check
            try:
                while not stop.is_set():
                    app.prometheus_metrics()
                    app.drift_snapshot()
                    app.registry.list()
                    time.sleep(0.01)
            except Exception as e:  # noqa: BLE001
                failures.append(("scrape", repr(e)))

        threads = [
            threading.Thread(
                target=predictor, args=(t,), name="predict-%d" % t,
                daemon=True,
            )
            for t in range(6)
        ] + [
            threading.Thread(target=swapper, name="hot-swap", daemon=True),
            threading.Thread(target=scraper, name="scrape", daemon=True),
        ]
        for t in threads:
            t.start()
        # ONE shared deadline for all workers, and a hung thread is a NAMED failure —
        # a deadlock is exactly the bug class this smoke exists to catch,
        # not something to mask behind a successful drain
        deadline = time.monotonic() + 240
        for t in threads[:7]:
            t.join(timeout=max(0.0, deadline - time.monotonic()))
            if t.is_alive():
                failures.append((t.name, "thread hung past the join deadline"))
        stop.set()
        threads[7].join(timeout=30)
        if threads[7].is_alive():
            failures.append((threads[7].name, "scrape thread hung"))

        text = app.prometheus_metrics()
        if "lgbtpu_requests_total" not in text:
            failures.append(
                ("scrape", "final scrape lacks lgbtpu_requests_total")
            )

        drained = app.drain(timeout_s=30.0)
        if app.batcher is not None:
            app.batcher.close()
        if not drained:
            failures.append(("drain", "in-flight requests outlived drain"))

        edges = sanitize.lock_edges()
        if not edges:
            failures.append(
                ("locks", "no acquisition-order edges recorded — "
                          "instrumentation never engaged")
            )

    # ---- seeded tripwires: a clean run only counts if the teeth bite ----
    seeded = {}
    try:
        import jax

        with sanitize.transfer_scope("seeded"):
            jax.jit(lambda a: a * 2)(np.ones(4, np.float32))
        seeded["transfer"] = "MISSED"
    except sanitize.SanitizerError:
        seeded["transfer"] = "caught"
    a = sanitize.make_lock("seed.A")
    b = sanitize.make_lock("seed.B")
    with a:
        with b:
            pass
    try:
        with b:
            with a:
                pass
        seeded["inversion"] = "MISSED"
    except sanitize.SanitizerError:
        seeded["inversion"] = "caught"

    ok = not failures and all(v == "caught" for v in seeded.values())
    # ONE compact line: a driver reads the last JSON line of stdout
    print(json.dumps({
        "ok": ok,
        "san_smoke": "PASS" if ok else "FAIL",
        "failures": failures,
        "seeded": seeded,
        "lock_edges": len(edges),
    }))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
