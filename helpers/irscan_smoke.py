"""graftir smoke: real-program scan + seeded-violation self-check in ONE
invocation.

Wired as ``helpers/check.sh --ir``. What it proves, end to end, on whatever backend is present:

 1. the registry bootstrap trains the tiny corpus, reaches the chunked
    device path, and traces EVERY registered entry point abstractly over
    the quick shape lattice (no program executes);
 2. the real tree is clean under IR001-IR006 modulo the checked-in
    justified baseline (zero silent suppressions — stale entries fail);
 3. the lowered programs match the checked-in fingerprint contract when
    this environment is the one the contract was pinned on (a foreign
    env skips LOUDLY, it never rubber-stamps);
 4. each of the six IR rules catches its own seeded violation — a scan
    that can no longer see a poisoned program must fail here, not pass
    silently forever.

Exit 0 and a final compact JSON line on success; exit 1 with the reason
otherwise.
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def fail(msg):
    print("irscan_smoke: FAIL: %s" % msg, file=sys.stderr)
    print(json.dumps({"ok": False, "error": msg[:300]}), flush=True)
    sys.exit(1)


def main():
    # the sharded entry needs a multi-device mesh; on CPU hosts pin the
    # same virtual 8-device platform the module CLI and the tests use —
    # BEFORE jax initializes a backend
    if os.environ.get("JAX_PLATFORMS", "cpu").startswith("cpu"):
        from lightgbm_tpu.utils.platform import force_cpu_devices

        force_cpu_devices(8)

    from lightgbm_tpu.obs import irscan

    # -- seeded violations: every rule proves it still bites --------------
    selfcheck = irscan.run_selfcheck()
    missed = sorted(r for r, ok in selfcheck.items() if not ok)
    if missed:
        fail("seeded violation(s) NOT caught: %s" % ", ".join(missed))

    # -- the real tree, quick lattice, baseline + contract ----------------
    result = irscan.run_scan()
    for reason in result.skipped:
        print("irscan_smoke: skipped %s" % reason, file=sys.stderr)
    if not result.audits:
        fail("scan audited zero programs")
    baseline, _ = irscan.load_baseline(irscan.DEFAULT_BASELINE)
    new, stale = irscan.compare_to_baseline(result.findings, baseline)
    if new:
        fail("unsuppressed finding(s): %s"
             % "; ".join(f.format() for f in new[:5]))
    if stale:
        fail("stale baseline entr(ies): %s" % "; ".join(sorted(stale)))
    problems, skip = irscan.check_contract(
        irscan.load_contract(irscan.DEFAULT_CONTRACT),
        result.audits, result.trace_counts,
    )
    if skip is not None:
        print("irscan_smoke: contract %s" % skip, file=sys.stderr)
    if problems:
        fail("fingerprint contract: %s" % "; ".join(problems[:5]))

    out = {
        "ok": True,
        "entries": len(result.trace_counts),
        "programs": len(result.audits),
        "findings_baselined": len(result.findings),
        "rules_selfchecked": sorted(selfcheck),
        "contract": "skipped" if skip is not None else "ok",
        "skipped_entries": result.skipped,
    }
    print("irscan_smoke: PASS — %d entries, %d programs, contract=%s, "
          "%d rule(s) self-checked"
          % (out["entries"], out["programs"], out["contract"],
             len(out["rules_selfchecked"])), file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
