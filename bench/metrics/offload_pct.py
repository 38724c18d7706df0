"""offload_pct: share of the queries completed in the window that the
offload (host CPU) tier served, from the engine's per-tier completion
counters."""


def read(run):
    a = run.counters["start"]["per_device"]
    b = run.counters["end"]["per_device"]
    done = {t: b.get(t, 0) - a.get(t, 0) for t in b}
    total = sum(done.values())
    return 100.0 * done.get("CPU", 0) / total if total else None
