"""p50_ms: median latency, from the due time to the future's completion,
of the queries due in the window that completed."""


def read(run):
    return run.summary.get("p50_ms")
