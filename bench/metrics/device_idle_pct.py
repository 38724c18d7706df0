"""device_idle_pct: share of the traced window in which no operation ran
on the device (one less the union of the ``XLA Ops`` intervals over the
window, averaged over the chips)."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * run.trace.idle_share
