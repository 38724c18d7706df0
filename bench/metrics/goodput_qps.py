"""goodput_qps: queries due in the window that completed within the
configuration's SLO of their due time, over the window's seconds.  A query
rejected as BUSY, failed or never answered is a miss."""


def read(run):
    return run.summary.get("goodput_qps")
