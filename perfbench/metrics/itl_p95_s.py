"""95th percentile of the gaps between consecutive tokens delivered to the
handles of the requests due in the window."""


def read(run):
    return run.pct(run.itls(), 95)
