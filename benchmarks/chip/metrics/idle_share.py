"""Share of the window in which no operation ran on the device: one less
the union of device operation intervals over the window."""


def read(run):
    if run.summary is None:
        return None
    return run.summary.idle_share * 100.0
