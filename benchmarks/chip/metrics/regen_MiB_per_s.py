"""Fragment bytes regenerated by the window's completed repairs, in MiB,
over the whole window."""


def read(run):
    t = run.traffic
    if not t.mix["repair"]:
        return None
    cfg = t.config
    return t.repairs * cfg["alpha"] * cfg["cell_bytes"] / 2**20 / run.window_s
