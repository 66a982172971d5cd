"""95th percentile of the wall time of every plan_many call in the window,
in milliseconds."""
import numpy as np


def read(run):
    walls = [w for _, _, w in run.traffic.plan_calls]
    if not walls:
        return None
    return float(np.percentile(walls, 95)) * 1e3
