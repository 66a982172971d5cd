"""Device milliseconds of the jit planner's programs per step: per
``plan_many`` call where a step plans with one scheme, per batch where
every scheme of the mix plans the batch once."""
import cb_trace


def read(run):
    steps = run.traffic.steps
    if run.summary is None or not steps:
        return None
    s = cb_trace.planner_seconds(run.summary)
    return s * 1e3 / steps if s else None
