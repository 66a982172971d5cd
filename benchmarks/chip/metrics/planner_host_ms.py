"""Host milliseconds per plan_many call: the call's wall time less the
device time of the planner's programs inside it, averaged over the calls."""
import cb_trace


def read(run):
    calls = run.traffic.plan_calls
    if run.summary is None or not calls:
        return None
    s = cb_trace.planner_seconds(run.summary)
    if not s:
        return None
    return (sum(w for _, _, w in calls) - s) * 1e3 / len(calls)
