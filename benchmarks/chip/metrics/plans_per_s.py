"""Plans returned by the window's plan_many calls, over the whole window."""


def read(run):
    plans = run.traffic.plans_returned()
    return plans / run.window_s if plans else None
