"""Host milliseconds per repair in the coded store around the kernel: the
span of execute_plan less the spans of its GF matmul calls, over the
completed repairs.  With several workers it includes the time a worker
waits on the other threads."""


def read(run):
    t = run.traffic
    if not t.repairs:
        return None
    return (sum(t.exec_s) - sum(t.exec_mm_s)) * 1e3 / t.repairs
