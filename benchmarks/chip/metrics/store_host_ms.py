"""Host milliseconds per repair in the coded store around the kernel: the
span of execute_plan less the spans of its GF matmul calls."""


def read(run):
    t = run.traffic
    if not t.repairs:
        return None
    return (sum(t.exec_s) - sum(t.mm_s)) * 1e3 / t.repairs
