"""How many block-group reconstructions were in flight at once, on average
over the time in which any was: the summed durations of every store's
``execute_plan`` calls over the union of their intervals.  It reads 1.0
where the workers repair strictly in turn, gaps between repairs or not,
and W where W of them overlap throughout.

Read from each worker's store counters (``RlncSimulator.reconstructions``,
each repair's start and end on ``time.perf_counter``), for the repairs
that the records hold; says what each store's nodes hold
(``host_bytes()``).  A store that keeps no counters reads nothing.  A
window of one closed loop with no ``workers`` (the records' shape before
mixes had them) repairs in turn, so it reads 1.0 where it repaired."""


def _union_s(spans) -> float:
    total, end = 0.0, float("-inf")
    for s, e in sorted(spans):
        if e > end:
            total += e - max(s, end)
            end = e
    return total


def read(run):
    t = run.traffic
    workers = getattr(t, "workers", None)
    if workers is None:
        return 1.0 if t.repairs else None
    spans, held = [], []
    for w in workers:
        recs = getattr(w.store, "reconstructions", None)
        if recs is None:
            return None
        done = t.worker_of["repaired"].count(w.index)
        spans += [(r.start_s, r.end_s) for r in recs[:done]]
        held.append(w.store.host_bytes())
    busy = _union_s(spans)
    if busy <= 0.0:
        return None
    run.say("repair_overlap", repairs=len(spans), busy_s=busy,
            host_bytes=",".join(map(str, held)))
    return sum(e - s for s, e in spans) / busy
