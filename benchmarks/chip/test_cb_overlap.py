"""The ``repair_overlap`` reader on synthetic store counters: 1.0 for
workers that repair strictly in turn, W for W that overlap throughout,
nothing where the store keeps no counters."""
import types

import pytest

import cb_harness


class _Store:
    """A store's counters as ``RlncSimulator.reconstructions`` keeps them:
    each repair's start and end on ``time.perf_counter``."""

    def __init__(self, spans):
        self.reconstructions = [types.SimpleNamespace(start_s=s, end_s=e)
                                for s, e in spans]

    def host_bytes(self):
        return 1234


def _read(*stores, done=None):
    """The reader on a run whose records hold ``done[w]`` repairs of
    worker ``w`` (by default all its store counted); with what it said."""
    workers = [types.SimpleNamespace(index=i, store=s)
               for i, s in enumerate(stores)]
    if done is None:
        done = [len(getattr(s, "reconstructions", [])) for s in stores]
    repaired = [i for i, n in enumerate(done) for _ in range(n)]
    said = []
    run = types.SimpleNamespace(
        traffic=types.SimpleNamespace(workers=workers,
                                      worker_of={"repaired": repaired}),
        say=lambda phase, **f: said.append((phase, f)))
    return cb_harness.reader("repair_overlap")(run), said


def test_two_workers_strictly_in_turn_read_one():
    got, said = _read(_Store([(0.0, 1.0), (2.0, 3.0)]),
                      _Store([(1.0, 2.0), (3.0, 4.0)]))
    assert got == pytest.approx(1.0)
    assert said == [("repair_overlap", {"repairs": 4, "busy_s": 4.0,
                                        "host_bytes": "1234,1234"})]
    # in turn with gaps between the repairs (the plans, a wait)
    got, _ = _read(_Store([(0.0, 1.0), (5.0, 6.0)]),
                   _Store([(2.0, 3.0), (3.5, 4.0)]))
    assert got == pytest.approx(1.0)


def test_two_workers_overlapping_throughout_read_two():
    got, _ = _read(_Store([(0.0, 2.0), (2.0, 4.0)]),
                   _Store([(0.0, 1.5), (1.5, 4.0)]))
    assert got == pytest.approx(2.0)


def test_partial_overlap_counts_repairs_in_flight_while_any_is():
    # [0, 4] and [2, 6]: 8 s of repairs in the 6 s some repair ran
    got, _ = _read(_Store([(0.0, 4.0)]), _Store([(2.0, 6.0)]))
    assert got == pytest.approx(8.0 / 6.0)


def test_one_loop_without_workers_repairs_in_turn():
    run = types.SimpleNamespace(traffic=types.SimpleNamespace(repairs=3),
                                say=lambda *a, **k: None)
    assert cb_harness.reader("repair_overlap")(run) == 1.0
    run.traffic.repairs = 0
    assert cb_harness.reader("repair_overlap")(run) is None


def test_only_the_repairs_the_records_hold_count():
    # a traced run's records hold each worker's first repairs only
    got, _ = _read(_Store([(0.0, 1.0), (1.0, 9.0)]),
                   _Store([(0.0, 1.0), (1.0, 2.0)]), done=[1, 1])
    assert got == pytest.approx(2.0)


@pytest.mark.parametrize("stores", [
    (types.SimpleNamespace(nodes={}), _Store([(0.0, 1.0)])),
    (None, None),
    (_Store([]), _Store([])),
], ids=["store_without_counters", "planner_mix", "no_repair"])
def test_nothing_to_read_reads_none(stores):
    got, said = _read(*stores)
    assert got is None and said == []
