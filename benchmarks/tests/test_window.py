import numpy as np

from benchmarks.harness import window as win


class FakeClock:
    def __init__(self):
        self.now = 0.0

    def __call__(self):
        return self.now


def run(durations, seconds):
    clock = FakeClock()
    remaining = iter(durations)

    def call():
        clock.now += next(remaining)
        return [{"x": 1}]

    return win.run_window([("a", call), ("b", call)], seconds, {"a": 1000, "b": 1000}, clock=clock)


def test_window_closes_when_the_query_in_flight_completes():
    w = run([1.0] * 20, seconds=4.5)
    assert len(w.records) == 5 and w.seconds == 5.0
    assert [r.qid for r in w.records] == ["a", "b", "a", "b", "a"]  # round robin in traffic order
    assert win.end_to_end(w, 3.0)["rows_per_s"][0] == 5 * 1000 / 5.0


def test_a_stalled_query_lowers_rows_per_s_and_no_query_is_left_out():
    steady = win.end_to_end(run([1.0] * 20, seconds=10.0), 1.0)
    stalled_window = run([1.0, 1.0, 6.0] + [1.0] * 20, seconds=10.0)
    stalled = win.end_to_end(stalled_window, 1.0)
    assert stalled["rows_per_s"][0] < steady["rows_per_s"][0]
    assert stalled["rows_per_s"][0] == len(stalled_window.records) * 1000 / stalled_window.seconds
    walls = [r.wall_ms for r in stalled_window.records]
    assert max(walls) == 6000.0
    # the tail is the tail of all the window's queries, the stalled one among them
    assert stalled["query_p95_ms"][0] == float(np.percentile(walls, 95)) > 1000.0
    assert stalled["setup_s"] == (1.0, "s")
