"""The arrival process and the latency arithmetic, which are the yardstick's
own: a schedule is a function of the parameters, the seed and the window
alone, every seed gets the same set of gaps, latency runs from the DUE
time, and the percentile is written out."""
import numpy as np
import pytest

from perfbench import traffic


def test_schedule_is_a_function_of_seed_rate_and_window():
    p = {"rate_rps": 200.0}
    a, b = traffic.schedule(p, 2 ** 31 + 5, 10.0), traffic.schedule(
        p, 2 ** 31 + 5, 10.0)
    assert np.array_equal(a, b)
    assert not np.array_equal(a, traffic.schedule(p, 6, 10.0))
    assert len(a) == 2000 and np.all(np.diff(a) > 0) and a[-1] < 10.0


def test_every_seed_gets_the_same_gaps_in_another_order():
    p = {"rate_rps": 50.0}
    gaps = [np.sort(np.diff(traffic.schedule(p, s, 4.0), prepend=0.0))
            for s in (1, 2, 3)]
    assert np.allclose(gaps[0], gaps[1]) and np.allclose(gaps[0], gaps[2])
    # the gaps of a Poisson process: mean 1/rate, coefficient of variation 1
    assert gaps[0].mean() == pytest.approx(1 / 50.0, rel=0.01)
    assert gaps[0].std() / gaps[0].mean() == pytest.approx(1.0, abs=0.1)


def test_phases_make_bursts_and_keep_the_mean_rate():
    p = {"rate_rps": 100.0, "phases": [{"seconds": 2, "rate_x": 1.6},
                                       {"seconds": 2, "rate_x": 0.4}]}
    due = traffic.schedule(p, 0, 8.0)
    assert len(due) == 800
    per_phase = np.histogram(due, bins=[0, 2, 4, 6, 8])[0]
    assert per_phase.tolist() == [320, 80, 320, 80]


@pytest.mark.parametrize("bad", [{"rate_rps": 0.0}, {"rate_rps": -1.0}])
def test_schedule_refuses_a_rate_that_is_not_positive(bad):
    with pytest.raises(ValueError):
        traffic.schedule(bad, 0, 1.0)


def test_latency_runs_from_the_due_time_so_a_late_submit_lengthens_it():
    due = np.array([0.0, 0.1, 0.2])
    submitted = np.array([0.0, 0.1, 0.7])   # the generator stalled
    done = submitted + 0.05                 # the server took 50 ms each
    assert traffic.latencies_ms(due, done).tolist() == pytest.approx(
        [50.0, 50.0, 550.0])
    assert traffic.late_ms(due, submitted).tolist() == pytest.approx(
        [0.0, 0.0, 500.0])
    # a request sent early by the clock's grain is not "negative late"
    assert traffic.late_ms([1.0], [0.9999]).tolist() == [0.0]


@pytest.mark.parametrize("values,q,want", [
    ([5.0], 95, 5.0),
    ([1, 2, 3, 4, 5], 50, 3.0),
    ([1, 2, 3, 4], 50, 2.5),
    ([4, 1, 3, 2], 0, 1.0),
    ([4, 1, 3, 2], 100, 4.0),
    (list(range(101)), 95, 95.0),
    ([10, 20], 95, 19.5),
])
def test_percentile(values, q, want):
    assert traffic.percentile(values, q) == pytest.approx(want)
    assert traffic.percentile(values, q) == pytest.approx(
        float(np.percentile(values, q)))


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        traffic.percentile([], 50)
