"""The traffic generator: a seed repeats exactly, seeds differ in order and
not in amount, and lateness is measured from the due time."""

import numpy as np

from benchmark import harness, loadgen, serve_driver

POISSON = {"process": "poisson", "knee_per_s": 12.5, "load": 0.8}
LOGNORMAL = {"dist": "lognormal", "median": 128, "sigma": 0.8, "lo": 16, "hi": 512}


def test_schedule_repeats_exactly_and_differs_between_mixes():
    a = loadgen.arrival_times(POISSON, 45.0, 7)
    b = loadgen.arrival_times(POISSON, 45.0, 7)
    c = loadgen.arrival_times(POISSON, 45.0, 8)  # another mix's traffic_seed
    assert np.array_equal(a, b)
    assert len(a) == len(c) == 450 and not np.array_equal(a, c)
    assert a[0] == 0.0 and np.all(np.diff(a) >= 0) and a[-1] < 45.0
    assert abs(np.diff(a).std() / np.diff(a).mean() - 1.0) < 0.15  # exponential gaps


def test_the_offered_rate_is_the_measured_knee_times_the_load():
    assert loadgen.offered_rate({"knee_per_s": 4.5, "load": 0.8}) == 3.6
    below = loadgen.arrival_times({"process": "poisson", "knee_per_s": 4.5, "load": 0.8}, 45.0, 7)
    above = loadgen.arrival_times({"process": "poisson", "knee_per_s": 4.5, "load": 1.25}, 45.0, 7)
    assert len(below) == 162 and len(above) == 253


def test_gamma_gaps_are_burstier_than_poisson():
    p = np.diff(loadgen.arrival_times(POISSON, 200.0, 7))
    g = np.diff(loadgen.arrival_times({"process": "gamma", "cv": 3.0, "knee_per_s": 12.5, "load": 0.8}, 200.0, 7))
    assert abs(p.std() / p.mean() - 1.0) < 0.15
    assert g.std() / g.mean() > 2.0


def test_prompts_same_lengths_in_another_order_and_inside_the_vocabulary():
    a = loadgen.draw_prompts(LOGNORMAL, 300, 50304, 7, seed=1)
    b = loadgen.draw_prompts(LOGNORMAL, 300, 50304, 7, seed=1)
    c = loadgen.draw_prompts(LOGNORMAL, 300, 50304, 7, seed=2)
    assert a == b and a != c
    assert sorted(map(len, a)) == sorted(map(len, c))
    assert min(map(len, a)) >= 16 and max(map(len, a)) <= 512
    assert 100 <= np.median([len(p) for p in a]) <= 160
    assert all(1 <= t < 50303 for p in a for t in p)


def test_program_seed_fits_31_bits_for_a_seed_past_2_31():
    assert 0 <= loadgen.program_seed(2**31 + 12345) < 2**31 - 1
    assert loadgen.program_seed(1) != loadgen.program_seed(2)


def test_percentile_is_nearest_rank():
    xs = list(range(1, 101))
    assert loadgen.percentile(xs, 95) == 95 and loadgen.percentile(xs, 50) == 50
    assert loadgen.percentile([3.0], 95) == 3.0


class _Stream:
    def __init__(self):
        self.closed, self.buf = False, []

    def drain(self):
        out, self.buf = self.buf, []
        return out


class _SlowServer:
    """Emits one token per open request per pump, 20 ms a pump: requests
    that fall due during a pump are submitted late, and their latency must
    still count from when they were due."""

    def __init__(self, budget):
        self.budget, self.streams, self.sent, self.results = budget, {}, {}, {}

    def submit(self, prompts, stream=True):
        rid = len(self.streams)
        self.streams[rid], self.sent[rid] = _Stream(), 0
        return [rid]

    def stream(self, rid):
        return self.streams[rid]

    def _pump_once(self):
        import time

        time.sleep(0.02)
        busy = False
        for rid, s in self.streams.items():
            if not s.closed:
                busy = True
                s.buf.append(1)
                self.sent[rid] += 1
                if self.sent[rid] == self.budget:
                    s.closed = True
                    self.results[rid] = {"tokens": [1] * self.budget, "length": self.budget}
        return busy

    def poll(self, rid):
        return self.results.get(rid)

    def pop_result(self, rid):
        return self.results.pop(rid)


def test_lateness_and_ttft_count_from_the_due_time():
    server = _SlowServer(budget=3)
    due = np.array([0.0, 0.005, 0.010])  # the last two fall due inside the first pump
    out = serve_driver.drive(server, [[1]] * 3, due, 0.02, 5.0, harness.Spans(), budget=3)
    assert not out["unfinished"] and len(out["done"]) == 3
    late = [c for c in out["done"] if c.due > 0]
    assert all(c.submitted - c.due >= 0.009 for c in late)
    # first token one pump after submission: from the due time that is
    # the lag plus the pump, never the pump alone
    assert all(c.token_times[0] - c.due >= 0.02 + 0.009 for c in late)
    assert all(len(c.token_times) == 3 and c.surplus == 0 for c in out["done"])
