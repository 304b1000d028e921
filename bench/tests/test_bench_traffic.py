"""The traffic generator and the open-loop accounting."""
import json
import sys
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

from harness import drive, traffic  # noqa: E402

# an open-loop mix for the generator's own tests (no cell runs it)
CHAT = {"loop": "open", "system_prompts": {"count": 4, "tokens": 384},
        "prompt_tokens": {"dist": "lognormal", "median": 192, "sigma": 0.8,
                          "min": 32, "max": 512},
        "output_tokens": {"dist": "lognormal", "median": 32, "sigma": 0.7,
                          "min": 8, "max": 64},
        "sampling": {"temperature": 0.7, "top_k": 50, "greedy_every": 4}}
LONGGEN = json.loads((BENCH / "traffic" / "longgen.json").read_text())
SEED = 2 ** 31 + 12345


def test_same_seed_same_schedule():
    a = traffic.Generator(CHAT, SEED, 1000).open_loop(3.0, -2, 10)
    b = traffic.Generator(CHAT, SEED, 1000).open_loop(3.0, -2, 10)
    assert [r.due for r in a] == [r.due for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert [(r.max_new, r.greedy, r.sample_seed) for r in a] == \
        [(r.max_new, r.greedy, r.sample_seed) for r in b]


def test_seeds_change_the_inputs_not_the_schedule():
    """Seeds share the schedule's sizes and gaps, in another order."""
    a = traffic.Generator(CHAT, 1, 1000).open_loop(3.0, -2, 10)
    b = traffic.Generator(CHAT, 2, 1000).open_loop(3.0, -2, 10)
    assert sorted((len(r.prompt), r.max_new, r.greedy) for r in a) == \
        sorted((len(r.prompt), r.max_new, r.greedy) for r in b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    assert not any(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert [r.sample_seed for r in a] != [r.sample_seed for r in b]
    # stratified: the gaps are quantiles of the exponential, the same
    # set for every seed in an order of its own
    # (the first gap of each order only sets where the first request
    # lies and is not between two)
    ga, gb = (sorted(np.diff([r.due for r in x])) for x in (a, b))
    shared = set(np.round(ga, 9)) & set(np.round(gb, 9))
    assert len(shared) >= len(ga) - 1
    assert ga[0] < 0.05 < 1.0 < ga[-1]


def test_lengths_respect_their_clips():
    reqs = traffic.Generator(CHAT, SEED, 1000).open_loop(10.0, 0, 100)
    user = [len(r.prompt) - 384 for r in reqs]
    assert min(user) >= 32 and max(user) <= 512
    assert all(8 <= r.max_new <= 64 for r in reqs)
    assert all((r.prompt >= 0).all() and (r.prompt < 1000).all()
               for r in reqs)
    # every prompt opens with one of the 4 system prompts
    assert len({r.prompt[:384].tobytes() for r in reqs}) == 4
    assert sum(r.greedy for r in reqs) == (len(reqs) + 3) // 4
    dues = sorted(r.due for r in reqs)
    assert dues[0] >= 0 and dues[-1] <= 100


def test_closed_loop_first_wave_is_staggered():
    gen = traffic.Generator(LONGGEN, SEED, 1000)
    queues = gen.closed_loop(8, 3, LONGGEN["first_wave"])
    assert [len(q) for q in queues] == [8 * [3]][0]
    firsts = [q[0] for q in queues]
    later = [r for q in queues for r in q[1:]]
    assert all(1024 <= r.max_new <= 2048 and 128 <= len(r.prompt) <= 1024
               for r in later)
    # the first wave keeps its prompts and gets stratified shares of its
    # budgets, from a few tokens to nearly the whole budget
    assert all(128 <= len(r.prompt) <= 1024 and 1 <= r.max_new <= 2048
               for r in firsts)
    budgets = sorted(r.max_new for r in firsts)
    assert len(set(budgets)) == 8
    assert budgets[0] <= 2048 // 16 + 1 and budgets[-1] >= 1024 * 15 // 16
    # every seed gives the clients the same work in the same order
    other = traffic.Generator(LONGGEN, SEED + 1, 1000).closed_loop(
        8, 3, LONGGEN["first_wave"])
    shape = lambda qs: [[(len(r.prompt), r.max_new, r.greedy) for r in q]
                        for q in qs]
    assert shape(queues) == shape(other)


class _FakeLoop:
    """An engine loop on a fake clock: each step takes ``step_s``, the
    first one ``stall_s``; every admitted request gets its first token
    at the end of the step after its admission."""

    def __init__(self, clock, step_s, stall_s):
        self.clock, self.step_s, self.stall_s = clock, step_s, stall_s
        self.pending, self.live = [], []
        self.steps = 0

    def add_request(self, request):
        h = type("H", (), {})()
        h.tokens, h.token_walls, h.finished, h._seq = [], [], False, None
        h.request = request
        self.pending.append(h)
        return h

    @property
    def has_work(self):
        return bool(self.pending or self.live)

    def step(self):
        self.clock.t += self.stall_s if self.steps == 0 else self.step_s
        self.steps += 1
        for h in self.live:
            h.tokens.append(1)
            h.token_walls.append(self.clock.t)
            h.finished = True
        self.live = [h for h in self.live if not h.finished]
        self.live += self.pending
        self.pending = []


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += max(s, 0.0)


def test_ttft_counts_from_due_time_so_a_stall_charges_the_queue():
    clock = _Clock()
    loop = _FakeLoop(clock, step_s=0.01, stall_s=1.0)
    reqs = [traffic.Req(prompt=np.zeros(4, np.int32), max_new=1,
                        greedy=True, sample_seed=0, due=0.1 * i)
            for i in range(5)]
    d = drive.Driver(loop, {"temperature": 0.0}, clock=clock,
                     sleep=clock.sleep)
    d.open_loop(reqs, window_open=0.0, close=1.0, deadline=5.0)
    assert len(d.records) == 5 and all(r.finished for r in d.records)
    # request 0 was submitted at 0 and the 1 s stall began at once;
    # requests due at 0.1 .. 0.4 were submitted only after it, late
    lags = [r.submit - r.due for r in d.records]
    assert lags[0] == 0.0
    assert all(lag > 0.5 for lag in lags[1:])
    ttft = [r.walls[0] - r.due for r in d.records]
    assert all(t > 0.6 for t in ttft)
    # time to first token from submission would hide the stall
    assert all(r.walls[0] - r.submit < 0.05 for r in d.records[1:])


def test_tokens_between_counts_the_window_only():
    recs = [type("R", (), {"walls": [0.5, 1.0, 1.5, 2.5]})(),
            type("R", (), {"walls": [1.2]})()]
    assert drive.tokens_between(recs, 1.0, 2.0) == 2
