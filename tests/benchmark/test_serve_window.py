"""The serving runner's window, on a stand-in engine: the generator's arrivals
go on after the close, submitted and not counted, until the window's requests
have finished, so that their last gaps are measured under load."""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import harness  # noqa: E402
from benchmark.runners import serve  # noqa: E402


class Request:
    def __init__(self, uid, prompt, want, arrival_s):
        self.uid, self.prompt, self.want = uid, prompt, want
        self.arrival_s, self.tokens, self.itl_s = arrival_s, [], []
        self.ttft_s = self.finished_s = self.failed = None


class Engine:
    """Every step takes 2 ms and gives each open request one token."""

    def __init__(self):
        self.waiting, self.running, self.submitted = [], [], []

    @property
    def idle(self):
        return not (self.waiting or self.running)

    def submit(self, prompt, *, max_new_tokens, tenant, arrival_s):
        req = Request(len(self.submitted), prompt, max_new_tokens, arrival_s)
        self.submitted.append(req)
        self.waiting.append(req)
        return req

    def step(self):
        self.running += self.waiting
        self.waiting = []
        time.sleep(0.002)
        now = time.monotonic()
        for r in self.running:
            if r.ttft_s is None:
                r.ttft_s = now - r.arrival_s
            else:
                r.itl_s.append(now - r.arrival_s - r.ttft_s - sum(r.itl_s))
            r.tokens.append(1)
            if len(r.tokens) == r.want:
                r.finished_s = now
        self.running = [r for r in self.running if r.finished_s is None]


def test_arrivals_go_on_after_the_close_and_are_not_counted():
    # one request every 10 ms for 2 s; an answer takes 100 steps = 0.2 s
    requests = [{"arrival_s": 0.01 * i, "prompt": [1, 2], "tenant": "default",
                 "max_new_tokens": 100} for i in range(200)]
    spans = harness.Spans()
    run = serve.drive(Engine(), requests, warm_s=0.1, seconds=0.3,
                      spans=spans, tracer=harness.Tracer(spans, False),
                      record_steps=False)
    t_open, t_close = run["t_open"], run["t_close"]
    after = [r for _, r, due in run["sent"] if due >= t_close]
    inside = [r for _, r, due in run["sent"] if t_open <= due < t_close]
    # the last of the window's requests finishes ~0.2 s after the close, and
    # some twenty others arrived meanwhile
    assert len(after) >= 10
    assert all(r.finished_s is not None for r in inside)
    assert any(r.finished_s is None for r in after)  # nobody waits for those
    assert len(run["sent"]) < len(requests)          # nor for the schedule
    e2e = serve.end_to_end(run, 0.3)
    assert e2e["attempted"] == len(inside) and e2e["failed"] == 0
    assert 25 <= e2e["attempted"] <= 31
    # every gap of the window's requests, the ones after the close too
    assert e2e["itl_p95_ms"] > 0 and e2e["ttft_p95_ms"] > 0
    assert sum(len(r.itl_s) for r in inside) == 99 * len(inside)
