"""Seeded open-loop traffic: the schedule, the sender, the per-request
consumers, and the percentile arithmetic.

Every seed gets THE SAME work in another order: the set of (prompt
length, output length) pairs and the set of gaps between arrivals are
fixed by the mix (stratified quantiles of its distributions), and the
seed only permutes them and draws the token ids.  Two runs then differ
by order and by noise, not by how much work they were given.

The loop is open: a request is sent when it is due whether or not the
earlier ones have finished, and its latency counts from when it was
DUE, so a stall is charged to every request it delays.
"""
import math
import threading
import time
from statistics import NormalDist

import numpy as np


def percentile(values, q):
    """Nearest-rank percentile (the arithmetic of tools/serve_bench.py):
    the value at index min(n - 1, int(q * n)) of the sorted list."""
    xs = sorted(values)
    if not xs:
        return None
    return xs[min(len(xs) - 1, int(q * len(xs)))]


def lognormal_lengths(n, median, sigma, lo, hi):
    """n whole lengths at the stratified quantiles (i + 0.5) / n of a
    log-normal, clipped to [lo, hi]."""
    inv = NormalDist().inv_cdf
    return [int(min(hi, max(lo, round(median * math.exp(
        sigma * inv((i + 0.5) / n)))))) for i in range(n)]


def exponential_gaps(n, rate):
    """n gaps at the stratified quantiles of an exponential with mean
    1 / rate (Poisson arrivals once shuffled)."""
    return [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]


class Request:
    __slots__ = ("due_s", "prompt", "max_new", "sent_s", "token_s",
                 "tokens", "error", "stream", "ended_s")

    def __init__(self, due_s, prompt, max_new):
        self.due_s = due_s          # relative to the window's opening
        self.prompt = prompt
        self.max_new = max_new
        self.sent_s = None
        self.token_s = []           # when the client received each token
        self.tokens = []
        self.error = None
        self.stream = None
        self.ended_s = None

    @property
    def finished(self):
        return self.error is None and len(self.tokens) == self.max_new


def schedule(mix, seconds, seed, vocab):
    """The requests of one run, in order of their due time.  ``mix``
    holds rate_per_s, ramp_s and the prompt / output length
    distributions; requests due before 0 are the ramp."""
    rate, ramp = float(mix["rate_per_s"]), float(mix["ramp_s"])
    n = max(1, round(rate * (ramp + seconds)))
    p, o = mix["prompt"], mix["output"]
    prompts = lognormal_lengths(n, p["median"], p["sigma"], p["min"],
                                p["max"])
    outputs = lognormal_lengths(n, o["median"], o["sigma"], o["min"],
                                o["max"])
    # the pairing of prompt and output lengths belongs to the mix
    outputs = [outputs[i] for i in np.random.default_rng(0).permutation(n)]
    gaps = exponential_gaps(n, rate)

    rng = np.random.default_rng(seed)
    order = rng.permutation(n)
    gap_order = rng.permutation(n)
    due, requests = -ramp, []
    for i, g in zip(order, gap_order):
        due += gaps[g]
        requests.append(Request(
            due, rng.integers(0, vocab, prompts[i], dtype=np.int32),
            outputs[i]))
    return requests


class OpenLoop:
    """Sends ``requests`` on schedule from one thread; one consumer
    thread a request stamps each token as the client receives it.

    ``submit(prompt, max_new)`` returns an iterable of tokens that has
    ``cancel()``.  ``on_event(host_ns, in_flight)`` is told whenever the
    number of requests in flight changes.
    """

    def __init__(self, submit, requests, on_event=None):
        self._submit = submit
        self.requests = requests
        self._on_event = on_event
        self._lock = threading.Lock()
        self._in_flight = 0
        self._consumers = []
        self._sender = threading.Thread(target=self._send, daemon=True,
                                        name="chipbench-sender")
        self._stop = threading.Event()
        self.t0 = None

    def start(self, t0):
        """``t0`` (time.perf_counter clock) is when the window opens;
        the first request is due ramp_s before it."""
        self.t0 = t0
        self._sender.start()

    def _count(self, delta):
        with self._lock:
            self._in_flight += delta
            n = self._in_flight
        if self._on_event is not None:
            self._on_event(time.perf_counter_ns(), n)

    def _send(self):
        for req in self.requests:
            wait = self.t0 + req.due_s - time.perf_counter()
            if wait > 0 and self._stop.wait(wait):
                return
            if self._stop.is_set():
                return
            req.sent_s = time.perf_counter() - self.t0
            self._count(+1)
            try:
                req.stream = self._submit(req.prompt, req.max_new)
            except Exception as e:   # noqa: BLE001 - shed or refused: counted
                req.error = e
                req.ended_s = time.perf_counter() - self.t0
                self._count(-1)
                continue
            th = threading.Thread(target=self._consume, args=(req,),
                                  daemon=True)
            self._consumers.append(th)
            th.start()

    def _consume(self, req):
        try:
            for tok in req.stream:
                req.token_s.append(time.perf_counter() - self.t0)
                req.tokens.append(int(tok))
        except Exception as e:   # noqa: BLE001 - a failed request: counted
            req.error = e
        req.ended_s = time.perf_counter() - self.t0
        self._count(-1)

    def stop_sending(self):
        self._stop.set()
        self._sender.join()

    def cancel_unfinished(self):
        for req in self.requests:
            if req.stream is not None and req.ended_s is None:
                req.stream.cancel()

    def join(self, timeout_s):
        """Wait for the consumers; True when all have ended."""
        deadline = time.perf_counter() + timeout_s
        for th in self._consumers:
            th.join(max(0.0, deadline - time.perf_counter()))
        return not any(th.is_alive() for th in self._consumers)
