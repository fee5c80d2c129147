"""Traffic: the request images, the arrival clock and the two loops,
read from a mix's parameter file (chipbench/traffic/<mix>.json).

Every seed gets the same work in another order: the multiset of image
sizes and of inter-arrival gaps is fixed by the mix file, the seed
draws the images' contents and permutes the order.  So two seeds
differ in which request meets which burst, not in how much there is to
do.

The image generator is a copy of the program's synthetic STD data
(bright text strips with character ticks on a dark, noisy background),
kept here so that the program cannot change the yardstick.
"""
from __future__ import annotations

import math
import threading
import time
from typing import Callable, Dict, List, Sequence, Tuple

import numpy as np

#: fixed seed of the size and gap multisets (the work, not its order)
WORK_SEED = 20230619


def percentile(values: Sequence[float], q: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of no values")
    pos = (len(v) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def _render(img, x0, y0, x1, y1, rng):
    img[y0:y1, x0:x1] += rng.uniform(0.5, 0.9)
    for cx in range(x0, x1, max((x1 - x0) // 6, 2)):
        img[y0:y1, cx:cx + 1] -= 0.3


def synthetic_image(h: int, w: int, rng, max_instances: int) -> np.ndarray:
    """One (h, w, 3) float32 scene with 1..max_instances text strips."""
    img = rng.uniform(0.0, 0.25, size=(h, w)).astype(np.float32)
    for _ in range(int(rng.integers(1, max_instances + 1))):
        tw = int(rng.integers(40, max(w // 3, 48)))
        th = int(rng.integers(12, max(h // 8, 16)))
        x0 = int(rng.integers(0, max(w - tw, 1)))
        y0 = int(rng.integers(0, max(h - th, 1)))
        _render(img, x0, y0, x0 + tw, y0 + th, rng)
    img = np.repeat(img[..., None], 3, axis=2)
    img += rng.normal(0, 0.02, size=img.shape).astype(np.float32)
    return np.clip(img, 0, 1).astype(np.float32)


def image_pool(mix: Dict, seed: int) -> List[np.ndarray]:
    """The mix's pool: sizes uniform over [min, max] in steps of
    ``step`` (a fixed multiset), contents and order from ``seed``."""
    size = mix["size"]
    n = int(mix["pool"])
    lo, hi, step = int(size["min"]), int(size["max"]), int(size["step"])
    work = np.random.default_rng(WORK_SEED)
    sides = work.integers(lo // step, hi // step + 1, size=(n, 2)) * step
    rng = np.random.default_rng(seed)
    order = rng.permutation(n)
    return [synthetic_image(int(sides[i, 0]), int(sides[i, 1]), rng,
                            int(mix["max_instances"])) for i in order]


def arrival_offsets(rate: float, seconds: float, seed: int) -> np.ndarray:
    """Poisson arrivals at ``rate`` per second over ``seconds``: n =
    rate * seconds requests, the n - 1 gaps between them the
    exponential distribution's quantiles at (i + 0.5) / (n - 1) scaled
    to a mean of 1 / rate (the same multiset for every seed) in an
    order drawn from ``seed``.  Returns each request's due offset, the
    first at 0 and all before ``seconds``."""
    n = max(int(round(rate * seconds)), 2)
    gaps = -np.log1p(-(np.arange(n - 1) + 0.5) / (n - 1))
    gaps *= (n - 1) / rate / gaps.sum()
    gaps = np.random.default_rng(seed).permutation(gaps)
    return np.concatenate([[0.0], np.cumsum(gaps)])


class Requests:
    """Per-request records of one window: due, sent and done times on
    the host's monotonic clock, the future, the pool slot."""

    def __init__(self):
        self.due: List[float] = []
        self.sent: List[float] = []
        self.done: Dict[int, float] = {}
        self.futures: List = []
        self.slot: List[int] = []
        self._lock = threading.Lock()
        self.completed = threading.Semaphore(0)

    def add(self, due: float, slot: int, submit: Callable[[int, int], object]):
        """Submit one request through ``submit(req, slot)`` and time it."""
        i = len(self.due)
        self.due.append(due)
        self.slot.append(slot)
        self.sent.append(time.perf_counter())
        fut = submit(i, slot)
        self.futures.append(fut)
        fut.add_done_callback(lambda f, i=i: self._done(i))
        return i

    def _done(self, i: int):
        t = time.perf_counter()
        with self._lock:
            self.done[i] = t
        self.completed.release()

    def wait_all(self, timeout: float) -> None:
        """Wait for every request, up to ``timeout`` seconds in all."""
        deadline = time.perf_counter() + timeout
        for f in self.futures:
            left = deadline - time.perf_counter()
            if left <= 0:
                return
            try:
                f.exception(timeout=left)
            except Exception:
                return


def closed_loop(submit, n_pool: int, clients: int, t0: float,
                seconds: float) -> Requests:
    """``clients`` requests outstanding: each completion sends the next,
    from one thread, until ``t0 + seconds``.  A request is due when it
    is sent."""
    reqs = Requests()
    end = t0 + seconds
    for _ in range(clients):
        reqs.add(time.perf_counter(), len(reqs.due) % n_pool, submit)
    while True:
        left = end - time.perf_counter()
        if left <= 0 or not reqs.completed.acquire(timeout=left):
            break
        if time.perf_counter() >= end:
            break
        reqs.add(time.perf_counter(), len(reqs.due) % n_pool, submit)
    return reqs


def open_loop(submit, n_pool: int, offsets: np.ndarray, t0: float
              ) -> Requests:
    """Send request i at ``t0 + offsets[i]`` from one thread, whatever
    is outstanding; late sends stay due at their scheduled time."""
    reqs = Requests()
    for i, off in enumerate(offsets):
        due = t0 + float(off)
        wait = due - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        reqs.add(due, i % n_pool, submit)
    return reqs


def latencies_ms(reqs: Requests, give_up: float) -> Tuple[List[float], int]:
    """Latency from due time to resolution of every request; one that
    never resolved counts at ``give_up`` (it stays in the tail).
    Returns (latencies, unfinished)."""
    out, unfinished = [], 0
    for i, due in enumerate(reqs.due):
        t = reqs.done.get(i)
        if t is None:
            unfinished += 1
            t = give_up
        out.append((t - due) * 1e3)
    return out, unfinished
