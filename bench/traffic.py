"""Traffic made from the seed before the window: keys and arrival times.

One general generator reads every mix from its data file under
``bench/traffic/``.  All keys and due times exist before the runtime
sees the first request; the stream adapters only hand out what is due.
A closed loop that outlasts its pool of keys recycles it row by row
(`Requests.row_of`).

Keys follow the truncated Zipf of the program's `SyntheticCorpus`
(probability of rank r proportional to r**-a over a seeded permutation
of the rows), drawn by inverse CDF: one CDF built once, then one
``searchsorted`` per batch of draws.  With the same seed the draws are
those of `SyntheticCorpus.tokens` exactly.

Arrival gaps of the open loop are the ``n`` quantiles of an exponential
distribution at the mix's rate, in an order shuffled by the seed: every
seed offers the same set of gaps, so the same load, in another order.
"""

from __future__ import annotations

import time
from typing import Dict, List

import numpy as np


class ZipfKeys:
    """Inverse-CDF sampler of a truncated Zipf over ``rows`` ids."""

    def __init__(self, rows: int, zipf_a: float, seed: int):
        ranks = np.arange(1, rows + 1, dtype=np.float64)
        p = ranks ** (-zipf_a)
        p /= p.sum()
        cdf = np.cumsum(p)
        cdf /= cdf[-1]
        self.cdf = cdf
        self.perm = np.random.default_rng(seed).permutation(rows)
        self.rng = np.random.default_rng(seed + 1)

    def draw(self, n: int) -> np.ndarray:
        """``n`` ids (int32)."""
        idx = self.cdf.searchsorted(self.rng.random(n), side="right")
        return self.perm[idx].astype(np.int32)


def exponential_gaps(rate: float, n: int, seed: int) -> np.ndarray:
    """``n`` inter-arrival gaps (s) at ``rate`` per second: the
    distribution's midpoint quantiles, shuffled by ``seed``."""
    q = (np.arange(n, dtype=np.float64) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    return np.random.default_rng([seed, 3]).permutation(gaps)


def request_keys(rows: int, traffic: Dict, n: int, seed: int) -> np.ndarray:
    """(n, keys_per_request) int32 keys of ``n`` requests."""
    k = int(traffic["keys_per_request"])
    keys = ZipfKeys(rows, float(traffic["zipf_a"]), seed).draw(n * k)
    return keys.reshape(n, k)


class Requests:
    """Per-release record of what the harness handed the runtime.

    Release ``i`` is the request with rid ``i``: rids are unique and
    increase.  It takes its keys from row `row_of(i)`: row ``i`` up to the
    last row, then the rows from ``pool_from`` on again, in order, so a
    closed loop that outlasts its pool recycles it.  ``due_ns`` is when the
    release was due (open loop: its scheduled arrival; closed loop: its
    release), on the `time.perf_counter_ns` clock; ``req`` holds the
    objects the runtime stamped on enqueue.  Both grow by doubling, so a
    release costs O(1) amortised."""

    def __init__(self, keys: np.ndarray, pool_from: int = 0):
        self.keys = keys
        self.pool_from = pool_from
        self.pool = keys.shape[0] - pool_from
        self.due_ns = np.full(keys.shape[0], -1, np.int64)
        self.req: List[object] = [None] * keys.shape[0]
        self.issued = 0

    def row_of(self, i: int) -> int:
        """The row of ``keys`` that release ``i`` takes."""
        if i < self.pool_from:
            return i
        return self.pool_from + (i - self.pool_from) % self.pool

    def make(self, i: int, due_ns: int):
        from repro.serve.requests import ServeRequest
        if i >= self.due_ns.size:
            more = max(i + 1, 2 * self.due_ns.size) - self.due_ns.size
            self.due_ns = np.concatenate(
                [self.due_ns, np.full(more, -1, np.int64)])
            self.req.extend([None] * more)
        r = ServeRequest(i, self.keys[self.row_of(i)])
        self.due_ns[i] = due_ns
        self.req[i] = r
        self.issued = max(self.issued, i + 1)
        return r

    def enqueue_ns(self, ids: np.ndarray) -> np.ndarray:
        return np.array([int(self.req[i].t_enqueue * 1e9) for i in ids],
                        np.int64)


class OpenLoop:
    """Stream adapter: every request whose due time has passed, in
    order, on each `arrivals` call (the runtime calls it once a round).
    Due times are ``origin_ns`` plus the cumulative gaps."""

    def __init__(self, requests: Requests, offsets_s: np.ndarray,
                 first: int = 0):
        self.r = requests
        self.offsets_ns = np.round(offsets_s * 1e9).astype(np.int64)
        self.first = first
        self.next = first
        self.origin_ns = None

    def start(self, origin_ns: int) -> None:
        self.origin_ns = origin_ns

    def due_ns(self, i: int) -> int:
        return self.origin_ns + int(self.offsets_ns[i - self.first])

    def arrivals(self, rnd: int):
        now = time.perf_counter_ns() - self.origin_ns
        end = self.first + int(np.searchsorted(self.offsets_ns, now,
                                               side="right"))
        if end > self.r.keys.shape[0]:
            raise RuntimeError("open-loop schedule exhausted")
        out = [self.r.make(i, self.due_ns(i))
               for i in range(self.next, end)]
        self.next = end
        return out


class ClosedLoop:
    """Stream adapter keeping ``outstanding`` requests in the system:
    each one served (the runtime's ``serve.requests`` counter) releases
    the next, from rid ``requests.pool_from`` on.  Past the pool's last
    row the releases recycle the pool (`Requests.row_of`), so a run is
    never cut short by the program's own speed."""

    def __init__(self, requests: Requests, outstanding: int, bus):
        self.r = requests
        self.outstanding = outstanding
        self.bus = bus
        self.released = 0
        self.served0 = self._served()

    def _served(self) -> int:
        return int(self.bus.counter_value("serve.requests",
                                          tenant="default"))

    @property
    def pool_cycles(self) -> int:
        """How many times the releases wrapped past the pool's last row."""
        return max(0, self.released - 1) // self.r.pool

    def arrivals(self, rnd: int):
        n = self.outstanding - (self.released
                                - (self._served() - self.served0))
        now = time.perf_counter_ns()
        first = self.r.pool_from + self.released
        out = [self.r.make(i, now) for i in range(first, first + max(0, n))]
        self.released += len(out)
        return out
