"""Seeded open-loop arrival schedules — the one general generator every
open-loop traffic file is read by. Copied from the program's
``mempool/loadgen.py`` ``LoadGenerator`` so that no later PR can change
the traffic a cell offers (the original is listed in PERF.md for a later
PR to delete).

A traffic file gives ``clients``, ``rate_tx_per_s``, ``tx_bytes`` and
``profile`` (``poisson``, ``burst`` or ``uniform``; the burst profile
also ``burst_factor``, ``burst_every_s``, ``burst_len_s``). Arrivals come
from the seed alone; the program is handed only the payloads."""

from __future__ import annotations

import heapq
import random
from typing import List, Tuple

PROFILES = ("poisson", "burst", "uniform")


class LoadGenerator:
    """``rate`` is the total offered tx/s, split evenly over ``clients``;
    the burst profile multiplies each client's rate by ``burst_factor``
    during ``burst_len_s`` of every ``burst_every_s``."""

    def __init__(
        self,
        *,
        clients: int,
        rate: float,
        tx_bytes: int = 32,
        seed: int = 0,
        profile: str = "poisson",
        burst_factor: float = 8.0,
        burst_every_s: float = 1.0,
        burst_len_s: float = 0.25,
    ) -> None:
        if clients < 1:
            raise ValueError(f"clients must be >= 1, got {clients}")
        if rate <= 0:
            raise ValueError(f"rate must be > 0, got {rate}")
        if profile not in PROFILES:
            raise ValueError(f"profile must be one of {PROFILES}, got {profile!r}")
        self.clients = clients
        self.rate = rate
        self.tx_bytes = tx_bytes
        self.seed = seed
        self.profile = profile
        self.burst_factor = burst_factor
        self.burst_every_s = burst_every_s
        self.burst_len_s = burst_len_s
        self._rngs = [
            random.Random((seed << 20) ^ (c * 2654435761)) for c in range(clients)
        ]
        self._seq = [0] * clients
        self.emitted = 0
        #: (next arrival time, client) min-heap
        self._next: List[Tuple[float, int]] = [
            (self._gap(c, 0.0), c) for c in range(clients)
        ]
        heapq.heapify(self._next)

    @classmethod
    def from_traffic(cls, traffic: dict, seed: int) -> "LoadGenerator":
        extra = {
            k: traffic[k]
            for k in ("burst_factor", "burst_every_s", "burst_len_s")
            if k in traffic
        }
        return cls(
            clients=traffic["clients"],
            rate=traffic["rate_tx_per_s"],
            tx_bytes=traffic["tx_bytes"],
            profile=traffic["profile"],
            seed=seed,
            **extra,
        )

    def _client_rate(self, t: float) -> float:
        r = self.rate / self.clients
        if self.profile == "burst" and (t % self.burst_every_s) < self.burst_len_s:
            r *= self.burst_factor
        return r

    def _gap(self, c: int, t: float) -> float:
        r = self._client_rate(t)
        if self.profile == "uniform":
            return 1.0 / r
        return self._rngs[c].expovariate(r)

    def _payload(self, c: int) -> bytes:
        self._seq[c] += 1
        head = f"s{self.seed}c{c}-{self._seq[c]:08d}".encode()
        return head.ljust(self.tx_bytes, b".")

    def events_until(self, t: float) -> List[Tuple[float, int, bytes]]:
        """Pop every arrival due at or before ``t`` as (due, client,
        payload); call with non-decreasing ``t``."""
        out: List[Tuple[float, int, bytes]] = []
        while self._next[0][0] <= t:
            ts, c = heapq.heappop(self._next)
            out.append((ts, c, self._payload(c)))
            self.emitted += 1
            heapq.heappush(self._next, (ts + self._gap(c, ts), c))
        return out
