"""The plain references of the ``cluster`` driver, beside
``reference.py`` and like it importing nothing of the program: what a
run of validators as OS processes leaves on disk, read and judged from
the files alone.

- :class:`ClusterKeys` — the committee's keys as the cluster's dealer
  derives them from the layout's seed (seed of index i =
  sha256("dagrider-cluster-<seed>|ed|" + str(i))), derived here
  independently and compared with the key file in set-up.
- :func:`read_delivery_log` — one validator's ``delivery.jsonl``: a JSON
  object a line, a torn last line left out.
- :func:`bad_signatures` — every delivered vertex's signature, verified
  again from the log's own fields.
- :func:`transaction_faults` — the acknowledged transactions against the
  WALs and the longest delivery log.
- :func:`wan_round_floor_ms` — what the configured one-way delays alone
  make a DAG round cost, every processor infinitely fast.
"""

from __future__ import annotations

import hashlib
import json
from typing import Dict, Iterable, List, Mapping, Sequence

from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PrivateKey
from cryptography.hazmat.primitives.serialization import Encoding, PublicFormat

from benchmarks.harness import reference


class ClusterKeys(reference.Keys):
    """``reference.Keys`` under the cluster dealer's derivation."""

    def __init__(self, n: int, cluster_seed: int):
        prefix = f"dagrider-cluster-{cluster_seed}".encode() + b"|ed|"
        self._sk = [
            Ed25519PrivateKey.from_private_bytes(
                hashlib.sha256(prefix + str(i).encode()).digest()
            )
            for i in range(n)
        ]
        self._pk = [sk.public_key() for sk in self._sk]
        self.public = [pk.public_bytes(Encoding.Raw, PublicFormat.Raw) for pk in self._pk]


def read_delivery_log(path: str) -> List[dict]:
    out = []
    try:
        with open(path) as fh:
            for line in fh:
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue  # a torn tail
                if isinstance(rec, dict) and "r" in rec and "s" in rec:
                    out.append(rec)
    except OSError:
        pass
    return out


def edges_of(rec: dict) -> List[tuple]:
    """Strong and weak edges of a delivery record as (round, source)."""
    return [(r, s) for r, s in rec["se"]] + [(r, s) for r, s in rec["we"]]


def bad_signatures(keys: reference.Keys, log: Iterable[dict]) -> int:
    """How many records of ``log`` carry a signature that does not verify
    under the key of the record's source, over the canonical encoding of
    the record's own round, source, transactions, edges and coin share."""
    bad = 0
    for rec in log:
        msg = reference.signing_bytes(
            rec["r"],
            rec["s"],
            [bytes.fromhex(t) for t in rec["tx"]],
            [(r, s) for r, s in rec["se"]],
            [(r, s) for r, s in rec["we"]],
            bytes.fromhex(rec["cs"]),
        )
        if not keys.verify(rec["s"], msg, bytes.fromhex(rec["sig"])):
            bad += 1
    return bad


def transaction_faults(
    acknowledged: Mapping[str, int],
    wals: Sequence[Iterable[str]],
    logs: Sequence[Sequence[dict]],
) -> Dict[str, int]:
    """``acknowledged`` maps a transaction (hex) to the validator that
    acknowledged it, ``wals[i]`` is validator i's WAL as hex lines and
    ``logs[i]`` its delivery records. Durability: an acknowledged
    transaction is in its validator's WAL. Delivery: it is in some
    validator's log, and exactly once in the longest."""
    in_wal = [set(w) for w in wals]
    anywhere: set = set()
    for log in logs:
        for rec in log:
            anywhere.update(rec["tx"])
    times: Dict[str, int] = {}
    for rec in max(logs, key=len) if logs else ():
        for tx in rec["tx"]:
            if tx in acknowledged:
                times[tx] = times.get(tx, 0) + 1
    return {
        "acked_not_in_wal": sum(1 for tx, i in acknowledged.items() if tx not in in_wal[i]),
        "tx_lost": sum(1 for tx in acknowledged if tx not in anywhere),
        "tx_delivered_twice": sum(1 for k in times.values() if k > 1),
    }


def _kth(values: List[float], k: int) -> float:
    return sorted(values)[k - 1]


def wan_round_floor_ms(
    n: int, f: int, regions: Sequence[str], one_way_ms: Mapping[str, Mapping[str, float]],
    rounds: int = 40,
) -> float:
    """The mean time of a DAG round under the delays alone. A vertex
    proposed by s at time t is held by p at t + d(s, p); p has echoed by
    then, sends READY once 2f+1 echoes reached it (or f+1 READYs), and
    delivers once 2f+1 READYs did — three hops. A validator proposes its
    next vertex once 2f+1 vertices of its round are delivered to it, its
    own at once. Run for ``rounds`` rounds from a common start and
    averaged over the second half, over every validator."""

    def d(a: int, b: int) -> float:
        if a == b:
            return 0.0
        ra, rb = regions[a], regions[b]
        ms = one_way_ms.get(ra, {}).get(rb)
        return one_way_ms[rb][ra] if ms is None else ms

    q = 2 * f + 1
    dist = [[d(a, b) for b in range(n)] for a in range(n)]
    # delivery of s's vertex at v, relative to its proposal: the same
    # every round, so worked out once
    rel = [[0.0] * n for _ in range(n)]
    for s in range(n):
        echo_at = [dist[s][p] for p in range(n)]
        by_echo = [_kth([echo_at[e] + dist[e][p] for e in range(n)], q) for p in range(n)]
        ready_at = list(by_echo)
        for _ in range(n):  # amplification: f+1 READYs also make one
            amp = [
                min(ready_at[p], _kth([ready_at[e] + dist[e][p] for e in range(n)], f + 1))
                for p in range(n)
            ]
            if amp == ready_at:
                break
            ready_at = amp
        for v in range(n):
            done = _kth([ready_at[p] + dist[p][v] for p in range(n)], q)
            rel[s][v] = 0.0 if s == v else max(done, echo_at[v])
    start = [0.0] * n
    marks = []
    for _ in range(rounds):
        start = [_kth([start[s] + rel[s][v] for s in range(n)], q) for v in range(n)]
        marks.append(sum(start) / n)
    half = rounds // 2
    return (marks[-1] - marks[half - 1]) / (rounds - half)
