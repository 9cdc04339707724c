"""The plain reference's part for a committee some of whose validators
have crashed (driver ``cluster_crash``), beside ``reference_cluster.py``
and like it importing nothing of the program: what the run left on
disk, read and judged from the files alone.

- :func:`last_proposed_round` — the highest round a validator's event
  log shows it advanced to. A runner writes the ``round_advance`` line,
  line-buffered, before it makes and broadcasts that round's vertex, so
  no vertex of a round above it ever left the validator.
- :func:`stamps_after` — lines of a log (events or deliveries) stamped
  later than a wall time: what a validator killed at that time cannot
  have written.
- :func:`crashed_still_running` — victims that were alive after their
  kill: a process still running, a clean stop's report, or a line
  stamped after the kill.
- :func:`delivered_from_the_dead` — delivered vertices under a crashed
  validator's name above the last round it proposed.
- :func:`wan_round_floor_ms` — ``reference_cluster.wan_round_floor_ms``
  over a live set: a dead validator sends nothing, echoes nothing and
  proposes nothing, and the quorums stay 2f+1 of n.
"""

from __future__ import annotations

import json
from typing import Iterable, List, Mapping, Optional, Sequence


def _records(path: str) -> Iterable[dict]:
    try:
        with open(path) as fh:
            for line in fh:
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue  # a torn tail
                if isinstance(rec, dict):
                    yield rec
    except OSError:
        return


def last_proposed_round(events_log: str) -> int:
    """0 where the log shows no round: genesis is nobody's proposal."""
    return max(
        (rec.get("round", 0) for rec in _records(events_log)
         if rec.get("event") == "round_advance"),
        default=0,
    )


def stamps_after(path: str, wall: float) -> int:
    return sum(1 for rec in _records(path) if rec.get("ts", 0.0) > wall)


def crashed_still_running(victims: Sequence[Mapping]) -> int:
    """``victims``: one mapping a crashed validator — ``killed_at`` (the
    wall time by which its SIGKILL had been waited for; None if it was
    never killed), ``alive`` (its process was still running when the
    window opened), ``final_report`` (it left a clean stop's report) and
    ``late_lines`` (:func:`stamps_after` the kill, over its event and
    delivery logs). Counts the victims that any of these shows alive
    after the kill."""
    return sum(
        1 for v in victims
        if v["killed_at"] is None or v["alive"] or v["final_report"] or v["late_lines"]
    )


def delivered_from_the_dead(log: Sequence[Mapping], last_proposed: Mapping[int, int]) -> int:
    """``log``: delivery records (``r``, ``s``); ``last_proposed``: for
    each crashed validator the last round its own event log shows it
    proposed. Counts the records of a crashed source above that round."""
    return sum(
        1 for rec in log if rec["s"] in last_proposed and rec["r"] > last_proposed[rec["s"]]
    )


def _kth(values: List[float], k: int) -> float:
    return sorted(values)[k - 1]


def wan_round_floor_ms(
    n: int,
    f: int,
    regions: Sequence[str],
    one_way_ms: Mapping[str, Mapping[str, float]],
    live: Optional[Sequence[int]] = None,
    rounds: int = 40,
) -> float:
    """The mean time of a DAG round under the delays alone, over the
    validators in ``live`` (all n where None). A vertex proposed by s at
    time t is held by p at t + d(s, p); p has echoed by then, sends
    READY once 2f+1 echoes reached it (or f+1 READYs), and delivers once
    2f+1 READYs did. A validator proposes its next vertex once 2f+1
    vertices of its round are delivered to it, its own at once. Only the
    live send, echo, ready and propose; 2f+1 and f+1 are n's. Run for
    ``rounds`` rounds from a common start and averaged over the second
    half, over every live validator."""
    alive = list(range(n)) if live is None else sorted(live)
    q = 2 * f + 1
    if len(alive) < q:
        raise ValueError(f"{len(alive)} live validators make no quorum of {q}")

    def d(a: int, b: int) -> float:
        if a == b:
            return 0.0
        ra, rb = regions[a], regions[b]
        ms = one_way_ms.get(ra, {}).get(rb)
        return one_way_ms[rb][ra] if ms is None else ms

    dist = {a: {b: d(a, b) for b in alive} for a in alive}
    rel = {s: {} for s in alive}  # delivery of s's vertex at v, after its proposal
    for s in alive:
        echo_at = {p: dist[s][p] for p in alive}
        ready_at = {p: _kth([echo_at[e] + dist[e][p] for e in alive], q) for p in alive}
        for _ in range(n):  # amplification: f+1 READYs also make one
            amp = {
                p: min(ready_at[p], _kth([ready_at[e] + dist[e][p] for e in alive], f + 1))
                for p in alive
            }
            if amp == ready_at:
                break
            ready_at = amp
        for v in alive:
            done = _kth([ready_at[p] + dist[p][v] for p in alive], q)
            rel[s][v] = 0.0 if s == v else max(done, echo_at[v])
    start = {v: 0.0 for v in alive}
    marks = []
    for _ in range(rounds):
        start = {v: _kth([start[s] + rel[s][v] for s in alive], q) for v in alive}
        marks.append(sum(start.values()) / len(alive))
    half = rounds // 2
    return (marks[-1] - marks[half - 1]) / (rounds - half)
