"""Naming the device's idle gaps (``harness/trace.py``): by the innermost
host span over them, the program's own spans among them, in one sweep
that gives what the pairwise reading of every stretch gave.
"""

import os
import random
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import trace  # noqa: E402

MS = 1_000_000  # ns


def named_pairwise(gaps, spans) -> dict:
    """Each gap cut at every span's edge, each stretch given to the
    shortest span that covers it (the first listed of two as long)."""
    into = {}
    for a, b in gaps:
        over = [s for s in spans if s[1] < b and s[1] + s[2] > a]
        cuts = sorted({a, b, *(max(a, s[1]) for s in over),
                       *(min(b, s[1] + s[2]) for s in over)})
        for lo, hi in zip(cuts, cuts[1:]):
            inner = [s for s in over if s[1] <= lo and s[1] + s[2] >= hi]
            name = min(inner, key=lambda s: s[2])[0] if inner else trace.UNANNOTATED
            into[name] = into.get(name, 0.0) + (hi - lo)
    return into


def one_chip(ops, host) -> dict:
    return {"chips": {"/device:TPU:0": {"ops": ops, "modules": []}}, "host": host}


def test_a_gap_under_a_nested_pump_span_is_named_by_the_inner_one():
    # the device runs at 0-1 ms and 9-10 ms; the pump's cycle is open
    # from 1 to 9 and admits vertices from 2 to 6
    events = one_chip(
        [["fusion.1", 0, 1 * MS], ["fusion.1", 9 * MS, 1 * MS]],
        [["pump.run", 1 * MS, 8 * MS], ["pump.insert", 2 * MS, 4 * MS]],
    )
    gaps = dict(trace.reduce(events, 0.010)["idle_gaps"])
    assert gaps["pump.insert"] == pytest.approx(0.004)
    assert gaps["pump.run"] == pytest.approx(0.004)
    assert trace.UNANNOTATED not in gaps


def test_the_program_s_span_layers_name_the_gaps():
    from dag_rider_tpu.obs.spans import KNOWN_SPANS

    layers = {name.split(".", 1)[0] + "." for name in KNOWN_SPANS}
    assert layers <= set(trace.HOST_PREFIXES)
    assert {"bench.", "verify_batch.", "pump.", "coin.", "sign.", "mempool.", "seam.",
            "sidecar.", "remote.", "host."} <= set(trace.HOST_PREFIXES)


@pytest.mark.parametrize("seed", range(12))
def test_the_sweep_names_each_stretch_as_the_pairwise_reading_does(seed):
    rng = random.Random(seed)
    names = ["bench.sim_run", "pump.run", "pump.insert", "coin.share", "sign.vertex"]
    spans = []
    for _ in range(rng.randrange(0, 60)):
        start = rng.randrange(0, 1000)
        spans.append([rng.choice(names), start, rng.choice([0, 1, 5, 20, 100, rng.randrange(400)])])
    cuts = sorted(rng.sample(range(0, 1200), 2 * rng.randrange(1, 8)))
    gaps = [(float(a), float(b)) for a, b in zip(cuts[::2], cuts[1::2])]
    into = {}
    trace._name_gaps(gaps, spans, into)
    want = named_pairwise(gaps, spans)
    assert set(into) == set(want)
    for name, seconds in want.items():
        assert into[name] == pytest.approx(seconds)
    assert sum(into.values()) == pytest.approx(sum(b - a for a, b in gaps))


def test_a_trace_with_the_pumps_many_spans_reduces_in_one_pass():
    """Six seconds of a committee cycle at n=256 hold tens of thousands
    of the program's spans over a handful of long gaps."""
    host, at = [], 0
    for cycle in range(8):
        host.append(["pump.run", at, 700 * MS])
        for view in range(256):
            t = at + view * 2 * MS + 1
            host += [["pump.inbox", t, MS // 2], ["pump.insert", t + MS // 2, MS // 4],
                     ["pump.propose", t + MS, MS // 2], ["sign.vertex", t + MS, MS // 8],
                     ["coin.share", t + 3 * MS // 2, MS // 8]]
        at += 750 * MS
    ops = [["fusion.1", k * 750 * MS + 710 * MS, 5 * MS] for k in range(8)]
    t0 = time.monotonic()
    r = trace.reduce(one_chip(ops, host), 6.0)
    assert time.monotonic() - t0 < 30
    gaps = dict(r["idle_gaps"])
    # per cycle: 256 views' 0.5 ms of inbox, the pump's own 348 ms
    assert gaps["pump.inbox"] == pytest.approx(8 * 256 * 0.0005)
    assert gaps["pump.run"] == pytest.approx(8 * (256 * 0.000625 + 0.188))
    assert {"pump.insert", "pump.propose", "sign.vertex", "coin.share"} <= set(gaps)
    assert sum(gaps.values()) + r["busy_s"] == pytest.approx(6.0)
