"""From the profiler's trace to numbers: device busy and idle time, the
operations and programs that took it, and the idle gaps named by what
the host was doing.

Two steps, so that the arithmetic can be checked on a small recorded
trace without a profiler: :func:`extract` reads an ``.xplane.pb`` into
plain lists of ``[name, start_ns, duration_ns]``; :func:`reduce` does
everything else.
"""

from __future__ import annotations

import glob
import heapq
import os
import shutil
import time
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]


def _program_prefixes() -> Tuple[str, ...]:
    """The layers of every span the program may open (``<layer>.``, from
    ``obs.spans.KNOWN_SPANS``); none from a program without them."""
    try:
        from dag_rider_tpu.obs.spans import KNOWN_SPANS
    except ImportError:
        return ()
    return tuple(sorted({name.split(".", 1)[0] + "." for name in KNOWN_SPANS}))


#: host spans the gaps are named by: the ones the benchmark's drivers put
#: around their own calls, the verify seam's, and every span the program
#: opens (``pump.``, ``coin.``, ``sign.``, ``sidecar.``, ...)
HOST_PREFIXES = tuple(dict.fromkeys(("bench.", "verify_batch.") + _program_prefixes()))
BETWEEN_OPS = "device:between_ops_of_a_program"
UNANNOTATED = "host:unannotated"


def short(name: str) -> str:
    """An operation's own name out of the HLO text the trace names it by
    ("%fusion.1 = s32[...] fusion(...)" -> "fusion.1")."""
    return name.split(" = ", 1)[0].lstrip("%")


def extract(xplane_path: str) -> dict:
    """Device operations and programs per chip, and the host's named
    spans, from one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    chips: Dict[str, dict] = {}
    host: List[list] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU"):
            chip = chips.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                if line.name == "XLA Ops":
                    into = chip["ops"]
                elif line.name == "XLA Modules":
                    into = chip["modules"]
                else:
                    continue
                into.extend([short(e.name), e.start_ns, e.duration_ns] for e in line.events)
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host.extend(
                    [e.name, e.start_ns, e.duration_ns]
                    for e in line.events
                    if e.name.startswith(HOST_PREFIXES)
                )
    return {"chips": chips, "host": host}


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def total(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def complement(covered: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """[lo, hi] minus a sorted disjoint cover."""
    out, at = [], lo
    for a, b in covered:
        if a > at:
            out.append((at, min(a, hi)))
        at = max(at, b)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return [(a, b) for a, b in out if b > a]


def _name_gaps(gaps: Sequence[Interval], spans: Sequence[list], into: Dict[str, float]) -> None:
    """Share sorted, disjoint idle gaps out among the host spans that
    cover them, each stretch going to the innermost (shortest) span over
    it, the earlier-listed of two as long; one sweep over the spans by
    their start, so that a trace with tens of thousands of the program's
    spans reduces in one pass."""
    order = sorted(range(len(spans)), key=lambda i: spans[i][1])
    nxt = 0
    #: (duration, index, end) of the spans started so far; one that has
    #: ended leaves only when it comes to the top
    open_: List[tuple] = []
    for a, b in gaps:
        at = a
        while at < b:
            while nxt < len(order) and spans[order[nxt]][1] <= at:
                i = order[nxt]
                heapq.heappush(open_, (spans[i][2], i, spans[i][1] + spans[i][2]))
                nxt += 1
            while open_ and open_[0][2] <= at:
                heapq.heappop(open_)
            until = b
            if nxt < len(order):
                until = min(until, spans[order[nxt]][1])
            if open_:
                until = min(until, open_[0][2])
                name = spans[open_[0][1]][0]
            else:
                name = UNANNOTATED
            into[name] = into.get(name, 0.0) + (until - at)
            at = until


def _top(book: Dict[str, float], k: int = 10) -> List[list]:
    return [[n, s * 1e-9] for n, s in sorted(book.items(), key=lambda kv: -kv[1])[:k]]


def reduce(events: dict, window_s: float) -> Optional[dict]:
    """Busy seconds (the union of the intervals in which an operation
    ran, averaged over the chips), the traced window's length, the ten
    operations with most device time, the idle time by what the host was
    doing, and every program's runs. None when no operation ran."""
    chips = [c for c in events["chips"].values() if c["ops"]]
    if not chips:
        return None
    busy_ns = 0.0
    op_book: Dict[str, float] = {}
    gap_book: Dict[str, float] = {}
    programs: Dict[str, List[float]] = {}
    host = events["host"]
    for chip in chips:
        busy = union([(s, s + d) for _, s, d in chip["ops"]])
        busy_ns += total(busy)
        for name, _, d in chip["ops"]:
            op_book[name] = op_book.get(name, 0.0) + d
        for name, _, d in chip["modules"]:
            programs.setdefault(name, []).append(d * 1e-9)
    # gaps are named on the first chip: one host drives them all
    first = chips[0]
    busy = union([(s, s + d) for _, s, d in first["ops"]])
    lo = min([busy[0][0]] + [s for _, s, _ in host])
    hi = max(lo + window_s * 1e9, busy[-1][1])
    inside = union([(s, s + d) for _, s, d in first["modules"]]) or busy
    between = total(complement(busy, lo, hi)) - total(complement(inside, lo, hi))
    if between > 0:
        gap_book[BETWEEN_OPS] = between
    _name_gaps(complement(inside, lo, hi), host, gap_book)
    return {
        "busy_s": busy_ns * 1e-9 / len(chips),
        "window_s": window_s,
        "device_ops": [[n, s / len(chips)] for n, s in _top(op_book)],
        "idle_gaps": _top(gap_book),
        "programs": programs,
    }


def program_seconds(trace: Optional[dict], needle: str) -> List[float]:
    """Device seconds of every traced run of the programs whose name
    holds ``needle``."""
    if not trace:
        return []
    return [d for name, ds in trace["programs"].items() if needle in name for d in ds]


def idle_pct(trace: Optional[dict]) -> Optional[float]:
    """1 - busy over the traced window, in percent."""
    if not trace:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])


class WindowTracer:
    """Traces the last ``for_s`` seconds of a window: the driver calls
    :meth:`tick` with the window's elapsed seconds each cycle and
    :meth:`stop` when the window has closed."""

    def __init__(self, out_dir: str, window_s: float, for_s: float):
        self.out_dir = out_dir
        self.start_at = max(0.0, window_s - for_s)
        self.started: Optional[float] = None
        self.stopped: Optional[float] = None

    def tick(self, t: float) -> None:
        if self.started is None and t >= self.start_at:
            import jax

            shutil.rmtree(self.out_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # the pump's Python frames would drown it
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.out_dir, profiler_options=opts)
            self.started = time.monotonic()

    def stop(self) -> None:
        if self.started is not None and self.stopped is None:
            import jax

            self.stopped = time.monotonic()
            jax.profiler.stop_trace()

    def reduce(self) -> Optional[dict]:
        """The reduced trace; the trace's files are removed."""
        if self.started is None or self.stopped is None:
            return None
        try:
            paths = glob.glob(
                os.path.join(self.out_dir, "plugins", "profile", "*", "*.xplane.pb")
            )
            if not paths:
                return None
            return reduce(extract(paths[0]), self.stopped - self.started)
        finally:
            shutil.rmtree(self.out_dir, ignore_errors=True)
