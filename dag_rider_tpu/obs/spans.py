"""The one span primitive: ``with obs.span(name):`` and ``obs.count(name)``.

One pair of ``perf_counter_ns`` reads feeds two sinks:

- **The book**, always on and process-wide: per name ``count``,
  ``total_ns``, ``max_ns`` and ``child_ns`` — the part of the name's
  time that spans opened inside it, on the same thread, covered — so a
  name's self time is ``total_ns - child_ns``. Each thread writes its
  own share and :func:`snapshot` adds the shares up, so two threads
  closing the same name lose no count and the hot path takes no lock.
- **The timeline**, only while a ``jax.profiler`` session is open
  (``TraceAnnotation.is_enabled()``): the span also enters a
  ``TraceAnnotation(name)`` and lands in the ``.xplane.pb`` host plane,
  on the device trace's own clock. The profiler's timeline is relative
  to the session's start, not to any clock Python can read, so an
  annotation is the only stamp that sits beside the device's operations
  without a conversion. jax is never imported from here: a process that
  has not loaded it has no session to write to.

After exit a span carries its duration (``span.ns`` / ``span.seconds``):
the event log's ``phase_*`` events and the ``utils.metrics`` gauges take
theirs from it, so every interval is timed once.

There is no knob. With no profiler session a span is two clock reads,
one ``is_enabled()`` and a handful of integer adds.
"""

from __future__ import annotations

import gc
import sys
import threading
from time import perf_counter_ns
from typing import Dict, List, Optional

#: the spans' clock, for the two stamps of a :func:`record`
clock_ns = perf_counter_ns

#: Every span name the package may open or record, ``<layer>.<phase>``
#: (driderlint's spans checker holds literal names to this set, like
#: ``slog.KNOWN_EVENTS``: a typo'd name makes a record no metric reads).
KNOWN_SPANS = frozenset(
    {
        # consensus/simulator.py — one cycle of Simulation.run
        "pump.run",
        "pump.deliver",
        "pump.collect",
        "pump.verify",
        "pump.apply",
        "pump.step",
        # consensus/process.py — one pass of Process.step
        "pump.inbox",
        "pump.cert",
        "pump.insert",
        "pump.propose",
        "pump.wave",
        "pump.chain",
        # how many waves one commit closed (itself and the undecided
        # waves its leader chain walked back over): a length booked
        # through record() for its count, sum and max — not a time
        "pump.chain_waves",
        "pump.order",
        "pump.prune",
        "pump.sync",
        "coin.share",
        "coin.combine",
        "sign.vertex",
        # verifier/pipeline.py, verifier/tpu.py — the verify seam
        "seam.window",
        "seam.overlap",
        "verify_batch.prepare",
        "verify_batch.dispatch",
        "verify_batch.resolve",
        # verifier/sidecar.py — the served handler
        "sidecar.rpc",
        "sidecar.decode",
        "sidecar.between_rpcs",
        # verifier/sidecar.py — one served VerifyCerts call, and the
        # decoding of its certificates
        "sidecar.cert_rpc",
        "sidecar.cert_decode",
        # verifier/cert.py CertBackend — one certificate's check: the
        # digests' H(d) (host SHA, the device's square roots and
        # cofactor ladder), the Miller program's dispatch to its product
        # on the host, and the host's final exponentiation
        "cert.hash",
        "cert.miller",
        "cert.final_exp",
        # mempool/ — submit -> vertex, one closed span per block
        "mempool.wait",
        # node.py — one pass of the validator's loop (its idle sleep
        # left out)
        "node.tick",
        # the periodic checkpoint the same thread writes between passes
        # (three files and a manifest, an fsync each)
        "node.checkpoint",
        # transport/net.py — a logical broadcast on the caller's thread
        # (encode, MAC, WAN verdict, hand-over), one network attempt
        # handed to gRPC (a frame, or the frames for one peer that fell
        # due together), one received frame (MAC check, decode, inbox),
        # and how long a held frame really waited
        "net.broadcast",
        "net.send",
        "net.recv",
        "net.delay",
        # transport/rbc.py — one received frame of reliable broadcast,
        # by kind (the upward delivery and the votes it sends included)
        "rbc.val",
        "rbc.echo",
        "rbc.ready",
        # cluster/runner.py — acknowledged transactions into the WAL
        "wal.append",
        # verifier/sidecar.py — one RemoteVerifier.verify_batch, encode
        # to mask
        "remote.verify",
        # generation-2 collections of the interpreter's garbage
        "host.gc",
    }
)

#: Every counter :func:`count` may bump.
KNOWN_COUNTS = frozenset(
    {
        "pump.round_advance",
        # transport/net.py — frames handed to gRPC (a ``net.send`` may
        # carry several)
        "net.messages",
        # verifier/tpu.py — objects TPUVerifier.warmup took out of the
        # collector's reach after compiling its program
        "heap.frozen_objects",
        # verifier/sidecar.py — vertices a request's frames decoded to,
        # and core/types.py — those of any decoder's whose packed edge
        # lists someone read, and so became tuples of ids
        "sidecar.vertices_decoded",
        "codec.edges_unpacked",
        # verifier/sidecar.py — bytes of the requests the handler took
        # (a whole round an RPC: ~5.6 KB a vertex at n=1,024)
        "sidecar.request_bytes",
        # verifier/tpu.py — bytes of the comb tables on the device (every
        # key's and the base point's), once where they are built
        "verifier.table_bytes",
        # verifier/tpu.py — a comb program compiled from the lowered
        # program stored beside the compile cache, and one traced and
        # lowered because none was stored there (or it would not load)
        "verifier.program_loaded",
        "verifier.program_lowered",
        # mempool/ — blocks cut for a vertex that was being made (a
        # proposer's ``block_source``), and blocks cut ahead of one
        # (``build_blocks``, for a caller that stages them)
        "mempool.cut_at_propose",
        "mempool.cut_ahead",
        # consensus/process.py — a wave tried at its last round that
        # committed its leader, and one that did not (no leader vertex,
        # or no quorum of votes); a catch-up request sent
        "pump.wave_commit",
        "pump.wave_skip",
        "pump.sync_request",
        # consensus/process.py — vertices the buffer drain admitted into
        # the DAG: by the round-batched drain (whole round groups, one
        # insert_many), and by the scalar walk that is its oracle
        "pump.admit_batched",
        "pump.admit_scalar",
        # transport/net.py — a failed attempt put back for another try;
        # the failure detector reporting a peer down; a frame handed to
        # a sender for a peer it holds down (a probe, since the rest of
        # that peer's frames are dropped before they cost anything)
        "net.retry",
        "net.peer_down",
        "net.to_down_peer",
        # verifier/base.py — a vertex VertexSigner signed through
        # libcrypto's Ed25519, and one it signed in pure Python
        "sign.native",
        "sign.python",
        # verifier/cert.py CertBackend — pairs a certificate check
        # multiplied (padding left out), those of them the host oracle
        # took instead of the device program, and the bytes of the
        # committee's line tables on the device, once where they are built
        "cert.pairs",
        "cert.host_fallback",
        "cert.table_bytes",
    }
)


class _Share:
    """One thread's share of the book."""

    __slots__ = ("spans", "counts", "covered", "gc")

    def __init__(self) -> None:
        #: name -> [count, total_ns, max_ns, child_ns]
        self.spans: Dict[str, List[int]] = {}
        self.counts: Dict[str, int] = {}
        #: ns of the spans closed so far inside the innermost open one
        self.covered = 0
        self.gc: Optional["span"] = None


#: every thread's share; appended to (atomic), never removed from
_shares: List[_Share] = []


class _Local(threading.local):
    def __init__(self) -> None:  # once per thread, at its first span
        self.share = _Share()
        _shares.append(self.share)


_local = _Local()


def snapshot() -> dict:
    """The book over every thread so far, as plain dicts:
    ``{"spans": {name: {count, total_ns, max_ns, child_ns}},
    "counts": {name: n}}``."""
    spans: Dict[str, List[int]] = {}
    counts: Dict[str, int] = {}
    for share in list(_shares):
        for name, stat in list(share.spans.items()):
            c, total, longest, child = tuple(stat)
            into = spans.setdefault(name, [0, 0, 0, 0])
            into[0] += c
            into[1] += total
            into[2] = max(into[2], longest)
            into[3] += child
        for name, n in list(share.counts.items()):
            counts[name] = counts.get(name, 0) + n
    keys = ("count", "total_ns", "max_ns", "child_ns")
    return {
        "spans": {name: dict(zip(keys, s)) for name, s in spans.items()},
        "counts": counts,
    }


_annotation = None


def _find_annotation():
    """``jax.profiler.TraceAnnotation``, or None while that module is
    still half imported."""
    global _annotation
    _annotation = getattr(
        sys.modules["jax.profiler"], "TraceAnnotation", None
    )
    return _annotation


class span:
    """``with span("layer.phase") as s: ...`` — then ``s.ns`` /
    ``s.seconds`` hold what the block took."""

    __slots__ = ("name", "ns", "_t0", "_outer", "_share", "_ann")

    def __init__(self, name: str) -> None:
        self.name = name

    def __enter__(self) -> "span":
        share = self._share = _local.share
        # what the enclosing span had covered so far waits here while
        # this one's own children add up from zero
        self._outer = share.covered
        share.covered = 0
        ann = _annotation
        if ann is None and "jax.profiler" in sys.modules:
            ann = _find_annotation()
        if ann is not None and ann.is_enabled():
            ann = self._ann = ann(self.name)
            ann.__enter__()
        else:
            self._ann = None
        self._t0 = perf_counter_ns()
        return self

    def __exit__(self, et, ev, tb) -> None:
        ns = self.ns = perf_counter_ns() - self._t0
        if self._ann is not None:
            self._ann.__exit__(et, ev, tb)
        share = self._share
        child_ns = share.covered
        share.covered = self._outer + ns
        stat = share.spans.get(self.name)
        if stat is None:
            share.spans[self.name] = [1, ns, ns, child_ns]
            return
        stat[0] += 1
        stat[1] += ns
        if ns > stat[2]:
            stat[2] = ns
        stat[3] += child_ns

    @property
    def seconds(self) -> float:
        return self.ns * 1e-9


def record(name: str, ns: int) -> None:
    """Book a span that is already closed — a wait measured between two
    stamps (``sidecar.between_rpcs``, ``mempool.wait``). It covers no
    time of the calling thread, so it nests under nothing and is not on
    the timeline."""
    stat = _local.share.spans.setdefault(name, [0, 0, 0, 0])
    stat[0] += 1
    stat[1] += ns
    if ns > stat[2]:
        stat[2] = ns


def count(name: str, by: int = 1) -> None:
    counts = _local.share.counts
    counts[name] = counts.get(name, 0) + by


def _on_gc(phase: str, info: dict) -> None:
    if info["generation"] != 2:
        return
    share = _local.share
    if phase == "start":
        share.gc = span("host.gc")
        share.gc.__enter__()
    elif share.gc is not None:
        share.gc.__exit__(None, None, None)
        share.gc = None


def watch_gc() -> None:
    """Time the interpreter's full (generation-2) collections as
    ``host.gc``, nested under whatever span one interrupts. Installs
    once however often it is called."""
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)
