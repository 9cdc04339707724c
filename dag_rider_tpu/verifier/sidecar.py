"""gRPC Verifier sidecar — the north star's deployment shape.

BASELINE.json: "The TPU Verifier impl ships whole-round vertex batches
over gRPC to a JAX sidecar that runs vmap'd Ed25519 ... batch-verify".
Two halves:

- :class:`VerifierSidecarServer` — hosts any Verifier backend (normally
  :class:`~dag_rider_tpu.verifier.tpu.TPUVerifier` pinned to the chip)
  behind the unary method ``/dagrider.Verifier/VerifyBatch``, and a
  round-certificate backend (:class:`~dag_rider_tpu.verifier.cert.CertBackend`)
  behind ``/dagrider.Verifier/VerifyCerts``, on the same worker;
- :class:`RemoteVerifier` — a drop-in Verifier whose ``verify_batch``
  round-trips the batch to the sidecar;
- :class:`RemoteCertVerifier` — a drop-in ``CertVerifier`` whose round
  certificates are checked by the sidecar.

Wire format (no protobuf codegen in the image — generic byte handlers,
like transport/net.py): request = concatenated length-prefixed frames of
codec-encoded vertices (``VerifyCerts``: of codec-encoded certificates,
``codec.encode_certificates``); response = one byte per vertex or
certificate (0x00/0x01 mask). The mask therefore stays byte-identical
across in-process CPU, in-process TPU, and remote-TPU verifier
placements.
"""

from __future__ import annotations

import random
import threading
import time
from typing import List, Optional, Sequence

import grpc

from dag_rider_tpu import obs
from dag_rider_tpu.core import codec
from dag_rider_tpu.core.types import RoundCertificate, Vertex
from dag_rider_tpu.verifier.base import KeyRegistry, Verifier, VerifierUnavailableError
from dag_rider_tpu.verifier.cert import CertVerifier

_METHOD = "/dagrider.Verifier/VerifyBatch"
_CERT_METHOD = "/dagrider.Verifier/VerifyCerts"
_identity = lambda b: b  # noqa: E731

#: Largest request or reply either end accepts, in bytes. gRPC's own
#: default (4 MiB) refuses a whole round of a 1,024-validator committee
#: (5.7 MB: 683 strong edges of 8 bytes a vertex). A round is one RPC,
#: so the ceiling is sized for the largest the repo names — n=1,024,
#: each vertex with 683 strong and up to 341 weak edges (8 KiB) and a
#: block of the mempool's default 8 KiB, ~17 MB in all — with ~4x room.
#: A request over it is a transport fault: the client's send or the
#: server's receive fails, and the batch reads all-invalid (fail-closed).
MAX_MESSAGE_BYTES = 64 * 1024 * 1024
_MESSAGE_OPTIONS = (
    ("grpc.max_receive_message_length", MAX_MESSAGE_BYTES),
    ("grpc.max_send_message_length", MAX_MESSAGE_BYTES),
)


def _encode_batch(vertices: Sequence[Vertex]) -> bytes:
    return b"".join(codec.frame(codec.encode_vertex(v)) for v in vertices)


def _decode_batch(payload: bytes) -> List[Vertex]:
    out: List[Vertex] = []
    offset = 0
    while offset < len(payload):
        item = codec.read_frame(payload, offset)
        if item is None:
            raise ValueError("truncated batch frame")
        blob, offset = item
        out.append(codec.decode_vertex(blob)[0])
    obs.count("sidecar.vertices_decoded", len(out))
    return out


class _VerifyHandler(grpc.GenericRpcHandler):
    def __init__(self, backend: Optional[Verifier], certs=None):
        self._backend = backend
        self._certs = certs
        #: when the last ``unary`` returned, on the spans' clock; the
        #: server has one worker thread, so from there to the next entry
        #: is gRPC's share and nothing else (``sidecar.between_rpcs``)
        self._left_ns: Optional[int] = None

    def service(self, handler_call_details):
        method = handler_call_details.method
        if method == _METHOD and self._backend is not None:
            serve, rpc_span = self._serve_batch, "sidecar.rpc"
        elif method == _CERT_METHOD and self._certs is not None:
            serve, rpc_span = self._serve_certs, "sidecar.cert_rpc"
        else:
            return None

        def unary(request: bytes, context) -> bytes:
            if self._left_ns is not None:
                obs.spans.record(
                    "sidecar.between_rpcs",
                    obs.spans.clock_ns() - self._left_ns,
                )
            obs.count("sidecar.request_bytes", len(request))
            try:
                with obs.span(rpc_span):
                    return serve(request, context)
            finally:
                self._left_ns = obs.spans.clock_ns()

        return grpc.unary_unary_rpc_method_handler(
            unary, request_deserializer=_identity, response_serializer=_identity
        )

    def _serve_batch(self, request: bytes, context) -> bytes:
        try:
            with obs.span("sidecar.decode"):
                batch = _decode_batch(request)
        except ValueError:
            context.abort(grpc.StatusCode.INVALID_ARGUMENT, "malformed batch")
        mask = self._backend.verify_batch(batch)
        return bytes(1 if ok else 0 for ok in mask)

    def _serve_certs(self, request: bytes, context) -> bytes:
        try:
            with obs.span("sidecar.cert_decode"):
                certs = codec.decode_certificates(request)
        except ValueError:
            context.abort(
                grpc.StatusCode.INVALID_ARGUMENT, "malformed certificate batch"
            )
        verdicts = self._certs.verify_certs(certs)
        return bytes(1 if ok else 0 for ok in verdicts)


class VerifierSidecarServer:
    """Hosts a Verifier backend on an insecure local port (the sidecar
    lives on the same machine/pod as the consensus host; transport auth is
    a deployment concern layered via gRPC creds if needed)."""

    def __init__(
        self,
        backend: Optional[Verifier],
        listen_addr: str = "127.0.0.1:0",
        *,
        prep_workers: Optional[int] = None,
        cert_backend=None,
    ):
        """``backend`` serves ``VerifyBatch`` and ``cert_backend`` (a
        :class:`~dag_rider_tpu.verifier.cert.CertBackend`) ``VerifyCerts``;
        either may be None, and a method without a backend answers
        UNIMPLEMENTED."""
        from concurrent import futures

        # Parallel host-prep engine (verifier/prep.py): an explicit
        # worker count overrides the backend's env-derived default, set
        # before warmup so the first prep builds the right pool.
        if prep_workers is not None and hasattr(backend, "prep_workers"):
            backend.prep_workers = int(prep_workers)
        # Device-backed sidecars fix their bucket and compile its
        # program BEFORE the port opens (TPUVerifier.warmup): the first
        # VerifyBatch RPC never eats a cold XLA compile, a program the
        # chip refuses fails the start-up, and no RPC — whose handler
        # would turn an exception into a status the client retries —
        # ever compiles. Host-only backends (CPUVerifier oracle) have no
        # warmup — no jax import.
        self.warmup_compile_s = 0.0
        for hosted in (backend, cert_backend):
            if hasattr(hosted, "warmup"):
                self.warmup_compile_s += hosted.warmup()
        # full collections from here on walk what the handler makes
        # (the heap the program's tracing left was frozen by warmup);
        # they keep their name so that a heap that grows back shows
        obs.spans.watch_gc()
        # one worker: device dispatches serialize anyway, and a single
        # thread keeps per-backend batching deterministic.
        self._server = grpc.server(
            futures.ThreadPoolExecutor(max_workers=1), options=_MESSAGE_OPTIONS
        )
        self._server.add_generic_rpc_handlers(
            (_VerifyHandler(backend, cert_backend),)
        )
        self.bound_port = self._server.add_insecure_port(listen_addr)
        self._server.start()

    @property
    def address(self) -> str:
        return f"127.0.0.1:{self.bound_port}"

    def stop(self) -> None:
        self._server.stop(grace=None)


class RemoteVerifier(Verifier):
    """Verifier seam implementation that defers to a sidecar.

    Fail-closed **per attempt** (SURVEY.md D10: signatures before any
    state change): a transport failure — RPC error, deadline, or a
    malformed/mis-sized reply — must never admit a vertex. What happens
    after a failed attempt is configurable:

    - ``retries`` > 0 re-sends the same payload with exponential backoff
      plus seeded jitter, reconnecting the channel between attempts (a
      restarted sidecar gets a fresh connection instead of a wedged one);
    - once every attempt has failed, the default is the pre-round-9
      contract — the whole batch reads ``[False] * n``, indistinguishable
      from n invalid signatures at the mask level (the
      ``sidecar_rpc_failures`` counter is what tells the two apart in
      metrics);
    - with ``raise_on_unavailable=True`` exhaustion raises
      :class:`VerifierUnavailableError` instead, so a degradation ladder
      (verifier/resilient.py) can hand the batch to its next tier rather
      than permanently rejecting valid vertices on a sidecar blip.

    Either way no attempt ever accepts a vertex it could not check.
    """

    def __init__(
        self,
        address: str,
        *,
        timeout: float = 30.0,
        retries: int = 0,
        backoff_s: float = 0.05,
        backoff_cap_s: float = 2.0,
        jitter: float = 0.5,
        seed: int = 0,
        raise_on_unavailable: bool = False,
    ):
        self._address = address
        self._timeout = timeout
        self._retries = max(0, int(retries))
        self._backoff_s = float(backoff_s)
        self._backoff_cap_s = float(backoff_cap_s)
        self._jitter = float(jitter)
        self._rng = random.Random(seed)
        self.raise_on_unavailable = raise_on_unavailable
        self._lock = threading.Lock()
        #: transport-level failures (RPC error/timeout/bad reply) — NOT
        #: invalid signatures; surfaced as metrics counter
        #: ``sidecar_rpc_failures`` so chaos runs can tell a dead sidecar
        #: from a batch of forgeries (both read all-False at mask level)
        self.rpc_failures = 0
        #: re-sends of a payload after a failed attempt
        self.retries_total = 0
        self._connect()

    def _connect(self) -> None:
        self._channel = grpc.insecure_channel(
            self._address, options=_MESSAGE_OPTIONS
        )
        self._call = self._channel.unary_unary(
            _METHOD,
            request_serializer=_identity,
            response_deserializer=_identity,
        )

    def reconnect(self) -> None:
        """Tear down and rebuild the channel — between retry attempts and
        when a health probe wants a fresh connection to a restarted
        sidecar (gRPC keeps a failed subchannel in backoff otherwise)."""
        with self._lock:
            self._channel.close()
            self._connect()

    def _invoke(self, payload: bytes) -> bytes:
        """One locked RPC attempt — the seam the chaos harness
        (verifier/faults.py) shadows to inject sidecar failures."""
        with self._lock:
            return self._call(payload, timeout=self._timeout)

    def ping(self) -> bool:
        """Health probe: round-trip an EMPTY batch (encodes to b"", the
        backend verifies nothing and answers b""). True iff the sidecar
        answered — used by the degradation ladder to promote this tier
        back after recovery. Never counts toward rpc_failures."""
        try:
            return self._invoke(b"") == b""
        except (grpc.RpcError, VerifierUnavailableError):
            return False

    def stats(self) -> dict:
        return {
            "sidecar_rpc_failures": self.rpc_failures,
            "retries": self.retries_total,
        }

    def verify_batch(self, vertices: Sequence[Vertex]) -> List[bool]:
        if not vertices:
            return []
        with obs.span("remote.verify"):
            return self._verify(vertices)

    def _verify(self, vertices: Sequence[Vertex]) -> List[bool]:
        payload = _encode_batch(vertices)
        delay = self._backoff_s
        for attempt in range(self._retries + 1):
            try:
                mask = self._invoke(payload)
            except (grpc.RpcError, VerifierUnavailableError):
                self.rpc_failures += 1
            else:
                if len(mask) == len(vertices):
                    return [b == 1 for b in mask]
                # a mis-sized reply is a transport fault, not a verdict
                self.rpc_failures += 1
            if attempt < self._retries:
                self.retries_total += 1
                time.sleep(delay * (1.0 + self._jitter * self._rng.random()))
                delay = min(delay * 2.0, self._backoff_cap_s)
                self.reconnect()
        if self.raise_on_unavailable:
            raise VerifierUnavailableError(
                f"sidecar {self._address} unavailable after "
                f"{self._retries + 1} attempt(s)"
            )
        return [False] * len(vertices)

    def close(self) -> None:
        self._channel.close()


class RemoteCertVerifier(CertVerifier):
    """A :class:`CertVerifier` whose round certificates the sidecar checks
    (``VerifyCerts``): a validator configured ``verifier: "remote"`` with
    ``cert: "agg"`` holds one in place of a local CertVerifier.

    Aggregation (``make_certificate``, ``make_span``) and span checks stay
    local; every round certificate not already answered goes to the
    sidecar, one RPC for the certificates of a call, each with a verdict
    of its own. Fail-closed: a failed or mis-sized reply admits nothing,
    and its certificates are not remembered, so a later call asks again.
    Verdicts the sidecar gave are memoized like CertVerifier's."""

    def __init__(
        self,
        address: str,
        registry: KeyRegistry,
        quorum: int,
        *,
        timeout: float = 30.0,
        msm: Optional[str] = None,
    ):
        super().__init__(registry, quorum, msm=msm, pair="host")
        self._address = address
        self._timeout = timeout
        self._channel = grpc.insecure_channel(address, options=_MESSAGE_OPTIONS)
        self._call = self._channel.unary_unary(
            _CERT_METHOD,
            request_serializer=_identity,
            response_deserializer=_identity,
        )
        self._lock = threading.Lock()
        #: RPCs that failed or came back mis-sized
        self.rpc_failures = 0

    def verify_remote(self, certs: Sequence[RoundCertificate]) -> List[bool]:
        """One RPC: the sidecar's verdict for each certificate, with no
        memo on either side; all False where the RPC fails."""
        return self._ask(certs) or [False] * len(certs)

    def _ask(self, certs: Sequence[RoundCertificate]) -> Optional[List[bool]]:
        if not certs:
            return []
        try:
            with self._lock:
                reply = self._call(
                    codec.encode_certificates(certs), timeout=self._timeout
                )
        except grpc.RpcError:
            reply = None
        if reply is None or len(reply) != len(certs):
            self.rpc_failures += 1
            return None
        return [b == 1 for b in reply]

    def verify_certificate(self, cert: RoundCertificate) -> bool:
        return self.verify_many([cert])[0]

    def verify_many(self, certs: Sequence[RoundCertificate]) -> List[bool]:
        verdicts: List[Optional[bool]] = [
            self._verdicts.get(c.signing_key()) for c in certs
        ]
        self.stats["certs_checked"] += len(certs)
        self.stats["verdict_hits"] += sum(v is not None for v in verdicts)
        fresh = [i for i, v in enumerate(verdicts) if v is None]
        got = self._ask([certs[i] for i in fresh])
        if got is None:
            return [bool(v) for v in verdicts]
        for i, ok in zip(fresh, got):
            verdicts[i] = ok
            self._remember(certs[i].signing_key(), ok)
            self.stats["certs_valid" if ok else "certs_invalid"] += 1
            if ok and self.on_certified is not None:
                self.on_certified(certs[i])
        return [bool(v) for v in verdicts]

    def close(self) -> None:
        self._channel.close()
