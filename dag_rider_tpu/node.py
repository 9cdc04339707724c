"""Runnable committee node — ``python -m dag_rider_tpu.node``.

The reference is a library with no main package (SURVEY §3); a framework
needs a deployment shape. This wires the full stack for one participant:
gRPC transport (+ optional Bracha RBC), the batched device Verifier,
Ed25519 vertex signing, the threshold-BLS (or round-robin) coin, periodic
checkpointing, and structured logs — all from one JSON config.

Subcommands:

- ``keygen --n 4 --threshold 2 --out keys.json`` — dealer-style committee
  key material: Ed25519 registry + per-node seeds, threshold-BLS shares.
  (Deterministic dealer = test/deploy convenience; a production committee
  would run a DKG so nobody ever holds the group secret.)
- ``run --config node0.json`` — start one node and pump until stopped.

Config (JSON):
{
  "index": 0, "n": 4, "listen": "127.0.0.1:7000",
  "peers": {"1": "127.0.0.1:7001", ...},
  "keys": "keys.json",            // from keygen
  "rbc": true,                     // Bracha reliable broadcast stage
  "verifier": "device",            // | "sharded" | "cpu" | "remote" | "none"
  "verify_bucket": 512,            // optional: dispatch bucket (default:
                                   // n rounded up to a power of two)
  "verify_depth": 2,               // optional: in-flight dispatch window
  "verify_prep_workers": 4,        // optional: parallel host-prep workers
  "verify_fallback": "cpu",        // optional: degradation-ladder floor
                                   // under device/sharded/remote
                                   // (default DAGRIDER_VERIFY_FALLBACK)
  "verify_retry": 1,               // optional: retries per ladder tier /
                                   // sidecar attempt resends
                                   // (default DAGRIDER_VERIFY_RETRY)
  "coin": "threshold_bls",         // | "round_robin" | "fixed"
  "coin_msm": "host",              // "device": share aggregation on the mesh
  "cert": "agg",                   // aggregated round certificates (ISSUE 9):
                                   // one BLS aggregate check admits a whole
                                   // round; default "off" (per-vertex path);
                                   // env default DAGRIDER_CERT
  "cert_msm": "host",              // | "device" | "sharded" — certificate
                                   // aggregation seam (DAGRIDER_CERT_MSM)

  "wan": {"seed": 0,               // optional: delay/drop at the send seam.
          "regions": ["a", "b", ...],  // a delay per link: every node's
          "one_way_ms": {"a": {"a": 1, "b": 31}},  // region, the one-way
          "jitter": 0.02},         // ms between regions, +/- a fraction;
                                   // or one class: "delay_ms": [lo, hi]
                                   // ("delay_rate", "drop" either way)
  "checkpoint_dir": "ckpt/node0",  // optional, periodic + on shutdown
  "checkpoint_every_s": 30,
  "submit_interval_s": 0.5,        // synthetic client load (0: none)

  "mempool": true,                 // round 10: admission + batching
                                   // front door (dag_rider_tpu/mempool).
                                   // true = env-tuned knobs
                                   // (DAGRIDER_MEMPOOL_CAP etc.), or a
                                   // dict of MempoolConfig overrides:
                                   // {"cap": 65536, "batch_bytes": 8192,
                                   //  "batch_deadline_ms": 50, ...}.
                                   // Absent/false = the legacy direct
                                   // one-block-per-submit path.
  "auto_propose": false            // explicit gate on the synthetic
                                   // n{i}-auto-{seq} generator; defaults
                                   // ON only when no mempool is attached
                                   // (load tests through the mempool
                                   // must measure injected traffic only)
}
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import threading
import time
from collections import deque
from typing import Deque, Dict, Optional

from dag_rider_tpu import obs
from dag_rider_tpu.config import Config
from dag_rider_tpu.consensus.coin import FixedCoin, RoundRobinCoin, ThresholdCoin
from dag_rider_tpu.consensus.process import Process
from dag_rider_tpu.core.types import Block
from dag_rider_tpu.crypto import threshold as th
from dag_rider_tpu.transport.rbc import RbcTransport
from dag_rider_tpu.utils import checkpoint
from dag_rider_tpu.utils.slog import EventLog, NOOP, stdlib_sink
from dag_rider_tpu.verifier.base import KeyRegistry, VertexSigner


# ----------------------------------------------------------------------
# keygen
# ----------------------------------------------------------------------

def generate_keys(
    n: int, threshold: int, seed: Optional[str] = None
) -> dict:
    """Committee key material as one JSON-serializable dict.

    ``seed`` pins the material deterministically — tests/fixtures only.
    Left unset (the CLI default), a fresh 256-bit secret is drawn from
    os.urandom: a guessable seed makes every identity seed publicly
    re-derivable, which in turn voids the DKG's share confidentiality
    (anyone can compute the pairwise channel keys offline)."""
    if seed is None:
        import secrets

        seed = secrets.token_hex(32)
    reg, seeds = KeyRegistry.generate(n, seed_prefix=seed.encode() + b"|ed|")
    coin_keys = th.ThresholdKeys.generate(n, threshold, seed=seed.encode())
    from dag_rider_tpu.crypto import bls12381 as bls

    # per-node BLS certificate keys (ISSUE 9 aggregated round
    # certificates) — distinct from the threshold-coin shares: cert
    # signatures are independent per node, never Shamir-combined
    import hashlib

    cert_sks = [
        int.from_bytes(
            hashlib.sha256(
                seed.encode() + b"|cert|" + str(i).encode()
            ).digest(),
            "big",
        )
        % bls.R
        for i in range(n)
    ]
    return {
        "n": n,
        "threshold": threshold,
        "ed25519_public": [pk.hex() for pk in reg.public_keys],
        "ed25519_seeds": [s.hex() for s in seeds],
        "bls_group_pk": bls.g2_serialize(coin_keys.group_pk).hex(),
        "bls_share_pks": [
            bls.g2_serialize(pk).hex() for pk in coin_keys.share_pks
        ],
        "bls_share_sks": [hex(sk) for sk in coin_keys.share_sks],
        "bls_cert_pks": [
            bls.g2_serialize(bls.pk_of(sk)).hex() for sk in cert_sks
        ],
        "bls_cert_sks": [hex(sk) for sk in cert_sks],
    }


def _dump_secret_file(path: str, blob: dict) -> None:
    """Write a key file owner-readable only (0600): these carry Ed25519
    seeds / BLS share secrets, and a world-readable default would hand
    any local user the node's DKG channel keys."""
    fd = os.open(path, os.O_WRONLY | os.O_CREAT | os.O_TRUNC, 0o600)
    # open()'s mode only applies on CREATION — overwriting a
    # pre-existing world-readable file must tighten it too
    os.fchmod(fd, 0o600)
    with os.fdopen(fd, "w") as fh:
        json.dump(blob, fh, indent=1)


def load_keys(blob: dict):
    """(KeyRegistry, seeds, ThresholdKeys) from a keygen dict."""
    from dag_rider_tpu.crypto import bls12381 as bls

    reg = KeyRegistry(tuple(bytes.fromhex(pk) for pk in blob["ed25519_public"]))
    # DKG-produced files scrub other nodes' identity seeds (null)
    seeds = [
        bytes.fromhex(s) if s else None for s in blob["ed25519_seeds"]
    ]
    coin_keys = th.ThresholdKeys(
        blob["threshold"],
        bls.g2_deserialize(bytes.fromhex(blob["bls_group_pk"])),
        [bls.g2_deserialize(bytes.fromhex(p)) for p in blob["bls_share_pks"]],
        # DKG-produced files carry only this node's secret (null
        # elsewhere) — the dealerless property
        [int(sk, 16) if sk else None for sk in blob["bls_share_sks"]],
    )
    if blob.get("bls_cert_pks"):
        # certificate PKI rides the same registry (ISSUE 9); older key
        # files without it simply leave the cert path gated off
        import dataclasses

        reg = dataclasses.replace(
            reg,
            bls_public_keys=tuple(
                bls.g2_deserialize(bytes.fromhex(p))
                for p in blob["bls_cert_pks"]
            ),
        )
    return reg, seeds, coin_keys


# ----------------------------------------------------------------------
# node
# ----------------------------------------------------------------------

class Node:
    """One running participant; owns the pump thread."""

    def __init__(self, cfg: dict, *, log: Optional[EventLog] = None):
        n = int(cfg["n"])
        index = int(cfg["index"])
        gc_depth = cfg.get("gc_depth")
        self.ccfg = Config(
            n=n,
            coin=cfg.get("coin", "round_robin"),
            propose_empty=bool(cfg.get("propose_empty", True)),
            # bounded DAG memory for long-running nodes (None = grow
            # forever, reference-compatible)
            gc_depth=int(gc_depth) if gc_depth is not None else None,
            # hot-path pump flavor; None defers to DAGRIDER_PUMP / vector
            pump=cfg.get("pump"),
            # aggregated round certificates; None defers to DAGRIDER_CERT
            cert=cfg.get("cert"),
            # certificate patience is counted in quiescent pump ticks
            # (~ms each): the in-process default of 6 is far too tight
            # for share aggregation over real sockets, so the cluster
            # harness overrides it per node
            cert_patience=int(cfg.get("cert_patience", 6)),
            # the loop below steps every ~2 ms: a sync request waits for
            # a silence as long as two of this node's own recent rounds
            sync_silence_rounds=2.0,
        )
        with open(cfg["keys"]) as fh:
            keyblob = json.load(fh)
        reg, seeds, coin_keys = load_keys(keyblob)
        if reg.n != n:
            raise ValueError(f"keys are for n={reg.n}, config says n={n}")

        # Causal tracing + flight recorder (ISSUE 13, DAGRIDER_TRACE):
        # tee the ring recorder and the flight trigger watch into
        # whatever sink the caller brought (e.g. --verbose's stdlib
        # bridge), so pump_error / verify_exhausted leave a post-mortem.
        self.tracing = None
        if obs.trace_enabled():
            self.tracing = obs.build_tracing(
                base_sink=log.sink if log is not None else None,
                context={"node": index},
            )
            log = self.tracing.log
        self.log = log if log is not None else NOOP
        peers: Dict[int, str] = {int(k): v for k, v in cfg.get("peers", {}).items()}
        # Lazy: transport/net.py imports grpc at module scope, and grpcio
        # is the optional [net] extra — keygen must work without it.
        from dag_rider_tpu.transport.net import GrpcTransport, WanFault

        # WAN emulation at the real send seam (ISSUE 19): delay/drop
        # apply to genuine gRPC sends between OS processes, not a
        # simulator queue. {"wan": {"seed": s, "drop": p, "delay_rate":
        # p, ...}} with either one link class for every peer —
        # "delay_ms": [lo, hi] — or a delay per link: "regions" (the
        # region of every node, by index), "one_way_ms" (region ->
        # region -> one-way ms) and "jitter" (a fraction of the link's
        # delay, uniform either way). Seed is offset by index so peers
        # do not fault in lockstep.
        wan = cfg.get("wan")
        send_fault = None
        if wan:
            links = {}
            if "one_way_ms" in wan:
                regions = list(wan["regions"])
                if len(regions) != n:
                    raise ValueError(
                        f'"wan" names {len(regions)} regions for n={n}'
                    )
                links = {
                    "region": regions[index],
                    "peer_regions": {
                        j: r for j, r in enumerate(regions) if j != index
                    },
                    "one_way_ms": wan["one_way_ms"],
                    "jitter": float(wan.get("jitter", 0.0)),
                }
            send_fault = WanFault(
                seed=int(wan.get("seed", 0)) + index,
                delay_ms=tuple(wan.get("delay_ms", (0.0, 0.0))),
                delay_rate=float(wan.get("delay_rate", 1.0)),
                drop=float(wan.get("drop", 0.0)),
                **links,
            )

        auth = None
        master_hex = cfg.get("auth_master")
        if master_hex:
            # Pairwise-MAC frame auth (transport/auth.py): the cluster
            # dealer puts one shared master secret in every node's config;
            # each node derives only its own key row. Without it the
            # Deliver endpoint accepts forged control frames (VERDICT r3
            # missing #5).
            from dag_rider_tpu.transport.auth import FrameAuth

            auth = FrameAuth.for_node(bytes.fromhex(master_hex), index, n)
        snap_fresh = cfg.get("snapshot_freshness_s", 300.0)
        self.net = GrpcTransport(
            index,
            cfg["listen"],
            peers,
            auth=auth,
            # Peer state transfer (elastic recovery past the GC horizon):
            # serve our live DAG window; it is self-certifying, see
            # utils.checkpoint.restore_from_snapshot. Attested (ISSUE
            # 20): the envelope carries our verified span chain so a
            # joiner settles the window with ~1 pairing per span; falls
            # back to the plain blob when no spans are banked.
            snapshot_provider=lambda: checkpoint.attested_snapshot_bytes(
                self.process
            ),
            # Donor-side availability knobs: per-relayer serve interval,
            # and the request-timestamp freshness window (fleets with
            # known clock skew widen it; null in the JSON config
            # disables freshness checking entirely).
            snapshot_min_interval_s=float(
                cfg.get("snapshot_min_interval_s", 1.0)
            ),
            snapshot_freshness_s=(
                None if snap_fresh is None else float(snap_fresh)
            ),
            send_fault=send_fault,
            log=self.log,
        )
        transport = self.net
        if cfg.get("rbc", True):
            transport = RbcTransport(self.net, index, n, self.ccfg.f)

        verifier = None
        kind = cfg.get("verifier", "device")
        # Round-9 resilience knobs. "verify_fallback": "cpu" ladders the
        # configured verifier onto a CPUVerifier floor (ResilientVerifier:
        # bounded per-tier retry, background health probe + promotion, a
        # batch rejected only after the whole ladder fails).
        # "verify_retry" is the per-tier retry count (and the sidecar's
        # resend count for a bare "remote"). Explicit config beats the
        # DAGRIDER_VERIFY_FALLBACK / DAGRIDER_VERIFY_RETRY env defaults.
        from dag_rider_tpu.verifier.resilient import (
            default_verify_fallback,
            default_verify_retry,
        )

        fallback = cfg.get("verify_fallback")
        fallback = (
            default_verify_fallback() if fallback is None else str(fallback)
        )
        if fallback and fallback != "cpu":
            raise ValueError(
                f'verify_fallback must be "cpu" or empty, got {fallback!r}'
            )
        retry = cfg.get("verify_retry")
        retry = default_verify_retry() if retry is None else int(retry)

        def _ladder(primary):
            from dag_rider_tpu.verifier.cpu import CPUVerifier
            from dag_rider_tpu.verifier.resilient import ResilientVerifier

            return ResilientVerifier(
                [primary, CPUVerifier(reg)], retries=retry, log=self.log
            )

        if kind in ("device", "sharded"):
            # Wrap the device verifier (which refuses a CPU backend
            # nobody asked for) in a depth-K dispatch window whose
            # construction fixes the bucket — verify_bucket, else n
            # rounded up to a power of two — and compiles its program.
            # Every later batch is padded or chunked to that shape, so
            # the first consensus round eats no XLA compile, a program
            # the chip refuses fails the start-up, and nothing compiles
            # under the pump loop's catch-all. "sharded" shares every
            # knob (verify_bucket/verify_depth) and lays the batch over
            # a device mesh sized by DAGRIDER_MESH (virtual devices
            # under JAX_PLATFORMS=cpu — parallel/mesh.py); its bucket
            # rounds up to a mesh multiple internally, masks stay
            # byte-identical to the single-chip program.
            from dag_rider_tpu.verifier.pipeline import VerifierPipeline
            from dag_rider_tpu.verifier.tpu import TPUVerifier

            if kind == "sharded":
                from dag_rider_tpu.parallel.mesh import mesh_from_env
                from dag_rider_tpu.parallel.sharded_verifier import (
                    ShardedTPUVerifier,
                )

                base = ShardedTPUVerifier(reg, mesh_from_env())
            else:
                base = TPUVerifier(reg)
            bucket = cfg.get("verify_bucket")
            # parallel host-prep engine (verifier/prep.py): explicit
            # config beats the DAGRIDER_PREP_WORKERS env default
            prep = cfg.get("verify_prep_workers")
            if prep:
                base.prep_workers = int(prep)
            depth = cfg.get("verify_depth")
            verifier = VerifierPipeline(
                base,
                depth=int(depth) if depth else None,
                fixed_bucket=int(bucket) if bucket else None,
                log=self.log,
            )
            if fallback:
                # ladder wiring also hands the pipeline's quarantined
                # chunks to the CPU floor (quarantine_verifier)
                verifier = _ladder(verifier)
        elif kind == "cpu":
            from dag_rider_tpu.verifier.cpu import CPUVerifier

            verifier = CPUVerifier(reg)
        elif kind == "remote":
            # The north star's stated deployment shape (BASELINE.json:
            # "gRPC to a JAX sidecar"): consensus host ships whole-round
            # batches to a VerifierSidecarServer at verifier_address.
            from dag_rider_tpu.verifier.sidecar import RemoteVerifier

            addr = cfg.get("verifier_address")
            if not addr:
                raise ValueError(
                    'verifier "remote" needs a "verifier_address"'
                )
            verifier = RemoteVerifier(
                addr,
                timeout=float(cfg.get("verifier_timeout_s", 30.0)),
                retries=retry,
            )
            if fallback:
                verifier = _ladder(verifier)
        elif kind != "none":
            raise ValueError(f"unknown verifier {kind!r}")

        coin = None
        if self.ccfg.coin == "threshold_bls":
            msm = None
            msm_kind = cfg.get("coin_msm", "host")
            if msm_kind == "device":
                from dag_rider_tpu.parallel.msm import ShardedMSM

                msm = ShardedMSM()
            elif msm_kind != "host":
                raise ValueError(f"unknown coin_msm {msm_kind!r}")
            coin = ThresholdCoin(coin_keys, index, n, msm=msm)
        elif self.ccfg.coin == "fixed":
            coin = FixedCoin(0)
        elif self.ccfg.coin == "round_robin":
            coin = RoundRobinCoin(n)

        cert_signer = cert_verifier = None
        if self.ccfg.cert == "agg":
            # aggregated round certificates (ISSUE 9): needs the cert PKI
            # in the key file AND a verifier (the aggregator tier still
            # verifies its own rounds per-vertex)
            if verifier is None:
                raise ValueError('cert "agg" needs a verifier (not "none")')
            if not reg.bls_public_keys:
                raise ValueError(
                    'cert "agg" needs bls_cert_pks in the key file '
                    "(re-run keygen)"
                )
            sk_hex = (keyblob.get("bls_cert_sks") or [None] * n)[index]
            if not sk_hex:
                raise ValueError(
                    'cert "agg" needs this node\'s bls_cert_sks entry'
                )
            from dag_rider_tpu.verifier.base import CertSigner
            from dag_rider_tpu.verifier.cert import CertVerifier

            cert_signer = CertSigner(int(sk_hex, 16))
            cert_verifier = CertVerifier(
                reg, self.ccfg.quorum, msm=cfg.get("cert_msm")
            )
            if hasattr(verifier, "cert_verifier"):
                # ladder deployments surface the certificate gauges in
                # the same resilience bundle (verifier/resilient.py)
                verifier.cert_verifier = cert_verifier

        self.delivered = []
        self.mempool = None

        # Byzantine-over-sockets (ISSUE 19): {"adversary": {"kind":
        # "equivocate", "seed": 7}} swaps in a ByzantineProcess whose
        # forged wire output crosses REAL process boundaries — the same
        # round-11 behaviors, now probing honest admission gates over
        # gRPC instead of a simulator queue.
        adv = cfg.get("adversary")
        behavior = None
        if adv:
            from dag_rider_tpu.consensus.adversary import make_behavior

            behavior = make_behavior(
                adv["kind"], seed=int(adv.get("seed", 0))
            )

        def _build_process() -> Process:
            if behavior is not None:
                from dag_rider_tpu.consensus.adversary import (
                    ByzantineProcess,
                )

                proc_cls = ByzantineProcess
                extra = {"behavior": behavior}
            else:
                proc_cls = Process
                extra = {}
            return proc_cls(
                self.ccfg,
                index,
                transport,
                coin=coin,
                verifier=verifier,
                signer=VertexSigner(seeds[index]),
                cert_signer=cert_signer,
                cert_verifier=cert_verifier,
                on_deliver=self._on_deliver,
                log=self.log,
                **extra,
            )

        def _attach() -> None:
            """(Re)bind everything keyed to the current Process's
            metrics object — also used by the corrupt-checkpoint
            rebuild path below, which swaps in a fresh Process."""
            mp_cfg = cfg.get("mempool")
            if mp_cfg:
                from dag_rider_tpu.config import MempoolConfig
                from dag_rider_tpu.mempool import Mempool

                self.mempool = Mempool(
                    MempoolConfig.from_dict(
                        mp_cfg if isinstance(mp_cfg, dict) else None
                    ),
                    metrics=self.process.metrics,
                    log=self.process.log,
                )
                self.process.on_propose = self.mempool.observe_proposed
                self.process.block_source = self.mempool
            self.net.attach_metrics(self.process.metrics)
            if self.tracing is not None:
                self.tracing.flight.add_metrics_source(
                    str(index), self.process.metrics.snapshot
                )

        self.process = _build_process()
        # Round-10 ingestion edge: "mempool": true (env-tuned) or a dict
        # of MempoolConfig overrides attaches the admission + batching
        # front door; submit() then routes through it and the proposer
        # cuts each vertex's block from the pool when it makes the
        # vertex. Absent/false keeps the legacy direct-block path.
        _attach()
        self.ckpt_dir = cfg.get("checkpoint_dir")
        self.ckpt_every = float(cfg.get("checkpoint_every_s", 30))
        #: per-peer state-transfer fetch deadline — short, because the
        #: fetch runs on the pump thread (one candidate per cycle)
        self.snapshot_timeout_s = float(cfg.get("snapshot_timeout_s", 5.0))
        self.submit_interval = float(cfg.get("submit_interval_s", 0))
        #: the synthetic n{i}-auto-{seq} generator gate: default ON only
        #: without a mempool (legacy behavior); with one attached, load
        #: tests must measure injected traffic only, so the generator
        #: needs an explicit opt-in
        self.auto_propose = bool(
            cfg.get("auto_propose", self.mempool is None)
        )
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._submit_lock = threading.Lock()
        self._submit_queue: Deque[Block] = deque()
        self._stopped = False

        if self.ckpt_dir and checkpoint.present(self.ckpt_dir):
            # present() (not latest_round): a torn manifest must reach
            # restore() so the corruption is COUNTED, not silently
            # mistaken for a first boot.
            try:
                checkpoint.restore(
                    self.process, self.ckpt_dir, mempool=self.mempool
                )
                self.log.event("restored", round=self.process.round)
            except checkpoint.CorruptCheckpointError as e:
                # kill -9 landed mid-save on a pre-atomic layout, or the
                # disk bit-rotted: start empty (fresh Process — restore
                # validates before mutating, but a rebuild costs nothing
                # and guarantees genesis state) and let snapshot sync
                # re-join us past whatever the cluster pruned. Accepted
                # transactions are the WAL's job, not the checkpoint's.
                unsub = getattr(transport, "unsubscribe", None)
                if unsub is not None:
                    unsub()
                self.process = _build_process()
                _attach()
                self.process.metrics.inc("checkpoint_corrupt")
                self.log.event("checkpoint_corrupt", error=str(e)[:200])

    def _on_deliver(self, vertex) -> None:
        self.delivered.append(vertex)
        if self.mempool is not None:
            # close the submit→a_deliver latency books for our payloads
            self.mempool.observe_delivered(vertex.block)

    def submit(self, block: Block, *, client: str = "client0"):
        """Client API — the mempool front door (round 10). With a
        mempool attached the block's transactions go through admission
        (accept/throttle/shed) into the pool, and the returned
        SubmitResult carries the backpressure signal: overload sheds
        and reports, it does not raise. Without one, the legacy direct
        path: the block lands whole in a handoff queue the pump thread
        drains — Process state is only ever touched from the pump
        thread (a caller-thread process.submit racing the pump's step()
        corrupted state rarely enough to be a flaky-suite heisenbug).
        Either way, after stop() nothing is drained again, so a late
        submit raises instead of silently swallowing the block."""
        with self._submit_lock:
            if self._stopped:
                raise RuntimeError(
                    f"node {self.process.index} is stopped; block not accepted"
                )
            if self.mempool is None:
                self._submit_queue.append(block)
                return None
            # under the same lock as the stop check: a submit racing
            # stop() must not slip into the pool after the shutdown
            # checkpoint already persisted it
            return self.mempool.submit(block.transactions, client=client)

    def start(self) -> None:
        # which verifier, on which platform, running which program: a
        # validator that came up on the wrong one says so in its log
        stats = getattr(self.process.verifier, "stats", None)
        self.log.event(
            "started",
            round=self.process.round,
            verifier=type(self.process.verifier).__name__,
            **(stats() if callable(stats) else {}),
        )
        self.process.defer_steps = True
        self.process.start()
        self._thread = threading.Thread(target=self._pump_loop, daemon=True)
        self._thread.start()

    def stop(self) -> None:
        # Refuse new submissions first: anything enqueued after the final
        # _drain_submissions below would never be drained again.
        with self._submit_lock:
            self._stopped = True
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=10)
            if self._thread.is_alive():
                # A wedged pump thread may still be mutating Process;
                # draining or checkpointing from this thread would race
                # it (and persist a mid-mutation snapshot). Leave state
                # alone and just tear the transport down.
                self.log.event("stop_pump_hung")
                self.net.close()
                return
        # The pump thread is down; flush any blocks still queued into the
        # Process (safe from this thread now) so the shutdown checkpoint
        # carries them — queued client submissions must not vanish.
        try:
            self._drain_submissions()
        except Exception as e:  # noqa: BLE001 — shutdown must proceed,
            # but never silently: the dropped block and stranded
            # remainder need a trace.
            self.log.event("stop_drain_error", error=repr(e)[:200])
        if self.mempool is not None:
            # final gauge refresh so the post-stop snapshot is current
            self.process.metrics.observe_mempool(self.mempool.stats())
        if self.ckpt_dir:
            # pending mempool transactions ride mempool.json in the same
            # checkpoint: accepted traffic survives the restart
            checkpoint.save(self.process, self.ckpt_dir, mempool=self.mempool)
        self.net.close()

    def _pump_loop(self) -> None:
        last_ckpt = last_submit = last_gauge = time.monotonic()
        seq = 0
        while not self._stop.is_set():
            try:
                self._pump_once()
                now = time.monotonic()
                if (
                    self.auto_propose
                    and self.submit_interval
                    and now - last_submit >= self.submit_interval
                ):
                    last_submit = now
                    seq += 1
                    payload = f"n{self.process.index}-auto-{seq}".encode()
                    if self.mempool is not None:
                        # explicit auto_propose with a mempool: the
                        # synthetic load takes the front door too, so it
                        # shows up in the same gauges as real traffic
                        self.mempool.submit(
                            (payload,),
                            client=f"auto{self.process.index}",
                        )
                    else:
                        self.process.submit(Block((payload,)))
                if self.mempool is not None and now - last_gauge >= 1.0:
                    # stats() is counter reads, but snapshot consumers
                    # only need ~1 Hz freshness — keep it off the hot loop
                    last_gauge = now
                    self.process.metrics.observe_mempool(
                        self.mempool.stats()
                    )
                if (
                    self.ckpt_dir
                    and self.ckpt_every > 0
                    and now - last_ckpt >= self.ckpt_every
                ):
                    last_ckpt = now
                    with obs.span("node.checkpoint"):
                        checkpoint.save(
                            self.process, self.ckpt_dir, mempool=self.mempool
                        )
                    self.log.event("checkpointed", round=self.process.round)
            except Exception as e:  # noqa: BLE001 — a BFT node must not
                # die silently: before this guard, any exception
                # (step, checkpoint IO, anything) killed the daemon pump
                # thread and the node kept accepting traffic it never
                # processed (observed as a stalled cluster with empty
                # diagnostics).
                self.process.metrics.inc("pump_errors")
                self.log.event("pump_error", error=repr(e)[:200])
                time.sleep(0.01)

    def _drain_submissions(self) -> None:
        """Move queued client blocks into the Process, one at a time; on
        an exception the not-yet-processed remainder goes back to the
        front of the queue (the failing block is dropped and logged —
        retrying it forever would livelock the pump). Deques at both
        ends: the old list's pop(0) drain was O(n) per block."""
        with self._submit_lock:
            pending, self._submit_queue = self._submit_queue, deque()
        while pending:
            block = pending.popleft()
            try:
                self.process.submit(block)
            except Exception:
                with self._submit_lock:
                    pending.extend(self._submit_queue)
                    self._submit_queue = pending
                raise

    def _pump_once(self) -> None:
        with obs.span("node.tick"):
            moved = self._tick()
        if not moved:
            time.sleep(0.002)

    def _tick(self) -> int:
        self._drain_submissions()
        if self.process.state_transfer_needed:
            self._state_transfer()
        moved = self.net.pump(256)
        self.process.step()
        return moved

    def _state_transfer(self) -> None:
        """f+1 peers reported GC floors above our round (sync_nack):
        anti-entropy cannot help, so fetch a peer's live window and
        replay it (utils.checkpoint.restore_from_snapshot — signatures
        verified, consensus state recomputed locally, atomic on
        failure). Runs on the pump thread, which owns all Process state
        — so at most ONE candidate is tried per pump cycle with a short
        RPC deadline (a dead peer must not stall consensus pumping for
        tens of seconds; the next cycle tries the next candidate). The
        highest-reported floor goes first (the most caught-up donor);
        when every candidate has failed, the flag clears and nacks must
        re-accrue before another attempt (no hot fetch loop against
        dead/Byzantine peers)."""
        nacks = self.process._horizon_nacks
        if not nacks:
            self.process.state_transfer_needed = False
            self.log.event("state_transfer_failed")
            return
        peer = max(nacks, key=nacks.get)
        nacks.pop(peer)  # consumed: success clears the rest, failure moves on
        blob = self.net.fetch_snapshot(
            peer, timeout_s=self.snapshot_timeout_s
        )
        if blob and checkpoint.restore_from_snapshot(
            self.process,
            blob,
            verifier=self.process.verifier,
            span_verifier=getattr(self.process, "cert_verifier", None),
        ):
            self.log.event(
                "state_transferred",
                peer=peer,
                round=self.process.round,
                base=self.process.dag.base_round,
            )
            return
        self.log.event("state_transfer_attempt_failed", peer=peer)
        if not nacks:
            self.process.state_transfer_needed = False
            self.log.event("state_transfer_failed")


# ----------------------------------------------------------------------
# CLI
# ----------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="dag_rider_tpu.node")
    sub = ap.add_subparsers(dest="cmd", required=True)
    kg = sub.add_parser("keygen", help="generate committee key material")
    kg.add_argument("--n", type=int, required=True)
    kg.add_argument("--threshold", type=int, required=True)
    kg.add_argument(
        "--seed",
        default=None,
        help="deterministic committee seed — tests only; default draws "
        "fresh randomness (a guessable seed voids DKG confidentiality)",
    )
    kg.add_argument(
        "--out",
        default=None,
        help="combined key file holding EVERY node's secrets (dealer "
        "deployments / tests). Omit it when --per-node-dir is given: "
        "for a DKG ceremony the combined file is exactly the "
        "single-holder-decrypts-everything artifact to avoid",
    )
    kg.add_argument(
        "--per-node-dir",
        default=None,
        help="also write <dir>/node<i>-identity.json per node, each "
        "holding ONLY that node's secrets (the files a DKG ceremony "
        "should start from — a combined file holding every seed lets "
        "any single holder decrypt all DKG share traffic)",
    )
    dk = sub.add_parser(
        "dkg",
        help="dealerless coin keygen: joint-Feldman DKG over gRPC "
        "(replaces keygen's BLS dealer; Ed25519 identities from --keys "
        "bootstrap the private share channels)",
    )
    dk.add_argument("--keys", required=True, help="keygen file (identities)")
    dk.add_argument("--index", type=int, required=True)
    dk.add_argument("--threshold", type=int, required=True)
    dk.add_argument("--listen", required=True)
    dk.add_argument(
        "--peers",
        required=True,
        help='comma list "0=host:port,1=host:port,..." (all n participants)',
    )
    dk.add_argument("--out", required=True, help="per-node key file")
    dk.add_argument("--timeout", type=float, default=15.0)
    rn = sub.add_parser("run", help="run one node until interrupted")
    rn.add_argument("--config", required=True)
    rn.add_argument("--duration", type=float, default=0, help="0 = forever")
    rn.add_argument("--verbose", action="store_true")
    args = ap.parse_args(argv)

    if args.cmd == "keygen":
        if not args.out and not args.per_node_dir:
            raise SystemExit("keygen needs --out and/or --per-node-dir")
        blob = generate_keys(args.n, args.threshold, args.seed)
        if args.out:
            _dump_secret_file(args.out, blob)
            print(
                f"wrote {args.out} (n={args.n}, threshold={args.threshold})"
            )
        if args.per_node_dir:
            os.makedirs(args.per_node_dir, exist_ok=True)
            for i in range(args.n):
                per = dict(blob)
                per["ed25519_seeds"] = [
                    s if j == i else None
                    for j, s in enumerate(blob["ed25519_seeds"])
                ]
                per["bls_share_sks"] = [
                    sk if j == i else None
                    for j, sk in enumerate(blob["bls_share_sks"])
                ]
                per["bls_cert_sks"] = [
                    sk if j == i else None
                    for j, sk in enumerate(blob["bls_cert_sks"])
                ]
                path = os.path.join(
                    args.per_node_dir, f"node{i}-identity.json"
                )
                _dump_secret_file(path, per)
            print(
                f"wrote {args.n} per-node identity files under "
                f"{args.per_node_dir} (each holds only its own secrets)"
            )
        return 0

    if args.cmd == "dkg":
        from dag_rider_tpu.crypto import bls12381 as bls
        from dag_rider_tpu.crypto import dkg as dkg_mod
        from dag_rider_tpu.transport.auth import FrameAuth
        from dag_rider_tpu.transport.blobbus import BlobBus

        with open(args.keys) as fh:
            keyblob = json.load(fh)
        my_seed = bytes.fromhex(keyblob["ed25519_seeds"][args.index])
        pks = [bytes.fromhex(p) for p in keyblob["ed25519_public"]]
        n = len(pks)
        peers = {}
        for part in args.peers.split(","):
            k, _, addr = part.partition("=")
            peers[int(k)] = addr
        # Frame authentication from the Ed25519 identities themselves
        # (pairwise ECDH keys — dkg.channel_key): sender indices on DKG
        # traffic must be unforgeable or one Byzantine peer could stamp
        # garbage commitments with an honest dealer's index and split
        # the committee's qualified-set verdicts. No extra dealer
        # secret involved — the identities ARE the PKI bootstrap.
        pair_keys = {
            j: dkg_mod.channel_key(my_seed, pks[j])
            for j in range(n)
            if j != args.index
        }
        if any(k is None for k in pair_keys.values()):
            raise ValueError("malformed identity public key in --keys")
        bus = BlobBus(
            args.index, args.listen, peers,
            auth=FrameAuth(args.index, pair_keys),
        )
        try:
            res = dkg_mod.run_dkg_networked(
                bus,
                n,
                args.threshold,
                my_seed,
                pks,
                phase_timeout_s=args.timeout,
            )
        finally:
            bus.close()
        # same shape as keygen, but every secret list carries ONLY this
        # node's entries — the dealerless property the DKG exists for
        # (copying all n identity seeds into each out-file would hand
        # any single file-holder every channel key and thereby the
        # group secret)
        out = dict(keyblob)
        out["ed25519_seeds"] = [
            keyblob["ed25519_seeds"][i] if i == args.index else None
            for i in range(n)
        ]
        out["threshold"] = args.threshold
        out["bls_group_pk"] = bls.g2_serialize(res.group_pk).hex()
        out["bls_share_pks"] = [
            bls.g2_serialize(pk).hex() for pk in res.share_pks
        ]
        out["bls_share_sks"] = [
            hex(res.share_sk) if i == args.index else None for i in range(n)
        ]
        if out.get("bls_cert_sks"):
            # same dealerless scrub for the certificate secrets
            out["bls_cert_sks"] = [
                sk if i == args.index else None
                for i, sk in enumerate(out["bls_cert_sks"])
            ]
        out["dkg_qualified"] = list(res.qualified)
        _dump_secret_file(args.out, out)
        print(
            f"wrote {args.out} (dkg n={n}, threshold={args.threshold}, "
            f"qualified={list(res.qualified)})"
        )
        return 0

    with open(args.config) as fh:
        cfg = json.load(fh)
    log = NOOP
    if args.verbose:
        logging.basicConfig(level=logging.DEBUG, format="%(message)s")
        log = EventLog(stdlib_sink(), node=cfg["index"])
    node = Node(cfg, log=log)
    node.start()
    try:
        if args.duration > 0:
            time.sleep(args.duration)
        else:
            while True:
                time.sleep(3600)
    except KeyboardInterrupt:
        pass
    finally:
        node.stop()
    snap = node.process.metrics.snapshot()
    print(json.dumps({"delivered": len(node.delivered), "metrics": snap}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
