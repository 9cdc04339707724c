"""The Verifier seam — the north-star plugin boundary.

BASELINE.json: the per-vertex reliable-broadcast signature verification is
"lifted behind a new batched Verifier interface, introduced as a sibling to
the existing Transport plugin boundary" (reference ``process/transport.go:6``
is the only seam the reference has). A Process takes a Verifier at
construction and hands it *whole batches* of vertices; backends:

- :class:`dag_rider_tpu.verifier.cpu.CPUVerifier` — host RFC 8032 path,
- :class:`dag_rider_tpu.verifier.tpu.TPUVerifier` — vmapped JAX limb-field
  path, one DAG round per device dispatch.

Both must produce **byte-identical accept masks**, which is what makes the
CPU-vs-TPU commit order byte-identical (the consensus state machine is a
deterministic function of the accept masks and the delivery schedule).
"""

from __future__ import annotations

import abc
import dataclasses
from typing import List, Optional, Sequence

from dag_rider_tpu import config, obs
from dag_rider_tpu.core.types import Vertex
from dag_rider_tpu.crypto import ed25519
from dag_rider_tpu.utils import native


@dataclasses.dataclass(frozen=True)
class KeyRegistry:
    """source index -> Ed25519 public key (32 bytes). The PKI the
    reference's TODO names (``process.go:388``)."""

    public_keys: tuple

    @staticmethod
    def generate(n: int, seed_prefix: bytes = b"dagrider-test-key-"):
        """Deterministic test PKI: seeds derived from the index. NOT for
        production use (seeds are guessable by construction)."""
        import hashlib

        seeds, pubs = [], []
        for i in range(n):
            seed = hashlib.sha256(seed_prefix + str(i).encode()).digest()
            sk, pk = ed25519.generate_keypair(seed)
            seeds.append(sk)
            pubs.append(pk)
        return KeyRegistry(tuple(pubs)), seeds

    def key_of(self, source: int) -> Optional[bytes]:
        """Public key of ``source``, or None when out of range — the seam
        must be total: a bad source yields a reject bit, never an
        IndexError or (worse) negative-index aliasing to another node's
        key."""
        if not 0 <= source < len(self.public_keys):
            return None
        return self.public_keys[source]

    @property
    def n(self) -> int:
        return len(self.public_keys)

    #: source index -> BLS12-381 G2 public key (affine fp2 tuple) for the
    #: aggregated round-certificate path (ISSUE 9). Empty when the
    #: deployment has no certificate keys — everything cert-related gates
    #: on this being populated.
    bls_public_keys: tuple = ()

    def bls_key_of(self, source: int):
        """BLS certificate public key of ``source`` — total, like
        :meth:`key_of`."""
        if not 0 <= source < len(self.bls_public_keys):
            return None
        return self.bls_public_keys[source]

    @staticmethod
    def generate_with_cert(
        n: int, seed_prefix: bytes = b"dagrider-test-key-"
    ):
        """The :meth:`generate` test PKI plus per-process BLS certificate
        keys. Returns (registry, ed25519 seeds, bls secret keys); the
        BLS secrets are what :class:`CertSigner` wraps."""
        import hashlib

        from dag_rider_tpu.crypto import bls12381 as bls

        reg, seeds = KeyRegistry.generate(n, seed_prefix)
        sks, pks = [], []
        for i in range(n):
            sk = (
                int.from_bytes(
                    hashlib.sha256(
                        seed_prefix + b"|bls|" + str(i).encode()
                    ).digest(),
                    "big",
                )
                % bls.R
            )
            sks.append(sk)
            pks.append(bls.pk_of(sk))
        reg = dataclasses.replace(reg, bls_public_keys=tuple(pks))
        return reg, seeds, sks


class VertexSigner:
    """Signs this process's own vertices (held by the Process). The key
    expansion (incl. deriving the public key) is done once here, not per
    signature.

    Signing goes through libcrypto's Ed25519 (``utils/native.py``) while
    ``DAGRIDER_NATIVE`` is on, the default; with it off, or where
    libcrypto's Ed25519 cannot be resolved, through
    :func:`ed25519.sign_expanded` — the byte-identical oracle (RFC 8032
    signing is deterministic). The counters ``sign.native`` and
    ``sign.python`` say which path signed each vertex."""

    #: ``_native`` before the first native signature has made the key
    _UNMADE = object()

    def __init__(self, seed: bytes):
        self._seed = seed
        self._a, self._prefix, self._A_enc = ed25519.expand_seed(seed)
        #: libcrypto's key, made (and the library loaded) at the first
        #: native signature; None where it cannot be made
        self._native = self._UNMADE

    @property
    def public_key(self) -> bytes:
        return self._A_enc

    def sign_vertex(self, v: Vertex) -> Vertex:
        message = v.signing_bytes()
        if config.env_flag("DAGRIDER_NATIVE"):
            if self._native is self._UNMADE:
                self._native = native.Ed25519Key.make(self._seed)
            if self._native is not None:
                obs.count("sign.native")
                return dataclasses.replace(v, signature=self._native.sign(message))
        obs.count("sign.python")
        sig = ed25519.sign_expanded(self._a, self._prefix, self._A_enc, message)
        return dataclasses.replace(v, signature=sig)


class CertSigner:
    """BLS-signs this process's own vertex digests for the aggregated
    round-certificate path (ISSUE 9). Separate from :class:`VertexSigner`
    on purpose: the ed25519 vertex signature stays the per-vertex oracle;
    the BLS signature only ever feeds certificate aggregation."""

    def __init__(self, sk: int):
        self._sk = sk

    def sign_digest(self, digest: bytes) -> bytes:
        from dag_rider_tpu.crypto import bls12381 as bls

        return bls.sign(self._sk, digest)

    def sign_digests(self, digests: Sequence[bytes]) -> List[bytes]:
        """Round-batched share signing (ISSUE 12 tentpole 1): one
        :func:`bls12381.sign_many` call amortizes the hash-to-curve field
        maps and scalar ladders across every digest, routed by
        DAGRIDER_CERT_SIGN. Byte-identical to mapping
        :meth:`sign_digest` — tests/test_cert_phase2.py pins it."""
        from dag_rider_tpu.crypto import bls12381 as bls

        return bls.sign_many([self._sk] * len(digests), digests)

    #: domain tag for lane availability acks (ISSUE 17) — keeps an ack
    #: share from ever being replayable as a vertex cert share: both are
    #: BLS signatures under the same key, but a cert share signs a raw
    #: vertex digest while an ack signs the tagged batch digest
    LANE_ACK_DOMAIN = b"dagrider-lane-ack-v1|"

    def sign_availability(self, digest: bytes) -> bytes:
        """Sign a lane-batch availability ack: the attestation that this
        process holds (and has integrity-checked) the payload bytes
        hashing to ``digest``. 2f+1 of these aggregate into the batch
        availability certificate via :meth:`CertVerifier.aggregate` —
        the same G1 share machinery as round certificates."""
        from dag_rider_tpu.crypto import bls12381 as bls

        return bls.sign(self._sk, self.LANE_ACK_DOMAIN + digest)


class VerifierUnavailableError(RuntimeError):
    """A verifier backend could not be reached or could not complete an
    attempt (transport failure, dead sidecar, poisoned device state) — as
    opposed to a *verdict*: no statement about signature validity is
    implied. Backends raise it (when configured to) so a degradation
    ladder (verifier/resilient.py) can distinguish "try the next tier"
    from "these signatures are invalid"; without a ladder the same
    condition fail-closes to an all-False mask."""


class VerifierCompileError(RuntimeError):
    """A device program failed to lower or compile (a Mosaic refusal, a
    VMEM overflow, an API the installed JAX no longer has); the message
    names the shape and the device. It never clears on retry and says
    the deployment is broken. Serving stacks compile before they take a
    batch (TPUVerifier.warmup), so this surfaces at construction — never
    inside a window that contains faults."""


class Verifier(abc.ABC):
    """Batched vertex-signature verification."""

    @abc.abstractmethod
    def verify_batch(self, vertices: Sequence[Vertex]) -> List[bool]:
        """Accept mask, same order as input. Must be a pure function of
        (vertex bytes, registry) — no randomness — so CPU and TPU backends
        agree bit-for-bit."""

    def verify_rounds(
        self, rounds: Sequence[Sequence[Vertex]]
    ) -> List[List[bool]]:
        """Accept masks for several rounds' batches. Semantically
        equivalent to mapping :meth:`verify_batch`; device backends
        override this to merge the rounds into one padded dispatch
        (amortizing the fixed per-dispatch cost)."""
        return [self.verify_batch(r) for r in rounds]
