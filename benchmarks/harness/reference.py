"""The plain references `correct` is decided against. Nothing here
imports the program.

- :func:`signing_bytes` — the canonical encoding of what a source
  attests to, written out from the wire format's description.
- :class:`Keys` — the committee's deterministic test PKI (seed of
  index i = sha256("dagrider-test-key-" + str(i))), derived here
  independently and compared with the program's registry in set-up.
- :meth:`Keys.verify` — Ed25519 verification by OpenSSL through
  ``cryptography``; :func:`verify_plain` — the same decision written out
  from RFC 8032 in plain Python (too slow for a run's thousands of
  signatures; the tests hold the two to each other on every kind of
  wrong vertex the traffic makes).
- :func:`delivered_order_faults` — agreement and exactly-once over the
  delivered logs of every view.
- :func:`order_unexplained` — one view's delivered order, explained
  again from the delivered vertices' own edges by DAG-Rider's rule.
"""

from __future__ import annotations

import hashlib
import struct
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from cryptography.exceptions import InvalidSignature
from cryptography.hazmat.primitives.asymmetric.ed25519 import (
    Ed25519PrivateKey,
    Ed25519PublicKey,
)
from cryptography.hazmat.primitives.serialization import (
    Encoding,
    PublicFormat,
)

KEY_SEED_PREFIX = b"dagrider-test-key-"
L = 2**252 + 27742317777372353535851937790883648493

Edge = Tuple[int, int]


def encode_edges(label: bytes, edges: Iterable[Edge]) -> bytes:
    ordered = sorted(edges)
    return (
        label
        + struct.pack("<I", len(ordered))
        + b"".join(struct.pack("<II", r, s) for r, s in ordered)
    )


def signing_bytes(
    rnd: int,
    source: int,
    transactions: Sequence[bytes],
    strong: Iterable[Edge] = (),
    weak: Iterable[Edge] = (),
    coin_share: bytes = b"",
    *,
    strong_encoded: Optional[bytes] = None,
) -> bytes:
    """``strong_encoded`` is ``encode_edges(b"S", strong)`` when the
    caller has it already (a round's vertices share their strong edges)."""
    block = struct.pack("<I", len(transactions)) + b"".join(
        struct.pack("<I", len(tx)) + tx for tx in transactions
    )
    return b"".join(
        (
            b"dagrider-vertex-v1",
            struct.pack("<II", rnd, source),
            block,
            strong_encoded
            if strong_encoded is not None
            else encode_edges(b"S", strong),
            encode_edges(b"W", weak),
            b"C",
            struct.pack("<I", len(coin_share)),
            coin_share,
        )
    )


class Keys:
    """The committee's signing keys and public keys, by source index."""

    def __init__(self, n: int):
        self._sk = [
            Ed25519PrivateKey.from_private_bytes(
                hashlib.sha256(KEY_SEED_PREFIX + str(i).encode()).digest()
            )
            for i in range(n)
        ]
        self.public = [
            sk.public_key().public_bytes(Encoding.Raw, PublicFormat.Raw)
            for sk in self._sk
        ]
        self._pk = [sk.public_key() for sk in self._sk]

    def sign(self, source: int, message: bytes) -> bytes:
        return self._sk[source].sign(message)

    def verify(self, source: int, message: bytes, signature: bytes) -> bool:
        """The verdict the configuration guarantees: the signature is 64
        bytes and verifies under the key of the CLAIMED source."""
        if not 0 <= source < len(self._pk) or len(signature) != 64:
            return False
        return verify_with(self._pk[source], message, signature)


def verify_with(pk: Ed25519PublicKey, message: bytes, signature: bytes) -> bool:
    try:
        pk.verify(signature, message)
    except InvalidSignature:
        return False
    return True


# -- RFC 8032 section 5.1 in plain Python ---------------------------------

_P = 2**255 - 19
_D = -121665 * pow(121666, _P - 2, _P) % _P
_SQRT_M1 = pow(2, (_P - 1) // 4, _P)


def _recover_x(y: int, sign: int) -> Optional[int]:
    if y >= _P:
        return None
    x2 = (y * y - 1) * pow(_D * y * y + 1, _P - 2, _P) % _P
    if x2 == 0:
        return None if sign else 0
    x = pow(x2, (_P + 3) // 8, _P)
    if (x * x - x2) % _P:
        x = x * _SQRT_M1 % _P
    if (x * x - x2) % _P:
        return None
    return _P - x if x & 1 != sign else x


def _decompress(data: bytes):
    y = int.from_bytes(data, "little")
    sign, y = y >> 255, y & ((1 << 255) - 1)
    x = _recover_x(y, sign)
    return None if x is None else (x, y, 1, x * y % _P)


def _add(p, q):
    a = (p[1] - p[0]) * (q[1] - q[0]) % _P
    b = (p[1] + p[0]) * (q[1] + q[0]) % _P
    c = 2 * p[3] * q[3] * _D % _P
    d = 2 * p[2] * q[2] % _P
    e, f, g, h = b - a, d - c, d + c, b + a
    return (e * f % _P, g * h % _P, f * g % _P, e * h % _P)


def _mul(s: int, p):
    q = (0, 1, 1, 0)
    while s:
        if s & 1:
            q = _add(q, p)
        p = _add(p, p)
        s >>= 1
    return q


_BY = 4 * pow(5, _P - 2, _P) % _P
_B = (_recover_x(_BY, 0), _BY, 1, _recover_x(_BY, 0) * _BY % _P)


def verify_plain(public_key: bytes, message: bytes, signature: bytes) -> bool:
    """[s]B == R + [k]A with s < L (RFC 8032 section 5.1.7)."""
    if len(public_key) != 32 or len(signature) != 64:
        return False
    a, r = _decompress(public_key), _decompress(signature[:32])
    s = int.from_bytes(signature[32:], "little")
    if a is None or r is None or s >= L:
        return False
    k = int.from_bytes(
        hashlib.sha512(signature[:32] + public_key + message).digest(),
        "little",
    ) % L
    lhs, rhs = _mul(s, _B), _add(r, _mul(k, a))
    return (
        (lhs[0] * rhs[2] - rhs[0] * lhs[2]) % _P == 0
        and (lhs[1] * rhs[2] - rhs[1] * lhs[2]) % _P == 0
    )


# -- the delivered logs ----------------------------------------------------


def delivered_order_faults(logs: Sequence[Sequence[tuple]]) -> Dict[str, int]:
    """``logs[i]`` is view i's delivered sequence of hashable records.
    Agreement: every log is a prefix of the longest. Exactly-once: no
    record twice in the longest. Returns the count of views that
    diverge and of records delivered twice."""
    longest = max(logs, key=len) if logs else ()
    diverged = 0
    for log in logs:
        if log is longest:
            continue
        if any(a != b for a, b in zip(log, longest)):
            diverged += 1
    seen: set = set()
    twice = 0
    for rec in longest:
        if rec in seen:
            twice += 1
        seen.add(rec)
    return {"views_diverged": diverged, "records_twice": twice}


def _history(leader: Edge, edges: Dict[Edge, Sequence[Edge]], delivered: set, floor: int):
    """The causal history of ``leader`` over strong and weak edges that
    is not delivered yet and lies above round ``floor``; None where it
    leads to a vertex the log never delivers."""
    out, stack = {leader}, [leader]
    while stack:
        for e in edges[stack.pop()]:
            e = (e[0], e[1])
            if e[0] <= floor or e in delivered or e in out:
                continue
            if e not in edges:
                return None
            out.add(e)
            stack.append(e)
    return out


def order_unexplained(
    log: Sequence[Tuple[int, int, Sequence[Edge]]], *, gc_depth: int, wave_length: int
) -> int:
    """``log`` is one view's delivered sequence of ``(round, source,
    edges)``, genesis (round 0) left out. DAG-Rider's rule (the paper's
    Algorithm 3, ``order_vertices``): the log is a run of chunks, each
    the causal history, as far as it is not delivered yet, of one wave's
    leader, a vertex of the wave's first round, in ascending (round,
    source), with the leaders' waves ascending; the configuration's
    ``gc_depth`` leaves out what lies that many rounds or more under the
    leader. Which vertex the coin makes a leader, and whether its wave
    had the votes, is not recomputed: any first-round vertex may lead.
    Returns how many records are left from the first chunk that the rule
    does not explain; 0 when it explains the whole log."""
    ids = [(r, s) for r, s, _ in log]
    edges = {(r, s): e for r, s, e in log}
    delivered: set = set()
    at, led = 0, 0
    while at < len(ids):
        end = None
        for e in range(at, len(ids)):
            rnd = ids[e][0]
            if e > at and ids[e] <= ids[e - 1]:
                break  # a chunk ascends
            # a leader is alone of its round in its own history
            if rnd % wave_length != 1 % wave_length or rnd <= led:
                continue
            if e > at and ids[e - 1][0] == rnd:
                continue
            want = _history(ids[e], edges, delivered, max(0, rnd - gc_depth))
            if want is not None and sorted(want) == ids[at : e + 1]:
                end = e
                break
        if end is None:
            return len(ids) - at
        delivered.update(ids[at : end + 1])
        led = ids[end][0]
        at = end + 1
    return 0
