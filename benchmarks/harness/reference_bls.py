"""The plain reference the ``sidecar_cert`` cell's verdicts are decided
against. Nothing here imports the program.

- :class:`Keys` — the committee's BLS secret keys, derived again from the
  test PKI's seed (sk of index i = sha256("dagrider-test-key-|bls|" + i)
  mod r); no public key is needed: the rule below reads secrets.
- G1 arithmetic of BLS12-381 (y^2 = x^3 + 4 over Fp) in Jacobian
  coordinates, the compressed encoding (flags 0x80 compressed, 0x40
  infinity, 0x20 the larger y), decompression with the r-order subgroup
  check, and :func:`hash_to_g1` written from the try-and-increment
  definition: x = SHA-512(domain || ctr as 4 bytes little-endian || msg)
  mod p for ctr = 0, 1, ... until x^3 + 4 is a square, the smaller root,
  then the cofactor h1 = (x_BLS - 1)^2 / 3 cleared; a candidate whose
  cleared point is the identity is passed over.
- :func:`verdict` — a certificate is valid iff its signers are a quorum
  (2f+1) of distinct committee indices in increasing order, its digests
  are parallel to them, and its aggregate decompresses to
  ``sum(sk_i * H(d_i))``. Exact without a pairing: e(., G2) is injective
  on the r-torsion, so ``e(agg, G2) == prod e(H(d_i), pk_i)`` holds iff
  the two G1 points are equal.
- :func:`make_pool` — the seeded rounds' certificates the clients send,
  honest ones as an aggregator assembles them at quorum and one of each
  wrong kind (:data:`KINDS`).
"""

from __future__ import annotations

import hashlib
import random
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

P = 0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFAAAB
R = 0x73EDA753299D7D483339D80809A1D80553BDA402FFFE5BFEFFFFFFFF00000001
X_BLS = -0xD201000000010000
H1 = (X_BLS - 1) ** 2 // 3
DOMAIN = b"dagrider-coin-v1"
KEY_SEED = b"dagrider-test-key-|bls|"

#: the wrong kinds, one certificate of each in a pool
KINDS = (
    "share_left_out",  # one signer's share left out of the aggregate
    "digest_swapped",  # one digest swapped for another round's
    "signer_swapped",  # one signer index swapped for a non-signer's
    "under_quorum",  # one signer fewer than 2f+1
    "not_in_g1",  # an aggregate on the curve, outside the r-order subgroup
    "duplicated_signer",  # one signer listed twice
)

Point = Optional[Tuple[int, int]]  # affine; None is the identity
Jac = Optional[Tuple[int, int, int]]


class Cert(NamedTuple):
    """A round certificate as it goes on the wire, in plain fields."""

    rnd: int
    signers: Tuple[int, ...]
    digests: Tuple[bytes, ...]
    agg: bytes
    wrong: str  # "" for an honest certificate, else the kind


def quorum(n: int) -> int:
    return 2 * ((n - 1) // 3) + 1


# -- G1 ---------------------------------------------------------------------


def _dbl(p: Jac) -> Jac:
    if p is None or p[1] == 0:
        return None
    x, y, z = p
    a, b = x * x % P, y * y % P
    c = b * b % P
    d = 2 * ((x + b) ** 2 - a - c) % P
    e = 3 * a % P
    x3 = (e * e - 2 * d) % P
    return x3, (e * (d - x3) - 8 * c) % P, 2 * y * z % P


def _add(p: Jac, q: Jac) -> Jac:
    if p is None:
        return q
    if q is None:
        return p
    x1, y1, z1 = p
    x2, y2, z2 = q
    z1z1, z2z2 = z1 * z1 % P, z2 * z2 % P
    u1, u2 = x1 * z2z2 % P, x2 * z1z1 % P
    s1, s2 = y1 * z2 * z2z2 % P, y2 * z1 * z1z1 % P
    h, r = (u2 - u1) % P, 2 * (s2 - s1) % P
    if h == 0:
        return _dbl(p) if r == 0 else None
    i = 4 * h * h % P
    j, v = h * i % P, u1 * i % P
    x3 = (r * r - j - 2 * v) % P
    y3 = (r * (v - x3) - 2 * s1 * j) % P
    return x3, y3, ((z1 + z2) ** 2 - z1z1 - z2z2) * h % P


def _jac(p: Point) -> Jac:
    return None if p is None else (p[0], p[1], 1)


def _affine(p: Jac) -> Point:
    if p is None or p[2] == 0:
        return None
    zi = pow(p[2], P - 2, P)
    zi2 = zi * zi % P
    return p[0] * zi2 % P, p[1] * zi2 * zi % P


def mul(k: int, p: Point) -> Point:
    """[k]P by double-and-add; k is not reduced (the cofactor's points
    are not in the r-torsion)."""
    acc: Jac = None
    base = _jac(p)
    for bit in bin(k)[2:] if k > 0 else "":
        acc = _add(_dbl(acc), base) if bit == "1" else _dbl(acc)
    return _affine(acc)


def add(p: Point, q: Point) -> Point:
    return _affine(_add(_jac(p), _jac(q)))


def msm(scalars: Sequence[int], points: Sequence[Point], window: int = 6) -> Point:
    """sum [k_i] P_i by buckets (Pippenger)."""
    top = max((k.bit_length() for k in scalars), default=0)
    acc: Jac = None
    mask = (1 << window) - 1
    pts = [_jac(p) for p in points]
    for w in reversed(range((top + window - 1) // window)):
        for _ in range(window):
            acc = _dbl(acc)
        buckets: List[Jac] = [None] * (mask + 1)
        for k, p in zip(scalars, pts):
            d = (k >> (w * window)) & mask
            if d:
                buckets[d] = _add(buckets[d], p)
        running: Jac = None
        for d in range(mask, 0, -1):
            running = _add(running, buckets[d])
            acc = _add(acc, running)
    return _affine(acc)


def on_curve(p: Point) -> bool:
    return p is not None and (p[1] * p[1] - p[0] ** 3 - 4) % P == 0


def in_subgroup(p: Point) -> bool:
    return on_curve(p) and mul(R, p) is None


def compress(p: Point) -> bytes:
    if p is None:
        return bytes([0xC0]) + bytes(47)
    out = bytearray(p[0].to_bytes(48, "big"))
    out[0] |= 0x80 | (0x20 if p[1] > (P - 1) // 2 else 0)
    return bytes(out)


def decompress(data: bytes) -> Point:
    """The G1 point of a compressed encoding, or None: not 48 bytes, not
    flagged compressed, the infinity (never a signature), x >= p, off the
    curve, or outside the r-order subgroup."""
    if len(data) != 48 or not data[0] & 0x80 or data[0] & 0x40:
        return None
    x = int.from_bytes(bytes([data[0] & 0x1F]) + data[1:], "big")
    if x >= P:
        return None
    y2 = (x**3 + 4) % P
    y = pow(y2, (P + 1) // 4, P)
    if y * y % P != y2:
        return None
    if (y > (P - 1) // 2) != bool(data[0] & 0x20):
        y = P - y
    return (x, y) if in_subgroup((x, y)) else None


def _candidates(msg: bytes, domain: bytes):
    """The curve points of the try-and-increment map, before the cofactor
    is cleared, in the order the counter finds them."""
    ctr = 0
    while True:
        h = hashlib.sha512(domain + ctr.to_bytes(4, "little") + msg).digest()
        x = int.from_bytes(h, "big") % P
        y2 = (x**3 + 4) % P
        y = pow(y2, (P + 1) // 4, P)
        if y * y % P == y2:
            yield x, min(y, P - y)
        ctr += 1


def hash_to_g1(msg: bytes, domain: bytes = DOMAIN) -> Point:
    for q in _candidates(msg, domain):
        pt = mul(H1, q)
        if pt is not None:
            return pt
    raise AssertionError("unreachable")


# -- the committee and the rule ----------------------------------------------


class Keys:
    def __init__(self, n: int):
        self.n = n
        self.secret = [
            int.from_bytes(hashlib.sha256(KEY_SEED + str(i).encode()).digest(), "big") % R
            for i in range(n)
        ]
        self._h: Dict[bytes, Point] = {}

    def h(self, digest: bytes) -> Point:
        if digest not in self._h:
            self._h[digest] = hash_to_g1(digest)
        return self._h[digest]

    def aggregate(self, signers: Sequence[int], digests: Sequence[bytes]) -> Point:
        """sum sk_signer * H(digest): the aggregate of the signers' shares."""
        return msm([self.secret[s] for s in signers], [self.h(d) for d in digests])


def verdict(keys: Keys, cert: Cert) -> bool:
    s = cert.signers
    if len(s) < quorum(keys.n) or len(s) != len(cert.digests):
        return False
    if any(b <= a for a, b in zip(s, s[1:])) or s[0] < 0 or s[-1] >= keys.n:
        return False
    agg = decompress(cert.agg)
    return agg is not None and agg == keys.aggregate(s, cert.digests)


def expected_verdicts(keys: Keys, pool: Sequence[Cert]) -> List[bool]:
    return [verdict(keys, c) for c in pool]


# -- the pool ------------------------------------------------------------------


def _cofactor_point() -> Point:
    """A point of the cofactor's order: [r] of a curve point outside G1."""
    for q in _candidates(b"not in G1", DOMAIN):
        t = mul(R, q)
        if t is not None:
            return t
    raise AssertionError("unreachable")


def make_pool(keys: Keys, rounds: int, kinds: Sequence[str], seed: int) -> List[Cert]:
    """``rounds`` certificates of rounds 1..rounds: each round's quorum of
    signers and their 32-byte digests drawn from ``seed``; one
    certificate of each of ``kinds`` wrong, at rounds drawn from the
    seed; the rest honest (sorted signers, the sum of their shares)."""
    n, q = keys.n, quorum(keys.n)
    if len(kinds) > rounds or not set(kinds) <= set(KINDS):
        raise ValueError(f"kinds {kinds!r} do not fit {rounds} rounds")
    rng = random.Random(seed)
    drawn = []
    for _ in range(rounds):
        signers = sorted(rng.sample(range(n), q))
        drawn.append((signers, [rng.randbytes(32) for _ in signers]))
    wrong = dict(zip(rng.sample(range(rounds), len(kinds)), kinds))
    pool = []
    for r, (signers, digests) in enumerate(drawn):
        kind = wrong.get(r, "")
        j = rng.randrange(q - 1)
        shares = list(zip(signers, digests))  # what the aggregate sums
        if kind == "share_left_out":
            shares = shares[:j] + shares[j + 1 :]
        elif kind == "digest_swapped":
            digests = digests[:j] + [drawn[(r + 1) % rounds][1][j]] + digests[j + 1 :]
        elif kind == "signer_swapped":
            k = rng.choice(sorted(set(range(n)) - set(signers)))
            pairs = sorted(zip(signers[:j] + [k] + signers[j + 1 :], digests))
            signers, digests = [s for s, _ in pairs], [d for _, d in pairs]
        elif kind == "under_quorum":
            signers, digests = signers[:j] + signers[j + 1 :], digests[:j] + digests[j + 1 :]
            shares = list(zip(signers, digests))
        elif kind == "duplicated_signer":
            signers = signers[: j + 1] + [signers[j]] + signers[j + 2 :]
            digests = digests[: j + 1] + [digests[j]] + digests[j + 2 :]
            shares = list(zip(signers, digests))
        agg = keys.aggregate([s for s, _ in shares], [d for _, d in shares])
        if kind == "not_in_g1":
            agg = add(agg, _cofactor_point())
        pool.append(Cert(r + 1, tuple(signers), tuple(digests), compress(agg), kind))
    return pool
