"""Whole DAG rounds of signed vertices, made from the seed, with a fixed
number of each round's vertices wrong — one way each — so that an
all-accept answer is wrong. Signed here with the committee's test keys
(:class:`reference.Keys`), over this package's own encoding of what a
vertex attests to; the program is handed only the finished vertices.

The five ways (the kinds ``chip_smoke.adversarial_batch`` makes under an
unchanged registry): a flipped signature bit, s + L (non-canonical), a
signature under another source's index, a truncated signature, a
tampered block.
"""

from __future__ import annotations

import random
from typing import List, NamedTuple, Sequence, Tuple

from benchmarks.harness import reference

KINDS = ("flipped_bit", "s_plus_l", "other_source", "truncated", "tampered_block")


class Signed(NamedTuple):
    """A vertex as it goes on the wire, in plain fields."""

    rnd: int
    source: int  # the source it CLAIMS
    transactions: Tuple[bytes, ...]
    strong: Tuple[Tuple[int, int], ...]
    signature: bytes
    wrong: str  # "" for an honest vertex, else the kind


def quorum(n: int) -> int:
    return 2 * ((n - 1) // 3) + 1


def sign(keys: reference.Keys, rnd, source, txs, strong, strong_enc=None) -> Signed:
    msg = reference.signing_bytes(rnd, source, txs, strong, strong_encoded=strong_enc)
    return Signed(rnd, source, tuple(txs), tuple(strong), keys.sign(source, msg), "")


def corrupt(v: Signed, kind: str, n: int, rng: random.Random) -> Signed:
    sig = v.signature
    if kind == "flipped_bit":
        b = bytearray(sig)
        b[rng.randrange(64)] ^= 1 << rng.randrange(8)
        return v._replace(signature=bytes(b), wrong=kind)
    if kind == "s_plus_l":
        s = int.from_bytes(sig[32:], "little") + reference.L
        return v._replace(signature=sig[:32] + s.to_bytes(32, "little"), wrong=kind)
    if kind == "other_source":
        return v._replace(source=(v.source + 1) % n, wrong=kind)
    if kind == "truncated":
        return v._replace(signature=sig[:63], wrong=kind)
    if kind == "tampered_block":
        return v._replace(transactions=(b"tampered",), wrong=kind)
    raise ValueError(f"unknown kind {kind!r}")


def make_pool(
    keys: reference.Keys, *, n: int, rounds: int, wrong_per_round: int, seed: int
) -> List[List[Signed]]:
    """``rounds`` rounds of n vertices, each with 2f+1 strong edges and
    one 32-byte transaction; ``wrong_per_round`` of them wrong, the
    places and the kinds' order drawn from the seed."""
    rng = random.Random(seed)
    q = quorum(n)
    pool = []
    for r in range(1, rounds + 1):
        strong = tuple((r - 1, s) for s in range(q))
        strong_enc = reference.encode_edges(b"S", strong)
        vs = [
            sign(
                keys, r, i,
                (f"s{seed}-r{r}-tx-{i}".encode().ljust(32, b"."),),
                strong, strong_enc,
            )
            for i in range(n)
        ]
        first = rng.randrange(len(KINDS))
        for j, i in enumerate(sorted(rng.sample(range(n), wrong_per_round))):
            vs[i] = corrupt(vs[i], KINDS[(first + j) % len(KINDS)], n, rng)
        pool.append(vs)
    return pool


def to_vertices(round_: Sequence[Signed]) -> list:
    """The program's input objects for one round."""
    from dag_rider_tpu.core.types import Block, Vertex, VertexID

    edges = {}
    out = []
    for v in round_:
        se = edges.get(v.strong)
        if se is None:
            se = edges[v.strong] = tuple(VertexID(r, s) for r, s in v.strong)
        out.append(
            Vertex(
                id=VertexID(v.rnd, v.source),
                block=Block(v.transactions),
                strong_edges=se,
                signature=v.signature,
            )
        )
    return out


def expected_mask(keys: reference.Keys, round_: Sequence[Signed]) -> List[bool]:
    """The reference's verdicts for one round, each recomputed from the
    bytes that went on the wire. Raises if a verdict disagrees with how
    the vertex was made: the traffic itself would then be at fault."""
    out = []
    for v in round_:
        ok = keys.verify(
            v.source,
            reference.signing_bytes(v.rnd, v.source, v.transactions, v.strong),
            v.signature,
        )
        if ok != (v.wrong == ""):
            raise AssertionError(
                f"reference verdict {ok} for a vertex made {v.wrong or 'honest'!r}"
            )
        out.append(ok)
    return out
