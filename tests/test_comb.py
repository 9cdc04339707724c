"""Comb verify path vs windowed path vs CPU oracle — bit-identical masks.

The comb path (ops/comb.py, TPUVerifier default) replaces the per-vertex
variable-base scalar multiplication with fixed-key table sums; its accept
mask must match both the original windowed device program and the host
RFC 8032 oracle on every batch, including adversarial ones — the
north-star CPU-vs-TPU commit-order equivalence reduces to this.
"""

import dataclasses

import pytest

from dag_rider_tpu.core.types import Block, Vertex, VertexID
from dag_rider_tpu.crypto import ed25519
from dag_rider_tpu.verifier.base import KeyRegistry, VertexSigner
from dag_rider_tpu.verifier.cpu import CPUVerifier
from dag_rider_tpu.verifier.tpu import TPUVerifier


@pytest.fixture(scope="module")
def setup():
    reg, seeds = KeyRegistry.generate(6)
    signers = [VertexSigner(s) for s in seeds]
    vs = []
    for i in range(6):
        v = Vertex(
            id=VertexID(2, i),
            block=Block((f"tx-{i}".encode(),)),
            strong_edges=(VertexID(1, 0), VertexID(1, 1), VertexID(1, 2)),
        )
        vs.append(signers[i].sign_vertex(v))
    return reg, vs


def _adversarial(vs):
    s_big = int.to_bytes(
        int.from_bytes(vs[2].signature[32:], "little") + ed25519.L,
        32,
        "little",
    )
    y_bad = int.to_bytes(2**255 - 10, 32, "little")
    flip = bytearray(vs[4].signature)
    flip[17] ^= 0x40
    return [
        dataclasses.replace(vs[0], signature=b"\x00" * 64),
        dataclasses.replace(vs[1], block=Block((b"tampered",))),
        dataclasses.replace(vs[2], signature=vs[2].signature[:32] + s_big),
        dataclasses.replace(vs[3], signature=y_bad + vs[3].signature[32:]),
        dataclasses.replace(vs[4], signature=bytes(flip)),
        dataclasses.replace(vs[5], id=VertexID(2, 999)),
    ]


def test_comb_mask_matches_windowed_and_cpu(setup):
    reg, vs = setup
    batch = vs + _adversarial(vs)
    cpu = CPUVerifier(reg).verify_batch(batch)
    windowed = TPUVerifier(reg, comb=False).verify_batch(batch)
    comb = TPUVerifier(reg).verify_batch(batch)
    assert cpu == windowed == comb
    assert cpu[: len(vs)] == [True] * len(vs)
    assert not any(cpu[len(vs) :])


def test_comb_fuzz_masks_match_cpu_oracle(setup):
    """Seeded corruption fuzz: random byte/bit damage across signature,
    R, key index, block and edges must always produce the oracle's mask
    through the comb path (the north-star equivalence is only as strong
    as its behavior on garbage)."""
    import random

    reg, vs = setup
    rng = random.Random(1234)
    tv = TPUVerifier(reg, comb=True)
    cpu = CPUVerifier(reg)
    batch = []
    for _ in range(24):
        v = rng.choice(vs)
        mode = rng.randrange(5)
        if mode == 0:  # signature damage
            sig = bytearray(v.signature)
            for _ in range(rng.randrange(1, 4)):
                sig[rng.randrange(64)] ^= 1 << rng.randrange(8)
            v = dataclasses.replace(v, signature=bytes(sig))
        elif mode == 1:  # payload damage (signed bytes change)
            v = dataclasses.replace(
                v, block=Block((rng.randbytes(rng.randrange(1, 40)),))
            )
        elif mode == 2:  # source redirect (wrong key)
            v = dataclasses.replace(
                v, id=VertexID(v.id.round, rng.randrange(reg.n))
            )
        elif mode == 3:  # edge tamper
            v = dataclasses.replace(
                v,
                strong_edges=tuple(
                    VertexID(e.round, (e.source + 1) % reg.n)
                    for e in v.strong_edges
                ),
            )
        # mode 4: leave valid
        batch.append(v)
    assert tv.verify_batch(batch) == cpu.verify_batch(batch)


def test_verify_rounds_merged_matches_per_round(setup):
    reg, vs = setup
    v = TPUVerifier(reg, comb=True)
    rounds = [vs[:2], [], vs[2:5], _adversarial(vs)[:3]]
    merged = v.verify_rounds(rounds)
    per_round = [v.verify_batch(r) for r in rounds]
    assert merged == per_round
    assert merged[1] == []


def _affine(p4x22):
    from dag_rider_tpu.ops import field as F

    X = F.from_limbs(p4x22[0]) % F.P_INT
    Y = F.from_limbs(p4x22[1]) % F.P_INT
    Z = F.from_limbs(p4x22[2]) % F.P_INT
    zi = pow(Z, F.P_INT - 2, F.P_INT)
    return X * zi % F.P_INT, Y * zi % F.P_INT


def _host_affine(pt):
    from dag_rider_tpu.ops import field as F

    X, Y, Z, _ = pt
    zi = pow(Z, F.P_INT - 2, F.P_INT)
    return X * zi % F.P_INT, Y * zi % F.P_INT


def test_comb_key_table_entries_match_host(setup):
    """Spot-check device-built comb tables: TABLE[key, w, d] == d*16^w*A."""
    import numpy as np

    from dag_rider_tpu.crypto import ed25519 as host
    from dag_rider_tpu.ops import field as F

    reg, _ = setup
    tv = TPUVerifier(reg, comb=True)
    tables, _ = tv._comb_tables()  # padded [rows, 128] gather layout
    tab = np.asarray(tables)[:, : 4 * F.LIMBS].reshape(
        reg.n, 64, 16, 4, F.LIMBS
    )
    for key, w, d in [(0, 0, 1), (1, 0, 7), (2, 3, 15), (5, 63, 9)]:
        a_pt = host.point_decompress(reg.public_keys[key])
        want = _host_affine(host.scalar_mult(d * (16**w), a_pt))
        assert _affine(tab[key, w, d]) == want, (key, w, d)
