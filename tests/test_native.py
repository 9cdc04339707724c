"""Native C++ host component vs the pure-Python oracle.

SURVEY §2a: host-side native code in C++ where the runtime needs it. The
challenge-scalar batch (SHA-512(R||A||M) mod L) is the verify host path's
last per-row loop; the native path must be byte-identical to hashlib and
the verifier must produce identical masks with it on or off. A process
signs its own vertices through libcrypto's Ed25519; every signature must
be byte-identical to the pure-Python RFC 8032 signer's.
"""

import hashlib
import os
import random

import numpy as np
import pytest

from dag_rider_tpu.config import Config
from dag_rider_tpu.consensus.simulator import Simulation
from dag_rider_tpu.core.types import Block, Vertex, VertexID
from dag_rider_tpu.crypto import ed25519
from dag_rider_tpu.obs import spans
from dag_rider_tpu.utils import native
from dag_rider_tpu.verifier.base import KeyRegistry, VertexSigner
from test_ed25519 import RFC_VECTORS

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_challenge_batch_matches_hashlib():
    rng = np.random.default_rng(0)
    n = 300
    rs = rng.integers(0, 256, size=(n, 32)).astype(np.uint8)
    pks = rng.integers(0, 256, size=(n, 32)).astype(np.uint8)
    msgs = [
        rng.integers(0, 256, size=int(ln)).astype(np.uint8).tobytes()
        for ln in rng.integers(0, 400, size=n)
    ]
    out = native.challenge_batch(rs, pks, msgs)
    for i in range(n):
        k = (
            int.from_bytes(
                hashlib.sha512(
                    rs[i].tobytes() + pks[i].tobytes() + msgs[i]
                ).digest(),
                "little",
            )
            % ed25519.L
        )
        assert out[i].tobytes() == k.to_bytes(32, "little"), f"row {i}"


def test_challenge_batch_extreme_digests():
    """Rows engineered near the reduction's edge: all-0xFF digest inputs
    and empty messages."""
    rs = np.full((4, 32), 0xFF, dtype=np.uint8)
    pks = np.full((4, 32), 0xFF, dtype=np.uint8)
    msgs = [b"", b"\xff" * 500, b"\x00", b"x" * 127]
    out = native.challenge_batch(rs, pks, msgs)
    for i in range(4):
        k = (
            int.from_bytes(
                hashlib.sha512(
                    rs[i].tobytes() + pks[i].tobytes() + msgs[i]
                ).digest(),
                "little",
            )
            % ed25519.L
        )
        assert out[i].tobytes() == k.to_bytes(32, "little")


def test_verifier_masks_identical_native_on_off(monkeypatch):
    import dataclasses

    from dag_rider_tpu.core.types import Block, Vertex, VertexID
    from dag_rider_tpu.verifier.base import KeyRegistry, VertexSigner
    from dag_rider_tpu.verifier.tpu import TPUVerifier

    reg, seeds = KeyRegistry.generate(4)
    signers = [VertexSigner(s) for s in seeds]
    vs = []
    for i in range(4):
        v = Vertex(
            id=VertexID(1, i),
            block=Block((f"tx{i}".encode(),)),
            strong_edges=(VertexID(0, 0), VertexID(0, 1), VertexID(0, 2)),
        )
        vs.append(signers[i].sign_vertex(v))
    vs.append(dataclasses.replace(vs[1], signature=b"\x11" * 64))
    ver = TPUVerifier(reg)
    monkeypatch.setenv("DAGRIDER_NATIVE", "1")
    with_native = ver.verify_batch(vs)
    monkeypatch.setenv("DAGRIDER_NATIVE", "0")
    without = ver.verify_batch(vs)
    assert with_native == without == [True, True, True, True, False]


def test_object_is_keyed_on_source_content(tmp_path, monkeypatch):
    """An object from an older challenge.cpp must never be loaded: the
    object's name carries the source's hash, whatever its mtime."""
    import shutil

    src = tmp_path / "challenge.cpp"
    shutil.copy(native._SRC, src)
    monkeypatch.setattr(native, "_DIR", str(tmp_path))
    monkeypatch.setattr(native, "_SRC", str(src))
    monkeypatch.setattr(native, "_lib", None)
    first = native._so_path()
    native.load()
    assert [p.name for p in tmp_path.glob("*.so")] == [first.split("/")[-1]]
    src.write_text(src.read_text() + "\n// edited\n")
    monkeypatch.setattr(native, "_lib", None)
    second = native._so_path()
    assert second != first
    native.load()
    # rebuilt for the new source; the stale object is gone
    assert [p.name for p in tmp_path.glob("*.so")] == [second.split("/")[-1]]


# -- vertex signing ---------------------------------------------------------------


def _python_sign(seed: bytes, message: bytes) -> bytes:
    return ed25519.sign_expanded(*ed25519.expand_seed(seed), message)


@pytest.mark.parametrize("vec", RFC_VECTORS, ids=["test1", "test2", "test3"])
def test_native_signature_matches_rfc8032_vectors(vec):
    seed = bytes.fromhex(vec["seed"])
    msg = bytes.fromhex(vec["msg"])
    sig = native.Ed25519Key.make(seed).sign(msg)
    assert sig == bytes.fromhex(vec["sig"]) == _python_sign(seed, msg)


def test_native_signature_matches_python_on_seeded_pairs():
    """Seeded (seed, message) pairs from the empty message to 64 KiB,
    lengths around SHA-512's 128-byte blocks among them."""
    rng = random.Random(39)
    lengths = [0, 1, 63, 64, 111, 112, 127, 128, 129, 255, 256, 1800, 65536]
    lengths += [rng.randrange(0, 65537) for _ in range(200 - len(lengths))]
    for i, ln in enumerate(lengths):
        seed = rng.randbytes(32)
        msg = rng.randbytes(ln)
        key = native.Ed25519Key.make(seed)
        assert key.sign(msg) == _python_sign(seed, msg), f"pair {i}, {ln} bytes"


def _round_of(n: int, r: int):
    """Round ``r`` of an n-validator committee as a proposer makes it:
    2f+1 strong edges into round r-1, a weak edge into round r-2, a
    block of 32-byte transactions."""
    f = (n - 1) // 3
    rng = random.Random(n * 1000 + r)
    out = []
    for i in range(n):
        strong = tuple(VertexID(r - 1, s) for s in rng.sample(range(n), 2 * f + 1))
        txs = tuple(rng.randbytes(32) for _ in range(rng.randrange(0, 64)))
        out.append(
            Vertex(
                id=VertexID(r, i),
                block=Block(txs),
                strong_edges=strong,
                weak_edges=(VertexID(r - 2, rng.randrange(n)),),
            )
        )
    return out


@pytest.mark.parametrize("n", (4, 64))
def test_signed_vertices_identical_native_and_python(n, monkeypatch):
    _, seeds = KeyRegistry.generate(n)
    signers = [VertexSigner(s) for s in seeds]
    for v in _round_of(n, 3) + _round_of(n, 7):
        monkeypatch.setenv("DAGRIDER_NATIVE", "1")
        fast = signers[v.id.source].sign_vertex(v)
        monkeypatch.setenv("DAGRIDER_NATIVE", "0")
        slow = signers[v.id.source].sign_vertex(v)
        assert fast.signature == slow.signature == _python_sign(
            seeds[v.id.source], v.signing_bytes()
        ), v.id


def _counts() -> dict:
    c = spans.snapshot()["counts"]
    return {k: c.get(k, 0) for k in ("sign.native", "sign.python")}


@pytest.mark.parametrize(
    "flag,path", (("1", "sign.native"), ("", "sign.native"), ("0", "sign.python"))
)
def test_the_counters_name_the_path_that_signed(flag, path, monkeypatch):
    """On by default; ``DAGRIDER_NATIVE=0`` signs in pure Python."""
    monkeypatch.setenv("DAGRIDER_NATIVE", flag)
    _, seeds = KeyRegistry.generate(4)
    signer = VertexSigner(seeds[2])
    before = _counts()
    signed = [signer.sign_vertex(v) for v in _round_of(4, 5)]
    after = _counts()
    assert {k: after[k] - before[k] for k in after} == {
        k: 4 if k == path else 0 for k in after
    }
    assert all(
        s.signature == _python_sign(seeds[2], s.signing_bytes()) for s in signed
    )


def test_a_key_that_cannot_be_made_signs_in_python(monkeypatch):
    """Where libcrypto's Ed25519 cannot be resolved the signer falls back
    to the oracle, and says so through its counter."""
    monkeypatch.setenv("DAGRIDER_NATIVE", "1")
    monkeypatch.setattr(native.Ed25519Key, "make", classmethod(lambda cls, seed: None))
    _, seeds = KeyRegistry.generate(4)
    signer = VertexSigner(seeds[0])
    v = _round_of(4, 2)[0]
    before = _counts()
    assert signer.sign_vertex(v).signature == _python_sign(seeds[0], v.signing_bytes())
    after = _counts()
    assert (after["sign.native"] - before["sign.native"], after["sign.python"] - before["sign.python"]) == (0, 1)


def _committee(flag: str, monkeypatch):
    monkeypatch.setenv("DAGRIDER_NATIVE", flag)
    cfg = Config(n=4, coin="round_robin", propose_empty=True, gc_depth=24)
    sim = Simulation(cfg, verifier="cpu")
    for i in range(cfg.n):
        for k in range(3):
            sim.processes[i].submit(Block((f"p{i}-b{k}".encode().ljust(32, b"."),)))
    before = _counts()
    while min(p.round for p in sim.processes) < 14:
        sim.run(max_messages=cfg.n * cfg.n)
    sim.check_agreement()
    after = _counts()
    logs = [
        [(v.id, v.signature, v.digest()) for v in sim.deliveries[i]]
        for i in range(cfg.n)
    ]
    return logs, {k: after[k] - before[k] for k in after}


def test_committee_runs_identical_native_and_python(monkeypatch):
    """One n=4 committee signed natively and one signed in pure Python
    deliver the same vertices, with the same signatures and digests, in
    the same order at every view."""
    fast, fast_counts = _committee("1", monkeypatch)
    slow, slow_counts = _committee("0", monkeypatch)
    assert fast == slow
    assert len(fast[0]) >= 4 * 8
    assert all(sig is not None for log in fast for _, sig, _ in log)
    assert fast_counts["sign.python"] == 0 < fast_counts["sign.native"]
    assert slow_counts["sign.native"] == 0 < slow_counts["sign.python"]
    assert fast_counts["sign.native"] == slow_counts["sign.python"]


@pytest.mark.parametrize(
    "counts,share",
    (
        ({"sign.native": 1}, 100.0),
        ({"sign.python": 1}, 0.0),
        ({"sign.native": 3, "sign.python": 1}, 75.0),
        ({}, None),
    ),
)
def test_the_benchmark_reads_the_share_signed_natively(counts, share, monkeypatch):
    from benchmarks.harness import cells

    monkeypatch.setattr(spans, "snapshot", lambda: {"spans": {}, "counts": counts})
    read = cells.load_readers(ROOT, [{"name": "sign_native_pct"}])["sign_native_pct"]
    assert read({"trace": {"busy_s": 0.1}}) == share
    assert read({"trace": None}) is None
