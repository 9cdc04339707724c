"""Native C++ host component vs the pure-Python oracle.

SURVEY §2a: host-side native code in C++ where the runtime needs it. The
challenge-scalar batch (SHA-512(R||A||M) mod L) is the verify host path's
last per-row loop; the native path must be byte-identical to hashlib and
the verifier must produce identical masks with it on or off.
"""

import hashlib

import numpy as np

from dag_rider_tpu.crypto import ed25519
from dag_rider_tpu.utils import native



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
