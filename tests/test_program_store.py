"""The comb program kept in its lowered form beside the compile cache
(``TPUVerifier._aot_lower``, ``utils/jaxcache.py``): a second verifier of
the same shape compiles it without tracing a kernel and agrees with the
host oracle; every part of the program's name is a miss when it changes;
a stored file that is not whole, or does not deserialize or compile, is
a miss that is lowered and written again; writers racing on one name
leave one whole file; and the benchmark's reader of the two counters.

On the CPU backend at bucket 16 (the jnp tree), with the store pointed
at a temporary directory.
"""

import dataclasses
import os
import re
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dag_rider_tpu.core.types import Block, Vertex, VertexID
from dag_rider_tpu.obs import spans
from dag_rider_tpu.utils import jaxcache
from dag_rider_tpu.verifier import tpu
from dag_rider_tpu.verifier.base import KeyRegistry, VertexSigner
from dag_rider_tpu.verifier.cpu import CPUVerifier
from dag_rider_tpu.verifier.tpu import TPUVerifier

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOADED = "verifier.program_loaded"
LOWERED = "verifier.program_lowered"


def counted(name: str) -> int:
    return spans.snapshot()["counts"].get(name, 0)


def point_store_at(monkeypatch, path) -> None:
    monkeypatch.setattr(jaxcache, "program_dir", lambda: str(path))


@pytest.fixture(scope="module")
def keys():
    return KeyRegistry.generate(8)


@pytest.fixture(scope="module")
def pool(keys):
    """Eight sound vertices and the wrong ones: a zeroed, a borrowed and
    a missing signature, a tampered block, s + L, and a flipped bit."""
    signers = [VertexSigner(s) for s in keys[1]]
    sound = [
        signers[i].sign_vertex(
            Vertex(
                id=VertexID(2, i),
                block=Block((f"tx-{i}".encode(),)),
                strong_edges=(VertexID(1, 0), VertexID(1, 1)),
            )
        )
        for i in range(8)
    ]
    s_plus_l = (
        int.from_bytes(sound[4].signature[32:], "little") + tpu.ed25519.L
    ).to_bytes(32, "little")
    flipped = bytearray(sound[5].signature)
    flipped[7] ^= 0x10
    wrong = [
        dataclasses.replace(sound[0], signature=bytes(64)),
        dataclasses.replace(sound[1], signature=sound[2].signature),
        dataclasses.replace(sound[3], block=Block((b"tampered",))),
        dataclasses.replace(sound[4], signature=sound[4].signature[:32] + s_plus_l),
        dataclasses.replace(sound[5], signature=bytes(flipped)),
        dataclasses.replace(sound[6], signature=None),
    ]
    return sound + wrong


@pytest.fixture(scope="module")
def stored(keys, tmp_path_factory):
    """A first verifier's warm-up against an empty store: what it counted
    and the one file it left there."""
    root = tmp_path_factory.mktemp("programs")
    with pytest.MonkeyPatch.context() as mp:
        point_store_at(mp, root)
        before = (counted(LOADED), counted(LOWERED))
        TPUVerifier(keys[0]).warmup()
        after = (counted(LOADED), counted(LOWERED))
    (name,) = os.listdir(root)
    return {
        "name": name,
        "bytes": (root / name).read_bytes(),
        "loaded": after[0] - before[0],
        "lowered": after[1] - before[1],
    }


class _Refuses:
    """Stands in for the traced program: any use of it is a trace."""

    def __getattr__(self, attr):
        raise AssertionError(f"the comb program was traced ({attr})")

    def __call__(self, *args, **kwargs):
        raise AssertionError("the comb program was traced")


def _no_trace(*args, **kwargs):
    raise AssertionError("the comb program was exported")


# -- a hit --------------------------------------------------------------------


def test_a_second_verifier_loads_the_program_without_a_trace(
    keys, pool, stored, tmp_path, monkeypatch
):
    assert (stored["loaded"], stored["lowered"]) == (0, 1)
    assert stored["name"].startswith("comb-")
    point_store_at(monkeypatch, tmp_path)
    (tmp_path / stored["name"]).write_bytes(stored["bytes"])
    monkeypatch.setattr(jax.export, "export", _no_trace)
    monkeypatch.setattr(tpu, "_device_verify_comb", _Refuses())

    before = (counted(LOADED), counted(LOWERED))
    v = TPUVerifier(keys[0])
    assert v.warmup() > 0.0
    assert (counted(LOADED), counted(LOWERED)) == (before[0] + 1, before[1])
    assert list(v._aot) == [(16, "jnp")]
    want = CPUVerifier(keys[0]).verify_batch(pool)
    assert want == [True] * 8 + [False] * 6
    assert v.verify_batch(pool) == want
    assert list(v._aot) == [(16, "jnp")]
    # a third verifier of the shape in this process takes the program the
    # second one built, from jax's in-process caches
    monkeypatch.setattr(jax.export, "deserialize", _no_trace)
    TPUVerifier(keys[0]).warmup()
    assert (counted(LOADED), counted(LOWERED)) == (before[0] + 2, before[1])


# -- the name -----------------------------------------------------------------


def _args(size: int, key_rows: int) -> tuple:
    return (
        jax.ShapeDtypeStruct((size, tpu._U8_COLS), jnp.uint8),
        jax.ShapeDtypeStruct((size, 23), jnp.int32),
        jax.ShapeDtypeStruct((key_rows, 128), jnp.int32),
        jax.ShapeDtypeStruct((1024, 128), jnp.int32),
    )


class _Client:
    platform_version = "another build"


class _Device:
    client = _Client()


PARTS = (
    "sources", "size", "impl", "table_shape", "jax", "jaxlib", "platform",
    "platform_version", "device_kind", "x64", "ops_knob",
)


@pytest.mark.parametrize("part", PARTS)
def test_each_part_of_the_name_is_a_miss_when_it_changes(
    part, keys, stored, tmp_path, monkeypatch
):
    point_store_at(monkeypatch, tmp_path)
    v = TPUVerifier(keys[0])
    size, impl, args = 16, "jnp", _args(16, 8 * 1024)
    name = v._program_name(size, impl, args)
    assert v._program_name(size, impl, _args(16, 8 * 1024)) == name
    jaxcache.write_program(name, stored["bytes"])
    assert jaxcache.read_program(name) == stored["bytes"]

    if part == "sources":
        monkeypatch.setattr(tpu, "_source_digest", lambda: "0" * 64)
    elif part == "size":
        size = 32
    elif part == "impl":
        impl = "pallas"
    elif part == "table_shape":
        args = _args(16, 9 * 1024)
    elif part == "jax":
        monkeypatch.setattr(jax, "__version__", "0.0.1")
    elif part == "jaxlib":
        monkeypatch.setattr(tpu.jaxlib, "__version__", "0.0.1")
    elif part == "platform":
        v.platform = "tpu"
    elif part == "platform_version":
        monkeypatch.setattr(jax, "devices", lambda *a: [_Device()])
    elif part == "device_kind":
        v.device_kind = "TPU v5 lite"
    elif part == "ops_knob":
        (knob,) = tpu._OPS_KNOBS
        monkeypatch.setenv(knob, "0")
    if part == "x64":
        with jax.enable_x64(True):
            other = v._program_name(size, impl, args)
    else:
        other = v._program_name(size, impl, args)
    assert other != name
    assert jaxcache.read_program(other) is None


def test_the_name_holds_every_knob_that_ops_reads():
    ops = os.path.join(ROOT, "dag_rider_tpu", "ops")
    read = set()
    for f in os.listdir(ops):
        if f.endswith(".py"):
            with open(os.path.join(ops, f)) as fh:
                read |= set(re.findall(r"env_\w+\(\s*\"(DAGRIDER_\w+)\"", fh.read()))
    assert read == set(tpu._OPS_KNOBS)


# -- a file that will not load --------------------------------------------------


def _other_program() -> bytes:
    """A whole, loadable export of another program: it deserializes,
    and its signature is not the comb program's."""
    spec = jax.ShapeDtypeStruct((3,), jnp.int32)
    return jax.export.export(jax.jit(lambda x: x + 1))(spec).serialize()


@pytest.mark.parametrize(
    "spoil", ("truncated", "garbage", "not_an_export", "another_program")
)
def test_a_stored_program_that_will_not_load_is_lowered_and_written_again(
    spoil, keys, pool, stored, tmp_path, monkeypatch
):
    point_store_at(monkeypatch, tmp_path)
    name, good = stored["name"], stored["bytes"]
    path = tmp_path / name
    if spoil == "truncated":
        path.write_bytes(good[: len(good) // 2])
    elif spoil == "garbage":
        path.write_bytes(np.random.default_rng(7).bytes(len(good)))
    elif spoil == "not_an_export":
        jaxcache.write_program(name, b"\x00" * 64 + good[:1000])
    else:
        jaxcache.write_program(name, _other_program())

    before = (counted(LOADED), counted(LOWERED))
    v = TPUVerifier(keys[0])
    v.warmup()
    assert (counted(LOADED), counted(LOWERED)) == (before[0], before[1] + 1)
    assert v.verify_batch(pool) == CPUVerifier(keys[0]).verify_batch(pool)
    # written again, whole: the comb program's own signature
    again = jax.export.deserialize(bytearray(jaxcache.read_program(name)))
    assert [a.shape for a in again.in_avals] == [
        (16, tpu._U8_COLS), (16, 23), (8 * 1024, 128), (1024, 128)
    ]
    assert os.listdir(tmp_path) == [name]


@pytest.mark.parametrize("cut", ("flipped", "truncated", "empty"))
def test_a_file_that_is_not_whole_reads_as_nothing(cut, tmp_path, monkeypatch):
    """The digest is checked before any byte reaches the deserializer."""
    point_store_at(monkeypatch, tmp_path)
    payload = np.random.default_rng(3).bytes(4096)
    jaxcache.write_program("comb-x", payload)
    assert jaxcache.read_program("comb-x") == payload
    path = tmp_path / "comb-x"
    data = bytearray(path.read_bytes())
    if cut == "flipped":
        data[-1] ^= 1
    elif cut == "truncated":
        del data[-1:]
    else:
        data = bytearray()
    path.write_bytes(bytes(data))
    assert jaxcache.read_program("comb-x") is None


def test_a_store_that_cannot_be_written_is_no_error(tmp_path, monkeypatch):
    blocker = tmp_path / "a-file"
    blocker.write_bytes(b"")
    point_store_at(monkeypatch, blocker / "programs")
    jaxcache.write_program("comb-x", b"payload")
    assert jaxcache.read_program("comb-x") is None
    monkeypatch.setattr(jaxcache, "program_dir", lambda: None)
    jaxcache.write_program("comb-x", b"payload")
    assert jaxcache.read_program("comb-x") is None


# -- writers racing -------------------------------------------------------------


def test_writers_racing_on_one_name_leave_one_whole_loadable_file(
    tmp_path, monkeypatch
):
    point_store_at(monkeypatch, tmp_path)
    spec = jax.ShapeDtypeStruct((4,), jnp.int32)
    payloads = [
        jax.export.export(jax.jit(lambda x, k=k: x + k))(spec).serialize()
        for k in range(6)
    ]
    start = threading.Barrier(12)

    def writer(i):
        start.wait(timeout=30)
        for _ in range(25):
            jaxcache.write_program("comb-raced", payloads[i % len(payloads)])

    held = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=writer, args=(i,)) for i in range(12)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(held)
    assert not any(t.is_alive() for t in threads)
    assert os.listdir(tmp_path) == ["comb-raced"]
    blob = jaxcache.read_program("comb-raced")
    assert blob in payloads
    k = payloads.index(blob)
    out = jax.export.deserialize(bytearray(blob)).call(jnp.arange(4, dtype=jnp.int32))
    assert np.asarray(out).tolist() == [k, k + 1, k + 2, k + 3]


# -- the benchmark's reader -------------------------------------------------------


@pytest.mark.parametrize(
    "counts,share",
    (
        ({LOADED: 1}, 100.0),
        ({LOWERED: 1}, 0.0),
        ({LOADED: 3, LOWERED: 1}, 75.0),
        ({}, None),
    ),
)
def test_the_benchmark_reads_the_share_of_programs_loaded(counts, share, monkeypatch):
    from benchmarks.harness import cells

    monkeypatch.setattr(spans, "snapshot", lambda: {"spans": {}, "counts": counts})
    read = cells.load_readers(ROOT, [{"name": "program_loaded_pct"}])["program_loaded_pct"]
    assert read({"trace": {"busy_s": 0.1}}) == share
    assert read({"trace": None}) is None
