"""A vertex decoded from the wire keeps its edge lists as the frame's
bytes until someone reads them, and takes its signed bytes from slices
of the frame (``core/codec.py`` ``decode_vertex``, ``core/types.py``
``Vertex.from_packed``) — held here to the decoder it replaced, which
built every ``VertexID`` at once and is kept below as the oracle; the
frames that must fail at decode; the sidecar path on which no edge is
ever read; and the benchmark's metric that says so
(``sidecar_edges_kept_packed_pct``).
"""

import copy
import dataclasses
import os
import pickle
import random
import struct
import sys
import threading

import grpc
import numpy as np
import pytest

from dag_rider_tpu.core import codec
from dag_rider_tpu.core.types import Block, BroadcastMessage, Vertex, VertexID
from dag_rider_tpu.obs import spans
from dag_rider_tpu.verifier import CPUVerifier
from dag_rider_tpu.verifier.base import KeyRegistry
from dag_rider_tpu.verifier.sidecar import (
    _METHOD,
    VerifierSidecarServer,
    _decode_batch,
    _encode_batch,
)
from dag_rider_tpu.verifier.tpu import TPUVerifier

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import cells, controls, reference, roundpool  # noqa: E402

#: the mechanism test: one round of the sidecar cells' shape under the
#: registry size most of the suite builds its verifiers over
N_KEYS, ROUND, EDGES = 4, 256, 171
UNPACKED = "codec.edges_unpacked"
DECODED = "sidecar.vertices_decoded"


def counted(name: str) -> int:
    return spans.snapshot()["counts"].get(name, 0)


def packed(v: Vertex) -> bool:
    return "_packed_edges" in v.__dict__


# -- the oracle: decode_vertex as it stood before this change ----------------


def decode_vertex_eager(data: bytes, offset: int = 0):
    magic = data[offset : offset + 4]
    if magic == b"DRv1":
        nblobs = 2
    elif magic == b"DRv2":
        nblobs = 3
    else:
        raise ValueError("bad vertex magic")
    offset += 4
    rnd, source = struct.unpack_from("<II", data, offset)
    offset += 8
    block, offset = Block.decode(data, offset)
    edge_sets = []
    for _ in range(2):
        (count,) = struct.unpack_from("<I", data, offset)
        offset += 4
        edges = []
        for _ in range(count):
            er, es = struct.unpack_from("<II", data, offset)
            offset += 8
            edges.append(VertexID(er, es))
        edge_sets.append(tuple(edges))
    blobs = []
    for _ in range(nblobs):
        (ln,) = struct.unpack_from("<i", data, offset)
        offset += 4
        if ln < 0:
            blobs.append(None)
        else:
            blobs.append(data[offset : offset + ln])
            offset += ln
    v = Vertex(
        id=VertexID(rnd, source),
        block=block,
        strong_edges=edge_sets[0],
        weak_edges=edge_sets[1],
        coin_share=blobs[0],
        signature=blobs[1],
        cert_sig=blobs[2] if nblobs == 3 else None,
    )
    return v, offset


# -- the frames --------------------------------------------------------------

_RNG = random.Random(29)
BIG = 2**31


def ids(rnd, sources):
    return tuple(VertexID(rnd, s) for s in sources)


def blob(*parts) -> bytes:
    """A frame's optional blobs: None is length -1."""
    out = []
    for p in parts:
        out.append(struct.pack("<i", -1 if p is None else len(p)))
        out.append(p or b"")
    return b"".join(out)


def edge_list(pairs) -> bytes:
    return struct.pack("<I", len(pairs)) + b"".join(
        struct.pack("<II", r, s) for r, s in pairs
    )


def hand_built(strong, weak=(), magic=b"DRv1", share=b"share", sig=b"s" * 64):
    """A frame a foreign encoder might write: the edges in the order
    given."""
    return b"".join(
        (magic, struct.pack("<II", 9, 3), Block((b"tx",)).encode(),
         edge_list(strong), edge_list(weak), blob(share, sig))
    )


CANONICAL = {
    "bare": Vertex(id=VertexID(0, 0)),
    "one_edge": Vertex(id=VertexID(1, 2), strong_edges=ids(0, [1]), signature=b"x" * 64),
    "quorum_of_256": Vertex(
        id=VertexID(8, 255),
        block=Block((b"a" * 32,)),
        strong_edges=ids(7, _RNG.sample(range(256), 171)),  # encoded sorted
        signature=bytes(_RNG.randrange(256) for _ in range(64)),
    ),
    "full_block_and_weak_edges": Vertex(
        id=VertexID(12, 5),
        block=Block((b"", b"one", b"\x00" * 300, bytes(range(256)))),
        strong_edges=ids(11, range(0, 7)),
        weak_edges=ids(9, [6]) + ids(3, [2, 4]) + ids(10, [0]),
        signature=b"\x01" * 64,
        coin_share=b"\x02" * 48,
    ),
    "empty_share": Vertex(
        id=VertexID(4, 1), strong_edges=ids(3, [0, 1, 2]), coin_share=b"",
        signature=b"\x03" * 64,
    ),
    "no_signature": Vertex(id=VertexID(4, 1), strong_edges=ids(3, [0, 2]), coin_share=b"c"),
    "cert_sig": Vertex(
        id=VertexID(6, 2), block=Block((b"t",)), strong_edges=ids(5, [0, 1, 3]),
        weak_edges=ids(2, [1]), signature=b"\x04" * 64, coin_share=None,
        cert_sig=b"\x05" * 96,
    ),
    "empty_cert_sig": Vertex(id=VertexID(6, 2), signature=b"\x04" * 64, cert_sig=b""),
    "beyond_int32": Vertex(
        id=VertexID(BIG + 5, 2**32 - 1),
        strong_edges=ids(BIG + 4, [0, BIG, 2**32 - 1]),
        weak_edges=ids(BIG, [BIG + 1]) + ids(2**32 - 1, [7]),
        signature=b"\x06" * 64,
    ),
}
FRAMES = {name: codec.encode_vertex(v) for name, v in CANONICAL.items()}
#: frames only another encoder writes: out of order, or holding duplicates
FOREIGN = {
    "strong_unsorted": hand_built([(8, 2), (8, 0), (8, 1)]),
    "weak_unsorted": hand_built([(8, 0), (8, 1)], weak=[(5, 1), (3, 9)]),
    "round_before_source": hand_built([(8, 0), (7, 5)]),
    "duplicates_in_order": hand_built([(8, 0), (8, 0), (8, 1)], weak=[(2, 2), (2, 2)]),
    "duplicates_unsorted": hand_built([(8, 1), (8, 0), (8, 1)], magic=b"DRv2") + blob(b"c" * 96),
    "unsorted_beyond_int32": hand_built([(BIG, 1), (1, BIG)]),
}
FRAMES.update(FOREIGN)
IN_ORDER = sorted(set(CANONICAL) | {"duplicates_in_order"})


@pytest.fixture(params=sorted(FRAMES))
def frame(request):
    return FRAMES[request.param]


def both(frame):
    new, end_new = codec.decode_vertex(frame)
    old, end_old = decode_vertex_eager(frame)
    assert end_new == end_old == len(frame)
    assert type(new) is Vertex and packed(new)
    return new, old


# -- equal to what the eager decoder gave ------------------------------------


def test_equal_both_ways_before_and_after_the_edges_are_read(frame):
    new, old = both(frame)
    assert new == old and not packed(new)
    assert new == old and old == new
    new, old = both(frame)
    assert old == new and not packed(new)
    new, _ = both(frame)
    again, _ = both(frame)
    assert new == again and not packed(new) and not packed(again)
    assert new != dataclasses.replace(old, weak_edges=old.weak_edges + (VertexID(0, 0),))


def test_hash_and_repr(frame):
    new, old = both(frame)
    assert hash(new) == hash(old)
    new, old = both(frame)
    assert repr(new) == repr(old) and "_packed" not in repr(new)
    assert {new: 1}[old] == 1


def test_fields_and_edges_in_the_wires_order(frame):
    new, old = both(frame)
    assert new.strong_edges == old.strong_edges and not packed(new)
    assert new.weak_edges == old.weak_edges
    assert all(type(e) is VertexID for e in new.strong_edges + new.weak_edges)
    assert (new.id, new.block, new.signature, new.coin_share, new.cert_sig) == (
        old.id, old.block, old.signature, old.coin_share, old.cert_sig
    )
    assert type(new.id) is VertexID and (new.round, new.source) == new.id
    # the weak list alone, read first, unpacks both
    new, old = both(frame)
    assert new.weak_edges == old.weak_edges and new.strong_edges == old.strong_edges
    assert dataclasses.astuple(new) == dataclasses.astuple(old)


def test_signing_bytes_and_digest_without_reading_an_edge(frame):
    new, old = both(frame)
    assert new.signing_bytes() == old.signing_bytes()
    assert new.digest() == old.digest()
    new, old = both(frame)
    assert new.digest() == old.digest()


@pytest.mark.parametrize("name", sorted(FRAMES))
def test_the_memo_is_seeded_only_where_the_wire_is_in_canonical_order(name):
    new, old = both(FRAMES[name])
    seeded = new.__dict__.get("_signing_bytes")
    if name in IN_ORDER:
        assert seeded == old.signing_bytes()
        assert new.signing_bytes() is seeded and packed(new)
    else:
        # signing_bytes() sorts, as it always did, and so reads the edges
        assert seeded is None
        assert new.signing_bytes() == old.signing_bytes() and not packed(new)


def test_edge_arrays(frame):
    new, old = both(frame)
    got, want = new.edge_arrays(), old.edge_arrays()
    assert len(got) == len(want) == 4
    for g, w in zip(got, want):
        assert g.dtype == w.dtype == np.int64 and np.array_equal(g, w)


def test_replace_builds_an_ordinary_vertex(frame):
    new, old = both(frame)
    a = dataclasses.replace(new, signature=b"other")
    b = dataclasses.replace(old, signature=b"other")
    assert a == b and not packed(a) and "_signing_bytes" not in a.__dict__
    assert a.signing_bytes() == b.signing_bytes() == old.signing_bytes()
    assert a.__dict__["strong_edges"] == old.strong_edges


@pytest.mark.parametrize("clone", (
    lambda v: pickle.loads(pickle.dumps(v)),
    lambda v: pickle.loads(pickle.dumps(v, protocol=2)),
    copy.copy,
    copy.deepcopy,
), ids=("pickle", "pickle2", "copy", "deepcopy"))
def test_pickle_and_copy_of_a_vertex_still_packed_and_of_one_read(frame, clone):
    new, old = both(frame)
    twin = clone(new)
    assert packed(new) and packed(twin)  # cloning read nothing
    assert twin == old and old == twin and hash(twin) == hash(old)
    assert twin.signing_bytes() == old.signing_bytes()
    assert new == old  # unpacks
    twin = clone(new)
    assert not packed(twin) and twin == old and twin.digest() == old.digest()


@pytest.mark.parametrize("name", sorted(CANONICAL))
def test_encode_of_decode_is_the_canonical_frame(name):
    new, _ = both(FRAMES[name])
    assert codec.encode_vertex(new) == FRAMES[name]
    made = CANONICAL[name]  # the encoder sorts what it was given
    assert new == dataclasses.replace(
        made,
        strong_edges=tuple(sorted(made.strong_edges)),
        weak_edges=tuple(sorted(made.weak_edges)),
    )


@pytest.mark.parametrize("name", sorted(FOREIGN))
def test_encode_of_a_foreign_frame_is_what_the_eager_decoders_vertex_encodes_to(name):
    new, old = both(FRAMES[name])
    assert codec.encode_vertex(new) == codec.encode_vertex(old)


def test_decode_message_of_a_val_frame_and_an_offset_into_a_buffer(frame):
    _, old = both(frame)
    header = struct.pack("<IIB", old.round, 1, 0) + struct.pack("<ii", -1, -1)
    data = header + b"\x01" + frame
    msg, end = codec.decode_message(data)
    assert end == len(data) and packed(msg.vertex)
    assert msg == BroadcastMessage(vertex=old, round=old.round, sender=1)
    many = codec.decode_many(b"DRb1" + struct.pack("<I", 2) + data + data)
    assert [m.vertex for m in many] == [old, old]
    v, end = codec.decode_vertex(b"ab" + frame + b"tail", 2)
    assert end == 2 + len(frame) and v == old


def test_an_ordinary_vertex_never_reaches_the_descriptor():
    v = Vertex(id=VertexID(1, 1))
    assert v.__dict__["strong_edges"] == () == v.__dict__["weak_edges"]
    before = counted(UNPACKED)
    w = Vertex(id=VertexID(2, 0), strong_edges=ids(1, [0, 1]))
    assert w.strong_edges == ids(1, [0, 1]) and w.weak_edges == ()
    assert dataclasses.replace(w).strong_edges == w.strong_edges
    assert counted(UNPACKED) == before
    assert Vertex.strong_edges == () == Vertex.weak_edges  # the fields' default
    assert [f.default for f in dataclasses.fields(Vertex)][2:4] == [(), ()]


def test_an_instance_with_neither_the_field_nor_the_bytes_is_not_edgeless():
    hollow = object.__new__(Vertex)
    for name in ("strong_edges", "weak_edges"):
        with pytest.raises(AttributeError, match=name):
            getattr(hollow, name)


def test_a_vertex_counts_once_whoever_reads_and_however_often():
    new, _ = both(FRAMES["full_block_and_weak_edges"])
    before = counted(UNPACKED)
    new.signing_bytes(), new.digest(), new.id, new.block, new.signature
    assert counted(UNPACKED) == before and packed(new)
    new.weak_edges, new.strong_edges, new.edge_arrays(), hash(new), repr(new)
    assert counted(UNPACKED) == before + 1


def test_many_threads_reading_one_packed_vertex_agree_and_count_it_once():
    """The descriptor fills a shared ``__dict__`` with no lock: every
    reader gets equal tuples, whoever unpacks, and the vertex counts
    once."""
    frames = [FRAMES["quorum_of_256"], FRAMES["full_block_and_weak_edges"]] * 100
    vertices = [codec.decode_vertex(f)[0] for f in frames]
    want = [decode_vertex_eager(f)[0] for f in frames[:2]]
    readers = 16
    gate = threading.Barrier(readers)
    seen = [None] * readers

    def read(k):
        gate.wait(timeout=10)
        got = []
        for v in vertices if k % 2 else vertices[::-1]:
            if k % 4 < 2:  # half the readers ask for the weak list first
                got.append((v.weak_edges, v.strong_edges))
            else:
                strong = v.strong_edges
                got.append((v.weak_edges, strong))
        seen[k] = got if k % 2 else got[::-1]

    before = counted(UNPACKED)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=read, args=(k,)) for k in range(readers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=20)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert counted(UNPACKED) - before == len(vertices)
    for got in seen:
        assert got == [(w.weak_edges, w.strong_edges) for w in want] * 100
    assert not any(packed(v) for v in vertices) and vertices == want * 100


# -- malformed frames fail at decode, as ValueError --------------------------


def test_every_truncation_of_a_frame_is_a_value_error(frame):
    for cut in range(len(frame)):
        with pytest.raises(ValueError):
            codec.decode_vertex(frame[:cut])
    # and inside a buffer that ends where the cut frame does
    for cut in range(0, len(frame), 7):
        with pytest.raises(ValueError):
            codec.decode_vertex(b"ab" + frame[:cut], 2)


def _patched(frame: bytes, at: int, fmt: str, value: int) -> bytes:
    return frame[:at] + struct.pack(fmt, value) + frame[at + struct.calcsize(fmt) :]


_F = FRAMES["full_block_and_weak_edges"]
_BLOCK_AT = 12
_STRONG_AT = _BLOCK_AT + len(CANONICAL["full_block_and_weak_edges"].block.encode())
_WEAK_AT = _STRONG_AT + 4 + 8 * 7
_SHARE_AT = _WEAK_AT + 4 + 8 * 4
_SIG_AT = _SHARE_AT + 4 + 48
MALFORMED = {
    "bad_magic": b"DRv3" + _F[4:],
    "empty": b"",
    "magic_alone": b"DRv1",
    "tx_count_overruns": _patched(_F, _BLOCK_AT, "<I", 2**32 - 1),
    "tx_length_overruns": _patched(_F, _BLOCK_AT + 4, "<I", len(_F)),
    "strong_count_overruns": _patched(_F, _STRONG_AT, "<I", (len(_F) - _STRONG_AT) // 8 + 1),
    "strong_count_huge": _patched(_F, _STRONG_AT, "<I", 2**32 - 1),
    "weak_count_overruns": _patched(_F, _WEAK_AT, "<I", 2**29),
    "share_length_overruns": _patched(_F, _SHARE_AT, "<i", len(_F)),
    "signature_length_overruns": _patched(_F, _SIG_AT, "<i", 65),
    "signature_length_huge": _patched(_F, _SIG_AT, "<i", 2**31 - 1),
    "v2_without_its_third_blob": b"DRv2" + _F[4:],
}


def test_the_offsets_the_malformed_frames_patch_are_the_fields():
    assert struct.unpack_from("<I", _F, _STRONG_AT) == (7,)
    assert struct.unpack_from("<I", _F, _WEAK_AT) == (4,)
    assert struct.unpack_from("<i", _F, _SHARE_AT) == (48,)
    assert struct.unpack_from("<i", _F, _SIG_AT) == (64,) and _SIG_AT + 68 == len(_F)


@pytest.mark.parametrize("name", sorted(MALFORMED))
def test_a_malformed_frame_is_a_value_error_at_decode(name):
    bad = MALFORMED[name]
    with pytest.raises(ValueError):
        codec.decode_vertex(bad)
    with pytest.raises(ValueError):
        _decode_batch(codec.frame(FRAMES["bare"]) + codec.frame(bad))
    with pytest.raises(ValueError):
        codec.decode_message(
            struct.pack("<IIB", 1, 1, 0) + struct.pack("<ii", -1, -1) + b"\x01" + bad
        )


def test_a_batch_cut_inside_a_frame_is_a_value_error():
    payload = codec.frame(FRAMES["bare"]) + codec.frame(FRAMES["cert_sig"])
    for cut in range(len(codec.frame(FRAMES["bare"])) + 1, len(payload)):
        with pytest.raises(ValueError):
            _decode_batch(payload[:cut])


@pytest.fixture(scope="module")
def registry():
    return KeyRegistry.generate(N_KEYS)[0]


def test_the_handler_answers_a_malformed_batch_with_invalid_argument(registry):
    server = VerifierSidecarServer(CPUVerifier(registry), "127.0.0.1:0")
    channel = grpc.insecure_channel(server.address)
    try:
        call = channel.unary_unary(
            _METHOD, request_serializer=lambda b: b, response_deserializer=lambda b: b
        )
        good = codec.frame(FRAMES["one_edge"])
        assert call(good, timeout=10.0) == b"\x00"  # no such signature: refused, served
        for name in ("strong_count_overruns", "signature_length_overruns", "bad_magic"):
            with pytest.raises(grpc.RpcError) as err:
                call(good + codec.frame(MALFORMED[name]), timeout=10.0)
            assert err.value.code() == grpc.StatusCode.INVALID_ARGUMENT, name
        with pytest.raises(grpc.RpcError) as err:
            call(good + codec.frame(_F)[:-9], timeout=10.0)
        assert err.value.code() == grpc.StatusCode.INVALID_ARGUMENT
        assert call(good + good, timeout=10.0) == b"\x00\x00"
    finally:
        channel.close()
        server.stop()


# -- the mechanism: the verifier reads no edge, the control reads them all ---

@pytest.fixture(scope="module")
def signed_round():
    """256 vertices of 171 strong edges each under the 4 test keys, eight
    of them wrong — each of the five kinds at least once."""
    keys = reference.Keys(N_KEYS)
    rng = random.Random(2929)
    strong = tuple((6, s) for s in range(EDGES))
    enc = reference.encode_edges(b"S", strong)
    vs = [
        roundpool.sign(keys, 7, i % N_KEYS, (b"tx-%d" % i,), strong, enc)
        for i in range(ROUND)
    ]
    for j, i in enumerate(sorted(rng.sample(range(ROUND), 8))):
        vs[i] = roundpool.corrupt(vs[i], roundpool.KINDS[j % 5], N_KEYS, rng)
    return keys, vs


def test_the_device_verifier_reads_no_edge_and_the_control_reads_every_one(
    registry, signed_round
):
    keys, round_ = signed_round
    want = roundpool.expected_mask(keys, round_)
    assert want.count(False) == 8 and {v.wrong for v in round_} == {"", *roundpool.KINDS}
    payload = _encode_batch(roundpool.to_vertices(round_))

    device = TPUVerifier(registry)
    device.fixed_bucket = 16  # the shape the suite's other files compile
    decoded, unpacked = counted(DECODED), counted(UNPACKED)
    batch = _decode_batch(payload)
    assert counted(DECODED) - decoded == ROUND
    assert all(packed(v) and "_signing_bytes" in v.__dict__ for v in batch)
    assert device.verify_batch(batch) == want
    assert counted(UNPACKED) == unpacked and all(packed(v) for v in batch)

    # the host oracle reads source, signature and signed bytes too
    assert CPUVerifier(registry).verify_batch(batch) == want
    assert counted(UNPACKED) == unpacked

    # the control reads the edges of every vertex it is given, and is
    # wrong where it always was: the s + L vertices, and only those
    batch = _decode_batch(payload)
    lax = controls.LaxVerifier(registry).verify_batch(batch)
    assert counted(UNPACKED) - unpacked == ROUND and not any(packed(v) for v in batch)
    assert counted(DECODED) - decoded == 2 * ROUND
    assert [i for i in range(ROUND) if lax[i] != want[i]] == [
        i for i, v in enumerate(round_) if v.wrong == "s_plus_l"
    ]


# -- the metric --------------------------------------------------------------

METRIC = "sidecar_edges_kept_packed_pct"
MANIFEST = cells.load_manifest(ROOT)
ENTRY = [m for m in MANIFEST["per_layer"] if m["name"] == METRIC]
TRACED = {"programs": {}, "busy_s": 0.1, "window_s": 4.0}


def obs_with(trace) -> dict:
    return {"samples": {}, "counters": {}, "seconds": 40.0, "trace": trace,
            "device_kind": "TPU v5 lite", "config": {"n": 256}}


@pytest.fixture(scope="module")
def read():
    return cells.load_readers(ROOT, ENTRY)[METRIC]


def test_the_manifest_has_the_share_once_under_the_sidecar_servers_layer():
    assert ENTRY == [{
        "name": METRIC, "unit": "pct", "better": "higher", "source": "program_counter",
        "layer": "sidecar server", "moves": "verified_sigs_per_s",
        "workloads": ["sidecar256.colocated4"],
    }]
    # letter for letter the layer of the handler's other metrics
    assert ENTRY[0]["layer"] in {
        m["layer"] for m in MANIFEST["per_layer"] if m["name"] == "sidecar_decode_ms_per_rpc"
    }
    # it follows the 45 entries the manifest had: nothing was moved
    assert [m["name"] for m in MANIFEST["per_layer"]].index(METRIC) == 45
    assert cells.reader_path(ROOT, METRIC).endswith(METRIC + ".py")
    source = open(cells.reader_path(ROOT, METRIC)).read()
    assert f'"{DECODED}"' in source and f'"{UNPACKED}"' in source
    assert {DECODED, UNPACKED} <= spans.KNOWN_COUNTS


@pytest.mark.parametrize("counts, want", (
    ({}, None),
    ({UNPACKED: 40}, None),  # a cluster's validators unpack; no sidecar decoded
    ({DECODED: 0}, None),
    ({DECODED: 512}, 100.0),
    ({DECODED: 512, UNPACKED: 0}, 100.0),
    ({DECODED: 512, UNPACKED: 512}, 0.0),
    ({DECODED: 512, UNPACKED: 128}, 75.0),
))
def test_reader_on_hand_made_books(read, monkeypatch, counts, want):
    book = {"spans": {}, "counts": {"heap.frozen_objects": 9, **counts}}
    monkeypatch.setattr(spans, "snapshot", lambda: book)
    assert read(obs_with(TRACED)) == want
    assert read(obs_with(None)) is None  # a run that takes no trace


def test_reader_returns_nothing_from_a_program_without_the_span_module(read, monkeypatch):
    import dag_rider_tpu.obs as obs_pkg

    monkeypatch.delattr(obs_pkg, "spans")
    monkeypatch.setitem(sys.modules, "dag_rider_tpu.obs.spans", None)
    assert read(obs_with(TRACED)) is None
