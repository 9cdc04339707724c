"""TPUVerifier vs CPUVerifier — byte-identical accept masks and commit order.

The north star (BASELINE.json): "CPU-vs-TPU commit order byte-identical".
The consensus state machine is a deterministic function of the accept masks
and the delivery schedule, so mask equality on every batch (including
adversarial ones) implies commit-order equality; the end-to-end sim test
checks the full pipeline anyway.
"""

import dataclasses
import random

import pytest

from dag_rider_tpu.config import Config
from dag_rider_tpu.consensus.simulator import Simulation
from dag_rider_tpu.core.types import Block, Vertex, VertexID
from dag_rider_tpu.crypto import ed25519
from dag_rider_tpu.verifier.base import KeyRegistry, VertexSigner
from dag_rider_tpu.verifier.cpu import CPUVerifier
from dag_rider_tpu.verifier.tpu import TPUVerifier


@pytest.fixture(scope="module")
def keys():
    return KeyRegistry.generate(8)


@pytest.fixture(scope="module")
def signed_vertices(keys):
    reg, seeds = keys
    signers = [VertexSigner(s) for s in seeds]
    out = []
    for i in range(8):
        v = Vertex(
            id=VertexID(3, i),
            block=Block((f"tx-{i}".encode(),)),
            strong_edges=(VertexID(2, 0), VertexID(2, 1), VertexID(2, 2)),
        )
        out.append(signers[i].sign_vertex(v))
    return out


def corruptions(vs):
    rng = random.Random(99)
    bad = [
        dataclasses.replace(vs[0], signature=b"\x00" * 64),
        dataclasses.replace(vs[1], signature=vs[2].signature),
        dataclasses.replace(vs[3], block=Block((b"tampered",))),
        dataclasses.replace(vs[6], signature=None),
    ]
    # s >= L (malleability)
    s_big = int.to_bytes(
        int.from_bytes(vs[4].signature[32:], "little") + ed25519.L,
        32,
        "little",
    )
    bad.append(
        dataclasses.replace(vs[4], signature=vs[4].signature[:32] + s_big)
    )
    # R.y >= p
    ybad = int.to_bytes(2**255 - 10, 32, "little")
    bad.append(
        dataclasses.replace(vs[5], signature=ybad + vs[5].signature[32:])
    )
    # random bit flips across R, s
    for i in range(6):
        sig = bytearray(vs[i].signature)
        sig[rng.randrange(64)] ^= 1 << rng.randrange(8)
        bad.append(dataclasses.replace(vs[i], signature=bytes(sig)))
    return bad


def test_masks_byte_identical(keys, signed_vertices):
    reg, _ = keys
    batch = signed_vertices + corruptions(signed_vertices)
    cpu = CPUVerifier(reg).verify_batch(batch)
    tpu = TPUVerifier(reg).verify_batch(batch)
    assert cpu == tpu
    assert cpu[: len(signed_vertices)] == [True] * len(signed_vertices)
    assert not any(cpu[len(signed_vertices) :])


def test_empty_and_padding(keys, signed_vertices):
    reg, _ = keys
    tpu = TPUVerifier(reg)
    assert tpu.verify_batch([]) == []
    # batch sizes straddling the bucket boundary behave identically
    assert tpu.verify_batch(signed_vertices[:1]) == [True]
    assert tpu.verify_batch(signed_vertices[:3]) == [True] * 3


def test_out_of_range_source(keys, signed_vertices):
    reg, _ = keys
    v = dataclasses.replace(
        signed_vertices[0], id=VertexID(3, 999)
    )
    assert TPUVerifier(reg).verify_batch([v]) == [False]
    assert CPUVerifier(reg).verify_batch([v]) == [False]


def test_invalid_registry_key():
    reg, seeds = KeyRegistry.generate(4)
    # replace key 2 with a non-decompressible encoding (y = 2 not on curve)
    pubs = list(reg.public_keys)
    pubs[2] = int.to_bytes(2, 32, "little")
    broken = KeyRegistry(tuple(pubs))
    signer = VertexSigner(seeds[2])
    v = signer.sign_vertex(
        Vertex(id=VertexID(1, 2), strong_edges=(VertexID(0, 0),))
    )
    assert TPUVerifier(broken).verify_batch([v]) == [False]
    assert CPUVerifier(broken).verify_batch([v]) == [False]


def test_commit_order_byte_identical_cpu_vs_tpu():
    """4-node simulation run twice — once with the CPU verifier, once with
    the TPU verifier — must deliver the identical vertex sequence on every
    node (the north-star equivalence, end to end).

    ``propose_empty=False`` + a finite block supply makes the cluster
    quiesce on its own after ~2 waves, which bounds the number of device
    dispatches (the round-1 version ran to ``max_messages`` and took >9
    minutes on the CPU backend)."""
    logs = {}
    for backend in ("cpu", "tpu"):
        cfg = Config(n=4, signature_scheme="ed25519", propose_empty=False)
        reg, seeds = KeyRegistry.generate(cfg.n)
        make = CPUVerifier if backend == "cpu" else TPUVerifier
        sim = Simulation(
            cfg,
            verifier_factory=lambda i: make(reg),
            signer_factory=lambda i: VertexSigner(seeds[i]),
        )
        sim.submit_blocks(8)
        sim.run(max_messages=4000)
        sim.check_agreement()
        logs[backend] = [
            [(vid.round, vid.source) for vid in p.delivered_log]
            for p in sim.processes
        ]
        assert any(logs[backend]), "no deliveries happened"
        assert any(
            p.metrics.counters["waves_decided"] >= 1 for p in sim.processes
        )
        # Live-pipeline batching (north star: one round per dispatch): the
        # burst pump must hand the Verifier round-sized batches, not
        # singletons.
        sizes = [s for p in sim.processes for s in p.metrics.verify_batch_sizes]
        assert sizes and sum(sizes) / len(sizes) >= 2.0, sizes
    assert logs["cpu"] == logs["tpu"]


# -- a bare verifier: one program a bucket, no window -------------------


@pytest.mark.parametrize("count,dispatches", [(40, 3), (16, 1), (17, 2)])
def test_bare_verifier_cuts_an_oversize_batch_at_the_bucket(
    keys, signed_vertices, count, dispatches
):
    """No pipeline over it: a batch larger than the fixed bucket is cut
    at the bucket and each chunk dispatched and resolved in turn — the
    CPU oracle's mask, ceil(count / 16) dispatches."""
    reg, _ = keys
    pool = (signed_vertices + corruptions(signed_vertices)) * 2
    batch = pool[:count]
    want = CPUVerifier(reg).verify_batch(batch)
    assert True in want and False in want
    v = TPUVerifier(reg)
    v.fixed_bucket = 16
    assert v.verify_batch(batch) == want
    assert v.total_dispatches == dispatches
    assert v.total_sigs_dispatched == count
    assert v.stats()["bucket"] == 16


def test_one_compiled_program_after_warmup_and_an_oversize_batch(
    keys, signed_vertices
):
    """``stats()["compile_s"]`` holds one entry a compiled shape: one
    after warmup(), and still one after a batch of three chunks."""
    reg, _ = keys
    v = TPUVerifier(reg)
    v.warmup()
    assert list(v.stats()["compile_s"]) == ["16xjnp"]
    assert v.verify_batch(signed_vertices * 5) == [True] * 40
    assert v.total_dispatches == 3
    assert list(v.stats()["compile_s"]) == ["16xjnp"]


def test_the_comb_tables_are_counted_once_in_bytes():
    """``verifier.table_bytes``: every key's comb table and the base
    point's in the [rows, 128] int32 gather layout — (n + 1) keys x 64
    windows x 16 entries x 128 lanes x 4 bytes — counted where they are
    built, and not again where they are looked up."""
    from dag_rider_tpu.obs import spans

    def counted() -> int:
        return spans.snapshot()["counts"].get("verifier.table_bytes", 0)

    v = TPUVerifier(KeyRegistry.generate(16)[0])
    before = counted()
    tables, b_tab = v._comb_tables()
    v._comb_tables()
    assert counted() - before == (16 + 1) * 64 * 16 * 128 * 4
    assert tables.nbytes + b_tab.nbytes == counted() - before
