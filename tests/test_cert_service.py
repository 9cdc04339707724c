"""Round certificates on the verify service, at n=4 (f=1, quorum 3) on
the CPU backend: the committee's line tables against the oracle's
precompute, the fixed-shape Miller program against
``bls.multi_pairing_check`` and the benchmark's plain reference on every
kind of wrong certificate, the wire format, ``VerifyCerts`` end to end
over a unix socket on both lanes, and the node's wiring."""

import os
import sys

import grpc
import pytest

from dag_rider_tpu.core import codec
from dag_rider_tpu.core.types import RoundCertificate
from dag_rider_tpu.crypto import bls12381 as bls
from dag_rider_tpu.verifier.base import KeyRegistry
from dag_rider_tpu.verifier.cert import CertBackend, CertVerifier, decompress_aggregate

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import reference_bls  # noqa: E402

N, QUORUM, SEED = 4, 3, 2**31 + 29
ROUNDS = 8


@pytest.fixture(scope="module")
def committee():
    reg, _, sks = KeyRegistry.generate_with_cert(N)
    keys = reference_bls.Keys(N)
    pool = reference_bls.make_pool(keys, ROUNDS, reference_bls.KINDS, SEED)
    want = reference_bls.expected_verdicts(keys, pool)
    certs = [
        RoundCertificate(round=c.rnd, signers=c.signers, digests=c.digests, agg_sig=c.agg)
        for c in pool
    ]
    return reg, sks, pool, certs, want


@pytest.fixture(scope="module")
def device(committee):
    backend = CertBackend(committee[0], QUORUM, pair="device")
    backend.warmup()
    return backend


def _pairs(reg, cert):
    agg = bls.g1_decompress(cert.agg_sig)
    return [(agg, bls.g2_neg(bls.G2_GEN))] + [
        (bls.hash_to_g1(d), reg.bls_key_of(s)) for s, d in zip(cert.signers, cert.digests)
    ]


def _host_miller(pairs):
    """The oracle's shared-accumulator replay, before the final
    exponentiation."""
    evs = [(p[0], p[1], bls.g2_precompute(q)) for p, q in pairs]
    acc, slot = bls.FP12_ONE, 0
    for bit in bls._X_BITS:
        acc = bls.fp12_sqr(acc)
        for _ in range(2 if bit == "1" else 1):  # the doubling line, then the addition's
            for xp, yp, coeffs in evs:
                acc = bls.fp12_mul(acc, bls._line_eval(*coeffs[slot], xp, yp))
            slot += 1
    return bls.fp12_conj(acc)


def test_line_tables_are_the_oracles_precompute(committee):
    from dag_rider_tpu.ops import bls_pairing, field381 as f

    keys = list(committee[0].bls_public_keys) + [bls.g2_neg(bls.G2_GEN)]
    table, usable = bls_pairing.line_table(keys)
    assert table.shape == (bls_pairing.SLOTS, N + 1, 2, 12, f.LIMBS) and all(usable)
    for i, q in enumerate(keys):
        for s, (lam, c) in enumerate(bls.g2_precompute(q)):
            for k, want in enumerate((lam, c)):
                got = [f.from_limbs(table[s, i, k, j]) for j in range(12)]
                assert got == bls_pairing._fp12_flat(want), (i, s, k)


def test_a_key_without_a_table_is_marked_and_its_certificates_go_to_the_host(committee):
    from dag_rider_tpu.ops import bls_pairing

    table, usable = bls_pairing.line_table([None, committee[0].bls_public_keys[1]])
    assert usable == [False, True] and not table[:, 0].any()


@pytest.mark.parametrize("at", range(ROUNDS))
def test_every_verdict_is_the_oracles_and_the_references(committee, device, at):
    reg, _, pool, certs, want = committee
    cert = certs[at]
    got = device.verify_certs([cert])
    assert got == CertBackend(reg, QUORUM, pair="host").verify_certs([cert])
    assert got == [want[at]] == [CertVerifier(reg, QUORUM, pair="host").verify_certificate(cert)]
    assert want[at] == (pool[at].wrong == "")
    if pool[at].wrong not in ("under_quorum", "duplicated_signer", "not_in_g1"):
        # the product check itself, on the pairs the certificate names
        assert got == [bls.multi_pairing_check(_pairs(reg, cert))]


def test_the_product_is_the_oracles_element_and_pad_rows_contribute_one(committee, device):
    """One signer (three pad rows) and a quorum (one pad row): the
    program's Miller product is the host replay's Fp12 element."""
    from dag_rider_tpu.ops import bls_g1, field381 as f

    reg, sks = committee[0], committee[1]
    hashes = bls_g1.HashToG1Batch(N)
    for signers in ([2], [0, 1, 3]):
        digests = [bytes([7 + s]) * 32 for s in signers]
        hx, hy = hashes(digests)
        points = [bls.hash_to_g1(d) for d in digests]
        assert [(f.from_limbs(x), f.from_limbs(y)) for x, y in zip(hx, hy)] == points
        agg = bls.g1_sum([bls.g1_mul(sks[s], h) for s, h in zip(signers, points)])
        got = device._pairing.product(agg, signers, hx, hy)
        pairs = [(agg, bls.g2_neg(bls.G2_GEN))] + [
            (h, reg.bls_key_of(s)) for s, h in zip(signers, points)
        ]
        assert got == _host_miller(pairs)
        assert bls.final_exponentiation(got) == bls.FP12_ONE


def test_the_cubed_final_exponentiation_is_the_oracles_cubed():
    import random

    rng = random.Random(3)
    f = tuple(
        tuple(tuple(rng.randrange(bls.P) for _ in range(2)) for _ in range(3)) for _ in range(2)
    )
    g = bls.final_exponentiation(f)
    assert bls.final_exponentiation_cubed(f) == bls.fp12_mul(g, bls.fp12_sqr(g))
    assert bls.final_exponentiation_cubed(bls.FP12_ONE) == bls.FP12_ONE


def test_the_pallas_row_multiply_is_field381s_in_interpret_mode():
    """The TPU engine of the Miller program's batched products, run by
    the Pallas interpreter: the limbs out equal field381.mul's, reduced
    or not (the same limb algorithm)."""
    import numpy as np

    from dag_rider_tpu.ops import bls_pairing, field381 as f

    rng = np.random.default_rng(11)
    vals = [int.from_bytes(rng.bytes(48), "big") % f.P_INT for _ in range(2 * 200)]
    a, b = f.to_limbs_bulk(vals[:200]), f.to_limbs_bulk(vals[200:])
    got = np.asarray(bls_pairing._mul_pallas(a.T, b.T, interpret=True)).T
    assert (got == np.asarray(f.mul(a, b))).all()


def test_an_aggregate_outside_g1_passes_the_pairing_but_not_decompression(committee):
    reg, _, pool, certs, _ = committee
    (at,) = [i for i, c in enumerate(pool) if c.wrong == "not_in_g1"]
    assert bls.g1_decompress(certs[at].agg_sig) is not None
    assert decompress_aggregate(certs[at].agg_sig) is None
    assert bls.multi_pairing_check(_pairs(reg, certs[at]))  # the gap the check closes
    assert not CertVerifier(reg, QUORUM).verify_certificate(certs[at])


def test_certificates_round_trip_the_wire_and_malformed_frames_are_refused(committee):
    certs = committee[3]
    wire = codec.encode_certificates(certs)
    assert codec.decode_certificates(wire) == certs
    assert codec.decode_certificates(b"") == []
    one = codec.encode_certificate(certs[0])
    for bad in (
        wire[:-1],  # the last frame cut short
        wire + b"\x00",  # a byte after the last frame
        codec.frame(one[:-5]),  # a frame holding part of a certificate
        codec.frame(one + b"\x00"),  # a frame holding more than one
        codec.frame(b"\xff" * 12),  # counts no certificate can have
    ):
        with pytest.raises(ValueError):
            codec.decode_certificates(bad)


@pytest.fixture
def served(tmp_path):
    from dag_rider_tpu.verifier.sidecar import RemoteCertVerifier, VerifierSidecarServer

    started = []

    def serve(backend):
        address = f"unix:{tmp_path}/c{len(started)}.sock"
        server = VerifierSidecarServer(None, address, cert_backend=backend)
        remote = RemoteCertVerifier(address, backend.registry, QUORUM, timeout=60.0)
        started.append((server, remote))
        return server, remote

    yield serve
    for server, remote in started:
        remote.close()
        server.stop()


@pytest.mark.parametrize("lane", ("device", "host"))
def test_verify_certs_end_to_end_over_a_unix_socket(committee, device, served, lane):
    from dag_rider_tpu.obs import spans

    reg, _, pool, certs, want = committee
    backend = device if lane == "device" else CertBackend(reg, QUORUM, pair="host")
    before = spans.snapshot()
    server, remote = served(backend)
    assert remote.verify_remote(certs) == want
    assert [remote.verify_certificate(c) for c in certs] == want
    assert remote.verify_many(certs) == want  # now from the memo
    assert remote.rpc_failures == 0 and remote.stats["verdict_hits"] == len(certs)
    after = spans.snapshot()
    count = lambda book, name: book["spans"].get(name, {"count": 0})["count"]  # noqa: E731
    assert count(after, "sidecar.cert_rpc") - count(before, "sidecar.cert_rpc") == 1 + len(certs)
    pairs = lambda book: book["counts"].get("cert.pairs", 0)  # noqa: E731
    fallback = lambda book: book["counts"].get("cert.host_fallback", 0)  # noqa: E731
    # two fresh checks of each certificate that reaches the product
    early = ("under_quorum", "duplicated_signer", "not_in_g1")
    checked = 2 * sum(len(c.signers) + 1 for c in pool if c.wrong not in early)
    assert pairs(after) - pairs(before) == checked
    host = fallback(after) - fallback(before)
    assert host == (0 if lane == "device" else pairs(after) - pairs(before))


def test_a_malformed_request_is_refused_and_a_failed_rpc_admits_nothing(committee, device, served):
    reg, _, _, certs, want = committee
    server, remote = served(CertBackend(reg, QUORUM, pair="host"))
    with pytest.raises(grpc.RpcError) as err:
        remote._call(b"\x01\x02\x03", timeout=10.0)
    assert err.value.code() == grpc.StatusCode.INVALID_ARGUMENT
    server.stop()
    honest = certs[want.index(True)]
    assert remote.verify_remote([honest]) == [False] and remote.rpc_failures == 1
    assert remote.verify_certificate(honest) is False and remote.rpc_failures == 2
    assert honest.signing_key() not in remote._verdicts  # asked again next time


def test_a_method_without_a_backend_is_unimplemented(tmp_path, committee):
    from dag_rider_tpu.verifier.cpu import CPUVerifier
    from dag_rider_tpu.verifier.sidecar import RemoteCertVerifier, VerifierSidecarServer

    address = f"unix:{tmp_path}/v.sock"
    server = VerifierSidecarServer(CPUVerifier(committee[0]), address)
    remote = RemoteCertVerifier(address, committee[0], QUORUM, timeout=10.0)
    try:
        assert remote.verify_remote(committee[3][:2]) == [False, False]
        assert remote.rpc_failures == 1
    finally:
        remote.close()
        server.stop()


def test_a_remote_validator_with_aggregate_certificates_checks_them_through_its_sidecar(tmp_path):
    import json

    from dag_rider_tpu import node as node_mod
    from dag_rider_tpu.verifier.sidecar import RemoteCertVerifier

    keys_path = tmp_path / "keys.json"
    keys_path.write_text(json.dumps(node_mod.generate_keys(N, 2)))
    nd = node_mod.Node(
        {
            "index": 0, "n": N, "listen": "127.0.0.1:0", "peers": {},
            "keys": str(keys_path), "rbc": False, "verifier": "remote",
            "verifier_address": "127.0.0.1:1", "coin": "round_robin", "cert": "agg",
        }
    )
    try:
        cv = nd.process.cert_verifier
        assert isinstance(cv, RemoteCertVerifier) and cv._address == "127.0.0.1:1"
        assert cv.quorum == QUORUM
    finally:
        nd.net.close()
