"""A whole round of a 1,024-validator committee through the verify
sidecar: 5.7 MB in one request, over gRPC's 4 MiB default, so both ends
set ``sidecar.MAX_MESSAGE_BYTES``. A request over that ceiling is a
transport fault: the batch reads all-invalid and nothing is admitted.
"""

import os
import shutil
import sys
import tempfile

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import reference, roundpool  # noqa: E402
from dag_rider_tpu.core.types import Block, Vertex, VertexID  # noqa: E402
from dag_rider_tpu.obs import spans  # noqa: E402
from dag_rider_tpu.verifier import sidecar  # noqa: E402
from dag_rider_tpu.verifier.base import KeyRegistry  # noqa: E402
from dag_rider_tpu.verifier.cpu import CPUVerifier  # noqa: E402

N = 1024
SEED = 2**31 + 36


@pytest.fixture(scope="module")
def round_():
    keys = reference.Keys(N)
    (signed,) = roundpool.make_pool(keys, n=N, rounds=1, wrong_per_round=8, seed=SEED)
    return roundpool.to_vertices(signed), roundpool.expected_mask(keys, signed)


@pytest.fixture(scope="module")
def server():
    # a short path: a unix socket's name holds ~107 bytes
    tmp = tempfile.mkdtemp(prefix="wide-")
    address = "unix:" + os.path.join(tmp, "v.sock")
    srv = sidecar.VerifierSidecarServer(
        CPUVerifier(KeyRegistry.generate(N)[0]), address
    )
    yield address
    srv.stop()
    shutil.rmtree(tmp, ignore_errors=True)


def oversize_batch():
    """Four vertices whose blocks hold 17 MiB each: 68 MiB on the wire."""
    tx = b"x" * (17 * 2**20)
    return [
        Vertex(id=VertexID(1, i), block=Block((tx,)), signature=b"\x00" * 64)
        for i in range(4)
    ]


def request_bytes() -> int:
    return spans.snapshot()["counts"].get("sidecar.request_bytes", 0)


def test_a_whole_round_at_n1024_is_served_in_one_rpc(round_, server):
    vertices, want = round_
    payload = len(sidecar._encode_batch(vertices))
    assert 4 * 2**20 < payload < sidecar.MAX_MESSAGE_BYTES
    remote = sidecar.RemoteVerifier(server)
    before = request_bytes()
    try:
        assert remote.verify_batch(vertices) == want
        assert remote.rpc_failures == 0
        assert sum(want) == N - 8
        # the handler counts what it took: the round, and a ping's nothing
        assert remote.ping()
        assert request_bytes() - before == payload
    finally:
        remote.close()


@pytest.mark.parametrize("refused_by", ("client", "server"))
def test_a_request_over_the_ceiling_fails_closed_and_the_next_round_is_served(
    refused_by, round_, server, monkeypatch
):
    vertices, want = round_
    # what is served next need not be a whole round: a slice of one
    vertices, want = vertices[:64], want[:64]
    big = oversize_batch()
    assert len(sidecar._encode_batch(big)) > sidecar.MAX_MESSAGE_BYTES
    if refused_by == "server":
        # a client on gRPC's defaults sends it whole; the server's receive
        # ceiling refuses it before the handler sees a byte
        monkeypatch.setattr(sidecar, "_MESSAGE_OPTIONS", ())
    remote = sidecar.RemoteVerifier(server)
    monkeypatch.undo()
    before = request_bytes()
    try:
        assert remote.verify_batch(big) == [False] * len(big)
        assert remote.rpc_failures == 1
        assert request_bytes() == before
        assert remote.verify_batch(vertices) == want
        assert remote.rpc_failures == 1
        assert request_bytes() - before == len(sidecar._encode_batch(vertices))
    finally:
        remote.close()
