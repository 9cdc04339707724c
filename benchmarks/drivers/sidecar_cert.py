"""Driver ``sidecar_cert``: the verify service checking BLS aggregate
round certificates — this process holds the chip and hosts
``VerifierSidecarServer`` with ``CertBackend`` on a unix socket; the
validators are client OS processes (``sidecar_cert_client.py``) that
never load libtpu, each calling ``RemoteCertVerifier.verify_remote``
with one round's certificate, one RPC in flight. ``sidecar``'s layout,
with certificates for rounds.

``build`` makes the device backend the configuration states and hands it
to ``assemble``; ``run_window`` drives an assembled stack (a test
assembles one over the host oracle, or a control).
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import tempfile
import time
from typing import Dict, List

import jax

from benchmarks.harness import reference_bls

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CLIENT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "sidecar_cert_client.py")
#: a verdict is waited for this long past the window's close
DRAIN_BOUND_S = 60.0


class TimedCertBackend:
    """The benchmark's wrapper around the sidecar's certificate backend:
    when each ``verify_certs`` was entered and left, on this process's
    clock."""

    def __init__(self, backend):
        self.backend = backend
        self.spans: List[tuple] = []

    def warmup(self) -> float:
        warm = getattr(self.backend, "warmup", None)
        return warm() if callable(warm) else 0.0

    def verify_certs(self, certs):
        t0 = time.monotonic()
        with jax.profiler.TraceAnnotation("bench.server.verify_certs"):
            verdicts = self.backend.verify_certs(certs)
        self.spans.append((t0, time.monotonic()))
        return verdicts


class Stack:
    def __init__(self, config: dict, traffic: dict, seed: int):
        self.config = config
        self.traffic = traffic
        self.seed = seed
        self.n = config["n"]
        self.quorum = reference_bls.quorum(self.n)
        self.keys = reference_bls.Keys(self.n)
        self.tmp = tempfile.mkdtemp(prefix="bc-")
        sock = os.path.join(self.tmp, "c.sock")
        # a unix socket's path holds ~107 bytes; under a longer TMPDIR the
        # socket lives in the abstract namespace, named after this one
        self.address = (
            "unix:" + sock if len(sock) < 100 else "unix-abstract:" + sock[-90:]
        )
        self.clients: List[subprocess.Popen] = []
        self.server = None
        self.timed = None
        self.setup_parts: Dict[str, float] = {}


def _await(client: subprocess.Popen, word: str) -> None:
    line = client.stdout.readline().strip()
    if line != word:
        raise RuntimeError(f"client {client.pid} said {line!r}, not {word!r}")


def start_clients(stack: Stack) -> None:
    """The validators start first: they make their pools while this
    process builds tables and compiles its programs."""
    t = stack.traffic
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    for _ in range(t["clients"]):
        stack.clients.append(
            subprocess.Popen(
                [
                    sys.executable, CLIENT, "--root", ROOT,
                    "--address", stack.address, "--n", str(stack.n),
                    "--rounds", str(t["pool_rounds"]),
                    "--kinds", ",".join(t["wrong_kinds"]),
                    "--seed", str(stack.seed),
                ],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, env=env,
            )
        )


def _registry(stack: Stack):
    """The program's committee, held to the reference's keys."""
    from dag_rider_tpu.verifier.base import KeyRegistry

    registry, _, secrets = KeyRegistry.generate_with_cert(stack.n)
    if list(secrets) != stack.keys.secret:
        raise AssertionError("the program's committee keys are not the configuration's")
    return registry


def build(config: dict, traffic: dict, seed: int) -> Stack:
    # a program without the certificate service fails here, before any
    # client is started
    from dag_rider_tpu.verifier.cert import CertBackend
    from dag_rider_tpu.verifier.sidecar import RemoteCertVerifier  # noqa: F401

    stack = Stack(config, traffic, seed)
    try:
        start_clients(stack)
        t0 = time.monotonic()
        backend = CertBackend(_registry(stack), stack.quorum, pair="device")
        stack.setup_parts["build_s"] = time.monotonic() - t0
        assemble(stack, backend)
        stats = backend.stats()
        stack.setup_parts["tables_s"] = stats["table_build_s"]
        stack.setup_parts["compile_s"] = stats["compile_s"]
    except BaseException:
        close(stack)
        raise
    return stack


def control_stack(control, config: dict, traffic: dict, seed: int) -> Stack:
    """This driver's stack with ``control(registry, quorum)`` as the
    sidecar's certificate backend."""
    stack = Stack(config, traffic, seed)
    try:
        return assemble(stack, control(_registry(stack), stack.quorum))
    except BaseException:
        close(stack)
        raise


def assemble(stack: Stack, backend) -> Stack:
    """The sidecar over ``backend`` and every client through one RPC."""
    from dag_rider_tpu.verifier.sidecar import VerifierSidecarServer

    if not stack.clients:
        start_clients(stack)
    stack.timed = TimedCertBackend(backend)
    t0 = time.monotonic()
    stack.server = VerifierSidecarServer(None, stack.address, cert_backend=stack.timed)
    stack.setup_parts["server_up_s"] = time.monotonic() - t0
    t0 = time.monotonic()
    for c in stack.clients:
        _await(c, "POOL")
    stack.setup_parts["pool_wait_s"] = time.monotonic() - t0
    t0 = time.monotonic()
    for c in stack.clients:
        c.stdin.write("SERVE\n")
        c.stdin.flush()
    for c in stack.clients:
        _await(c, "READY")
    stack.setup_parts["first_rpcs_s"] = time.monotonic() - t0
    return stack


def _maps_libtpu(pid: int) -> bool:
    try:
        with open(f"/proc/{pid}/maps") as fh:
            return any("libtpu" in line for line in fh)
    except OSError:
        return False


def run_window(stack: Stack, seconds: float, tracer=None) -> dict:
    spans0 = len(stack.timed.spans)
    t0 = time.monotonic()
    t_close = t0 + seconds
    for c in stack.clients:
        c.stdin.write(f"GO {t_close!r}\n")
        c.stdin.flush()
    touched = None
    while True:
        t = time.monotonic() - t0
        if t >= seconds:
            break
        if tracer is not None:
            tracer.tick(t)
        if touched is None and t >= min(1.0, seconds / 2):
            touched = sum(_maps_libtpu(c.pid) for c in stack.clients)
        time.sleep(min(0.2, seconds - t))
    if tracer is not None:
        tracer.stop()
    rpcs = []
    for c in stack.clients:
        out, _ = c.communicate(timeout=DRAIN_BOUND_S)
        if c.returncode != 0:
            raise RuntimeError(f"client {c.pid} exited {c.returncode}")
        rpcs.extend(json.loads(out)["rpcs"])
    spans = [s for s in stack.timed.spans[spans0:] if s[0] <= t_close]
    failed = sum(1 for r in rpcs if r[3] or len(r[4]) != 1)
    back = [r for r in rpcs if r[2] <= t_close and not r[3]]
    return {
        "t_open": t0,
        "seconds": seconds,
        "attempted": len(rpcs),
        "failed": failed,
        "samples": {
            "rpc_latency_s": [r[2] - r[1] for r in rpcs],
            "server_span_s": [b - a for a, b in spans],
            "server_gap_s": [b[0] - a[1] for a, b in zip(spans, spans[1:])],
        },
        "counters": {
            # each certificate stands for the 2f+1 signatures it aggregates
            "sigs_back_in_window": stack.quorum * len(back),
            "rpcs_back_in_window": len(back),
            "clients_with_libtpu": touched or 0,
            "window_s": seconds,
            "pair_rows": getattr(getattr(stack.timed.backend, "_pairing", None), "rows", None),
        },
        "rpcs": rpcs,
    }


def check(stack: Stack, observed: dict) -> dict:
    """Every verdict a client received, against the reference's verdict
    for that certificate of the pool."""
    t = stack.traffic
    pool = reference_bls.make_pool(
        stack.keys, t["pool_rounds"], t["wrong_kinds"], stack.seed
    )
    want = reference_bls.expected_verdicts(stack.keys, pool)
    mismatches = 0
    for k, _, _, failures, verdict in observed["rpcs"]:
        if not failures:
            mismatches += verdict != ("1" if want[k] else "0")
    return {
        "verdict_mismatches": {"value": mismatches, "limit": 0},
        "rpcs_failed": {"value": observed["failed"], "limit": 0},
        "clients_with_libtpu": {
            "value": observed["counters"]["clients_with_libtpu"], "limit": 0,
        },
    }


def close(stack: Stack) -> None:
    for c in stack.clients:
        if c.poll() is None:
            c.kill()
        c.wait()
        for pipe in (c.stdin, c.stdout):
            if pipe is not None:
                pipe.close()
    if stack.server is not None:
        stack.server.stop()
    shutil.rmtree(stack.tmp, ignore_errors=True)
