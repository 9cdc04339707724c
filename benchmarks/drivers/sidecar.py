"""Driver ``sidecar``: the verify service as deployed — this process
holds the chip and hosts ``VerifierSidecarServer`` over the device
verifier on a unix socket; the validators are client OS processes
(``sidecar_client.py``) that never load libtpu, each calling
``RemoteVerifier.verify_batch`` with one whole round, one RPC in flight.
``chip_smoke.py`` phase B's layout, with the validators' consensus left
out.

``build`` makes the device verifier the configuration states and hands
it to ``assemble``; ``run_window`` drives an assembled stack (a test
assembles one over the host verifier).
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

from benchmarks.harness import reference, roundpool

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CLIENT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "sidecar_client.py")
#: a mask is waited for this long past the window's close
DRAIN_BOUND_S = 60.0
QUIET = ("poisoned_windows", "quarantined", "quarantine_rejected")


class TimedBackend:
    """The benchmark's wrapper around the sidecar's backend: when each
    ``verify_batch`` was entered and left, on this process's clock."""

    def __init__(self, backend):
        self.backend = backend
        self.spans: List[tuple] = []

    def warmup(self) -> float:
        warm = getattr(self.backend, "warmup", None)
        return warm() if callable(warm) else 0.0

    def verify_batch(self, vertices):
        t0 = time.monotonic()
        with jax.profiler.TraceAnnotation("bench.server.verify_batch"):
            mask = self.backend.verify_batch(vertices)
        self.spans.append((t0, time.monotonic()))
        return mask


class Stack:
    def __init__(self, config: dict, traffic: dict, seed: int):
        self.config = config
        self.traffic = traffic
        self.seed = seed
        self.n = config["n"]
        self.keys = reference.Keys(self.n)
        self.tmp = tempfile.mkdtemp(prefix="bs-")
        sock = os.path.join(self.tmp, "v.sock")
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
    process builds tables and loads its program."""
    t = stack.traffic
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    for _ in range(t["clients"]):
        stack.clients.append(
            subprocess.Popen(
                [
                    sys.executable, CLIENT, "--root", ROOT,
                    "--address", stack.address, "--n", str(stack.n),
                    "--rounds", str(t["pool_rounds"]),
                    "--wrong", str(t["wrong_per_round"]),
                    "--seed", str(stack.seed),
                ],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, env=env,
            )
        )


def build(config: dict, traffic: dict, seed: int) -> Stack:
    from dag_rider_tpu.verifier.base import KeyRegistry
    from dag_rider_tpu.verifier.tpu import TPUVerifier

    stack = Stack(config, traffic, seed)
    try:
        start_clients(stack)
        t0 = time.monotonic()
        registry, _ = KeyRegistry.generate(stack.n)
        backend = TPUVerifier(registry)
        stack.setup_parts["build_s"] = time.monotonic() - t0
        assemble(stack, backend)
        vs = backend.stats()
        stack.setup_parts["tables_s"] = vs["table_build_s"]
        stack.setup_parts["compile_or_cache_load_s"] = sum(vs["compile_s"].values())
    except BaseException:
        close(stack)
        raise
    return stack


def control_stack(control, config: dict, traffic: dict, seed: int) -> Stack:
    """This driver's stack with ``control`` as the sidecar's backend."""
    from dag_rider_tpu.verifier.base import KeyRegistry

    stack = Stack(config, traffic, seed)
    try:
        return assemble(stack, control(KeyRegistry.generate(stack.n)[0]))
    except BaseException:
        close(stack)
        raise


def assemble(stack: Stack, backend) -> Stack:
    """The sidecar over ``backend`` and every client through one RPC."""
    from dag_rider_tpu.verifier.sidecar import VerifierSidecarServer

    if list(backend.registry.public_keys) != stack.keys.public:
        raise AssertionError("the program's committee keys are not the configuration's")
    if not stack.clients:
        start_clients(stack)
    stack.timed = TimedBackend(backend)
    t0 = time.monotonic()
    stack.server = VerifierSidecarServer(stack.timed, stack.address)
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
    n = stack.n
    spans = [s for s in stack.timed.spans[spans0:] if s[0] <= t_close]
    failed = sum(1 for r in rpcs if r[3] or len(r[4]) != n)
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
            "sigs_back_in_window": sum(n for r in rpcs if r[2] <= t_close and not r[3]),
            "rpcs_back_in_window": sum(1 for r in rpcs if r[2] <= t_close),
            "clients_with_libtpu": touched or 0,
            "window_s": seconds,
            "bucket": getattr(stack.timed.backend, "fixed_bucket", None),
        },
        "rpcs": rpcs,
    }


def check(stack: Stack, observed: dict) -> dict:
    """Every mask a client received, against the reference's verdicts
    for that round of the pool, vertex by vertex."""
    t = stack.traffic
    pool = roundpool.make_pool(
        stack.keys, n=stack.n, rounds=t["pool_rounds"],
        wrong_per_round=t["wrong_per_round"], seed=stack.seed,
    )
    want: Dict[int, str] = {}
    mismatches = 0
    for k, _, _, _, mask in observed["rpcs"]:
        if k not in want:
            want[k] = "".join(
                "1" if ok else "0" for ok in roundpool.expected_mask(stack.keys, pool[k])
            )
        ref = want[k]
        mismatches += sum(a != b for a, b in zip(mask, ref)) + abs(len(mask) - len(ref))
    backend = stack.timed.backend
    stats = backend.stats() if callable(getattr(backend, "stats", None)) else {}
    return {
        "mask_mismatches": {"value": mismatches, "limit": 0},
        "rpcs_failed": {"value": observed["failed"], "limit": 0},
        "clients_with_libtpu": {
            "value": observed["counters"]["clients_with_libtpu"], "limit": 0,
        },
        "contained_or_retried": {"value": sum(stats.get(k, 0) for k in QUIET), "limit": 0},
        "programs_beyond_one": {
            "value": len(stats.get("compile_s", {"host": 0})) - 1, "limit": 0,
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
