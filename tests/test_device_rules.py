"""Nothing on the served path may hide a missing or broken chip.

The rules PR 21 put in place, each checked on the CPU backend: where the
compile cache goes, which platform a device path accepts, what a mesh
shortfall does, that every serving stack compiles its one program before
it takes a batch (so no fault-contained window ever sees a compile), and
that the verifier says where it ran.
"""

import dataclasses
import json
import os
import subprocess
import sys

import pytest

from dag_rider_tpu.core.types import Block, Vertex, VertexID
from dag_rider_tpu.utils import jaxcache
from dag_rider_tpu.utils.slog import EventLog
from dag_rider_tpu.verifier import (
    CPUVerifier,
    VerifierCompileError,
    VerifierPipeline,
)
from dag_rider_tpu.verifier.base import KeyRegistry, VertexSigner
from dag_rider_tpu.verifier.tpu import TPUVerifier

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def keys():
    return KeyRegistry.generate(4)


def _signed(keys, count=8):
    seeds = keys[1]
    signers = [VertexSigner(s) for s in seeds]
    return [
        signers[j % 4].sign_vertex(
            Vertex(
                id=VertexID(1 + j // 4, j % 4),
                block=Block((f"tx-{j}".encode(),)),
                strong_edges=(VertexID(j // 4, 0),),
            )
        )
        for j in range(count)
    ]


# -- the compile cache is placed from outside --------------------------


_CACHE_PROBE = """
import json, os, sys
import jax, jax.numpy as jnp
import dag_rider_tpu.ops  # the one place that switches the cache on
def cache_probe(x):
    return x * 3 + 1
jax.jit(cache_probe)(jnp.arange(7)).block_until_ready()
print(json.dumps({"dir": jax.config.jax_compilation_cache_dir}))
"""


def _cache_probe(env_dir):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE],
        env=env,
        capture_output=True,
        text=True,
        timeout=120,
        check=True,
    ).stdout
    return json.loads(out.strip().splitlines()[-1])["dir"]


def _default_cache_entries():
    if not os.path.isdir(jaxcache._DEFAULT):
        return set()
    return set(os.listdir(jaxcache._DEFAULT))


def test_cache_dir_from_environment_is_left_alone(tmp_path):
    theirs = str(tmp_path / "their-cache")
    before = _default_cache_entries()
    assert _cache_probe(theirs) == theirs
    assert os.listdir(theirs), "nothing was cached where the env said"
    # other test processes may fill the checkout's cache meanwhile: only
    # the probe's own program must not be among what they added
    added = _default_cache_entries() - before
    assert not [e for e in added if e.startswith("jit_cache_probe-")], (
        "<checkout>/.jax_cache touched"
    )


def test_cache_dir_defaults_to_the_checkout():
    assert jaxcache._DEFAULT == os.path.join(ROOT, ".jax_cache")
    assert _cache_probe(None) == jaxcache._DEFAULT


def test_no_other_cache_dir_update_in_the_tree():
    hits = subprocess.run(
        ["git", "grep", "-l", "jax_compilation_cache_dir", "--", "*.py"],
        cwd=ROOT,
        capture_output=True,
        text=True,
    ).stdout.split()
    assert set(hits) <= {
        "dag_rider_tpu/utils/jaxcache.py",
        "tests/test_device_rules.py",
    }, hits


# -- the CPU backend only when asked for -------------------------------


def test_device_verifier_refuses_a_cpu_nobody_asked_for(keys, monkeypatch):
    reg, _ = keys
    assert TPUVerifier(reg).platform == "cpu"  # JAX_PLATFORMS=cpu: fine
    for env in (None, "tpu,cpu", ""):
        if env is None:
            monkeypatch.delenv("JAX_PLATFORMS")
        else:
            monkeypatch.setenv("JAX_PLATFORMS", env)
        with pytest.raises(RuntimeError, match="failed to initialise"):
            TPUVerifier(reg)
    from dag_rider_tpu.config import Config
    from dag_rider_tpu.consensus.simulator import Simulation

    with pytest.raises(RuntimeError, match="failed to initialise"):
        Simulation(Config(n=4), verifier="device")
    monkeypatch.setenv("JAX_PLATFORMS", " CPU ,tpu")
    assert TPUVerifier(reg).platform == "cpu"


def test_mesh_shortfall_is_an_error_on_an_accelerator(monkeypatch):
    import jax

    from dag_rider_tpu.parallel import mesh

    monkeypatch.setenv("DAGRIDER_MESH", "64")
    with pytest.warns(UserWarning, match="clamping"):
        assert mesh.mesh_from_env().devices.size == jax.device_count()

    class _Chip:
        platform = "tpu"

    monkeypatch.setattr(jax, "devices", lambda *a: [_Chip()])
    with pytest.raises(RuntimeError, match="DAGRIDER_MESH=64"):
        mesh.mesh_from_env()


# -- serving stacks compile before they take a batch -------------------


def _refuse(self, size, impl, tables, b_tab):
    raise RuntimeError("Mosaic failed to compile TPU kernel: not implemented")


def _node_config(keys_path, **over):
    return {
        "index": 0,
        "n": 4,
        "listen": "127.0.0.1:0",
        "peers": {},
        "keys": str(keys_path),
        "rbc": False,
        "verifier": "device",
        "coin": "round_robin",
        **over,
    }


@pytest.fixture
def keys_path(tmp_path):
    from dag_rider_tpu import node as node_mod

    path = tmp_path / "keys.json"
    node_mod.main(["keygen", "--n", "4", "--threshold", "2", "--out", str(path)])
    return path


def test_compile_error_fails_construction_not_a_window(keys, monkeypatch):
    from dag_rider_tpu.verifier.sidecar import VerifierSidecarServer

    reg, _ = keys
    vs = _signed(keys)
    monkeypatch.setattr(TPUVerifier, "_aot_lower", _refuse)

    # the stacks a node, a Simulation and a sidecar build
    with pytest.raises(VerifierCompileError, match="Mosaic failed"):
        VerifierPipeline(TPUVerifier(reg))
    with pytest.raises(VerifierCompileError):
        VerifierSidecarServer(TPUVerifier(reg), "127.0.0.1:0")
    # a pipeline told to put the compile off still compiles before its
    # window opens: neither a False mask nor a quarantine
    pipe = VerifierPipeline(TPUVerifier(reg), warmup=False)
    with pytest.raises(VerifierCompileError):
        pipe.verify_batch(vs)
    assert pipe.stats()["poisoned_windows"] == 0
    assert pipe.stats()["quarantined"] == 0
    # and a bare verifier's chunk-by-chunk pass raises it as it stands
    v = TPUVerifier(reg)
    v.fixed_bucket = 4
    with pytest.raises(VerifierCompileError):
        v.verify_rounds([vs])


def test_node_refuses_to_start_on_a_compile_error(keys_path, monkeypatch):
    """The pump loop logs and swallows every exception, so a node must
    never compile under it: the refusal is raised by the constructor
    (and the cluster runner, which builds the node first, exits
    non-zero), with or without the CPU ladder under the verifier."""
    from dag_rider_tpu import node as node_mod

    monkeypatch.setattr(TPUVerifier, "_aot_lower", _refuse)
    for over in ({}, {"verify_fallback": "cpu"}, {"verify_bucket": 64}):
        with pytest.raises(VerifierCompileError, match="Mosaic failed"):
            node_mod.Node(_node_config(keys_path, **over))


def test_served_stacks_run_one_program_whatever_the_batch(keys, keys_path):
    """After construction nothing is left to compile: a batch smaller
    than the bucket is padded to it, a larger one is chunked into it."""
    from dag_rider_tpu import node as node_mod
    from dag_rider_tpu.verifier.sidecar import (
        RemoteVerifier,
        VerifierSidecarServer,
    )

    with open(keys_path) as fh:
        node_keys = node_mod.load_keys(json.load(fh))
    pool = _signed(node_keys, 40)
    pool[7] = dataclasses.replace(pool[7], signature=bytes(64))
    want = [True] * 40
    want[7] = False
    nd = node_mod.Node(_node_config(keys_path))
    try:
        pipe = nd.process.verifier
        base = pipe.verifier
        assert pipe.fixed_bucket == 16 and list(base._aot) == [(16, "jnp")]
        for lo, hi in ((0, 1), (1, 17), (0, 40)):
            assert pipe.verify_batch(pool[lo:hi]) == want[lo:hi]
        assert list(base._aot) == [(16, "jnp")]
    finally:
        nd.net.close()

    reg, _ = keys
    pool = _signed(keys, 40)
    want = CPUVerifier(reg).verify_batch(pool)
    backend = TPUVerifier(reg)
    server = VerifierSidecarServer(backend, "127.0.0.1:0")
    remote = RemoteVerifier(server.address)
    try:
        assert backend.fixed_bucket == 16 and len(backend._aot) == 1
        assert remote.verify_batch(pool) == want
        assert remote.verify_batch(pool[:3]) == want[:3]
        assert len(backend._aot) == 1 and backend.stats()["bucket"] == 16
    finally:
        remote.close()
        server.stop()


def test_simulation_fixes_the_committees_bucket():
    """Simulation(verifier="device") takes the default path a node
    takes: one bucket, n rounded up, compiled when the window is built."""
    from dag_rider_tpu.config import Config
    from dag_rider_tpu.consensus.simulator import Simulation

    sim = Simulation(Config(n=4, propose_empty=True), verifier="device")
    v = sim.processes[0].verifier
    assert v.fixed_bucket is None and not v._aot
    sim.run(max_messages=64)
    assert v.fixed_bucket == 16 and list(v._aot) == [(16, "jnp")]
    assert v.stats()["dispatches"] > 0 and v.stats()["bucket"] == 16


def test_warmup_fixes_and_compiles_the_committees_shape(monkeypatch):
    """Not the 16-row minimum bucket: one round of the registry's n
    vertices rounded to its bucket, or the fixed bucket when set."""
    lowered = []
    monkeypatch.setattr(
        TPUVerifier, "_comb_tables_dev", lambda self: (None, None)
    )
    monkeypatch.setattr(
        TPUVerifier,
        "_aot_lower",
        lambda self, size, impl, tables, b_tab: lowered.append(size) or object(),
    )
    reg, _ = KeyRegistry.generate(40)
    default = TPUVerifier(reg)
    default.warmup()
    pinned = TPUVerifier(reg)
    pinned.fixed_bucket = 16
    pinned.warmup()
    assert lowered == [64, 16]
    assert (default.fixed_bucket, pinned.fixed_bucket) == (64, 16)
    assert default.warmup() == 0.0 and lowered == [64, 16]


# -- the verifier says where it ran ------------------------------------

_WHERE = ("platform", "device_kind", "impl", "bucket")
#: the window's containment counters: VerifierPipeline's alone
_CONTAINED = ("poisoned_windows", "quarantined", "quarantine_rejected")


def test_stats_carry_platform_and_counters_unconditionally(keys):
    reg, _ = keys
    v = TPUVerifier(reg)
    pipe = VerifierPipeline(v, warmup=False)
    for k in _WHERE:
        assert k in v.stats() and k in pipe.stats(), k
    for k in _CONTAINED:
        assert k in pipe.stats() and k not in v.stats(), k
    assert pipe.stats()["retries"] == pipe.stats()["fallbacks"] == 0
    assert pipe.verify_batch(_signed(keys)) == [True] * 8
    after = pipe.stats()
    assert after["platform"] == "cpu" and after["impl"] == "jnp"
    assert after["bucket"] == 16 and after["poisoned_windows"] == 0


def test_node_started_event_names_the_verifier(keys_path):
    from dag_rider_tpu import node as node_mod

    events = []
    nd = node_mod.Node(
        _node_config(keys_path), log=EventLog(events.append, node=0)
    )
    try:
        nd.start()
    finally:
        nd.stop()
    started = [e for e in events if e.get("event") == "started"]
    assert len(started) == 1
    for k in _WHERE + _CONTAINED + ("retries", "fallbacks"):
        assert k in started[0], k
    assert started[0]["verifier"] == "VerifierPipeline"
    assert started[0]["platform"] == "cpu"
