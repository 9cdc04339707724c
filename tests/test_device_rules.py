"""Nothing on the served path may hide a missing or broken chip.

The rules PR 21 put in place, each checked on the CPU backend: where the
compile cache goes, which platform a device path accepts, what a mesh
shortfall does, that a compile failure is never contained, and that the
verifier says where it ran.
"""

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
    ResilientVerifier,
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
    _, seeds = keys
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
from dag_rider_tpu.utils.jaxcache import enable_persistent_cache
enable_persistent_cache()
jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()
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
    assert _default_cache_entries() == before, "<checkout>/.jax_cache touched"


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


# -- a compile failure is never contained ------------------------------


def _refuse(self, size, impl, tables, b_tab):
    raise RuntimeError("Mosaic failed to compile TPU kernel: not implemented")


def test_compile_error_escapes_every_containment(keys, monkeypatch):
    reg, _ = keys
    vs = _signed(keys)
    monkeypatch.setattr(TPUVerifier, "_aot_lower", _refuse)

    # the window: neither a False mask nor a quarantine
    pipe = VerifierPipeline(TPUVerifier(reg), warmup=False)
    with pytest.raises(VerifierCompileError, match="Mosaic failed"):
        pipe.verify_batch(vs)
    assert pipe.stats()["poisoned_windows"] == 0
    assert pipe.stats()["quarantined"] == 0
    # construction compiles the committee's program: it fails there
    with pytest.raises(VerifierCompileError):
        VerifierPipeline(TPUVerifier(reg))
    # the verifier's own chunk-streaming window
    v = TPUVerifier(reg)
    v.fixed_bucket = 4
    with pytest.raises(VerifierCompileError):
        v.verify_rounds([vs])
    assert v.poisoned_windows == 0
    # the ladder: no quiet fall to the CPU floor
    ladder = ResilientVerifier(
        [VerifierPipeline(TPUVerifier(reg), warmup=False), CPUVerifier(reg)]
    )
    with pytest.raises(VerifierCompileError):
        ladder.verify_batch(vs)
    assert ladder.stats()["fallbacks"] == 0 and ladder.stats()["retries"] == 0


def test_compile_error_crosses_the_sidecar(keys, monkeypatch):
    from dag_rider_tpu.verifier.sidecar import (
        RemoteVerifier,
        VerifierSidecarServer,
    )

    reg, _ = keys
    backend = TPUVerifier(reg)
    server = VerifierSidecarServer(backend, "127.0.0.1:0", warmup=False)
    remote = RemoteVerifier(server.address, retries=2)
    try:
        monkeypatch.setattr(TPUVerifier, "_aot_lower", _refuse)
        with pytest.raises(VerifierCompileError, match="Mosaic failed"):
            remote.verify_batch(_signed(keys))
        assert remote.rpc_failures == 0 and remote.retries_total == 0
    finally:
        remote.close()
        server.stop()


def test_warmup_compiles_the_committees_shape(monkeypatch):
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
    TPUVerifier(reg).warmup()
    pinned = TPUVerifier(reg)
    pinned.fixed_bucket = 16
    pinned.warmup()
    assert lowered == [64, 16]


# -- the verifier says where it ran ------------------------------------

_ALWAYS = (
    "platform",
    "device_kind",
    "impl",
    "bucket",
    "poisoned_windows",
    "quarantined",
    "quarantine_rejected",
)


def test_stats_carry_platform_and_counters_unconditionally(keys):
    reg, _ = keys
    v = TPUVerifier(reg)
    pipe = VerifierPipeline(v, warmup=False)
    for stats in (v.stats(), pipe.stats()):
        for k in _ALWAYS:
            assert k in stats, k
    assert pipe.stats()["retries"] == pipe.stats()["fallbacks"] == 0
    assert pipe.verify_batch(_signed(keys)) == [True] * 8
    after = pipe.stats()
    assert after["platform"] == "cpu" and after["impl"] == "jnp"
    assert after["bucket"] == 16 and after["poisoned_windows"] == 0


def test_node_started_event_names_the_verifier(tmp_path):
    from dag_rider_tpu import node as node_mod

    keys_path = tmp_path / "keys.json"
    node_mod.main(
        ["keygen", "--n", "4", "--threshold", "2", "--out", str(keys_path)]
    )
    events = []
    nd = node_mod.Node(
        {
            "index": 0,
            "n": 4,
            "listen": "127.0.0.1:0",
            "peers": {},
            "keys": str(keys_path),
            "rbc": False,
            "verifier": "device",
            "coin": "round_robin",
        },
        log=EventLog(events.append, node=0),
    )
    try:
        nd.start()
    finally:
        nd.stop()
    started = [e for e in events if e.get("event") == "started"]
    assert len(started) == 1
    for k in _ALWAYS + ("retries", "fallbacks"):
        assert k in started[0], k
    assert started[0]["verifier"] == "VerifierPipeline"
    assert started[0]["platform"] == "cpu"
