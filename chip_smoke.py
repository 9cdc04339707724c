"""chip_smoke.py — the served path, once, on the chip.

    python3 chip_smoke.py [--seed N]

One process that owns the chip drives the system's main path through the
entry points a user has and checks what comes out against the host
oracles. It has no CPU mode: when jax's platform is not ``tpu`` it exits
non-zero before doing anything else (tests/test_chip_smoke.py rehearses
the phase functions at n=4 on the CPU backend instead).

- **A** — n=256, threshold-BLS coin, mempool in front, the device
  verifier in the loop: ``Simulation(verifier="device")`` under a seeded
  open-loop ``ClusterLoadDriver`` on the virtual clock, every knob at
  its default (so the dispatch bucket is the one the system fixes: n
  rounded up to a power of two). Accept masks of an honest and an
  adversarial round must equal ``CPUVerifier``'s bit for bit; every
  process decides >= 2 waves; every accepted transaction is delivered;
  one program was compiled; nothing on the verify path was contained
  or retried.
- **B** — the deployed layout: this process hosts the sidecar that holds
  the chip, four ``cluster.runner`` OS processes reach it with
  ``"verifier": "remote"`` and never touch the chip themselves.
- **C** — the non-default device lanes compile and match their oracles:
  the G1 MSM with the Mosaic tree engine, and the Ed25519 group kernels
  at one 4-D and one 2-D block shape.
- **D** — four chips (a stated skip with fewer): the sharded verifier
  (``verify_bucket`` 512, so each shard's 128 rows take the Mosaic
  tree) and the sharded MSM on a real mesh.

Prints the device line, one JSON line per phase, and last
``{"ok": true, "device": {...}}``. Exits non-zero on the first failed
check. Times and bytes in the phase lines are set-up facts about this
run, not a benchmark.
"""

from __future__ import annotations

import argparse
import dataclasses
import hashlib
import json
import os
import random
import shutil
import signal
import sys
import tempfile
import threading
import time
import traceback

#: the whole run, compilation included, must end inside the contract's
#: 1200 s with every child stopped — so it ends itself a little earlier
DEADLINE_S = 1150


#: the containment / retry / fallback counters that must stay zero
QUIET = (
    "poisoned_windows",
    "quarantined",
    "quarantine_rejected",
    "retries",
    "fallbacks",
)


class SmokeFailure(AssertionError):
    """A check of the smoke did not hold."""


_T0 = time.monotonic()


def note(msg: str) -> None:
    """Progress on stderr: where the time went if the run is cut short."""
    print(f"[smoke +{time.monotonic() - _T0:6.1f}s] {msg}", file=sys.stderr, flush=True)


def check(cond: bool, what: str, **ctx) -> None:
    if not cond:
        raise SmokeFailure(f"{what} {ctx}" if ctx else what)


# ----------------------------------------------------------------------
# Rounds to verify: honest, and every way a vertex can be wrong
# ----------------------------------------------------------------------


def signed_round(signers, rnd: int) -> list:
    """One honest round: every source's signed vertex."""
    from dag_rider_tpu.core.types import Block, Vertex, VertexID

    n = len(signers)
    quorum = 2 * ((n - 1) // 3) + 1
    edges = tuple(VertexID(rnd - 1, s) for s in range(quorum))
    return [
        signers[i].sign_vertex(
            Vertex(
                id=VertexID(rnd, i),
                block=Block((f"r{rnd}-tx-{i}".encode() * 2,)),
                strong_edges=edges,
            )
        )
        for i in range(n)
    ]


def order8_point_key() -> bytes:
    """Encoding of a point of order 8 — a public key outside the
    prime-order subgroup, under which [k]A vanishes for k = 0 mod 8."""
    from dag_rider_tpu.crypto import ed25519

    y = 2
    while True:
        pt = ed25519.point_decompress(y.to_bytes(32, "little"))
        if pt is not None:
            # [L]P is P's torsion part; it has order 8 iff [4] of it
            # is not the identity
            t = ed25519.scalar_mult(ed25519.L, pt)
            t4 = ed25519.scalar_mult(4, t)
            if not ed25519.point_equal(t4, ed25519.IDENTITY):
                return ed25519.point_compress(t)
        y += 1


def adversarial_batch(registry, signers, rnd: int, seed: int):
    """(registry', vertices): a round in which about a fifth of the
    vertices are wrong, one way each — a flipped signature bit, s + L
    (non-canonical), a signature under another source's index, a
    truncated signature, a tampered block — over a registry in which two
    sources hold an order-8 key, plus forgeries under those keys of
    which some verify (k = 0 mod 8) and some do not."""
    from dag_rider_tpu.core.types import Block, Vertex, VertexID
    from dag_rider_tpu.crypto import ed25519

    n = registry.n
    rng = random.Random(seed)
    torsion_key = order8_point_key()
    weak = sorted(rng.sample(range(n), 2 if n > 4 else 1))
    keys = list(registry.public_keys)
    for j in weak:
        keys[j] = torsion_key
    reg = dataclasses.replace(registry, public_keys=tuple(keys))

    vs = signed_round(signers, rnd)
    for i in range(0, n, 5):
        v, sig = vs[i], vs[i].signature
        kind = (i // 5) % 5
        if kind == 0:
            b = bytearray(sig)
            b[rng.randrange(64)] ^= 1 << rng.randrange(8)
            vs[i] = dataclasses.replace(v, signature=bytes(b))
        elif kind == 1:
            s_big = int.from_bytes(sig[32:], "little") + ed25519.L
            vs[i] = dataclasses.replace(
                v, signature=sig[:32] + s_big.to_bytes(32, "little")
            )
        elif kind == 2:
            vs[i] = dataclasses.replace(
                v, id=VertexID(rnd, (i + 1) % n)
            )
        elif kind == 3:
            vs[i] = dataclasses.replace(v, signature=sig[:63])
        else:
            vs[i] = dataclasses.replace(v, block=Block((b"tampered",)))

    # forgeries under the order-8 keys: R = [s]B ignores the key, so the
    # equation holds exactly when [k]A is the identity, k = 0 mod 8
    hits = 0
    ctr = 0
    forged = []
    while len(forged) < 16 or hits < 2:
        j = weak[ctr % len(weak)]
        s = rng.randrange(1, ed25519.L)
        r_enc = ed25519.point_compress(ed25519.scalar_mult_base(s))
        v = Vertex(
            id=VertexID(rnd + 1, j),
            block=Block((f"forged-{ctr}".encode(),)),
            strong_edges=(VertexID(rnd, 0),),
        )
        k = int.from_bytes(
            hashlib.sha512(r_enc + torsion_key + v.signing_bytes()).digest(),
            "little",
        ) % ed25519.L
        ctr += 1
        if k % 8 == 0:
            hits += 1
        elif len(forged) >= 16:
            continue
        forged.append(
            dataclasses.replace(
                v, signature=r_enc + s.to_bytes(32, "little")
            )
        )
    return reg, vs + forged


def mask_checks(make_stack, registry, signers, seed: int):
    """Accept masks at full width against the host oracle: honest rounds
    (enough of them to keep two bucket-sized chunks in flight) and the
    adversarial batch, each through ``make_stack(registry)`` — the stack
    a node builds, a ``VerifierPipeline`` over a device verifier of its
    own, whose construction fixes the bucket and compiles its program.
    Returns (honest stack, masks)."""
    from dag_rider_tpu.verifier.cpu import CPUVerifier

    honest = make_stack(registry)
    bucket = honest.fixed_bucket
    note(f"bucket {bucket} compiled: {honest.verifier.stats()['compile_s']}, "
         f"tables {honest.verifier.table_build_s:.1f}s")
    rounds = [
        signed_round(signers, r)
        for r in range(1, 2 * bucket // registry.n + 2)
    ]
    got = honest.verify_rounds(rounds)
    want = [CPUVerifier(registry).verify_batch(r) for r in rounds]
    check(got == want, "honest masks differ from CPUVerifier")
    check(all(all(m) for m in got), "an honest signature was rejected")
    note("honest masks equal the CPU oracle")

    adv_reg, adv = adversarial_batch(registry, signers, 5, seed)
    twin = make_stack(adv_reg)
    got_adv = twin.verify_batch(adv)
    want_adv = CPUVerifier(adv_reg).verify_batch(adv)
    check(
        got_adv == want_adv,
        "adversarial mask differs from CPUVerifier",
        differing=[i for i, (a, b) in enumerate(zip(got_adv, want_adv)) if a != b],
    )
    note("adversarial mask equals the CPU oracle")
    n_rej = want_adv.count(False)
    forged_ok = sum(want_adv[registry.n :])
    check(n_rej >= registry.n // 6, "adversarial batch rejected too little")
    check(forged_ok >= 2, "no order-8 forgery verified; the case is vacuous")
    check(
        honest.stats()["queue_depth_max"] >= 2,
        "the window never held two chunks",
        stats=honest.stats(),
    )
    for pipe in (honest, twin):
        st = pipe.stats()
        check(
            not any(st[k] for k in QUIET),
            "mask checks went through containment",
            stats=st,
        )
        check(
            list(pipe.verifier.stats()["compile_s"])
            == [f"{bucket}x{st['impl']}"],
            "the stack compiled more than its one program",
            programs=pipe.verifier.stats()["compile_s"],
        )
    return honest, {
        "honest": got,
        "adversarial": got_adv,
        "adversarial_rejected": n_rej,
        "order8_forgeries_accepted": forged_ok,
    }


# ----------------------------------------------------------------------
# Phase A — full width, in process
# ----------------------------------------------------------------------


def phase_a(
    *,
    n: int = 256,
    seed: int = 0,
    rate: float = 4000.0,
    load_s: float = 0.35,
    dt: float = 0.05,
    min_accepted: int = 1000,
    min_waves: int = 2,
    settle_s: float = 420.0,
    expect_platform: str = "tpu",
    expect_impl: str = "pallas",
) -> dict:
    import jax

    from dag_rider_tpu.config import Config, MempoolConfig
    from dag_rider_tpu.consensus.scenarios import coin_factory
    from dag_rider_tpu.consensus.simulator import Simulation
    from dag_rider_tpu.mempool.loadgen import ClusterLoadDriver, LoadGenerator
    from dag_rider_tpu.verifier.pipeline import VerifierPipeline
    from dag_rider_tpu.verifier.tpu import TPUVerifier

    cfg = Config(n=n, coin="threshold_bls", propose_empty=True, gc_depth=24)
    sim = Simulation(
        cfg,
        verifier="device",
        coin_factory=coin_factory("threshold_bls", n, cfg.f),
    )
    verifier = sim.processes[0].verifier
    check(type(verifier) is TPUVerifier, "not the device verifier")
    check(
        verifier.platform == expect_platform,
        "verifier on the wrong platform",
        platform=verifier.platform,
    )
    signers = [p.signer for p in sim.processes]
    note(f"simulation built: n={n}")

    # -- the mask, at full width, before any consensus: through what a
    # node with no "verify_bucket" builds, over verifiers of their own.
    # The simulation's verifier is not touched until its load is.
    stack, masks = mask_checks(
        lambda reg: VerifierPipeline(TPUVerifier(reg)),
        verifier.registry, signers, seed,
    )
    probe, bucket = stack.verifier, stack.fixed_bucket
    check(bucket >= n, "one round does not fit one dispatch", bucket=bucket)
    check(
        probe.last_impl == expect_impl and probe.last_size == bucket,
        "dispatch did not run the expected program",
        impl=probe.last_impl,
        bucket=probe.last_size,
    )
    mask, count = probe.dispatch_batch(signed_round(signers, 1))
    on = sorted(d.platform for d in mask.devices())
    check(on == [expect_platform], "mask not on the device", devices=on)
    probe.resolve_batch((mask, count))

    # -- the served path under load -----------------------------------
    check(
        verifier.fixed_bucket is None and not verifier.stats()["compile_s"],
        "the simulation's verifier was touched before its load",
    )
    gen = LoadGenerator(
        clients=32, rate=rate, tx_bytes=32, seed=seed, profile="poisson"
    )
    drv = ClusterLoadDriver(
        sim, gen, mcfg=MempoolConfig(cap=65536, batch_bytes=4096), dt=dt
    )
    t0 = time.monotonic()
    drv.run(load_s)
    note(f"load window and drain done: round {max(p.round for p in sim.processes)}")
    # until every process has decided min_waves and the last accepted
    # transaction is out: one round of messages at a time
    while time.monotonic() - t0 < settle_s:
        if min(p.decided_wave for p in sim.processes) >= min_waves and len(
            set(drv.delivered_txs(0))
        ) == len(drv.accepted):
            break
        sim.run(max_messages=n * n)
    wall = time.monotonic() - t0
    rounds = max(p.round for p in sim.processes)
    min_round = min(p.round for p in sim.processes)
    decided = [p.decided_wave for p in sim.processes]

    sim.check_agreement()
    audit = drv.audit()
    check(min(decided) >= min_waves, "a process decided too few waves",
          min_decided=min(decided))
    check(audit["accepted"] >= min_accepted, "too few accepted", audit=audit)
    check(
        audit["lost"] == 0
        and audit["duplicates"] == 0
        and audit["delivered"] == audit["accepted"],
        "audit",
        audit=audit,
    )

    # everything the simulation's verifier ever did, it did in the loop
    stats = verifier.stats()
    check(stats["dispatches"] > 0, "the verifier dispatched nothing")
    check(
        stats["sigs_dispatched"] >= n * (min_round - 1),
        "fewer signatures dispatched than rounds run",
        sigs=stats["sigs_dispatched"],
        rounds=min_round - 1,
    )
    # the bucket the system fixed by itself, and the one program for it
    check(
        verifier.fixed_bucket == bucket
        and stats["impl"] == expect_impl
        and stats["bucket"] == bucket
        and list(stats["compile_s"]) == [f"{bucket}x{expect_impl}"],
        "in-loop dispatch left the one expected program",
        fixed_bucket=verifier.fixed_bucket,
        stats=stats,
    )
    pipe = sim._verify_pipe
    check(pipe is not None, "the coalesced window never opened")
    window = pipe.stats()
    quiet = {k: window[k] for k in QUIET}
    check(not any(quiet.values()), "contained or retried", **quiet)
    mem = jax.devices()[0].memory_stats() or {}
    return {
        "phase": "A",
        "ok": True,
        "n": n,
        "platform": stats["platform"],
        "device_kind": stats["device_kind"],
        "impl": stats["impl"],
        "bucket": bucket,
        "bucket_set_by": "default",
        "decided_waves_min": min(decided),
        "decided_waves_max": max(decided),
        "rounds": rounds,
        "accepted": audit["accepted"],
        "delivered": audit["delivered"],
        "lost": audit["lost"],
        "duplicates": audit["duplicates"],
        "dispatches": stats["dispatches"],
        "sigs_dispatched": stats["sigs_dispatched"],
        **quiet,
        "masks_equal_cpu": True,
        "adversarial_rejected": masks["adversarial_rejected"],
        "order8_forgeries_accepted": masks["order8_forgeries_accepted"],
        "table_build_s": probe.stats()["table_build_s"],
        "compile_s": probe.stats()["compile_s"],
        "compile_s_from_cache": stats["compile_s"],
        "wall_s_per_round": round(wall / max(1, rounds), 3),
        "load_and_settle_s": round(wall, 1),
        # host seconds inside that wall, on the host's clock: filling the
        # transfer arrays, blocked on a mask, and the whole verify seam
        "in_loop_prepare_s": stats["prepare_s"],
        "in_loop_wait_s": window["wait_s"],
        "in_loop_seam_s": window["seam_s"],
        "peak_bytes_in_use": mem.get("peak_bytes_in_use"),
    }


# ----------------------------------------------------------------------
# Phase B — the deployed layout: one process per chip
# ----------------------------------------------------------------------


def _maps_libtpu(pid: int) -> bool:
    try:
        with open(f"/proc/{pid}/maps") as fh:
            return any("libtpu" in line for line in fh)
    except OSError:
        return False


def phase_b(
    *,
    seed: int = 0,
    load_s: float = 4.0,
    rate: float = 200.0,
    expect_platform: str = "tpu",
) -> dict:
    from dag_rider_tpu.cluster import client
    from dag_rider_tpu.cluster.audit import audit_cluster
    from dag_rider_tpu.cluster.directory import build_cluster
    from dag_rider_tpu.cluster.supervisor import ClusterSupervisor
    from dag_rider_tpu.node import load_keys
    from dag_rider_tpu.verifier.sidecar import VerifierSidecarServer
    from dag_rider_tpu.verifier.tpu import TPUVerifier

    n = 4
    root = tempfile.mkdtemp(prefix="dagrider-smoke-")
    addr = f"unix:{os.path.join(root, 'verifier.sock')}"
    server = sup = None
    try:
        spec = build_cluster(
            root,
            n,
            seed=seed,
            node_overrides={"verifier": "remote", "verifier_address": addr},
        )
        with open(os.path.join(root, "keys.json")) as fh:
            registry = load_keys(json.load(fh))[0]
        backend = TPUVerifier(registry)
        server = VerifierSidecarServer(backend, addr)
        sup = ClusterSupervisor(spec)
        sup.start_all()
        note(f"sidecar up: {backend.stats()['compile_s']}")
        dead = sup.wait_ready(60.0)
        check(not dead, "runners not ready", nodes=dead)
        note("runners ready")
        load: dict = {}
        loader = threading.Thread(
            target=lambda: load.update(
                client.drive_load(
                    spec, duration_s=load_s, rate=rate, seed=seed
                )
            ),
            daemon=True,
        )
        loader.start()
        loader.join(timeout=load_s + 30)
        check(not loader.is_alive(), "load driver did not finish")
        time.sleep(2.0)  # the tail of the load commits
        touched = [
            i for i, p in sup.procs.items() if _maps_libtpu(p.pid)
        ]
        forced = sup.stop_all()
        sup = None
        report = audit_cluster(spec)
        check(report["ok"], "cluster audit", violations=report["violations"])
        check(not forced, "runners had to be killed", nodes=forced)
        check(report["accepted_tx"] > 0, "no transaction was accepted")
        check(report["lost_tx"] == 0, "lost acknowledged transactions")
        check(not touched, "a runner loaded libtpu", nodes=touched)
        stats = backend.stats()
        check(stats["dispatches"] > 0, "the sidecar dispatched nothing")
        check(
            stats["platform"] == expect_platform,
            "sidecar on the wrong platform",
            platform=stats["platform"],
        )
        return {
            "phase": "B",
            "ok": True,
            "nodes": n,
            "verifier": "remote",
            "accepted": report["accepted_tx"],
            "delivered": report["delivered_tx"],
            "in_flight": report["in_flight_tx"],
            "lost": report["lost_tx"],
            "duplicates": report["duplicate_tx"],
            "decided_waves": report["decided_waves"],
            "sidecar_platform": stats["platform"],
            "sidecar_impl": stats["impl"],
            "sidecar_bucket": stats["bucket"],
            "sidecar_dispatches": stats["dispatches"],
            "sidecar_sigs": stats["sigs_dispatched"],
            "sidecar_compile_s": stats["compile_s"],
            "runners_with_libtpu": touched,
        }
    finally:
        if sup is not None:
            sup.stop_all(timeout_s=10.0)
        if server is not None:
            server.stop()
        shutil.rmtree(root, ignore_errors=True)


# ----------------------------------------------------------------------
# Phase C — the non-default device lanes
# ----------------------------------------------------------------------


def msm_check(msm, t: int, seed: int) -> None:
    """``msm`` over t random scalars and distinct points == the host
    oracle's sum."""
    from dag_rider_tpu.crypto import bls12381 as bls

    rng = random.Random(seed)
    pts, acc = [], bls.g1_mul(rng.randrange(1, bls.R))
    for _ in range(t):
        pts.append(acc)
        acc = bls.g1_double(acc)
    ks = [rng.randrange(0, bls.R) for _ in range(t)]
    check(
        msm(ks, pts) == bls.g1_msm(ks, pts),
        "device MSM differs from the host oracle",
    )


def group_kernel_checks(lanes: int, interpret: bool = False) -> None:
    """padd_xx / finish_check / pow22523 at one lane count against the
    jnp tree, on real signature data with some rows made wrong."""
    import jax.numpy as jnp
    import numpy as np

    from dag_rider_tpu.ops import comb, curve, field as F
    from dag_rider_tpu.ops import pallas_group as PG

    rng = np.random.default_rng(lanes)
    tab = comb.base_table_xyzt()  # [64, 16, 4, 22]: real curve points

    def points(seed_off):
        r = np.random.default_rng(lanes + seed_off)
        return jnp.asarray(
            tab[r.integers(0, 64, lanes), r.integers(1, 16, lanes)]
        )

    def limb_major(p):  # [lanes, 4, 22] -> [88, lanes]
        return jnp.moveaxis(p.reshape(lanes, PG.ROWS), 0, 1)

    p, q = points(1), points(2)
    got = PG.padd_xx(limb_major(p), limb_major(q), interpret=interpret)
    want = comb.padd_cached(p, comb.to_cached(q))
    check(
        bool(jnp.array_equal(got, limb_major(want))),
        "padd_xx differs from the jnp addition",
        lanes=lanes,
    )

    z = jnp.asarray(rng.integers(0, 4096, (lanes, F.LIMBS), dtype=np.int32))
    got = PG.pow22523(jnp.moveaxis(z, 0, 1), interpret=interpret)
    check(
        bool(
            jnp.array_equal(
                F.canonical(jnp.moveaxis(got, 0, 1)),
                F.canonical(F.pow22523(z)),
            )
        ),
        "pow22523 differs from the jnp chain",
        lanes=lanes,
    )

    # finish: pick R and kA, set lhs = R + kA so the equation holds;
    # then spoil lhs on every third row and R.y (2 has no root) on some
    r_pts, ka = points(3), points(4)  # table entries are affine: Z == 1
    r_y = np.array(F.canonical(r_pts[:, 1]))
    r_sign = np.asarray(F.canonical(r_pts[:, 0]))[:, 0] & 1
    lhs = np.array(comb.padd_cached(r_pts, comb.to_cached(ka)))
    lhs[::3] = np.asarray(p)[::3]
    r_y[5::7] = F.to_limbs(2)
    acc = jnp.stack([jnp.asarray(lhs), ka], axis=1)  # [lanes, 2, 4, 22]
    r_y, r_sign = jnp.asarray(r_y), jnp.asarray(r_sign.astype(np.int32))
    got = PG.finish_check(r_y, r_sign, acc, interpret=interpret)
    r_point, r_valid = curve.decompress(r_y, r_sign)
    rhs = curve.padd(r_point, comb.unpack_point(acc[:, 1]))
    want = curve.points_equal(comb.unpack_point(acc[:, 0]), rhs) & r_valid
    check(
        bool(jnp.array_equal(got, want)),
        "finish_check differs from the jnp tail",
        lanes=lanes,
    )
    check(
        bool(want[1]) and not bool(want[0]) and not bool(want[5]),
        "finish_check case is vacuous",
        lanes=lanes,
    )


def phase_c(*, seed: int = 0, msm_t: int = 128, expect_impl: str = "pallas") -> dict:
    from dag_rider_tpu.ops import bls_msm
    from dag_rider_tpu.parallel.mesh import make_mesh
    from dag_rider_tpu.parallel.msm import ShardedMSM

    # what a node reaches with "coin_msm": "device" / "cert_msm":
    # "device", held to one chip whatever the host has (phase D shards)
    sm = ShardedMSM(make_mesh(1))
    impl = bls_msm.msm_impl(msm_t // sm.n_shards)
    check(impl == expect_impl, "MSM tree engine", impl=impl)
    t0 = time.monotonic()
    msm_check(sm, msm_t, seed)
    msm_s = time.monotonic() - t0
    note(f"MSM ({impl}) equals the host oracle")
    t0 = time.monotonic()
    # 1,024 lanes take the 4-D (rows, 1, 8, 128) blocks; 512 the 2-D
    # (rows, 512) ones — the branch every one-round-per-dispatch bucket
    # ends its tree and runs its whole finish kernel in
    for lanes in (1024, 512):
        group_kernel_checks(lanes)
        note(f"group kernels at {lanes} lanes equal the jnp tree")
    return {
        "phase": "C",
        "ok": True,
        "msm_points": msm_t,
        "msm_devices": sm.n_shards,
        "msm_impl": impl,
        "msm_compile_and_check_s": round(msm_s, 1),
        "group_kernels": {"4d_lanes": 1024, "2d_lanes": 512},
        "group_kernels_s": round(time.monotonic() - t0, 1),
    }


# ----------------------------------------------------------------------
# Phase D — four chips
# ----------------------------------------------------------------------


def phase_d(
    *,
    n: int = 256,
    seed: int = 0,
    chips: int = 4,
    bucket: int = 512,
    msm_t: int = 512,
    expect_platform: str = "tpu",
    expect_impl: str = "pallas",
) -> dict:
    import jax

    if jax.device_count() < chips:
        return {"phase": "D", "skipped": f"{jax.device_count()} device"}

    from dag_rider_tpu.ops import bls_msm
    from dag_rider_tpu.parallel.mesh import make_mesh
    from dag_rider_tpu.parallel.msm import ShardedMSM
    from dag_rider_tpu.parallel.sharded_verifier import ShardedTPUVerifier
    from dag_rider_tpu.verifier.base import KeyRegistry, VertexSigner
    from dag_rider_tpu.verifier.pipeline import VerifierPipeline
    from dag_rider_tpu.verifier.tpu import TPUVerifier

    mesh = make_mesh(chips)
    registry, seeds = KeyRegistry.generate(n)
    signers = [VertexSigner(s) for s in seeds]
    # a node's "verifier": "sharded" with "verify_bucket": bucket
    stack, sharded = mask_checks(
        lambda reg: VerifierPipeline(
            ShardedTPUVerifier(reg, mesh), fixed_bucket=bucket
        ),
        registry, signers, seed,
    )
    sv = stack.verifier
    check(sv.mesh_devices == chips, "mesh size", mesh=sv.mesh_devices)
    check(
        sv.last_impl == expect_impl and sv.last_size == bucket,
        "per-shard program",
        impl=sv.last_impl,
        bucket=sv.last_size,
    )
    mask, count = sv.dispatch_batch(signed_round(signers, 1))
    check(
        len(mask.sharding.device_set) == chips
        and {d.platform for d in mask.sharding.device_set}
        == {expect_platform},
        "mask does not span the mesh",
        devices=[str(d) for d in mask.sharding.device_set],
    )
    sv.resolve_batch((mask, count))
    for tab in sv._comb_tables_dev():
        check(
            tab.sharding.is_fully_replicated
            and len(tab.sharding.device_set) == chips,
            "comb tables not replicated on every chip",
        )
    # the same rounds on one chip, through the single-chip stack at its
    # default bucket
    _, single = mask_checks(
        lambda reg: VerifierPipeline(TPUVerifier(reg)),
        registry, signers, seed,
    )
    k = len(single["honest"])  # the smaller bucket needed fewer rounds
    check(
        sharded["honest"][:k] == single["honest"]
        and sharded["adversarial"] == single["adversarial"],
        "sharded masks differ from the single-chip masks",
    )

    sm = ShardedMSM(mesh)
    impl = bls_msm.msm_impl(msm_t // chips)
    check(impl == expect_impl, "sharded MSM engine", impl=impl)
    t0 = time.monotonic()
    msm_check(sm, msm_t, seed)
    return {
        "phase": "D",
        "ok": True,
        "n": n,
        "mesh_devices": sv.mesh_devices,
        "bucket": bucket,
        "bucket_set_by": "verify_bucket",
        "shard_rows": bucket // chips,
        "impl": sv.last_impl,
        "mask_devices": chips,
        "tables_replicated": True,
        "masks_equal_single_chip_and_cpu": True,
        "compile_s": sv.stats()["compile_s"],
        "msm_points": msm_t,
        "msm_impl": impl,
        "msm_compile_and_check_s": round(time.monotonic() - t0, 1),
    }


# ----------------------------------------------------------------------


def _on_deadline(_sig, _frame):
    raise SmokeFailure(f"not done after {DEADLINE_S} s")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
    }
    print(json.dumps({"jax": jax.__version__, **device}), flush=True)
    if dev.platform != "tpu":
        print(
            f"chip_smoke: jax is on {dev.platform!r}, not a TPU; there is "
            "no CPU mode",
            file=sys.stderr,
        )
        return 2

    from dag_rider_tpu.utils import native

    signal.signal(signal.SIGALRM, _on_deadline)
    signal.alarm(DEADLINE_S)
    native.load()  # built here, from the tracked source; raises if it cannot
    for name, phase in zip("ABCD", (phase_a, phase_b, phase_c, phase_d)):
        t0 = time.monotonic()
        try:
            line = phase(seed=args.seed)
        except Exception as e:  # noqa: BLE001 — reported, then fatal
            traceback.print_exc()
            print(
                json.dumps(
                    {"phase": name, "ok": False, "error": repr(e)[:2000]}
                ),
                flush=True,
            )
            return 1
        line["seconds"] = round(time.monotonic() - t0, 1)
        print(json.dumps(line), flush=True)
    signal.alarm(0)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
