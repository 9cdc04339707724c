"""chip_smoke.py's control flow, rehearsed at n=4 on the CPU backend
before chip time is spent on it (on-chip-measurement §1).

The script itself has no CPU mode; these import its phase functions.
Phases A and D are on conftest's slow list (tier-1 skips them): run this
file without ``-m 'not slow'`` before changing the smoke.
The CPU backend selects the jnp tree engine: the Pallas interpreter
cannot stand in for it here — the whole comb program under
``pallas_interpret`` was still compiling after 5 minutes and 5 GB at
bucket 16 — so the Mosaic kernels are proven on the chip only.
"""

import os
import subprocess
import sys

import chip_smoke

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_script_refuses_to_run_without_a_tpu():
    """The driver's invocation on a machine with no chip: non-zero, and
    no result line, before any consensus work."""
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
        capture_output=True,
        text=True,
        timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode not in (0, None)
    lines = proc.stdout.strip().splitlines()
    assert len(lines) == 1 and '"platform": "cpu"' in lines[0]
    assert "no CPU mode" in proc.stderr


def test_phase_a_rehearsal():
    line = chip_smoke.phase_a(
        n=4,
        rate=2000.0,
        load_s=0.1,
        dt=0.01,
        min_accepted=100,
        settle_s=120.0,
        expect_platform="cpu",
        expect_impl="jnp",
    )
    assert line["ok"] and line["phase"] == "A"
    assert line["delivered"] == line["accepted"] >= 100
    assert line["decided_waves_min"] >= 2
    assert line["lost"] == line["duplicates"] == 0
    assert line["masks_equal_cpu"] and line["order8_forgeries_accepted"] >= 2
    assert line["bucket"] == 16 and line["bucket_set_by"] == "default"
    assert list(line["compile_s"]) == ["16xjnp"]
    assert list(line["compile_s_from_cache"]) == ["16xjnp"]
    assert line["in_loop_seam_s"] >= line["in_loop_wait_s"] >= 0.0
    for k in ("poisoned_windows", "quarantined", "retries", "fallbacks"):
        assert line[k] == 0


def test_phase_b_rehearsal():
    line = chip_smoke.phase_b(load_s=2.0, rate=100.0, expect_platform="cpu")
    assert line["ok"] and line["phase"] == "B"
    assert line["accepted"] > 0 and line["lost"] == 0
    assert line["sidecar_dispatches"] > 0
    assert line["runners_with_libtpu"] == []


def test_phase_c_helpers_rehearsal():
    """msm_check against a host double-and-add (the device MSM is a slow
    compile on the CPU backend and has its own tests)."""
    from dag_rider_tpu.crypto import bls12381 as bls

    def host_msm(ks, pts):
        acc = None
        for k, p in zip(ks, pts):
            acc = bls.g1_add(acc, bls.g1_mul(k, p))
        return acc

    chip_smoke.msm_check(host_msm, 4, seed=1)
    bad = lambda ks, pts: host_msm(ks[:-1], pts[:-1])  # noqa: E731
    try:
        chip_smoke.msm_check(bad, 4, seed=1)
    except chip_smoke.SmokeFailure:
        pass
    else:
        raise AssertionError("msm_check accepted a wrong sum")


def test_phase_d_rehearsal_on_the_virtual_mesh():
    line = chip_smoke.phase_d(
        n=4,
        chips=8,
        bucket=16,
        msm_t=8,
        expect_platform="cpu",
        expect_impl="jnp",
    )
    assert line["ok"] and line["mesh_devices"] == 8


def test_phase_d_states_its_skip():
    assert chip_smoke.phase_d(chips=64) == {
        "phase": "D",
        "skipped": "8 device",
    }
