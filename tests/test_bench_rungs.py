"""bench.py helper coverage — the driver's benchmark entry points.

The ladder rungs are driven end-to-end on the chip, but their
*mechanics* — time-box extension toward a vertex target, the
verifier-seam breakdown, pipeline-off shadowing — must not regress
silently between captures: a broken rung costs chip time (round-5
postmortem: the sim256_sync shadow crash truncated the first on-chip
ladder).
"""

import bench
from dag_rider_tpu.verifier.base import KeyRegistry, VertexSigner
from dag_rider_tpu.verifier.tpu import TPUVerifier


def _built(n=8):
    reg, seeds = KeyRegistry.generate(n)
    return TPUVerifier(reg), [VertexSigner(s) for s in seeds]


def test_sim_rung_reports_breakdown_and_progress():
    v, signers = _built()
    e = bench._sim_rung(8, 2.0, v, signers, bucket=256, chunk=56)
    assert e["nodes"] == 8 and e["pipelined"] is True
    # a short box may not reach a committed wave (delivery needs 4+
    # rounds past compile) — assert on progress that must happen
    assert e["messages"] > 0 and e["max_round"] >= 1
    bd = e["verifier_breakdown"]
    assert bd["dispatches"] >= 1
    assert bd["sigs_dispatched"] >= bd["dispatches"]
    # the three shares partition the wall time (rounding slack)
    assert bd["prepare_s"] + bd["device_s"] <= e["seconds"] + 0.1
    assert v.fixed_bucket == 256


def test_sim_rung_extends_past_box_until_target_met():
    v, signers = _built()
    # 0.2s box alone cannot reach 40 vertices per view; the extension
    # must keep pumping past the box until the target is met OR the
    # max_s bound expires (slow/cold-cache hosts may hit the bound
    # first — the mechanism under test is the extension, not the speed)
    e = bench._sim_rung(
        8, 0.2, v, signers, bucket=256, chunk=56,
        target_per_view=40, max_s=60.0,
    )
    assert e["seconds"] > 0.2, "extension never engaged"
    assert (
        e["vertices_delivered_per_view"] >= 40 or e["seconds"] >= 60.0
    ), e
    assert e["messages"] > 0


def test_sim_rung_pipeline_off_runs_and_restores_seam():
    """The pipeline-off B side must run the synchronous path via the
    pipeline_enabled flag (which replaced the round-5 None shadow whose
    crash truncated a ladder) and restore the flag afterwards.
    Byte-identity of the two paths is covered deterministically by
    test_determinism.py::test_pipelined_coalesced_path_matches_sync_path
    — a wall-clock time-boxed rung pair cannot assert equality."""
    v, signers = _built()
    e_on = bench._sim_rung(8, 1.5, v, signers, bucket=256, chunk=56)
    e_off = bench._sim_rung(
        8, 1.5, v, signers, bucket=256, chunk=56, pipelined=False
    )
    assert e_on["pipelined"] is True and e_off["pipelined"] is False
    assert e_off["messages"] > 0 and e_off["max_round"] >= 1
    # the A side reports the window gauges; the B side reads empty
    assert e_on["verifier_breakdown"]["queue_depth"] >= 1
    assert 0.0 <= e_on["verifier_breakdown"]["overlap_fraction"] <= 1.0
    assert e_off["verifier_breakdown"]["queue_depth_max"] == 0
    # flag restored: the async seam is live again
    assert v.pipeline_enabled is True
    pending = v.dispatch_batch([])
    assert v.resolve_batch(pending) == []


def test_vec_ab_rung_asserts_equal_commit_order():
    """The round-12 scalar-vs-vector A/B: both sides must reach the
    target round, the entry must carry both throughput sides + the
    speedup ratio, and the rung itself enforces byte-identical per-view
    commit order (it raises on divergence — the tier1-vec CI smoke
    relies on that)."""
    e = bench._vec_ab_rung(8, 30.0, 8)
    assert e["commit_order_identical"] is True
    assert e["scalar"]["max_round"] >= 8
    assert e["vector"]["max_round"] >= 8
    assert e["scalar"]["msgs_per_sec"] > 0
    assert e["vector"]["msgs_per_sec"] > 0
    assert e["speedup"] > 0
    assert e["scalar"]["vertices_delivered_total"] > 0
