"""Multi-chip sharded Verifier — data-parallel verify over a device mesh.

The n=1024 rung of the benchmark ladder (BASELINE.json: "1024-node
full-wave MSM, multi-host pmap on v5e-16" — here pjit/NamedSharding, the
modern spelling): one DAG round's vertex batch is laid out [B, ...] and
sharded over the mesh's "batch" axis, so each chip verifies B/n_chips
signatures; the accept mask gathers back to host. No cross-chip
collectives are needed in the verify itself (it is embarrassingly
data-parallel) — XLA inserts the result all-gather; ICI carries it.

First-class on the async seam (round 7): this class overrides ONLY the
placement hooks of :class:`~dag_rider_tpu.verifier.tpu.TPUVerifier`
(``_round_bucket``/``_put``/``_aot_lower``/...), so
``dispatch_batch``/``resolve_batch``/``warmup``/the chunked
``verify_batch`` — and therefore every caller: ``VerifierPipeline``,
``Simulation.run``'s coalesced window, node.py — ride the mesh without a
single duplicated line of dispatch logic. Before round 7 those methods
were silently inherited un-overridden and every async caller dispatched
single-chip; the hook seam makes that fallback structurally impossible
(tests/test_parallel.py asserts the dispatched mask spans the mesh).

The round-8 parallel host-prep engine (verifier/prep.py) rides the same
seam for free: ``prep_batch``/``prep_batch_async`` run entirely ABOVE the
placement hooks (row blocks write into the host staging slot before
``_put``/``_note_dispatch`` ever see it), so sharded dispatch gets
multi-worker prep and prep-ahead with zero code here — the staging slot
stays one full-batch host array and only `_put` splits it over the mesh.

Byte-identical masks: chunk boundaries come from the caller-visible
``fixed_bucket`` exactly as on the single-chip path; only the PAD size of
each dispatch rounds up to a multiple of the mesh batch axis, and padding
rows are sliced off before any consumer sees them. So CPU / 1-chip /
N-chip runs agree bit-for-bit at every pipeline depth (test_pipeline.py
on the virtual 8-device CPU mesh).

Fault containment is ``VerifierPipeline``'s (verifier/pipeline.py) and
sits above the placement hooks too: a poisoned window over this class
salvages, re-arms the (full-batch host) staging ring through
``reset_staging`` and quarantines exactly as over the single-chip
verifier; like it, this class raises on a fault. The chaos harness
(verifier/faults.py) arms this class through the identical instance-
attribute shadows (tests/test_chaos.py runs its suite on both).
"""

from __future__ import annotations

import functools
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from dag_rider_tpu import config
from dag_rider_tpu.ops import curve, field
from dag_rider_tpu.parallel.mesh import (
    batch_sharding,
    make_mesh,
    replicated,
)
from dag_rider_tpu.verifier.base import KeyRegistry
from dag_rider_tpu.verifier.tpu import TPUVerifier, _bucket, _comb_impl


class ShardedTPUVerifier(TPUVerifier):
    """TPUVerifier whose device dispatch shards the batch over a mesh.

    Verification is embarrassingly data-parallel: every per-vertex input
    (digits, key index, R.y) shards over the mesh's "batch" axis while
    the comb tables replicate (every chip holds the registry's tables —
    they are read-only and gather-indexed by the local shard's rows).
    ``comb=False`` falls back to sharding the windowed program.
    """

    def __init__(
        self,
        registry: KeyRegistry,
        mesh: Optional[Mesh] = None,
        comb: bool = True,
    ):
        super().__init__(registry, comb=comb)
        self.mesh = mesh if mesh is not None else make_mesh()
        self._n_shards = int(np.prod(self.mesh.devices.shape))
        self._mesh_key = tuple(int(d) for d in self.mesh.devices.shape)
        self._batch_sharding = batch_sharding(self.mesh)
        self._repl_tables = None

        #: per-shard gauges of the most recent dispatch (pipeline
        #: stats() surfaces them)
        self.mesh_devices = self._n_shards
        self.last_shard_batch = 0
        self.last_shard_imbalance = 0.0
        self.total_shard_imbalance = 0.0

        sharding = self._batch_sharding

        @functools.partial(
            jax.jit,
            in_shardings=(sharding,) * 9,
            out_shardings=sharding,
        )
        def _sharded_verify(
            s_nibbles, k_nibbles, a_x, a_y, a_t, a_valid, r_y, r_sign, prevalid
        ):
            one = jnp.broadcast_to(jnp.asarray(field.ONE), a_x.shape)
            a_point = (a_x, a_y, one, a_t)
            return curve.verify_core(
                s_nibbles, k_nibbles, a_point, a_valid, r_y, r_sign, prevalid
            )

        self._sharded_verify = _sharded_verify

        #: impl -> compiled shard_map comb kernel, built lazily. shard_map
        #: (not GSPMD jit) because Mosaic pallas_call kernels do not lower
        #: under auto-partitioning — per-shard they run as-is, so the
        #: flagship single-chip Pallas path and the multi-chip path are
        #: the SAME program per shard (round-3 VERDICT weak #4; pattern
        #: proven by parallel/msm.py).
        self._comb_kernels = {}

    def _sharded_comb_kernel(self, impl: str):
        if impl not in self._comb_kernels:
            from jax.sharding import PartitionSpec as P

            @functools.partial(
                jax.shard_map,
                mesh=self.mesh,
                in_specs=(P("batch"), P("batch"), P(), P()),
                out_specs=P("batch"),
                # pallas_call can't declare per-axis varying metadata, so
                # the static varying-axis tracker must stand down (same
                # as parallel/msm.py); the specs above are the truth.
                check_vma=False,
            )
            def _local(u8, i32, key_tables, b_table):
                from dag_rider_tpu.verifier.tpu import _device_verify_comb

                return _device_verify_comb.__wrapped__(
                    u8, i32, key_tables, b_table, impl=impl
                )

            self._comb_kernels[impl] = jax.jit(_local)
        return self._comb_kernels[impl]

    # -- placement hooks (see TPUVerifier's dispatch seam) ----------------

    def _round_bucket(self, b: int) -> int:
        # Pad every dispatch to a multiple of the mesh so each shard gets
        # an equal slice — the GSPMD/shard_map programs require it, and
        # the rounding must apply to the fixed bucket and the
        # power-of-two ladder alike or shard padding diverges from the
        # 1-chip program shape.
        b = int(b)
        if b % self._n_shards:
            b += self._n_shards - b % self._n_shards
        assert b % self._n_shards == 0
        return b

    def _bucket_size(self, n: int) -> int:
        """Padded dispatch size for an n-vertex batch: the single-chip
        power-of-two ladder, then mesh-rounded."""
        return self._round_bucket(_bucket(n))

    def _select_impl(self, size: int) -> str:
        # Per-shard impl selection mirrors the single-chip rule (Pallas
        # on a real TPU backend for lane-aligned shards, jnp elsewhere);
        # DAGRIDER_SHARDED_COMB_IMPL overrides — e.g. "pallas_interpret"
        # exercises the kernel bodies on the virtual CPU mesh
        # (dryrun_multichip / tests).
        return config.env_str("DAGRIDER_SHARDED_COMB_IMPL") or _comb_impl(
            max(1, size // self._n_shards)
        )

    def _aot_key(self, size: int, impl: str) -> tuple:
        # mesh shape in the key: a warmup for an 8-device mesh must not
        # be served to a reconfigured 4-device run of the same bucket
        return (size, impl, self._mesh_key)

    def _put(self, arr: np.ndarray) -> jax.Array:
        # one NamedSharding device_put = n_shards per-device sub-buffer
        # transfers; each staging-ring slot stays a full-batch host array
        # so the ring discipline (the window's depth + 2 slots) is unchanged
        return jax.device_put(arr, self._batch_sharding)

    def _comb_tables_dev(self):
        if self._repl_tables is None:
            tables, b_tab = self._comb_tables()
            repl = replicated(self.mesh)
            self._repl_tables = (
                jax.device_put(tables, repl),
                jax.device_put(b_tab, repl),
            )
        return self._repl_tables

    def _windowed_dispatch(self, args) -> jax.Array:
        return self._sharded_verify(*(jnp.asarray(a) for a in args))

    def _aot_lower(self, size: int, impl: str, tables, b_tab):
        # lowered with sharding-carrying avals at the exact dispatch shape
        shd = self._batch_sharding
        return (
            self._sharded_comb_kernel(impl)
            .lower(
                jax.ShapeDtypeStruct((size, 131), jnp.uint8, sharding=shd),
                jax.ShapeDtypeStruct((size, 23), jnp.int32, sharding=shd),
                tables,
                b_tab,
            )
            .compile()
        )

    def _note_dispatch(self, size: int, count: int) -> None:
        sb = size // self._n_shards
        self.last_shard_batch = sb
        if sb:
            per = [
                min(max(count - i * sb, 0), sb) for i in range(self._n_shards)
            ]
            self.last_shard_imbalance = (max(per) - min(per)) / sb
        else:
            self.last_shard_imbalance = 0.0
        self.total_shard_imbalance += self.last_shard_imbalance
