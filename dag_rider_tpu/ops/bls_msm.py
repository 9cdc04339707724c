"""BLS12-381 G1 multi-scalar multiplication on the accelerator.

The device half of threshold-share aggregation
(:func:`dag_rider_tpu.crypto.threshold.aggregate`): the combination
sigma = sum_i lambda_i * sigma_i is a G1 MSM — the TPU-acceleration target
BASELINE.json names for the n=256/1024 rungs (configs #4-5) and the
riskiest item of the build plan (SURVEY.md §7). The pairing checks stay
host-side (:mod:`dag_rider_tpu.crypto.bls12381`), exactly as ordering
decisions do.

Design, TPU-first rather than a CPU-algorithm port:

- Field: :mod:`dag_rider_tpu.ops.field381` (signed 12-bit int32 limbs,
  fold-matrix reduction — no widening multiply needed).
- Group law: the **Renes-Costello-Batina complete addition formulas**
  (eprint 2015/1060, Algorithm 7 specialized to a = 0, b3 = 3*4 = 12) in
  homogeneous projective coordinates. Complete means *no* exceptional
  cases: P == Q, P == -Q, and the identity (0:1:0) all flow through the
  same 12M straight-line program — zero data-dependent control flow, no
  device-side equality tests or inversions, which is exactly what XLA
  wants. A Jacobian ladder with branch selects would cost less raw M but
  serializes on canonical() equality checks; completeness is the right
  trade on this hardware.
- MSM shape: per-point 4-bit windowed scalar multiplication (radix-16
  table of 0..15 multiples, 63 windows for the 255-bit scalar group order,
  4 doublings + 1 table add per window) vmapped over the points, then a
  pairwise tree reduction over the point axis. Pippenger bucket
  accumulation needs data-dependent scatters — hostile to the compiler;
  batched windows + tree sum keep every step dense and fused.

Scalars are taken mod r (the G1 group order) on the host; points arrive as
host affine tuples (already decompressed/validated by
``bls12381.g1_decompress``) and return as one host affine tuple.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from dag_rider_tpu.ops import field381 as F

R_INT = 0x73EDA753299D7D483339D80809A1D80553BDA402FFFE5BFEFFFFFFFF00000001
P_INT = F.P_INT
WINDOWS = 64  # 256-bit scalar capacity in 4-bit windows (r is 255 bits)

Point = Tuple[jax.Array, jax.Array, jax.Array]  # homogeneous (X, Y, Z)


def identity(shape=()) -> Point:
    """The group identity (0 : 1 : 0)."""
    zero = jnp.broadcast_to(jnp.asarray(F.ZERO), (*shape, F.LIMBS))
    one = jnp.broadcast_to(jnp.asarray(F.ONE), (*shape, F.LIMBS))
    return (zero, one, zero)


def padd(p: Point, q: Point) -> Point:
    """Complete addition, RCB15 Algorithm 7 (a = 0, b3 = 12): 12M + 2m."""
    X1, Y1, Z1 = p
    X2, Y2, Z2 = q
    t0 = F.mul(X1, X2)
    t1 = F.mul(Y1, Y2)
    t2 = F.mul(Z1, Z2)
    t3 = F.mul(F.add(X1, Y1), F.add(X2, Y2))
    t3 = F.sub(t3, F.add(t0, t1))
    t4 = F.mul(F.add(Y1, Z1), F.add(Y2, Z2))
    t4 = F.sub(t4, F.add(t1, t2))
    x3 = F.mul(F.add(X1, Z1), F.add(X2, Z2))
    y3 = F.sub(x3, F.add(t0, t2))
    x3 = F.add(F.add(t0, t0), t0)  # 3 X1 X2
    t2 = F.mul_small(t2, 12)  # b3 Z1 Z2
    z3 = F.add(t1, t2)
    t1 = F.sub(t1, t2)
    y3 = F.mul_small(y3, 12)  # b3 (X1 Z2 + X2 Z1)
    X3 = F.sub(F.mul(t3, t1), F.mul(t4, y3))
    Y3 = F.add(F.mul(y3, x3), F.mul(t1, z3))
    Z3 = F.add(F.mul(z3, t4), F.mul(x3, t3))
    return (X3, Y3, Z3)


def pdouble(p: Point) -> Point:
    """Doubling via the complete formula (P + P is a valid input to it)."""
    return padd(p, p)


def pselect(cond: jax.Array, p: Point, q: Point) -> Point:
    return tuple(F.select(cond, a, b) for a, b in zip(p, q))


# ---------------------------------------------------------------------------
# Windowed scalar multiplication + tree-sum MSM
# ---------------------------------------------------------------------------


def _gather_entry(table: Tuple[jax.Array, ...], idx: jax.Array) -> Point:
    """table coords [..., 16, LIMBS]; idx int32[...] in [0, 16)."""
    out = []
    for coord in table:
        g = jnp.take_along_axis(
            coord, idx[..., None, None].astype(jnp.int32), axis=-2
        )
        out.append(g[..., 0, :])
    return tuple(out)


def _identity_like(p: Point) -> Point:
    """Identity (0 : 1 : 0) with ``p``'s shape, DERIVED from ``p``
    (0*X, 0*Y + 1, 0*Z) rather than broadcast from constants, so that
    under shard_map the scan/fori carries built from it inherit the batch
    axis's "varying" type from the inputs (shard_map rejects an unvarying
    carry that becomes varying after one body application)."""
    one = jnp.broadcast_to(jnp.asarray(F.ONE), p[1].shape)
    return (
        jnp.zeros_like(p[0]),
        jnp.zeros_like(p[1]) + one,
        jnp.zeros_like(p[2]),
    )


def _point_tables(p: Point) -> Tuple[jax.Array, ...]:
    """Radix-16 multiples [0..15]P per point: coords [..., 16, LIMBS].

    Built via scan — one padd body in the HLO instead of 14 inlined ones
    (compile-time win; identical values).
    """
    ident = _identity_like(p)

    def _entry(prev, _):
        nxt = padd(prev, p)
        return nxt, nxt

    _, steps = jax.lax.scan(_entry, ident, None, length=15)
    return tuple(
        jnp.moveaxis(
            jnp.concatenate([ident[c][None], steps[c]], axis=0), 0, -2
        )
        for c in range(3)
    )


def scalar_mul(nibbles: jax.Array, p: Point) -> Point:
    """[k]P — 4-bit fixed windows, MSB first, batched over leading dims.

    nibbles: int32[..., 64], little-endian. The window walk is a fori_loop
    so the HLO stays one window long regardless of scalar size. (The MSM
    path uses :func:`window_sums` instead — this per-point ladder remains
    for single-scalar consumers and differential tests.)
    """
    table = _point_tables(p)
    ident = _identity_like(p)

    def body(i, acc):
        acc = pdouble(pdouble(pdouble(pdouble(acc))))
        idx = jnp.take(nibbles, WINDOWS - 1 - i, axis=-1)
        return padd(acc, _gather_entry(table, idx))

    return jax.lax.fori_loop(0, WINDOWS, body, ident)


def window_sums(nibbles: jax.Array, p: Point, impl: str = "jnp") -> Point:
    """Per-window partial sums S_w = sum_i [d_{i,w}] P_i, coords [64, L].

    The TPU-shaped half of the MSM (round-4; same restructuring that took
    the Ed25519 comb from a sequential walk to a wide tree):
    radix-16 tables per point, ONE take_along_axis gathering every
    window's digit entry ([T, 64, L]), then a pairwise tree reduction
    over the point axis with full batch-level ILP. Work is
    15T (tables) + 64T (tree) complete additions versus the ladder's
    320T, with no 64-step dependent accumulator chain over the batch.

    impl: "jnp" (portable tree) or "pallas"/"pallas_interpret" — the
    tree's additions as single Mosaic launches with all intermediates in
    VMEM (ops/pallas_group381.py), bit-identical.
    """
    table = _point_tables(p)  # [T, 16, L] per coord
    ent = tuple(
        jnp.take_along_axis(c, nibbles[..., None], axis=-2) for c in table
    )  # [T, 64, L]
    if impl in ("pallas", "pallas_interpret"):
        from dag_rider_tpu.ops import pallas_group381 as PG381

        stacked = jnp.stack(ent, axis=-2)  # [T, 64, 3, L]
        stacked = jnp.moveaxis(stacked, 0, 1)  # [64, T, 3, L]
        acc = PG381.tree_sum_xyz381(
            stacked, interpret=impl == "pallas_interpret"
        )  # [64, 3, L]
        return tuple(acc[:, c] for c in range(3))
    acc = tree_reduce(ent)  # [1, 64, L]
    return tuple(c[0] for c in acc)


def horner_combine(wsums: Point) -> Point:
    """sum_w 16^w S_w from [64, L] window sums — 4 doublings + 1 add per
    window on a single point (negligible next to the batch tree)."""
    ident = _identity_like(tuple(c[0] for c in wsums))

    def body(i, acc):
        acc = pdouble(pdouble(pdouble(pdouble(acc))))
        w = tuple(jnp.take(c, WINDOWS - 1 - i, axis=0) for c in wsums)
        return padd(acc, w)

    return jax.lax.fori_loop(0, WINDOWS, body, ident)


def tree_reduce(acc: Point) -> Point:
    """Pairwise-fold a [t, ...] point batch to [1, ...] — any t >= 1
    (odd counts carry their last element into the next level)."""
    t = acc[0].shape[0]
    while t > 1:
        half = t // 2
        folded = padd(
            tuple(c[:half] for c in acc),
            tuple(c[half : 2 * half] for c in acc),
        )
        if t % 2:
            folded = tuple(
                jnp.concatenate([fc, c[2 * half :]], axis=0)
                for fc, c in zip(folded, acc)
            )
        acc = folded
        t = half + t % 2
    return acc


@functools.partial(jax.jit, static_argnames=("impl",))
def msm_kernel(
    nibbles: jax.Array,
    px: jax.Array,
    py: jax.Array,
    pz: jax.Array,
    impl: str = "jnp",
) -> Point:
    """sum_i [k_i] P_i for a padded batch of T points.

    nibbles: int32[T, 64]; px/py/pz: int32[T, 33]. Pad slots use scalar 0
    (maps to the identity). Returns one projective point (X, Y, Z) [33].
    """
    wsums = window_sums(nibbles, (px, py, pz), impl=impl)  # [64, 33] each
    return horner_combine(wsums)


def msm_impl(t: int) -> str:
    """Tree-impl selection, mirroring verifier.tpu._comb_impl: Mosaic
    kernels on the TPU backend for lane-aligned batches, portable jnp
    everywhere else. DAGRIDER_MSM_PALLAS=0 (default 1) pins jnp — the
    kernels are bit-identical, this is purely a speed selection."""
    from dag_rider_tpu import config

    if not config.env_flag("DAGRIDER_MSM_PALLAS"):
        return "jnp"
    if t >= 128 and jax.default_backend() == "tpu":
        return "pallas"
    return "jnp"


# ---------------------------------------------------------------------------
# Host seam: threshold.aggregate(msm=...) plug
# ---------------------------------------------------------------------------


def _nibbles(k: int) -> np.ndarray:
    out = np.zeros(WINDOWS, dtype=np.int32)
    for i in range(WINDOWS):
        out[i] = (k >> (4 * i)) & 0xF
    return out


def _pad(n: int, base: int = 4) -> int:
    """Smallest base * 2^k >= max(n, base) — the padded batch size."""
    t = base
    while t < n:
        t *= 2
    return t


def pack_inputs(
    scalars: Sequence[int], points: Sequence[tuple], t: int
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Marshal host (scalar, affine point) pairs into padded kernel inputs.

    Pad slots (and None points) become the identity (0 : 1 : 0) with
    scalar 0; scalars are reduced mod r. Shared by the single-device
    :func:`msm` and the mesh-sharded ``parallel.msm.ShardedMSM`` so the
    crypto-sensitive marshalling lives exactly once.
    """
    if len(scalars) != len(points):
        raise ValueError("scalars/points length mismatch")
    nib = np.zeros((t, WINDOWS), dtype=np.int32)
    px = np.zeros((t, F.LIMBS), dtype=np.int32)
    py = np.zeros((t, F.LIMBS), dtype=np.int32)
    pz = np.zeros((t, F.LIMBS), dtype=np.int32)
    py[:] = F.ONE
    for i, (k, pt) in enumerate(zip(scalars, points)):
        if pt is None:
            continue  # identity contributes nothing regardless of scalar
        nib[i] = _nibbles(k % R_INT)
        px[i] = F.to_limbs(pt[0])
        py[i] = F.to_limbs(pt[1])
        pz[i] = F.ONE
    return nib, px, py, pz


def unpack_point(X, Y, Z) -> Optional[tuple]:
    """Projective limb point -> host affine (x, y) tuple (None: identity)."""
    xi = F.from_limbs(np.asarray(F.canonical(X)))
    yi = F.from_limbs(np.asarray(F.canonical(Y)))
    zi = F.from_limbs(np.asarray(F.canonical(Z)))
    if zi == 0:
        return None
    z_inv = pow(zi, P_INT - 2, P_INT)
    return (xi * z_inv % P_INT, yi * z_inv % P_INT)


def msm(scalars: Sequence[int], points: Sequence[tuple]) -> Optional[tuple]:
    """Device MSM over host affine points; the ``msm=`` backend of
    :func:`dag_rider_tpu.crypto.threshold.aggregate`.

    Args:
        scalars: python ints (reduced mod r here).
        points: affine (x, y) int tuples or None (identity), as produced by
            ``bls12381.g1_decompress``.

    Returns an affine (x, y) tuple, or None for the identity.
    """
    t = _pad(len(points))
    nib, px, py, pz = pack_inputs(scalars, points, t)
    X, Y, Z = msm_kernel(
        jnp.asarray(nib),
        jnp.asarray(px),
        jnp.asarray(py),
        jnp.asarray(pz),
        impl=msm_impl(t),
    )
    return unpack_point(X, Y, Z)


def sum_points(points: Sequence[tuple]) -> Optional[tuple]:
    """Plain G1 point sum as an all-ones MSM — the device half of
    certificate signature aggregation (ISSUE 9). Same input/output
    conventions as :func:`msm`."""
    return msm([1] * len(points), points)
