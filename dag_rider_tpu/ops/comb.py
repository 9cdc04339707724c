"""Fixed-key comb verification — the fast device Ed25519 path.

The committee (KeyRegistry) is fixed for the lifetime of a run, so the
variable-base scalar multiplication [k]A that dominates
:func:`dag_rider_tpu.ops.curve.verify_core` (252 doublings + 63 adds per
signature, ~2400 field muls) can be replaced by a *comb* walk over
per-key precomputed tables — 64 cached adds, zero doublings, exactly like
the existing fixed-base path for B. Per-signature cost drops from ~3200
field muls to ~1300 (measured on-chip: the dispatch is mul-throughput
bound, so wall time follows the mul count).

Tables are built ON DEVICE at verifier construction (one batched dispatch
over all n keys — ~1.3k point ops at batch n), stored in HBM
([n, 64, 16, 4, 22] int32 ≈ 92 MB at n=256), never uploaded from host.

Semantics are unchanged: the walk computes [s]B and [k]A exactly (any
A, including adversarial keys outside the prime-order subgroup — the
equation is NOT rearranged into [s]B - [k]A, which would differ for
8-torsion components), then checks [s]B == R + [k]A projectively. The
accept mask is bit-identical to both `curve.verify_core` and the CPU
oracle (tests/test_comb.py — valid, corrupted, and malleable batches).

Representation notes:

- a *packed* point is one int32 array [..., 4, 22] with rows (X, Y, Z, T)
  — every field op then moves 4 coordinates per XLA op instead of 1,
  which matters because the dispatch cost is op-count x op-size bound;
- a *cached* entry is rows (Y-X, Y+X, 2d*T, 2Z): the add-2008-hwcd-3
  addition of a cached entry is exactly 2 packed muls + cheap linear ops.

Reference seam: SURVEY.md §2a (the north-star batched Verifier);
the reference itself has no crypto (process.go carries none — D10).
"""

from __future__ import annotations


import numpy as np
import jax
import jax.numpy as jnp

from dag_rider_tpu.ops import curve, field as F

WINDOWS = 64  # 4-bit windows over 256-bit scalars
ENTRIES = 16


def pack_point(p: curve.Point) -> jax.Array:
    """(X, Y, Z, T) tuple of [..., 22] -> packed [..., 4, 22]."""
    return jnp.stack(p, axis=-2)


def unpack_point(a: jax.Array) -> curve.Point:
    return tuple(a[..., i, :] for i in range(4))


def to_cached(packed: jax.Array) -> jax.Array:
    """Packed XYZT [..., 4, 22] -> cached (Y-X, Y+X, 2dT, 2Z).

    Row-wise (one real multiply, the 2dT row) rather than a packed
    constant multiply — cheaper, and bit-identical limb representations
    to the Pallas kernel's in-VMEM transform (tests/test_pallas_group.py
    asserts raw-coordinate equality, not just mask equality)."""
    x = packed[..., 0, :]
    y = packed[..., 1, :]
    z = packed[..., 2, :]
    t = packed[..., 3, :]
    return jnp.stack(
        [F.sub(y, x), F.add(y, x), F.mul(t, jnp.asarray(F.D2)), F.add(z, z)],
        axis=-2,
    )


def padd_cached(p: jax.Array, c: jax.Array) -> jax.Array:
    """Packed point + cached entry -> packed point (complete addition).

    add-2008-hwcd-3 with the cached operand pre-transformed:
      A = (Y1-X1)*c0, B = (Y1+X1)*c1, C = T1*c2, D = Z1*c3
      E = B-A, F = D-C, G = D+C, H = B+A
      X3 = E*F, Y3 = G*H, Z3 = F*G, T3 = E*H
    Two packed muls; the stacking/linear steps are cheap elementwise ops.
    """
    x1 = p[..., 0, :]
    y1 = p[..., 1, :]
    z1 = p[..., 2, :]
    t1 = p[..., 3, :]
    lhs = jnp.stack([F.sub(y1, x1), F.add(y1, x1), t1, z1], axis=-2)
    abcd = F.mul(lhs, c)
    a = abcd[..., 0, :]
    b = abcd[..., 1, :]
    cc = abcd[..., 2, :]
    d = abcd[..., 3, :]
    e = F.sub(b, a)
    f = F.sub(d, cc)
    g = F.add(d, cc)
    h = F.add(b, a)
    efge = jnp.stack([e, g, f, e], axis=-2)
    fhgh = jnp.stack([f, h, g, h], axis=-2)
    out = F.mul(efge, fhgh)  # rows (X3, Y3, Z3, T3)
    # F.mul output row order: (E*F, G*H, F*G, E*H) == (X3, Y3, Z3, T3)
    return out


def pdouble_packed(p: jax.Array) -> jax.Array:
    """Packed doubling (dbl-2008-hwcd) — 2 packed muls + linear ops."""
    x1 = p[..., 0, :]
    y1 = p[..., 1, :]
    z1 = p[..., 2, :]
    sq_in = jnp.stack([x1, y1, z1, F.add(x1, y1)], axis=-2)
    sq = F.mul(sq_in, sq_in)  # (X^2, Y^2, Z^2, (X+Y)^2)
    a = sq[..., 0, :]
    b = sq[..., 1, :]
    c2 = F.add(sq[..., 2, :], sq[..., 2, :])
    s = sq[..., 3, :]
    h = F.add(a, b)
    e = F.sub(h, s)
    g = F.sub(a, b)
    f = F.add(c2, g)
    efge = jnp.stack([e, g, f, e], axis=-2)
    fhgh = jnp.stack([f, h, g, h], axis=-2)
    return F.mul(efge, fhgh)


# ---------------------------------------------------------------------------
# Device-side comb-table construction (batched over keys)
# ---------------------------------------------------------------------------


@jax.jit
def build_key_tables(a_x: jax.Array, a_y: jax.Array, a_t: jax.Array) -> jax.Array:
    """Packed-XYZT comb tables for every key: [n, 64, 16, 4, 22] int32.

    TABLE[key, w, d] = d * 16^w * A_key. Built in one dispatch:
    an outer scan over the 64 windows (carry: the window base 16^w * A),
    an inner scan over the 15 nonzero digits. ~64*(15+4) batched point
    ops total — about the cost of one verify dispatch, once per registry.
    """
    n = a_x.shape[0]
    one = jnp.broadcast_to(jnp.asarray(F.ONE), (n, F.LIMBS))
    base = jnp.stack([a_x, a_y, one, a_t], axis=-2)  # packed [n, 4, 22]
    ident = pack_point(curve.identity((n,)))

    def window_step(b, _):
        b_cached = to_cached(b)

        def entry_step(prev, _):
            nxt = padd_cached(prev, b_cached)
            return nxt, nxt

        _, entries = jax.lax.scan(entry_step, ident, None, length=ENTRIES - 1)
        # entries: [15, n, 4, 22]; prepend identity (d = 0)
        table_w = jnp.concatenate([ident[None], entries], axis=0)
        nb = pdouble_packed(pdouble_packed(pdouble_packed(pdouble_packed(b))))
        return nb, table_w

    _, tables = jax.lax.scan(window_step, base, None, length=WINDOWS)
    # tables: [64, 16, n, 4, 22] -> [n, 64, 16, 4, 22]
    return jnp.transpose(tables, (2, 0, 1, 3, 4))


def base_table_xyzt() -> np.ndarray:
    """Packed-XYZT comb table for the base point B: [64, 16, 4, 22]
    (host-built from curve.b_table()'s affine entries: Z == 1, T = x*y)."""
    xs, ys, ts = curve.b_table()  # [64, 16, 22] each, affine
    ones = np.broadcast_to(F.ONE, xs.shape).copy()
    return np.stack([xs, ys, ones, ts], axis=2)  # [64, 16, 4, 22]


ROW_PAD = 128  # gather-row width: one aligned lane tile


def pad_rows(tables: jax.Array) -> jax.Array:
    """[..., 16, 4, 22] tables -> flat [rows, 128] gather layout.

    TPU row-gathers run ~2.2x faster from 512-byte lane-aligned rows
    than from the raw 352-byte [4, 22] entries (measured on-chip,
    round 3); the 40 pad lanes are sliced off after gather.
    """
    flat = tables.reshape((-1, 4 * F.LIMBS))
    return jnp.pad(flat, ((0, 0), (0, ROW_PAD - 4 * F.LIMBS)))


# ---------------------------------------------------------------------------
# The comb verify core
# ---------------------------------------------------------------------------


def tree_sum_packed(entries: jax.Array) -> jax.Array:
    """Sum a power-of-two axis of packed XYZT points (jnp fallback).

    entries: [..., M, 4, 22] XYZT, M a power of two. Each level halves
    the axis with one wide packed add (first half + to_cached(second
    half)); log2(M) levels of WIDE ops — the whole reduction is ~20 XLA
    ops regardless of M, so the VPU sees huge elementwise ops instead of
    a long dependent chain (the sequential 64-step walk was
    latency-bound — round 3). The TPU fast path is
    :func:`dag_rider_tpu.ops.pallas_group.tree_sum_xyzt` (bit-identical).
    """
    acc = entries
    while acc.shape[-3] > 1:
        m = acc.shape[-3] // 2
        acc = padd_cached(
            acc[..., :m, :, :], to_cached(acc[..., m:, :, :])
        )
    return acc[..., 0, :, :]


def comb_verify_core(
    s_nibbles: jax.Array,
    k_nibbles: jax.Array,
    key_idx: jax.Array,
    key_tables: jax.Array,
    b_table: jax.Array,
    a_valid: jax.Array,
    r_y: jax.Array,
    r_sign: jax.Array,
    prevalid: jax.Array,
    impl: str = "jnp",
) -> jax.Array:
    """Batched [s]B == R + [k]A with both scalar muls as comb sums.

    s_nibbles/k_nibbles: int32[B, 64] little-endian 4-bit digits;
    key_idx: int32[B] row of each vertex's key in the registry;
    key_tables: [n, 64, 16, 4, 22] from :func:`build_key_tables`;
    b_table: [64, 16, 4, 22] from :func:`base_table_xyzt`.

    A comb scalar mul is a pure sum of per-window table entries (no
    doublings), so both sides are ONE fused gather ([B, 2, 64, 4, 22] —
    axis 1 is ([s]B, [k]A)) followed by a 6-level tree reduction of wide
    packed adds. The R decompression chain (the one unavoidable
    sequential part) runs concurrently — it has no data dependence on
    the trees until the final addition.

    impl: "jnp" (portable) or "pallas" (TPU kernels for the tree and the
    sqrt chain — bit-identical results, one HBM pass per operand).

    key_tables/b_table arrive in the padded [rows, 128] gather layout of
    :func:`pad_rows`.
    """
    wins = jnp.arange(WINDOWS, dtype=jnp.int32)[None, :]
    b_rows = jnp.take(b_table, wins * ENTRIES + s_nibbles, axis=0)
    a_idx = (key_idx[:, None] * WINDOWS + wins) * ENTRIES + k_nibbles
    a_rows = jnp.take(key_tables, a_idx, axis=0)
    stacked = jnp.stack([b_rows, a_rows], axis=1)  # [B, 2, 64, 128]
    entries = stacked[..., : 4 * F.LIMBS].reshape(
        (*stacked.shape[:-1], 4, F.LIMBS)
    )  # [B, 2, 64, 4, 22]

    if impl in ("pallas", "pallas_interpret"):
        from dag_rider_tpu.ops import pallas_group

        interp = impl == "pallas_interpret"
        acc = pallas_group.tree_sum_xyzt(entries, interpret=interp)  # [B, 2, 4, 22]
        # decompress + rhs addition + projective equality in one launch
        ok = pallas_group.finish_check(r_y, r_sign, acc, interpret=interp)
        return ok & a_valid & prevalid
    acc = tree_sum_packed(entries)
    lhs = unpack_point(acc[:, 0])  # [s]B
    ka = unpack_point(acc[:, 1])  # [k]A
    r_point, r_valid = curve.decompress(r_y, r_sign)
    rhs = curve.padd(r_point, ka)
    return curve.points_equal(lhs, rhs) & a_valid & r_valid & prevalid
