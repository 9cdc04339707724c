"""Pallas TPU kernels for whole Edwards group ops — the comb tree's engine.

Why these exist (measured on-chip, round 3): the jnp field
multiply runs its 484 MACs at near-VPU-peak *inside* one fused op, but a
group addition is ~10 multiplies with stacks/slices/carries between them,
and XLA materializes the intermediate columns between every step — the
comb tree ran ~20x above its compute floor, memory-bound on HLO temps.
Each kernel here performs one complete point addition (two full
schoolbook multiplies per coordinate set, carries, the 2^255==19 fold)
with every intermediate in VMEM/vector registers: HBM sees exactly one
read of each operand block and one write of the result.

Layout: limb-major [88, N] int32 — rows are (coordinate, limb) pairs
(4 x 22), N is the flattened batch in the 128-wide lane axis. The comb
pipeline gathers row-major table entries, transposes ONCE to limb-major,
runs the whole reduction tree in these kernels, and transposes the tiny
result back. Tree levels pair first-half/second-half (contiguous lane
slices — pairing order is free by associativity), never strided lanes.

Bit-exactness: the limb math is the same signed-12-bit schoolbook as
:mod:`dag_rider_tpu.ops.field` (same masks, shifts, fold constants, same
carry counts), so results are bit-identical to the jnp path
(tests/test_pallas_group.py runs interpret mode against the jnp oracle).
"""

from __future__ import annotations

import functools
from typing import List, Sequence

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from dag_rider_tpu.ops import field as F

L = F.LIMBS  # 22
ROWS = 4 * L  # 88


# ---------------------------------------------------------------------------
# In-kernel limb math on lists of [1, T] lane vectors
# ---------------------------------------------------------------------------


def _carry2(rows: List, steps: int = 2) -> List:
    """field.carry on a 22-row list (parallel steps, top fold)."""
    for _ in range(steps):
        cs = [r >> F.LIMB_BITS for r in rows]
        rows = [r & F.LIMB_MASK for r in rows]
        rows[0] = rows[0] + cs[L - 1] * F.TOP_FOLD
        for j in range(L - 1):
            rows[j + 1] = rows[j + 1] + cs[j]
    return rows


def _add22(a: List, b: List) -> List:
    return _carry2([x + y for x, y in zip(a, b)])


def _sub22(a: List, b: List) -> List:
    return _carry2([x - y for x, y in zip(a, b)])


def _dbl22(a: List) -> List:
    return _carry2([x + x for x in a])


def _mul22(a: List, b) -> List:
    """Schoolbook multiply of 22-row lists (b may be a list of rows or a
    22-int constant limb vector); same steps as field.mul."""
    b_const = not isinstance(b[0], jax.Array)
    c = [None] * 43
    for i in range(L):
        for j in range(L):
            if b_const:
                if b[j] == 0:
                    continue
                t = a[i] * int(b[j])
            else:
                t = a[i] * b[j]
            k = i + j
            c[k] = t if c[k] is None else c[k] + t
    zero = jnp.zeros_like(a[0])
    c = [zero if x is None else x for x in c] + [zero, zero, zero]  # 46 cols
    for _ in range(2):
        carries = [x >> F.LIMB_BITS for x in c]
        c = [x & F.LIMB_MASK for x in c]
        for k in range(len(c) - 1):
            c[k + 1] = c[k + 1] + carries[k]
    lo = c[:L]
    hi = c[L : 2 * L]
    t = [h * 19 for h in hi]
    for j in range(L):
        lo[j] = lo[j] + ((t[j] & 0x7) << 9)
    up = [tj >> 3 for tj in t]
    for j in range(L - 1):
        lo[j + 1] = lo[j + 1] + up[j]
    t2 = up[L - 1] * 19
    lo[0] = lo[0] + ((t2 & 0x7) << 9)
    lo[1] = lo[1] + (t2 >> 3)
    lo[1] = lo[1] + c[44] * 23104
    lo[2] = lo[2] + c[45] * 23104
    return _carry2(lo, steps=3)


_D2_LIMBS = [int(v) for v in F.D2]
_D_LIMBS = [int(v) for v in F.D]
_SQRT_M1_LIMBS = [int(v) for v in F.SQRT_M1]
_BIG_P = [int(v) for v in F.BIG_P]


def _seq_carry_fold_rows(rows: List) -> List:
    """In-kernel twin of field._seq_carry_fold (exact sequential pass)."""
    carry_in = jnp.zeros_like(rows[0])
    out = []
    for i in range(L):
        v = rows[i] + carry_in
        out.append(v & F.LIMB_MASK)
        carry_in = v >> F.LIMB_BITS
    out[0] = out[0] + carry_in * F.TOP_FOLD
    hi = out[L - 1] >> 3
    out[L - 1] = out[L - 1] & 0x7
    out[0] = out[0] + hi * 19
    return out


def _canon22(rows: List) -> List:
    """In-kernel twin of field.canonical — unique representative mod p."""
    rows = [r + _BIG_P[i] for i, r in enumerate(rows)]
    for _ in range(3):
        rows = _seq_carry_fold_rows(rows)
    t = list(rows)
    t[0] = t[0] + 19
    carry_in = jnp.zeros_like(t[0])
    tt = []
    for i in range(L):
        v = t[i] + carry_in
        tt.append(v & F.LIMB_MASK)
        carry_in = v >> F.LIMB_BITS
    ge_p = (tt[L - 1] >> 3) > 0
    tt[L - 1] = tt[L - 1] & 0x7
    return [jnp.where(ge_p, tt[i], rows[i]) for i in range(L)]


def _is_zero22(rows: List):
    c = _canon22(rows)
    acc = c[0] == 0
    for i in range(1, L):
        acc = acc & (c[i] == 0)
    return acc


def _eq22(a: List, b: List):
    return _is_zero22(_sub22(a, b))


def _parity22(rows: List):
    return _canon22(rows)[0] & 1


def _neg22(a: List) -> List:
    return _carry2([-x for x in a])


def _select22(cond, a: List, b: List) -> List:
    return [jnp.where(cond, x, y) for x, y in zip(a, b)]


def _read_point(ref) -> List[List]:
    """Block ref -> 4 coordinate row-lists (X, Y, Z, T).

    2D blocks ([88, T]) keep rows as [1, T]; 4D blocks ([88, 1, 8, 128])
    give each row a full (8, 128) vreg — 8x the lane-axis utilization
    (the [1, T] layout left 7 of 8 sublanes idle per op)."""
    if len(ref.shape) == 2:
        return [
            [ref[c * L + i : c * L + i + 1, :] for i in range(L)]
            for c in range(4)
        ]
    return [[ref[c * L + i, 0] for i in range(L)] for c in range(4)]


def _write_point(ref, coords: Sequence[List]) -> None:
    if len(ref.shape) == 2:
        for c in range(4):
            for i in range(L):
                ref[c * L + i : c * L + i + 1, :] = coords[c][i]
    else:
        for c in range(4):
            for i in range(L):
                ref[c * L + i, 0] = coords[c][i]


def _padd_core(p: List[List], qc: List[List]) -> List[List]:
    """add-2008-hwcd-3 with q pre-transformed to cached rows
    (Y-X, Y+X, 2dT, 2Z). Returns XYZT row-lists."""
    x1, y1, z1, t1 = p
    a = _mul22(_sub22(y1, x1), qc[0])
    b = _mul22(_add22(y1, x1), qc[1])
    cc = _mul22(t1, qc[2])
    d = _mul22(z1, qc[3])
    e = _sub22(b, a)
    f = _sub22(d, cc)
    g = _add22(d, cc)
    h = _add22(b, a)
    return [_mul22(e, f), _mul22(g, h), _mul22(f, g), _mul22(e, h)]


def _padd_xx_kernel(p_ref, q_ref, o_ref):
    """Packed XYZT + packed XYZT -> packed XYZT (complete addition)."""
    p = _read_point(p_ref)
    q = _read_point(q_ref)
    x2, y2, z2, t2 = q
    qc = [
        _sub22(y2, x2),
        _add22(y2, x2),
        _mul22(t2, _D2_LIMBS),
        _dbl22(z2),
    ]
    _write_point(o_ref, _padd_core(p, qc))


def _pow22523_rows(z: List) -> List:
    """z^(2^252 - 3) on row lists — the RFC 8032 sqrt exponent chain,
    entirely in VMEM. fori_loop keeps the Mosaic program small for the
    long square runs; tuple carries, not stacked arrays (jnp.stack of 22
    rows forced a VMEM re-layout every iteration — the 250-deep chain
    spent ~5x its multiply time shuffling, measured on-chip)."""

    def nsq(x: List, n: int) -> List:
        if n <= 4:
            for _ in range(n):
                x = _mul22(x, x)
            return x

        def body(_, rows):
            return tuple(_mul22(list(rows), list(rows)))

        out = jax.lax.fori_loop(0, n, body, tuple(x))
        return list(out)

    t0 = _mul22(z, z)                       # 2
    t1 = _mul22(z, nsq(t0, 2))              # 9
    t0 = _mul22(t0, t1)                     # 11
    t0 = _mul22(t1, _mul22(t0, t0))         # 31
    t0 = _mul22(nsq(t0, 5), t0)             # 2^10 - 1
    t1 = _mul22(nsq(t0, 10), t0)            # 2^20 - 1
    t2 = _mul22(nsq(t1, 20), t1)            # 2^40 - 1
    t1 = _mul22(nsq(t2, 10), t0)            # 2^50 - 1
    t2 = _mul22(nsq(t1, 50), t1)            # 2^100 - 1
    t3 = _mul22(nsq(t2, 100), t2)           # 2^200 - 1
    t1 = _mul22(nsq(t3, 50), t1)            # 2^250 - 1
    return _mul22(nsq(t1, 2), z)            # 2^252 - 3


def _read_rows(ref, start: int, count: int) -> List:
    if len(ref.shape) == 2:
        return [ref[start + i : start + i + 1, :] for i in range(count)]
    return [ref[start + i, 0] for i in range(count)]


def _pow22523_kernel(z_ref, o_ref):
    out = _pow22523_rows(_read_rows(z_ref, 0, L))
    for i in range(L):
        if len(o_ref.shape) == 2:
            o_ref[i : i + 1, :] = out[i]
        else:
            o_ref[i, 0] = out[i]


def _finish_kernel(y_ref, sign_ref, acc_ref, o_ref):
    """Everything after the comb trees, in ONE launch: R decompression
    (incl. the sqrt chain), rhs = R + [k]A, and the projective equality
    [s]B == rhs — the equality/parity tests each need an exact canonical
    pass (22-step sequential carries), which as XLA ops were a long
    dependent chain of tiny kernels.

    y_ref: [22, T] R.y limbs; sign_ref: [1, T] sign bits;
    acc_ref: [176, T] — rows 0..87 = [s]B (XYZT), 88..175 = [k]A.
    o_ref: [1, T] int32 — 1 iff R decompressed valid AND lhs == rhs.
    Ports curve.decompress + curve.padd + curve.points_equal exactly
    (same decision tree; boolean output bit-identical by canonicality).
    """
    y = _read_rows(y_ref, 0, L)
    sign = _read_rows(sign_ref, 0, 1)[0]
    lhs = [_read_rows(acc_ref, c * L, L) for c in range(4)]
    ka = [_read_rows(acc_ref, 88 + c * L, L) for c in range(4)]

    one = [jnp.ones_like(y[0])] + [jnp.zeros_like(y[0])] * (L - 1)
    y2 = _mul22(y, y)
    u = _sub22(y2, one)
    v = _add22(_mul22(y2, _D_LIMBS), one)
    v3 = _mul22(_mul22(v, v), v)
    v7 = _mul22(_mul22(v3, v3), v)
    cand = _mul22(_mul22(u, v3), _pow22523_rows(_mul22(u, v7)))
    vxx = _mul22(v, _mul22(cand, cand))
    root1 = _eq22(vxx, u)
    root2 = _eq22(vxx, _neg22(u))
    x = _select22(root1, cand, _mul22(cand, _SQRT_M1_LIMBS))
    valid = root1 | root2
    x_zero = _is_zero22(x)
    valid = valid & ~(x_zero & (sign == 1))
    flip = _parity22(x) != sign
    x = _select22(flip, _neg22(x), x)
    r_point = [x, y, one, _mul22(x, y)]

    # rhs = R + [k]A (complete addition, ka cached on the fly)
    x2, y2k, z2, t2 = ka
    qc = [
        _sub22(y2k, x2),
        _add22(y2k, x2),
        _mul22(t2, _D2_LIMBS),
        _dbl22(z2),
    ]
    rhs = _padd_core(r_point, qc)

    # projective equality lhs == rhs
    ex = _is_zero22(
        _sub22(_mul22(lhs[0], rhs[2]), _mul22(rhs[0], lhs[2]))
    )
    ey = _is_zero22(
        _sub22(_mul22(lhs[1], rhs[2]), _mul22(rhs[1], lhs[2]))
    )
    bit = (ex & ey & valid).astype(jnp.int32)
    if len(o_ref.shape) == 2:
        o_ref[0:1, :] = bit
    else:
        o_ref[0, 0] = bit


# ---------------------------------------------------------------------------
# Host-callable wrappers
# ---------------------------------------------------------------------------


def _block(n: int) -> int:
    for b in (512, 256, 128):
        if n % b == 0:
            return b
    return n  # below one lane tile: the whole array is the block


_VREG = 8 * 128  # one (8, 128) int32 vector register's worth of lanes


def _call_rowwise(kernel, out_rows: int, interpret: bool, *args: jax.Array):
    """Run `kernel` over [rows_i, N] operands, blocked for full-vreg rows.

    Row counts may differ per operand (each arg's shape[0] is used); the
    lane count N must match. When N divides into (8, 128) vregs the
    operands are viewed as [rows, G, 8, 128] and each block is one
    vreg-shaped row set; otherwise a flat [rows, blk] 2D block is used.
    The 2D branch is on the served path, not only in tests: a dispatch
    of B signatures runs tree levels of 64B, 32B, ..., 2B lanes and the
    finish kernel at B lanes, so at one n=256 round per dispatch
    (B = 256) the last tree level (512 lanes) and the whole finish
    kernel — sqrt chain included — are 2D, and at B = 512 the finish
    kernel still is. Both branches compile under jax 0.9.0 / libtpu
    0.0.34 and match the jnp tree on a v5e (chip_smoke.py phases A, C).
    """
    n = args[0].shape[1]
    if n % _VREG == 0:
        g = n // _VREG
        shaped = [a.reshape(a.shape[0], g, 8, 128) for a in args]
        out = pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((out_rows, g, 8, 128), jnp.int32),
            grid=(g,),
            in_specs=[
                pl.BlockSpec((a.shape[0], 1, 8, 128), lambda i: (0, i, 0, 0))
                for a in args
            ],
            out_specs=pl.BlockSpec(
                (out_rows, 1, 8, 128), lambda i: (0, i, 0, 0)
            ),
            interpret=interpret,
        )(*shaped)
        return out.reshape(out_rows, n)
    blk = _block(n)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((out_rows, n), jnp.int32),
        grid=(n // blk,),
        in_specs=[
            pl.BlockSpec((a.shape[0], blk), lambda i: (0, i)) for a in args
        ],
        out_specs=pl.BlockSpec((out_rows, blk), lambda i: (0, i)),
        interpret=interpret,
    )(*args)


@functools.partial(jax.jit, static_argnames=("interpret",))
def padd_xx(p: jax.Array, q: jax.Array, *, interpret: bool = False) -> jax.Array:
    """p, q: int32[88, N] packed XYZT (N a multiple of 128) -> [88, N]."""
    return _call_rowwise(_padd_xx_kernel, ROWS, interpret, p, q)


@functools.partial(jax.jit, static_argnames=("interpret",))
def pow22523(z: jax.Array, *, interpret: bool = False) -> jax.Array:
    """z: int32[22, N] -> z^(2^252-3): one launch, zero HBM between muls.

    The production path runs this chain inside :func:`finish_check`'s
    kernel; this standalone entry exists for benchmarking and as the
    kernel-level unit under test."""
    return _call_rowwise(_pow22523_kernel, L, interpret, z)


@functools.partial(jax.jit, static_argnames=("interpret",))
def finish_check(
    r_y: jax.Array, r_sign: jax.Array, acc: jax.Array, *, interpret: bool = False
) -> jax.Array:
    """The post-tree tail of comb verification as ONE kernel launch.

    r_y: int32[B, 22]; r_sign: int32[B]; acc: int32[B, 2, 4, 22]
    (axis 1 = ([s]B, [k]A)). Returns bool[B]: R valid AND [s]B == R+[k]A.
    """
    b = r_y.shape[0]
    y_t = jnp.moveaxis(r_y, 0, 1)  # [22, B]
    sign_t = r_sign.reshape(1, b)
    acc_t = jnp.moveaxis(acc.reshape(b, 8, L), 0, -1).reshape(8 * L, b)
    out = _call_rowwise(_finish_kernel, 1, interpret, y_t, sign_t, acc_t)
    return out.reshape(b).astype(bool)


def tree_sum_xyzt(entries: jax.Array, *, interpret: bool = False) -> jax.Array:
    """Sum M packed XYZT points per element: [..., M, 4, 22] -> [..., 4, 22].

    Transposes once to limb-major [88, M * flat], halves the lane axis
    each level with :func:`padd_xx` (contiguous first-half/second-half
    pairing), transposes the tiny result back. M must be a power of two;
    identity entries are harmless padding (complete formulas).
    """
    *lead, m, four, limbs = entries.shape
    assert four == 4 and limbs == L and m & (m - 1) == 0
    flat = int(np.prod(lead)) if lead else 1
    # [..., M, 4, 22] -> [4, 22, M, flat] -> [88, M * flat]
    x = jnp.moveaxis(entries.reshape(flat, m, 4, L), 0, -1)  # [M, 4, 22, flat]
    x = jnp.moveaxis(x, 0, -2)  # [4, 22, M, flat]
    x = x.reshape(ROWS, m * flat)
    while m > 1:
        half = m // 2 * flat
        x = padd_xx(x[:, :half], x[:, half:], interpret=interpret)
        m //= 2
    out = x.reshape(4, L, *lead) if lead else x.reshape(4, L)
    return jnp.moveaxis(jnp.moveaxis(out, 1, -1), 0, -2)  # [..., 4, 22]
