"""Pallas TPU kernel for whole BLS12-381 G1 additions — the MSM tree engine.

Same rationale as :mod:`dag_rider_tpu.ops.pallas_group` (measured on-chip,
round 3): a group addition is ~12 field multiplies with
stacks/slices/carries between them, and XLA materializes the intermediate
columns of every step in HBM — the Ed25519 comb tree ran ~20x above its
compute floor until its additions became single kernel launches. The MSM
window tree (:func:`dag_rider_tpu.ops.bls_msm.window_sums`) has the same
shape; this kernel performs one complete RCB15 addition per launch with
every intermediate in VMEM.

Layout: limb-major [99, N] int32 — rows are (coordinate, limb) pairs
(3 x 33 homogeneous X, Y, Z), N the flattened batch in the 128-wide lane
axis. Tree levels pair first-half/second-half contiguous lane slices.

Bit-exactness: the limb math replicates :mod:`dag_rider_tpu.ops.field381`
step for step (same masks, carry counts, fold matrix) and the addition
replicates :func:`dag_rider_tpu.ops.bls_msm.padd` op for op, so results
are bit-identical to the jnp path (tests/test_pallas_group381.py runs
interpret mode against it).
"""

from __future__ import annotations

import functools
from typing import List, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from dag_rider_tpu.ops import field381 as F
from dag_rider_tpu.ops.pallas_group import _call_rowwise

L = F.LIMBS  # 33
COORDS = 3
ROWS = COORDS * L  # 99
_NCOLS = F._NCOLS  # 67
_FOLD = [[int(v) for v in row] for row in F.FOLD]  # [35][32]
_FOLD_TOP = [int(v) for v in F._FOLD_TOP]  # [33]


# ---------------------------------------------------------------------------
# In-kernel limb math on lists of lane-vector rows (twin of field381)
# ---------------------------------------------------------------------------


def _carry33(rows: List, steps: int = 2) -> List:
    """field381.carry on a 33-row list: parallel carry steps, the top
    (weight 2^396) carry folding back through the 2^396 mod p row."""
    for _ in range(steps):
        cs = [r >> F.LIMB_BITS for r in rows]
        rows = [r & F.LIMB_MASK for r in rows]
        top = cs[L - 1]
        for j in range(L - 1):
            rows[j + 1] = rows[j + 1] + cs[j]
        for i in range(L):
            if _FOLD_TOP[i]:
                rows[i] = rows[i] + top * _FOLD_TOP[i]
    return rows


def _add33(a: List, b: List) -> List:
    return _carry33([x + y for x, y in zip(a, b)])


def _sub33(a: List, b: List) -> List:
    return _carry33([x - y for x, y in zip(a, b)])


def _mul_small33(a: List, k: int) -> List:
    return _carry33([x * k for x in a], steps=3)


def _mul33(a: List, b: List) -> List:
    """Schoolbook 33x33 -> 67 columns, two normalize passes, fold-matrix
    reduction — the exact step sequence of field381.mul."""
    c = [None] * (2 * L - 1)  # columns 0..64
    for i in range(L):
        for j in range(L):
            t = a[i] * b[j]
            k = i + j
            c[k] = t if c[k] is None else c[k] + t
    zero = jnp.zeros_like(a[0])
    c = [zero if x is None else x for x in c] + [zero, zero]  # 67 cols
    for _ in range(2):
        carries = [x >> F.LIMB_BITS for x in c]
        c = [x & F.LIMB_MASK for x in c]
        for k in range(len(c) - 1):
            c[k + 1] = c[k + 1] + carries[k]
    lo = c[:32]
    hi = c[32:_NCOLS]  # 35 columns
    for j in range(len(hi)):
        row = _FOLD[j]
        for i in range(32):
            if row[i]:
                lo[i] = lo[i] + hi[j] * row[i]
    out = lo + [zero]  # limb 32 = 0
    return _carry33(out, steps=3)


# ---------------------------------------------------------------------------
# Complete addition (RCB15 Algorithm 7, a = 0, b3 = 12) — bls_msm.padd twin
# ---------------------------------------------------------------------------


def _padd381_core(p: List[List], q: List[List]) -> List[List]:
    X1, Y1, Z1 = p
    X2, Y2, Z2 = q
    t0 = _mul33(X1, X2)
    t1 = _mul33(Y1, Y2)
    t2 = _mul33(Z1, Z2)
    t3 = _mul33(_add33(X1, Y1), _add33(X2, Y2))
    t3 = _sub33(t3, _add33(t0, t1))
    t4 = _mul33(_add33(Y1, Z1), _add33(Y2, Z2))
    t4 = _sub33(t4, _add33(t1, t2))
    x3 = _mul33(_add33(X1, Z1), _add33(X2, Z2))
    y3 = _sub33(x3, _add33(t0, t2))
    x3 = _add33(_add33(t0, t0), t0)  # 3 X1 X2
    t2 = _mul_small33(t2, 12)  # b3 Z1 Z2
    z3 = _add33(t1, t2)
    t1 = _sub33(t1, t2)
    y3 = _mul_small33(y3, 12)  # b3 (X1 Z2 + X2 Z1)
    X3 = _sub33(_mul33(t3, t1), _mul33(t4, y3))
    Y3 = _add33(_mul33(y3, x3), _mul33(t1, z3))
    Z3 = _add33(_mul33(z3, t4), _mul33(x3, t3))
    return [X3, Y3, Z3]


def _read_point(ref) -> List[List]:
    if len(ref.shape) == 2:
        return [
            [ref[c * L + i : c * L + i + 1, :] for i in range(L)]
            for c in range(COORDS)
        ]
    return [[ref[c * L + i, 0] for i in range(L)] for c in range(COORDS)]


def _write_point(ref, coords: Sequence[List]) -> None:
    if len(ref.shape) == 2:
        for c in range(COORDS):
            for i in range(L):
                ref[c * L + i : c * L + i + 1, :] = coords[c][i]
    else:
        for c in range(COORDS):
            for i in range(L):
                ref[c * L + i, 0] = coords[c][i]


def _padd381_kernel(p_ref, q_ref, o_ref):
    _write_point(o_ref, _padd381_core(_read_point(p_ref), _read_point(q_ref)))


# ---------------------------------------------------------------------------
# Host-callable wrappers
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("interpret",))
def padd381_xx(
    p: jax.Array, q: jax.Array, *, interpret: bool = False
) -> jax.Array:
    """p, q: int32[99, N] packed XYZ -> [99, N] complete addition."""
    return _call_rowwise(_padd381_kernel, ROWS, interpret, p, q)


def tree_sum_xyz381(
    entries: jax.Array, *, interpret: bool = False
) -> jax.Array:
    """Sum M packed XYZ points per element: [..., M, 3, 33] -> [..., 3, 33].

    Transposes once to limb-major [99, M * flat], halves the lane axis
    each level with :func:`padd381_xx` (contiguous first-half/second-half
    pairing — order is free by associativity), transposes the tiny result
    back. M must be a power of two; identity (0:1:0) entries are harmless
    padding (complete formulas).
    """
    *lead, m, coords, limbs = entries.shape
    assert coords == COORDS and limbs == L and m & (m - 1) == 0
    flat = int(np.prod(lead)) if lead else 1
    x = jnp.moveaxis(entries.reshape(flat, m, COORDS, L), 0, -1)
    x = jnp.moveaxis(x, 0, -2)  # [3, 33, M, flat]
    x = x.reshape(ROWS, m * flat)
    while m > 1:
        half = m // 2 * flat
        x = padd381_xx(x[:, :half], x[:, half:], interpret=interpret)
        m //= 2
    out = x.reshape(COORDS, L, *lead) if lead else x.reshape(COORDS, L)
    return jnp.moveaxis(jnp.moveaxis(out, 1, -1), 0, -2)  # [..., 3, 33]
