"""Device multi-pairing Miller product — the `DAGRIDER_CERT_PAIR=device`
lane (ISSUE 12 tentpole 2).

The certificate aggregate check is one product check
``e(agg, -g2) * prod_i e(H(d_i), pk_i) == 1``. The host fast path
(`crypto/bls12381.multi_pairing_check`) already replays per-key
precomputed line coefficients over the fixed 63-bit Miller schedule; this
module moves the replay onto the accelerator: all pairs' line evaluations
per schedule step run lane-parallel as batched Fp12 limb arithmetic on
:mod:`dag_rider_tpu.ops.field381`, a uniform `lax.scan` walks the
schedule (add-step products are computed every step and gated by the
schedule flag — branch-free), and only the cheap-but-branchy final
exponentiation stays on host.

Bit-identity with the host oracle is structural: every limb op is exact
mod-p arithmetic, so the Miller accumulator is the same Fp12 *element*
regardless of product association, and conjugation + final
exponentiation of equal elements give equal verdicts AND equal GT
values. The only host-side escape is a vertical line in a precomputed
schedule (impossible for r-order G2 points, whose schedule never hits
the point at infinity mid-walk) — those pairs route to the host oracle.

Like the sharded MSM and the G1 signing lane, this is a where-the-work-
runs lane: on the 1-core CPU host it loses to the host replay (round
15's A/B); the lane is the committee-scale accelerator story for the
verify side.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from dag_rider_tpu.crypto import bls12381 as bls
from dag_rider_tpu.ops import field381 as f

#: schedule length (63 bits below the leading one of |x|)
_N_STEPS = len(bls._X_BITS)

P_INT = f.P_INT

#: fp12 one as packed limbs [12, LIMBS]
_ONE_PACKED = np.zeros((12, f.LIMBS), dtype=np.int32)
_ONE_PACKED[0] = f.ONE


def _fp12_flat(x) -> List[int]:
    """Host fp12 tuple -> 12 coefficient ints, (a0 a1 a2 b0 b1 b2) each
    (re, im) — the packed coefficient order used on device."""
    (a0, a1, a2), (b0, b1, b2) = x
    return [
        a0[0], a0[1], a1[0], a1[1], a2[0], a2[1],
        b0[0], b0[1], b1[0], b1[1], b2[0], b2[1],
    ]


def _fp12_unflat(c: Sequence[int]):
    return (
        ((c[0], c[1]), (c[2], c[3]), (c[4], c[5])),
        ((c[6], c[7]), (c[8], c[9]), (c[10], c[11])),
    )


# --- packed tower arithmetic (coefficient axis -2, limb axis -1) -----------


def _unpack(a):
    c = [a[..., j, :] for j in range(12)]
    return (
        ((c[0], c[1]), (c[2], c[3]), (c[4], c[5])),
        ((c[6], c[7]), (c[8], c[9]), (c[10], c[11])),
    )


def _pack(x):
    (a0, a1, a2), (b0, b1, b2) = x
    return jnp.stack(
        [
            a0[0], a0[1], a1[0], a1[1], a2[0], a2[1],
            b0[0], b0[1], b1[0], b1[1], b2[0], b2[1],
        ],
        axis=-2,
    )


def _fp2_add(x, y):
    return (f.add(x[0], y[0]), f.add(x[1], y[1]))


def _fp2_sub(x, y):
    return (f.sub(x[0], y[0]), f.sub(x[1], y[1]))


def _fp2_mul(x, y):
    a, b = x
    c, d = y
    return (
        f.sub(f.mul(a, c), f.mul(b, d)),
        f.add(f.mul(a, d), f.mul(b, c)),
    )


def _fp2_mul_xi(x):
    """x * (1 + u): (a - b) + (a + b) u."""
    a, b = x
    return (f.sub(a, b), f.add(a, b))


def _fp6_add(x, y):
    return tuple(_fp2_add(a, b) for a, b in zip(x, y))


def _fp6_sub(x, y):
    return tuple(_fp2_sub(a, b) for a, b in zip(x, y))


def _fp6_mul(x, y):
    a0, a1, a2 = x
    b0, b1, b2 = y
    t0 = _fp2_mul(a0, b0)
    t1 = _fp2_mul(a1, b1)
    t2 = _fp2_mul(a2, b2)
    c0 = _fp2_add(
        t0,
        _fp2_mul_xi(
            _fp2_sub(
                _fp2_mul(_fp2_add(a1, a2), _fp2_add(b1, b2)),
                _fp2_add(t1, t2),
            )
        ),
    )
    c1 = _fp2_add(
        _fp2_sub(
            _fp2_mul(_fp2_add(a0, a1), _fp2_add(b0, b1)), _fp2_add(t0, t1)
        ),
        _fp2_mul_xi(t2),
    )
    c2 = _fp2_add(
        _fp2_sub(
            _fp2_mul(_fp2_add(a0, a2), _fp2_add(b0, b2)), _fp2_add(t0, t2)
        ),
        t1,
    )
    return (c0, c1, c2)


def _fp6_mul_by_v(x):
    return (_fp2_mul_xi(x[2]), x[0], x[1])


def _fp12_mul_packed(xa, ya):
    x, y = _unpack(xa), _unpack(ya)
    a0, a1 = x
    b0, b1 = y
    t0 = _fp6_mul(a0, b0)
    t1 = _fp6_mul(a1, b1)
    c0 = _fp6_add(t0, _fp6_mul_by_v(t1))
    c1 = _fp6_sub(
        _fp6_mul(_fp6_add(a0, a1), _fp6_add(b0, b1)), _fp6_add(t0, t1)
    )
    return _pack((c0, c1))


@jax.jit
def _eval_lines(lam, c, xp, yp):
    """The precomputed lines at (xp, yp): (c - lam*xp) + yp at coefficient
    a0.re — the packed twin of the host `_line_eval` non-vertical arm,
    evaluated for every schedule step and pair at once.
    lam, c: [steps, n, 12, LIMBS]; xp, yp: [n, LIMBS]."""
    ell = f.sub(c, f.mul(lam, xp[None, :, None, :]))
    ell0 = f.add(ell[..., 0, :], yp[None])
    return jnp.concatenate([ell0[..., None, :], ell[..., 1:, :]], axis=-2)


# One jitted fp12 multiply reused for the whole walk: compiled once per
# operand shape ([steps, 12, L] for the cross-pair product, [12, L] for
# the accumulator) and shared across every pair count — a monolithic
# scan-the-schedule kernel was bit-identical but took minutes of XLA
# compile per pair-count; ~200 small dispatches beat that by >100x.
_mul_packed_jit = jax.jit(_fp12_mul_packed)
_canonical_jit = jax.jit(f.canonical)


# --- host-side schedule marshalling ----------------------------------------

#: q -> (dbl_lam, dbl_c, add_lam, add_c) limb arrays [steps, 12, LIMBS]
_SLOT_CACHE: dict = {}
_SLOT_CACHE_MAX = 1024

def _slot_limbs(q):
    """Per-step (doubling, addition) line-coefficient limb arrays for G2
    point q; vertical slots (never hit by r-order points) return None and
    the caller falls back to the host oracle."""
    hit = _SLOT_CACHE.get(q)
    if hit is not None:
        return hit
    coeffs = bls.g2_precompute(q)
    if any(lam is None for lam, _ in coeffs):
        return None
    dbl_lam, dbl_c, add_lam, add_c = [], [], [], []
    idx = 0
    zero12 = [0] * 12
    for bit in bls._X_BITS:
        lam, c = coeffs[idx]
        idx += 1
        dbl_lam.append(_fp12_flat(lam))
        dbl_c.append(_fp12_flat(c))
        if bit == "1":
            lam, c = coeffs[idx]
            idx += 1
            add_lam.append(_fp12_flat(lam))
            add_c.append(_fp12_flat(c))
        else:
            add_lam.append(zero12)
            add_c.append(zero12)
    out = tuple(
        f.to_limbs_bulk(
            [v for step in arr for v in step]
        ).reshape(_N_STEPS, 12, f.LIMBS)
        for arr in (dbl_lam, dbl_c, add_lam, add_c)
    )
    if len(_SLOT_CACHE) >= _SLOT_CACHE_MAX:
        _SLOT_CACHE.clear()
    _SLOT_CACHE[q] = out
    return out


def miller_product(pairs: Sequence[Tuple[object, object]]):
    """The Miller-loop product of (G1, G2) pairs as a host fp12 tuple
    (conjugated for the negative x, exactly like the host oracle) — feed
    to `bls.final_exponentiation`. None-containing pairs contribute 1."""
    evs = []
    for p, q in pairs:
        if p is None or q is None:
            continue
        slots = _slot_limbs(q)
        if slots is None:
            # vertical schedule slot: not reachable for subgroup keys;
            # route the whole product to the host oracle for exactness
            return None
        evs.append((p[0] % P_INT, p[1] % P_INT, slots))
    if not evs:
        return bls.FP12_ONE
    n = len(evs)
    xp = jnp.asarray(f.to_limbs_bulk([e[0] for e in evs]))
    yp = jnp.asarray(f.to_limbs_bulk([e[1] for e in evs]))
    stacked = [
        jnp.asarray(
            np.stack([e[2][k] for e in evs], axis=1)
        )  # [steps, n, 12, LIMBS]
        for k in range(4)
    ]
    evals_d = _eval_lines(stacked[0], stacked[1], xp, yp)
    evals_a = _eval_lines(stacked[2], stacked[3], xp, yp)
    # cross-pair product, all schedule steps at once ([steps, 12, LIMBS])
    dprod, aprod = evals_d[:, 0], evals_a[:, 0]
    for k in range(1, n):
        dprod = _mul_packed_jit(dprod, evals_d[:, k])
        aprod = _mul_packed_jit(aprod, evals_a[:, k])
    # schedule walk on the [12, LIMBS] accumulator (garbage add-step
    # products are never touched — the host loop skips them)
    acc = jnp.asarray(_ONE_PACKED)
    for s, bit in enumerate(bls._X_BITS):
        acc = _mul_packed_jit(acc, acc)
        acc = _mul_packed_jit(acc, dprod[s])
        if bit == "1":
            acc = _mul_packed_jit(acc, aprod[s])
    out = np.asarray(_canonical_jit(acc))
    fvals = [f.from_limbs(out[j]) for j in range(12)]
    res = _fp12_unflat(fvals)
    if bls.X_PARAM < 0:
        res = bls.fp12_conj(res)
    return res


def multi_pairing_check(pairs: Sequence[Tuple[object, object]]) -> bool:
    """Device twin of `bls.multi_pairing_check` — bit-identical verdicts
    (pinned on the full Byzantine certificate matrix in tests)."""
    fm = miller_product(pairs)
    if fm is None:
        return bls.multi_pairing_check(pairs)
    return bls.final_exponentiation(fm) == bls.FP12_ONE
