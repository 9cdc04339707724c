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


# --- the verify service's certificate program -------------------------------
#
# A served round certificate is checked by ONE dispatch of one program at
# one shape: the committee's per-key line tables live on the device (built
# once, gathered by signer index), every pair keeps its own accumulator
# through the schedule, and a log-depth tree multiplies the accumulators
# at the end. The shape is fixed by the committee (n + 1 pairs, padded to
# 1 + a power of two), so nothing compiles after warm-up; pad rows carry
# the line value 1 and contribute 1. The accumulator is the same Fp12
# element as the host replay's (exact arithmetic, reassociated), so the
# verdicts are the oracle's.
#
# Inside the program the limbs lie on the LEADING axis ([33, ..., pairs]),
# the pairs on the lane axis, and every Fp product of a step — the 12 of
# the line evaluations and the 36 of the squaring, then the 54 of the
# multiply (Karatsuba at each tower level) — is gathered into ONE batched
# multiply: on the TPU a Pallas kernel running pallas_group381's limb
# schoolbook with every column in VMEM (XLA would write each product's
# 33 x 33 columns to HBM), elsewhere field381.mul. Both are the same limb
# algorithm, so the limbs out are the same.

#: one flag per consumed schedule slot: True for a doubling line (the
#: accumulator squares before it multiplies), False for an addition line
_SLOT_DOUBLES = tuple(
    dbl for bit in bls._X_BITS for dbl in ((True, False) if bit == "1" else (True,))
)
SLOTS = len(_SLOT_DOUBLES)

#: 1 / (1 + u): the untwist puts a twist line's slope at w^5 and its
#: constant at w^3, each divided by xi (see :func:`line_table`)
_XI_INV = bls.fp2_inv(bls.XI)


def _fp2_inv_many(vals: Sequence[tuple]) -> List[tuple]:
    """Inverses of nonzero Fp2 elements with one field inversion
    (Montgomery's trick)."""
    prefix = [bls.FP2_ONE]
    for v in vals:
        prefix.append(bls.fp2_mul(prefix[-1], v))
    inv = bls.fp2_inv(prefix[-1])
    out = [None] * len(vals)
    for i in range(len(vals) - 1, -1, -1):
        out[i] = bls.fp2_mul(inv, prefix[i])
        inv = bls.fp2_mul(inv, vals[i])
    return out


def line_table(keys: Sequence[object]) -> Tuple[np.ndarray, List[bool]]:
    """Every key's Miller lines as limb rows ``[SLOTS, len(keys), 2, 12,
    LIMBS]`` int32 (slope, then constant, each a packed Fp12) and whether
    each key can use them.

    The lines are those of :func:`bls.g2_precompute`, computed on the
    twist in Fp2 with one batched inversion a slot for all keys: under
    the untwist (x, y) -> (x w^-2, y w^-3) a twist slope ``l`` becomes
    ``(l / xi) w^5`` and a constant ``c = l x - y`` becomes
    ``(c / xi) w^3``. A key that is the identity, or whose schedule meets
    a vertical line or a point of order two (no r-order key does), is
    marked unusable and its rows stay 0: its certificates go to the host
    oracle."""
    k = len(keys)
    usable = [q is not None for q in keys]
    t = [q if q is not None else (bls.FP2_ONE, bls.FP2_ONE) for q in keys]
    vals: List[int] = []

    def slopes(nums, dens):
        """Each key's slope at this slot; its line, scaled for the
        untwist, goes to ``vals``."""
        invs = _fp2_inv_many([d if d != bls.FP2_ZERO else bls.FP2_ONE for d in dens])
        out = []
        for num, inv, (xt, yt) in zip(nums, invs, t):
            lam = bls.fp2_mul(num, inv)
            c = bls.fp2_sub(bls.fp2_mul(lam, xt), yt)
            lam_e, c_e = bls.fp2_mul(lam, _XI_INV), bls.fp2_mul(c, _XI_INV)
            vals.extend((lam_e[0], lam_e[1], c_e[0], c_e[1]))
            out.append(lam)
        return out

    def step(lams, xqs):
        return [
            (x3, bls.fp2_sub(bls.fp2_mul(lam, bls.fp2_sub(xt, x3)), yt))
            for lam, xq, (xt, yt) in zip(lams, xqs, t)
            for x3 in (bls.fp2_sub(bls.fp2_sub(bls.fp2_mul(lam, lam), xt), xq),)
        ]

    for bit in bls._X_BITS:
        nums, dens = [], []
        for i, (xt, yt) in enumerate(t):
            if yt == bls.FP2_ZERO:
                usable[i] = False
            nums.append(bls.fp2_scalar(bls.fp2_mul(xt, xt), 3))
            dens.append(bls.fp2_scalar(yt, 2))
        t = step(slopes(nums, dens), [xt for xt, _ in t])
        if bit != "1":
            continue
        nums, dens, xqs = [], [], []
        for i, (xt, yt) in enumerate(t):
            q = keys[i] if keys[i] is not None else t[i]
            if xt == q[0]:
                # t == q takes the tangent, as the oracle does; t == -q is
                # the vertical line
                if yt != q[1] or yt == bls.FP2_ZERO:
                    usable[i] = False
                nums.append(bls.fp2_scalar(bls.fp2_mul(xt, xt), 3))
                dens.append(bls.fp2_scalar(yt, 2))
            else:
                nums.append(bls.fp2_sub(q[1], yt))
                dens.append(bls.fp2_sub(q[0], xt))
            xqs.append(q[0])
        t = step(slopes(nums, dens), xqs)
    limbs = f.to_limbs_bulk(vals).reshape(SLOTS, k, 4, f.LIMBS)
    table = np.zeros((SLOTS, k, 2, 12, f.LIMBS), dtype=np.int32)
    # slope at b2 (w^5 = v^2 w), constant at b1 (w^3 = v w): packed 10-11, 8-9
    table[:, :, 0, 10:12] = limbs[:, :, 0:2]
    table[:, :, 1, 8:10] = limbs[:, :, 2:4]
    for i, ok in enumerate(usable):
        if not ok:
            table[:, i] = 0
    return table, usable


def _lm_carry(x, steps: int = 2):
    """field381.carry with the limbs on axis 0."""
    top = jnp.asarray(f._FOLD_TOP).reshape((f.LIMBS,) + (1,) * (x.ndim - 1))
    for _ in range(steps):
        c = x >> f.LIMB_BITS
        low = x & f.LIMB_MASK
        x = low + jnp.concatenate([jnp.zeros_like(c[:1]), c[:-1]], axis=0) + c[-1:] * top
    return x


def _lm_add(a, b):
    return _lm_carry(a + b)


def _lm_sub(a, b):
    return _lm_carry(a - b)


def _mul_rows_kernel(a_ref, b_ref, o_ref):
    from dag_rider_tpu.ops import pallas_group381

    # blocks [LIMBS, 1, 8, 128]: _mul_pallas pads to whole vregs, so
    # _call_rowwise always takes its vreg-shaped branch
    a = [a_ref[i, 0] for i in range(f.LIMBS)]
    b = [b_ref[i, 0] for i in range(f.LIMBS)]
    out = pallas_group381._mul33(a, b)
    for i in range(f.LIMBS):
        o_ref[i, 0] = out[i]


def _mul_pallas(a, b, interpret: bool = False):
    """field381.mul of limb-major [33, N] rows in one Pallas launch, the
    lanes padded to whole (8, 128) vregs."""
    from dag_rider_tpu.ops.pallas_group import _VREG, _call_rowwise

    n = a.shape[1]
    pad = -n % _VREG
    if pad:
        a = jnp.pad(a, ((0, 0), (0, pad)))
        b = jnp.pad(b, ((0, 0), (0, pad)))
    return _call_rowwise(_mul_rows_kernel, f.LIMBS, interpret, a, b)[:, :n]


def _lm_mul(a, b):
    """field381.mul of limb-major [33, N] rows: the Pallas kernel on the
    TPU backend, field381.mul itself elsewhere."""
    if jax.default_backend() == "tpu":
        return _mul_pallas(a, b)
    return f.mul(a.T, b.T).T


class _Products:
    """The independent Fp products of one step, multiplied in one batch:
    ``prod(a, b)`` books a product and returns its index; ``run()``
    returns them all."""

    def __init__(self):
        self.a, self.b = [], []

    def __call__(self, a, b) -> int:
        self.a.append(a)
        self.b.append(b)
        return len(self.a) - 1

    def run(self):
        a = jnp.stack(self.a, axis=1)
        b = jnp.stack(self.b, axis=1)
        out = _lm_mul(a.reshape(f.LIMBS, -1), b.reshape(f.LIMBS, -1)).reshape(a.shape)
        return [out[:, i] for i in range(len(self.a))]


def _lm_fp2_add(x, y):
    return (_lm_add(x[0], y[0]), _lm_add(x[1], y[1]))


def _lm_fp2_sub(x, y):
    return (_lm_sub(x[0], y[0]), _lm_sub(x[1], y[1]))


def _lm_fp2_mul_xi(x):
    return (_lm_sub(x[0], x[1]), _lm_add(x[0], x[1]))


def _lm_fp6_add(x, y):
    return tuple(_lm_fp2_add(a, b) for a, b in zip(x, y))


def _lm_fp6_sub(x, y):
    return tuple(_lm_fp2_sub(a, b) for a, b in zip(x, y))


def _lm_fp6_mul_by_v(x):
    return (_lm_fp2_mul_xi(x[2]), x[0], x[1])


# A plan books a tower product's Fp products and returns the function
# that assembles the product from the batch's results.


def _fp2_plan(x, y, prod):
    """Karatsuba: ac - bd, (a + b)(c + d) - ac - bd."""
    (a, b), (c, d) = x, y
    i, j, k = prod(a, c), prod(b, d), prod(_lm_add(a, b), _lm_add(c, d))
    return lambda r: (_lm_sub(r[i], r[j]), _lm_sub(_lm_sub(r[k], r[i]), r[j]))


def _fp6_plan(x, y, prod):
    """:func:`_fp6_mul`'s six Fp2 products."""
    a0, a1, a2 = x
    b0, b1, b2 = y
    plans = [
        _fp2_plan(a0, b0, prod),
        _fp2_plan(a1, b1, prod),
        _fp2_plan(a2, b2, prod),
        _fp2_plan(_lm_fp2_add(a1, a2), _lm_fp2_add(b1, b2), prod),
        _fp2_plan(_lm_fp2_add(a0, a1), _lm_fp2_add(b0, b1), prod),
        _fp2_plan(_lm_fp2_add(a0, a2), _lm_fp2_add(b0, b2), prod),
    ]

    def done(r):
        t0, t1, t2, s12, s01, s02 = (p(r) for p in plans)
        return (
            _lm_fp2_add(t0, _lm_fp2_mul_xi(_lm_fp2_sub(s12, _lm_fp2_add(t1, t2)))),
            _lm_fp2_add(_lm_fp2_sub(s01, _lm_fp2_add(t0, t1)), _lm_fp2_mul_xi(t2)),
            _lm_fp2_add(_lm_fp2_sub(s02, _lm_fp2_add(t0, t2)), t1),
        )

    return done


def _fp12_plan(x, y, prod):
    """:func:`_fp12_mul_packed`'s three Fp6 products."""
    (a0, a1), (b0, b1) = x, y
    p0, p1 = _fp6_plan(a0, b0, prod), _fp6_plan(a1, b1, prod)
    p2 = _fp6_plan(_lm_fp6_add(a0, a1), _lm_fp6_add(b0, b1), prod)

    def done(r):
        t0, t1, t2 = p0(r), p1(r), p2(r)
        return (
            _lm_fp6_add(t0, _lm_fp6_mul_by_v(t1)),
            _lm_fp6_sub(t2, _lm_fp6_add(t0, t1)),
        )

    return done


def _fp12_sqr_plan(x, prod):
    """(a + b w)^2 = (a^2 + v b^2) + 2ab w, with two Fp6 products."""
    a, b = x
    pab = _fp6_plan(a, b, prod)
    pt = _fp6_plan(_lm_fp6_add(a, b), _lm_fp6_add(a, _lm_fp6_mul_by_v(b)), prod)

    def done(r):
        ab, t = pab(r), pt(r)
        return (_lm_fp6_sub(_lm_fp6_sub(t, ab), _lm_fp6_mul_by_v(ab)), _lm_fp6_add(ab, ab))

    return done


def _lm_unpack(a):
    """[33, 12, ...] -> the tower's nested tuples of [33, ...] rows, in
    the packed coefficient order (a0 a1 a2 b0 b1 b2, each re, im)."""
    c = [a[:, j] for j in range(12)]
    return (
        ((c[0], c[1]), (c[2], c[3]), (c[4], c[5])),
        ((c[6], c[7]), (c[8], c[9]), (c[10], c[11])),
    )


def _lm_pack(x):
    return jnp.stack([c for half in x for fp2 in half for c in fp2], axis=1)


def _lm_fp12_mul(x, y):
    prod = _Products()
    done = _fp12_plan(_lm_unpack(x), _lm_unpack(y), prod)
    return _lm_pack(done(prod.run()))


def _cert_miller(tables, idx, xp, yp, valid):
    """The Miller product of one certificate's pairs, canonical [12, LIMBS].

    tables [SLOTS, keys, 2, 12, LIMBS]; idx [1 + T] the key row of each
    pair (row 0 the aggregate's, against -G2; T a power of two); xp, yp
    [1 + T, LIMBS] the pairs' affine G1 points; valid [1 + T], False on a
    pad row, whose lines read 1."""
    rows = idx.shape[0]
    one = jnp.zeros((f.LIMBS, 12, rows), jnp.int32).at[0, 0].set(1)
    # the pairs' line rows, gathered once: [SLOTS, 2, LIMBS, 12, rows]
    lines = jnp.transpose(tables[:, idx], (0, 2, 4, 3, 1))
    xs, ys = xp.T, yp.T
    keep = valid[None, None, :]

    def slot(acc, step):
        line, dbl = step
        prod = _Products()
        sqr = _fp12_sqr_plan(_lm_unpack(acc), prod)
        lam_x = [prod(line[0][:, j], xs) for j in range(12)]
        r = prod.run()
        # the line at the pair's point: c - lam * xp, plus yp at a0.re
        ell = [_lm_sub(line[1][:, j], r[i]) for j, i in enumerate(lam_x)]
        ell[0] = _lm_add(ell[0], ys)
        ell = jnp.where(keep, jnp.stack(ell, axis=1), one)
        acc = jnp.where(dbl, _lm_pack(sqr(r)), acc)
        return _lm_fp12_mul(acc, ell), None

    acc, _ = jax.lax.scan(slot, one, (lines, jnp.asarray(np.array(_SLOT_DOUBLES))))
    rest = acc[:, :, 1:]
    width = rest.shape[2]
    ones = one[:, :, 1:]

    def level(i, x):
        # x[j] *= x[j + width / 2^(i+1)]: after the last level x[0] is the product
        shifted = jax.lax.dynamic_slice_in_dim(
            jnp.concatenate([x, ones], axis=2), width >> (i + 1), width, axis=2
        )
        return _lm_fp12_mul(x, shifted)

    rest = jax.lax.fori_loop(0, width.bit_length() - 1, level, rest)
    return f.canonical(_lm_fp12_mul(acc[:, :, :1], rest[:, :, :1])[:, :, 0].T)


_cert_miller_jit = jax.jit(_cert_miller)


class CertPairing:
    """A committee's line tables on the device and the one program that
    multiplies a certificate's pairs over them.

    ``keys`` are the committee's BLS public keys (G2) by signer index;
    the table holds one more row, -G2's, for the aggregate's pair. A
    check holds ``rows`` = 1 + the least power of two >= n pairs."""

    def __init__(self, keys: Sequence[object]):
        self.n = len(keys)
        self.rows = 1 + (1 << max(0, (self.n - 1).bit_length()))
        table, self.usable = line_table(list(keys) + [bls.g2_neg(bls.G2_GEN)])
        self.table_bytes = table.nbytes
        self._tables = jax.device_put(table)

    def product(self, agg, signers: Sequence[int], hx: np.ndarray, hy: np.ndarray):
        """The Miller product of ``[(agg, -G2)] + [(H_i, pk_signer_i)]`` as
        a host Fp12 (conjugated for the negative x, as the oracle's), for
        ``bls.final_exponentiation``; None where a key cannot use its
        table. ``hx``, ``hy`` are the H_i's canonical limbs, one row per
        signer."""
        k = len(signers)
        if not self.usable[self.n] or not all(self.usable[s] for s in signers):
            return None
        idx = np.zeros(self.rows, dtype=np.int32)
        idx[0] = self.n
        idx[1 : 1 + k] = signers
        xp = np.zeros((self.rows, f.LIMBS), dtype=np.int32)
        yp = np.zeros((self.rows, f.LIMBS), dtype=np.int32)
        xp[0], yp[0] = f.to_limbs_bulk([agg[0] % P_INT, agg[1] % P_INT])
        xp[1 : 1 + k], yp[1 : 1 + k] = hx, hy
        valid = np.zeros(self.rows, dtype=bool)
        valid[: 1 + k] = True
        out = np.asarray(_cert_miller_jit(self._tables, idx, xp, yp, valid))
        res = _fp12_unflat([f.from_limbs(out[j]) for j in range(12)])
        return bls.fp12_conj(res) if bls.X_PARAM < 0 else res
