"""GF(2^255 - 19) arithmetic in int32 limbs — the TPU field layer.

The north star (BASELINE.json) calls for "vmap'd Ed25519 ... batch-verify
... one DAG round per device dispatch". The reference has no crypto at all
(SURVEY.md D10); this module is the field underneath the device-side group
arithmetic in :mod:`dag_rider_tpu.ops.curve`.

Design (SURVEY.md §7 "hard parts (a)"): TPUs have no widening 64-bit
multiply, so field elements are **22 little-endian limbs of 12 bits held in
int32** (radix 2^12, 264 bits of headroom over the 255-bit field):

- limbs are *signed*: subtraction is plain limb-wise ``a - b`` with no
  added bias, and arithmetic shifts make carry steps sign-correct.
- "reduced" invariant (what every public op accepts and returns):
  ``|limb0| < 2^14`` and ``|limb_i| < 2^13`` for i >= 1. With 12-bit
  radix this keeps every schoolbook product column below
  2 * 2^27 + 20 * 2^26 < 2^31 — the whole multiply fits int32 with no
  widening multiply.
- carries propagate in *parallel* (all limbs shift simultaneously, a
  constant number of steps) — every step is a handful of elementwise ops
  on the whole [batch, limbs] array, instead of a 22-deep sequential
  chain. Exact sequential passes are used only inside
  :func:`canonical`, where strict uniqueness is required.
- multiplication is schoolbook via one outer product + a pad/reshape
  anti-diagonal sum (static shapes, no gathers), then the high columns
  fold through 2^255 == 19 (mod p).

Everything is shape-polymorphic over leading batch dims and jit/vmap safe;
no Python control flow depends on traced values.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

# --- representation parameters --------------------------------------------

LIMB_BITS = 12
LIMBS = 22  # 22 * 12 = 264 >= 255
LIMB_MASK = (1 << LIMB_BITS) - 1
P_INT = 2**255 - 19

# 2^255 == 19 (mod p). Limb 21 spans bits 252..263, so one unit of the
# virtual "limb 22" (weight 2^264 = 2^255 * 2^9) folds to 19 * 2^9 at limb 0.
TOP_FOLD = 19 << 9  # 9728


def to_limbs(x: int) -> np.ndarray:
    """Host helper: python int in [0, 2^264) -> limb vector (int32[22])."""
    if not 0 <= x < 2**264:
        raise ValueError("out of limb range")
    out = np.zeros(LIMBS, dtype=np.int32)
    for i in range(LIMBS):
        out[i] = x & LIMB_MASK
        x >>= LIMB_BITS
    return out


def from_limbs(limbs) -> int:
    """Host helper: limb vector -> python int (signed limbs allowed)."""
    arr = np.asarray(limbs, dtype=np.int64)
    val = 0
    for i in reversed(range(arr.shape[-1])):
        val = (val << LIMB_BITS) + int(arr[..., i])
    return val


def bytes_to_limbs(data: bytes) -> np.ndarray:
    """32 little-endian bytes -> limb vector. Values >= p are representable;
    callers needing canonicity check it explicitly (RFC 8032 decoding)."""
    return to_limbs(int.from_bytes(data, "little"))


# Module constants in limb form (captured as jnp constants under jit).
P_LIMBS = to_limbs(P_INT)
# 2^14 * p: a multiple of p, every limb scaled by 2^14 (values < 2^26).
# Added inside canonical() to force any reduced (possibly negative) value
# positive before exact normalization: |reduced value| < 2^13 * 2^253 <
# 2^266 < 2^14 * p.
BIG_P = (P_LIMBS.astype(np.int64) << 14).astype(np.int32)

D_INT = (-121665 * pow(121666, P_INT - 2, P_INT)) % P_INT
D2_INT = (2 * D_INT) % P_INT
SQRT_M1_INT = pow(2, (P_INT - 1) // 4, P_INT)

ZERO = np.zeros(LIMBS, dtype=np.int32)
ONE = to_limbs(1)
D = to_limbs(D_INT)
D2 = to_limbs(D2_INT)
SQRT_M1 = to_limbs(SQRT_M1_INT)


# --- carry propagation -----------------------------------------------------


def _carry_step(x: jax.Array) -> jax.Array:
    """One parallel carry step with the 2^255 == 19 fold at the top limb.

    Arithmetic shift + mask decompose v = (v >> 12) * 4096 + (v & 0xFFF)
    exactly for signed v, so negative limbs carry correctly.
    """
    c = x >> LIMB_BITS
    low = x & LIMB_MASK
    shifted = jnp.concatenate([c[..., -1:] * TOP_FOLD, c[..., :-1]], axis=-1)
    return low + shifted


def carry(x: jax.Array, steps: int = 2) -> jax.Array:
    """Propagate carries back to the reduced invariant.

    Two steps suffice for |limbs| < 2^15 (add/sub results); three for
    |limbs| < 2^26 (scaled values). The result satisfies |limb0| < 2^14
    (it absorbs the top fold, which is < 9728 + 4096) and
    |limb_i| < 2^13 elsewhere.
    """
    for _ in range(steps):
        x = _carry_step(x)
    return x


# --- ring ops --------------------------------------------------------------


def add(a: jax.Array, b: jax.Array) -> jax.Array:
    """a + b (mod p), reduced."""
    return carry(a + b, steps=2)


def sub(a: jax.Array, b: jax.Array) -> jax.Array:
    """a - b (mod p), reduced. Signed limbs: no bias needed."""
    return carry(a - b, steps=2)


def neg(a: jax.Array) -> jax.Array:
    return carry(-a, steps=2)


_NCOLS = 46  # 43 product columns + headroom so no carry is ever dropped


def _columns(a: jax.Array, b: jax.Array) -> jax.Array:
    """Schoolbook product columns c[k] = sum_{i+j=k} a_i b_j -> [..., 46].

    Shift-accumulate: 22 statically-sliced multiply-adds into one
    [..., 46] accumulator. Ties the outer-product + pad/reshape
    anti-diagonal formulation in on-chip speed but peaks at 2x the input
    footprint instead of 22x (the [..., 22, 46] intermediate made wide
    batched ops HBM-traffic-bound and OOM'd the 8k-sig merged dispatch —
    round 3). Static shapes; no gathers.
    """
    batch = jnp.broadcast_shapes(a.shape[:-1], b.shape[:-1])
    a = jnp.broadcast_to(a, (*batch, LIMBS))
    b = jnp.broadcast_to(b, (*batch, LIMBS))
    c = jnp.zeros((*batch, _NCOLS), dtype=a.dtype)
    for i in range(LIMBS):
        c = c.at[..., i : i + LIMBS].add(a[..., i : i + 1] * b)
    return c


def mul(a: jax.Array, b: jax.Array) -> jax.Array:
    """a * b (mod p), reduced. Inputs must be reduced."""
    c = _columns(a, b)  # 46 columns, |col| < 2^31, cols 44+ start at 0
    # Normalize columns before folding (the fold multiplies by 19 * 2^9 so
    # columns must be small first). Two parallel steps bring |col| below
    # 2^12.1; carries spill into columns 44/45 and none fall off the end
    # (col 45 stays < 4, its own carry is 0).
    for _ in range(2):
        cc = c >> LIMB_BITS
        c = (c & LIMB_MASK) + jnp.concatenate(
            [jnp.zeros_like(cc[..., :1]), cc[..., :-1]], axis=-1
        )
    lo = c[..., :LIMBS]
    hi = c[..., LIMBS : LIMBS + LIMBS]  # cols 22..43: weight 19 * 2^(12j+9)
    t = hi * 19  # |t| < 2^17
    # t * 2^9 split across two limbs: low 3 bits of t stay at offset 9,
    # the rest moves one limb up.
    lo = lo + ((t & 0x7) << 9)
    up = t >> 3
    lo = lo + jnp.concatenate(
        [jnp.zeros_like(up[..., :1]), up[..., :-1]], axis=-1
    )
    # up[21] lands at limb 22 (weight 2^264 == 19 * 2^9): fold once more.
    t2 = up[..., -1] * 19  # |t2| < 2^18
    lo = lo.at[..., 0].add((t2 & 0x7) << 9)
    lo = lo.at[..., 1].add(t2 >> 3)
    # cols 44/45: weights 2^528 == 361 * 2^18 and 2^540 == 361 * 2^30
    # (mod p), both exactly 2^6 * 361 = 23104 times a limb weight.
    lo = lo.at[..., 1].add(c[..., 44] * 23104)
    lo = lo.at[..., 2].add(c[..., 45] * 23104)
    return carry(lo, steps=3)


def square(a: jax.Array) -> jax.Array:
    return mul(a, a)


def nsquare(a: jax.Array, n: int) -> jax.Array:
    """a^(2^n) via fori_loop (keeps the HLO small for long chains)."""
    if n <= 4:
        for _ in range(n):
            a = square(a)
        return a
    return jax.lax.fori_loop(0, n, lambda _, x: square(x), a)


def mul_small(a: jax.Array, k: int) -> jax.Array:
    """a * k for python int 0 <= k < 2^12."""
    return carry(a * jnp.int32(k), steps=3)


# --- exponentiation chains (ref10-structure, public algorithm) -------------


def pow22523(z: jax.Array) -> jax.Array:
    """z^(2^252 - 3) (mod p) — the exponent of RFC 8032 §5.1.3 square-root
    decompression: sqrt candidate x = u v^3 (u v^7)^(2^252 - 3)."""
    t0 = square(z)                     # 2
    t1 = mul(z, nsquare(t0, 2))        # 9
    t0 = mul(t0, t1)                   # 11
    t0 = mul(t1, square(t0))           # 31 = 2^5 - 1
    t0 = mul(nsquare(t0, 5), t0)       # 2^10 - 1
    t1 = mul(nsquare(t0, 10), t0)      # 2^20 - 1
    t2 = mul(nsquare(t1, 20), t1)      # 2^40 - 1
    t1 = mul(nsquare(t2, 10), t0)      # 2^50 - 1
    t2 = mul(nsquare(t1, 50), t1)      # 2^100 - 1
    t3 = mul(nsquare(t2, 100), t2)     # 2^200 - 1
    t1 = mul(nsquare(t3, 50), t1)      # 2^250 - 1
    return mul(nsquare(t1, 2), z)      # 2^252 - 3


def invert(z: jax.Array) -> jax.Array:
    """z^(p-2) = z^(2^255 - 21) (mod p); maps 0 -> 0."""
    t0 = square(z)                     # 2
    t1 = mul(z, nsquare(t0, 2))        # 9
    t0m = mul(t0, t1)                  # 11
    t1 = mul(t1, square(t0m))          # 31 = 2^5 - 1
    t1 = mul(nsquare(t1, 5), t1)       # 2^10 - 1
    t2 = mul(nsquare(t1, 10), t1)      # 2^20 - 1
    t3 = mul(nsquare(t2, 20), t2)      # 2^40 - 1
    t2 = mul(nsquare(t3, 10), t1)      # 2^50 - 1
    t3 = mul(nsquare(t2, 50), t2)      # 2^100 - 1
    t4 = mul(nsquare(t3, 100), t3)     # 2^200 - 1
    t2 = mul(nsquare(t4, 50), t2)      # 2^250 - 1
    return mul(nsquare(t2, 5), t0m)    # 2^255 - 32 + 11 = 2^255 - 21


# --- canonicalization / predicates ----------------------------------------


def _seq_carry_fold(x: jax.Array) -> jax.Array:
    """Exact sequential carry pass (22 steps) + fold of all bits >= 255.

    Unlike the parallel :func:`carry`, this cannot leave a ripple (a chain
    of 0xFFF limbs propagating one place per step), so a few passes give
    strictly normalized limbs — required before value comparison.
    """
    carry_in = jnp.zeros_like(x[..., 0])
    limbs = []
    for i in range(LIMBS):
        v = x[..., i] + carry_in
        limbs.append(v & LIMB_MASK)
        carry_in = v >> LIMB_BITS
    out = jnp.stack(limbs, axis=-1)
    out = out.at[..., 0].add(carry_in * TOP_FOLD)
    hi = out[..., LIMBS - 1] >> 3  # bits 255..263, weight 2^255 == 19
    out = out.at[..., LIMBS - 1].set(out[..., LIMBS - 1] & 0x7)
    out = out.at[..., 0].add(hi * 19)
    return out


def canonical(x: jax.Array) -> jax.Array:
    """Unique representative in [0, p), limbs strictly in [0, 2^12).

    BIG_P (= 2^14 * p > any reduced magnitude) forces the value positive;
    three exact passes normalize to value < 2^255 with strict limbs; then
    x >= p is decided by whether x + 19 reaches bit 255 (for x in
    [0, 2^255): x >= p  <=>  x + 19 >= 2^255, and
    x - p == (x + 19) - 2^255).
    """
    x = x + jnp.asarray(BIG_P)
    for _ in range(3):
        x = _seq_carry_fold(x)
    t = x.at[..., 0].add(19)
    carry_in = jnp.zeros_like(t[..., 0])
    limbs = []
    for i in range(LIMBS):
        v = t[..., i] + carry_in
        limbs.append(v & LIMB_MASK)
        carry_in = v >> LIMB_BITS
    t = jnp.stack(limbs, axis=-1)
    ge_p = (t[..., LIMBS - 1] >> 3) > 0  # bit 255 set => x >= p
    t = t.at[..., LIMBS - 1].set(t[..., LIMBS - 1] & 0x7)  # == x - p
    return jnp.where(ge_p[..., None], t, x)


def is_zero(x: jax.Array) -> jax.Array:
    """x == 0 (mod p) -> bool[...]. Input must be reduced."""
    return jnp.all(canonical(x) == 0, axis=-1)


def eq(a: jax.Array, b: jax.Array) -> jax.Array:
    return is_zero(sub(a, b))


def parity(x: jax.Array) -> jax.Array:
    """Low bit of the canonical representative (RFC 8032 sign bit)."""
    return canonical(x)[..., 0] & 1


def select(cond: jax.Array, a: jax.Array, b: jax.Array) -> jax.Array:
    """cond ? a : b, limb-wise; cond is bool[...] broadcast over limbs."""
    return jnp.where(cond[..., None], a, b)
