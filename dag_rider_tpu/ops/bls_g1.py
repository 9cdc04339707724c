"""Batched G1 hash-map powers and scalar ladders on the field381 limb
kernels — the `DAGRIDER_CERT_SIGN=device` lane (ISSUE 12 tentpole 1).

Same split as the round-3 verifier prep: SHA challenge hashing stays
per-row on the host (`crypto/bls12381._hash_candidate_x`), while the two
heavy batch primitives run as jitted lax.scan ladders over
:mod:`dag_rider_tpu.ops.field381` int32 limbs:

- :func:`pow_p_batch` — shared-exponent powering (the try-and-increment
  square root y2^((p+1)/4) and the affine-conversion inverse z^(p-2));
- :func:`g1_ladder_batch` — left-to-right Jacobian double-and-add over
  all rows at once, transcribing the host oracle's `_jac_double` /
  `_jac_madd` formulas limb-for-limb.

Exactness is the contract: every limb op is exact mod-p arithmetic, so
the ladder result equals the oracle's for every reachable input. The one
branch not worth a device implementation — a mixed addition hitting
H == 0 (the accumulator meeting ±base mid-ladder, possible only for
tiny-order non-torsion candidates) — raises a per-row fallback flag and
the caller re-signs that row on the host, preserving byte-identity.

Like the sharded MSM, this lane is about where the work runs, not local
wall-clock: on this 1-core CPU host the limb kernels lose to the cffi
native lane (round 15); the lane exists so committee-scale
signing has a real accelerator story next to `ops/bls_msm.py`.
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from dag_rider_tpu.ops import field381 as f

P = f.P_INT


def _jac_double(X, Y, Z):
    """EFD dbl-2009-l, limb transcription of the oracle's _jac_double.
    All-zero (X, Y, Z) — the identity encoding — is a fixed point."""
    A = f.mul(X, X)
    B = f.mul(Y, Y)
    C = f.mul(B, B)
    t = f.add(X, B)
    D = f.mul_small(f.sub(f.sub(f.mul(t, t), A), C), 2)
    E = f.mul_small(A, 3)
    X3 = f.sub(f.mul(E, E), f.mul_small(D, 2))
    Y3 = f.sub(f.mul(E, f.sub(D, X3)), f.mul_small(C, 8))
    Z3 = f.mul_small(f.mul(Y, Z), 2)
    return X3, Y3, Z3


def _jac_madd(X, Y, Z, x2, y2):
    """EFD madd-2007-bl main branch + the H == 0 detection the step
    function turns into a fallback flag."""
    Z1Z1 = f.mul(Z, Z)
    U2 = f.mul(x2, Z1Z1)
    S2 = f.mul(f.mul(y2, Z), Z1Z1)
    H = f.sub(U2, X)
    r = f.mul_small(f.sub(S2, Y), 2)
    h_zero = f.is_zero(H)
    HH = f.mul(H, H)
    I = f.mul_small(HH, 4)
    J = f.mul(H, I)
    V = f.mul(X, I)
    X3 = f.sub(f.sub(f.mul(r, r), J), f.mul_small(V, 2))
    Y3 = f.sub(f.mul(r, f.sub(V, X3)), f.mul_small(f.mul(Y, J), 2))
    t = f.add(Z, H)
    Z3 = f.sub(f.sub(f.mul(t, t), Z1Z1), HH)
    return X3, Y3, Z3, h_zero


@functools.lru_cache(maxsize=8)
def _pow_fn(nbits: int):
    """Jitted shared-exponent power scan; exponent bits arrive as data
    (top bit excluded — the accumulator starts at the base)."""

    @jax.jit
    def run(base, bits):
        def body(acc, b):
            acc = f.mul(acc, acc)
            acc = f.select(b != 0, f.mul(acc, base), acc)
            return acc, None

        acc, _ = jax.lax.scan(body, base, bits)
        return f.canonical(acc)

    return run


@functools.lru_cache(maxsize=8)
def _ladder_fn(nbits: int):
    """Jitted batched Jacobian ladder over per-row scalar bit columns."""

    @jax.jit
    def run(px, py, bits):
        n = px.shape[0]
        one = jnp.broadcast_to(jnp.asarray(f.ONE), px.shape)

        def body(carry, b):
            X, Y, Z, inf, fb = carry
            X, Y, Z = _jac_double(X, Y, Z)
            Xm, Ym, Zm, h_zero = _jac_madd(X, Y, Z, px, py)
            bit = b != 0
            fb = fb | (bit & ~inf & h_zero)
            take_init = bit & inf
            take_madd = bit & ~inf
            X = f.select(take_init, px, f.select(take_madd, Xm, X))
            Y = f.select(take_init, py, f.select(take_madd, Ym, Y))
            Z = f.select(take_init, one, f.select(take_madd, Zm, Z))
            inf = inf & ~bit
            return (X, Y, Z, inf, fb), None

        zero = jnp.zeros_like(px)
        inf0 = jnp.ones((n,), dtype=bool)
        fb0 = jnp.zeros((n,), dtype=bool)
        (X, Y, Z, inf, fb), _ = jax.lax.scan(
            body, (zero, zero, zero, inf0, fb0), bits
        )
        # affine conversion stays on device: one batched z^(p-2) pass
        zbits = jnp.asarray(
            np.array(
                [(P - 2) >> k & 1 for k in range((P - 2).bit_length() - 2, -1, -1)],
                dtype=np.int32,
            )
        )

        def inv_body(acc, b):
            acc = f.mul(acc, acc)
            acc = f.select(b != 0, f.mul(acc, Z), acc)
            return acc, None

        zi, _ = jax.lax.scan(inv_body, Z, zbits)
        zi2 = f.mul(zi, zi)
        xa = f.canonical(f.mul(X, zi2))
        ya = f.canonical(f.mul(Y, f.mul(zi2, zi)))
        return xa, ya, inf, fb

    return run


def _bit_columns(scalars: Sequence[int]) -> Tuple[np.ndarray, int]:
    """MSB-first bit columns [nbits, n] over the max scalar width (leading
    zeros keep short rows on the identity — exact, like the oracle)."""
    nbits = max(int(s).bit_length() for s in scalars)
    nbytes = (nbits + 7) // 8
    raw = np.frombuffer(
        b"".join(int(s).to_bytes(nbytes, "big") for s in scalars),
        dtype=np.uint8,
    ).reshape(len(scalars), nbytes)
    bits = np.unpackbits(raw, axis=1)[:, nbytes * 8 - nbits :]
    return np.ascontiguousarray(bits.T).astype(np.int32), nbits


def pow_p_batch(values: Sequence[int], exp: int) -> List[int]:
    """[v^exp mod p for v in values] on the limb kernels."""
    if not values:
        return []
    if exp.bit_length() < 2:
        return [pow(v % P, exp, P) for v in values]
    base = jnp.asarray(np.stack([f.to_limbs(v % P) for v in values]))
    ebits = np.array(
        [exp >> k & 1 for k in range(exp.bit_length() - 2, -1, -1)],
        dtype=np.int32,
    )
    out = _pow_fn(exp.bit_length())(base, jnp.asarray(ebits))
    out = np.asarray(out)
    return [f.from_limbs(out[i]) for i in range(out.shape[0])]


def g1_ladder_batch(
    scalars: Sequence[int], points: Sequence[Tuple[int, int]]
) -> Tuple[List[Optional[Tuple[int, int]]], List[bool]]:
    """Batched [k_i]P_i over E(Fp); (results, fallback_mask) with None for
    identity results and flagged rows for the host to re-sign."""
    n = len(scalars)
    if n == 0:
        return [], []
    bits, nbits = _bit_columns(scalars)
    px = jnp.asarray(np.stack([f.to_limbs(p[0]) for p in points]))
    py = jnp.asarray(np.stack([f.to_limbs(p[1]) for p in points]))
    xa, ya, inf, fb = _ladder_fn(nbits)(px, py, jnp.asarray(bits))
    xa, ya = np.asarray(xa), np.asarray(ya)
    inf, fb = np.asarray(inf), np.asarray(fb)
    results: List[Optional[Tuple[int, int]]] = []
    for i in range(n):
        if inf[i] or fb[i]:
            results.append(None)
        else:
            results.append((f.from_limbs(xa[i]), f.from_limbs(ya[i])))
    return results, [bool(x) for x in fb]
