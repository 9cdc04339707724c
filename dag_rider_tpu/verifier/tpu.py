"""TPU Verifier backend — the north-star device path.

BASELINE.json: "whole-round vertex batches ... vmap'd Ed25519 batch-verify
... one DAG round per device dispatch. Target: >= 50k vertex-signatures
verified/sec on a single v5e chip at n=256, with CPU-vs-TPU commit order
byte-identical."

Work split (SURVEY.md §7 hard part (b) — all *ordering* stays host-side,
the device returns only accept bits):

- host: byte parsing, SHA-512 challenge scalars (k), the s < L
  malleability check, y < p canonicity checks, public-key decompression
  (cached per KeyRegistry at construction), batch padding;
- device: point decompression of R, [s]B from the fixed-base comb table,
  windowed [k]A, the group equation [s]B == R + [k]A — all over the
  int32 limb field (ops/field.py) in one jitted dispatch per DAG round.

Batches are padded to power-of-two buckets so XLA compiles a handful of
program shapes, then results are sliced back. The accept mask is a pure
function of (vertex bytes, registry) — identical to CPUVerifier's, which
makes CPU-vs-TPU commit order byte-identical (tests/test_verifier_tpu.py).
"""

from __future__ import annotations

import functools
import gc
import hashlib
import time
from typing import List, NamedTuple, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from dag_rider_tpu import config, obs
from dag_rider_tpu.core.types import Vertex
from dag_rider_tpu.crypto import ed25519
from dag_rider_tpu.ops import curve, field
from dag_rider_tpu.utils.jaxcache import device_platform
from dag_rider_tpu.verifier.base import (
    KeyRegistry,
    Verifier,
    VerifierCompileError,
)
from dag_rider_tpu.verifier.prep import PrepEngine

_MIN_BUCKET = 16
#: columns of the u8 transfer: 64 + 64 nibble digits of s and k, then
#: R's sign bit, prevalid, a_valid (see _device_verify_comb)
_U8_COLS = 131


def _native_enabled() -> bool:
    """Native challenge hashing on by default; DAGRIDER_NATIVE=0 (or
    false/no/off) selects the hashlib path."""
    return config.env_flag("DAGRIDER_NATIVE")


def _bucket(n: int) -> int:
    b = _MIN_BUCKET
    while b < n:
        b *= 2
    return b


_BIT_POW = (1 << np.arange(field.LIMB_BITS, dtype=np.int32)).astype(np.int32)


def bytes_to_limbs_batch(raw: np.ndarray) -> np.ndarray:
    """uint8[B, 32] little-endian -> int32[B, 22] 12-bit limbs, vectorized.

    Only the low 255 bits are kept (bit 255 is the sign bit in encodings
    that carry one; callers strip it from the byte array first if needed).
    One unpackbits + one matvec — no Python loop over bit positions.
    """
    bits = np.unpackbits(raw, axis=-1, bitorder="little")  # [B, 256]
    pad = field.LIMBS * field.LIMB_BITS - bits.shape[-1]  # 264 - 256
    bits = np.pad(bits, [(0, 0)] * (bits.ndim - 1) + [(0, pad)])
    grouped = bits.reshape(*raw.shape[:-1], field.LIMBS, field.LIMB_BITS)
    return grouped.astype(np.int32) @ _BIT_POW


_L_BYTES_LE = np.frombuffer(
    ed25519.L.to_bytes(32, "little"), dtype=np.uint8
)
_P_BYTES_LE = np.frombuffer(field.P_INT.to_bytes(32, "little"), dtype=np.uint8)


def _lex_lt(rows: np.ndarray, bound_le: np.ndarray) -> np.ndarray:
    """Batched ``int(row, little) < int(bound, little)`` over uint8[B, 32].

    Big-endian lexicographic compare: the most significant differing byte
    decides; equal rows are not less-than.
    """
    be = rows[:, ::-1]
    bound_be = bound_le[::-1]
    diff = be != bound_be
    first = np.argmax(diff, axis=1)  # 0 when no byte differs
    rows_idx = np.arange(be.shape[0])
    return diff.any(axis=1) & (be[rows_idx, first] < bound_be[first])


def scalar_to_nibbles(x: int) -> np.ndarray:
    """256-bit int -> int32[64] little-endian 4-bit windows."""
    out = np.zeros(64, dtype=np.int32)
    for i in range(64):
        out[i] = (x >> (4 * i)) & 0xF
    return out


def nibbles_batch(raw: np.ndarray) -> np.ndarray:
    """uint8[B, 32] little-endian scalar bytes -> int32[B, 64] nibble
    windows, vectorized (nib[2i] = byte[i] & 0xF, nib[2i+1] = byte[i] >> 4)."""
    out = np.empty((*raw.shape[:-1], 64), dtype=np.int32)
    out[..., 0::2] = raw & 0xF
    out[..., 1::2] = raw >> 4
    return out


@functools.partial(jax.jit, static_argnames=())
def _device_verify(
    s_nibbles: jax.Array,
    k_nibbles: jax.Array,
    a_x: jax.Array,
    a_y: jax.Array,
    a_t: jax.Array,
    a_valid: jax.Array,
    r_y: jax.Array,
    r_sign: jax.Array,
    prevalid: jax.Array,
) -> jax.Array:
    one = jnp.broadcast_to(jnp.asarray(field.ONE), a_x.shape)
    a_point = (a_x, a_y, one, a_t)
    return curve.verify_core(
        s_nibbles, k_nibbles, a_point, a_valid, r_y, r_sign, prevalid
    )


@functools.partial(jax.jit, static_argnames=("impl",))
def _device_verify_comb(
    u8: jax.Array,
    i32: jax.Array,
    key_tables: jax.Array,
    b_table: jax.Array,
    impl: str = "jnp",
) -> jax.Array:
    """Unpack the two packed transfer arrays (see _prepare comb mode) and
    run the comb verify core."""
    from dag_rider_tpu.ops import comb

    s_nibbles = u8[:, :64].astype(jnp.int32)
    k_nibbles = u8[:, 64:128].astype(jnp.int32)
    r_sign = u8[:, 128].astype(jnp.int32)
    prevalid = u8[:, 129].astype(bool)
    a_valid = u8[:, 130].astype(bool)
    key_idx = i32[:, 0]
    r_y = i32[:, 1:]
    return comb.comb_verify_core(
        s_nibbles,
        k_nibbles,
        key_idx,
        key_tables,
        b_table,
        a_valid,
        r_y,
        r_sign,
        prevalid,
        impl=impl,
    )


_B_TABLE_CACHED: Optional[np.ndarray] = None


def _b_table_cached() -> np.ndarray:
    global _B_TABLE_CACHED
    if _B_TABLE_CACHED is None:
        from dag_rider_tpu.ops import comb

        _B_TABLE_CACHED = comb.base_table_xyzt()
    return _B_TABLE_CACHED


def _comb_impl(size: int) -> str:
    """Pallas kernels on the TPU backend for lane-aligned batches;
    portable jnp everywhere else. Both are bit-identical — this is purely
    a speed selection (the jnp tree is memory-bound on HLO temps; the
    kernels do one HBM pass per operand)."""
    if not config.env_flag("DAGRIDER_PALLAS_GROUP"):
        return "jnp"
    if size >= 128 and jax.default_backend() == "tpu":
        return "pallas"
    return "jnp"


class PreppedBatch(NamedTuple):
    """Opaque handle between the prep_batch/dispatch_prepped halves of a
    dispatch: the device-ready transfer arrays (normally views of a
    staging-ring slot), the padded size, the real row count, and the
    prep wall seconds (booked at dispatch time, on the dispatching
    thread)."""

    args: tuple
    size: int
    count: int
    prep_s: float


class TPUVerifier(Verifier):
    """Batched Ed25519 verification on the accelerator.

    Runs on jax's default backend. The CPU backend is accepted only when
    ``JAX_PLATFORMS`` names ``cpu`` (the tests do); a CPU backend jax fell
    back to because the TPU failed to initialise raises at construction
    (utils/jaxcache.device_platform).

    Two ways to run it. Raw, ``fixed_bucket`` unset: every batch pads to
    its own power-of-two bucket and each new shape compiles on first
    use — the tests. Serving: :meth:`warmup` fixes one bucket and
    compiles its program, and from then on every batch is padded or
    chunked to that shape, so nothing compiles after start-up.

    One program a bucket — prep, dispatch, resolve — and no window: a
    batch larger than the bucket is cut at it and each chunk dispatched
    and resolved in turn, and a fault raises. The in-flight window with
    its containment is :class:`~dag_rider_tpu.verifier.pipeline.
    VerifierPipeline`, which every stack that contains faults holds over
    this class (a node, the simulator); the sidecar turns a raise into a
    failed RPC, which its client answers fail-closed.
    """

    def __init__(self, registry: KeyRegistry, comb: bool = True):
        """``comb=True`` uses the fixed-key comb path (ops/comb.py):
        per-key tables built on device once, ~2.5x fewer field muls per
        signature, identical accept masks. ``comb=False`` is the
        original windowed path — the tests' differential oracle."""
        self._comb = comb
        if _native_enabled():
            # build/load now: a broken toolchain fails construction,
            # not a prep inside the fault-contained window
            from dag_rider_tpu.utils import native

            native.load()
        #: platform / device_kind of the backend every dispatch lands on
        self.platform = device_platform()
        self.device_kind = jax.devices()[0].device_kind
        self._key_tables = None  # device tables, built lazily
        # compiled executables keyed (size, impl) — see _program()
        self._aot: dict = {}
        #: lower+compile seconds of each program in _aot, same keys
        self.compile_s: dict = {}
        #: seconds the one-off device comb-table build took
        self.table_build_s = 0.0
        #: (padded size, impl) of the most recent dispatch
        self.last_size = 0
        self.last_impl = ""
        # reusable host staging rings per padded size — see _stage()
        self._staging: dict = {}
        self._staging_idx: dict = {}
        # two dispatches in flight (a direct user of dispatch_batch /
        # resolve_batch) and two preps ahead; a window holder asks for
        # more through cover_in_flight()
        self._ring_slots = 4
        # parallel host-prep engine (verifier/prep.py), built lazily by
        # _prep() so a prep_workers override set after construction
        # still takes effect on first use
        self._prep_engine: Optional[PrepEngine] = None
        #: cumulative seconds spent in warmup()'s AOT lower+compile
        self.warmup_compile_s = 0.0
        self.registry = registry
        n = registry.n
        self._a_x = np.zeros((n, field.LIMBS), dtype=np.int32)
        self._a_y = np.zeros((n, field.LIMBS), dtype=np.int32)
        self._a_t = np.zeros((n, field.LIMBS), dtype=np.int32)
        self._a_valid = np.zeros(n, dtype=bool)
        for i, pk in enumerate(registry.public_keys):
            pt = ed25519.point_decompress(pk) if len(pk) == 32 else None
            if pt is None:
                continue
            x, y, _, t = pt  # Z == 1 from decompress
            self._a_x[i] = field.to_limbs(x)
            self._a_y[i] = field.to_limbs(y)
            self._a_t[i] = field.to_limbs(t)
            self._a_valid[i] = True

    # -- host-side batch preparation ------------------------------------

    def _prep_block(
        self,
        vertices: Sequence[Vertex],
        lo: int,
        hi: int,
        comb: bool,
        dest: Tuple[np.ndarray, ...],
    ) -> None:
        # Vectorized host prep (round-2 VERDICT weak #3: the per-vertex
        # Python loop must clear ~50k iterations/s at the north-star rate).
        # Structural checks, the s < L malleability compare and the
        # r_y < p canonicity compare are batched numpy; only the SHA-512
        # challenge hashing walks the batch (variable-length messages).
        #
        # Operates on rows [lo, hi) of one padded dispatch and writes the
        # finished rows straight into ``dest``'s block offsets. Every
        # computation here is ROW-LOCAL — parsing, the lexicographic
        # bound compares, the per-row challenge hash, limb packing — so a
        # row-block partition of [0, size) is byte-identical to one
        # full-range call: the invariant the parallel prep engine
        # (verifier/prep.py) rides. Rows >= len(vertices) are padding:
        # structurally invalid and zero-filled, exactly as serial prep
        # pads them. The numpy kernels and the native challenge_batch
        # release the GIL, so concurrent blocks genuinely overlap.
        rows = hi - lo
        sig_raw = np.zeros((rows, 64), dtype=np.uint8)
        pk_raw = np.zeros((rows, 32), dtype=np.uint8)
        k_raw = np.zeros((rows, 32), dtype=np.uint8)
        src = np.zeros(rows, dtype=np.int64)
        structural = np.zeros(rows, dtype=bool)
        msgs: List[bytes] = []
        for j in range(lo, min(hi, len(vertices))):
            v = vertices[j]
            jl = j - lo
            pk = self.registry.key_of(v.source)
            sig = v.signature
            if pk is None or sig is None or len(sig) != 64 or len(pk) != 32:
                msgs.append(b"")
                continue
            sig_raw[jl] = np.frombuffer(sig, dtype=np.uint8)
            pk_raw[jl] = np.frombuffer(pk, dtype=np.uint8)
            src[jl] = v.source
            structural[jl] = True
            msgs.append(v.signing_bytes())
        s_raw = sig_raw[:, 32:]
        r_raw = sig_raw[:, :32].copy()
        # s < L, batched: big-endian lexicographic compare against L.
        s_lt_l = _lex_lt(s_raw, _L_BYTES_LE)
        # r_y < p, batched (sign bit masked off first).
        r_sign = (r_raw[:, 31] >> 7).astype(np.int32)
        r_raw[:, 31] &= 0x7F
        r_lt_p = _lex_lt(r_raw, _P_BYTES_LE)
        prevalid = structural & s_lt_l & r_lt_p
        # k = SHA-512(R || A || M) mod L per valid row — one native C++
        # batch call (utils/native.py; differential-tested against the
        # hashlib path, which DAGRIDER_NATIVE=0 selects and which stays
        # the oracle). Both are per-row pure functions, so a per-block
        # call hashes the same bytes a whole-batch call would.
        idx = np.flatnonzero(prevalid)
        if len(idx) and _native_enabled():
            from dag_rider_tpu.utils import native

            k_raw[idx] = native.challenge_batch(
                sig_raw[idx, :32], pk_raw[idx], [msgs[j] for j in idx]
            )
        else:
            for j in idx:
                k = (
                    int.from_bytes(
                        hashlib.sha512(
                            sig_raw[j, :32].tobytes()
                            + pk_raw[j].tobytes()
                            + msgs[j]
                        ).digest(),
                        "little",
                    )
                    % ed25519.L
                )
                k_raw[j] = np.frombuffer(
                    k.to_bytes(32, "little"), dtype=np.uint8
                )
        r_y_limbs = bytes_to_limbs_batch(r_raw)
        if comb:
            u8, i32 = dest
            u8 = u8[lo:hi]
            i32 = i32[lo:hi]
            u8[:, :64] = nibbles_batch(np.where(prevalid[:, None], s_raw, 0))
            u8[:, 64:128] = nibbles_batch(k_raw)
            u8[:, 128] = r_sign
            u8[:, 129] = prevalid
            u8[:, 130] = self._a_valid[src] & prevalid
            i32[:, 0] = src
            i32[:, 1:] = r_y_limbs
            return
        s_nib, k_nib, a_x, a_y, a_t, valid, r_y, r_sg, pv = dest
        s_nib[lo:hi] = nibbles_batch(np.where(prevalid[:, None], s_raw, 0))
        k_nib[lo:hi] = nibbles_batch(k_raw)
        a_x[lo:hi] = self._a_x[src]
        a_y[lo:hi] = self._a_y[src]
        a_t[lo:hi] = self._a_t[src]
        valid[lo:hi] = self._a_valid[src] & prevalid
        r_y[lo:hi] = r_y_limbs
        r_sg[lo:hi] = r_sign
        pv[lo:hi] = prevalid

    def _prepare(
        self,
        vertices: Sequence[Vertex],
        size: int,
        comb: bool = False,
        out: Optional[Tuple[np.ndarray, np.ndarray]] = None,
    ) -> Tuple[np.ndarray, ...]:
        """Host prep for one padded dispatch of ``size`` rows.

        comb mode packs two transfers instead of seven: per-transfer
        latency was a large share of the fixed dispatch cost when last
        measured (round 3). u8 carries nibble digits + flag bits; i32
        carries key index + R.y limbs. Every row and column of the output
        is fully overwritten, so the caller may hand in a reused staging
        pair (out=) — see _stage() for the aliasing discipline.

        The row fill itself runs through the prep engine
        (verifier/prep.py): one block when ``prep_workers`` is 1 or the
        dispatch is small (structurally the serial path), otherwise up
        to ``prep_workers`` row blocks filled concurrently, each writing
        its own offsets of the SAME output arrays. The partition is
        invisible in the bytes (see _prep_block)."""
        if comb:
            if out is not None:
                dest: Tuple[np.ndarray, ...] = out
            else:
                dest = (
                    np.empty((size, _U8_COLS), dtype=np.uint8),
                    np.empty((size, 23), dtype=np.int32),
                )
        else:
            dest = (
                np.empty((size, 64), dtype=np.int32),
                np.empty((size, 64), dtype=np.int32),
                np.empty((size, field.LIMBS), dtype=np.int32),
                np.empty((size, field.LIMBS), dtype=np.int32),
                np.empty((size, field.LIMBS), dtype=np.int32),
                np.empty(size, dtype=bool),
                np.empty((size, field.LIMBS), dtype=np.int32),
                np.empty(size, dtype=np.int32),
                np.empty(size, dtype=bool),
            )
        eng = self._prep()
        eng.run_blocks(
            lambda lo, hi: self._prep_block(vertices, lo, hi, comb, dest),
            eng.plan(size),
        )
        return dest

    def _comb_tables(self):
        """Device comb tables in the padded [rows, 128] gather layout
        (built once, first dispatch) + the base-point table."""
        if self._key_tables is None:
            from dag_rider_tpu.ops import comb

            t0 = time.perf_counter()
            built = comb.build_key_tables(
                jnp.asarray(self._a_x),
                jnp.asarray(self._a_y),
                jnp.asarray(self._a_t),
            )
            self._b_table_dev = jax.jit(comb.pad_rows)(
                jnp.asarray(_b_table_cached())
            )
            self._key_tables = jax.jit(comb.pad_rows)(built)
            jax.block_until_ready((self._key_tables, self._b_table_dev))
            self.table_build_s = time.perf_counter() - t0
            obs.count(
                "verifier.table_bytes",
                self._key_tables.nbytes + self._b_table_dev.nbytes,
            )
        return self._key_tables, self._b_table_dev

    def cover_in_flight(self, depth: int) -> None:
        """A window holder (VerifierPipeline) says how many dispatches it
        keeps in flight; the staging ring grows to cover them and the
        two preps that run ahead of the window (see _stage)."""
        self._ring_slots = max(self._ring_slots, int(depth) + 2)

    def _stage(self, size: int) -> Tuple[np.ndarray, np.ndarray]:
        """Reusable (u8, i32) host staging pair for one dispatch.

        A small ring instead of a fresh np.empty per dispatch: the CPU
        PJRT client may alias a host array zero-copy into the program, so
        a slot must not be rewritten while a dispatch that shipped it can
        still be executing. The ring holds the window's depth + 2 slots
        (cover_in_flight) and the window keeps at most depth dispatches
        in flight, so a slot's previous dispatch has always resolved
        before the slot comes around again."""
        ring = self._staging.get(size)
        if ring is None or len(ring) < self._ring_slots:
            ring = [
                (
                    np.empty((size, _U8_COLS), dtype=np.uint8),
                    np.empty((size, 23), dtype=np.int32),
                )
                for _ in range(self._ring_slots)
            ]
            self._staging[size] = ring
            self._staging_idx[size] = 0
        i = self._staging_idx[size]
        self._staging_idx[size] = (i + 1) % len(ring)
        return ring[i]

    def reset_staging(self) -> None:
        """Re-arm the staging ring after a poisoned window (round-9
        containment seam). The cursor no longer matches the in-flight
        count once a dispatch or resolve has failed, so the only way to
        keep the aliasing discipline is FRESH slots: the old ring list
        is dropped, not rewritten — any orphan dispatch still executing
        keeps its zero-copy views of the old arrays alive, and the next
        _stage() builds a new ring that cannot alias them."""
        self._staging.clear()
        self._staging_idx.clear()

    # -- dispatch seam hooks ---------------------------------------------
    # dispatch_batch/warmup route every placement-sensitive decision
    # through these overridables, so ShardedTPUVerifier (parallel/
    # sharded_verifier.py) inherits the async/AOT/staging machinery —
    # padding, chunk boundaries, FIFO resolve — unchanged, and only the
    # placement (mesh-rounded buckets, NamedSharding device_put, the
    # shard_map program, mesh-keyed AOT entries) differs. The mask stays
    # a pure function of (vertex bytes, registry) under every override.

    def _round_bucket(self, b: int) -> int:
        """Final padded-size adjustment (mesh subclasses round up to a
        multiple of the batch axis; single-chip is the identity)."""
        return int(b)

    def _select_impl(self, size: int) -> str:
        """Comb tree engine for a padded dispatch of ``size`` rows."""
        return _comb_impl(size)

    def _aot_key(self, size: int, impl: str) -> tuple:
        """Cache key for the AOT-compiled program at this shape."""
        return (size, impl)

    def _put(self, arr: np.ndarray) -> jax.Array:
        """Host staging array -> committed device input."""
        return jax.device_put(arr)

    def _comb_tables_dev(self):
        """(key_tables, b_table) placed where the dispatch needs them."""
        return self._comb_tables()

    def _windowed_dispatch(self, args) -> jax.Array:
        """The comb=False oracle path's device call."""
        return _device_verify(*(jnp.asarray(a) for a in args))

    def _aot_lower(self, size: int, impl: str, tables, b_tab):
        """lower+compile the comb program at the exact dispatch shape.
        (The inputs are not donated: the only output is the bool mask,
        which can alias neither — on the chip XLA answered a donation
        with "Some donated buffers were not usable", PR 21.)"""
        return _device_verify_comb.lower(
            jax.ShapeDtypeStruct((size, _U8_COLS), jnp.uint8),
            jax.ShapeDtypeStruct((size, 23), jnp.int32),
            tables,
            b_tab,
            impl=impl,
        ).compile()

    def _note_dispatch(self, size: int, count: int) -> None:
        """Per-dispatch gauge hook (mesh subclasses book shard balance)."""

    def _program(self, size: int, impl: str):
        """The compiled comb program for a padded dispatch of ``size``
        rows, built (tables included) on first use of the shape — the
        only place the verifier compiles. A serving stack has been
        through :meth:`warmup`, so for it this is a lookup; a lowering
        or compile failure is a :class:`VerifierCompileError` naming the
        shape and the device."""
        key = self._aot_key(size, impl)
        exe = self._aot.get(key)
        if exe is None:
            try:
                tables, b_tab = self._comb_tables_dev()
                t0 = time.perf_counter()
                exe = self._aot_lower(size, impl, tables, b_tab)
            except Exception as e:
                raise VerifierCompileError(
                    f"comb program (rows={size}, impl={impl!r}) failed to "
                    f"compile on {self.platform}/{self.device_kind}: {e!r}"
                ) from e
            self._aot[key] = exe
            self.compile_s[key] = time.perf_counter() - t0
        return exe

    def warmup(self, bucket: Optional[int] = None) -> float:
        """Fix the dispatch bucket and compile its program: ``bucket``,
        else the bucket already fixed, else one round of the registry's
        n vertices rounded to its bucket.

        What every serving stack does before it takes a batch
        (VerifierPipeline at construction and again before each window
        opens, VerifierSidecarServer before its port opens, a node
        through either). From here on every batch is padded, or chunked
        (verify_batch), to this one shape, so the first consensus round
        never eats the XLA compile and nothing compiles inside a
        fault-contained window: a program the chip refuses raises here.
        With the persistent cache the lower+compile is a disk hit after
        the first ever run. A call that compiled then
        collects the heap once and freezes it (``gc.freeze``), so the
        served path's full collections walk only what it makes itself;
        a call that found the program there freezes nothing. Returns
        the seconds spent
        (cumulative in ``warmup_compile_s``); 0.0 when the program is
        already there. The windowed (comb=False) oracle path keeps its
        lazy jit cache — it is never on the hot path."""
        if not self._comb:
            return 0.0
        self.fixed_bucket = int(
            bucket or self.fixed_bucket or _bucket(self.registry.n)
        )
        size = self._round_bucket(self.fixed_bucket)
        impl = self._select_impl(size)
        key = self._aot_key(size, impl)
        if key in self._aot:
            return 0.0
        self._program(size, impl)
        self.warmup_compile_s += self.compile_s[key]
        # Tracing the program leaves over a million tracked objects
        # that nothing will ever free, and whatever else the stack has
        # built by now is as long-lived. Unfrozen, every full collection
        # of the served path walks them all (0.35-0.5 s on the chip's
        # host, PERF.md section 6, PR 27). Frozen, they are in no
        # generation: later collections walk and count only what was
        # made after this line. The collection comes first so that no
        # garbage is frozen with the rest. A cycle that is alive now and
        # dies later is never reclaimed (reference counts still free
        # everything else), so this runs only here, where a program was
        # just compiled: once per program, never per call.
        gc.collect()
        held = gc.get_freeze_count()
        gc.freeze()
        obs.count("heap.frozen_objects", gc.get_freeze_count() - held)
        return self.compile_s[key]

    #: host-prep / device-dispatch seconds of the most recent
    #: verify_batch call.
    last_prepare_s: float = 0.0
    last_dispatch_s: float = 0.0

    #: Cumulative verifier-seam accounting across a whole run: how much
    #: wall time went to host prep vs device dispatch+sync, over how
    #: many dispatches and signatures (stats()), so an in-loop
    #: shortfall is attributable: fixed per-dispatch cost or host
    #: consensus work.
    total_prepare_s: float = 0.0
    total_dispatch_s: float = 0.0
    total_dispatches: int = 0
    total_sigs_dispatched: int = 0

    #: When set (warmup() sets it), every dispatch pads to exactly this
    #: bucket and verify_batch/verify_rounds chunk larger batches into
    #: it — ONE compiled program shape for a whole consensus run,
    #: instead of a power-of-two ladder of XLA compiles as burst sizes
    #: wander.
    fixed_bucket: Optional[int] = None

    #: Requested worker count for the parallel host-prep engine
    #: (verifier/prep.py). None defers to DAGRIDER_PREP_WORKERS (default
    #: 1 = serial). Assigning a new value rebuilds the engine on the
    #: next prep — only reassign between runs, never while preps are in
    #: flight. node.py's "verify_prep_workers" config lands here.
    prep_workers: Optional[int] = None

    def _prep(self) -> PrepEngine:
        """The verifier's prep engine, (re)built lazily so a
        ``prep_workers`` override picked up between runs takes effect
        without losing the compiled programs or comb tables."""
        want = (
            int(self.prep_workers) if self.prep_workers is not None else None
        )
        eng = self._prep_engine
        if eng is None or (want is not None and eng.workers != want):
            if eng is not None:
                eng.close()
            eng = self._prep_engine = PrepEngine(want)
        return eng

    def prep_stats(self) -> dict:
        """Gauges of the parallel host-prep engine — surfaced through
        pipeline stats() and the per-process metrics snapshot.
        ``parallel_fraction`` is the no-silent-fallback gauge: rows that
        actually took the row-block parallel path over all rows
        prepped."""
        eng = self._prep()
        return {
            "workers": eng.workers,
            "last_blocks": eng.last_blocks,
            "parallel_fraction": eng.parallel_fraction(),
            "rows_total": eng.rows_total,
            "rows_parallel": eng.rows_parallel,
            "serial_retries": eng.serial_retries,
        }

    def stats(self) -> dict:
        """Where the work ran and what it cost: the backend, the program
        of the latest dispatch and the cumulative seam accounting."""
        return {
            "platform": self.platform,
            "device_kind": self.device_kind,
            "impl": self.last_impl,
            "bucket": self.last_size,
            "dispatches": self.total_dispatches,
            "sigs_dispatched": self.total_sigs_dispatched,
            "prepare_s": round(self.total_prepare_s, 4),
            "dispatch_s": round(self.total_dispatch_s, 4),
            "table_build_s": round(self.table_build_s, 2),
            "compile_s": {
                "x".join(str(p) for p in k[:2]): round(v, 2)
                for k, v in self.compile_s.items()
            },
        }

    def prep_batch(self, vertices: Sequence[Vertex]) -> "PreppedBatch":
        """Host half of :meth:`dispatch_batch`: bucket selection,
        staging-slot claim, and the (possibly row-parallel) _prepare.
        Returns a :class:`PreppedBatch` handle for
        :meth:`dispatch_prepped`.

        Safe to run on the prep engine's seam thread
        (:meth:`prep_batch_async`): the only verifier state it advances
        is the staging-ring cursor, and the seam executor serializes
        prep calls FIFO, so ring slots are claimed strictly in chunk
        order. Timing is carried in the handle and booked by
        dispatch_prepped on the dispatching thread."""
        if self.fixed_bucket and len(vertices) <= self.fixed_bucket:
            size = self._round_bucket(int(self.fixed_bucket))
        else:
            size = self._round_bucket(_bucket(len(vertices)))
        with obs.span("verify_batch.prepare") as prepare:
            out = self._stage(size) if self._comb else None
            args = self._prepare(vertices, size, comb=self._comb, out=out)
        return PreppedBatch(args, size, len(vertices), prepare.seconds)

    def prep_batch_async(self, vertices: Sequence[Vertex]):
        """:meth:`prep_batch` queued on the engine's dedicated FIFO seam
        thread; returns a Future of the PreppedBatch. The pipeline
        callers use this to run chunk k+2's prep concurrently with chunk
        k+1's prep and chunk k's device execution. Callers keep at most
        2 preps outstanding and submit a new one only after the window
        has drained below depth — with the staging ring's depth + 2
        slots (cover_in_flight) that guarantees a slot's previous
        dispatch has resolved before the slot is claimed again."""
        return self._prep().submit(self.prep_batch, vertices)

    def dispatch_prepped(self, prepped: "PreppedBatch"):
        """Device half of :meth:`dispatch_batch`: ship an already-prepped
        batch, NO sync. Books the prep accounting carried in the handle
        (so counters mutate only on the dispatching thread even when
        prep ran on the seam thread)."""
        args, size, count, prep_s = prepped
        impl = self._select_impl(size) if self._comb else "windowed"
        exe = self._program(size, impl) if self._comb else None
        self.last_size, self.last_impl = size, impl
        self.last_prepare_s = prep_s
        self.total_prepare_s += prep_s
        self.total_dispatches += 1
        self.total_sigs_dispatched += count
        self._note_dispatch(size, count)
        with obs.span("verify_batch.dispatch"):
            if self._comb:
                u8, i32 = args
                tables, b_tab = self._comb_tables_dev()
                mask = exe(self._put(u8), self._put(i32), tables, b_tab)
            else:
                mask = self._windowed_dispatch(args)
        return mask, count

    def dispatch_batch(self, vertices: Sequence[Vertex]):
        """Asynchronous half of verify: host prep + device dispatch, NO
        sync. Returns an opaque (device_mask, count) pending handle for
        :meth:`resolve_batch`. Lets a caller overlap round k+1's host prep
        with round k's device execution — the steady-state pipeline shape
        of burst delivery (one dispatch per DAG round). Composed from the
        prep_batch/dispatch_prepped halves, which pipeline callers drive
        separately to overlap prep across chunks."""
        return self.dispatch_prepped(self.prep_batch(vertices))

    def verify_rounds(
        self, rounds: Sequence[Sequence[Vertex]]
    ) -> List[List[bool]]:
        """Verify several DAG rounds as ONE merged batch (one dispatch
        where the merge fits the bucket, :meth:`verify_batch`'s chunks
        where it does not) and split the mask after — for catch-up sync
        and burst consumers. The mask is that of mapping verify_batch."""
        mask = self.verify_batch([v for r in rounds for v in r])
        out, pos = [], 0
        for r in rounds:
            out.append(mask[pos : pos + len(r)])
            pos += len(r)
        return out

    @staticmethod
    def resolve_batch(pending) -> List[bool]:
        """Blocking half: device mask -> per-vertex host bools."""
        mask, count = pending
        return [bool(m) for m in np.asarray(mask)[:count]]

    def _resolve_timed(self, pending) -> List[bool]:
        """resolve_batch plus the device-seconds accounting the seam
        breakdown expects."""
        with obs.span("verify_batch.resolve") as resolve:
            out = self.resolve_batch(pending)
        self.last_dispatch_s = resolve.seconds
        self.total_dispatch_s += self.last_dispatch_s
        return out

    def verify_batch(self, vertices: Sequence[Vertex]) -> List[bool]:
        """One dispatch, resolved at once; more than the fixed bucket
        holds is cut at the bucket and each chunk dispatched and resolved
        in turn. A fault raises: the window that contains faults is
        VerifierPipeline's."""
        if not vertices:
            return []
        cap = self.fixed_bucket
        if not cap or len(vertices) <= cap:
            return self._resolve_timed(self.dispatch_batch(vertices))
        mask: List[bool] = []
        for i in range(0, len(vertices), cap):
            mask.extend(
                self._resolve_timed(self.dispatch_batch(vertices[i : i + cap]))
            )
        return mask
