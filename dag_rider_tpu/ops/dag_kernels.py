"""Dense-tensor DAG kernels (the TPU-native graph layer).

The reference implements graph queries by pointer-chasing and linear scans:
``path()`` is a per-query BFS (``process/process.go:89-148``) and
``present()`` scans the entire DAG per predecessor
(``process/process.go:374-384``) — O(n^2 * rounds) per vertex admission.

Here the DAG is encoded as dense tensors indexed by (round, source):

- ``exists[R, n]``  : bool — vertex (r, i) is in the DAG.
- ``strong[R, n, n]``: bool — strong[r, i, j] means vertex (r, i) has a
  strong edge to vertex (r-1, j). Row r=0 is unused (genesis has no edges).
- weak edges (round-skipping, rare) are kept sparse on the host; an optional
  dense ``weak[R, n, R, n]`` form is supported for small configs/tests.

Reachability then becomes a chain of boolean matrix products — an exact MXU
fit: reach(r_hi -> r_lo) = strong[r_hi] @ strong[r_hi-1] @ ... @
strong[r_lo+1], and the wave-commit rule "2f+1 round-(w,4) vertices have a
strong path to the leader" (``process/process.go:331-339``) is one 3-matmul
chain + a popcount.

All kernels are pure jnp and jit-able; ``n`` and ``R`` are static shapes.
Matmuls are done in float32/bf16 (counts saturate via > 0) so XLA tiles them
onto the MXU; booleans only materialize at the edges.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# ---------------------------------------------------------------------------
# Boolean semiring primitives
# ---------------------------------------------------------------------------


def _bmm(a: jax.Array, b: jax.Array) -> jax.Array:
    """Boolean matrix product: (a @ b) > 0, computed in float32 on the MXU.

    a: [..., m, k] bool, b: [..., k, p] bool -> [..., m, p] bool.
    """
    return (
        jnp.matmul(
            a.astype(jnp.float32),
            b.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        > 0.0
    )


@jax.jit
def reach_chain(strong_stack: jax.Array) -> jax.Array:
    """Multi-round strong reachability as a matmul chain.

    Args:
        strong_stack: bool[k, n, n], ordered top round first:
            strong_stack[0] maps round r_hi -> r_hi - 1,
            strong_stack[k-1] maps round r_lo + 1 -> r_lo.

    Returns:
        bool[n, n]: entry (i, j) — vertex (r_hi, i) has a strong path to
        vertex (r_lo, j). Rows of absent vertices are all-zero because their
        strong rows are all-zero.

    Replaces repeated BFS calls over consecutive rounds (reference ``path``,
    ``process/process.go:89-148``, restricted to strong edges).
    """

    def step(carry, s):
        return _bmm(carry, s), None

    init = strong_stack[0]
    if strong_stack.shape[0] == 1:
        return init
    out, _ = lax.scan(step, init, strong_stack[1:])
    return out


# ---------------------------------------------------------------------------
# Round advancement + admission (Algorithm 2)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("quorum",))
def round_complete(exists_row: jax.Array, *, quorum: int) -> jax.Array:
    """|dag[r]| >= 2f+1 — the round-advance condition
    (reference ``process/process.go:236``)."""
    return jnp.sum(exists_row.astype(jnp.int32)) >= quorum


@jax.jit
def admission_mask(
    strong_pred: jax.Array,
    exists_prev: jax.Array,
    weak_pred: jax.Array,
    exists: jax.Array,
) -> jax.Array:
    """Which buffered vertices have *all* predecessors already in the DAG.

    This is the buffer-drain predicate of Algorithm 2 (reference
    ``process/process.go:208-228``), vectorized over a whole buffer:

    Args:
        strong_pred: bool[B, n]   — strong-edge targets in round r-1.
        exists_prev: bool[n]      — exists[r-1].
        weak_pred:   bool[B, R, n] — weak-edge targets across all rounds.
        exists:      bool[R, n]   — full presence bitmap.

    Returns:
        bool[B] — admissible[b] iff every referenced predecessor exists.
    """
    strong_ok = ~jnp.any(strong_pred & ~exists_prev[None, :], axis=-1)
    weak_ok = ~jnp.any(weak_pred & ~exists[None, :, :], axis=(-2, -1))
    return strong_ok & weak_ok


@functools.partial(jax.jit, static_argnames=("quorum",))
def strong_edge_quorum(strong_pred: jax.Array, *, quorum: int) -> jax.Array:
    """r_deliver admission gate: vertex carries >= 2f+1 strong edges
    (reference ``process/process.go:164-168``). strong_pred: bool[B, n]."""
    return jnp.sum(strong_pred.astype(jnp.int32), axis=-1) >= quorum


# ---------------------------------------------------------------------------
# Wave commit (Algorithm 3)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("quorum",))
def wave_commit_votes(
    strong_wave: jax.Array,
    exists_r4: jax.Array,
    leader: jax.Array,
    *,
    quorum: int,
) -> tuple[jax.Array, jax.Array]:
    """The wave-commit quorum check (reference ``process/process.go:331-339``).

    Args:
        strong_wave: bool[3, n, n] — strong adjacency for rounds
            (w,4), (w,3), (w,2), i.e. strong_wave[0] maps round(w,4) ->
            round(w,3), ..., strong_wave[2] maps round(w,2) -> round(w,1).
        exists_r4: bool[n] — presence bitmap of round(w,4).
        leader: int32 scalar — source index of the wave-w leader vertex at
            round(w,1).

    Returns:
        (commit: bool scalar, votes: bool[n]) — votes[i] iff vertex
        (round(w,4), i) exists and has a strong path to the leader; commit
        iff popcount(votes) >= 2f+1.
    """
    reach = reach_chain(strong_wave)  # [n, n]: round(w,4) -> round(w,1)
    votes = reach[:, leader] & exists_r4
    commit = jnp.sum(votes.astype(jnp.int32)) >= quorum
    return commit, votes


@jax.jit
def leader_reach(strong_wave: jax.Array, hi_leader: jax.Array) -> jax.Array:
    """One step of the retroactive leader-chain descent
    (reference ``process/process.go:342-350``).

    Args:
        strong_wave: bool[k, n, n] — adjacency chain from the higher
            leader's round down to the lower leader's round + 1 (k = 4 for
            consecutive waves).
        hi_leader: int32 — source of the already-committed higher leader.

    Returns:
        bool[n] — which sources' vertices at the lower round are reachable
        from the higher leader by a strong path.
    """
    reach = reach_chain(strong_wave)
    return reach[hi_leader, :]


# ---------------------------------------------------------------------------
# Causal closure (total ordering support)
# ---------------------------------------------------------------------------


@jax.jit
def closure_from(seeds: jax.Array, strong: jax.Array) -> jax.Array:
    """Strong-edge causal history of a seed set.

    Propagates reachability downward round by round:
        reached[r-1] |= reached[r] @ strong[r]

    Args:
        seeds: bool[R, n] — starting vertices (e.g. one-hot of a leader).
        strong: bool[R, n, n].

    Returns:
        bool[R, n] — all vertices reachable from the seeds via strong paths
        (seeds included). This is the dense analog of the per-vertex BFS the
        reference runs inside ``orderVertices`` (``process/process.go:417-431``).
    """
    R = seeds.shape[0]

    def step(carry_row, xs):
        seed_row, strong_r = xs  # seed_row = seeds[r-1]; strong_r = strong[r]
        nxt = seed_row | _bmm(carry_row[None, :], strong_r)[0]
        return nxt, nxt

    init = seeds[R - 1]
    if R == 1:
        return seeds
    xs = (seeds[R - 2 :: -1], strong[: 0 : -1])
    _, rows = lax.scan(step, init, xs)
    return jnp.concatenate([rows[::-1], init[None, :]], axis=0)


@jax.jit
def closure_from_full(
    seeds: jax.Array, strong: jax.Array, weak: jax.Array
) -> jax.Array:
    """Causal history over strong *and* weak edges (dense weak form).

    weak: bool[R, n, R, n] — weak[r, i, r2, j] means (r, i) has a weak edge
    to (r2, j), r2 < r-1. Dense weak tensors are only practical for small
    configs (tests, n<=16); production ordering keeps weak edges sparse on
    the host (see consensus.dag_state), exactly as the north star keeps
    ordering host-side.

    Returns bool[R, n] as in :func:`closure_from`.
    """
    R, n = seeds.shape

    def body(r_rev, acc):
        r = R - 1 - r_rev
        row = acc[r]  # finalized: nothing above r is unprocessed
        strong_contrib = _bmm(row[None, :], strong[r])[0]
        acc = lax.cond(
            r > 0,
            lambda a: a.at[r - 1].set(a[r - 1] | strong_contrib),
            lambda a: a,
            acc,
        )
        weak_contrib = (
            jnp.tensordot(
                row.astype(jnp.float32),
                weak[r].astype(jnp.float32).reshape(n, R * n),
                axes=1,
            )
            > 0.0
        ).reshape(R, n)
        return acc | weak_contrib

    return lax.fori_loop(0, R, body, seeds)


# ---------------------------------------------------------------------------
# Host twins (numpy)
# ---------------------------------------------------------------------------
#
# The same predicates in numpy, for a host that has a round in hand: a
# jitted dispatch costs ~50-100 us on CPU — more than the whole batched
# numpy op at n=256. tests/test_pump_vector.py pins each twin equal to
# its jitted sibling on random DAGs so they cannot drift apart. Bool @
# bool numpy matmul is the established idiom here (consensus/process.py
# _weak_edges_for). The twin the round-batched pump calls in every
# validator's process, :func:`leader_reach`'s, lives with its caller
# (``Process._reach_from``): importing this module imports jax.


def reach_chain_np(strong_stack) -> "np.ndarray":
    """Numpy twin of :func:`reach_chain`: bool[k, n, n] top round first ->
    bool[n, n] reachability from round r_hi to round r_lo."""
    out = strong_stack[0]
    for s in strong_stack[1:]:
        out = out @ s
    return np.asarray(out, dtype=bool)


def round_complete_np(exists_row, *, quorum: int) -> bool:
    """Numpy twin of :func:`round_complete`."""
    return bool(np.count_nonzero(exists_row) >= quorum)


def admission_mask_np(strong_pred, exists_prev, weak_pred, exists):
    """Numpy twin of :func:`admission_mask` (same shapes/semantics)."""
    strong_ok = ~np.any(strong_pred & ~exists_prev[None, :], axis=-1)
    weak_ok = ~np.any(weak_pred & ~exists[None, :, :], axis=(-2, -1))
    return strong_ok & weak_ok


def strong_edge_quorum_np(strong_pred, *, quorum: int):
    """Numpy twin of :func:`strong_edge_quorum`: bool[B]."""
    return np.count_nonzero(strong_pred, axis=-1) >= quorum


@jax.jit
def pairwise_reach(strong: jax.Array) -> jax.Array:
    """All-pairs strong reachability: bool[R, n, R*? ] — here returned as
    reach[R, n, n] where reach[r] maps round-r vertices to round-0... no:

    Returns reach[R, n, R, n]? That is O((Rn)^2); instead this returns the
    cumulative chain products chain[r] = strong[r] @ ... @ strong[1],
    i.e. chain[r][i, j] — (r, i) strongly reaches (0, j). Useful for genesis
    anchoring tests. chain[0] = I.
    """
    R, n, _ = strong.shape

    def step(carry, s):
        nxt = _bmm(s, carry)
        return nxt, nxt

    init = jnp.eye(n, dtype=bool)
    _, outs = lax.scan(step, init, strong[1:])
    return jnp.concatenate([init[None], outs], axis=0)
