"""The controls: what `correct` has to refuse. Each is the reference put
in the program's place with one stated guarantee broken — the step that
would tempt a later PR. Each driver puts one in its verifier's place
through its own ``control_stack``.

- :class:`LaxVerifier` — Ed25519 verification that leaves out the
  ``s < L`` check (RFC 8032 section 5.1.7), so a non-canonical ``s + L``
  is accepted: masks no longer equal the host oracle's.
- :class:`AcceptAll` — no verification at all.
"""

from __future__ import annotations

from typing import List, Sequence

from benchmarks.harness import reference


class _Control:
    def __init__(self, registry):
        self.registry = registry

    def verify_rounds(self, rounds: Sequence[Sequence]) -> List[List[bool]]:
        return [self.verify_batch(r) for r in rounds]


class LaxVerifier(_Control):
    """A drop-in verifier (``registry``, ``verify_batch``,
    ``verify_rounds``) over the reference, without the canonical-s
    check."""

    def __init__(self, registry):
        super().__init__(registry)
        self._keys = reference.Keys(registry.n)

    def verify_batch(self, vertices: Sequence) -> List[bool]:
        out = []
        for v in vertices:
            sig = v.signature or b""
            if len(sig) == 64:
                s = int.from_bytes(sig[32:], "little") % reference.L
                sig = sig[:32] + s.to_bytes(32, "little")
            msg = reference.signing_bytes(
                v.id.round, v.id.source, v.block.transactions,
                v.strong_edges, v.weak_edges, v.coin_share or b"",
            )
            out.append(self._keys.verify(v.id.source, msg, sig))
        return out


class AcceptAll(_Control):
    def verify_batch(self, vertices: Sequence) -> List[bool]:
        return [True] * len(vertices)
