"""The ``inloop`` driver's forged vertices (``_forge``): at n=4 a round's
four sources run out, and the fifth forgery at that round goes under the
next one instead of drawing for ever; at n=256, where a round always has
a free source, the draws are the ones the loop made before that cure.
"""

import os
import random
import sys
import threading
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import cells, reference, roundpool  # noqa: E402

inloop = cells.load_driver(ROOT, "inloop")


def stack_of(n: int, seed: int):
    """What ``_forge`` reads of a ``Stack``, made as ``Stack`` makes it."""
    rng = random.Random(seed ^ 0x5EED)
    return types.SimpleNamespace(
        rng=rng, n=n, keys=reference.Keys(n), forged_ids=set(),
        first_kind=rng.randrange(len(roundpool.KINDS)),
    )


def forge_before_the_cure(stack, rnd: int):
    """``_forge`` as it drew before a round's sources could run out."""
    from dag_rider_tpu.core.types import BroadcastMessage

    rng, n = stack.rng, stack.n
    q = roundpool.quorum(n)
    strong = tuple((rnd - 1, s) for s in range(q))
    kind = roundpool.KINDS[(stack.first_kind + len(stack.forged_ids)) % len(roundpool.KINDS)]
    while True:
        honest = roundpool.sign(
            stack.keys, rnd, rng.randrange(n), (b"forged".ljust(32, b"."),), strong
        )
        wrong = roundpool.corrupt(honest, kind, n, rng)
        if (rnd, wrong.source) not in stack.forged_ids:
            break
    stack.forged_ids.add((rnd, wrong.source))
    (vertex,) = roundpool.to_vertices([wrong])
    return BroadcastMessage(vertex=vertex, round=rnd, sender=wrong.source)


def drawn(forge, stack, rounds, monkeypatch) -> tuple:
    """(round, source, kind, signature) of each forgery, one a round of
    ``rounds``, the kind as ``roundpool.corrupt`` was asked for it; and
    how many vertices were drawn for them."""
    kinds = []
    corrupt = roundpool.corrupt

    def keeping(v, kind, n, rng):
        kinds.append(kind)
        return corrupt(v, kind, n, rng)

    monkeypatch.setattr(roundpool, "corrupt", keeping)
    out = []
    for rnd in rounds:
        msg = forge(stack, rnd)
        out.append((msg.round, msg.sender, kinds[-1], msg.vertex.signature))
    return out, len(kinds)


def test_a_round_whose_sources_are_all_forged_moves_the_forgery_to_the_next_round():
    stack = stack_of(4, 2**31 + 4001)
    got = []

    def five():
        got.extend(inloop._forge(stack, 7) for _ in range(4 + 1))

    worker = threading.Thread(target=five, daemon=True)
    worker.start()
    worker.join(timeout=60)
    assert not worker.is_alive(), "a forgery at a round with no free source never returned"
    assert len(got) == 5
    assert [m.round for m in got] == [7, 7, 7, 7, 8]
    assert {m.sender for m in got[:4]} == {0, 1, 2, 3}
    assert stack.forged_ids == {(7, s) for s in range(4)} | {(8, got[4].sender)}


@pytest.mark.parametrize("seed", [2**31 + 4003, 3_000_000_019])
def test_at_n256_the_first_fifty_forgeries_are_those_drawn_before_the_cure(seed, monkeypatch):
    # 25 forgeries a round, so that some draws meet a source already taken
    rounds = [100] * 25 + [101] * 25
    cured, draws = drawn(inloop._forge, stack_of(256, seed), rounds, monkeypatch)
    before = drawn(forge_before_the_cure, stack_of(256, seed), rounds, monkeypatch)
    assert (cured, draws) == before
    assert draws > 50 and len({(r, s) for r, s, _, _ in cured}) == 50
    assert [k for _, _, k, _ in cured[:5]] != [cured[0][2]] * 5  # the kinds take turns
