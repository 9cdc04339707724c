"""Driver ``cluster_crash``: the ``cluster`` driver's committee with the
fault its traffic names — the validators in ``crashed`` are booted with
the rest and SIGKILLed before the window opens, and nobody restarts
them. Everything but the fault is the ``cluster`` driver's own: this
module loads a copy of it for itself and calls its functions.

The fault goes by validator 0's round, not by the clock: the victims
die when validator 0 has reached ``kill_at_round`` (a crash happens to a
running validator: its broadcasts are in flight, its sockets go dead
under its peers' senders), and the window opens when validator 0 has
reached ``kill_at_round + settle_rounds``. With the configuration's
fixed committee every run's window then starts in the same wave and
meets the same sequence of dead leaders.

With f validators down a quorum of 2f+1 is every validator left alive.
So a wrong vertex claims a CRASHED validator's slot: relayed to every
validator but its claimed source it then reaches all the living, who
echo it, deliver it and refuse it. Under a living validator's name it
would reach one fewer than a quorum and never be delivered.

Beside the ``cluster`` driver's comparisons ``check`` holds the crash to
what the files show (``harness/reference_crash.py``): no victim alive
after its kill, and nothing delivered under a victim's name above the
last round its own event log shows it proposed.
"""

from __future__ import annotations

import os
import time

from benchmarks.harness import cells, reference_crash, roundpool

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
#: this driver's own copy of the ``cluster`` driver (``load_driver``
#: makes a new module each time and registers it nowhere)
cluster = cells.load_driver(ROOT, "cluster")
#: validator 0 is waited for this long to reach the kill's round, and
#: again to reach the window's
SETTLE_BOUND_S = 180.0

#: of a validator's ``final`` report, what the run's stderr shows
FINAL_COUNTERS = (
    "rounds_advanced", "waves_decided", "waves_skipped", "sync_requested", "sync_served",
    "net_sends", "net_send_errors", "net_retries", "net_drops", "net_down_peer_drops",
    "net_peer_down", "net_peer_recovered", "net_rpc_unavailable", "net_rpc_deadline_exceeded",
)

forge_under_any_source = cluster._forge


def forge_under_a_crashed_source(stack, rnd: int) -> roundpool.Signed:
    """``cluster._forge`` with the source the vertex claims, as sent, one
    of the crashed (the kind ``other_source`` is the signature of the
    validator before it)."""
    rng, n = stack.rng, stack.n
    strong = tuple((rnd - 1, s) for s in range(roundpool.quorum(n)))
    kind = roundpool.KINDS[(stack.first_kind + len(stack.forged)) % len(roundpool.KINDS)]
    claimed = rng.choice(stack.traffic["crashed"])
    signer = (claimed - 1) % n if kind == "other_source" else claimed
    honest = roundpool.sign(stack.keys, rnd, signer, (b"forged".ljust(32, b"."),), strong)
    wrong = roundpool.corrupt(honest, kind, n, rng)
    if wrong.source != claimed:
        raise AssertionError(f"a wrong vertex of kind {kind} claims {wrong.source}, not {claimed}")
    stack.forged.append(wrong)
    return wrong


cluster._forge = forge_under_a_crashed_source


def _round_of_validator0(stack) -> int:
    return max((r for _, r in cluster.rounds_reached(stack.spec.nodes[0].events_log)), default=0)


def _wait_for_round(stack, rnd: int) -> None:
    t0 = time.monotonic()
    while _round_of_validator0(stack) < rnd:
        if time.monotonic() - t0 > SETTLE_BOUND_S:
            raise RuntimeError(
                f"validator 0 stands at round {_round_of_validator0(stack)}, not {rnd}, "
                f"after {SETTLE_BOUND_S:.0f} s"
            )
        time.sleep(0.05)


def crash(stack):
    """Kill the victims at the traffic's round and wait out its settle
    rounds; the stack is closed if the committee does not get there."""
    t, c = stack.traffic, stack.config
    try:
        if t["kill"] != "SIGKILL" or t["crashed"] != c["crashed"]:
            raise ValueError("the traffic's fault is not the configuration's")
        if len(t["crashed"]) > c["f"] or 0 in t["crashed"]:
            raise ValueError("at most f validators crash, and validator 0 holds the chip")
        t0 = time.monotonic()
        _wait_for_round(stack, t["kill_at_round"])
        stack.killed_at = {}
        for i in t["crashed"]:
            stack.sup.kill(i)  # SIGKILL, and waited for
            stack.killed_at[i] = time.time()
        stack.killed_at_round = _round_of_validator0(stack)
        _wait_for_round(stack, t["kill_at_round"] + t["settle_rounds"])
        stack.setup_parts["kill_and_settle_s"] = time.monotonic() - t0
    except BaseException:
        cluster.close(stack)
        raise
    return stack


def build(config: dict, traffic: dict, seed: int):
    return crash(cluster.build(config, traffic, seed))


def control_stack(control, config: dict, traffic: dict, seed: int):
    return crash(cluster.control_stack(control, config, traffic, seed))


def run_window(stack, seconds: float, tracer=None) -> dict:
    """The ``cluster`` driver's window, drain and stop, and then what
    the victims left on disk, read into what was observed."""
    crashed = stack.traffic["crashed"]
    alive = {i: stack.sup.procs[i].poll() is None for i in crashed}
    observed = cluster.run_window(stack, seconds, tracer)
    victims = []
    for i in crashed:
        nf, at = stack.spec.nodes[i], stack.killed_at.get(i)
        victims.append(
            {
                "validator": i,
                "killed_at": at,
                "alive": alive[i],
                "final_report": os.path.exists(nf.final_report),
                "late_lines": 0 if at is None else (
                    reference_crash.stamps_after(nf.events_log, at)
                    + reference_crash.stamps_after(nf.delivery_log, at)
                ),
                "last_proposed": reference_crash.last_proposed_round(nf.events_log),
            }
        )
    observed["victims"] = victims
    finals = [cluster._read_json(nf.final_report) or {} for nf in stack.spec.nodes]
    observed["counters"]["crash"] = {
        "crashed": list(crashed),
        "killed_at_round": stack.killed_at_round,
        "window_from_round": stack.traffic["kill_at_round"] + stack.traffic["settle_rounds"],
        "victims_last_proposed": [v["last_proposed"] for v in victims],
        "victims_delivered": [len(observed["logs"][i]) for i in crashed],
        "live_delivered": [
            len(log) for i, log in enumerate(observed["logs"]) if i not in crashed
        ],
        # the validators' own counters at their clean stop, by validator
        # (0 for the crashed, who left none): a parent that books none of
        # the new spans still tells its dead-peer sends and its requests
        "finals": {
            name: [f.get("metrics", {}).get(name, 0) for f in finals] for name in FINAL_COUNTERS
        },
        "rbc_open_slots_at_stop": [f.get("rbc_open_slots") for f in finals],
    }
    return observed


def check(stack, observed: dict) -> dict:
    """The ``cluster`` driver's comparisons and the crash's two."""
    compared = cluster.check(stack, observed)
    victims = observed["victims"]
    longest = max(observed["logs"], key=len)
    compared["crashed_still_running"] = {
        "value": reference_crash.crashed_still_running(victims), "limit": 0,
    }
    compared["delivered_from_the_dead"] = {
        "value": reference_crash.delivered_from_the_dead(
            longest, {v["validator"]: v["last_proposed"] for v in victims}
        ),
        "limit": 0,
    }
    return compared


close = cluster.close
