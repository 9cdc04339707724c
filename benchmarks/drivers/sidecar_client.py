"""One validator of the ``sidecar`` driver: an OS process that never
loads libtpu, makes the cell's pool of rounds from the seed, and calls
``RemoteVerifier.verify_batch`` with one whole round at a time, one RPC
in flight, until the window closes.

    stdout: POOL            the pool is made
    stdin:  SERVE           the sidecar's port is open
    stdout: READY           one whole-round RPC has come back
    stdin:  GO <t_close>    the window is open until t_close (monotonic)
    stdout: one JSON object: every RPC as [round, sent, back, failures, mask]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", required=True)
    ap.add_argument("--address", required=True)
    ap.add_argument("--n", type=int, required=True)
    ap.add_argument("--rounds", type=int, required=True)
    ap.add_argument("--wrong", type=int, required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args()
    sys.path.insert(0, args.root)
    from benchmarks.harness import reference, roundpool
    from dag_rider_tpu.verifier.sidecar import RemoteVerifier

    keys = reference.Keys(args.n)
    pool = roundpool.make_pool(
        keys, n=args.n, rounds=args.rounds, wrong_per_round=args.wrong, seed=args.seed
    )
    rounds = [roundpool.to_vertices(r) for r in pool]
    print("POOL", flush=True)
    if sys.stdin.readline().strip() != "SERVE":
        return 2
    remote = RemoteVerifier(args.address, timeout=30.0)
    remote.verify_batch(rounds[0])
    print("READY", flush=True)
    go = sys.stdin.readline().split()
    if len(go) != 2 or go[0] != "GO":
        return 2
    t_close = float(go[1])
    rpcs = []
    k = 0
    while True:
        sent = time.monotonic()
        if sent >= t_close:
            break
        failures = remote.rpc_failures
        mask = remote.verify_batch(rounds[k])
        back = time.monotonic()
        rpcs.append(
            [k, sent, back, remote.rpc_failures - failures,
             "".join("1" if ok else "0" for ok in mask)]
        )
        k = (k + 1) % len(rounds)
    remote.close()
    json.dump({"rpcs": rpcs}, sys.stdout)
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    os.environ["JAX_PLATFORMS"] = "cpu"  # a validator has no chip
    raise SystemExit(main())
