"""One validator of the ``sidecar_cert`` driver: an OS process that never
loads libtpu, makes the cell's pool of round certificates from the seed
(``harness/reference_bls.py``), and calls
``RemoteCertVerifier.verify_remote`` with one round's certificate at a
time, one RPC in flight, until the window closes.

    stdout: POOL            the pool is made
    stdin:  SERVE           the sidecar's port is open
    stdout: READY           one certificate's RPC has come back
    stdin:  GO <t_close>    the window is open until t_close (monotonic)
    stdout: one JSON object: every RPC as [pool index, sent, back, failures, verdict]
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
    ap.add_argument("--kinds", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args()
    sys.path.insert(0, args.root)
    from benchmarks.harness import reference_bls
    from dag_rider_tpu.core.types import RoundCertificate
    from dag_rider_tpu.verifier.base import KeyRegistry
    from dag_rider_tpu.verifier.sidecar import RemoteCertVerifier

    keys = reference_bls.Keys(args.n)
    kinds = [k for k in args.kinds.split(",") if k]
    pool = [
        RoundCertificate(round=c.rnd, signers=c.signers, digests=c.digests, agg_sig=c.agg)
        for c in reference_bls.make_pool(keys, args.rounds, kinds, args.seed)
    ]
    registry = KeyRegistry.generate_with_cert(args.n)[0]
    print("POOL", flush=True)
    if sys.stdin.readline().strip() != "SERVE":
        return 2
    remote = RemoteCertVerifier(
        args.address, registry, reference_bls.quorum(args.n), timeout=30.0
    )
    remote.verify_remote(pool[:1])
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
        verdicts = remote.verify_remote(pool[k : k + 1])
        back = time.monotonic()
        rpcs.append(
            [k, sent, back, remote.rpc_failures - failures,
             "".join("1" if ok else "0" for ok in verdicts)]
        )
        k = (k + 1) % len(pool)
    remote.close()
    json.dump({"rpcs": rpcs}, sys.stdout)
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    os.environ["JAX_PLATFORMS"] = "cpu"  # a validator has no chip
    raise SystemExit(main())
