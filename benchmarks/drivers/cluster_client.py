"""The load of the ``cluster`` driver: an OS process that never loads
libtpu and offers its share of the cell's open-loop arrivals through the
validators' ``Submit`` door — client c at validator c, one transaction
an RPC, as the cluster's own client does (the door's wire format, JSON
with the transaction in hex, is written out here; nothing of the
program is imported). RPCs are issued without waiting for the answer,
so that a slow validator holds no arrival back.

    stdout: READY                     every validator's door answers
    stdin:  GO <t0_wall> <seconds>    arrivals are due from t0 on
    --out:  one JSON object: every arrival as
            [client, head, due, sent, acked, verdict]
            (seconds from t0; ``head`` is the payload before its
            padding; verdict "accepted", "deduped", "shed" or "error")
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

SUBMIT = "/dagrider.Transport/Submit"
#: an answer is waited for this long past the window's close
SETTLE_S = 10.0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", required=True)
    ap.add_argument("--addresses", required=True, help="JSON list, by validator")
    ap.add_argument("--traffic", required=True, help="the traffic mix, JSON")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--share", required=True, help="k/m: clients c with c %% m == k")
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    sys.path.insert(0, args.root)
    import grpc

    from benchmarks.harness.loadgen import LoadGenerator

    addresses = json.loads(args.addresses)
    k, m = (int(x) for x in args.share.split("/"))
    n = len(addresses)
    gen = LoadGenerator.from_traffic(json.loads(args.traffic), args.seed)
    mine = [c for c in range(gen.clients) if c % m == k]
    channels = {c % n: grpc.insecure_channel(addresses[c % n]) for c in mine}
    stubs = {
        v: ch.unary_unary(
            SUBMIT, request_serializer=lambda b: b, response_deserializer=lambda b: b
        )
        for v, ch in channels.items()
    }
    for ch in channels.values():
        grpc.channel_ready_future(ch).result(timeout=60)
    print("READY", flush=True)
    go = sys.stdin.readline().split()
    if len(go) != 3 or go[0] != "GO":
        return 2
    t0, seconds = float(go[1]), float(go[2])

    rows = []
    calls = []  # a call whose handle is dropped is cancelled
    pending = threading.Semaphore(0)

    def answered(row):
        def done(fut):
            row[4] = time.time() - t0
            try:
                verdict = json.loads(fut.result())
                if verdict.get("accepted"):
                    row[5] = "accepted"
                elif verdict.get("deduped"):
                    row[5] = "deduped"
                else:
                    row[5] = "shed"
            except Exception:  # noqa: BLE001 — an RPC error, an empty or
                # malformed answer: the transaction is not acknowledged
                row[5] = "error"
            pending.release()

        return done

    while True:
        t = time.time() - t0
        for due, c, tx in gen.events_until(min(t, seconds)):
            if c % m != k:
                continue
            body = json.dumps({"client": f"c{c}", "txs": [tx.hex()]}).encode()
            row = [c, tx.rstrip(b".").decode(), due, time.time() - t0, None, None]
            rows.append(row)
            call = stubs[c % n].future(body, timeout=SETTLE_S)
            call.add_done_callback(answered(row))
            calls.append(call)
        if t >= seconds:
            break
        time.sleep(0.001)
    deadline = time.time() + SETTLE_S + 1.0
    for _ in rows:
        pending.acquire(timeout=max(0.0, deadline - time.time()))
    for ch in channels.values():
        ch.close()
    with open(args.out + ".tmp", "w") as fh:
        json.dump({"rows": rows}, fh)
    os.replace(args.out + ".tmp", args.out)
    return 0


if __name__ == "__main__":
    os.environ["JAX_PLATFORMS"] = "cpu"  # a client has no chip
    raise SystemExit(main())
