"""Driver ``cluster``: a committee as a deployment runs it — every
validator its own OS process (``python -m dag_rider_tpu.cluster.runner``
under ``ClusterSupervisor``), peers over sockets with reliable broadcast
on, a delay on every link by the two ends' regions, acknowledgements
after the WAL, and validator 0's vertex signatures going by
``RemoteVerifier`` to the sidecar that holds the chip, inside its
consensus loop. This process holds the chip and hosts that sidecar (as
driver ``sidecar`` does); the load comes from client OS processes
(``cluster_client.py``) through the validators' ``Submit`` door.

``build`` makes the device verifier the configuration states and hands
it to ``assemble``; ``run_window`` drives an assembled stack (a test
assembles one over the host verifier). A program that cannot delay each
link by its own amount is refused before anything is built: under this
cell's name no LAN is measured.

The committee — its signing keys, the coin's shares and with them the
sequence of leaders, the frame keys — is the configuration's
(``committee_seed``) and the same in every run, as ``committee256``'s
fixed PKI is; ``--seed`` draws the arrivals, every link's jitter and
the wrong vertices.

The benchmark keeps its own books. A transaction is timed from when it
was DUE to the wall stamp of its ``a_deliver`` in the delivery log of
the validator it was submitted to. A wrong-signature vertex goes out
every few seconds by the clock (the traffic's ``forged_vertices_per_s``,
the five wrong kinds in turn) as a relayed VAL frame to every validator
but the one it claims to be from, under a round far beyond any the run
reaches: reliable broadcast gives a (round, source) slot to the first
vertex that claims it, so a wrong vertex under a round the committee
will reach would censor the honest one. Reliable broadcast delivers it
everywhere and every verifier must refuse it — validator 0's through
the sidecar, where :class:`Recorder` keeps every vertex asked about and
every mask answered.
"""

from __future__ import annotations

import inspect
import json
import os
import random
import shutil
import struct
import subprocess
import sys
import tempfile
import time
from typing import Dict, List, Optional

import jax

from benchmarks.harness import reference, reference_cluster, roundpool

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CLIENT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "cluster_client.py")
#: how long past the window's close a transaction is waited for
DRAIN_BOUND_S = 60.0
#: the committee runs this far before the window opens (one wave and its coin)
WARM_ROUNDS = 5
#: the validators are waited for this long to come up, and to warm
BOOT_BOUND_S = 180.0
DELIVER = "/dagrider.Transport/Deliver"


def refuse_a_program_without_per_link_delays() -> None:
    """Exit, with a message, where the program's ``WanFault`` takes no
    delay matrix or ``build_cluster`` no region and verifier per node."""
    from dag_rider_tpu.cluster.directory import build_cluster
    from dag_rider_tpu.transport.net import WanFault

    missing = [
        f"{fn.__qualname__}({name}=)"
        for fn, name in (
            (WanFault.__init__, "one_way_ms"),
            (build_cluster, "regions"),
            (build_cluster, "verifiers"),
        )
        if name not in inspect.signature(fn).parameters
    ]
    if missing:
        raise SystemExit(
            "benchmarks/drivers/cluster.py: this program cannot delay each link "
            "by its regions' distance (it lacks " + ", ".join(missing) + "); "
            "the cell is a WAN's and is not run as a LAN"
        )


class Recorder:
    """The sidecar's backend with its books: per ``verify_batch`` when it
    was entered and left on this process's clock, the vertices asked
    about and the mask answered."""

    def __init__(self, backend):
        self.backend = backend
        self.registry = backend.registry
        self.calls: List[tuple] = []

    def warmup(self) -> float:
        warm = getattr(self.backend, "warmup", None)
        return warm() if callable(warm) else 0.0

    def verify_batch(self, vertices):
        t0 = time.monotonic()
        with jax.profiler.TraceAnnotation("bench.server.verify_batch"):
            mask = self.backend.verify_batch(vertices)
        self.calls.append((t0, time.monotonic(), list(vertices), list(mask)))
        return mask


class Stack:
    def __init__(self, config: dict, traffic: dict, seed: int):
        self.config = config
        self.traffic = traffic
        self.seed = seed
        self.n = config["n"]
        self.keys = reference_cluster.ClusterKeys(self.n, config["committee_seed"])
        self.tmp = tempfile.mkdtemp(prefix="bc-")
        sock = os.path.join(self.tmp, "v.sock")
        # a unix socket's path holds ~107 bytes; under a longer TMPDIR the
        # socket lives in the abstract namespace, named after this one
        self.address = "unix:" + sock if len(sock) < 100 else "unix-abstract:" + sock[-90:]
        self.spec = None
        self.sup = None
        self.server = None
        self.recorder: Optional[Recorder] = None
        self.clients: List[subprocess.Popen] = []
        self.client_outs: List[str] = []
        self.rng = random.Random(seed ^ 0x5EED)
        self.first_kind = self.rng.randrange(len(roundpool.KINDS))
        self.forged: List[roundpool.Signed] = []
        self.forged_calls: list = []  # a call whose handle is dropped is cancelled
        self.channels: Dict[int, object] = {}
        self.setup_parts: Dict[str, float] = {"host_cpus": os.cpu_count() or 0}


def regions_of(config: dict) -> List[str]:
    names = config["regions"]
    return [names[i % len(names)] for i in range(config["n"])]


def lay_out(stack: Stack):
    """The cluster's workspace as the configuration states it."""
    from dag_rider_tpu.cluster.directory import build_cluster

    c = stack.config
    root = os.path.join(stack.tmp, "cluster")
    if len(root) > 80:
        raise RuntimeError(f"TMPDIR is too long for the validators' unix sockets: {root}")
    spec = build_cluster(
        root,
        c["n"],
        transport=c["transport"],
        seed=c["committee_seed"],
        coin=c["coin"],
        cert=c["cert"],
        rbc=c["rbc"],
        gc_depth=c["gc_depth"],
        wan={
            "seed": stack.seed,
            "one_way_ms": c["one_way_delay_ms"],
            "jitter": c["delay_jitter"],
        },
        regions=regions_of(c),
        verifiers={0: {"kind": "remote", "address": stack.address}},
    )
    with open(os.path.join(spec.root, "keys.json")) as fh:
        public = [bytes.fromhex(pk) for pk in json.load(fh)["ed25519_public"]]
    if public != stack.keys.public:
        raise AssertionError("the program's committee keys are not the configuration's")
    return spec


def registry_of(stack: Stack):
    from dag_rider_tpu.verifier.base import KeyRegistry

    return KeyRegistry(tuple(stack.keys.public))


def start_clients(stack: Stack) -> None:
    t = stack.traffic
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    procs = t["client_processes"]
    for k in range(procs):
        out = os.path.join(stack.tmp, f"client{k}.json")
        stack.client_outs.append(out)
        stack.clients.append(
            subprocess.Popen(
                [
                    sys.executable, CLIENT, "--root", ROOT,
                    "--addresses", json.dumps(stack.spec.addresses),
                    "--traffic", json.dumps({k_: t[k_] for k_ in t if k_ != "why"}),
                    "--seed", str(stack.seed), "--share", f"{k}/{procs}", "--out", out,
                ],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, env=env,
            )
        )


def build(config: dict, traffic: dict, seed: int) -> Stack:
    refuse_a_program_without_per_link_delays()
    from dag_rider_tpu.verifier.tpu import TPUVerifier

    stack = Stack(config, traffic, seed)
    try:
        t0 = time.monotonic()
        backend = TPUVerifier(registry_of(stack))
        stack.setup_parts["build_s"] = time.monotonic() - t0
        assemble(stack, backend)
        vs = backend.stats()
        stack.setup_parts["tables_s"] = vs["table_build_s"]
        stack.setup_parts["compile_or_cache_load_s"] = sum(vs["compile_s"].values())
    except BaseException:
        close(stack)
        raise
    return stack


def control_stack(control, config: dict, traffic: dict, seed: int) -> Stack:
    """This driver's stack with ``control`` as the sidecar's backend."""
    refuse_a_program_without_per_link_delays()
    stack = Stack(config, traffic, seed)
    try:
        backend = control(registry_of(stack))
        if hasattr(backend, "_keys"):
            # the controls verify under ``reference.Keys``, the test PKI of
            # the other drivers; this committee's keys are its dealer's
            backend._keys = stack.keys
        return assemble(stack, backend)
    except BaseException:
        close(stack)
        raise


def rounds_reached(events_log: str) -> List[tuple]:
    """(wall stamp, round) of every round a validator's event log says
    it advanced to."""
    out = []
    try:
        with open(events_log) as fh:
            for line in fh:
                if '"round_advance"' not in line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                out.append((rec["ts"], rec["round"]))
    except OSError:
        pass
    return out


def assemble(stack: Stack, backend) -> Stack:
    """The sidecar over ``backend`` first (a validator whose sidecar is
    not up refuses every vertex), then the validators, their first wave,
    and the clients at the validators' doors."""
    from dag_rider_tpu.cluster.supervisor import ClusterSupervisor
    from dag_rider_tpu.verifier.sidecar import VerifierSidecarServer

    stack.spec = lay_out(stack)
    stack.recorder = Recorder(backend)
    t0 = time.monotonic()
    stack.server = VerifierSidecarServer(stack.recorder, stack.address)
    stack.setup_parts["server_up_s"] = time.monotonic() - t0
    t0 = time.monotonic()
    python_path = os.pathsep.join(p for p in (ROOT, os.environ.get("PYTHONPATH")) if p)
    stack.sup = ClusterSupervisor(stack.spec, trace=False, env={"PYTHONPATH": python_path})
    stack.sup.start_all()
    down = stack.sup.wait_ready(BOOT_BOUND_S)
    if down:
        raise RuntimeError(f"validators {down} did not come up: {_stderr_tail(stack, down[0])}")
    stack.setup_parts["validators_up_s"] = time.monotonic() - t0
    start_clients(stack)
    t0 = time.monotonic()
    events = stack.spec.nodes[0].events_log
    while max((r for _, r in rounds_reached(events)), default=0) < WARM_ROUNDS:
        if time.monotonic() - t0 > BOOT_BOUND_S:
            raise RuntimeError(f"validator 0 did not reach round {WARM_ROUNDS}")
        time.sleep(0.2)
    stack.setup_parts["warm_rounds_s"] = time.monotonic() - t0
    for c in stack.clients:
        line = c.stdout.readline().strip()
        if line != "READY":
            raise RuntimeError(f"client {c.pid} said {line!r}, not 'READY'")
    return stack


def _stderr_tail(stack: Stack, index: int) -> str:
    try:
        with open(stack.spec.nodes[index].stderr) as fh:
            return fh.read()[-2000:]
    except OSError:
        return ""


def _maps_libtpu(pid: int) -> bool:
    try:
        with open(f"/proc/{pid}/maps") as fh:
            return any("libtpu" in line for line in fh)
    except OSError:
        return False


def _forge(stack: Stack, rnd: int) -> roundpool.Signed:
    """One wrong vertex for round ``rnd``; the kind and the source come
    from the seed, and it never claims to be validator 0's (who would
    then not be asked about it)."""
    rng, n = stack.rng, stack.n
    strong = tuple((rnd - 1, s) for s in range(roundpool.quorum(n)))
    kind = roundpool.KINDS[(stack.first_kind + len(stack.forged)) % len(roundpool.KINDS)]
    while True:
        honest = roundpool.sign(
            stack.keys, rnd, rng.randrange(n), (b"forged".ljust(32, b"."),), strong
        )
        wrong = roundpool.corrupt(honest, kind, n, rng)
        if wrong.source != 0:
            break
    stack.forged.append(wrong)
    return wrong


def send_forged(stack: Stack, wrong: roundpool.Signed) -> None:
    """The wrong vertex as a VAL frame to every validator but the one it
    claims to be from, relayed under that one's pair keys: what a
    validator that signs carelessly, or a peer that relays a corrupted
    copy, puts on the wire."""
    import grpc

    from dag_rider_tpu.core import codec
    from dag_rider_tpu.core.types import BroadcastMessage
    from dag_rider_tpu.transport.auth import FrameAuth

    (vertex,) = roundpool.to_vertices([wrong])
    payload = codec.encode_message(
        BroadcastMessage(vertex=vertex, round=wrong.rnd, sender=wrong.source)
    )
    with open(stack.spec.nodes[0].config) as fh:
        master = bytes.fromhex(json.load(fh)["node"]["auth_master"])
    auth = FrameAuth.for_node(master, wrong.source, stack.n)
    prefix = struct.pack("<I", wrong.source)
    for dest in range(stack.n):
        if dest == wrong.source:
            continue
        chan = stack.channels.get(dest)
        if chan is None:
            chan = stack.channels[dest] = grpc.insecure_channel(stack.spec.addresses[dest])
        call = chan.unary_unary(
            DELIVER, request_serializer=lambda b: b, response_deserializer=lambda b: b
        )
        # not waited for: a busy validator holds no other's copy back
        stack.forged_calls.append(
            call.future(prefix + payload + auth.tag(dest, payload), timeout=30.0)
        )


def _tail(path: str, offset: int) -> tuple:
    """The whole lines of ``path`` from byte ``offset`` on, and where
    they end."""
    try:
        with open(path, "rb") as fh:
            fh.seek(offset)
            data = fh.read()
    except OSError:
        return [], offset
    end = data.rfind(b"\n") + 1
    return data[:end].splitlines(), offset + end


def _settle(stack: Stack, books: Dict[str, list], offsets: List[int]) -> int:
    """Close the books of transactions newly delivered, in its own
    vertex, at the validator each was submitted to; returns how many."""
    done = 0
    for i, nf in enumerate(stack.spec.nodes):
        lines, offsets[i] = _tail(nf.delivery_log, offsets[i])
        own = f'"s": {i},'.encode()
        for line in lines:
            if own not in line:
                continue
            rec = json.loads(line)
            if rec["s"] != i:
                continue
            for tx in rec["tx"]:
                b = books.get(tx)
                if b is not None and b[2] == i and b[4] is None:
                    b[4] = rec["ts"]
                    done += 1
    return done


def run_window(stack: Stack, seconds: float, tracer=None) -> dict:
    """The measured window, the bounded drain after it, and the
    validators' clean stop. Returns what was observed, the files the
    run left read into it; nothing is compared here."""
    traffic, n = stack.traffic, stack.n
    forge_every = 1.0 / traffic["forged_vertices_per_s"]
    lead = traffic["forged_round_lead"]
    events = stack.spec.nodes[0].events_log
    calls0 = len(stack.recorder.calls)
    offsets = [os.path.getsize(nf.delivery_log) for nf in stack.spec.nodes]
    round0 = max((r for _, r in rounds_reached(events)), default=0)

    t0, t0_wall = time.monotonic(), time.time()
    for c in stack.clients:
        c.stdin.write(f"GO {t0_wall!r} {seconds!r}\n")
        c.stdin.flush()
    touched = None
    sent = 0
    while True:
        t = time.monotonic() - t0
        if t >= seconds:
            break
        if tracer is not None:
            tracer.tick(t)
        # forged vertex k is due at (k + 1/2) / rate seconds
        if int(t / forge_every + 0.5) > sent:
            with jax.profiler.TraceAnnotation("bench.forge"):
                send_forged(stack, _forge(stack, round0 + lead + sent))
            sent += 1
        if touched is None and t >= min(1.0, seconds / 2):
            pids = [p.pid for p in stack.sup.procs.values()] + [c.pid for c in stack.clients]
            touched = sum(_maps_libtpu(pid) for pid in pids)
        time.sleep(max(0.0, min(0.1, seconds - t)))
    t_close = time.monotonic()
    if tracer is not None:
        tracer.stop()
    window_s = t_close - t0
    calls_in_window = len(stack.recorder.calls)
    reached = rounds_reached(events)
    rounds = max((r for ts, r in reached if ts <= t0_wall + window_s), default=round0) - round0

    rows = []
    for c, out in zip(stack.clients, stack.client_outs):
        c.wait(timeout=DRAIN_BOUND_S)
        if c.returncode != 0:
            raise RuntimeError(f"client {c.pid} exited {c.returncode}")
        with open(out) as fh:
            rows.extend(json.load(fh)["rows"])
    size = traffic["tx_bytes"]
    #: books[tx hex] = [due, sent, validator, acknowledged, delivered stamp]
    books: Dict[str, list] = {}
    for c, head, due, at, _acked, verdict in rows:
        tx = head.encode().ljust(size, b".").hex()
        books[tx] = [due, at, c % n, verdict in ("accepted", "deduped"), None]
    owed = sum(1 for b in books.values() if b[3])
    done = _settle(stack, books, offsets)
    while done < owed and time.monotonic() - t_close < DRAIN_BOUND_S:
        time.sleep(0.25)
        done += _settle(stack, books, offsets)
    t_end_wall = time.time()
    drain_s = time.monotonic() - t_close
    forced = stack.sup.stop_all(timeout_s=60.0)

    latencies, lags = [], []
    in_window = 0
    for due, at, _, _, stamp in books.values():
        lags.append(at - due)
        if stamp is None:
            latencies.append(t_end_wall - t0_wall - due)  # it has waited this long
        else:
            latencies.append(stamp - t0_wall - due)
            if stamp <= t0_wall + seconds:
                in_window += 1
    third = seconds / 3.0
    shed = sum(1 for b in books.values() if not b[3])
    verdicts: Dict[str, int] = {}
    for row in rows:
        verdicts[row[5]] = verdicts.get(row[5], 0) + 1

    logs = [reference_cluster.read_delivery_log(nf.delivery_log) for nf in stack.spec.nodes]
    wals = []
    for nf in stack.spec.nodes:
        with open(nf.submits_wal) as fh:
            wals.append([line.strip() for line in fh])
    validator_books, finals = [], []
    for nf in stack.spec.nodes:
        validator_books.append(_read_json(nf.span_book))
        finals.append(_read_json(nf.final_report))
    calls = stack.recorder.calls
    return {
        "t_open": t0,
        "seconds": seconds,
        "attempted": len(books),
        "failed": shed + (owed - done),
        "samples": {
            "commit_latency_s": latencies,
            "commit_latency_first_third_s": [
                l for l, b in zip(latencies, books.values()) if b[0] < third
            ],
            "commit_latency_last_third_s": [
                l for l, b in zip(latencies, books.values()) if b[0] >= 2 * third
            ],
            "inject_lag_s": lags,
            "ack_latency_s": [r[4] - r[3] for r in rows if r[4] is not None],
            "server_span_s": [b - a for a, b, _, _ in calls[calls0:calls_in_window]],
            "vertices_per_rpc": [len(v) for _, _, v, _ in calls[calls0:calls_in_window]],
        },
        "counters": {
            "tx_delivered_in_window": in_window,
            "tx_shed": shed,
            "tx_undelivered": owed - done,
            "verdicts": verdicts,
            "rounds_advanced": rounds,
            "window_s": window_s,
            "drain_s": drain_s,
            "forged_sent": sent,
            "forged_frames_failed": sum(
                1 for f in stack.forged_calls if not f.done() or f.exception() is not None
            ),
            "runners_with_libtpu": touched or 0,
            "validators_killed_at_stop": len(forced),
            "bucket": getattr(stack.recorder.backend, "fixed_bucket", None),
            "sig_rejects": [
                (f or {}).get("metrics", {}).get("msgs_rejected_signature", 0) for f in finals
            ],
            "validator0_book": validator_books[0],
            "cluster_book": _sum_books(validator_books),
        },
        "books": books,
        "logs": logs,
        "wals": wals,
        "verify_calls": [(v, m) for _, _, v, m in calls],
    }


def _read_json(path: str) -> Optional[dict]:
    try:
        with open(path) as fh:
            return json.load(fh)
    except (OSError, ValueError):
        return None


def _sum_books(books: List[Optional[dict]]) -> Optional[dict]:
    """The validators' span books added up, name by name."""
    books = [b for b in books if b]
    if not books:
        return None
    spans: Dict[str, dict] = {}
    counts: Dict[str, int] = {}
    for book in books:
        for name, s in book["spans"].items():
            into = spans.setdefault(name, {"count": 0, "total_ns": 0, "max_ns": 0, "child_ns": 0})
            for k in ("count", "total_ns", "child_ns"):
                into[k] += s[k]
            into["max_ns"] = max(into["max_ns"], s["max_ns"])
        for name, k in book["counts"].items():
            counts[name] = counts.get(name, 0) + k
    return {"spans": spans, "counts": counts, "validators": len(books)}


def check(stack: Stack, observed: dict) -> dict:
    """Each number compared, beside its limit. The reference gives its
    own verdict on every vertex the sidecar was asked about in the run
    and holds each mask to it, looks for each wrong vertex sent among
    them, verifies every vertex the longest delivery log holds from that
    log's own fields, explains that log's order by DAG-Rider's rule,
    holds every other validator's log to it, and audits the acknowledged
    transactions against the WALs and the logs."""
    keys = stack.keys
    c = observed["counters"]
    mismatches = 0
    refused = set()
    for vertices, mask in observed["verify_calls"]:
        mismatches += abs(len(vertices) - len(mask))
        for v, bit in zip(vertices, mask):
            msg = reference.signing_bytes(
                v.id.round, v.id.source, v.block.transactions,
                v.strong_edges, v.weak_edges, v.coin_share or b"",
            )
            ok = keys.verify(v.id.source, msg, v.signature or b"")
            mismatches += bool(bit) != ok
            if not bit:
                refused.add((v.id.round, v.id.source, v.signature))
    unrefused = sum(
        1 for w in stack.forged if (w.rnd, w.source, w.signature) not in refused
    )
    logs = observed["logs"]
    longest = max(logs, key=len)
    order = reference.delivered_order_faults(
        [[(rec["r"], rec["s"], rec["d"]) for rec in log] for log in logs]
    )
    unexplained = reference.order_unexplained(
        [(rec["r"], rec["s"], reference_cluster.edges_of(rec)) for rec in longest],
        gc_depth=stack.config["gc_depth"],
        wave_length=stack.config["wave_length"],
    )
    acknowledged = {tx: b[2] for tx, b in observed["books"].items() if b[3]}
    txs = reference_cluster.transaction_faults(acknowledged, observed["wals"], logs)
    return {
        "mask_mismatches": {"value": mismatches, "limit": 0},
        "forged_not_refused_at_validator0": {"value": unrefused, "limit": 0},
        "delivered_bad_signatures": {
            "value": reference_cluster.bad_signatures(keys, longest), "limit": 0,
        },
        "order_unexplained": {"value": unexplained, "limit": 0},
        "views_diverged": {"value": order["views_diverged"], "limit": 0},
        "vertices_delivered_twice": {"value": order["records_twice"], "limit": 0},
        "tx_lost": {"value": txs["tx_lost"], "limit": 0},
        "tx_delivered_twice": {"value": txs["tx_delivered_twice"], "limit": 0},
        "acked_not_in_wal": {"value": txs["acked_not_in_wal"], "limit": 0},
        "runners_with_libtpu": {"value": c["runners_with_libtpu"], "limit": 0},
        "validators_killed_at_stop": {"value": c["validators_killed_at_stop"], "limit": 0},
        # a failed RPC refuses its whole batch at the validator, and the
        # sidecar's books never show it: validator 0 refused what was
        # forged and nothing else
        "validator0_rejects_off_expected": {
            "value": abs(c["sig_rejects"][0] - c["forged_sent"]), "limit": 0,
        },
    }


def close(stack: Stack) -> None:
    for c in stack.clients:
        if c.poll() is None:
            c.kill()
        c.wait()
        for pipe in (c.stdin, c.stdout):
            if pipe is not None:
                pipe.close()
    if stack.sup is not None:
        for proc in stack.sup.procs.values():
            if proc.poll() is None:
                proc.kill()
        stack.sup.stop_all(timeout_s=10.0)
    for chan in stack.channels.values():
        chan.close()
    if stack.server is not None:
        stack.server.stop()
    shutil.rmtree(stack.tmp, ignore_errors=True)
