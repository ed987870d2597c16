"""The serving fleet: a replicated front tier (docs/SERVING.md "The fleet").

One server survives crashes (PR 12), device loss (PR 14), and dying
disks (PR 18) — but it is still one host.  The fleet lifts the same
journal-fold durability argument one level up: a stdlib front tier owns
admission for N supervised replica processes, routes every request by
consistent hash of its **bucket key** (H, W, engine) so compile caches
and bucket groups stay hot per-replica, and migrates a dead replica's
open intents instead of waiting out its supervisor restart.

Topology::

    client ──> FleetServer (front tier, 127.0.0.1)
                 │  routing: HashRing over alive replicas,
                 │  keyed by bucket (H, W, engine)
                 │  fleet journal: epoch / route / handoff records
                 ├──> replica r0  (supervise → python -m gol_tpu.serve)
                 ├──> replica r1   each with its own state dir,
                 └──> replica r2   journal, and compile caches

Three ideas carry the design:

- **Routing epoch** (the PAPERS.md "setup once, fire often" schedule,
  one level up): the consistent-hash ring is *pinned* — it only changes
  on a membership event, and every change bumps an integer epoch that
  is journaled in the front tier's own journal and stamped into every
  proxied request as ``owner_epoch``.  A front-tier crash restores its
  epoch and route map from the journal fold (:func:`fleet_replay`).
- **Handoff moves intents, never state** (the redistribution framing):
  on a ``replica_dead`` verdict from the
  :class:`gol_tpu.resilience.health.HostMonitor`, the front tier folds
  the dead replica's journal, and re-admits each open
  (admitted-but-incomplete) intent to a surviving replica under the
  SAME request id — open requests replay from their initial pattern,
  which is exact (Life is deterministic), so no board bytes move.
- **Ownership fencing** makes the migration idempotent and first-wins:
  a ``handoff`` record lands on BOTH sides (the dead replica's journal
  and the fleet's own) before the re-admit, so the original replica
  returning from supervisor restart folds its journal, finds the
  intent fenced (``owner_epoch`` < the handoff epoch), and re-runs
  nothing; a replica returning alive from a stall gets a live
  ``POST /fence`` instead.  Exactly-once holds at the *fold* level:
  even a straggler ``complete`` physically written under the old epoch
  does not count (gol_tpu/serve/journal.py).

Everything is observable: schema-v14 ``fleet`` events
(route/epoch/handoff/replica), the ``gol_fleet_*`` metrics, and
``GET /fleet/status``.  The fault sites ``replica.kill`` /
``replica.stall`` / ``fleet.partition`` fire from the front tier's
probe loop so the chaos matrix and ``scripts/fleet_smoke.py`` exercise
the real code path.  Fleet mode off changes nothing: the single-server
stack never imports this module and its journals carry no
``owner_epoch`` (the trace-identity pin in tests/test_fleet.py).
"""

from __future__ import annotations

import argparse
import bisect
import dataclasses
import glob
import hashlib
import http.server
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
from typing import Dict, List, Optional, Tuple

from gol_tpu.resilience import faults as faults_mod
from gol_tpu.resilience.health import HostMonitor
from gol_tpu.serve import journal as journal_mod
from gol_tpu.serve.client import SimClient

#: gol_tpu.ops.bitlife.BITS, restated so the front tier never imports
#: the device stack (it proxies bytes; it must start in milliseconds).
_WORD_BITS = 32


def bucket_key(
    size: int, engine: str, quantum: int
) -> Tuple[int, int, str]:
    """The routing key: the bucket the serve scheduler would group this
    request into (scheduler._group_for, restated without the device
    stack).  Serve groups always run the masked programs, so
    ``pallas_bitpack`` resolves to its documented ``bitpack`` fallback
    — identical requests land in identical groups on whichever replica
    the ring picks."""
    up = -(-size // quantum) * quantum
    packable = size % _WORD_BITS == 0
    if engine == "dense":
        name = "dense"
    elif engine == "bitpack":
        name = "bitpack"  # unpackable widths: the replica rejects (400)
    else:  # auto / pallas_bitpack — the serve fallback collapses both
        name = "bitpack" if packable else "dense"
    return (up, up, name)


class HashRing:
    """Consistent hashing over replica names (64 vnodes each).

    Rebuilt ONLY on membership change — the routing-epoch pin: between
    epochs, a bucket key always lands on the same replica, which is
    what keeps its compiled programs and bucket groups hot."""

    def __init__(self, members: List[str], vnodes: int = 64) -> None:
        ring = []
        for m in sorted(members):
            for v in range(vnodes):
                ring.append((_hash64(f"{m}#{v}"), m))
        ring.sort()
        self._hashes = [h for h, _ in ring]
        self._members = [m for _, m in ring]

    def lookup(self, key: Tuple) -> str:
        if not self._hashes:
            raise RuntimeError("hash ring is empty: no alive replicas")
        h = _hash64("|".join(str(k) for k in key))
        i = bisect.bisect_right(self._hashes, h) % len(self._hashes)
        return self._members[i]


def _hash64(s: str) -> int:
    return int.from_bytes(
        hashlib.sha256(s.encode()).digest()[:8], "big"
    )


def fleet_replay(path: str) -> Tuple[int, List[str], Dict[str, dict]]:
    """Fold the front tier's own journal: ``(epoch, members, routes)``.

    ``epoch``/``members`` come from the newest ``epoch`` record;
    ``routes`` maps request id -> ``{"replica", "bucket", "epoch"}``
    with ``handoff`` records overriding earlier routes (a handoff IS a
    re-route).  Torn lines are unacknowledged and ignored, same
    tolerance as :func:`gol_tpu.serve.journal.replay` — a front-tier
    crash+restart reconstructs its routing state from this fold."""
    epoch = 0
    members: List[str] = []
    routes: Dict[str, dict] = {}
    if not os.path.exists(path):
        return epoch, members, routes
    with open(path, encoding="utf-8", errors="replace") as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
            kind = rec.get("rec")
            if kind == "epoch":
                e = int(rec.get("epoch", 0) or 0)
                if e >= epoch:
                    epoch = e
                    members = list(rec.get("members", []))
            elif kind == "route":
                routes[rec["id"]] = {
                    "replica": rec.get("replica"),
                    "bucket": rec.get("bucket"),
                    "epoch": int(rec.get("epoch", 0) or 0),
                }
            elif kind == "handoff":
                e = int(rec.get("epoch", 0) or 0)
                r = routes.get(rec["id"])
                if r is None or e >= r["epoch"]:
                    routes[rec["id"]] = {
                        "replica": rec.get("dst"),
                        "bucket": (r or {}).get("bucket"),
                        "epoch": e,
                    }
    return epoch, members, routes


@dataclasses.dataclass
class ReplicaHandle:
    """One supervised replica as the front tier sees it."""

    name: str
    base_url: str
    state_dir: str  # the replica's --state-dir (its journal lives here)
    manifest: str = ""  # supervisor manifest (live attempt's pid)
    proc: Optional[subprocess.Popen] = None  # the supervisor process

    @property
    def journal_path(self) -> str:
        return os.path.join(self.state_dir, "journal.jsonl")


class FleetFront:
    """The fleet state machine (transport-free core).

    :class:`FleetServer` puts HTTP in front of it; the chaos cells
    drive it in-process.  Thread model mirrors the serve
    scheduler: handler threads call :meth:`submit` / :meth:`result`
    through the lock; the owner's main loop calls :meth:`poll`.
    """

    def __init__(
        self,
        replicas: List[ReplicaHandle],
        state_dir: str,
        quantum: int = 64,
        default_engine: str = "auto",
        events=None,
        registry=None,
        monitor: Optional[HostMonitor] = None,
        client_timeout: float = 30.0,
        probe_timeout: float = 2.0,
    ) -> None:
        if not replicas:
            raise ValueError("a fleet needs at least one replica")
        self.replicas = {r.name: r for r in replicas}
        self.state_dir = state_dir
        self.quantum = quantum
        self.default_engine = default_engine
        self._events = events
        self._registry = registry
        self._lock = threading.RLock()
        self._clients = {
            r.name: SimClient(r.base_url, timeout=client_timeout)
            for r in replicas
        }
        # Heartbeats get their own short-timeout clients: a probe into
        # a frozen replica must read as a missed beat in ~probe_timeout,
        # not hang the whole probe loop for the proxy timeout.
        self._probe_clients = {
            r.name: SimClient(r.base_url, timeout=probe_timeout)
            for r in replicas
        }
        self._monitor = monitor or HostMonitor(
            [r.name for r in replicas], events=events, registry=registry
        )
        self._journal = journal_mod.Journal(
            os.path.join(state_dir, "fleet.journal.jsonl")
        )
        # A restarted front tier restores its epoch and route map from
        # its own journal fold, then ALWAYS bumps: membership was
        # re-formed, and requests proxied before the crash must be
        # distinguishable from requests proxied after it.
        prev_epoch, _members, routes = fleet_replay(self._journal.path)
        self._routes = routes  # id -> {"replica", "bucket", "epoch"}
        self._epoch = prev_epoch
        self._ring = HashRing(self._monitor.alive)
        # Ids migrated OFF a replica while it was out, fenced live on
        # its restore (a stall survivor holds them in memory; a journal
        # fold only fences a restart).
        self._migrated: Dict[str, set] = {}
        # Re-admissions that could not land yet (target busy /
        # unreachable): retried every poll until they stick.
        self._pending: List[dict] = []
        self._partitioned_until: Dict[str, float] = {}
        self._stalled_until: Dict[str, float] = {}  # SIGCONT due times
        self._seq = 0
        self._tick = 0
        self.routed_total = 0
        self.handoffs_total = 0
        self.draining = False
        self._bump_epoch("boot")

    # -- epoch / emission -----------------------------------------------------

    def _bump_epoch(self, reason: str) -> None:
        with self._lock:
            self._epoch += 1
            members = self._monitor.alive
            self._ring = HashRing(members)
            self._journal.append(
                journal_mod.record(
                    "epoch", f"epoch-{self._epoch}",
                    epoch=self._epoch, members=members, reason=reason,
                )
            )
            self._emit(
                "epoch", epoch=self._epoch, members=members,
                reason=reason,
            )

    def _emit(self, action: str, **fields) -> None:
        if self._events is not None:
            self._events.fleet_event(action, **fields)
        elif self._registry is not None:
            self._registry.observe(
                dict(event="fleet", action=action, **fields)
            )

    @property
    def epoch(self) -> int:
        return self._epoch

    @property
    def alive(self) -> List[str]:
        return self._monitor.alive

    # -- admission ------------------------------------------------------------

    def submit(self, body, direct: bool = False) -> Tuple[int, dict]:
        """Route one request: ``(status, payload)``.

        Proxy mode forwards to the routed replica and relays its
        answer; ``direct`` mode answers 307 with the replica hint — the
        client re-POSTs there itself (one less proxy hop per request;
        the route is journaled either way).  Both stamp the current
        routing epoch into the proxied body as ``owner_epoch``."""
        if not isinstance(body, dict):
            return 400, {"error": "request body must be a JSON object"}
        if self.draining:
            return 503, {
                "error": "fleet draining", "retry_after": 5.0,
                "routing_epoch": self._epoch,
            }
        size = body.get("size")
        engine = body.get("engine", self.default_engine)
        if not isinstance(size, int) or isinstance(size, bool) or size < 1:
            return 400, {
                "error": f"'size' must be an integer >= 1, got {size!r}"
            }
        with self._lock:
            rid = body.get("id")
            if rid is None:
                # The front tier NEEDS an id (the route map keys on it),
                # so unlike the single server it mints before routing
                # and the replica sees a caller-supplied id.
                self._seq += 1
                rid = f"flt-{os.getpid()}-{self._seq:06d}"
            key = bucket_key(size, engine, self.quantum)
            bucket = f"{key[0]}x{key[1]}:{key[2]}"
            replica = self._ring.lookup(key)
            epoch = self._epoch
            self._journal.append(
                journal_mod.record(
                    "route", rid, bucket=bucket, replica=replica,
                    epoch=epoch,
                )
            )
            self._routes[rid] = {
                "replica": replica, "bucket": bucket, "epoch": epoch,
            }
            self.routed_total += 1
            self._emit(
                "route", request_id=rid, bucket=bucket,
                replica=replica, epoch=epoch,
            )
            client = self._clients[replica]
            base_url = self.replicas[replica].base_url
        out = {**body, "id": rid, "owner_epoch": epoch}
        if direct:
            return 307, {
                "replica": base_url, "id": rid,
                "owner_epoch": epoch, "routing_epoch": epoch,
                "bucket": bucket,
            }
        try:
            status, payload = client._call("POST", "/simulate", out)
        except OSError:
            # The replica died between verdicts.  The admit never
            # landed, so this is a clean backpressure reject — the
            # client resubmits the same id and the NEXT route (post
            # handoff epoch) wins.
            return 503, {
                "error": f"replica {replica} unreachable; retry",
                "retry_after": 1.0, "routing_epoch": epoch, "id": rid,
            }
        if isinstance(payload, dict):
            payload.setdefault("routing_epoch", epoch)
        return status, payload

    def result(self, request_id: str) -> Tuple[int, dict]:
        with self._lock:
            route = self._routes.get(request_id)
            epoch = self._epoch
            if route is None:
                return 404, {
                    "error": f"unknown request {request_id!r}",
                    "routing_epoch": epoch,
                }
            name = route["replica"]
            blind = (
                not self._monitor.is_alive(name)
                or self._partitioned_until.get(name, 0.0) > time.time()
            )
            client = self._clients[name]
        if blind:
            # Mid-handoff: the owner is out and the migration has not
            # (re)settled.  Never a 404 — the intent is journaled.
            return 202, {
                "id": request_id, "status": "migrating",
                "routing_epoch": epoch,
            }
        try:
            status, payload = client.result(request_id)
        except OSError:
            return 202, {
                "id": request_id, "status": "migrating",
                "routing_epoch": epoch,
            }
        if isinstance(payload, dict) and status != 200:
            payload.setdefault("routing_epoch", epoch)
        return status, payload

    # -- the probe loop -------------------------------------------------------

    def poll(self) -> None:
        """One probe round: fire armed fleet faults, heartbeat every
        replica, react to the monitor's verdicts, retry stranded
        re-admissions.  The owner calls this every probe interval."""
        self._tick += 1
        tick = self._tick
        self._fire_faults(tick)
        now = time.time()
        for name, due in list(self._stalled_until.items()):
            if now >= due:
                del self._stalled_until[name]
                self._signal_replica(name, signal.SIGCONT)
        for name in sorted(self.replicas):
            if self._partitioned_until.get(name, 0.0) > now:
                verdicts = self._monitor.beat(name, ok=False, tick=tick)
            else:
                t0 = time.time()
                try:
                    self._probe_clients[name].healthz()
                    ok, lat = True, time.time() - t0
                except Exception:
                    ok, lat = False, 0.0
                verdicts = self._monitor.beat(
                    name, ok, latency_s=lat, tick=tick
                )
            for v in verdicts:
                if v.kind == "replica_dead":
                    self._on_dead(name)
                elif v.kind == "replica_restore":
                    self._on_restore(name)
        self._retry_pending()

    def _fire_faults(self, tick: int) -> None:
        names = sorted(self.replicas)
        spec = faults_mod.fire("replica.kill", tick)
        if spec is not None:
            # Real process death: the supervisor restarts it, and the
            # restart's journal fold must find its intents fenced.
            self._signal_replica(
                names[spec.device % len(names)], signal.SIGKILL
            )
        spec = faults_mod.fire("replica.stall", tick)
        if spec is not None:
            # A real freeze (SIGSTOP, SIGCONT after delay_s): the
            # process keeps its memory, wakes mid-batch, and its late
            # journal writes must lose to the handoff at fold level.
            name = names[spec.device % len(names)]
            self._signal_replica(name, signal.SIGSTOP)
            self._stalled_until[name] = (
                time.time() + max(spec.delay_s, 0.0)
            )
        spec = faults_mod.fire("fleet.partition", tick)
        if spec is not None:
            # One-sided cut: the front goes blind for delay_s while the
            # replica stays healthy AND KEEPS EXECUTING — the hardest
            # exactly-once case (a live owner that looks dead).
            name = names[spec.device % len(names)]
            self._partitioned_until[name] = (
                time.time() + max(spec.delay_s, 0.0)
            )

    def _signal_replica(self, name: str, sig: int) -> None:
        handle = self.replicas[name]
        try:
            with open(handle.manifest) as f:
                pid = json.load(f)["attempts"][-1]["pid"]
            os.kill(pid, sig)
        except (OSError, KeyError, IndexError, ValueError,
                json.JSONDecodeError):
            pass  # already gone — the probe loop finds out either way

    # -- membership transitions ----------------------------------------------

    def _on_dead(self, name: str) -> None:
        with self._lock:
            self._bump_epoch(f"replica_dead:{name}")
            self._migrate(name)

    def _on_restore(self, name: str) -> None:
        with self._lock:
            self._bump_epoch(f"replica_restore:{name}")
            ids = sorted(self._migrated.pop(name, ()))
            epoch = self._epoch
            client = self._clients[name]
        if ids:
            # The journal fold fences a RESTARTED replica; a replica
            # back from a stall still holds the migrated intents live
            # in memory — the fence endpoint drops (and journals) them.
            try:
                client._call(
                    "POST", "/fence", {"ids": ids, "epoch": epoch}
                )
            except OSError:
                pass  # its own journal fold fences on the next restart

    def _migrate(self, name: str) -> None:
        """Move the dead replica's open intents to survivors — intent
        records only, never board state (open requests replay from
        their initial pattern, which is exact)."""
        handle = self.replicas[name]
        entries, _torn = journal_mod.replay(handle.journal_path)
        alive = self._monitor.alive
        epoch = self._epoch
        moved = self._migrated.setdefault(name, set())
        for rid, e in entries.items():
            if e["status"] not in ("admitted", "started"):
                continue  # completed results are durable — never moved
            req = dict(e["admit"].get("request") or {})
            key = bucket_key(
                int(req.get("size", 1) or 1),
                req.get("engine") or self.default_engine,
                self.quantum,
            )
            dst = self._ring.lookup(key) if alive else None
            handoff = journal_mod.record(
                "handoff", rid, epoch=epoch, src=name, dst=dst,
                by="fleet",
            )
            # Both sides, fence FIRST: the dead replica's journal (so
            # its restart fold finds ownership moved before any re-run
            # could journal), then the fleet's own (so a front restart
            # re-resolves the route).
            _append_foreign(handle.journal_path, handoff)
            self._journal.append(handoff)
            moved.add(rid)
            self.handoffs_total += 1
            bucket = f"{key[0]}x{key[1]}:{key[2]}"
            self._emit(
                "handoff", request_id=rid, src=name, dst=dst,
                epoch=epoch, bucket=bucket,
            )
            if dst is None:
                continue  # no survivors: routes stay parked on None
            self._routes[rid] = {
                "replica": dst, "bucket": bucket, "epoch": epoch,
            }
            self._pending.append(
                {
                    "id": rid, "dst": dst,
                    "body": {**req, "id": rid, "owner_epoch": epoch},
                }
            )

    def _retry_pending(self) -> None:
        with self._lock:
            pending, self._pending = self._pending, []
        for item in pending:
            dst = item["dst"]
            ok = False
            if self._monitor.is_alive(dst):
                try:
                    status, _payload = self._clients[dst]._call(
                        "POST", "/simulate", item["body"]
                    )
                    ok = status in (200, 202)
                except OSError:
                    ok = False
            else:
                # The target died too; re-route at the current epoch.
                with self._lock:
                    route = self._routes.get(item["id"])
                    if route is not None and route["replica"] != dst:
                        item["dst"] = route["replica"]
                        item["body"]["owner_epoch"] = route["epoch"]
            if not ok:
                with self._lock:
                    self._pending.append(item)

    # -- status / shutdown ----------------------------------------------------

    def status(self) -> dict:
        with self._lock:
            return {
                "epoch": self._epoch,
                "replicas": sorted(self.replicas),
                "alive": self._monitor.alive,
                "routed_total": self.routed_total,
                "handoffs_total": self.handoffs_total,
                "routes": len(self._routes),
                "pending_readmits": len(self._pending),
                "draining": self.draining,
            }

    def outstanding_pending(self) -> int:
        with self._lock:
            return len(self._pending)

    def drain(self, timeout_s: float = 60.0) -> None:
        """Graceful fleet drain: stop admitting, ask every replica to
        drain, wait for the supervisors to exit 0."""
        with self._lock:
            if self.draining:
                return
            self.draining = True
        self._emit("drain", epoch=self._epoch)
        for name in list(self._stalled_until):
            del self._stalled_until[name]
            self._signal_replica(name, signal.SIGCONT)
        for name in sorted(self.replicas):
            try:
                self._clients[name].shutdown()
            except Exception:
                pass
        deadline = time.time() + timeout_s
        for name in sorted(self.replicas):
            proc = self.replicas[name].proc
            if proc is None:
                continue
            try:
                proc.wait(timeout=max(deadline - time.time(), 0.1))
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait(timeout=5.0)

    def close(self) -> None:
        self._journal.close()


def _append_foreign(path: str, rec: dict) -> None:
    """Append one record into ANOTHER process's journal (the handoff
    write into a dead replica's file).  Heals a torn tail first — the
    replica may have died mid-append — with the same newline discipline
    as :meth:`Journal.append`, then fsyncs per record."""
    heal = False
    if os.path.exists(path) and os.path.getsize(path):
        with open(path, "rb") as f:
            f.seek(-1, os.SEEK_END)
            heal = f.read(1) != b"\n"
    line = json.dumps(rec, sort_keys=True)
    with open(path, "ab") as f:
        if heal:
            f.write(b"\n")
        f.write(line.encode() + b"\n")
        f.flush()
        os.fsync(f.fileno())


# -- HTTP ---------------------------------------------------------------------


class _FleetHandler(http.server.BaseHTTPRequestHandler):
    # Set on the per-server class copy by FleetServer:
    front: FleetFront
    registry = None
    stop_event: threading.Event
    direct: bool = False

    def _json(self, status: int, payload: dict, location=None) -> None:
        body = json.dumps(payload, sort_keys=True).encode()
        self.send_response(status)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        if location is not None:
            self.send_header("Location", location)
        self.end_headers()
        self.wfile.write(body)

    def log_message(self, *args):  # silence per-request stderr noise
        pass

    def do_GET(self):  # noqa: N802 - http.server API
        path = self.path.rstrip("/")
        front = self.front
        if path == "/healthz":
            self._json(
                200,
                {
                    "ok": True,
                    "alive": len(front.alive),
                    "replicas": len(front.replicas),
                    "epoch": front.epoch,
                    "draining": front.draining,
                },
            )
        elif path == "/readyz":
            alive = len(front.alive)
            ready = alive >= 1 and not front.draining
            self._json(
                200 if ready else 503,
                {
                    "ready": ready,
                    # Degraded = serving with reduced capacity; the
                    # smoke drill asserts this flips on and back off
                    # across a replica kill.
                    "degraded": alive < len(front.replicas),
                    "alive": alive,
                    "replicas": len(front.replicas),
                    "epoch": front.epoch,
                    "draining": front.draining,
                },
            )
        elif path == "/metrics":
            if self.registry is None:
                self.send_error(404, "no metrics registry attached")
                return
            from gol_tpu.telemetry.metrics import CONTENT_TYPE

            body = self.registry.render().encode()
            self.send_response(200)
            self.send_header("Content-Type", CONTENT_TYPE)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
        elif path == "/fleet/status":
            self._json(200, front.status())
        elif path.startswith("/result/"):
            status, payload = front.result(path[len("/result/"):])
            self._json(status, payload)
        else:
            self.send_error(
                404,
                "routes: /simulate /result/<id> /healthz /readyz "
                "/metrics /fleet/status",
            )

    def do_POST(self):  # noqa: N802 - http.server API
        path = self.path.rstrip("/")
        if path == "/simulate":
            try:
                length = int(self.headers.get("Content-Length", "0"))
                body = json.loads(self.rfile.read(length)) if length else {}
            except (ValueError, json.JSONDecodeError) as e:
                self._json(400, {"error": f"body is not valid JSON: {e}"})
                return
            status, payload = self.front.submit(body, direct=self.direct)
            if status == 307:
                self._json(
                    307, payload,
                    location=payload["replica"] + "/simulate",
                )
            else:
                self._json(status, payload)
        elif path == "/shutdown":
            self.stop_event.set()
            self._json(200, {"ok": True, "draining": True})
        else:
            self.send_error(404, "POST routes: /simulate /shutdown")


class FleetServer:
    """Threaded HTTP listener over one :class:`FleetFront`."""

    def __init__(
        self, front: FleetFront, port: int, registry=None,
        direct: bool = False,
    ) -> None:
        self.stop_event = threading.Event()
        handler = type(
            "BoundFleetHandler",
            (_FleetHandler,),
            {
                "front": front,
                "registry": registry,
                "stop_event": self.stop_event,
                "direct": direct,
            },
        )
        self._httpd = http.server.ThreadingHTTPServer(
            ("127.0.0.1", port), handler
        )
        self._httpd.daemon_threads = True
        self._thread = threading.Thread(
            target=self._httpd.serve_forever,
            name="gol-fleet-http",
            daemon=True,
        )
        self._thread.start()

    @property
    def port(self) -> int:
        return self._httpd.server_address[1]

    def close(self) -> None:
        self._httpd.shutdown()
        self._httpd.server_close()
        self._thread.join(timeout=5.0)


# -- process management / CLI -------------------------------------------------


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def tpu_chip_count(env) -> int:
    """TPU chips on this host, read from its device files, without
    touching JAX (a parent that initialized a backend would hold the
    chips its replicas need).  0 when ``JAX_PLATFORMS`` rules the TPU
    out, as the tests' CPU runs do."""
    platforms = env.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms:
        return 0
    return len(glob.glob("/dev/accel[0-9]*")) or len(
        glob.glob("/dev/vfio/[0-9]*")
    )


def replica_envs(n: int, env, chips: int) -> List[Dict[str, str]]:
    """One environment per replica.  On a TPU host a chip belongs to one
    process, so replica k sees chip k alone (the TPU visible-chip
    environment) and more replicas than chips is refused.  CPU runs get
    ``env`` unchanged."""
    if not chips:
        return [dict(env) for _ in range(n)]
    if n > chips:
        raise ValueError(
            f"{n} TPU replicas need {n} chips, and this host has {chips}: "
            "a chip belongs to one process"
        )
    return [
        dict(
            env,
            TPU_VISIBLE_CHIPS=str(k),
            TPU_CHIPS_PER_PROCESS_BOUNDS="1,1,1",
            TPU_PROCESS_BOUNDS="1,1,1",
            TPU_PROCESS_PORT=str(_free_port()),
            # The libtpu lock file is per host; the visible-chip split
            # above is what keeps the replicas off each other's chip.
            ALLOW_MULTIPLE_LIBTPU_LOAD="1",
        )
        for k in range(n)
    ]


def spawn_replicas(ns, state_dir: str) -> List[ReplicaHandle]:
    """Launch N supervised replicas (``supervise -- python -m
    gol_tpu.serve``), each with its own state dir, port and — on a TPU
    host — chip (:func:`replica_envs`).  The children must NOT inherit
    the fleet's fault plan (the fleet fires ``replica.*`` sites itself —
    an inherited plan would re-arm inside every replica) nor a stale
    restart counter."""
    env = {
        k: v
        for k, v in os.environ.items()
        if k not in ("GOL_FAULT_PLAN", "GOL_RESTART_ATTEMPT")
    }
    envs = replica_envs(ns.replicas, env, tpu_chip_count(env))
    replicas = []
    for k in range(ns.replicas):
        name = f"r{k}"
        rdir = os.path.join(state_dir, name)
        os.makedirs(rdir, exist_ok=True)
        port = _free_port()
        manifest = os.path.join(rdir, "manifest.json")
        cmd = [
            sys.executable, "-m", "gol_tpu.resilience", "supervise",
            "--max-restarts", str(ns.max_restarts),
            "--backoff-base", "0.05",
            "--manifest", manifest,
            "--",
            sys.executable, "-m", "gol_tpu.serve",
            "--state-dir", rdir,
            "--port", str(port),
            "--slots", str(ns.slots),
            "--queue-depth", str(ns.queue_depth),
            "--chunk", str(ns.chunk),
            "--bucket-quantum", str(ns.bucket_quantum),
            "--engine", ns.engine,
        ]
        proc = subprocess.Popen(
            cmd, env=envs[k],
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
        )
        replicas.append(
            ReplicaHandle(
                name=name,
                base_url=f"http://127.0.0.1:{port}",
                state_dir=rdir,
                manifest=manifest,
                proc=proc,
            )
        )
    return replicas


def wait_replicas_healthy(
    replicas: List[ReplicaHandle], timeout_s: float = 60.0
) -> None:
    deadline = time.time() + timeout_s
    for r in replicas:
        client = SimClient(r.base_url, timeout=5.0)
        while True:
            try:
                client.healthz()
                break
            except Exception:
                if time.time() > deadline:
                    raise TimeoutError(
                        f"replica {r.name} not healthy after {timeout_s}s"
                    )
                time.sleep(0.1)


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m gol_tpu.serve.fleet",
        description="replicated serving front tier "
        '(docs/SERVING.md, "The fleet")',
    )
    p.add_argument(
        "--state-dir", required=True,
        help="fleet root: the front tier's journal plus one replica "
        "state dir per replica (r0/, r1/, ...)",
    )
    p.add_argument("--port", type=int, default=0)
    p.add_argument(
        "--replicas", type=int, default=3,
        help="supervised replica processes to spawn (default 3)",
    )
    p.add_argument("--slots", type=int, default=4)
    p.add_argument("--queue-depth", type=int, default=8)
    p.add_argument("--chunk", type=int, default=4)
    p.add_argument("--bucket-quantum", type=int, default=64)
    p.add_argument(
        "--engine", default="auto",
        choices=["auto", "dense", "bitpack", "pallas_bitpack"],
    )
    p.add_argument(
        "--probe-interval", type=float, default=0.25,
        help="seconds between /healthz probe rounds (default 0.25)",
    )
    p.add_argument("--miss-threshold", type=int, default=3)
    p.add_argument("--restore-beats", type=int, default=2)
    p.add_argument("--max-restarts", type=int, default=3)
    p.add_argument(
        "--direct", action="store_true",
        help="307 direct-to-replica mode: answer routing hints instead "
        "of proxying request bodies (clients re-POST themselves)",
    )
    p.add_argument(
        "--telemetry", default=None,
        help="front-tier event stream dir (default: "
        "<state-dir>/telemetry; 'none' disables)",
    )
    p.add_argument("--run-id", default=None)
    p.add_argument(
        "--fault-plan", default=None,
        help="fault plan for the FLEET's own sites (replica.kill / "
        "replica.stall / fleet.partition); never inherited by replicas",
    )
    return p


def main(argv=None) -> int:
    ns = build_parser().parse_args(argv)

    try:
        if ns.fault_plan:
            faults_mod.install(faults_mod.FaultPlan.load(ns.fault_plan))
        else:
            faults_mod.install_from_env()
    except faults_mod.FaultPlanError as e:
        print(e)
        return 255

    from gol_tpu.telemetry.metrics import MetricsRegistry

    os.makedirs(ns.state_dir, exist_ok=True)
    telemetry_dir = ns.telemetry
    if telemetry_dir is None:
        telemetry_dir = os.path.join(ns.state_dir, "telemetry")
    elif telemetry_dir == "none":
        telemetry_dir = None

    registry = MetricsRegistry()
    events = None
    if telemetry_dir:
        from gol_tpu import telemetry as telemetry_mod

        events = telemetry_mod.EventLog(
            telemetry_dir, run_id=ns.run_id, process_index=0
        )
        events.observer = registry.observe
        events.on_shed = registry.count_shed
        events.run_header(
            {
                "driver": "fleet",
                "replicas": ns.replicas,
                "engine": ns.engine,
                "bucket_quantum": ns.bucket_quantum,
                "probe_interval_s": ns.probe_interval,
            },
            devices=False,
        )

    replicas = spawn_replicas(ns, ns.state_dir)
    try:
        wait_replicas_healthy(replicas)
        monitor = HostMonitor(
            [r.name for r in replicas],
            miss_threshold=ns.miss_threshold,
            restore_beats=ns.restore_beats,
            events=events,
            registry=registry,
        )
        front = FleetFront(
            replicas,
            ns.state_dir,
            quantum=ns.bucket_quantum,
            default_engine=ns.engine,
            events=events,
            registry=registry,
            monitor=monitor,
        )
        server = FleetServer(
            front, ns.port, registry=registry, direct=ns.direct
        )
        stop = server.stop_event

        def _graceful(signum, frame):
            stop.set()

        signal.signal(signal.SIGTERM, _graceful)
        signal.signal(signal.SIGINT, _graceful)

        print(
            f"fleet: listening on http://127.0.0.1:{server.port} "
            f"({ns.replicas} replicas, state {ns.state_dir})",
            flush=True,
        )
        try:
            while not stop.is_set():
                front.poll()
                time.sleep(ns.probe_interval)
        finally:
            front.drain()
            server.close()
            front.close()
            if events is not None:
                events.close()
        print("fleet: drained; exiting", flush=True)
        return 0
    except BaseException:
        for r in replicas:
            if r.proc is not None and r.proc.poll() is None:
                r.proc.kill()
        raise


if __name__ == "__main__":
    sys.exit(main())
