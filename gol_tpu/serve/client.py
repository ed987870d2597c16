"""A tiny stdlib client for the serving tier (docs/SERVING.md).

``urllib`` only — the client exists for the smoke drill and the chaos
cells, not as a product surface.  The one behavior that
matters is **idempotent resubmission**: callers pass their own request
``id``, and :meth:`SimClient.submit` retries connection errors (the
server may be mid-supervised-restart) by resubmitting the same id —
admission is exactly-once on the id, so a retry can never double-run a
request.  429/503 rejections surface as :class:`Backpressure` with the
server's ``retry_after`` hint.

The client is fleet-aware (docs/SERVING.md "The fleet"), and both
behaviors are inert against a single server:

- a 307 from a front tier in direct-to-replica mode carries the routed
  replica's base URL plus the ``owner_epoch`` to stamp; ``submit``
  re-POSTs there itself (one hop, never a loop).
- a 404 from :meth:`wait_for` that carries a ``routing_epoch`` is a
  mid-handoff window, not a verdict: the poll retries until the 404
  survives an epoch CHANGE (the fleet re-resolved membership and still
  does not know the id) — a plain 404 with no epoch stays immediately
  fatal, exactly as before.
"""

from __future__ import annotations

import json
import time
import urllib.error
import urllib.request
from typing import Optional


class Backpressure(RuntimeError):
    """The server explicitly rejected (429/503) — retry later."""

    def __init__(
        self, status: int, message: str, retry_after: Optional[float]
    ) -> None:
        super().__init__(message)
        self.status = status
        self.retry_after = retry_after


class SimClient:
    def __init__(self, base_url: str, timeout: float = 30.0) -> None:
        self.base_url = base_url.rstrip("/")
        self.timeout = timeout

    def _call(self, method: str, path: str, body: Optional[dict] = None):
        data = (
            json.dumps(body).encode() if body is not None else None
        )
        url = (
            path if path.startswith("http") else self.base_url + path
        )
        req = urllib.request.Request(
            url, data=data, method=method,
            headers={"Content-Type": "application/json"},
        )
        try:
            with urllib.request.urlopen(req, timeout=self.timeout) as r:
                return r.status, json.loads(r.read() or b"{}")
        except urllib.error.HTTPError as e:
            try:
                payload = json.loads(e.read() or b"{}")
            except json.JSONDecodeError:
                payload = {"error": str(e)}
            return e.code, payload

    def submit(
        self,
        request: dict,
        connect_retries: int = 0,
        retry_delay_s: float = 0.5,
    ) -> dict:
        """POST /simulate.  ``connect_retries`` resubmits the same id
        across connection drops (supervised restarts) — safe because
        admission is idempotent on the id.  That safety is exactly why
        retries REQUIRE a caller-supplied ``id``: without one the server
        mints a fresh id per submission, so a resubmitted retry would be
        admitted (and run) twice."""
        if connect_retries > 0 and "id" not in request:
            raise ValueError(
                "connect_retries requires a caller-supplied 'id': "
                "server-generated ids make every resubmission a NEW "
                "request, so a retry would double-run it"
            )
        attempt = 0
        while True:
            try:
                status, payload = self._call(
                    "POST", "/simulate", request
                )
            except (urllib.error.URLError, ConnectionError, OSError):
                if attempt >= connect_retries:
                    raise
                attempt += 1
                time.sleep(retry_delay_s)
                continue
            if status in (200, 202):
                return payload
            if status == 307 and "replica" in payload:
                # Fleet direct-to-replica mode: the front tier answered
                # a routing hint instead of proxying.  Re-POST the body
                # to the routed replica ourselves, stamped with the id
                # the front minted and the routing epoch it pinned —
                # one hop only (a replica never answers 307 itself).
                routed = {
                    **request,
                    "id": payload["id"],
                    "owner_epoch": payload["owner_epoch"],
                }
                try:
                    status, payload = self._call(
                        "POST",
                        payload["replica"].rstrip("/") + "/simulate",
                        routed,
                    )
                except (
                    urllib.error.URLError, ConnectionError, OSError,
                ):
                    if attempt >= connect_retries:
                        raise
                    attempt += 1
                    time.sleep(retry_delay_s)
                    continue
                if status in (200, 202):
                    return payload
            if status in (429, 503):
                raise Backpressure(
                    status, payload.get("error", "rejected"),
                    payload.get("retry_after"),
                )
            raise RuntimeError(
                f"submit failed ({status}): {payload.get('error')}"
            )

    def result(self, request_id: str):
        """GET /result/<id> -> (status_code, payload)."""
        return self._call("GET", f"/result/{request_id}")

    def wait_for(
        self,
        request_id: str,
        timeout_s: float = 60.0,
        poll_s: float = 0.05,
        connect_retries: int = 0,
    ) -> dict:
        """Poll until the request reaches a terminal payload.  Connection
        drops are tolerated up to ``connect_retries`` times total (the
        supervised server may be restarting under an armed fault plan).

        Against a fleet front tier a 404 carries the ``routing_epoch``
        it was observed under; a mid-handoff poll (the id is between
        owners) must not read as lost, so the 404 only becomes fatal
        once it survives an epoch change — the membership event
        resolved and the fleet STILL does not know the id.  A 404
        without an epoch (a single server) stays immediately fatal."""
        deadline = time.time() + timeout_s
        drops = 0
        first_404_epoch: Optional[int] = None
        while time.time() < deadline:
            try:
                status, payload = self.result(request_id)
            except (urllib.error.URLError, ConnectionError, OSError):
                drops += 1
                if drops > connect_retries:
                    raise
                time.sleep(max(poll_s, 0.2))
                continue
            if status == 200:
                return payload
            if status == 404:
                epoch = payload.get("routing_epoch")
                if epoch is None:
                    raise KeyError(
                        f"server does not know {request_id!r}"
                    )
                if first_404_epoch is None:
                    first_404_epoch = epoch
                elif epoch > first_404_epoch:
                    raise KeyError(
                        f"fleet does not know {request_id!r} "
                        f"(held across routing epoch "
                        f"{first_404_epoch} -> {epoch})"
                    )
            time.sleep(poll_s)
        raise TimeoutError(
            f"request {request_id!r} not terminal after {timeout_s}s"
        )

    def trace_summary(self, request_id: str) -> dict:
        """The client-side view of its own trace (schema v12): the
        ``trace_id`` plus, once terminal, the server's latency
        decomposition — enough for a caller to log "my request spent
        X s queued, Y s computing, Z s stalled" and to hand the id to
        ``python -m gol_tpu.telemetry trace --request <id>`` for the
        full span tree.  Works mid-flight too (202 tickets carry the
        trace id; the decomposition is then empty)."""
        status, payload = self.result(request_id)
        if status == 404:
            raise KeyError(f"server does not know {request_id!r}")
        return {
            "id": request_id,
            "status": payload.get("status"),
            "trace_id": payload.get("trace_id", ""),
            "decomposition": payload.get("decomposition", {}),
        }

    def healthz(self) -> dict:
        status, payload = self._call("GET", "/healthz")
        if status != 200:
            raise RuntimeError(f"healthz returned {status}")
        return payload

    def shutdown(self) -> None:
        """Ask for a graceful drain (POST /shutdown)."""
        self._call("POST", "/shutdown")
