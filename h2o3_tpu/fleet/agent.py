"""Replica-side fleet agent: join, warm up, heartbeat, leave.

The reference's every-node heartbeat thread (SURVEY §L1) — each serve
replica runs one :class:`FleetAgent` that:

1. **joins** the router found at the first reachable
   ``H2O3_FLEET_SEEDS`` entry (``POST /3/Fleet/join``), admitted as
   ``joining`` — NOT routable;
2. **pre-warms** before taking traffic (warm cold-start): the join
   response carries the fleet's registry snapshot, and the agent
   deploys every model it can resolve with ``warm=True`` — compiles
   land in the shared persistent compile cache
   (``JAX_COMPILATION_CACHE_DIR``, cluster_boot.setup_compilation_cache),
   so a restarted replica's warmup is a cache read, and the first
   ROUTED request compiles zero XLA modules;
3. **heartbeats** every ``H2O3_FLEET_HEARTBEAT_MS``: incarnation token
   (epoch fence), batcher load, deployment list, and this replica's
   circuit-breaker states (``serve.circuit_states()``) — the push
   gossip channel. The response piggybacks every PEER's circuit state,
   which feeds ``serve.fleet.observe_peer_states`` so an open circuit
   anywhere sheds load here within two beats (sub-scrape latency; the
   telemetry-scrape pull in serve/fleet.py is now the fallback);
4. on a 409 (stale incarnation — this agent was evicted, e.g. a long
   GC pause or network partition healed) it **re-joins** with a fresh
   incarnation rather than beating into the void;
5. **leaves** gracefully on ``stop()`` so the router evicts nothing
   and peers expire this source's gossip immediately.

All agent→router HTTP rides ``resilience.retry_transient`` with an
explicit deadline (fleet-peer-discipline).
"""
from __future__ import annotations

import json
import os
import socket
import threading
import urllib.request
from typing import Dict, List, Optional

from h2o3_tpu.fleet.membership import heartbeat_ms, seeds

__all__ = ["FleetAgent"]


def _default_member_id() -> str:
    try:
        host = socket.gethostname()
    except OSError:
        host = "?"
    return f"{os.getpid()}@{host}"


def _post_json(url: str, payload: dict, *, timeout_s: float,
               site: str, attempts: int = 3) -> dict:
    """One control-plane POST behind the shared transient-retry policy.
    The socket timeout doubles as the per-attempt deadline; the whole
    call is bounded by retry_transient's backoff schedule."""
    from h2o3_tpu import resilience
    data = json.dumps(payload).encode()

    def _call():
        req = urllib.request.Request(
            url, data=data, method="POST",
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=timeout_s) as r:
            return json.loads(r.read().decode())

    return resilience.retry_transient(_call, site=site, attempts=attempts)


class FleetAgent:
    def __init__(self, base_url: str, *,
                 router_url: Optional[str] = None,
                 member_id: Optional[str] = None,
                 heartbeat_s: Optional[float] = None,
                 prewarm: bool = True):
        self.base_url = base_url.rstrip("/")
        self.member_id = member_id or _default_member_id()
        self.heartbeat_s = float(heartbeat_s if heartbeat_s is not None
                                 else heartbeat_ms() / 1000.0)
        self._router_url = (router_url.rstrip("/") if router_url
                            else None)
        # router-tier rotation (ISSUE 20): index into _router_urls();
        # a connect-class beat failure advances it so the beat stream
        # fails over to a peer router carrying the SAME incarnation
        # token (peer routers absorb tokens via gossip, so no rejoin)
        self._url_idx = 0
        self.prewarm = bool(prewarm)
        self.incarnation: Optional[int] = None
        self.routable = False
        self.last_error: Optional[str] = None
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # -- control plane ---------------------------------------------------

    def _router_urls(self) -> List[str]:
        """Every router this agent may talk to: the explicit url (if
        any) followed by all H2O3_FLEET_SEEDS entries, deduped (the
        only env-sourced peer read lives in membership.seeds). With a
        router TIER behind the seeds, any entry accepts this agent's
        beats — the tier gossips incarnations, so failing the stream
        over needs no rejoin."""
        urls: List[str] = []
        if self._router_url:
            urls.append(self._router_url)
        for s in seeds():
            u = s if s.startswith(("http://", "https://")) \
                else f"http://{s}"
            u = u.rstrip("/")
            if u not in urls:
                urls.append(u)
        if not urls:
            raise RuntimeError(
                "no fleet router configured — pass router_url or set "
                "H2O3_FLEET_SEEDS=host:port[,host:port]")
        return urls

    def router_url(self) -> str:
        """The CURRENT router endpoint (rotation advances on connect
        failure — see :meth:`_rotate_router`)."""
        urls = self._router_urls()
        return urls[self._url_idx % len(urls)]

    def _rotate_router(self, reason: str) -> None:
        """Advance the beat stream to the next router in the tier.
        A no-op with a single configured router; records a
        ``router_handoff`` flight-recorder event otherwise — the
        post-mortem's 'which front door heard this replica when'."""
        urls = self._router_urls()
        if len(urls) < 2:
            return
        old = urls[self._url_idx % len(urls)]
        self._url_idx = (self._url_idx + 1) % len(urls)
        new = urls[self._url_idx % len(urls)]
        try:
            from h2o3_tpu.telemetry import blackbox
            blackbox.record("router_handoff", self.member_id,
                            payload=f"from={old} to={new} "
                                    f"reason={reason}")
        except Exception:   # noqa: BLE001 — recorder is advisory
            pass

    @staticmethod
    def _note_epoch(out: dict) -> None:
        """Stamp the fleet epoch from a join/heartbeat response into
        serve.fleet so scoring responses can carry it
        (``X-H2O3-Fleet-Epoch`` — the client-affinity staleness
        signal)."""
        try:
            from h2o3_tpu.serve import fleet as serve_fleet
            ep = out.get("epoch")
            if ep is not None:
                serve_fleet.note_fleet_epoch(int(ep))
        except Exception:   # noqa: BLE001 — the header is advisory
            pass

    def join(self) -> dict:
        """Announce this replica; returns the join response (epoch,
        incarnation, registry snapshot). Deployment list reflects what
        is ALREADY deployed locally — prewarm() below may grow it
        before the routable beat."""
        from h2o3_tpu import serve
        body = {
            "member_id": self.member_id,
            "base_url": self.base_url,
            "heartbeat_ms": self.heartbeat_s * 1000.0,
            "deployments": [d.key for d in serve.deployments()],
            "routable": False,
        }
        urls = self._router_urls()
        out = None
        last: Optional[BaseException] = None
        for i in range(len(urls)):
            url = urls[self._url_idx % len(urls)]
            try:
                out = _post_json(f"{url}/3/Fleet/join", body,
                                 timeout_s=max(self.heartbeat_s * 4, 2.0),
                                 site="fleet.join",
                                 attempts=1 if len(urls) > 1 else 3)
                break
            except Exception as e:   # noqa: BLE001 — try the next router
                last = e
                if i < len(urls) - 1:
                    self._rotate_router(f"join: {type(e).__name__}")
        if out is None:
            raise last if last is not None else RuntimeError(
                "fleet join failed with no router reachable")
        self.incarnation = int(out.get("incarnation", 0))
        self._note_epoch(out)
        try:
            # stamp the flight recorder's ambient identity: every event
            # this replica appends from now on carries the admitted
            # epoch + incarnation (the merge's causal fence)
            from h2o3_tpu.telemetry import blackbox
            blackbox.set_identity(epoch=int(out.get("epoch", 0) or 0),
                                  incarnation=self.incarnation)
        except Exception:   # noqa: BLE001 — flight recorder is advisory
            pass
        return out

    def _prewarm(self, snapshot: Optional[dict]) -> dict:
        """Warm cold-start: deploy everything in the fleet registry
        snapshot that this process can resolve, compile-warm, BEFORE
        the routable beat. Never raises — a model this replica cannot
        resolve is reported, not fatal (the router simply won't route
        that model here, via the heartbeat's deployment list)."""
        from h2o3_tpu import serve
        if not snapshot:
            return {"deployed": [], "skipped": []}
        try:
            return serve.prewarm_from_snapshot(snapshot)
        except Exception as e:   # noqa: BLE001 — warmup is best-effort
            self.last_error = f"prewarm: {e!r}"
            return {"deployed": [], "skipped": [], "error": repr(e)}

    def _beat_payload(self) -> dict:
        import time
        from h2o3_tpu import serve
        deps = serve.deployments()
        load = max((d.batcher.load_factor for d in deps), default=0.0)
        payload = {
            "member_id": self.member_id,
            "incarnation": self.incarnation,
            "load": round(load, 4),
            "deployments": [d.key for d in deps],
            "circuit": serve.circuit_states(),
            "routable": self.routable,
            # the heartbeat exchange doubles as the cluster timeline's
            # skew estimator: the router subtracts its receipt wall
            # clock from this stamp (ISSUE 19 flight recorder)
            "wall": time.time(),
        }
        try:
            # fleet-scheduler gossip: admission headroom, per-class
            # queue depths, running count (versioned; a beat without it
            # just marks this replica local-only — never fails the beat)
            from h2o3_tpu.fleet import sched as fleet_sched
            payload["sched"] = fleet_sched.local_sched_payload()
        except Exception as e:   # noqa: BLE001 — beats outrank gossip
            self.last_error = f"sched payload: {e!r}"
        return payload

    def beat_once(self) -> bool:
        """One heartbeat; ingests the response's piggybacked peer
        circuit gossip. Returns False when the beat could not be
        delivered (the loop just tries again next tick) and re-joins
        on an incarnation fence rejection."""
        import urllib.error
        from h2o3_tpu.serve import fleet as serve_fleet
        try:
            out = _post_json(
                f"{self.router_url()}/3/Fleet/heartbeat",
                self._beat_payload(),
                timeout_s=max(self.heartbeat_s * 2, 1.0),
                site="fleet.heartbeat", attempts=1)
        except urllib.error.HTTPError as e:
            if e.code in (404, 409):
                # evicted (or router restarted): rejoin with a fresh
                # incarnation — a dead epoch's token must not be
                # reused. Returns False either way: join admits this
                # member as NOT routable, so the routable beat has not
                # been delivered yet (start()'s wait contract) — the
                # next tick's beat carries it
                self.last_error = f"heartbeat fenced ({e.code}); rejoining"
                try:
                    from h2o3_tpu.telemetry import blackbox
                    blackbox.record("incarnation_fence", self.member_id,
                                    payload=f"http={e.code} rejoining")
                except Exception:   # noqa: BLE001 — recorder is advisory
                    pass
                try:
                    self.join()
                except Exception as e2:   # noqa: BLE001 — next tick retries
                    self.last_error = f"rejoin failed: {e2!r}"
                return False
            self.last_error = f"heartbeat: {e!r}"
            return False
        except Exception as e:   # noqa: BLE001 — router may be restarting
            # connect-class failure: this front door is gone (or
            # bouncing) — fail the beat stream over to the next router
            # in the tier; our incarnation token travels via gossip so
            # the peer accepts the next beat without a rejoin
            self.last_error = f"heartbeat: {e!r}"
            self._rotate_router(f"beat: {type(e).__name__}")
            return False
        # push gossip: every peer's circuit states, grouped by source —
        # an open circuit on any replica sheds load HERE now, without
        # waiting for a telemetry scrape
        gossip: Dict[str, List[dict]] = {}
        for ent in out.get("gossip") or []:
            src = str(ent.get("source") or "?")
            gossip.setdefault(src, []).append(ent)
        for src, states in gossip.items():
            serve_fleet.observe_peer_states(
                states, src, self_process=(src == self.member_id))
        # fleet-scheduler gossip: the router's merged placement view
        # rides the same response — every replica sees every other
        # replica's headroom at heartbeat latency
        fs = out.get("fleet_sched")
        if fs is not None:
            from h2o3_tpu.fleet import sched as fleet_sched
            fleet_sched.observe_fleet_view(fs, self.member_id)
        self._note_epoch(out)
        return True

    # -- lifecycle -------------------------------------------------------

    def start(self, wait_routable_s: float = 0.0) -> "FleetAgent":
        """Join → prewarm → mark routable → heartbeat loop (daemon
        thread). ``wait_routable_s`` > 0 blocks until the routable
        beat was delivered (tests / scripted bring-up)."""
        out = self.join()
        if self.prewarm:
            self._prewarm(out.get("registry"))
        # fleet scheduler: this process is now addressable by the fleet
        # — identify it and route local submissions/preemptions through
        # the placement hooks (no-ops until a fleet view arrives)
        from h2o3_tpu.fleet import sched as fleet_sched
        fleet_sched.set_local_member(self.member_id, self.base_url)
        fleet_sched.install_hooks()
        self.routable = True
        routable_sent = threading.Event()

        def _loop():
            while not self._stop.is_set():
                if self.beat_once():
                    routable_sent.set()
                self._stop.wait(self.heartbeat_s)

        self._thread = threading.Thread(target=_loop, daemon=True, name="fleet-agent")  # h2o3-lint: allow[sched-discipline] the heartbeat loop is the fleet's liveness signal — it must never queue behind training admission
        self._thread.start()
        if wait_routable_s > 0:
            routable_sent.wait(wait_routable_s)
        return self

    def stop(self, leave: bool = True) -> None:
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(max(self.heartbeat_s * 4, 2.0))
        if leave and self.incarnation is not None:
            try:
                _post_json(f"{self.router_url()}/3/Fleet/leave",
                           {"member_id": self.member_id,
                            "incarnation": self.incarnation},
                           timeout_s=2.0, site="fleet.leave", attempts=1)
            except Exception as e:   # noqa: BLE001 — the detector will evict
                self.last_error = f"leave: {e!r}"
