"""FleetAutoscaler — SLO-driven elastic capacity for the serving
fleet.

Closes the control loop ROADMAP item 3 names between four shipped
subsystems (docs/robustness.md "Elastic autoscaling & overload
control"):

- **When** — the router's multi-window SLO burn-rate alerts (round 12:
  short AND long window must both burn, so a blip never scales) plus
  the adaptive overload controller's ``degraded`` flag decide
  scale-OUT; scale-IN waits for every objective's error budget to
  recover AND the fleet to run demonstrably idle (router queue empty,
  per-replica outstanding under ``scale_in_util``, history-plane
  placement p99 back under the overload target) for a full
  ``recovery_hold_s`` — hysteresis on top of per-direction cooldowns,
  so the controller never flaps. A scale decision inside
  ``flap_window_s`` of the OPPOSITE decision still executes (the
  capacity need is real) but counts ``fleet_autoscale_flaps_total`` —
  the canary gate fails on ANY flap, which is the "never flaps"
  contract made enforceable.
- **Scale-out execution** — ``spawn_fn(index)`` builds a fresh
  replica (the builder owns ``ServingEngine.warmup()`` — the round-14
  warm-boot contract); the autoscaler then holds it OUTSIDE the fleet
  until its first heartbeat reports ``state=serving`` AND ``warmed``
  (the supervisor's boot gate, applied pre-adoption so the router's
  placement boot gate never stalls the live fleet on a booting
  newcomer), and only then ``router.adopt_replica``\\ s it. The
  compile counts frozen at adoption are exported via ``spawned`` —
  the chaos drill's "a new replica takes traffic with zero new
  steady-state traces" assertion.
- **Scale-in execution** — pick the least-loaded serving replica
  (largest name on ties — deterministic),
  ``supervisor.mark_retiring`` it (exactly-one-owner: the supervisor
  must not read the coming silence/death as a crash and respawn it),
  then ``router.retire`` (hedge legs cancelled first, then drain:
  in-flight finishes token-exact, queued bounces and re-places) and
  ``router.remove_replica`` once drained with zero unresolved
  assignments — zero lost or duplicated requests, journal-anchored. A
  drain stuck past ``retire_timeout_s`` is killed and removed through
  the normal failover harvest (still exactly-once by rid).
- **Every decision** is journaled into the router's WAL
  (``scale_out`` / ``scale_in`` records via ``journal_event`` — a
  successor router surfaces them from ``reconcile()["autoscale"]``)
  and flight-dumped (``fleet_scale_out`` / ``fleet_scale_in``), so a
  crash mid-scale-event is recoverable and explainable.

``poll()`` is driven from the same control thread as
``FleetRouter.step()`` (and ``FleetSupervisor.poll()``), with an
injectable ``now`` for deterministic tests; ``watch()`` wraps the
common loop. Metrics land in the router's registry; the cached
``snapshot()`` rollup rides ``router.health()["autoscale"]`` (and the
``tools/fleet_top.py`` AUTOSCALER panel). ``tools/fleet_replay.py
--knob autoscale.<param>`` scores a policy offline against a recorded
traffic archive.

Env knobs (defaults when the ctor arg is None; catalogue in
docs/observability.md): ``PADDLE_TPU_AUTOSCALE_MIN`` /
``PADDLE_TPU_AUTOSCALE_MAX`` (fleet size bounds),
``PADDLE_TPU_AUTOSCALE_COOLDOWN_S`` (per-direction decision spacing),
``PADDLE_TPU_AUTOSCALE_HOLD_S`` (recovery hold before a scale-in).
"""
from __future__ import annotations

import collections
import os
import time

__all__ = ["FleetAutoscaler"]


def _env_float(name, default):
    v = os.environ.get(name)
    return float(default) if v in (None, "") else float(v)


def _env_int(name, default):
    v = os.environ.get(name)
    return int(default) if v in (None, "") else int(v)


class FleetAutoscaler:
    """Elastic scale-out/in controller over a FleetRouter.

    router: the FleetRouter to scale (its SLO tracker, overload
        controller, history plane and journal are the inputs; its
        dynamic-membership verbs are the actuators).
    spawn_fn: ``spawn_fn(index) -> replica`` — builds one NEW replica
        (unique name, engine warmed via ``warmup()``) each time the
        controller scales out. The replica is adopted only after its
        warm-boot heartbeat; a spawn that raises counts as a failed
        scale-out and respects the cooldown.
    supervisor: optional FleetSupervisor — scale-in victims are
        ``mark_retiring``-ed there BEFORE the drain so the supervision
        loop never resurrects a replica the autoscaler is removing.
    registry: metrics destination (default: the router's registry).
    min_replicas / max_replicas: fleet size bounds (env defaults
        PADDLE_TPU_AUTOSCALE_MIN=1 / PADDLE_TPU_AUTOSCALE_MAX=8).
    scale_out_cooldown_s / scale_in_cooldown_s: minimum spacing after
        a same-direction decision (env default
        PADDLE_TPU_AUTOSCALE_COOLDOWN_S=5; scale-in defaults to 3x
        the scale-out cooldown — adding capacity should be eager,
        removing it reluctant).
    recovery_hold_s: how long the recovered/idle condition must hold
        continuously before a scale-in (env default
        PADDLE_TPU_AUTOSCALE_HOLD_S=3).
    budget_floor: every SLO objective's ``budget_remaining`` must be
        at least this before a scale-in (burnt budget = no shrinking).
    scale_in_util: max mean per-replica outstanding/queue-limit
        utilization considered "idle enough" to shrink.
    boot_timeout_s: spawn -> warm-boot-heartbeat budget; past it the
        newcomer is killed and the scale-out counts as failed.
    retire_timeout_s: drain -> removable budget; past it the victim
        is killed and removed through the failover harvest.
    flap_window_s: opposite-direction decisions closer than this
        count as flaps (``fleet_autoscale_flaps_total``).
    """

    def __init__(self, router, spawn_fn, *, supervisor=None,
                 registry=None, min_replicas=None, max_replicas=None,
                 scale_out_cooldown_s=None, scale_in_cooldown_s=None,
                 recovery_hold_s=None, budget_floor=0.25,
                 scale_in_util=0.25, boot_timeout_s=60.0,
                 retire_timeout_s=60.0, flap_window_s=30.0):
        self.router = router
        self.spawn_fn = spawn_fn
        self.supervisor = supervisor
        self.min_replicas = _env_int("PADDLE_TPU_AUTOSCALE_MIN", 1) \
            if min_replicas is None else int(min_replicas)
        self.max_replicas = _env_int("PADDLE_TPU_AUTOSCALE_MAX", 8) \
            if max_replicas is None else int(max_replicas)
        if not 1 <= self.min_replicas <= self.max_replicas:
            raise ValueError(
                f"need 1 <= min_replicas({self.min_replicas}) <= "
                f"max_replicas({self.max_replicas})")
        cd = _env_float("PADDLE_TPU_AUTOSCALE_COOLDOWN_S", 5.0)
        self.scale_out_cooldown_s = cd if scale_out_cooldown_s is None \
            else float(scale_out_cooldown_s)
        self.scale_in_cooldown_s = 3.0 * self.scale_out_cooldown_s \
            if scale_in_cooldown_s is None else float(scale_in_cooldown_s)
        self.recovery_hold_s = _env_float(
            "PADDLE_TPU_AUTOSCALE_HOLD_S", 3.0) \
            if recovery_hold_s is None else float(recovery_hold_s)
        self.budget_floor = float(budget_floor)
        self.scale_in_util = float(scale_in_util)
        self.boot_timeout_s = float(boot_timeout_s)
        self.retire_timeout_s = float(retire_timeout_s)
        self.flap_window_s = float(flap_window_s)

        self.state = "steady"     # steady | booting | retiring
        self._pending_rep = None  # the newcomer awaiting its boot gate
        self._boot_deadline = None
        self._boot_started = None
        self._victim = None       # the replica draining toward removal
        self._retire_deadline = None
        self._last_out_at = None
        self._last_in_at = None
        self._recovered_since = None
        self._spawn_seq = 0
        self.spawned = []         # (replica, frozen compile counts at
        #                           adoption) — the zero-new-traces
        #                           assertion's ground truth
        self._events = collections.deque(maxlen=128)
        self._health = {}

        self.registry = registry if registry is not None \
            else router.registry
        reg = self.registry
        self._m_events = {}
        self._m_boots = {}
        self._m_flaps = reg.counter(
            "fleet_autoscale_flaps_total",
            help="scale decisions inside flap_window_s of the "
                 "opposite decision (controller oscillation — "
                 "canary-gated at ANY increase)")
        self._g_replicas = reg.gauge(
            "fleet_autoscale_replicas",
            help="replicas under autoscaler management (fleet "
                 "members + the one mid-boot)")
        # pre-export at 0 so history/canary gates can diff the series
        # at any two instants (the sentinel-counter convention)
        self._event_counter("out", "slo_burn")
        self._event_counter("in", "recovered")
        self._m_flaps.inc(0)
        router.autoscaler = self
        self._refresh(time.monotonic())

    # -- metrics -----------------------------------------------------------

    def _event_counter(self, direction, reason):
        from .router import labeled_counter
        return labeled_counter(
            self.registry, self._m_events, "fleet_autoscale_events_total",
            "autoscaler decisions/outcomes by direction and reason",
            direction=direction, reason=reason)

    def _bootmode_counter(self, mode):
        from .router import labeled_counter
        return labeled_counter(
            self.registry, self._m_boots, "fleet_boots_total",
            "warm boots adopted into rotation, by boot path (aot = "
            "restored from a serving artifact, traced = full Python "
            "trace + compile)", mode=mode)

    # -- control loop ------------------------------------------------------

    def poll(self, now=None):
        """One autoscale round; drive it from the router's control
        thread (``router.step(); sup.poll(); asc.poll()``). Returns
        the (event, detail) transitions this round — events:
        scale_out_started, scaled_out, boot_failed, scale_in_started,
        scaled_in, scale_in_forced."""
        now = time.monotonic() if now is None else float(now)
        events = []
        if self.state == "booting":
            self._poll_booting(now, events)
        elif self.state == "retiring":
            self._poll_retiring(now, events)
        else:
            self._decide(now, events)
        self._refresh(now)
        return events

    def watch(self, until, timeout_s=60.0, poll_s=0.005):
        """Drive ``router.step() + supervisor.poll() + poll()`` until
        ``until()`` is truthy (or raise on timeout) — the common
        elastic-drill loop."""
        deadline = time.monotonic() + float(timeout_s)
        while not until():
            self.router.step()
            if self.supervisor is not None:
                self.supervisor.poll()
            self.poll()
            if time.monotonic() > deadline:
                raise RuntimeError(
                    f"autoscaler watch timed out after {timeout_s}s")
            time.sleep(poll_s)

    # -- decision ----------------------------------------------------------

    def _live(self):
        """Fleet members currently servable (or booting back): what
        the size bounds count. Retiring/lost/quarantined members are
        already on their way out."""
        out = []
        for name, rep in self.router.replicas.items():
            if name == self._victim or name in self.router._lost \
                    or getattr(rep, "quarantined", False):
                continue
            out.append(name)
        return out

    def _overloaded(self):
        """The scale-out signal: any SLO multi-window burn pair
        firing (short AND long — round 12's alert shape), or the
        overload controller's standing-queue degraded flag. Returns
        the reason string or None."""
        alerts = self.router.slo_alerting
        if alerts:
            return "slo_burn:" + ",".join(alerts)
        if self.router.degraded:
            return "degraded"
        return None

    def _recovered(self):
        """The scale-in signal: alerts clear, budgets back above the
        floor, and the fleet demonstrably idle — router queue empty,
        mean outstanding utilization under ``scale_in_util``, and the
        history plane's recent placement p99 (when available) back
        under the overload target. Trend + budget, not a point
        sample; _decide additionally requires this to HOLD for
        recovery_hold_s."""
        r = self.router
        if r.slo_alerting or r.degraded or r._queue:
            return False
        for rep in r._slo_state.values():
            br = rep.get("budget_remaining")
            if br is not None and br < self.budget_floor:
                return False
        live = self._live()
        if not live:
            return False
        outstanding = r._outstanding()
        util = sum(outstanding.get(n, 0) for n in live) \
            / (len(live) * max(r.replica_queue_limit, 1))
        if util > self.scale_in_util:
            return False
        hist = getattr(r, "history", None)
        if hist is not None and r._overload_target_s is not None:
            try:
                p99 = hist.quantile_over_time(
                    "fleet_placement_wait_seconds", 0.99,
                    max(self.recovery_hold_s, 1.0))
            except Exception:  # noqa: BLE001 — trend is advisory
                p99 = None
            if p99 is not None and p99 > r._overload_target_s:
                return False
        return True

    def _decide(self, now, events):
        reason = self._overloaded()
        if reason is not None:
            self._recovered_since = None
            if len(self._live()) >= self.max_replicas:
                return
            if self._last_out_at is not None and \
                    now - self._last_out_at < self.scale_out_cooldown_s:
                return
            self._start_scale_out(now, reason, events)
            return
        if not self._recovered():
            self._recovered_since = None
            return
        if self._recovered_since is None:
            self._recovered_since = now
        if now - self._recovered_since < self.recovery_hold_s:
            return
        if len(self._live()) <= self.min_replicas:
            return
        if self._last_in_at is not None and \
                now - self._last_in_at < self.scale_in_cooldown_s:
            return
        self._start_scale_in(now, events)

    def _flap_check(self, now, direction):
        prev = self._last_in_at if direction == "out" \
            else self._last_out_at
        if prev is not None and now - prev < self.flap_window_s:
            self._m_flaps.inc()
            return True
        return False

    # -- scale-out ---------------------------------------------------------

    def _start_scale_out(self, now, reason, events):
        idx = self._spawn_seq
        self._spawn_seq += 1
        flap = self._flap_check(now, "out")
        self._last_out_at = now
        try:
            rep = self.spawn_fn(idx)
        except Exception as e:  # noqa: BLE001 — a failed spawn is a
            #                     failed scale-out, not a dead loop
            self._event_counter("out", "spawn_error").inc()
            self._note(now, "boot_failed", replica=None,
                       reason=f"spawn_error: {type(e).__name__}: {e}")
            events.append(("boot_failed", f"spawn#{idx}"))
            return
        self._pending_rep = rep
        self._boot_started = now
        self._boot_deadline = now + self.boot_timeout_s
        self.state = "booting"
        self._event_counter(
            "out", reason.split(":", 1)[0]).inc()
        self.router.journal_event("scale_out", replica=rep.name,
                                  reason=reason, flap=flap)
        self._note(now, "scale_out_started", replica=rep.name,
                   reason=reason, flap=flap)
        events.append(("scale_out_started", rep.name))

    def _poll_booting(self, now, events):
        rep = self._pending_rep
        snap = None
        try:
            snap = rep.scrape()
        except Exception:  # noqa: BLE001 — no heartbeat yet
            snap = None
        if snap and snap.get("state") == "serving" \
                and snap.get("warmed", True):
            # warm-boot gate passed: the newcomer joins the fleet with
            # its compile counts FROZEN — real traffic after this
            # point must trace nothing new (the supervisor picks the
            # name up automatically on its next poll)
            try:
                frozen = rep.compile_counts() if hasattr(
                    rep, "compile_counts") \
                    else rep.engine.compile_counts()
            except Exception:  # noqa: BLE001 — counts are assertion fuel
                frozen = None
            self.router.adopt_replica(rep)
            self.spawned.append((rep, frozen))
            self._pending_rep = None
            self._boot_deadline = None
            self.state = "steady"
            boot_s = now - self._boot_started
            # boot-path accounting: aot (restored from a serving
            # artifact) vs traced — the fleet_top BOOT column reads
            # this
            bi = snap.get("boot") or {}
            mode = str(bi.get("mode") or "traced")
            self._bootmode_counter(mode).inc()
            self._router_flight("fleet_scale_out", {
                "replica": rep.name, "boot_s": round(boot_s, 6),
                "boot_mode": mode,
                "fleet_size": len(self._live())})
            self._note(now, "scaled_out", replica=rep.name,
                       boot_s=round(boot_s, 6), boot_mode=mode)
            events.append(("scaled_out", rep.name))
            return
        dead = not getattr(rep, "alive", True)
        if dead or now > self._boot_deadline:
            reason = "exit_at_boot" if dead else "boot_timeout"
            try:
                rep.kill()
            except Exception:  # noqa: BLE001 — already gone
                pass
            self._event_counter("out", reason).inc()
            self._note(now, "boot_failed", replica=rep.name,
                       reason=reason)
            events.append(("boot_failed", rep.name))
            self._pending_rep = None
            self._boot_deadline = None
            self.state = "steady"

    # -- scale-in ----------------------------------------------------------

    def _pick_victim(self):
        """Least-loaded serving member; ties retire the LARGEST name
        (deterministic). None when nothing is eligible."""
        r = self.router
        outstanding = r._outstanding()
        cands = []
        for name in self._live():
            rep = r.replicas[name]
            if not rep.alive or rep.state != "serving":
                continue
            cands.append(name)
        if not cands:
            return None
        return max(cands,
                   key=lambda n: (-outstanding.get(n, 0), n))

    def _start_scale_in(self, now, events):
        victim = self._pick_victim()
        if victim is None:
            return
        flap = self._flap_check(now, "in")
        self._last_in_at = now
        self._victim = victim
        self._retire_deadline = now + self.retire_timeout_s
        self.state = "retiring"
        # ownership handoff FIRST: from here the supervisor must not
        # resurrect the victim whatever its process does
        if self.supervisor is not None:
            self.supervisor.mark_retiring(victim)
        self.router.retire(victim)
        self._event_counter("in", "recovered").inc()
        self.router.journal_event("scale_in", replica=victim,
                                  reason="recovered", flap=flap)
        self._router_flight("fleet_scale_in", {
            "replica": victim, "fleet_size": len(self._live()),
            "flap": flap})
        self._note(now, "scale_in_started", replica=victim, flap=flap)
        events.append(("scale_in_started", victim))

    def _poll_retiring(self, now, events):
        name = self._victim
        rep = self.router.replicas.get(name)
        if rep is None:
            # someone else removed it — done either way
            self._victim = None
            self.state = "steady"
            return
        outstanding = self.router._outstanding().get(name, 0)
        drained = not rep.alive and rep.state in ("drained", "dead")
        if drained and outstanding == 0:
            self.router.remove_replica(name)
            self._victim = None
            self.state = "steady"
            self._note(now, "scaled_in", replica=name)
            events.append(("scaled_in", name))
            return
        if now > self._retire_deadline:
            # a wedged drain must not pin the controller: kill the
            # victim and remove it through the failover harvest —
            # in-flight work continuation-resubmits, still
            # exactly-once by rid
            try:
                rep.kill()
            except Exception:  # noqa: BLE001 — already gone
                pass
            try:
                self.router.remove_replica(name)
            except RuntimeError:
                # the kill has not landed yet (a worker inside an
                # uninterruptible stall outlives kill()'s bounded
                # join) — stay in `retiring` and re-attempt next
                # poll instead of crashing the control loop
                return
            self._event_counter("in", "forced").inc()
            self._note(now, "scale_in_forced", replica=name,
                       outstanding=outstanding)
            events.append(("scale_in_forced", name))
            self._victim = None
            self.state = "steady"

    # -- accounting --------------------------------------------------------

    def _note(self, now, event, **detail):
        self._events.append(dict(detail, event=event,
                                 t=round(now, 6)))

    def _router_flight(self, tag, extra):
        try:
            self.router._flight_dump(tag, dict(
                extra, autoscale=self.snapshot()))
        except Exception:  # noqa: BLE001 — postmortems are best-effort
            pass

    def _refresh(self, now):
        live = self._live()
        self._g_replicas.set(
            len(live) + (1 if self._pending_rep is not None else 0))
        last = self._events[-1] if self._events else None
        self._health = {
            "state": self.state,
            "replicas": len(live),
            "min": self.min_replicas, "max": self.max_replicas,
            "booting": None if self._pending_rep is None
            else self._pending_rep.name,
            "retiring": self._victim,
            "recovered_for_s": None if self._recovered_since is None
            else round(now - self._recovered_since, 6),
            "last_decision": None if last is None else dict(last),
            "events": len(self._events)}

    def snapshot(self):
        """Cached rollup for ``router.health()["autoscale"]`` and the
        fleet_top AUTOSCALER panel (health() runs on exporter HTTP
        threads — this must stay a cheap dict copy)."""
        return dict(self._health)

    def health(self):
        """Live controller state + the bounded decision log — what an
        operator reads when asking "why did the fleet just grow"."""
        return dict(self.snapshot(),
                    decisions=[dict(e) for e in self._events])
