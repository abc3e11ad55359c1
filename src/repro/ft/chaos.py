"""Seeded chaos schedules over an elastic proc fleet.

The sensor-network scenario (Lostanlen et al., PAPERS.md) is long-lived
streams on flaky remote nodes with no fixed fleet: workers crash, stall,
join and leave while the stream runs. This module turns that into a
repeatable adversary: `make_schedule(seed, n_items)` derives a randomized
but fully seed-determined event schedule — SIGKILL, mid-run join,
graceful drain, SIGSTOP stall — and `ChaosRunner` fires it against a live
`ShardedPlan` proc run through the plan's `FleetControl` handle while the
stream is being consumed.

Events trigger on PROGRESS (chunks accepted so far), not wall time, so a
schedule lands at comparable stream positions regardless of compile cost
or host speed. Target choice is necessarily runtime state (who is alive,
who holds leases): kills and stalls prefer lease holders, because a
victim holding work is what exercises redelivery and speculation; if no
preferred target exists the event defers briefly, then fires anyway.

Safety guards, not mercy: an event that would leave ZERO active workers
(killing or draining the last one) spawns a replacement first — a schedule
tests elasticity, not the obvious theorem that an empty fleet makes
no progress. Everything else is fair game, and the acceptance bar is
absolute: every chunk exactly once, bit-identical to `two_phase`.
"""
from __future__ import annotations

import random
import threading
import time
from dataclasses import dataclass

ACTIONS = ("kill", "join", "drain", "stall")


@dataclass
class ChaosEvent:
    """One scheduled disruption: fires once `after_done` chunks have been
    accepted. `target`/`fired_at_done` are filled at fire time."""
    after_done: int
    action: str
    stall_s: float = 6.0
    fired: bool = False
    deferred: int = 0
    target: int = None
    fired_at_done: int = None


def make_schedule(seed, n_items, actions=ACTIONS, extra_events=0,
                  stall_s=(5.0, 9.0)):
    """Derive a seed-determined schedule with AT LEAST one event per
    action in `actions`, plus `extra_events` extra random ones. Biases
    baked in from what each action needs to be observable: the join goes
    EARLY (a late joiner must sign in before the stream drains — process
    start + imports cost real seconds), the stall goes LATE (a stalled
    lease holder near end-of-stream is the shape speculative re-lease
    exists for). Same seed -> same schedule, always."""
    rng = random.Random(int(seed))
    n_items = int(n_items)
    hi = max(1, n_items - 2)
    events = []
    for a in actions:
        if a == "join":
            after = rng.randint(1, min(2, hi))
        elif a == "stall":
            after = rng.randint(max(1, n_items - 3), hi)
        else:
            after = rng.randint(1, hi)
        events.append(ChaosEvent(after, a, round(rng.uniform(*stall_s), 2)))
    for _ in range(max(0, int(extra_events))):
        events.append(ChaosEvent(rng.randint(1, hi), rng.choice(actions),
                                 round(rng.uniform(*stall_s), 2)))
    order = {a: i for i, a in enumerate(actions)}
    events.sort(key=lambda e: (e.after_done, order[e.action]))
    return events


class ChaosRunner:
    """Consume `plan.run(stream)` on a thread while firing `schedule`
    against `plan.fleet`. Returns (results, fired_events).

    The plan is flipped to `elastic=True`: with a chaos driver attached,
    an all-dead instant is a moment between a kill and its replacement,
    not a verdict — the plan's stall timeout stays as the backstop."""

    def __init__(self, plan, stream, schedule, seed=0, poll_s=0.1,
                 defer_s=4.0):
        self.plan = plan
        self.stream = stream
        self.schedule = list(schedule)
        self.seed = int(seed)
        self.poll_s = float(poll_s)
        # how long kill/stall may wait for a lease-holding victim before
        # firing at whoever is alive
        self.defer_ticks = max(1, int(float(defer_s) / self.poll_s))
        plan.elastic = True
        self.fired: list[ChaosEvent] = []

    # -- targeting ----------------------------------------------------------
    def _active(self, fleet):
        """Live shards not on their way out (drained workers are dying by
        request — disrupting them proves nothing)."""
        out = []
        for k, h in fleet.live().items():
            st = fleet.service.workers.get(h.worker)
            if st is None or st.state == "active":
                out.append(k)
        return sorted(out)

    def _holders(self, fleet, shards):
        qs = fleet.service
        return [k for k in shards
                if qs.queue.leases_held(fleet.handles[k].worker)]

    def _ensure_capacity(self, fleet, losing):
        """About to remove the last active worker: spawn a replacement
        first (recorded as an extra join) so the stream keeps a path
        forward."""
        active = self._active(fleet)
        if len(active) - 1 < 1 and losing in active:
            h = fleet.spawn()
            ev = ChaosEvent(after_done=-1, action="join", fired=True,
                            target=h.shard)
            self.fired.append(ev)

    # -- firing -------------------------------------------------------------
    def _fire(self, ev: ChaosEvent, fleet, rng, done):
        if ev.action == "join":
            h = fleet.spawn()
            ev.target = h.shard
        else:
            # prefer fully-active victims; fall back to anything alive
            # (killing a draining worker is still legitimate chaos, and
            # the schedule's every-action guarantee must not starve)
            candidates = self._active(fleet) or sorted(fleet.live())
            if not candidates:
                ev.deferred += 1     # fleet momentarily empty; retry
                return ev.deferred > 10 * self.defer_ticks
            if ev.action in ("kill", "stall"):
                holders = self._holders(fleet, candidates)
                if not holders and ev.deferred < self.defer_ticks:
                    ev.deferred += 1     # wait for a victim holding work
                    return False
                pick = rng.choice(holders or candidates)
                if ev.action == "kill":
                    self._ensure_capacity(fleet, pick)
                    fleet.kill(pick)
                else:
                    fleet.stall(pick, ev.stall_s)
            else:                        # drain
                pick = rng.choice(candidates)
                self._ensure_capacity(fleet, pick)
                fleet.drain(pick)
            ev.target = pick
        ev.fired = True
        ev.fired_at_done = int(done)
        self.fired.append(ev)
        return True

    def run(self):
        results, err = [], []

        def consume():
            try:
                for res in self.plan.run(self.stream):
                    results.append(res)
            except BaseException as e:     # noqa: BLE001 — reraised below
                err.append(e)

        t = threading.Thread(target=consume, daemon=True,
                             name="chaos-consumer")
        t.start()
        # target choice is seeded separately from the schedule so adding
        # events to a schedule does not reshuffle every pick
        rng = random.Random(self.seed * 7919 + 13)
        pending = list(self.schedule)
        try:
            while t.is_alive():
                fleet = self.plan.fleet
                if fleet is None:           # plan still setting up
                    time.sleep(self.poll_s)
                    continue
                done, _total = fleet.service.progress()
                for ev in list(pending):
                    if done >= ev.after_done and not err:
                        if self._fire(ev, fleet, rng, done):
                            pending.remove(ev)
                t.join(self.poll_s)
        finally:
            if self.plan.fleet is not None:
                self.plan.fleet.resume_all()   # no stalled orphans
            t.join()
        if err:
            raise err[0]
        return results, self.fired
