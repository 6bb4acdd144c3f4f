"""Fleet-wide admission signals (ISSUE 17 satellite: the gateway's
admission controller consults the FLEET, not one hard-wired engine).

`FleetSignals` implements the same provider protocol as admission.py's
single-engine `SchedulerSignals`, with the semantics shifted from "is
THIS engine saturated" to "is ANY replica able to serve":

| signal        | N=1 (SchedulerSignals)      | fleet (this class)        |
|---------------|-----------------------------|---------------------------|
| drain_state   | scheduler paused / DRAINING | DRAINING, or EVERY live replica paused |
| dead_reason   | this engine dead            | EVERY replica dead        |
| queue_depth   | this scheduler's queue      | MIN over live replicas    |
| kv_pressure   | this pool in headroom band  | EVERY live pool pressured |
| adapters_busy | this store can't admit      | NO live store can admit   |

A classified refusal with `Retry-After` therefore only happens when
the whole fleet is saturated — one rolling or dead replica never sheds
traffic the rest of the fleet can carry.
"""

from __future__ import annotations

from typing import Optional

from ..engine import deadlines


class FleetSignals:
    """Admission signal provider over a SessionRouter's live fleet."""

    def __init__(self, router):
        self.router = router

    def _live(self):
        return [r for r in self.router.replicas
                if r.name not in self.router._retired
                and r.dead_reason() is None]

    def drain_state(self) -> Optional[str]:
        if deadlines.DRAINING:
            return "draining"
        live = self._live()
        if not live:
            return None   # dead fleet reports through dead_reason()
        reasons = []
        for r in live:
            paused = r.scheduler.paused
            if paused is None:
                return None   # someone is open for business
            reasons.append(paused)
        if any(p == "fleet.drain" for p in reasons):
            return "draining"
        return f"paused:{reasons[0]}"

    def dead_reason(self) -> Optional[str]:
        reasons = [r.dead_reason() for r in self.router.replicas
                   if r.name not in self.router._retired]
        if reasons and all(x is not None for x in reasons):
            return reasons[0]
        return None

    def queue_depth(self) -> int:
        live = self._live()
        if not live:
            return 0
        return min(r.scheduler.describe()["admission"]["queued"]
                   for r in live)

    def kv_pressure(self, headroom: float) -> bool:
        live = self._live()
        if not live:
            return False
        for r in live:
            engine = r.engine
            kv = getattr(engine, "kv", None)
            if kv is None:
                return False   # a replica without a pool never pressures
            floor = int(kv.usable_pages() * headroom)
            if (kv.free_pages() > floor
                    or getattr(engine, "kv_offload", None) is not None):
                return False
        return True

    def adapters_busy(self, adapters) -> bool:
        live = self._live()
        if not live:
            return False
        for r in live:
            store = getattr(r.engine, "lora", None)
            if store is None or store.can_admit(adapters):
                return False
        return True
