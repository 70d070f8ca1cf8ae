"""Health-gated backend fallback chain for kernel dispatch (DESIGN.md §13).

A Pallas miscompile or a device OOM mid-stream should degrade throughput,
not kill the update pipeline.  Every chained dispatch site (``slot_update``
fused apply, ``slot_walk``) runs its attempt through :func:`run_chain`,
which walks the backend chain

    pallas → xla → host ref        (or xla → ref when pallas isn't requested)

under a per-(site, backend) circuit breaker:

* **closed** — backend healthy, dispatch goes straight through (cost on the
  healthy path: one dict lookup);
* each candidate gets **retry-once** (transient failures — a flaky
  allocation — don't trip the breaker needlessly);
* two consecutive failures **trip** the breaker: the backend is *open* for
  an exponentially growing cool-down (``cooldown * 2^(trips-1)``, capped),
  and dispatch falls through to the next link;
* an expired cool-down moves the breaker to **half-open**: exactly ONE
  probe dispatch is admitted (``admit`` returns ``"probe"``; concurrent
  dispatchers are refused until the probe resolves or its window lapses)
  and gets a single attempt — success closes the breaker (full
  re-promotion, trip history cleared), failure re-trips it with a doubled
  cool-down.  A probe that never reports back (its thread died) expires
  after one base cool-down so the backend is not stranded half-open.

The last link of a chain is always attempted even when its breaker is open
(there is nothing further to fall back to); if it too fails,
:class:`FallbackExhausted` carries the final error.

A fall-through is never silent: every dispatch that leaves a link for the
next one counts in ``breaker.fallthroughs[site]``, and the first trip of
each (site, backend) logs its cause with the traceback.  A program that
must prove it served from a given backend (the chip smoke run) reads the
counter and ``LAST_USED``.

``faultinject.fire(f"{site}.{backend}")`` runs *before* every attempt, so
injected kernel failures hit with operands untouched — which also means a
donated-buffer first attempt can always be retried on the next link.  A
real failure *after* a donated buffer was consumed is not retryable (jax
reports the deleted buffer and the chain exhausts); injection points and
off-device failures (compile/lowering errors) both fire pre-execution, so
every failure mode this layer is tested against falls back cleanly.

:class:`SimulatedCrash` is a BaseException and flies through the chain —
a process kill is not a kernel failure.
"""
from __future__ import annotations

import collections
import logging
import threading
import time
from typing import Callable, Optional

from ..runtime import faultinject

CHAINS = {
    "pallas": ("pallas", "xla", "ref"),
    "xla": ("xla", "ref"),
    "ref": ("ref",),
}

#: retries per candidate before its breaker trips (retry-once)
RETRIES = 1

#: site -> backend that served the most recent successful dispatch
LAST_USED: dict = {}

log = logging.getLogger(__name__)


class FallbackExhausted(RuntimeError):
    """Every backend in the chain failed; ``__cause__`` is the final error."""


class CircuitBreaker:
    """Per-key trip/cool-down state.  Keys are (site, backend) tuples.

    The clock is injectable so tests drive cool-down expiry with a
    simulated clock instead of sleeping.  All transitions are guarded by
    a lock so concurrent dispatchers (the serve layer) share one breaker
    safely; ``admit`` implements the explicit half-open protocol.
    """

    def __init__(
        self,
        *,
        cooldown: float = 0.25,
        max_cooldown: float = 30.0,
        clock: Callable[[], float] = time.monotonic,
    ):
        self.cooldown = cooldown
        self.max_cooldown = max_cooldown
        self.clock = clock
        self._lock = threading.Lock()
        # key -> {"trips": int, "open_until": float, "probe_until": float}
        # probe_until > 0 means a half-open probe is in flight until then
        self._state: dict = {}
        #: site -> dispatches that fell through to a later link
        self.fallthroughs: collections.Counter = collections.Counter()
        self._logged: set = set()  # keys whose first trip was logged

    def available(self, key) -> bool:
        with self._lock:
            st = self._state.get(key)
            return st is None or self.clock() >= st["open_until"]

    def admit(self, key) -> Optional[str]:
        """Half-open admission: ``"closed"`` (healthy, dispatch freely),
        ``"probe"`` (this caller is THE single half-open probe and gets
        one attempt), or ``None`` (open / probe already in flight —
        fall through to the next link)."""
        with self._lock:
            st = self._state.get(key)
            if st is None:
                return "closed"
            now = self.clock()
            if now < st["open_until"]:
                return None
            if now < st["probe_until"]:
                return None  # another dispatcher holds the probe slot
            # claim the probe slot; a probe that never resolves expires
            # after one base cool-down instead of stranding the backend
            st["probe_until"] = now + self.cooldown
            return "probe"

    def record_fallthrough(self, site: str) -> None:
        with self._lock:
            self.fallthroughs[site] += 1

    def first_trip(self, key) -> bool:
        """True exactly once per key: the first trip, whose cause is logged."""
        with self._lock:
            if key in self._logged:
                return False
            self._logged.add(key)
            return True

    def trip(self, key) -> None:
        with self._lock:
            st = self._state.setdefault(
                key, {"trips": 0, "open_until": 0.0, "probe_until": 0.0}
            )
            st["trips"] += 1
            wait = min(
                self.cooldown * (2.0 ** (st["trips"] - 1)), self.max_cooldown
            )
            st["open_until"] = self.clock() + wait
            st["probe_until"] = 0.0  # probe resolved (by failing)

    def record_success(self, key) -> None:
        # full re-promotion: the trip history is cleared, not just paused
        with self._lock:
            self._state.pop(key, None)

    def state(self, key) -> Optional[dict]:
        with self._lock:
            st = self._state.get(key)
            return None if st is None else dict(st)

    def reset(self) -> None:
        with self._lock:
            self._state.clear()
            self.fallthroughs.clear()
            self._logged.clear()


#: process-wide breaker shared by all chained dispatch sites
BREAKER = CircuitBreaker()


def run_chain(site: str, backend: str, attempt: Callable, *, breaker: Optional[CircuitBreaker] = None):
    """Run ``attempt(candidate)`` down ``CHAINS[backend]``.

    Returns ``(result, used_backend)``.  Raises :exc:`FallbackExhausted`
    when every candidate fails; lets :class:`SimulatedCrash` (BaseException)
    propagate untouched.
    """
    br = breaker if breaker is not None else BREAKER
    candidates = CHAINS.get(backend, (backend,))
    last_err: Optional[Exception] = None
    for i, b in enumerate(candidates):
        key = (site, b)
        last = i == len(candidates) - 1
        mode = br.admit(key)
        if mode is None:
            if not last:
                br.record_fallthrough(site)
                continue  # cooling down; the chain floor always gets a shot
            mode = "probe"  # open floor: one attempt, nothing to fall to
        # half-open probes get exactly one attempt; closed links retry-once
        tries = 1 if mode == "probe" else RETRIES + 1
        for _ in range(tries):
            try:
                faultinject.fire(f"{site}.{b}")
                out = attempt(b)
            except Exception as e:
                last_err = e
                continue
            br.record_success(key)
            LAST_USED[site] = b
            return out, b
        br.trip(key)
        if br.first_trip(key):
            log.warning(
                "%s: backend %r tripped after %d failed attempt(s)%s",
                site, b, tries, "" if last else "; falling through",
                exc_info=last_err,
            )
        if not last:
            br.record_fallthrough(site)
    raise FallbackExhausted(
        f"{site}: all backends failed (chain {candidates}, requested {backend!r})"
    ) from last_err
