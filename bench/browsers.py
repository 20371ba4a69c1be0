"""Closed-loop emulated browsers driving ``SharedDBEngine``'s served path.

Each emulated browser (EB) sends one web interaction, waits until every
query of it is answered, thinks, and sends the next (TPC-W clause 5).
An interaction is *due* when its EB's think time ends; its latency runs
from that moment to the collect that routes its last ticket, so time the
harness spends elsewhere counts against the system, not for it.

The loop keeps at most ``pipeline_depth`` beats in flight and never lets
``dispatch()`` collect on its own, so every ``collect()`` returns exactly
one beat: the beat log then says which writes each beat was offered and
how many tickets of each template it answered, and the loop hands each
beat's tickets to ``on_routed`` (the harness's sample for the reference)
and keeps none of them itself.
"""
from __future__ import annotations

import dataclasses
import heapq
import time
from typing import Callable, Dict, List, Optional

import numpy as np
from jax.profiler import TraceAnnotation

from tpcw_gen import InteractionGenerator, think_time


@dataclasses.dataclass
class Inter:
    """One web interaction in flight or done."""
    eb: int
    kind: str
    due: float
    left: int
    done: Optional[float] = None


@dataclasses.dataclass
class Beat:
    """One dispatched heartbeat: the writes it was offered, how many
    tickets of each template it answered, the engine's counters for it and
    how long the host waited in ``collect()`` for it."""
    writes: list
    t_dispatch: float
    t_collected: Optional[float] = None
    answered: Optional[Dict[str, int]] = None
    stats: Optional[dict] = None
    collect_s: Optional[float] = None


class ClosedLoop:
    """The EB population of one traffic file over one engine."""

    def __init__(self, engine, gen: InteractionGenerator, traffic: Dict,
                 think_rngs: List[np.random.Generator],
                 on_routed: Callable[[int, dict, Dict[str, list]], None]):
        self.engine = engine
        self.on_routed = on_routed
        self.gen = gen
        self.think = traffic["think"]
        self.n_eb = int(traffic["emulated_browsers"])
        self.stagger = traffic["start"] == "staggered"
        self.rngs = think_rngs
        self.depth = engine.pipeline_depth
        self.beats: List[Beat] = []
        self.inters: List[Inter] = []
        self._by_ticket: Dict[int, Inter] = {}
        self._writes: list = []
        self._uncollected = 0
        self._heap: list = []
        self.closed = False

    # ------------------------------------------------------------ pieces
    def submit(self, inter, eb: int, due: float) -> Inter:
        """Hand one interaction's queries and writes to the engine."""
        eng = self.engine
        for table, kind, payload in inter.writes:
            eng.submit_update(table, kind, payload)
            self._writes.append((table, kind, payload))
        tickets = [eng.submit(name, params) for name, params in inter.queries]
        rec = Inter(eb, inter.kind, due, len(tickets))
        for t in tickets:
            self._by_ticket[t.id] = rec
        self.inters.append(rec)
        return rec

    def dispatch(self) -> None:
        with TraceAnnotation("dispatch"):
            t = time.perf_counter()
            self.beats.append(Beat(self._writes, t))
            self._writes = []
            self.engine.dispatch()
            self._uncollected += 1

    def collect(self) -> None:
        with TraceAnnotation("collect"):
            t = time.perf_counter()
            routed = self.engine.collect()
            now = time.perf_counter()
        b = len(self.beats) - self._uncollected
        beat = self.beats[b]
        self._uncollected -= 1
        beat.t_collected = now
        beat.collect_s = now - t
        beat.answered = {n: len(ts) for n, ts in routed.items()}
        beat.stats = dict(self.engine.last_collect_stats)
        self.on_routed(b, beat.stats, routed)
        for tickets in routed.values():
            for t in tickets:
                rec = self._by_ticket.pop(t.id)
                rec.left -= 1
                if rec.left == 0:
                    rec.done = now
                    if not self.closed:
                        due = now + think_time(self.rngs[rec.eb], self.think)
                        heapq.heappush(self._heap, (due, rec.eb))

    def beat_sync(self) -> Beat:
        """One synchronous beat of whatever is queued (warm-up)."""
        self.dispatch()
        self.collect()
        return self.beats[-1]

    # ------------------------------------------------------------ window
    def open(self, t_open: float) -> None:
        """Every EB's first interaction: due at the window's opening, or
        after one think time when the traffic starts staggered (with a
        memoryless think time that is the steady state)."""
        for eb in range(self.n_eb):
            due = t_open + (think_time(self.rngs[eb], self.think)
                            if self.stagger else 0.0)
            heapq.heappush(self._heap, (due, eb))

    def run_until(self, t_stop: float) -> None:
        """Serve until ``t_stop`` on the host clock."""
        eng, heap = self.engine, self._heap
        while True:
            now = time.perf_counter()
            if now >= t_stop:
                return
            if heap and heap[0][0] <= now:
                with TraceAnnotation("generate"):
                    while heap and heap[0][0] <= now:
                        due, eb = heapq.heappop(heap)
                        self.submit(self.gen.next(), eb, due)
            if self._uncollected >= self.depth:
                self.collect()
            elif eng.pending():
                self.dispatch()
            elif self._uncollected:
                self.collect()
            else:
                wake = min(heap[0][0] if heap else t_stop, t_stop)
                with TraceAnnotation("wait"):
                    time.sleep(max(0.0, wake - time.perf_counter()))

    def close(self, grace_s: float) -> None:
        """Stop sending interactions; answer what was sent, waiting at
        most ``grace_s`` past the close."""
        self.closed = True
        self._heap.clear()
        t_stop = time.perf_counter() + grace_s
        eng = self.engine
        while (eng.pending() or self._uncollected) \
                and time.perf_counter() < t_stop:
            if self._uncollected >= self.depth or not eng.pending():
                self.collect()
            else:
                self.dispatch()
