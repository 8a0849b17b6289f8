"""The closed loop: clients, the engine, and the host-clock stamps.

The loop drives the program through its public entry only:
``ServingEngine.submit`` and ``ServingEngine.step``.  After each ``step``
returns it stamps every new output token with the host clock and lets a
client whose request reached ``next_on`` send its next one.
"""
from __future__ import annotations

import time
from typing import Callable, Dict, List, Optional

from benchkit.timeline import Record
from benchkit.traffic import Traffic


class Loop:
    def __init__(self, eng, traffic: Traffic,
                 clock: Callable[[], float] = time.perf_counter,
                 log: Optional[Callable[[str], None]] = None):
        self.eng = eng
        self.traffic = traffic
        self.clock = clock
        self.records: Dict[str, Record] = {}
        self.active: List[str] = []
        self.sent = [0] * traffic.clients
        self.steps = 0
        self._warm = 0
        self.log = log

    # -- requests -----------------------------------------------------------
    def _submit(self, tokens, max_new: int, client: Optional[int],
                index: int) -> Record:
        from repro.serving.request import Request
        req = Request(prompt_len=len(tokens), max_new_tokens=max_new,
                      arrival_time=self.eng.now)
        self.eng.submit(req, tokens=tokens)
        rec = Record(req.req_id, client, index, len(tokens), self.clock())
        self.records[req.req_id] = rec
        self.active.append(req.req_id)
        return rec

    def send(self, c: int) -> Record:
        """Client c sends its next request of the mix."""
        i = self.sent[c]
        self.sent[c] += 1
        tokens, max_new = self.traffic.request(c, i)
        return self._submit(tokens, max_new, c, i)

    def send_warm(self, length: int, max_new: int) -> Record:
        """A set-up request outside the clients' sequences."""
        self._warm += 1
        tokens = self.traffic.tokens(length, 1 << 20, self._warm)
        return self._submit(tokens, max_new, None, self._warm)

    # -- iterations -----------------------------------------------------------
    def step(self) -> bool:
        """One engine iteration.  False when the engine had no work."""
        plan = self.eng.step()
        t = self.clock()
        self.steps += 1
        for rid in list(self.active):
            st = self.eng.states[rid]
            rec = self.records[rid]
            new = len(st.out_tokens) - len(rec.stamps)
            if new > 0:
                rec.stamps.extend([t] * new)
            finished = st.req.finish_time is not None
            if finished:
                rec.done = t
                self.active.remove(rid)
            if rec.client is not None and not rec.next_sent and (
                    (self.traffic.next_on == "first_token" and rec.stamps)
                    or finished):
                rec.next_sent = True
                self.send(rec.client)
        return plan is not None

    def run_until(self, cond: Callable[[], bool], max_steps: int = 100000
                  ) -> None:
        t0 = self.clock()
        for i in range(max_steps):
            if cond():
                return
            if not self.step():
                raise RuntimeError("the engine ran out of work in set-up")
            if self.log is not None and i % 10 == 9:
                self.log(f"set-up step {self.steps}: {self.clock() - t0:.1f} s"
                         f", {sum(1 for r in self.records.values() if r.stamps)}"
                         f" requests with a first token")
        raise RuntimeError("set-up did not reach its goal")

    # -- prefill progress ---------------------------------------------------
    def cursor(self, rid: str):
        """The request's prefill cursor (layer, prompt tokens done in that
        layer), from its layer-segmented pacing state; (layers, 0) once
        the first token is out."""
        rec = self.records[rid]
        L = self.eng.cfg.num_layers
        if rec.stamps:
            return (L, 0)
        req = self.eng.states[rid].req
        return (min(int(req.prefill_layer), L),
                int(req.prefill_layer_tokens_done))

    def mark(self, edge: int) -> None:
        """Record every request's prefill progress at the window's start
        (0) or end (1), in prompt tokens: (layer * prompt + tokens done
        in the layer) / layers."""
        L = self.eng.cfg.num_layers
        for rid, rec in self.records.items():
            cur = self.cursor(rid)
            p = min(float(rec.prompt_len),
                    (cur[0] * rec.prompt_len + cur[1]) / L)
            if edge == 0:
                rec.progress0, rec.cursor0 = p, cur
            rec.progress1, rec.cursor1 = p, cur


def setup(loop: Loop, plan: Dict,
          warm: Optional[Callable[[], None]] = None) -> Dict[str, float]:
    """Run a mix's ``setup`` block; returns the seconds of each part.

    * ``warm``: set-up requests outside the clients' sequences, so the
      planes reach the capacities and row counts, and compile the shapes,
      that the window will use.  Each entry is ``{"lengths": ..., "wave":
      k}``: ``lengths`` is ``"min"``, ``"max"``, ``"all"`` (the mix's
      whole length set) or a list of those and numbers; they are sent
      ``wave`` at a time, each wave served to its end;
    * then every client sends its first request, and the loop runs until
      each has its first token;
    * ``then_steps``: further iterations before the window opens, in
      which the decode planes reach the capacity the window uses;
    * ``warm``, the caller's, runs next (``warm_s``), and then one more
      iteration, so the gap between a request's last set-up token and
      its first window token is one iteration, not the warm-up.
    """
    clock = loop.clock
    lens = loop.traffic.lengths
    times: Dict[str, float] = {}

    def resolve(x):
        if x == "min":
            return [min(lens)]
        if x == "max":
            return [max(lens)]
        if x == "all":
            return list(lens)
        if isinstance(x, list):
            return [n for y in x for n in resolve(y)]
        return [int(x)]

    t = clock()
    for entry in plan.get("warm", []):
        todo = resolve(entry["lengths"])
        k = int(entry.get("wave", len(todo)))
        for i in range(0, len(todo), k):
            recs = [loop.send_warm(n, loop.traffic.max_new_tokens)
                    for n in todo[i:i + k]]
            loop.run_until(lambda: all(r.done is not None for r in recs))
    times["warm_requests_s"] = clock() - t
    t = clock()
    firsts = [loop.send(c) for c in range(loop.traffic.clients)]
    loop.run_until(lambda: all(r.stamps for r in firsts))
    times["sessions_s"] = clock() - t
    t = clock()
    for _ in range(int(plan.get("then_steps", 0))):
        loop.step()
    times["warm_steps_s"] = clock() - t
    if warm is not None:
        t = clock()
        warm()
        times["warm_s"] = clock() - t
        loop.step()
    return times
