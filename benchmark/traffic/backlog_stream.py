"""A generation engine kept full from a backlog: batch generation over a
table column far longer than the engine's slots, or a generation server at or
above its capacity.  Whole operations back to back (``back_to_back``) would
end every operation in a drain in which the steps run on emptying slots; here
the window sees the steady state.

The traffic file gives the two length distributions (``prompt_tokens`` and
``answer_tokens``: lognormal ``median`` and ``sigma``, clipped to ``min`` and
``max``), how many requests the list holds (``requests``) and the seed their
lengths are drawn from (``length_seed``), ``rate_metric``, ``ramp_seconds``
and ``trace_seconds``.  Every ``--seed`` gets the SAME set of lengths, in an
order of its own and with token ids of its own (uniform over the
vocabulary), so that two seeds differ in what is said and not in how much
work there is; a window that outlasts the list starts it again.

One thread, this one, feeds the engine: it submits until the engine's
admission says ``SlotsExhausted`` and then one request for every ``on_done``
(which the engine's thread calls; it only counts and wakes the feeder).  The
window opens once every slot has been refilled at least once (at most
``ramp_seconds`` are waited for that; set-up), and closes on the clock.

    tokens generated in the window = for every request, the tokens its
    handle holds at the window's close (or at its end, if it ended inside)
    less those it held at the opening

and ``rate_metric`` is that over the window's length.  The growth of the
program's own counter of generated tokens is printed beside it and may differ
by the tokens of ``count_gap_steps`` steps (two: each edge is read a moment
apart from the counters).
``attempted`` counts the requests that ended inside the window, ``failed``
those of them whose outcome is not ``ok`` and every submit the pool refused.

After the window the engine is closed (what is still in flight is cancelled:
a drain would run up to ``max_new_tokens`` steps on emptying slots in every
run), and the checks run: every request that ended got exactly the tokens it
asked for, the pool is back to its trash page alone, and the family's
comparison of a
sample of the finished requests with the plain reference (after
``memory_peak_bytes`` is read and the engine's state is dropped).
"""
from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Tuple

import numpy as np

from benchmark import measure


def lengths(spec: Dict[str, float], n: int, rng) -> np.ndarray:
    """``n`` lengths, lognormal about ``median``, clipped to [min, max]."""
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def make_requests(mix: Dict[str, Any], vocab: int, seed: int
                  ) -> List[Tuple[np.ndarray, int]]:
    """The backlog: ``(prompt token ids, answer length)`` pairs.  The set of
    length pairs is the mix's; the order and the ids are the seed's."""
    n = int(mix["requests"])
    rng = np.random.default_rng(int(mix["length_seed"]))
    prompts = lengths(mix["prompt_tokens"], n, rng)
    answers = lengths(mix["answer_tokens"], n, rng)
    order = np.random.default_rng([int(seed), 1]).permutation(n)
    ids = np.random.default_rng([int(seed), 2]).integers(
        0, vocab, int(prompts.sum()), dtype=np.int32)
    out, at = [], 0
    for i in order:
        out.append((ids[at:at + prompts[i]], int(answers[i])))
        at += prompts[i]
    return out


class _Feeder:
    """The backlog, the handles it made, and the hand-over between the
    engine's ``on_done`` and the feeding thread."""

    def __init__(self, run, system, requests):
        self.run, self.system, self.requests = run, system, requests
        self.slots_full, self.pool_full = system.admission_errors()
        self.free = threading.Semaphore(0)
        self.next = 0
        self.handles: List[Any] = []           # every request admitted
        self.asked: Dict[int, int] = {}        # id(handle) -> answer length
        self.done: List[Any] = []              # handles, as they ended
        self.refused = 0
        self.joins_by_slot = [0] * system.slots

    def _on_done(self, handle) -> None:        # the engine's thread
        self.done.append(handle)
        self.free.release()

    def submit_next(self) -> bool:
        """Submit the backlog's next request; False when no slot is free."""
        prompt, answer = self.requests[self.next % len(self.requests)]
        try:
            with self.run.spans.span("submit"):
                h = self.system.submit(prompt, answer, self._on_done)
        except self.slots_full:
            return False
        except self.pool_full as e:
            self.refused += 1
            self.run.fail(f"submit {self.next} was refused: {e}")
            self.next += 1
            return True
        self.next += 1
        self.handles.append(h)
        self.asked[id(h)] = answer
        self.joins_by_slot[h.slot] += 1
        return True

    def fill(self) -> None:
        while self.submit_next():
            pass

    def feed_until(self, deadline_s: float, stop=lambda: False) -> None:
        """One request for every ``on_done`` until the deadline (or until
        ``stop()`` holds after a submit)."""
        while not stop():
            left = deadline_s - time.perf_counter()
            if left <= 0:
                return
            with self.run.spans.span("wait_for_slot"):
                woke = self.free.acquire(timeout=left)
            if woke and time.perf_counter() < deadline_s:
                self.submit_next()

    def held(self) -> Dict[int, int]:
        """Tokens every admitted request holds now."""
        return {id(h): len(h.tokens) for h in self.handles}


def run(run, family) -> Dict[str, Any]:
    mix = run.mix
    system = family.build(run)
    with run.spans.span("make_requests"):
        requests = make_requests(mix, system.vocab_size, run.seed)
    system.warm_up()
    feeder = _Feeder(run, system, requests)

    # ramp: fill the slots, then feed until every slot has been refilled
    with run.spans.span("ramp"):
        feeder.fill()
        feeder.feed_until(
            time.perf_counter() + float(mix["ramp_seconds"]),
            stop=lambda: min(feeder.joins_by_slot) >= 2)
    run.note(f"ramp: {len(feeder.done)} requests ended, joins by slot "
             f"{feeder.joins_by_slot}; set-up by span: " + ", ".join(
                 f"{n} {t1 - t0:.2f} s" for n, t0, t1 in run.spans.records
                 if n not in ("submit", "wait_for_slot")))

    run.setup_done()
    with run.window():
        t0 = time.perf_counter()
        at_open, ended_before = feeder.held(), len(feeder.done)
        feeder.feed_until(t0 + run.seconds)
        at_close, ended_by_close = feeder.held(), len(feeder.done)
    window_s = run.window_s

    in_window = feeder.done[ended_before:ended_by_close]
    # a request that ended inside holds its whole answer; at_close was read
    # after it ended, so the same difference serves both kinds
    tokens = sum(n - at_open.get(key, 0) for key, n in at_close.items())
    counted = run.counter("mmlspark_runner_decode_tokens_total")
    value = measure.rate(tokens, window_s)
    run.facts.update(tokens_in_window=tokens, requests_in_window=len(in_window),
                     **_window_work(feeder, at_open, at_close))
    run.note(f"{tokens} tokens of {len(in_window)} ended and "
             f"{system.slots} running requests in {window_s:.3f} s: "
             f"{mix['rate_metric']} = {value:.2f}; the program's "
             f"decode_tokens_total grew by {counted}")
    if counted is not None:
        run.check("token_count_gap", abs(counted - tokens),
                  int(mix["count_gap_steps"]) * system.slots)

    # the engine closes: what is still in flight is cancelled, its pages freed
    with run.spans.span("close"):
        system.close()
    bad = [h for h in in_window if h.status != "ok"]
    short = sum(1 for h in feeder.done
                if h.status == "ok" and len(h.tokens) != feeder.asked[id(h)])
    run.check("requests_not_ok", len(bad), 0)
    run.check("answers_of_wrong_length", short, 0)
    run.check("pool_pages_left_in_use", system.pages_in_use(), 0)
    run.facts["pool_high_water"] = system.pool_high_water()

    run.memory_peak_bytes()              # before the reference touches the chip
    finished = [h for h in in_window if h.status == "ok"]
    system.release()
    system.check_served(finished)
    return {"attempted": len(in_window), "failed": len(bad) + feeder.refused,
            "end_to_end": {mix["rate_metric"]: value}}


def _window_work(feeder: _Feeder, at_open: Dict[int, int],
                 at_close: Dict[int, int]) -> Dict[str, float]:
    """What the window's steps and joins had to do at the TRUE lengths, for
    the readers of the shares: the tokens the steps generated (a request's
    first token comes from its join's prefill), the positions those steps
    attended to, the prompts prefilled and the positions they attended to."""
    step_tokens = context = prompt_tokens = prompt_context = 0.0
    for h in feeder.handles:
        lo, hi = at_open.get(id(h), 0), at_close[id(h)]
        if hi <= lo:
            continue
        if lo == 0:                       # joined inside the window
            prompt_tokens += h.length
            prompt_context += h.length * (h.length + 1) / 2.0
        # generated token j (1-based; j >= 2 come from steps) attends to
        # the prompt and the j - 1 tokens before it
        first = max(lo + 1, 2)
        if hi >= first:
            n = hi - first + 1
            step_tokens += n
            context += n * h.length + (first - 1 + hi - 1) * n / 2.0
    return {"step_tokens": step_tokens, "step_context_tokens": context,
            "prefill_tokens": prompt_tokens,
            "prefill_context_tokens": prompt_context}
