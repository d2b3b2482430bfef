"""A generation engine kept full from a backlog of questions over a few long
documents: question answering, extraction and agents over contracts, code
bases or transcripts, each document asked many times.  ``backlog_stream``'s
closed loop (its feeder is used as it is), with what long shared contexts
add:

* in set-up every document is prefilled ONCE (a request of the document and
  a short question, an answer of one token) and thereby retained by the
  engine's prefix index; the window then never prefills a document: every
  request in it is a document plus a question, joins with its uncovered part
  alone, and decodes over the whole context;
* a join's prefilled tokens are counted as ``length - covered``, not
  ``length``;
* the run FAILS if a window join's covered part is not the whole document:
  a miss would silently put a prefill of the whole document in the window;
* the prefix index is flushed before the check that no page is left in use.

The traffic file gives ``documents`` and ``document_tokens``, the question
and answer length distributions (``question_tokens``, ``answer_tokens``:
lognormal ``median`` and ``sigma``, clipped to ``min`` and ``max``),
``setup_question_tokens``, how many requests the list holds (``requests``)
and the seed their documents and lengths are drawn from (``length_seed``),
``rate_metric``, ``ramp_seconds``, ``count_gap_steps`` and ``trace_seconds``.
Every ``--seed`` gets the SAME list of (document, question length, answer
length) in an order of its own, and documents and questions of its own token
ids, uniform over the vocabulary.
"""
from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Tuple

import numpy as np

from benchmark import measure
from benchmark.traffic.backlog_stream import _Feeder, lengths


def make_documents(mix: Dict[str, Any], vocab: int, seed: int) -> np.ndarray:
    """``documents`` x ``document_tokens`` token ids from the seed."""
    return np.random.default_rng([int(seed), 3]).integers(
        0, vocab, (int(mix["documents"]), int(mix["document_tokens"])),
        dtype=np.int32)


def make_requests(mix: Dict[str, Any], docs: np.ndarray, vocab: int,
                  seed: int) -> List[Tuple[np.ndarray, int]]:
    """The backlog: ``(document + question token ids, answer length)``.  The
    list of (document, question length, answer length) is the mix's; the
    order and the question's ids are the seed's."""
    n = int(mix["requests"])
    rng = np.random.default_rng(int(mix["length_seed"]))
    which = rng.integers(0, len(docs), n)
    questions = lengths(mix["question_tokens"], n, rng)
    answers = lengths(mix["answer_tokens"], n, rng)
    order = np.random.default_rng([int(seed), 1]).permutation(n)
    ids = np.random.default_rng([int(seed), 2]).integers(
        0, vocab, int(questions.sum()), dtype=np.int32)
    out, at = [], 0
    for i in order:
        out.append((np.concatenate([docs[which[i]],
                                    ids[at:at + questions[i]]]),
                    int(answers[i])))
        at += questions[i]
    return out


def prefill_documents(run, system, docs: np.ndarray, question: int,
                      vocab: int) -> None:
    """One request a document, one at a time: the document and a question of
    ``question`` tokens, an answer of one token.  When it ends the engine
    retains its pages in the prefix index."""
    ids = np.random.default_rng([run.seed, 4]).integers(
        0, vocab, (len(docs), question), dtype=np.int32)
    for doc, q in zip(docs, ids):
        ended = threading.Event()
        h = system.submit(np.concatenate([doc, q]), 1,
                          lambda _h, _e=ended: _e.set())
        if not ended.wait(timeout=600) or h.status != "ok":
            raise RuntimeError(f"a document's prefill ended {h.status!r}")


def run(run, family) -> Dict[str, Any]:
    mix = run.mix
    system = family.build(run)
    doc_tokens = int(mix["document_tokens"])
    with run.spans.span("make_requests"):
        docs = make_documents(mix, system.vocab_size, run.seed)
        requests = make_requests(mix, docs, system.vocab_size, run.seed)
    system.warm_up()
    with run.spans.span("prefill_documents"):
        prefill_documents(run, system, docs,
                          int(mix["setup_question_tokens"]),
                          system.vocab_size)
    feeder = _Feeder(run, system, requests)

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
    tokens = sum(n - at_open.get(key, 0) for key, n in at_close.items())
    counted = run.counter("mmlspark_runner_decode_tokens_total")
    value = measure.rate(tokens, window_s)
    work = _window_work(feeder, at_open, at_close)
    misses = sum(1 for covered, _ in work["prefill_spans"]
                 if covered < doc_tokens)
    run.facts.update(tokens_in_window=tokens,
                     requests_in_window=len(in_window), **work)
    run.note(f"{tokens} tokens of {len(in_window)} ended and "
             f"{system.slots} running requests in {window_s:.3f} s: "
             f"{mix['rate_metric']} = {value:.2f}; the program's "
             f"decode_tokens_total grew by {counted}; "
             f"{len(work['prefill_spans'])} joins prefilled "
             f"{work['prefill_tokens']:.0f} tokens and found "
             f"{sum(c for c, _ in work['prefill_spans'])} cached")
    if counted is not None:
        run.check("token_count_gap", abs(counted - tokens),
                  int(mix["count_gap_steps"]) * system.slots)
    run.check("document_prefix_misses", misses, 0)

    with run.spans.span("close"):
        system.close()
    bad = [h for h in in_window if h.status != "ok"]
    short = sum(1 for h in feeder.done
                if h.status == "ok" and len(h.tokens) != feeder.asked[id(h)])
    run.check("requests_not_ok", len(bad), 0)
    run.check("answers_of_wrong_length", short, 0)
    run.facts["pool_high_water"] = system.pool_high_water()
    run.facts["prefix_pages_retained"] = system.flush_prefix_index()
    run.check("pool_pages_left_in_use", system.pages_in_use(), 0)

    run.memory_peak_bytes()              # before the reference touches the chip
    finished = [h for h in in_window if h.status == "ok"]
    system.release()
    system.check_served(finished)
    return {"attempted": len(in_window), "failed": len(bad) + feeder.refused,
            "end_to_end": {mix["rate_metric"]: value}}


def _window_work(feeder: _Feeder, at_open: Dict[int, int],
                 at_close: Dict[int, int]) -> Dict[str, Any]:
    """What the window's steps and joins had to do at the TRUE lengths, for
    the readers of the shares.  ``step_spans``: for every request, ``(context
    of the first token a step generated for it in the window, how many)``;
    token after token the context grows by one.  ``prefill_spans``: for every
    join in the window, ``(positions the prefix index covered, prompt
    length)``: the join prefilled the positions between.  The four sums are
    ``backlog_stream``'s, with a join's prefilled tokens counted as ``length -
    covered``."""
    step_spans, prefill_spans = [], []
    step_tokens = context = prompt_tokens = prompt_context = 0.0
    for h in feeder.handles:
        lo, hi = at_open.get(id(h), 0), at_close[id(h)]
        if hi <= lo:
            continue
        if lo == 0:                       # joined inside the window
            prefill_spans.append((int(h.covered), int(h.length)))
            prompt_tokens += h.length - h.covered
            prompt_context += (h.covered + 1 + h.length) \
                * (h.length - h.covered) / 2.0
        # generated token j (1-based; j >= 2 come from steps) attends to
        # the prompt and the j - 1 tokens before it
        first = max(lo + 1, 2)
        if hi >= first:
            n = hi - first + 1
            step_spans.append((int(h.length + first - 1), int(n)))
            step_tokens += n
            context += n * h.length + (first - 1 + hi - 1) * n / 2.0
    return {"step_tokens": step_tokens, "step_context_tokens": context,
            "prefill_tokens": prompt_tokens,
            "prefill_context_tokens": prompt_context,
            "step_spans": step_spans, "prefill_spans": prefill_spans}
