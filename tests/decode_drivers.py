"""The two drivers of ``ContinuousDecoder`` on the same requests (ISSUE 37):
``step()`` by hand, which retires every step it dispatches before it
returns, and the ``start()`` thread, which keeps one step in flight.  Shared
by the three module families' test files; not a test file itself."""
import time

import numpy as np

#: what one driver's run of the requests may take, cold compiles included
LIMIT_S = 300.0


def feed(dec, requests, on_thread, limit_s=LIMIT_S, left=None):
    """Serve ``requests`` [(prompt, max_new_tokens)] with backpressure: a
    request is submitted as soon as a slot is free, so later ones join and
    earlier ones leave mid-flight.  Driven by ``step()`` calls, or by the
    engine's own thread while this one sleeps.  Returns the handles; a list
    given as ``left`` collects them in the order they left."""
    from mmlspark_tpu.models import SlotsExhausted
    handles, pending = [], list(requests)
    deadline = time.monotonic() + limit_s
    while pending or not all(h.done.is_set() for h in handles):
        assert time.monotonic() < deadline, "the requests did not finish"
        while pending:
            try:
                prompt, budget = pending[0]
                handles.append(dec.submit(
                    prompt, max_new_tokens=budget,
                    on_done=None if left is None else left.append))
                pending.pop(0)
            except SlotsExhausted:
                break
        if on_thread:
            dec.start()              # once the slots are full; then a no-op
            time.sleep(0.0005)
        else:
            dec.step()
    return handles


def counter(runner, family):
    return runner.registry.family(family).labels(runner=runner.name).value


def retained_ids(index, tails=True):
    """The token sequences the prefix index retains: every node's path from
    the root, and (with ``tails``) every tail behind its node's path."""
    if index is None:
        return None
    out = set()
    with index._lock:
        for node in index._nodes.values():
            path, at = [], node
            while at is not None:
                path.append(at.chunk)
                at = at.parent
            path = b"".join(reversed(path))
            out.add(path)
            if tails and node.tail is not None:
                out.add(path + np.asarray(node.tail[1], np.int32).tobytes())
    return out


def eos_that_some_answers_hit(answers, budgets):
    """A token id that ends some answers early and leaves others whole:
    the one that first appears strictly inside the most answers."""
    inside = {}
    for toks, budget in zip(answers, budgets):
        for t in set(toks[1:budget - 1]):
            inside[t] = inside.get(t, 0) + 1
    assert inside, "no answer is long enough to end early"
    eos = max(sorted(inside), key=lambda t: (inside[t] < len(answers),
                                             inside[t]))
    cut = [toks.index(eos) + 1 if eos in toks else len(toks)
           for toks in answers]
    assert any(c < b for c, b in zip(cut, budgets)), "no answer hits the eos"
    return int(eos), cut


def serve_through_both_drivers(make_engine, requests):
    """``make_engine(name, eos_id)`` -> ``(runner, decoder)``.  Probes the
    answers without an eos, picks an eos that some of them hit, then serves
    the requests through ``step()`` by hand and through the ``start()``
    thread, on a fresh engine each.  Asserts what must not depend on the
    driver (every request's tokens and status, every page back in the pool
    or retained by the index, the index's retained ids) and what must (the
    overlap engages on the thread alone).  Returns the two runs' facts."""
    budgets = [b for _, b in requests]
    _, probe = make_engine("probe", None)
    answers = [list(h.tokens) for h in feed(probe, requests, False)]
    probe.close()
    assert [len(a) for a in answers] == budgets
    eos, cut = eos_that_some_answers_hit(answers, budgets)
    runs = {}
    for on_thread in (False, True):
        name = "thread" if on_thread else "hand"
        runner, dec = make_engine(name, eos)
        dec.warmup()
        keys = runner.compile_stats()
        left = []
        handles = feed(dec, requests, on_thread, left=left)
        dec.close()
        assert dec._in_flight is None
        index = dec.index
        runs[name] = dict(
            left=[handles.index(h) for h in left],
            chunks=retained_ids(index, tails=False),
            tokens=[list(h.tokens) for h in handles],
            status=[h.status for h in handles],
            slots=len({h.slot for h in handles}),
            pages_left=dec.pool.pages_in_use(),
            retained=retained_ids(index),
            retained_pages=index.stats()["retained_pages"] if index else 0,
            steps=dec.steps,
            overlapped=counter(
                runner, "mmlspark_runner_decode_steps_overlapped_total"),
            stale=counter(runner, "mmlspark_runner_decode_stale_rows_total"),
            minted=runner.compile_stats() != keys)
    hand, thread = runs["hand"], runs["thread"]
    # the same work, whoever drives: the answers, cut at the eos
    assert hand["tokens"] == [a[:c] for a, c in zip(answers, cut)]
    assert thread["tokens"] == hand["tokens"]
    assert set(hand["status"]) == set(thread["status"]) == {"ok"}
    assert hand["slots"] < len(requests)            # slots were taken again
    for run in runs.values():
        assert run["pages_left"] == run["retained_pages"]
        assert not run["minted"], "a join or a leave minted a compile key"
    # every full chunk is retained whoever drives.  A chunk's tail is its
    # FIRST finisher's (first wins), and under the thread who finishes first
    # follows when the feeder found the free slot: the tails are the same
    # whenever the requests left in the same order
    assert thread["chunks"] == hand["chunks"]
    assert sorted(thread["left"]) == sorted(hand["left"])
    if thread["left"] == hand["left"]:
        assert thread["retained"] == hand["retained"]
    # step() retires what it dispatches: nothing overlaps, nothing is stale
    assert hand["overlapped"] == 0 and hand["stale"] == 0
    assert 0 < thread["overlapped"] < thread["steps"]
    # a row that learnt of its eos a step late was stepped for nothing
    assert thread["stale"] > 0
    return runs
