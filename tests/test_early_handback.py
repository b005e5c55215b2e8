"""A request that must end inside the dispatch in flight gives its slot back
before that dispatch is fetched (PR 52): its successor's pieces ride the
dispatch submitted in the same step, not the one after. What must hold: the
replies, token for token and with the same end, are those of the engine
that releases at the apply (the hand-back forced off INSIDE the test: the
source has no switch); the slot-step ledger still adds up and its tokens are
the clients'; a request that has left its slot and is then cancelled, stops
early or is failed ends exactly once and leaves nothing behind; and the
paths that keep the late release (generation by diffusion, the static
scheduler, nobody waiting) never hand back.

CPU, float32, the five test configurations whose prompts ride; 4 slots,
pages of 8 tokens, 8 steps a dispatch (the cells' length), a closed loop of
8 callers over the 4 slots.
"""

import collections

import numpy as np
import pytest

from distributed_llm_training_and_inference_system_tpu.serve import (
    Request,
    SamplingParams,
)
from distributed_llm_training_and_inference_system_tpu.serve.scheduler import (
    ContinuousBatchingScheduler,
    RequestState,
)

import serving_support as support
from serving_support import LINEAR, SLOTS, idle

pytestmark = pytest.mark.usefixtures("short_kda_chunks")

# 8 steps a dispatch, the cells' length, where the shared shapes have 4: a
# reply of 12 tokens hands its slot back with its last tokens still owed,
# and the stop-token case picks its token among those
STEPS, CALLERS = 8, 8
EIGHT_STEPS = dict(decode_steps_per_dispatch=STEPS)
MODELS = ["gpt-test", "olmoe-test", "xing-test", "nemotron-h-test", LINEAR]
CLASSES = ("useful", "overrun", "prompt_wait", "empty")


def _late(eng):
    """The same engine releasing at the apply, as its parent did."""
    eng._hand_back_early = lambda: 0
    return eng


def _pool(n, seed=5, vocab=250):
    rng = np.random.default_rng(seed)
    return [Request(f"r{i}",
                    [int(t) for t in rng.integers(1, vocab,
                                                  int(rng.integers(5, 40)))],
                    SamplingParams(temperature=0.0,
                                   max_tokens=int(rng.integers(20, 60))))
            for i in range(n)]


def _closed_loop(eng, reqs, callers=CALLERS):
    """``callers`` requests outstanding at all times, the next sent when one
    ends. Returns what the clients saw: {request id: (tokens as streamed,
    the end's reason, how often it ended)}."""
    streamed = collections.defaultdict(list)
    ended = collections.Counter()
    backlog = list(reqs)

    def on_finish(req):
        ended[req.request_id] += 1
        if backlog:
            assert eng.scheduler.add_request(backlog.pop(0))

    eng.on_token = lambda req, toks: streamed[req.request_id].extend(toks)
    eng.on_finish = on_finish
    for _ in range(callers):
        assert eng.scheduler.add_request(backlog.pop(0))
    eng.run_until_idle()
    eng.on_token = eng.on_finish = None
    return {r.request_id: (streamed[r.request_id], r.finish_reason,
                           ended[r.request_id]) for r in reqs}


def _idle(eng):
    """... and no dispatch in flight, nobody leaving, nothing pinned."""
    assert eng._pending is None and not eng.scheduler.leaving
    assert not eng._reserved_by
    assert not eng._prefix_pins and not eng._snapshot_pins
    assert eng.scheduler.active_count == 0
    idle(eng)


@pytest.fixture(scope="module", params=MODELS)
def loops(request):
    """One model's saturated closed loop served twice: handing slots back
    early, and releasing at the apply. (engine, requests, what the clients
    saw, stats) of each."""
    out = []
    for release in (lambda eng: eng, _late):
        eng = release(support.engine(request.param, **EIGHT_STEPS))
        reqs = _pool(24)
        out.append((eng, reqs, _closed_loop(eng, reqs), eng.stats()))
    return out


def test_the_replies_are_those_of_the_engine_that_releases_late(loops):
    (_, early, seen, _), (_, late, want, _) = loops
    for a, b in zip(early, late):
        assert a.state is b.state is RequestState.FINISHED
        assert a.generated_tokens == b.generated_tokens
        assert len(a.generated_tokens) == a.sampling.max_tokens
    # ... and so are the streams: the same tokens, the same end, once
    assert seen == want
    assert all(reason == "length" and n == 1
               for _toks, reason, n in seen.values())
    assert all(toks == r.generated_tokens
               for r, (toks, _, _) in zip(early, seen.values()))


def test_a_successor_rides_the_next_dispatch_and_overrun_falls(loops):
    """A hand-back gives the slot's 8 steps of the chained dispatch to the
    successor: overrun a request falls by ~8 x the share of requests that
    handed back, and the same tokens take fewer decode steps."""
    (eng, reqs, _, early), (_, _, _, late) = loops
    n = len(reqs)
    handed = early["slot_steps"]["early_handbacks"]
    assert late["slot_steps"]["early_handbacks"] == 0
    assert early["finished"] == late["finished"] == n
    # all but those that ended while nobody waited (the last callers')
    assert n - CALLERS <= handed <= n
    fell = (late["slot_steps"]["overrun"]
            - early["slot_steps"]["overrun"]) / n
    assert abs(fell - STEPS * handed / n) < 1.5
    assert early["decode_steps"] < late["decode_steps"]


@pytest.mark.parametrize("which", [0, 1], ids=["early", "late"])
def test_the_ledger_adds_up_and_its_tokens_are_the_clients(loops, which):
    eng, reqs, seen, stats = loops[which]
    ledger = stats["slot_steps"]
    assert (sum(ledger[k] for k in CLASSES)
            == stats["decode_steps"] * SLOTS)
    clients = sum(len(toks) for toks, _, _ in seen.values())
    assert ledger["tokens_credited"] == clients
    assert ledger["useful"] + ledger["first_tokens"] == clients
    assert ledger["first_tokens"] == len(reqs)
    _idle(eng)


def test_the_counter_is_reset_with_the_ledger(loops):
    eng = loops[0][0]
    assert eng.total_early_handbacks > 0
    eng.reset_counters()
    assert eng.stats()["slot_steps"] == dict.fromkeys(
        (*CLASSES, "first_tokens", "tokens_credited", "early_handbacks"), 0)


# -- a request that has left its slot ---------------------------------------

@pytest.fixture(scope="module")
def engine(loops):
    """One engine a model for the cases below, the loop's that hands back
    early, found idle with its counters reset: each leaves it idle."""
    eng = loops[0][0]
    _idle(eng)
    return eng


class _Boom(Exception):
    pass


def _prompts(seed):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(1, 250, 9)]
            for _ in range(SLOTS + 1)]


def _until_one_leaves(eng, tag, short=None, then=None, seed=7):
    """Fill the slots (one short reply among long ones), queue one more
    request, and step until the short one gives its slot back. A request
    is leaving from the admit phase of a step to the apply that ends it, so
    what happens to it meanwhile (``then``: a handler's cancel between the
    engine's holds of the lock, a submit that raises) is done from a spy on
    ``_submit_group``, which the step calls in between. Returns (the
    request, the others) with that step run to its end, or raised out of."""
    prompts = _prompts(seed)
    long = SamplingParams(temperature=0.0, max_tokens=40)
    a = Request(f"{tag}0", prompts[0],
                short or SamplingParams(temperature=0.0, max_tokens=12))
    others = [Request(f"{tag}{i}", prompts[i], long)
              for i in range(1, SLOTS + 1)]
    for r in (a, *others):
        assert eng.scheduler.add_request(r)
    left, submit = [], eng._submit_group

    def spy(*args, **kwargs):
        if eng.scheduler.leaving and not left:
            left.append(list(a.generated_tokens))
            assert list(eng.scheduler.leaving) == [a.request_id]
            assert a.state is RequestState.RUNNING and a.slot is None
            assert a.finish_time is None
            assert a not in eng.scheduler.completed
            assert a.request_id not in eng._req_slot
            assert eng._pending["leaving"][0][0] is a
            # its slot is the waiting request's already
            assert others[-1].state is RequestState.PREFILLING
            assert others[-1].slot == eng._pending["leaving"][0][1]
            assert eng.scheduler.active_count == SLOTS + 1
            if then is not None:
                then(a)
        return submit(*args, **kwargs)

    eng._submit_group = spy
    try:
        for _ in range(8):
            if left:
                break
            eng.step()
    finally:
        del eng._submit_group
    assert left and 12 - len(left[0]) <= STEPS
    return a, others


def _ended_once(eng, req, others):
    eng.run_until_idle()
    assert list(eng.scheduler.completed).count(req) == 1
    assert all(r.state is RequestState.FINISHED
               and len(r.generated_tokens) == 40 for r in others)
    _idle(eng)


def test_a_leaving_request_ends_by_length_when_its_tokens_arrive(
        engine, monkeypatch):
    finished = engine.scheduler.total_finished
    ended, noted = [], []
    engine.on_finish = ended.append
    monkeypatch.setattr(engine.spans, "annotate",
                        lambda **ids: noted.append(ids))
    a, others = _until_one_leaves(engine, "len")
    engine.on_finish = None
    assert {"early_handbacks": 1} in noted      # on the admit span
    assert a.state is RequestState.FINISHED and a.finish_reason == "length"
    assert len(a.generated_tokens) == 12 and ended == [a]
    assert engine.scheduler.total_finished == finished + 1
    assert not engine.scheduler.leaving
    _ended_once(engine, a, others)


def test_a_leaving_request_that_is_cancelled_ends_there(engine):
    ended = []

    def cancel(a):
        with engine.lock:
            assert engine.scheduler.cancel(a.request_id)
            assert not engine.scheduler.cancel(a.request_id)
        assert a.state is RequestState.CANCELLED and ended == [a]
        ended.append(list(a.generated_tokens))

    engine.on_finish = ended.append
    a, others = _until_one_leaves(engine, "cancel", then=cancel)
    engine.on_finish = None
    # the tokens that came for it afterwards were nobody's
    assert ended == [a, a.generated_tokens] and len(ended[1]) < 12
    assert a.state is RequestState.CANCELLED
    _ended_once(engine, a, others)


def test_a_leaving_request_that_meets_a_stop_token_ends_there(engine):
    """The stop token is among the last the group in flight owes it: the
    request hands its slot back, ends on the stop before its last owed
    token, and what the group made for it past the stop is overrun."""
    for seed in range(7, 27):       # a prompt whose reply can show it
        probe = Request(f"probe{seed}", _prompts(seed)[0],
                        SamplingParams(temperature=0.0, max_tokens=12))
        assert engine.scheduler.add_request(probe)
        engine.run_until_idle()
        reply = probe.generated_tokens
        at = [i for i in (10, 9) if reply[i] not in reply[:i]]
        if at:
            break
    at, ledger = at[0], {}
    a, others = _until_one_leaves(
        engine, "stop", seed=seed, short=SamplingParams(
            temperature=0.0, max_tokens=12, stop_token_ids=(reply[at],)),
        then=lambda a: ledger.update(engine.stats()["slot_steps"]))
    assert a.state is RequestState.FINISHED and a.finish_reason == "stop"
    assert a.generated_tokens == reply[:at + 1]
    assert engine.stats()["slot_steps"]["overrun"] - ledger["overrun"] \
        >= STEPS - (at - 8)
    _ended_once(engine, a, others)


@pytest.mark.parametrize("then", ["fail_all", "recover"])
def test_a_leaving_request_in_flight_at_a_failure_is_failed_once(engine, then):
    """The submit behind the hand-back raises, as a device that fails does:
    the server's answer is ``fail_all`` and ``recover``."""
    def boom(a):
        raise _Boom("boom")

    ended = []
    engine.on_finish = ended.append
    with pytest.raises(_Boom):
        _until_one_leaves(engine, then, then=boom)
    (a,) = engine.scheduler.leaving.values()
    had = list(a.generated_tokens)
    engine.fail_all("boom")
    engine.on_finish = None
    assert a.state is RequestState.FAILED and a.error == "boom"
    assert a.finish_reason == "error" and a in ended
    assert engine._pending is None and not engine.scheduler.leaving
    assert engine.scheduler.active_count == engine.scheduler.queue_depth == 0
    assert list(engine.scheduler.completed).count(a) == 1
    if then == "recover":
        assert engine.recover()
    # the engine serves again, and the failed request's tokens never come
    seen = _closed_loop(engine, _pool(6, seed=11), callers=6)
    assert all(reason == "length" and n == 1 for _, reason, n in seen.values())
    assert a.generated_tokens == had
    _idle(engine)


# -- the paths that keep the late release ------------------------------------

def test_generation_by_diffusion_never_hands_back():
    # (pages and buckets of its block of 16 tokens)
    eng = support.engine("sdar-test", kv_block_size=16, prefill_chunk=16,
                         **EIGHT_STEPS)
    pipelined = []
    step = eng.step
    def spy():
        pipelined.append(eng._pending is not None
                         and bool(eng.scheduler.waiting))
        return step()
    eng.step = spy
    seen = _closed_loop(eng, _pool(12, vocab=200))
    assert any(pipelined)   # a group in flight AND a request waiting
    assert all(n == 1 for _, _, n in seen.values())
    assert eng.stats()["slot_steps"]["early_handbacks"] == 0
    _idle(eng)


@pytest.mark.parametrize("over", [
    dict(scheduler="static"),
    dict(pipelined_decode=False),
], ids=["static scheduler", "no dispatch in flight"])
def test_an_engine_with_no_dispatch_in_flight_never_hands_back(over):
    eng = support.engine("gpt-test", **EIGHT_STEPS, **over)
    reqs = _pool(12)
    seen = _closed_loop(eng, reqs)
    assert all(reason == "length" and n == 1 for _, reason, n in seen.values())
    assert eng.stats()["slot_steps"]["early_handbacks"] == 0
    _idle(eng)


def test_nobody_waiting_nobody_hands_back():
    """As many requests as slots: each ends inside a dispatch in flight
    with nobody waiting for its slot, and the release stays at the apply."""
    eng = support.engine("gpt-test", **EIGHT_STEPS)
    in_flight, step = [], eng.step

    def spy():
        in_flight.append(eng._pending is not None and any(
            r is not None and r.remaining_tokens <= STEPS
            for r in eng.scheduler.slots))
        return step()

    eng.step = spy
    seen = _closed_loop(eng, _pool(SLOTS), callers=SLOTS)
    assert any(in_flight)
    assert all(reason == "length" and n == 1 for _, reason, n in seen.values())
    assert eng.stats()["slot_steps"]["early_handbacks"] == 0
    _idle(eng)


def test_a_successor_that_does_not_ride_is_prefilled_behind_the_dispatch():
    """An engine whose prompts never ride (a prefill-complete hook is set)
    still hands back: the successor's prefill program queues behind the
    dispatch in flight, which is fetched and applied in the same step."""
    engines = [support.engine("gpt-test", **EIGHT_STEPS),
               _late(support.engine("gpt-test", **EIGHT_STEPS))]
    seen = []
    for eng in engines:
        eng.on_prefill_complete = lambda req: None
        seen.append(_closed_loop(eng, _pool(16)))
        assert eng.stats()["prefill_ride_tokens"] == 0
        _idle(eng)
    assert seen[0] == seen[1]
    assert engines[0].total_early_handbacks > 0
    assert engines[1].total_early_handbacks == 0


# -- the scheduler's part ----------------------------------------------------

def _seated(n=2):
    released = []
    s = ContinuousBatchingScheduler(max_batch_size=n,
                                    on_release=released.append)
    reqs = [Request(f"s{i}", [1, 2, 3]) for i in range(n)]
    for r in reqs:
        assert s.add_request(r)
    for r in s.admit():
        r.state = RequestState.RUNNING
    return s, reqs, released


def test_hand_back_empties_the_slot_and_finishes_nothing():
    s, (a, b), released = _seated()
    assert s.hand_back(0) is a
    assert s.slots == [None, b] and s.free_slots() == [0]
    assert a.state is RequestState.RUNNING and a.slot is None
    assert a.finish_time is None and not released and not s.completed
    assert s.leaving == {"s0": a} and s.active_count == 2
    assert s.total_finished == 0


@pytest.mark.parametrize("reason,state,counted", [
    ("length", RequestState.FINISHED, 1), ("stop", RequestState.FINISHED, 1),
    ("cancelled", RequestState.CANCELLED, 0)])
def test_finish_leaving_ends_it_as_a_release_ends_a_seated_one(
        reason, state, counted):
    s, (a, b), released = _seated()
    s.hand_back(0)
    s.finish_leaving(a, reason)
    assert a.state is state and a.finish_reason == reason
    assert a.finish_time is not None and released == [a]
    assert list(s.completed) == [a] and not s.leaving
    assert s.total_finished == counted and s.active_count == 1


def test_cancel_and_fail_all_find_a_leaving_request():
    s, (a, b), released = _seated()
    s.hand_back(0)
    assert s.cancel("s0") and not s.cancel("s0")
    assert a.state is RequestState.CANCELLED and released == [a]
    s.hand_back(1)
    assert s.fail_all("boom") == [b]
    assert b.state is RequestState.FAILED and b.error == "boom"
    assert released == [a, b] and not s.leaving and s.active_count == 0
