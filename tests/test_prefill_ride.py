"""A prompt admitted to a busy batch rides the decode dispatches (PR 36):
its rows join the decode steps' matmuls, a piece of ``RIDE_PAGES`` pages a
step, and no prefill program runs between two dispatches. What must hold:
the tokens a riding prompt is served and the K/V (or latent rows) its pages
hold are the cold program's, and behind a prefix hit the suffix program's;
under half occupancy nothing rides; a riding prompt that is cancelled,
preempted or failed gives its pages and slot back; and an engine that does
not ride (the hybrid under the static scheduler) keeps the parent's decode
program.

CPU, float32, the dense, the MoE, (PR 41) the latent, (PR 43) the
delta-rule and (PR 44) the hybrid state-space test configurations; 4 slots,
pages of 8 tokens, 4 steps a dispatch, so a piece is 16 rows: ONE chunk of
the state-space (``M``) layers' scan (``nemotron-h-test``'s ``chunk_size``
is 16), as the hybrid cell's 128 rows are one chunk of its 128; its riding
pieces are held to the COLD programs. The delta-rule (``K``) layers' chunk is 8
here (``ops/kda.py CHUNK`` is 64), so a piece is two sub-chunks with the
state carried between them, and its engines prefill a prompt over 16 tokens
chunk by chunk: a riding piece is held to the CHUNK programs, which read
and write the slot's state as it does.
"""

import collections
import functools
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import serving_support as support
from distributed_llm_training_and_inference_system_tpu.config import get_model_config
from distributed_llm_training_and_inference_system_tpu.models.gpt import (
    table_period)
from distributed_llm_training_and_inference_system_tpu.serve import (
    InferenceEngine,
    Request,
    SamplingParams,
)
from distributed_llm_training_and_inference_system_tpu.serve.decode import (
    PIECE_META, can_carry, decode_scan, extend_step_forward)
from distributed_llm_training_and_inference_system_tpu.serve.scheduler import (
    RequestState)
from serving_support import (
    LINEAR, PS, SLOTS, STEPS, fresh_tokens, idle)

pytestmark = pytest.mark.usefixtures("short_kda_chunks")

C = InferenceEngine.RIDE_PAGES * PS
HYBRID = "nemotron-h-test"
MODELS = ["gpt-test", "olmoe-test", "xing-test", LINEAR, HYBRID]
RNG = np.random.default_rng(36)
# the engine shapes the hash pins below were taken at: a slot's table of 24
# pages is in every program's text
PINNED = dict(max_seq_len=192)


@pytest.fixture(autouse=True, scope="module")
def _a_piece_writes_whole_pages():
    """A cell's piece is 128 to 256 rows and its write stages whole pages;
    a window of at most 16 rows stages sublane tiles (PR 54:
    ``ops/paged_attention.py write_window_to_pages``). This module's piece
    is ``C`` = 16 rows: keep it on the route every cell's piece takes, so
    that the programs pinned below are the cells' programs at a small size
    (the diffusion model's window of 8 rows stages tiles, here as in its
    cell)."""
    from distributed_llm_training_and_inference_system_tpu.ops import (
        paged_attention)
    plain = paged_attention._MAX_TILE_WINDOW
    paged_attention._MAX_TILE_WINDOW = C - 1
    yield
    paged_attention._MAX_TILE_WINDOW = plain


def _residents(n=2):
    """Fresh prompts for the slots that decode while the others ride."""
    return [fresh_tokens(9), fresh_tokens(13)][:n]


# what a prompt's pieces look like over a dispatch's 4 steps
SCENARIOS = {                    # prompt lengths
    "one piece": [12],
    "several pieces": [40],
    "a last piece of one row": [2 * C + 1],
    "ends in the dispatch's last step": [STEPS * C - 3],
    "two prompts queued in one dispatch": [C + 1, C + 4],
}
SAMPLING = {
    "greedy": dict(temperature=0.0),
    "seeded": dict(temperature=0.8, top_k=20, top_p=0.9, seed=7),
}


def _normalised_sha256(text):
    """sha256 of a lowered program's StableHLO without the two things a
    result RECORD in place of a tuple and a renamed closure change (PR 45):
    the ``jax.result_info`` attributes of the entry function's results
    (``"result[3]"`` against ``"result.k_pages"``) and the ``@jit_<name>`` of
    the module line. Nothing else is dropped: operations, parameters,
    results, aliasing and every inner function's name are hashed."""
    text = re.sub(r"^module @jit_\w+", "module @jit", text, count=1)
    text = re.sub(r'jax\.result_info = "[^"]*"', "", text)
    return hashlib.sha256(text.encode()).hexdigest()


@functools.cache
def _pair(name):
    return support.engine(name), support.engine(name)


@pytest.fixture(scope="module", params=MODELS)
def engines(request):
    """(the engine whose prompts ride, the cold reference) of one model,
    shared by the module's cases: every case leaves both idle."""
    return _pair(request.param)


def _keep_pages(eng, kept):
    """Keep each prompt's K and V rows (a latent model's one pool's rows) as
    its pages hold them when its first token is delivered ([L, Nkv, n, D],
    whichever pages they are)."""
    def hook(req, tokens):
        n = req.num_prompt_tokens
        if req.request_id in kept or not req.request_id.startswith("p"):
            return
        table = eng.kv.block_tables[req.slot][:-(-n // PS)]
        kept[req.request_id] = [
            np.asarray(pool)[:, table].transpose(0, 2, 1, 3, 4).reshape(
                pool.shape[0], pool.shape[2], -1, pool.shape[4])[:, :, :n]
            for pool in (eng.kv.k_pages, eng.kv.v_pages) if pool is not None]
    eng.on_token = hook


def _serve(eng, prompts, sampling, residents=2, tag=""):
    """``residents`` first (2: half the slots decode), then ``prompts``."""
    # six dispatches of decoding: the longest scenario rides two of them, and
    # what the residents make after the last piece is waiting, not coverage
    # (60 tokens were 15 dispatches a case, most of the file's 420 s: PR 61)
    long = SamplingParams(temperature=0.0, max_tokens=6 * STEPS)
    for i, p in enumerate(_residents(residents)):
        assert eng.scheduler.add_request(Request(f"res{tag}{i}", p, long))
    if residents:
        eng.step()
    reqs = [Request(f"p{tag}{i}", p, SamplingParams(max_tokens=12, **sampling))
            for i, p in enumerate(prompts)]
    for r in reqs:
        assert eng.scheduler.add_request(r)
    eng.run_until_idle()
    return reqs


# where a riding prompt's first piece starts: at 0, or behind the pages of a
# prefix another request left in the cache (the prompt's ``cached`` tokens:
# the reference at the idle engine is then the SUFFIX program; 8 pages,
# since off the TPU admission drops a hit shorter than the tail behind it)
CACHED = {"from 0": 0, "behind a prefix hit": 8 * PS}


@pytest.mark.parametrize("start", list(CACHED))
@pytest.mark.parametrize("sampling", list(SAMPLING))
@pytest.mark.parametrize("scenario", list(SCENARIOS))
def test_a_riding_prompt_is_served_the_cold_programs_tokens_and_pages(
        engines, scenario, sampling, start):
    riding, cold = engines
    if CACHED[start] and riding.cfg.is_recurrent:
        pytest.skip("a recurrent model reuses no prefix by page hash")
    tag = f"-{scenario}-{sampling}-{start}"
    # (fresh tokens a case: a repeated prompt would be a prefix hit)
    prefix = fresh_tokens(CACHED[start])
    if prefix:
        for eng in engines:     # the prefix's whole pages into the cache
            _serve(eng, [prefix + fresh_tokens(3)], SAMPLING["greedy"],
                   residents=0, tag=tag + "-prefix")
    # the scenario's lengths are what is left to prefill
    prompts = [prefix + fresh_tokens(n) for n in SCENARIOS[scenario]]
    rode, pages_rode, pages_cold = riding.stats(), {}, {}
    _keep_pages(riding, pages_rode)
    _keep_pages(cold, pages_cold)
    got = _serve(riding, prompts, SAMPLING[sampling], tag=tag)
    # the reference: the same prompts at an IDLE engine, so the cold program
    before = cold.stats()
    want = _serve(cold, prompts, SAMPLING[sampling], residents=0, tag=tag)
    assert (cold.stats()["prefill_ride_tokens"]
            == before["prefill_ride_tokens"])
    stats = riding.stats()
    assert (stats["prefill_ride_tokens"] - rode["prefill_ride_tokens"]
            == sum(SCENARIOS[scenario]))
    assert (stats["prefill_ride_steps"] - rode["prefill_ride_steps"]
            == sum(-(-n // C) for n in SCENARIOS[scenario]))
    assert (stats["prefix_cached_tokens"] - rode["prefix_cached_tokens"]
            == len(prompts) * len(prefix))
    riding.on_token = cold.on_token = None
    for a, b in zip(got, want):
        assert a.state is RequestState.FINISHED
        assert a.generated_tokens == b.generated_tokens
        for x, y in zip(pages_rode[a.request_id], pages_cold[b.request_id]):
            np.testing.assert_allclose(x, y, rtol=2e-4, atol=2e-5)
    idle(riding)
    idle(cold)


def test_under_half_occupancy_the_cold_program_runs_and_nothing_rides(engines):
    eng, _ = engines
    before = eng.stats()
    # one resident of four slots: under the gate
    [r] = _serve(eng, [fresh_tokens(40)], SAMPLING["greedy"],
                 residents=1, tag="-gate")
    after = eng.stats()
    assert r.state is RequestState.FINISHED
    assert after["prefill_ride_tokens"] == before["prefill_ride_tokens"]
    assert after["prefill_ride_steps"] == before["prefill_ride_steps"]
    assert after["prefill_tokens"] - before["prefill_tokens"] == 9 + 40
    idle(eng)


def test_the_riding_rows_are_counted_as_prefill_rows(engines):
    """``prefill_tokens`` / ``prefill_padded_tokens`` count a riding
    prompt's tokens and C rows a carrying step, so the live-row share
    keeps reading the truth."""
    eng, _ = engines
    # (a cold prompt first: on a worker that is dealt the module from here,
    # the residents' 32-row program is not compiled yet)
    _serve(eng, [fresh_tokens(5)], SAMPLING["greedy"], residents=0,
           tag="-rows-cold")
    before = eng.stats()
    _serve(eng, [fresh_tokens(2 * C + 1)], SAMPLING["greedy"], tag="-rows")
    after = eng.stats()
    # (the two residents are prefilled cold: 9 and 13 tokens, 32 rows each)
    assert (after["prefill_tokens"] - before["prefill_tokens"]
            == 9 + 13 + 2 * C + 1)
    assert (after["prefill_padded_tokens"] - before["prefill_padded_tokens"]
            == 2 * 32 + 3 * C)
    assert after["compiled_programs"] == before["compiled_programs"]


def _start_riding(eng, prompt, tag):
    """Two residents, then ``prompt`` admitted and its first dispatch
    submitted: the request rides."""
    long = SamplingParams(temperature=0.0, max_tokens=60)
    for i, p in enumerate(_residents()):
        assert eng.scheduler.add_request(Request(f"res-{tag}{i}", p, long))
    eng.step()
    req = Request(f"ride-{tag}", prompt,
                  SamplingParams(temperature=0.0, max_tokens=8))
    assert eng.scheduler.add_request(req)
    eng.step()
    assert req.request_id in eng._riding
    assert req.state is RequestState.PREFILLING
    return req


def test_a_cancelled_riding_prompt_gives_its_pages_and_slot_back(engines):
    eng, _ = engines
    req = _start_riding(eng, fresh_tokens(STEPS * C + 20), "cancel")
    held = eng.kv.free_pages
    with eng.lock:
        assert eng.scheduler.cancel(req.request_id)
    eng.step()
    assert req.state is RequestState.CANCELLED
    assert req.request_id not in eng._riding and not req.generated_tokens
    assert eng.kv.free_pages > held
    eng.run_until_idle()
    idle(eng)


def test_a_preempted_riding_prompt_is_requeued_and_served(engines):
    eng, cold = engines
    prompt = fresh_tokens(STEPS * C + 20)
    req = _start_riding(eng, prompt, "preempt")
    slot, preemptions = req.slot, eng.total_preemptions
    with eng.lock:
        eng._preempt(slot)
    assert eng.total_preemptions == preemptions + 1
    assert req.state is RequestState.QUEUED and req.slot is None
    assert req.request_id not in eng._riding
    assert eng.scheduler.slots[slot] is None
    eng.run_until_idle()
    [want] = _serve(cold, [prompt], dict(temperature=0.0), residents=0,
                    tag="-preempt")
    assert req.state is RequestState.FINISHED
    assert req.generated_tokens == want.generated_tokens[:8]
    idle(eng)


def test_a_dry_pool_preempts_the_riding_prompt_first():
    """The riding prompt is the newest admission: when a resident cannot
    grow its chain, it is the victim, and everything is still served."""
    eng = support.engine("gpt-test", kv_num_blocks=14, prefix_caching=False)
    long = SamplingParams(temperature=0.0, max_tokens=40)
    for i, p in enumerate(_residents()):
        assert eng.scheduler.add_request(Request(f"res{i}", p, long))
    eng.step()
    rider = Request("rider", fresh_tokens(40),
                    SamplingParams(temperature=0.0, max_tokens=4))
    assert eng.scheduler.add_request(rider)
    eng.run_until_idle()
    assert eng.total_preemptions >= 1 and rider.preemptions >= 1
    assert all(r.state is RequestState.FINISHED
               for r in eng.scheduler.completed)
    assert eng._reserved_pages == 0 and not eng._riding
    assert eng.kv.free_pages == eng.kv.num_pages - 1


def test_fail_all_drops_the_riding_prompts(engines):
    eng, _ = engines
    req = _start_riding(eng, fresh_tokens(STEPS * C + 20), "fail")
    eng.fail_all("boom")
    assert req.state is RequestState.FAILED and not eng._riding
    assert eng._pending is None
    eng.run_until_idle()
    idle(eng)


def test_a_riding_prompt_does_not_break_the_pipelined_chain(engines):
    """Neither the admission of a prompt that rides nor its last piece
    breaks the chain: the device arms the slot in the carry the next
    dispatch chains on, and the host, which learns the first token a
    dispatch later, already counts the slot resident."""
    eng, _ = engines
    req = _start_riding(eng, fresh_tokens(STEPS * C + 20), "chain")
    submits = eng.stats()["phases"]["llmctl.engine.decode.submit"]["n"]
    first = eng._pending         # carries 4 pieces, ends no prompt
    assert first is not None and not eng.active[req.slot]
    eng.step()                   # the rest is laid CHAINED onto it
    second = eng._pending
    assert second is not first
    assert eng._riding[req.request_id]["done"] == STEPS * C
    # the second dispatch arms the slot at its step 1 (0-based): resident
    # for the host, at the prompt's length less the 2 steps up to there
    assert req.state is RequestState.PREFILLING and eng.active[req.slot]
    assert eng.positions[req.slot] == STEPS * C + 20 - 2
    eng.step()                   # a third is chained BEFORE the second's
    assert eng._pending is not second            # tokens are known
    assert req.state is RequestState.RUNNING and not eng._riding
    # the first token, then the slot's steps 2 and 3 of that dispatch
    assert len(req.generated_tokens) == 3
    assert eng.positions[req.slot] == STEPS * C + 20 + 2
    assert (eng.stats()["phases"]["llmctl.engine.decode.submit"]["n"]
            == submits + 2)
    eng.run_until_idle()
    assert len(req.generated_tokens) == 8
    idle(eng)


def test_a_riding_slots_steps_wait_up_to_its_last_piece_and_then_decode(
        engines):
    """The slot-step ledger over the same chain: two residents decode, the
    prompt's six pieces ride the four steps of one dispatch and steps 0
    and 1 of the next, the fourth slot is empty. A seated slot waits
    (``prompt_wait``) through the first dispatch and up to and with the
    step that carries its last piece, whose token is the FIRST token; the
    two steps after it are decode steps of a slot that was NOT live when
    the dispatch was submitted (the accepted ``padded_slot_steps`` calls
    all four of that slot's steps padded)."""
    eng, _ = engines
    eng._drain_pending()

    def gained(since):
        now = eng.stats()
        got = {k: v - since["slot_steps"][k]
               for k, v in now["slot_steps"].items()}
        steps = now["decode_steps"] - since["decode_steps"]
        assert sum(got[k] for k in ("useful", "overrun", "prompt_wait",
                                    "empty")) == SLOTS * steps
        assert got.pop("early_handbacks") == 0      # nobody waits here
        return dict(got, decode_steps=steps,
                    padded=now["padded_slot_steps"]
                    - since["padded_slot_steps"]), now

    start = eng.stats()
    # (two cold prefills and the first dispatch, then the rider admitted and
    # its first four pieces chained behind it: the first is applied here)
    req = _start_riding(eng, fresh_tokens(STEPS * C + 20), "ledger")
    got, at = gained(start)
    assert got == {"useful": 2 * STEPS, "overrun": 0, "prompt_wait": 0,
                   "empty": 2 * STEPS, "first_tokens": 2,
                   "tokens_credited": 2 + 2 * STEPS, "decode_steps": STEPS,
                   "padded": 2 * STEPS}
    eng.step()          # the rest laid chained; the 4 pieces' dispatch applied
    got, at = gained(at)
    assert got == {"useful": 2 * STEPS, "overrun": 0, "prompt_wait": STEPS,
                   "empty": STEPS, "first_tokens": 0,
                   "tokens_credited": 2 * STEPS, "decode_steps": STEPS,
                   "padded": 2 * STEPS}
    eng.step()          # the dispatch that armed the slot at its step 1
    got, at = gained(at)
    assert len(req.generated_tokens) == 3
    assert got == {"useful": 2 * STEPS + 2, "overrun": 0, "prompt_wait": 2,
                   "empty": STEPS, "first_tokens": 1,
                   "tokens_credited": 2 * STEPS + 3, "decode_steps": STEPS,
                   "padded": 2 * STEPS}
    eng.run_until_idle()
    eng._drain_pending()
    got, _ = gained(start)
    assert got["first_tokens"] == 3 and got["prompt_wait"] == STEPS + 2
    assert got["tokens_credited"] == 60 + 60 + 8
    assert got["useful"] == got["tokens_credited"] - 3
    idle(eng)


@pytest.mark.parametrize("sampling", list(SAMPLING))
@pytest.mark.parametrize("every", [1, 2])
def test_the_host_catches_up_once_in_a_while_and_nothing_else_changes(
        engines, monkeypatch, every, sampling):
    """After ``CHAIN_DISPATCHES`` chained dispatches the host fetches and
    applies the one in flight BEFORE it admits and submits the next (the device
    idles for that long, once). A prompt whose pieces and arming lie on
    both sides of a catch-up is served the cold program's tokens."""
    eng, cold = engines
    monkeypatch.setattr(eng, "CHAIN_DISPATCHES", every)
    prompts = [fresh_tokens(100), fresh_tokens(C + 3)]
    tag = f"-catch{every}-{sampling}"
    long = SamplingParams(temperature=0.0, max_tokens=60)
    for i, p in enumerate(_residents()):
        assert eng.scheduler.add_request(Request(f"res{tag}{i}", p, long))
    eng.step()
    got = [Request(f"p{tag}{i}", p,
                   SamplingParams(max_tokens=12, **SAMPLING[sampling]))
           for i, p in enumerate(prompts)]
    for r in got:
        assert eng.scheduler.add_request(r)
    chained, rode = [], eng.stats()["prefill_ride_tokens"]
    while eng.step() or eng.scheduler.queue_depth:
        chained.append(eng._chained)
    assert max(chained) == every
    # the chain starts anew while the batch is still busy: a 0 between two
    # full chains, not only at the end
    first = chained.index(every)
    assert 0 in chained[first:] and every in chained[
        first + chained[first:].index(0):]
    assert (eng.stats()["prefill_ride_tokens"] - rode
            == sum(map(len, prompts)))
    want = _serve(cold, prompts, SAMPLING[sampling], residents=0, tag=tag)
    for a, b in zip(got, want):
        assert a.state is RequestState.FINISHED
        assert a.generated_tokens == b.generated_tokens
    idle(eng)
    idle(cold)


def test_an_engine_with_a_prefill_complete_hook_does_not_ride(engines):
    """The hook is owed the sequence before a decode step is spent on it
    (a disaggregated fleet's prefill role); a prompt that rode is decoding
    by the time the host sees its first token."""
    eng, _ = engines
    seen, before = [], eng.stats()["prefill_ride_tokens"]
    eng.on_prefill_complete = lambda r: seen.append(
        (r.request_id, r.state, len(r.generated_tokens)))
    _serve(eng, [fresh_tokens(20)], SAMPLING["greedy"], tag="-hook")
    eng.on_prefill_complete = None
    assert ("p-hook0", RequestState.RUNNING, 1) in seen
    assert eng.stats()["prefill_ride_tokens"] == before
    idle(eng)


def test_a_prompt_that_rode_is_a_prefix_hit_for_the_next(engines):
    eng, _ = engines
    if eng.cfg.is_recurrent:
        pytest.skip("a recurrent model reuses no prefix by page hash")
    prompt = fresh_tokens(3 * PS + 3)
    [a] = _serve(eng, [prompt], SAMPLING["greedy"], tag="-hit-a")
    cached = eng.stats()["prefix_cached_tokens"]
    [b] = _serve(eng, [prompt], SAMPLING["greedy"], tag="-hit-b")
    assert eng.stats()["prefix_cached_tokens"] - cached == 3 * PS
    assert a.generated_tokens == b.generated_tokens


@pytest.mark.parametrize("over", [
    dict(speculative="ngram"), dict(scheduler="static"),
    dict(quantization="int8"), dict(tensor_parallel=2)],
    ids=["speculative", "static scheduler", "int8 weights", "tp 2"])
def test_engines_that_keep_todays_path_never_ride(over):
    eng = support.engine("gpt-test", **over)
    assert eng._ride_rows == 0 and eng._decode_tail_args() == (None, None)
    reqs = _serve(eng, [fresh_tokens(20)], SAMPLING["greedy"])
    assert all(r.state is RequestState.FINISHED for r in reqs)
    assert eng.stats()["prefill_ride_tokens"] == 0


@pytest.mark.parametrize("name,page,rows", [
    ("gpt-test", 8, 16), ("gpt-test", 64, 128), ("olmoe-test", 64, 128),
    ("gpt-test", 128, 128), ("xing-test", 64, 128), ("xing-test", 256, 256),
    (LINEAR, 256, 256)])
def test_the_carry_is_read_off_the_page_size(name, page, rows):
    """``RIDE_PAGES`` pages a step, as chosen on the chip at pages of 64
    (128 rows); ONE page where a page alone holds as many (a latent model's
    pages of 256: two would be a window of 512 rows in one step; the
    delta-rule model's page is four sub-chunks of 64)."""
    eng = support.engine(name, kv_block_size=page, max_seq_len=512)
    assert eng._ride_rows == rows
    _state, pieces = eng._decode_tail_args()
    assert pieces.shape == (STEPS, PIECE_META + rows)


@pytest.mark.parametrize("name,over", [
    (HYBRID, dict(scheduler="static"))], ids=["hybrid, static scheduler"])
def test_a_layer_table_model_keeps_the_parents_decode_program(name, over):
    """A hybrid configuration (state-space layers in the table) rides since
    PR 44; where its engine does not (the static scheduler: ``_can_ride``)
    it hands its decode program no pieces, and the program lowers to the
    text of the parent's ``_decode_impl_n`` (written out below as it stood
    before PR 36, a tuple for its result): the table walked whole,
    ``recur_step`` unjitted."""
    eng = support.engine(name, **over)
    cfg = eng.cfg
    assert eng._ride_rows == 0
    args = (eng.params, eng.kv.k_pages, eng.kv.v_pages,
            jnp.asarray(eng.last_tokens), jnp.asarray(eng.positions),
            *eng._shared_decode_args(), *eng._decode_tail_args())
    assert len(args) == 13 and args[-1] is None     # the state, no pieces

    def _decode_impl_n(params, k_pages, v_pages, tokens, positions, tables,
                       stops, slot_keys, temp, top_k, top_p, state=None):
        out = decode_scan(
            params, tokens, positions, k_pages, v_pages, tables, stops,
            slot_keys, temp, top_k, top_p, cfg, STEPS, attn_impl="auto",
            w4_kernel_ok=True, w8_kernel_ok=False, return_moe_stats=True,
            ssm_state=state)
        return (out.sampled, out.tokens, out.positions, out.k_pages,
                out.v_pages, out.moe_stats, out.state)

    parents = jax.jit(_decode_impl_n, donate_argnums=(1, 2, 11)).lower(
        *args[:-1])
    assert _normalised_sha256(eng._decode_jit.lower(*args).as_text()) \
        == _normalised_sha256(parents.as_text())


# THE PINS. Every hash below is ``_normalised_sha256`` of a program's lowered
# StableHLO as the PARENT of PR 45 (2cf92d9) lowers it: ``git archive`` of
# that commit, this module's lowering helpers run there. The helper drops the
# ``jax.result_info`` attributes and the module line's ``@jit_<name>`` and
# nothing else, so a pin that holds says: the same operations over the same
# parameters, results and aliasing as the parent's program. (PR 45 put a
# record where a tuple's length depended on the model, one writer for a
# prompt's pages and the state pools' arming into ops/: none changes a
# traced operation.) When a PR means to move a program, print the new hash
# from the failing assertion and say here which commit it is of.

# the decode program (``_decode_jit``, pieces and all) of this module's engine
PARENTS_DECODE = {
    # (PR 56 MEANT to move these five: a decode step's window of ONE row
    # stages the sublane tile it touches, not a whole page a slot. The
    # hashes are of PR 56's own tree; the parent's (be7b4a5, the text PR 45's
    # parent lowered) were 54a27c57...23d64ddc, 55e215fc...adeb5c19,
    # d64f8944...824d25da, ca8bac76...01b24b26 and 74ad821e...1b82677f. This
    # module's pages are 8 rows of float32, one tile each: the text moves
    # though the staged bytes here do not. The piece of 16 rows in the same
    # programs keeps whole pages, as do all the prefill programs below.)
    "gpt-test": "88c47416ff8572777baa8b64cbbae043f6b08767f2eec3c12cf1d2a28f089c76",
    "olmoe-test": "d0733c28aac2ef91a3a4d13b92ea72ddc6574b84c8dc3b6c35b3aae4b891c977",
    "xing-test": "3755ca797e79d49e178d1335aab466a28358acbcf4a4b503bbede6048687bcc9",
    # (PR 47 MEANT to move this one: the denoise window is two blocks and
    # the commit rides the next block's first forward; PR 54 MEANT to move
    # it again: the window of 8 rows stages the sublane tiles it touches,
    # not two whole pages a slot. The hash is of PR 54's own tree; the
    # parent's (0fe0c16, PR 47's text) was 584661dc...d277e604. PR 56 left
    # it where it was)
    "sdar-test": "9c5ed40f7256ea0a2a98d2ed4b09d28d38910ef29381eab13627846fa7be5c7c",
    LINEAR: "a0f4446cfa5c5724085dd4de9cb1ef0cef09aecf5a60cb43d30ba2d8b62e7001",
    HYBRID: "f7ca03e9c55c9e6112f84a5b3be00fbc95d0eb5684e51d0687d549279946fab3",
}


@pytest.mark.parametrize("name", list(PARENTS_DECODE))
def test_the_other_models_decode_programs_are_the_parents(name):
    """The RIDING decode programs of the uniform stack (dense, MoE), of the
    latent, the delta-rule and the hybrid table lower to PR 56's text (the
    parent's but for the one-row write, which stages a tile), and the
    diffusion model's denoise program to PR 54's (PR 43 and 44 taught the
    table walk a recurrent layer's piece by one seam, ``recur_at``; PR 45
    made what a step and a dispatch return a record)."""
    eng = support.engine(name, **PINNED)
    assert eng._ride_rows == (0 if name == "sdar-test" else C)
    text = eng._decode_jit.lower(
        eng.params, eng.kv.k_pages, eng.kv.v_pages,
        *eng._decode_head_args(), *eng._shared_decode_args(),
        *eng._decode_tail_args()).as_text()
    assert _normalised_sha256(text) == PARENTS_DECODE[name]


# ``kimi-linear-test``'s prefill programs for the engine below (bucket 32),
# under this module's chunk of 8. The chunk and the final-chunk program read
# and write their slot's conv windows as a decode step's piece does (a
# masked sum and a select over the pool, ONE form for both callers:
# ``ops/kda.py slot_state``), and keep their layers' states stacked in the
# walk's carry; what they compute is held to the cold program's tokens and
# pools elsewhere (tests/test_kimi_linear.py, and this module's pieces
# against them)
LINEAR_PREFILL = {
    "cold": "4c8e46157ff0c80cfdc174143991a3c5fb3f81e5a3def687f2ea4420256d6f39",
    "chunk": "6e80a83be8a892eaff4b1026eeaf154982c5ef552f8c5284888d06c2f37a0186",
    "final chunk": "addc731e988e784fc71c385ca73bdf4bd1a7b8754d203deb2b8095e293456071",
}


def _shapes(tree):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)


def _vec(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def _prefill_texts(eng, bucket=32, programs=("cold", "suffix", "chunk")):
    """{program: lowered text} of an engine's cold, suffix (final-chunk) and
    chunk programs at one bucket, as the engine jits them."""
    from distributed_llm_training_and_inference_system_tpu.serve.sampling import (
        seed_key_data)
    params, kp, vp = _shapes((eng.params, eng.kv.k_pages, eng.kv.v_pages))
    # (a recurrent model's programs take its state pools and the slot)
    state = () if eng.kv.state is None else (_shapes(eng.kv.state), _vec())
    sampling = _shapes(eng._sampling_args(seed_key_data(0), 0,
                                          SamplingParams()))
    window = (params, _vec(1, bucket), _vec(1), _vec(1), kp, vp,
              _vec(1, eng.kv.max_pages_per_slot))
    lower = {
        "cold": lambda: eng._prefill_fn(bucket).lower(
            params, _vec(1, bucket), _vec(1), kp, vp,
            _vec(bucket // eng.kv.page_size), *sampling, *state),
        "suffix": lambda: eng._extend_prefill_fn(bucket).lower(
            *window, *sampling, *state),
        "chunk": lambda: eng._extend_chunk_fn(bucket).lower(*window, *state),
    }
    return {name: lower[name]().as_text() for name in programs}


def _linear_prefill_texts():
    """{program: lowered text} of the linear test model's cold, chunk and
    final-chunk programs as an engine jits them."""
    # (pinned at a table of 16 pages, buckets of 16 and chunks of 32)
    eng = support.engine(LINEAR, max_seq_len=128, prefill_chunk=16,
                         chunked_prefill_tokens=32)
    texts = _prefill_texts(eng)
    return {"cold": texts["cold"], "chunk": texts["chunk"],
            "final chunk": texts["suffix"]}


# ``nemotron-h-test``'s COLD prefill program (bucket 32) for the engine of
# ``_hybrid_cold_prefill_text``: under the riding gate its cold programs run
# as before PR 44
HYBRID_COLD_PREFILL = "ffc2768f7018028473533fbea7e1b8e2311e2623ee681d2061d650237c801541"


def _hybrid_cold_prefill_text():
    return _prefill_texts(support.engine(HYBRID, **PINNED),
                          programs=("cold",))["cold"]


def test_the_hybrid_models_cold_prefill_program_is_the_parents():
    """The hybrid rides since PR 44; under the gate its cold programs run
    as before, and lower to the parent's text."""
    assert _normalised_sha256(_hybrid_cold_prefill_text()) \
        == HYBRID_COLD_PREFILL


# ``sdar-test``'s cold, suffix and chunk programs (bucket 32) as the parent
# of PR 47 (b1ab06b) lowers them: PR 47 moved the commit into the denoise
# program's window (``extend_step_forward(head_from=)``, whose default leaves
# every other caller's text alone), and a prompt's programs are as they were
SDAR_PREFILL = {
    "cold": "6e71c8a9f8fff78c9cfeebed8c487d3ae44e85ed9f2cecd99efa9cf1eba0ed5c",
    "suffix": "1c07fca112b5b307939ce2d11242f70d331d9146336db1d1b7adb86b493cff6a",
    "chunk": "12b6f30238db3a113dd8a99aedf2407a76354e66ec61c37a135aba213538ff07",
}


def test_the_diffusion_models_prefill_programs_are_the_parents():
    assert {name: _normalised_sha256(text) for name, text in
            _prefill_texts(support.engine("sdar-test", **PINNED)).items()
            } == SDAR_PREFILL


def test_the_linear_models_prefill_programs_are_pinned():
    """The delta-rule model rides since PR 43 (its decode program carries
    pieces); under the gate its cold, chunk and final-chunk programs still
    run, and lower to the parent's text."""
    assert {name: _normalised_sha256(text)
            for name, text in _linear_prefill_texts().items()
            } == LINEAR_PREFILL


# every OTHER serve program the six test templates have, at bucket 32 of this
# module's ``_engine``: the cold prefill, the suffix prefill (a prompt behind
# a prefix hit, a chunked prompt's last chunk) and the prefill chunk; the
# dense model's cold prefill again over int8 and int4 pages (the writer's
# other two page formats). State-space (``M``) layers take no window over
# their state from an engine (no prefix hit, no chunk: ``kv_cache.REFUSED``)
SERVE_PROGRAMS = {
    ("gpt-test", "cold"):
        "210fa09f5c1aca437b4f155c59c5494c628d1aa99ea601375b6ac5e36a566592",
    ("gpt-test", "suffix"):
        "93cd4f2c0056d48d1dbabb6a8fbf4e9dd6cf3681087b4350779254e58d605981",
    ("gpt-test", "chunk"):
        "7433216e0dfbbb89644424541c0954c661a7122e554e1b238dc0a90d4b307249",
    ("gpt-test", "cold, int8 pages"):
        "02ff8d3a013b076e3df82131eccb6659b7ba520837a405279438de3fd72cc55a",
    ("gpt-test", "cold, int4 pages"):
        "0391474717888b103a850b0b2f6282a8b43eda349c1049e1d6ab81d1d3631277",
    ("olmoe-test", "cold"):
        "ab21c3118f3f43be52c3541b230418fb2955baf6b0dd4aa8a0008844b9534e0d",
    ("olmoe-test", "suffix"):
        "08e897e1b9cbf608786cd15a2fd347c928efccf0f9329f7d3115f94c07621054",
    ("olmoe-test", "chunk"):
        "8339b80efceedbe2191e804340127b3eb94a35aa730421cae6fc50c51c5c7c12",
    (HYBRID, "cold"):
        "ffc2768f7018028473533fbea7e1b8e2311e2623ee681d2061d650237c801541",
    ("xing-test", "cold"):
        "8c542423a6130a07d8dfd02da1b7e6e399e99dc215918a7ebdc97a6e2b4154b2",
    ("xing-test", "suffix"):
        "d584bb0235708b2b612d11a8c6bde2763e1e79363dc15aa6dbe5d41fe2d8629c",
    ("xing-test", "chunk"):
        "6e9a50f970aea33bf096ee83833f15f9b8e866ef2fa14f59eeac6cef7e6171a5",
    (LINEAR, "cold"):
        "c0c621e89ef339225fd1bedf77e76d33bd429dc50f32b85a60e2977fa1ce3646",
    (LINEAR, "suffix"):
        "658104beeed0e2f646760e72cd146bcc4378ec3c837ef681b3aebb98ba222760",
    (LINEAR, "chunk"):
        "9526342057c850ce28b46ca5af90a4e9c043305f1a2a7719bfe59f26b5e3112c",
    ("sdar-test", "cold"):
        "6e71c8a9f8fff78c9cfeebed8c487d3ae44e85ed9f2cecd99efa9cf1eba0ed5c",
    ("sdar-test", "suffix"):
        "1c07fca112b5b307939ce2d11242f70d331d9146336db1d1b7adb86b493cff6a",
    ("sdar-test", "chunk"):
        "12b6f30238db3a113dd8a99aedf2407a76354e66ec61c37a135aba213538ff07",
}


@functools.cache
def _serve_program_hashes(name):
    """{program: normalised hash} of one template's prefill programs: one
    engine and one lowering a program (no compile), whatever the cases."""
    programs = tuple(p for n, p in SERVE_PROGRAMS if n == name and "," not in p)
    hashes = _prefill_texts(support.engine(name, **PINNED),
                            programs=programs)
    for pages in [p for n, p in SERVE_PROGRAMS if n == name and "," in p]:
        eng = support.engine(name, kv_quantization=pages.split()[1],
                             **PINNED)
        hashes[pages] = _prefill_texts(eng, programs=("cold",))["cold"]
    return {p: _normalised_sha256(text) for p, text in hashes.items()}


@pytest.mark.parametrize("name,program", list(SERVE_PROGRAMS),
                         ids=[f"{n}: {p}" for n, p in SERVE_PROGRAMS])
def test_every_serve_program_lowers_to_the_parents_text(name, program):
    """PR 45 moved the cold prompt's page writer and the state pools' arming
    out of the engine and made every program return a record: each program
    of each template is the parent's, operation for operation."""
    assert _serve_program_hashes(name)[program] \
        == SERVE_PROGRAMS[(name, program)]


def _pools(cfg, pages, fill=None):
    """(k_pages, v_pages) of ``pages`` pages, zeros or ``fill``ed; a latent
    model's ONE pool of padded rows and None."""
    shape = ((cfg.num_layers, pages, 1, PS, cfg.mla.page_width)
             if cfg.is_latent else
             (cfg.kv_layers, pages, cfg.num_kv_heads, PS, cfg.head_dim))
    pool = jnp.asarray(np.zeros(shape) if fill is None else fill(size=shape),
                       jnp.float32)
    return pool, (None if cfg.is_latent else pool + 1)


def _state(cfg, slots, fill=None):
    """{"ssm_state": the ``K`` or ``M`` layers' pools} (zeros or ``fill``ed,
    the states small as a decayed state is), {} for a model without them."""
    if not cfg.is_recurrent:
        return {}
    if cfg.kda_layers:
        k = cfg.kda
        shapes = {"conv": (cfg.kda_layers, k.conv_kernel - 1, slots,
                           k.conv_channels),
                  "ssm": (cfg.kda_layers, slots, k.num_heads, k.head_dim,
                          k.head_dim)}
    else:
        m = cfg.ssm
        shapes = {"conv": (cfg.ssm_layers, slots, m.conv_kernel - 1,
                           m.conv_channels),
                  "ssm": (cfg.ssm_layers, slots, m.num_heads, m.head_dim,
                          m.state_size)}
    return {"ssm_state": {
        name: jnp.asarray(np.zeros(shape) if fill is None
                          else 0.1 * fill(size=shape), jnp.float32)
        for name, shape in shapes.items()}}


def _slot_rows(cfg, name, slot):
    """The index of ``slot``'s rows in the state pool ``name``: a ``K``
    model's conv pool lies [L, K-1, slot, C], every other [L, slot, ...]."""
    if name == "conv" and cfg.kda_layers:
        return (slice(None), slice(None), slot)
    return (slice(None), slot)


def _carry(result):
    """``decode_scan``'s final carry: its result but for what the steps
    sampled."""
    return result._replace(sampled=None, firsts=None)


def _same(a, b):
    """Two trees, leaf for leaf, bit for bit."""
    a, b = (jax.tree_util.tree_leaves(t) for t in (a, b))
    assert len(a) == len(b)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


@pytest.mark.parametrize("name", MODELS)
def test_a_step_without_a_piece_samples_what_the_plain_step_samples(name):
    """``decode_scan`` with all-zero pieces branches to the plain step: the
    same tokens, the same pools (a ``K`` model's states and conv windows of
    every slot bit for bit, the idle slot's untouched), and no first
    token."""
    cfg = get_model_config(name)
    params = support.params_of(cfg)
    B, pages = 3, 9
    tables = jnp.asarray([[1, 2], [3, 4], [5, 6]], jnp.int32)
    args = (jnp.asarray([5, 6, 7], jnp.int32), jnp.asarray([3, 9, 0]),
            *_pools(cfg, pages, RNG.normal), tables,
            jnp.asarray([16, 16, 0]),
            jnp.zeros((B, 2), jnp.uint32), jnp.zeros(B, jnp.float32),
            jnp.zeros(B, jnp.int32), jnp.ones(B, jnp.float32))
    state = _state(cfg, B, RNG.normal)
    plain = decode_scan(params, *args, cfg, STEPS, attn_impl="gather",
                        **state)
    rode = decode_scan(
        params, *args, cfg, STEPS, attn_impl="gather", **state,
        ride=jnp.zeros((STEPS, PIECE_META + C), jnp.int32))
    np.testing.assert_array_equal(plain.sampled, rode.sampled)
    np.testing.assert_array_equal(rode.firsts, 0)
    assert plain.firsts is None
    _same(_carry(plain), _carry(rode))
    for name_, pool in state.get("ssm_state", {}).items():
        idle = _slot_rows(cfg, name_, 2)
        np.testing.assert_array_equal(rode.state[name_][idle], pool[idle])


@pytest.mark.parametrize("name", MODELS + ["the linear cell's table",
                                            "the hybrid cell's table"])
def test_a_riding_programs_two_bodies_share_what_they_do_alike(name):
    """A program that rides holds a carrying and a plain step body, and
    every start pays for both (trace, lowering, the compile cache's read:
    ``setup_s``). The slots' page writes with their T = 1 attention and the
    sampler run at the same shapes in both, so each is ONE function of the
    lowered program, called from both bodies (PERF.md 6, PR 36); a program
    that does not ride holds neither. A latent layer table's windows are
    one function a SHAPE for all its layers too: the slots' T = 1 (both
    bodies) and the piece's T = C."""
    import re
    cfg = (get_model_config(name) if name in MODELS
           else _hybrid_cfg(HYBRID_CELLS_TABLE) if "hybrid" in name
           else _linear_cfg(CELLS_TABLE))
    params = support.params_of(cfg)
    B, pages = 3, 9
    args = (params, jnp.zeros(B, jnp.int32), jnp.zeros(B, jnp.int32),
            *_pools(cfg, pages),
            jnp.zeros((B, 2), jnp.int32), jnp.ones(B, jnp.int32),
            jnp.zeros((B, 2), jnp.uint32), jnp.zeros(B, jnp.float32),
            jnp.zeros(B, jnp.int32), jnp.ones(B, jnp.float32))

    state = _state(cfg, B)

    def program(*a, ride=None):
        return decode_scan(*a, cfg, STEPS, ride=ride, **state)
    rides = jax.jit(program).lower(
        *args, ride=jnp.zeros((STEPS, PIECE_META + C), jnp.int32)).as_text()
    plain = jax.jit(program).lower(*args).as_text()
    def functions(shared):
        """[calls of each private function named ``shared``], most first."""
        names = re.findall(rf"func\.func private @({shared}(?:_\d+)?)\(",
                           rides)
        assert not re.search(rf"@{shared}(_\d+)?\(", plain), shared
        return sorted((len(re.findall(rf"call @{name}\(", rides))
                       for name in names), reverse=True)
    assert functions("sample_tokens") == [2]
    if cfg.layer_pattern:
        # a call site a layer of the table's head and ONE for the loop over
        # its periodic part (``*D`` then ``*E`` x 2: two sites, not three;
        # the linear and the hybrid TEST tables are all head, the linear
        # cell's is ``KDKEKE*E`` then ``KEKEKE*E`` x 2, the hybrid cell's
        # ``MEMEM*E`` x 2)
        head, unit, reps = table_period(cfg)
        assert reps == (0 if name in (LINEAR, HYBRID) else 2)
        assert len(head) + reps * len(unit) == cfg.num_layers
        sites = collections.Counter(kind for kind, _ in head + unit)
        if cfg.is_latent:
            assert functions("_latent_windows") == [2 * sites["*"],
                                                    sites["*"]]
        else:   # K/V pages: the slots' T = 1 windows, from both bodies
            assert functions("_windows") == [2 * sites["*"]]
        # every recurrent layer's one-token update of all slots is ONE
        # function, called from both bodies at its (traced) layer
        recurrent = sites["K"] + sites["M"]
        assert functions("step_pools") == (
            [2 * recurrent] if recurrent else [])
        # and the ONE sampler takes whole tiles of 8 rows (B + 1 = 4 here)
        assert re.search(r"func\.func private @sample_tokens\("
                         rf"%arg0: tensor<8x{cfg.vocab_size}xf32>", rides)
    else:
        assert functions("_windows") == [2]
    # (the chunked form of a piece is text the plain program lacks)
    assert len(rides) < (3 if cfg.is_recurrent else 2) * len(plain)


@pytest.mark.parametrize("pattern,head,unit,reps", [
    ("*D*E*E*E", "*D", "*E", 3),          # the latent cell's table, shorter
    ("*D*D*E*E", "*D*D", "*E", 2),
    ("*E*E", "", "*E", 2),
    ("*D*E", "*D*E", "", 0),              # nothing repeats
    ("MEMEM*E", "MEMEM*E", "", 0),        # the hybrid test table
    ("KDKEKE*EKEKEKE*E", "KDKEKE*EKEKEKE*E", "", 0),   # the linear test table
    # the linear cell's table
    ("KDKEKE*EKEKEKE*EKEKEKE*E", "KDKEKE*E", "KEKEKE*E", 2),
    ("KDKE*EKE*E", "KD", "KE*E", 2),
    ("*EEE*EEE*EEE", "", "*EEE", 3),
    ("MEMEM*EMEMEM*E", "", "MEMEM*E", 2),  # the hybrid cell's 14-layer table
])
def test_a_tables_periodic_part(pattern, head, unit, reps):
    """``gpt.table_period``: the shortest head, then the unit that repeats
    to the table's end; repetition r's layer of a kind is the unit's index
    + r x the kind's count in the unit, which walks every layer once."""
    import collections
    import dataclasses
    from distributed_llm_training_and_inference_system_tpu.models.gpt import (
        table_layers)
    cfg = dataclasses.replace(get_model_config("xing-test"),
                              layer_pattern=pattern, num_layers=len(pattern))
    got_head, got_unit, got_reps = table_period(cfg)
    assert "".join(k for k, _ in got_head) == head
    assert "".join(k for k, _ in got_unit) == unit and got_reps == reps
    per_rep = collections.Counter(k for k, _ in got_unit)
    walked = got_head + [(k, i + r * per_rep[k])
                         for r in range(got_reps) for k, i in got_unit]
    assert walked == table_layers(cfg)


@pytest.mark.parametrize("rows", [256, 1024, 1280, 2048, 8192, 1300])
def test_the_running_count_of_expert_choices_is_the_cumsum(rows):
    """``layers.running_count`` (a carrying decode step ranks 1,280
    choices, past where XLA's cumsum is cheap on the chip): whole blocks on
    the MXU, the rest as ever, always the exact count."""
    from distributed_llm_training_and_inference_system_tpu.models.layers import (
        running_count)
    choice = RNG.integers(0, 8, rows)
    live = RNG.random(rows) < 0.8
    onehot = (choice[:, None] == np.arange(8)[None]) & live[:, None]
    got = jax.jit(running_count)(jnp.asarray(onehot))
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(got, np.cumsum(onehot, axis=0))


# -- a delta-rule (``K``) model's piece: a window from its slot's own state --

# the linear cell's table: three periods ``K K K *``, so a riding program
# walks ``KEKEKE*E`` x 2 by a loop (the test model's two periods are all head)
CELLS_TABLE = "KDKEKE*EKEKEKE*EKEKEKE*E"


def _table_cfg(name, table=None):
    """A test model, or it with the layer table ``table``."""
    import dataclasses
    cfg = get_model_config(name)
    return cfg if table is None else dataclasses.replace(
        cfg, layer_pattern=table, num_layers=len(table))


def _linear_cfg(table=None):
    """The linear test model, or it with the layer table ``table``."""
    return _table_cfg(LINEAR, table)


def _linear_step_case(live, start, table=None):
    """Four slots of the linear test model (or of it with ``table``): slots 0 and 1 decode, slot 2's
    prompt rides ONE piece of ``live`` rows at ``start`` (pages 5..), slot
    3 is idle. The pools are random (the states small), so every slot has
    a former occupant's state; (cfg, params, decode_scan's arguments, the
    state pools, the piece's row)."""
    cfg = _linear_cfg(table)
    params = support.params_of(cfg)
    B, pages = 4, 12
    tables = jnp.asarray([[1, 2, 0, 0], [3, 4, 0, 0], [5, 6, 7, 8],
                          [0, 0, 0, 0]], jnp.int32)
    args = (jnp.asarray([5, 6, 7, 8], jnp.int32), jnp.asarray([3, 9, 0, 0]),
            *_pools(cfg, pages, RNG.normal), tables,
            jnp.asarray([16, 16, 0, 0]),
            jnp.zeros((B, 2), jnp.uint32), jnp.zeros(B, jnp.float32),
            jnp.zeros(B, jnp.int32), jnp.ones(B, jnp.float32))
    piece = np.zeros((STEPS, PIECE_META + C), np.int32)
    piece[0, :PIECE_META] = (2, start, live, 0)
    piece[0, PIECE_META:] = RNG.integers(1, 250, C)     # padding: garbage
    return cfg, params, args, _state(cfg, B, RNG.normal)["ssm_state"], piece


@functools.cache
def _linear_programs(table):
    """(``decode_scan``'s final carry, the chunk program of slot 2) of
    the linear test model with ``table``, jitted ONCE for the cases below
    (a piece's slot, start and live rows are values, not shapes)."""
    cfg = _linear_cfg(table)

    def dispatch(params, args, state, ride=None):
        return decode_scan(params, *args, cfg, STEPS, attn_impl="gather",
                           ssm_state=state, ride=ride)

    def chunk(params, tokens, start, pool, table_row, ok, state):
        return extend_step_forward(
            params, tokens, start, pool, None, table_row, cfg, write_ok=ok,
            attn_impl="gather", ssm_state=state, state_slot=jnp.int32(2))
    return jax.jit(dispatch), jax.jit(chunk)


@pytest.mark.parametrize("table", [None, CELLS_TABLE],
                         ids=["walked whole", "walked by a loop"])
@pytest.mark.parametrize("start", [0, 2 * PS], ids=["from 0", "from its state"])
@pytest.mark.parametrize("live", [C, C - 5], ids=["whole", "prefix mask"])
def test_a_piece_leaves_the_pools_a_chunk_program_leaves(live, start, table):
    """A decode dispatch whose first step carries a piece, against the
    plain dispatch followed by the CHUNK program over the same rows of the
    same slot: the same latent pages, and in both state pools the piece's
    slot's rows equal to the chunk program's (from ZERO at ``start`` 0,
    whatever the slot held; from the slot's own rows behind that), the
    decoding slots' theirs, and the idle slot's rows as they were, bit for
    bit. (The riding program of the cell's table walks its periodic
    part by a loop, the piece's rows in the loop's carry; the plain program
    and the chunk program walk it whole.)"""
    cfg, params, args, state, piece = _linear_step_case(live, start, table)
    assert table_period(cfg)[2] == (2 if table else 0)
    dispatch, chunk = _linear_programs(table)
    rode = dispatch(params, args, state, jnp.asarray(piece))
    plain = dispatch(params, args, state)
    tokens = jnp.asarray(piece[:1, PIECE_META:])
    ok = (jnp.arange(C) < live)[None]
    after = chunk(params, tokens, jnp.asarray([start]), plain.k_pages,
                  args[4][2:3], ok, plain.state)
    pool, chunked = after.k_pages, after.state
    # the piece's live rows landed in pages 5.. (padding: the scratch page)
    np.testing.assert_allclose(rode.k_pages[:, 1:], pool[:, 1:], rtol=2e-4,
                               atol=2e-5)
    for name, mine in (("conv", (slice(None), slice(None), 2)),
                       ("ssm", (slice(None), 2))):
        got, want = np.asarray(rode.state[name]), np.asarray(chunked[name])
        np.testing.assert_allclose(got[mine], want[mine], rtol=2e-4,
                                   atol=2e-5)
        assert not np.allclose(got[mine], np.asarray(state[name])[mine])
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
        idle = (*mine[:-1], 3)
        np.testing.assert_array_equal(got[idle],
                                      np.asarray(state[name])[idle])
    if start == 0:      # ... and read nothing of the former occupant's
        fresh = {k: v.at[(slice(None), 2) if k == "ssm" else
                         (slice(None), slice(None), 2)].set(7.0)
                 for k, v in state.items()}
        again = dispatch(params, args, fresh, jnp.asarray(piece))
        _same(again.state, rode.state)


@pytest.fixture(scope="module")
def linear_engines():
    return _pair(LINEAR)


def test_a_document_rides_from_zero_in_a_slot_that_held_a_state(
        linear_engines):
    """A prompt of 5 pages (three pieces) in an engine whose every slot
    holds a former occupant's state and conv window: its first piece starts
    at 0 and takes both as zero, the next read what the one before wrote.
    The tokens are the chunk programs' on a clean engine."""
    eng, cold = linear_engines
    eng.kv.state = {name: jnp.asarray(RNG.normal(size=pool.shape) * 0.3,
                                      pool.dtype)
                    for name, pool in eng.kv.state.items()}
    prompt = fresh_tokens(5 * PS)
    before = eng.stats()
    [got] = _serve(eng, [prompt], SAMPLING["greedy"], tag="-occupied")
    [want] = _serve(cold, [prompt], SAMPLING["greedy"], residents=0,
                    tag="-occupied")
    after = eng.stats()
    assert got.generated_tokens == want.generated_tokens
    # the second and third piece read the slot's state: 3 pages of 5
    assert (after["state_carry_chunks"] - before["state_carry_chunks"],
            after["state_carry_tokens"] - before["state_carry_tokens"]
            ) == (2, 3 * PS)
    assert after["kda"]["state_carry_tokens"] == after["state_carry_tokens"]
    # (the clean engine's chunk programs: every chunk but the first)
    assert cold.stats()["state_carry_chunks"] >= 2
    idle(eng)
    idle(cold)


@pytest.mark.parametrize("how", ["cancel", "preempt", "fail_all"])
def test_a_slot_is_reused_after_a_riding_document_was_dropped(
        linear_engines, how):
    """A riding document dropped in its middle leaves a half-written state
    in its slot, and pieces of it still in flight; the next prompts (one of
    them takes that slot) ride from zero and are served the chunk
    programs' tokens."""
    eng, cold = linear_engines
    req = _start_riding(eng, fresh_tokens(STEPS * C + 20), f"dropped-{how}")
    slot = req.slot
    with eng.lock:
        if how == "cancel":
            assert eng.scheduler.cancel(req.request_id)
        elif how == "preempt":
            eng._preempt(slot)
            assert eng.scheduler.cancel(req.request_id)
    if how == "fail_all":
        eng.fail_all("boom")
    eng.run_until_idle()
    assert req.state in (RequestState.CANCELLED, RequestState.FAILED)
    idle(eng)
    prompts = [fresh_tokens(2 * C + 3), fresh_tokens(C + 1), fresh_tokens(3 * C)]
    tag = f"-after-{how}"
    # two residents and three riders: every slot is taken, ``slot`` too
    got = _serve(eng, prompts, SAMPLING["greedy"], tag=tag)
    want = _serve(cold, prompts, SAMPLING["greedy"], residents=0, tag=tag)
    for a, b in zip(got, want):
        assert a.state is RequestState.FINISHED
        assert a.generated_tokens == b.generated_tokens
    idle(eng)
    idle(cold)


# -- a state-space (``M``) model's piece: one chunk of the scan from its slot's
# -- own conv tail and state (PR 44) ------------------------------------------

# the hybrid cell's table: two motifs, so a riding program walks ``MEMEM*E``
# x 2 by a loop (the test model's one motif is all head)
HYBRID_CELLS_TABLE = "MEMEM*EMEMEM*E"


def _hybrid_cfg(table=None):
    """The hybrid test model, or it with the layer table ``table``."""
    return _table_cfg(HYBRID, table)


@pytest.mark.parametrize("name,table,carries", [
    ("gpt-test", None, True), ("olmoe-test", None, True),
    ("xing-test", None, True), (LINEAR, None, True), (HYBRID, None, True),
    (HYBRID, HYBRID_CELLS_TABLE, True), (LINEAR, CELLS_TABLE, True),
    (HYBRID, "*E*E*E*", True),          # no recurrent layer, K/V pages
    ("sdar-test", None, False),         # a window of 4 rows a slot already
    (HYBRID, "KEKEK*E", True),          # delta-rule layers beside K/V pages
    ("xing-test", "*DME*E", False),     # state-space layers, a latent pool
], ids=lambda v: str(v))
def test_which_layer_tables_a_decode_step_can_carry_a_piece_through(
        name, table, carries):
    """``can_carry`` reads the layer kinds and the kind of page pool: the
    uniform stack and every table that is served ride (delta-rule layers
    beside K/V pages since ``solar_open2``); diffusion and the one
    combination no program has run do not."""
    assert can_carry(_table_cfg(name, table)) is carries


def _hybrid_step_case(n, table=None):
    """Four slots of the hybrid test model (or of it with ``table``): slots
    0 and 1 decode, slot 2's prompt of ``n`` tokens rides from position 0,
    a piece a step (pages 5..), slot 3 is idle. The pools are random (the
    states small), so every slot has a former occupant's state; (cfg,
    params, decode_scan's arguments, the state pools, the pieces, the
    prompt)."""
    cfg = _hybrid_cfg(table)
    params = support.params_of(cfg)
    B, pages = 4, 14
    tables = np.zeros((B, 8), np.int32)
    tables[0, :2], tables[1, :2], tables[2] = (1, 2), (3, 4), range(5, 13)
    tables = jnp.asarray(tables)
    args = (jnp.asarray([5, 6, 7, 8], jnp.int32), jnp.asarray([3, 9, 0, 0]),
            *_pools(cfg, pages, RNG.normal), tables,
            jnp.asarray([16, 16, 0, 0]),
            jnp.zeros((B, 2), jnp.uint32), jnp.zeros(B, jnp.float32),
            jnp.zeros(B, jnp.int32), jnp.ones(B, jnp.float32))
    prompt = RNG.integers(1, 250, n)
    pieces = RNG.integers(1, 250, (STEPS, PIECE_META + C))  # padding: garbage
    pieces[:, :PIECE_META] = 0
    for k, start in enumerate(range(0, n, C)):
        live = min(C, n - start)
        pieces[k, :PIECE_META] = (2, start, live, 0)
        pieces[k, PIECE_META:PIECE_META + live] = prompt[start:start + live]
    return (cfg, params, args, _state(cfg, B, RNG.normal)["ssm_state"],
            pieces.astype(np.int32), prompt)


@functools.cache
def _hybrid_programs(table):
    """(``decode_scan``'s final carry, the cold program's K/V and state) of
    the hybrid test model with ``table``, jitted ONCE for the cases below
    (a piece's slot, start and live rows are values, not shapes)."""
    from distributed_llm_training_and_inference_system_tpu.models import gpt
    cfg = _hybrid_cfg(table)

    def dispatch(params, args, state, ride=None):
        return decode_scan(params, *args, cfg, STEPS, attn_impl="gather",
                           ssm_state=state, ride=ride)

    def cold(params, tokens, n):
        bucket = tokens.shape[1]
        live = (jnp.arange(bucket)[None] < n).astype(jnp.int32)
        _, (kd, vd), (tails, hs) = gpt.forward(
            params, tokens, cfg,
            kv_cache=gpt.init_kv_cache(cfg, 1, bucket, dtype=jnp.float32),
            cache_offset=jnp.zeros((1,), jnp.int32), segment_ids=live,
            return_ssm_state=True)
        return kd[:, 0], vd[:, 0], tails[:, 0], hs[:, 0]
    return jax.jit(dispatch), jax.jit(cold)


@pytest.mark.parametrize("table", [None, HYBRID_CELLS_TABLE],
                         ids=["walked whole", "walked by a loop"])
@pytest.mark.parametrize("n", [5, C, C + 1, 2 * C + 2, 3 * C - 5, 4 * C],
                         ids=["a short piece", "one piece",
                              "a last piece of 1 row", "of 2 rows",
                              "three pieces", "a piece every step"])
def test_pieces_leave_the_pools_the_cold_program_leaves(n, table):
    """A decode dispatch whose first steps carry a prompt's pieces (the
    first from ZERO whatever the slot held, each next from what the one
    before wrote into the float32 pool), against the COLD program over the
    whole prompt: in both state pools the prompt's slot's rows are the
    cold program's conv tail and state (a last piece of fewer than K-1
    rows keeps columns of the piece before), its pages hold the cold
    program's K and V, the decoding slots' rows are the plain dispatch's,
    and the idle slot's rows are as they were, bit for bit. (The riding
    program of the cell's table walks its two motifs by a loop, the
    pieces' rows in the loop's carry; the cold program walks it whole.)"""
    cfg, params, args, state, pieces, prompt = _hybrid_step_case(n, table)
    assert table_period(cfg)[2] == (2 if table else 0)
    dispatch, cold = _hybrid_programs(table)
    rode = dispatch(params, args, state, jnp.asarray(pieces))
    plain = dispatch(params, args, state)
    padded = np.full((1, 4 * C), 7, np.int32)
    padded[0, :n] = prompt
    kd, vd, tails, hs = cold(params, jnp.asarray(padded), n)
    for got, want in ((rode.k_pages, kd), (rode.v_pages, vd)):
        rows = np.asarray(got)[:, 5:13].transpose(0, 1, 3, 2, 4).reshape(
            got.shape[0], -1, *want.shape[2:])
        np.testing.assert_allclose(rows[:, :n], want[:, :n], rtol=2e-4,
                                   atol=2e-5)
    for name, want in (("conv", tails), ("ssm", hs)):
        got, was = np.asarray(rode.state[name]), np.asarray(state[name])
        np.testing.assert_allclose(got[:, 2], want, rtol=2e-4, atol=2e-5)
        assert not np.allclose(got[:, 2], was[:, 2])
        np.testing.assert_allclose(got[:, :2], np.asarray(
            plain.state[name])[:, :2], rtol=2e-4, atol=2e-5)
        assert not np.allclose(got[:, :2], was[:, :2])
        np.testing.assert_array_equal(got[:, 3], was[:, 3])
    # ... and read nothing of the former occupant's
    fresh = {k: v.at[:, 2].set(7.0) for k, v in state.items()}
    again = dispatch(params, args, fresh, jnp.asarray(pieces))
    _same(again.state, rode.state)


@pytest.fixture(scope="module")
def hybrid_cell_engines():
    """(riding, cold) engines of the hybrid test model with the CELL's
    two-motif table: the riding program walks it by a loop."""
    cfg = _hybrid_cfg(HYBRID_CELLS_TABLE)
    return support.engine(cfg), support.engine(cfg)


def test_prompts_of_one_two_and_four_pieces_ride_to_the_cold_tokens(
        hybrid_cell_engines):
    """Prompts of 1, 2 and 4 pieces (the last of each shorter than a
    piece; the third's last of ONE row) through the two-motif table, in an
    engine whose every slot holds a former occupant's state and conv tail:
    the greedy tokens of the cold programs on a clean engine, and the
    pieces behind a prompt's first counted as reading their slot's
    state."""
    eng, cold = hybrid_cell_engines
    eng.kv.state = {name: jnp.asarray(RNG.normal(size=pool.shape) * 0.3,
                                      pool.dtype)
                    for name, pool in eng.kv.state.items()}
    prompts = [fresh_tokens(C - 3), fresh_tokens(2 * C - 5), fresh_tokens(3 * C + 1)]
    before = eng.stats()
    got = _serve(eng, prompts, SAMPLING["greedy"], tag="-pieces")
    want = _serve(cold, prompts, SAMPLING["greedy"], residents=0,
                  tag="-pieces")
    after = eng.stats()
    for a, b in zip(got, want):
        assert a.state is RequestState.FINISHED
        assert a.generated_tokens == b.generated_tokens
    assert (after["prefill_ride_tokens"] - before["prefill_ride_tokens"]
            == sum(map(len, prompts)))
    assert after["prefill_ride_steps"] - before["prefill_ride_steps"] == 7
    # the second piece of the second prompt, three of the third
    assert (after["state_carry_chunks"] - before["state_carry_chunks"],
            after["state_carry_tokens"] - before["state_carry_tokens"]
            ) == (4, (C - 5) + (2 * C + 1))
    assert after["ssm"]["state_carry_tokens"] == after["state_carry_tokens"]
    # ``slot_steps`` counts the slots' one-token updates alone
    assert (after["ssm"]["slot_steps"] - before["ssm"]["slot_steps"]
            <= (after["decode_steps"] - before["decode_steps"]) * SLOTS)
    assert cold.stats()["state_carry_chunks"] == 0      # cold programs alone
    idle(eng)
    idle(cold)


@pytest.mark.parametrize("how", ["cancel", "preempt", "fail_all"])
def test_a_slot_is_reused_after_a_riding_hybrid_prompt_was_dropped(
        hybrid_cell_engines, how):
    """A riding prompt dropped in its middle leaves a half-written state in
    its slot, and pieces of it still in flight; the next prompts (one of
    them takes that slot) ride from zero and decode as on a fresh engine
    (the cold programs' tokens)."""
    eng, cold = hybrid_cell_engines
    req = _start_riding(eng, fresh_tokens(STEPS * C + 20), f"dropped-h-{how}")
    slot = req.slot
    with eng.lock:
        if how == "cancel":
            assert eng.scheduler.cancel(req.request_id)
        elif how == "preempt":
            eng._preempt(slot)
            assert eng.scheduler.cancel(req.request_id)
    if how == "fail_all":
        eng.fail_all("boom")
    eng.run_until_idle()
    assert req.state in (RequestState.CANCELLED, RequestState.FAILED)
    assert np.abs(np.asarray(eng.kv.state["ssm"][:, slot])).max() > 0
    idle(eng)
    prompts = [fresh_tokens(2 * C + 3), fresh_tokens(C + 1), fresh_tokens(3 * C)]
    tag = f"-after-h-{how}"
    # two residents and three riders: every slot is taken, ``slot`` too
    got = _serve(eng, prompts, SAMPLING["greedy"], tag=tag)
    want = _serve(cold, prompts, SAMPLING["greedy"], residents=0, tag=tag)
    for a, b in zip(got, want):
        assert a.state is RequestState.FINISHED
        assert a.generated_tokens == b.generated_tokens
    idle(eng)
    idle(cold)
