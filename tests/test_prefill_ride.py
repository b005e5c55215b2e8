"""A prompt admitted to a busy batch rides the decode dispatches (PR 36):
its rows join the decode steps' matmuls, a piece of ``RIDE_PAGES`` pages a
step, and no prefill program runs between two dispatches. What must hold:
the tokens a riding prompt is served and the K/V (or latent rows) its pages
hold are the cold program's, and behind a prefix hit the suffix program's;
under half occupancy nothing rides; a riding prompt that is cancelled,
preempted or failed gives its pages and slot back; and a model that cannot
ride (a layer table with recurrent layers) keeps the parent's decode
program.

CPU, float32, the dense, the MoE and (PR 41) the latent test
configurations; 4 slots, pages of 8 tokens, 4 steps a dispatch, so a piece
is 16 rows.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_training_and_inference_system_tpu.config import get_model_config
from distributed_llm_training_and_inference_system_tpu.config.schema import ServeConfig
from distributed_llm_training_and_inference_system_tpu.models import init
from distributed_llm_training_and_inference_system_tpu.models.gpt import (
    table_period)
from distributed_llm_training_and_inference_system_tpu.serve import (
    InferenceEngine,
    Request,
    SamplingParams,
)
from distributed_llm_training_and_inference_system_tpu.serve.decode import (
    PIECE_META, decode_scan)
from distributed_llm_training_and_inference_system_tpu.serve.scheduler import (
    RequestState)

PS, STEPS, SLOTS = 8, 4, 4
C = InferenceEngine.RIDE_PAGES * PS
MODELS = ["gpt-test", "olmoe-test", "xing-test"]
RNG = np.random.default_rng(36)


def _tokens(n):
    return [int(t) for t in RNG.integers(1, 250, n)]


def _residents(n=2):
    """Fresh prompts for the slots that decode while the others ride."""
    return [_tokens(9), _tokens(13)][:n]


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


def _engine(name, **over):
    cfg = get_model_config(name)
    opts = dict(model=name, max_batch_size=SLOTS, max_seq_len=192,
                prefill_chunk=32, kv_block_size=PS, dtype="float32",
                decode_steps_per_dispatch=STEPS)
    opts.update(over)
    return InferenceEngine(cfg, ServeConfig(**opts),
                           params=init(cfg, jax.random.PRNGKey(0)), seed=0)


@pytest.fixture(scope="module", params=MODELS)
def engines(request):
    """(the engine whose prompts ride, the cold reference) of one model,
    shared by the module's cases: every case leaves both idle."""
    return _engine(request.param), _engine(request.param)


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
    long = SamplingParams(temperature=0.0, max_tokens=60)
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


def _idle(eng):
    assert eng._reserved_pages == 0 and not eng._riding
    assert not eng._req_slot and not eng.active.any()
    assert all(r is None for r in eng.scheduler.slots)
    # every page is free or kept for a prefix hit: none is held by a slot
    assert eng.kv.free_pages == eng.kv.num_pages - 1


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
    tag = f"-{scenario}-{sampling}-{start}"
    # (fresh tokens a case: a repeated prompt would be a prefix hit)
    prefix = _tokens(CACHED[start])
    if prefix:
        for eng in engines:     # the prefix's whole pages into the cache
            _serve(eng, [prefix + _tokens(3)], SAMPLING["greedy"],
                   residents=0, tag=tag + "-prefix")
    # the scenario's lengths are what is left to prefill
    prompts = [prefix + _tokens(n) for n in SCENARIOS[scenario]]
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
    _idle(riding)
    _idle(cold)


def test_under_half_occupancy_the_cold_program_runs_and_nothing_rides(engines):
    eng, _ = engines
    before = eng.stats()
    # one resident of four slots: under the gate
    [r] = _serve(eng, [_tokens(40)], SAMPLING["greedy"],
                 residents=1, tag="-gate")
    after = eng.stats()
    assert r.state is RequestState.FINISHED
    assert after["prefill_ride_tokens"] == before["prefill_ride_tokens"]
    assert after["prefill_ride_steps"] == before["prefill_ride_steps"]
    assert after["prefill_tokens"] - before["prefill_tokens"] == 9 + 40
    _idle(eng)


def test_the_riding_rows_are_counted_as_prefill_rows(engines):
    """``prefill_tokens`` / ``prefill_padded_tokens`` count a riding
    prompt's tokens and C rows a carrying step, so the live-row share
    keeps reading the truth."""
    eng, _ = engines
    before = eng.stats()
    _serve(eng, [_tokens(2 * C + 1)], SAMPLING["greedy"], tag="-rows")
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
    req = _start_riding(eng, _tokens(STEPS * C + 20), "cancel")
    held = eng.kv.free_pages
    with eng.lock:
        assert eng.scheduler.cancel(req.request_id)
    eng.step()
    assert req.state is RequestState.CANCELLED
    assert req.request_id not in eng._riding and not req.generated_tokens
    assert eng.kv.free_pages > held
    eng.run_until_idle()
    _idle(eng)


def test_a_preempted_riding_prompt_is_requeued_and_served(engines):
    eng, cold = engines
    prompt = _tokens(STEPS * C + 20)
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
    _idle(eng)


def test_a_dry_pool_preempts_the_riding_prompt_first():
    """The riding prompt is the newest admission: when a resident cannot
    grow its chain, it is the victim, and everything is still served."""
    eng = _engine("gpt-test", kv_num_blocks=14, prefix_caching=False)
    long = SamplingParams(temperature=0.0, max_tokens=40)
    for i, p in enumerate(_residents()):
        assert eng.scheduler.add_request(Request(f"res{i}", p, long))
    eng.step()
    rider = Request("rider", _tokens(40),
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
    req = _start_riding(eng, _tokens(STEPS * C + 20), "fail")
    eng.fail_all("boom")
    assert req.state is RequestState.FAILED and not eng._riding
    assert eng._pending is None
    eng.run_until_idle()
    _idle(eng)


def test_a_riding_prompt_does_not_break_the_pipelined_chain(engines):
    """Neither the admission of a prompt that rides nor its last piece
    breaks the chain: the device arms the slot in the carry the next
    dispatch chains on, and the host, which learns the first token a
    dispatch later, already counts the slot resident."""
    eng, _ = engines
    req = _start_riding(eng, _tokens(STEPS * C + 20), "chain")
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
    _idle(eng)


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
    prompts = [_tokens(100), _tokens(C + 3)]
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
    _idle(eng)
    _idle(cold)


def test_an_engine_with_a_prefill_complete_hook_does_not_ride(engines):
    """The hook is owed the sequence before a decode step is spent on it
    (a disaggregated fleet's prefill role); a prompt that rode is decoding
    by the time the host sees its first token."""
    eng, _ = engines
    seen, before = [], eng.stats()["prefill_ride_tokens"]
    eng.on_prefill_complete = lambda r: seen.append(
        (r.request_id, r.state, len(r.generated_tokens)))
    _serve(eng, [_tokens(20)], SAMPLING["greedy"], tag="-hook")
    eng.on_prefill_complete = None
    assert ("p-hook0", RequestState.RUNNING, 1) in seen
    assert eng.stats()["prefill_ride_tokens"] == before
    _idle(eng)


def test_a_prompt_that_rode_is_a_prefix_hit_for_the_next(engines):
    eng, _ = engines
    prompt = _tokens(3 * PS + 3)
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
    eng = _engine("gpt-test", **over)
    assert eng._ride_rows == 0 and eng._decode_tail_args() == ()
    reqs = _serve(eng, [_tokens(20)], SAMPLING["greedy"])
    assert all(r.state is RequestState.FINISHED for r in reqs)
    assert eng.stats()["prefill_ride_tokens"] == 0


@pytest.mark.parametrize("name,page,rows", [
    ("gpt-test", 8, 16), ("gpt-test", 64, 128), ("olmoe-test", 64, 128),
    ("gpt-test", 128, 128), ("xing-test", 64, 128), ("xing-test", 256, 256)])
def test_the_carry_is_read_off_the_page_size(name, page, rows):
    """``RIDE_PAGES`` pages a step, as chosen on the chip at pages of 64
    (128 rows); ONE page where a page alone holds as many (a latent model's
    pages of 256: two would be a window of 512 rows in one step)."""
    eng = _engine(name, kv_block_size=page, max_seq_len=512)
    assert eng._ride_rows == rows
    [pieces] = eng._decode_tail_args()[1:]
    assert pieces.shape == (STEPS, PIECE_META + rows)


@pytest.mark.parametrize("name", ["nemotron-h-test", "kimi-linear-test"])
def test_a_layer_table_model_keeps_the_parents_decode_program(name):
    """A hybrid and a linear-attention configuration (recurrent layers in
    the table) do not ride: the engine hands their decode program no
    pieces, and the program lowers to the text of the parent's
    ``_decode_impl_n`` (written out below as it stood)."""
    cfg = get_model_config(name)
    eng = InferenceEngine(
        cfg, ServeConfig(model=name, max_batch_size=SLOTS, max_seq_len=128,
                         dtype="float32", kv_block_size=PS, prefill_chunk=16,
                         decode_steps_per_dispatch=STEPS),
        params=init(cfg, jax.random.PRNGKey(0)))
    assert eng._ride_rows == 0
    args = (eng.params, eng.kv.k_pages, eng.kv.v_pages,
            jnp.asarray(eng.last_tokens), jnp.asarray(eng.positions),
            *eng._shared_decode_args(), *eng._decode_tail_args())
    assert len(args) == 11 + int(cfg.is_recurrent)

    def _decode_impl_n(params, k_pages, v_pages, tokens, positions, tables,
                       stops, slot_keys, temp, top_k, top_p, state=None):
        (toks, pos, k_pages, v_pages, *rest), toks_seq = decode_scan(
            params, tokens, positions, k_pages, v_pages, tables, stops,
            slot_keys, temp, top_k, top_p, cfg, STEPS, attn_impl="auto",
            w4_kernel_ok=True, w8_kernel_ok=False, return_moe_stats=True,
            ssm_state=state)
        return (toks_seq, toks, pos, k_pages, v_pages, *rest)

    donate = (1, 2, 11) if cfg.is_recurrent else (1, 2)
    parents = jax.jit(_decode_impl_n, donate_argnums=donate).lower(*args)
    assert eng._decode_jit.lower(*args).as_text() == parents.as_text()


def _pools(cfg, pages, fill=None):
    """(k_pages, v_pages) of ``pages`` pages, zeros or ``fill``ed; a latent
    model's ONE pool of padded rows and None."""
    shape = ((cfg.num_layers, pages, 1, PS, cfg.mla.page_width)
             if cfg.is_latent else
             (cfg.num_layers, pages, cfg.num_kv_heads, PS, cfg.head_dim))
    pool = jnp.asarray(np.zeros(shape) if fill is None else fill(size=shape),
                       jnp.float32)
    return pool, (None if cfg.is_latent else pool + 1)


@pytest.mark.parametrize("name", MODELS)
def test_a_step_without_a_piece_samples_what_the_plain_step_samples(name):
    """``decode_scan`` with all-zero pieces branches to the plain step: the
    same tokens, the same pools, and no first token."""
    cfg = get_model_config(name)
    params = init(cfg, jax.random.PRNGKey(0))
    B, pages = 3, 9
    tables = jnp.asarray([[1, 2], [3, 4], [5, 6]], jnp.int32)
    args = (jnp.asarray([5, 6, 7], jnp.int32), jnp.asarray([3, 9, 0]),
            *_pools(cfg, pages, RNG.normal), tables,
            jnp.asarray([16, 16, 0]),
            jnp.zeros((B, 2), jnp.uint32), jnp.zeros(B, jnp.float32),
            jnp.zeros(B, jnp.int32), jnp.ones(B, jnp.float32))
    plain, toks = decode_scan(params, *args, cfg, STEPS, attn_impl="gather")
    rode, (toks_r, firsts) = decode_scan(
        params, *args, cfg, STEPS, attn_impl="gather",
        ride=jnp.zeros((STEPS, PIECE_META + C), jnp.int32))
    np.testing.assert_array_equal(toks, toks_r)
    np.testing.assert_array_equal(firsts, 0)
    for a, b in zip(plain, rode):
        assert (a is None and b is None) or np.array_equal(a, b)


@pytest.mark.parametrize("name", MODELS)
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
    cfg = get_model_config(name)
    params = init(cfg, jax.random.PRNGKey(0))
    B, pages = 3, 9
    args = (params, jnp.zeros(B, jnp.int32), jnp.zeros(B, jnp.int32),
            *_pools(cfg, pages),
            jnp.zeros((B, 2), jnp.int32), jnp.ones(B, jnp.int32),
            jnp.zeros((B, 2), jnp.uint32), jnp.zeros(B, jnp.float32),
            jnp.zeros(B, jnp.int32), jnp.ones(B, jnp.float32))

    def program(*a, ride=None):
        return decode_scan(*a, cfg, STEPS, ride=ride)
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
    if cfg.is_latent:
        # a call site a layer of the table's head and ONE for the loop over
        # its periodic part (``*D`` then ``*E`` x 2: two sites, not three)
        head, unit, reps = table_period(cfg)
        assert reps == 2 and len(head) + reps * len(unit) == cfg.num_layers
        sites = sum(kind == "*" for kind, _ in head + unit)
        assert functions("_latent_windows") == [2 * sites, sites]
        # and the ONE sampler takes whole tiles of 8 rows (B + 1 = 4 here)
        assert re.search(r"func\.func private @sample_tokens\("
                         rf"%arg0: tensor<8x{cfg.vocab_size}xf32>", rides)
    else:
        assert functions("_windows") == [2]
    assert len(rides) < 2 * len(plain)


@pytest.mark.parametrize("pattern,head,unit,reps", [
    ("*D*E*E*E", "*D", "*E", 3),          # the latent cell's table, shorter
    ("*D*D*E*E", "*D*D", "*E", 2),
    ("*E*E", "", "*E", 2),
    ("*D*E", "*D*E", "", 0),              # nothing repeats
    ("MEMEM*E", "MEMEM*E", "", 0),        # the hybrid test table
    ("KDKEKE*EKEKEKE*E", "KDKEKE*EKEKEKE*E", "", 0),   # the linear test table
    ("KDKE*EKE*E", "KD", "KE*E", 2),
    ("*EEE*EEE*EEE", "", "*EEE", 3),
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
