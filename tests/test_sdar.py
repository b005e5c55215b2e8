"""SDAR-shaped models through the program, against the plain reference
(``benchmark/reference/diffusion_decoder.py``): generation by diffusion over
blocks (rows of a block see each other; a denoise step fixes rows by
confidence; a finished block's K/V are stored by the next block's first
denoise forward, the first half of ONE window of two blocks), renormalised
top-k experts, per-head q/k norms. Float32 on the CPU at a tiny size, seeded
random weights: logits through the pages, then ``engine.generate`` token for
token and step for step, the wrong variants asserted to FAIL.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import diffusion_decoder
from distributed_llm_training_and_inference_system_tpu.config import get_model_config
from distributed_llm_training_and_inference_system_tpu.config.schema import (
    ConfigError,
    DiffusionConfig,
    ModelConfig,
    RunConfig,
    ServeConfig,
)
from distributed_llm_training_and_inference_system_tpu.models import gpt
from distributed_llm_training_and_inference_system_tpu.ops.paged_attention import (
    paged_attention_multi,
    write_window_to_pages,
)
from distributed_llm_training_and_inference_system_tpu.serve import (
    InferenceEngine,
    SamplingParams,
    engine as engine_mod,
)
from distributed_llm_training_and_inference_system_tpu.serve.decode import (
    can_carry,
    extend_step_forward,
)
from distributed_llm_training_and_inference_system_tpu.serve.sampling import (
    transfer_rows,
)

# Both sides compute in float32 and differ only in the order of their sums
# (the program adds a token's k expert outputs in one reduction, the
# reference all E one after another; the page kernels tile differently):
# measured 2e-7 to 8e-7 on logits of size ~1. 1e-4 is far above that and
# far below what a wrong mask moves them by (the causal mask: 2e-2 and more,
# asserted below).
TOL = 1e-4
MASK = 255
PS = 16     # page size of the paged tests: a whole number of blocks

PUBLISHED = {   # the keys of a published sdar_moe config.json, tiny values
    "name": "sdar-test", "model_type": "sdar_moe", "num_hidden_layers": 2,
    "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 256, "max_position_embeddings": 256, "num_experts": 8,
    "num_experts_per_tok": 2, "norm_topk_prob": True, "rms_norm_eps": 1e-6,
    "rope_theta": 1000000, "hidden_act": "silu", "tie_word_embeddings": False,
    "decoder_sparse_step": 1, "mlp_only_layers": [], "attention_bias": False,
    "qk_norm": "head", "block_length": 4, "denoising_steps": 4,
    "mask_token_id": MASK, "remasking_strategy": "low_confidence_dynamic",
    "confidence_threshold": 0.9, "dtype": "float32"}


def published(**changed) -> dict:
    return dict(PUBLISHED, **changed)


def model(config: dict) -> ModelConfig:
    return ModelConfig.from_published(config)


def seeded_params(cfg: ModelConfig, head_scale: float = 1.0):
    """Seeded weights with NON-trivial norm scales (init leaves them 0: the
    per-head q/k norm would be a plain one) and a router sharp enough that
    top-2 of 8 is not a coin toss. ``head_scale`` sharpens the output head:
    at 1 no confidence comes near the threshold, at 400 most pass it."""
    p = gpt.init(cfg, jax.random.PRNGKey(0))
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 8))
    for name in ("attn_norm", "mlp_norm", "q_norm", "k_norm"):
        s = p["blocks"][name]["scale"]
        p["blocks"][name]["scale"] = 0.3 * jax.random.normal(
            next(keys), s.shape, s.dtype)
    p["blocks"]["moe"]["router"]["kernel"] = \
        p["blocks"]["moe"]["router"]["kernel"] * 20.0
    # init's output projections are scaled for depth, and at this width
    # the residual stream of a masked row would stay the mask token's
    # embedding (one token repeated for ever): let the layers speak
    p["blocks"]["o"]["kernel"] = p["blocks"]["o"]["kernel"] * 30.0
    p["blocks"]["moe"]["down"]["kernel"] = \
        p["blocks"]["moe"]["down"]["kernel"] * 300.0
    p["lm_head"]["kernel"] = p["lm_head"]["kernel"] * head_scale
    return p


def prompt_of(n, seed=0):
    return np.random.default_rng(seed).integers(0, MASK, n).tolist()


def engine_for(config: dict, params=None, **serve):
    cfg = model(config)
    serve = {"max_batch_size": 4, "max_seq_len": 128, "kv_block_size": PS,
             "dtype": "float32", "prefill_chunk": PS, **serve}
    return InferenceEngine(
        cfg, ServeConfig(model=cfg.name, **serve),
        params=seeded_params(cfg) if params is None else params)


# -- the configuration -------------------------------------------------------

def test_a_published_config_builds_the_diffusion_model():
    cfg = model(PUBLISHED)
    assert cfg.is_diffusion and cfg.attention_block == 4
    assert cfg.diffusion == DiffusionConfig(4, 4, MASK,
                                            "low_confidence_dynamic", 0.9)
    assert cfg.qk_norm == "head" and cfg.ffn_size == 32     # ONE expert's
    assert cfg.moe.num_experts == 8 and cfg.moe.norm_topk_prob
    assert not can_carry(cfg)
    assert get_model_config("sdar-test").is_diffusion
    # model_type alone states the mechanism: its config.json has no key
    # for the per-head norms nor for the generation loop
    bare = {k: v for k, v in PUBLISHED.items() if k not in (
        "qk_norm", "block_length", "denoising_steps", "mask_token_id",
        "remasking_strategy", "confidence_threshold")}
    assumed = model(dict(bare, vocab_size=151936))
    assert assumed.qk_norm == "head"
    assert assumed.diffusion == DiffusionConfig(
        4, 4, 151669, "low_confidence_dynamic", 0.9)
    # the q/k norms' scales are one [head_dim] vector a layer
    p = jax.eval_shape(lambda: gpt.init(cfg, jax.random.PRNGKey(0)))
    assert p["blocks"]["q_norm"]["scale"].shape == (2, 16)
    leaves = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(p))
    assert leaves == cfg.param_count
    assert cfg.diffusion.transfer_schedule == (1, 1, 1, 1)
    assert DiffusionConfig(8, 3, 0).transfer_schedule == (3, 3, 2)


@pytest.mark.parametrize("changed, words", [
    ({"decoder_sparse_step": 2}, "decoder_sparse_step"),
    ({"mlp_only_layers": [1]}, "mlp_only_layers"),
    ({"denoising_steps": 5}, "denoising_steps"),
    ({"mask_token_id": 256}, "mask_token_id"),
    ({"remasking_strategy": "random"}, "remasking_strategy"),
    ({"layer_pattern": "*E"}, "uniform layer stack"),
    ({"qk_norm": "rows"}, "none|projection|head"),
])
def test_what_the_configuration_refuses_by_name(changed, words):
    with pytest.raises(ConfigError, match=words.replace("|", r"\|")):
        model(published(**changed))


# -- logits ------------------------------------------------------------------

@pytest.mark.parametrize("Bd", [4, 8])
def test_training_side_forward_matches_the_reference(Bd):
    """``gpt.forward`` with no cache attends under the block mask."""
    config = published(block_length=Bd, denoising_steps=4)
    cfg = model(config)
    params = seeded_params(cfg)
    tokens = prompt_of(21)
    tokens[17:] = [MASK] * 4
    got = gpt.forward(params, jnp.asarray([tokens]), cfg)[0]
    want = diffusion_decoder.logits(params, tokens, config)
    assert float(jnp.max(jnp.abs(got - want))) < TOL
    causal = diffusion_decoder.logits(params, tokens, config, mask_block=1)
    assert float(jnp.max(jnp.abs(got - causal))) > 100 * TOL
    with pytest.raises(ValueError, match="no block rule"):
        gpt.forward(params, jnp.asarray([tokens]), cfg, attn_impl="flash")


@pytest.mark.parametrize("Bd", [4, 8])
def test_prefill_then_denoise_windows_through_the_pages(Bd):
    """A prompt's whole blocks go into the pages as a prefill window does
    it; then windows of one block, the rest of the prompt fixed and masks
    after it, are forwarded over them: every row's logits are the
    reference's full forward's under the dense block mask."""
    config = published(block_length=Bd, denoising_steps=4)
    cfg = model(config)
    params = seeded_params(cfg)
    n = 2 * PS + Bd + 2                     # whole blocks, then 2 tokens
    prompt, run = prompt_of(n, seed=3), n // Bd * Bd
    pages = jnp.zeros((2, 8, 2, PS, 16), jnp.float32)
    table = jnp.asarray([[3, 5, 1, 6]], jnp.int32)
    bucket = 4 * PS
    window = jnp.zeros((1, bucket), jnp.int32).at[0, :run].set(
        jnp.asarray(prompt[:run]))
    ok = (jnp.arange(bucket) < run)[None]
    lg, kp, vp, *_ = extend_step_forward(
        params, window, jnp.zeros((1,), jnp.int32), pages, pages, table,
        cfg, write_ok=ok, return_moe_stats=True)
    want = diffusion_decoder.logits(params, prompt[:run], config)
    assert float(jnp.max(jnp.abs(lg[0, :run] - want))) < TOL

    canvas = prompt + [MASK] * (Bd - 2)
    step = jnp.asarray([canvas[run:]], jnp.int32)
    for fixed in (None, 7):                 # a second forward, a row fixed
        if fixed is not None:
            canvas[-1] = fixed
            step = step.at[0, -1].set(fixed)
        lg, kp, vp, *_ = extend_step_forward(
            params, step, jnp.asarray([run], jnp.int32), kp, vp, table, cfg)
        want = diffusion_decoder.logits(params, canvas, config,
                                        positions=range(run, run + Bd))
        assert float(jnp.max(jnp.abs(lg[0] - want))) < TOL
    # the next block over the COMMITTED one: what the commit forward left
    # in the pages is the finished block's K/V
    nxt = jnp.full((1, Bd), MASK, jnp.int32)
    lg, *_ = extend_step_forward(
        params, nxt, jnp.asarray([run + Bd], jnp.int32), kp, vp, table, cfg)
    want = diffusion_decoder.logits(
        params, canvas + [MASK] * Bd, config,
        positions=range(run + Bd, run + 2 * Bd))
    assert float(jnp.max(jnp.abs(lg[0] - want))) < TOL


@pytest.mark.parametrize("T, Bd", [(4, 4), (8, 8), (32, 4), (48, 8)])
def test_block_kernel_in_interpret_mode_matches_the_gather_fallback(T, Bd):
    """The page kernel under the block rule (row j sees its whole block)
    against the gather baseline, for the decode window of one block and
    prefill windows of many, starts on a block and off a page."""
    rng = np.random.default_rng(T + Bd)
    B, Nq, Nkv, D, NP = 3, 4, 2, 128, 12
    q = jnp.asarray(rng.normal(size=(B, T, Nq, D)), jnp.float32)
    k_new, v_new = (jnp.asarray(rng.normal(size=(B, T, Nkv, D)), jnp.float32)
                    for _ in range(2))
    pool = jnp.asarray(rng.normal(size=(2, NP, Nkv, PS, D)), jnp.float32)
    tables = jnp.asarray(rng.permutation(np.arange(1, NP))[:9].reshape(3, 3)
                         .tolist() if T <= 2 * PS else
                         [[1, 2, 3, 4, 5], [6, 7, 8, 9, 10], [11, 3, 5, 7, 9]],
                         jnp.int32)
    starts = jnp.asarray([0, PS - Bd if T <= PS else PS, 2 * Bd][:B],
                         jnp.int32)
    kp = write_window_to_pages(pool, k_new, tables, starts, None, 1)
    vp = write_window_to_pages(pool, v_new, tables, starts, None, 1)
    got = paged_attention_multi(q, kp, vp, tables, starts, impl="pallas",
                                layer=1, block=Bd)
    want = paged_attention_multi(q, kp, vp, tables, starts, impl="gather",
                                 layer=1, block=Bd)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5
    causal = paged_attention_multi(q, kp, vp, tables, starts, impl="gather",
                                   layer=1)
    assert float(jnp.max(jnp.abs(got - causal))) > 1e-2


def test_the_kernel_refuses_a_block_that_straddles_pages():
    q = jnp.zeros((1, 4, 4, 128))
    pool = jnp.zeros((1, 4, 2, 6, 128))
    with pytest.raises(ValueError, match="must divide the page size"):
        paged_attention_multi(q, pool, pool, jnp.zeros((1, 2), jnp.int32),
                              jnp.zeros((1,), jnp.int32), impl="pallas",
                              layer=0, block=4)


# -- the transfer rule -------------------------------------------------------

def test_transfer_rule_by_strategy():
    prob = jnp.asarray([[0.2, 0.95, 0.5, 0.93], [0.6, 0.1, 0.6, 0.3]])
    masked = jnp.asarray([[True, True, True, True],
                          [True, False, True, True]])
    wanted = jnp.asarray([1, 2])

    def fixed(strategy, threshold=0.9):
        fix, beyond = transfer_rows(prob, masked, wanted, strategy, threshold)
        return np.asarray(fix).tolist(), np.asarray(beyond).tolist()
    assert fixed("sequential") == (
        [[True, False, False, False], [True, False, True, False]], [0, 0])
    # the most confident masked rows; a tie goes to the row further left
    assert fixed("low_confidence_static") == (
        [[False, True, False, False], [True, False, True, False]], [0, 0])
    # every row over the threshold where those are at least the schedule's
    assert fixed("low_confidence_dynamic") == (
        [[False, True, False, True], [True, False, True, False]], [1, 0])
    # never a row that is not masked, however few are left
    fix, _ = transfer_rows(prob, masked & jnp.asarray([False, True])[:, None]
                           & jnp.asarray([True, False, False, False]),
                           jnp.asarray([2, 2]), "low_confidence_static", 0.9)
    assert np.asarray(fix).tolist() == [[False] * 4, [True, False, False,
                                                      False]]
    for strategy in ("sequential", "low_confidence_static",
                     "low_confidence_dynamic"):
        for row in range(2):
            want = diffusion_decoder.transfer(
                np.asarray(prob[row]), np.asarray(masked[row]),
                int(wanted[row]), strategy, 0.9)
            assert fixed(strategy)[0][row] == want.tolist()


# -- the engine --------------------------------------------------------------

LENGTHS = (3, 9, 16, 22, 32, 41)     # under a block, off and on one, pages


@pytest.mark.parametrize("Bd, steps", [(4, 4), (8, 4), (8, 3)])
@pytest.mark.parametrize("strategy", ["low_confidence_dynamic",
                                      "low_confidence_static", "sequential"])
def test_generate_follows_the_reference_token_for_token(Bd, steps, strategy):
    config = published(block_length=Bd, denoising_steps=steps,
                       remasking_strategy=strategy)
    engine = engine_for(config)
    prompts = [prompt_of(n, seed=n) for n in LENGTHS]
    # a prompt may hold the mask token's id, also among the rows that stand
    # fixed in its first window: they are the prompt's, not rows to fix
    prompts.append(prompt_of(14, seed=1)[:-2] + [MASK, 7])
    reqs = engine.generate(prompts, SamplingParams(temperature=0.0,
                                                   max_tokens=11))
    for req in reqs:
        tokens, steps_at = diffusion_decoder.generate(
            engine.params, req.prompt_tokens, config, 11)
        assert req.generated_tokens == tokens, len(req.prompt_tokens)
        assert req.unmask_steps == steps_at, len(req.prompt_tokens)
        assert req.finish_reason == "length"
    assert len({t for r in reqs for t in r.generated_tokens}) > 8
    d = engine.stats()["diffusion"]
    assert d["threshold_fixed"] == 0
    assert d["tokens_fixed"] >= sum(len(r.generated_tokens) for r in reqs)
    # no forward runs on a window without masks: every block but a reply's
    # last is stored by the forward that starts the next one
    blocks = sum(-(-(n + 11) // Bd) - n // Bd
                 for n in map(len, prompts))
    assert d["commit_slot_forwards"] == 0
    assert d["fused_commits"] == d["blocks_committed"] == blocks - len(reqs)
    assert d["forwards"] == engine.total_decode_steps


def test_a_sharp_head_makes_the_threshold_fire():
    """With confidences over the threshold a block is done in fewer than
    ``denoising_steps`` forwards, and the engine still follows the
    reference step for step."""
    cfg = model(PUBLISHED)
    params = seeded_params(cfg, head_scale=400.0)
    engine = engine_for(PUBLISHED, params)
    reqs = engine.generate([prompt_of(n, seed=n) for n in (8, 13, 30)],
                           SamplingParams(temperature=0.0, max_tokens=16))
    for req in reqs:
        tokens, steps_at = diffusion_decoder.generate(
            params, req.prompt_tokens, PUBLISHED, 16)
        assert (req.generated_tokens, req.unmask_steps) == (tokens, steps_at)
    d = engine.stats()["diffusion"]
    assert d["threshold_fixed"] > 0
    # more than the schedule's one token a denoise forward
    assert d["tokens_fixed"] > d["slot_forwards"] - d["commit_slot_forwards"]
    static = engine_for(published(remasking_strategy="low_confidence_static"),
                        params)
    static.generate([prompt_of(8, seed=8)],
                    SamplingParams(temperature=0.0, max_tokens=16))
    assert static.stats()["diffusion"]["threshold_fixed"] == 0


def _follows(engine, config, prompts, n=12, **forward) -> list:
    reqs = engine.generate(prompts, SamplingParams(temperature=0.0,
                                                   max_tokens=n))
    return [(r.generated_tokens, r.unmask_steps) == diffusion_decoder.generate(
        engine.params, r.prompt_tokens, config, n, **forward) for r in reqs]


def never_storing(denoise_scan):
    """The WRONG server of ``experiments/diffusion_check_readings.py``
    (what the chip's check is read against too): the window's first half
    never live. (The benchmark's ``commit_skipping`` wraps the form in
    which the commit was a forward of its own: it would find no window
    without masks to skip.)"""
    import importlib.util
    from pathlib import Path
    spec = importlib.util.spec_from_file_location(
        "diffusion_check_readings", Path(__file__).parents[1]
        / "experiments" / "diffusion_check_readings.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.never_storing(denoise_scan)


def test_the_wrong_variants_fail(monkeypatch):
    """What the token-for-token comparison must catch: a reference under
    the CAUSAL mask (the mechanism itself), one without the head norms,
    and a server that never stores a finished block (the K/V of a
    half-masked window left in the pages; ``never_storing`` in place of
    the engine's ``denoise_scan`` while its program is traced)."""
    prompts = [prompt_of(n, seed=n) for n in (9, 16, 22, 41)]
    engine = engine_for(PUBLISHED)
    assert all(_follows(engine, PUBLISHED, prompts))
    assert not all(_follows(engine, PUBLISHED, prompts, mask_block=1))
    assert not all(_follows(engine, published(qk_norm="none"), prompts))
    monkeypatch.setattr(engine_mod, "denoise_scan",
                        never_storing(engine_mod.denoise_scan))
    skipping = engine_for(PUBLISHED)
    first = _follows(skipping, PUBLISHED, prompts, n=2)
    follows = _follows(skipping, PUBLISHED, prompts)
    monkeypatch.undo()
    # its first block is still right (it follows a prefill program's K/V)
    assert all(first) and not any(follows)
    d = skipping.stats()["diffusion"]
    assert d["blocks_committed"] == d["fused_commits"] == 0
    assert d["tokens_fixed"] > 0


def test_a_prefix_cache_hit_serves_the_cold_runs_tokens():
    engine = engine_for(PUBLISHED, prefix_caching=True)
    shared = prompt_of(2 * PS + 6, seed=5)
    sampling = SamplingParams(temperature=0.0, max_tokens=10)
    cold = engine.generate([shared], sampling)[0]
    assert engine.total_prefix_cached_tokens == 0
    again = engine.generate([shared, shared[:2 * PS] + prompt_of(5, seed=6)],
                            sampling)
    assert engine.total_prefix_cached_tokens == 4 * PS
    assert again[0].generated_tokens == cold.generated_tokens
    assert again[0].unmask_steps == cold.unmask_steps
    for req in again:
        assert (req.generated_tokens, req.unmask_steps) == \
            diffusion_decoder.generate(engine.params, req.prompt_tokens,
                                       PUBLISHED, 10)


def test_chunked_prefill_and_a_full_batch_serve_the_same_tokens():
    """Prompts over the chunk length go chunk by chunk under the block
    rule; more requests than slots queue and take freed slots."""
    prompts = [prompt_of(n, seed=n) for n in (70, 5, 33, 50, 18, 64, 27)]
    sampling = SamplingParams(temperature=0.0, max_tokens=9)
    cold = engine_for(PUBLISHED, max_batch_size=8)
    chunked = engine_for(PUBLISHED, chunked_prefill_tokens=2 * PS,
                         max_batch_size=2)
    a, b = cold.generate(prompts, sampling), chunked.generate(prompts,
                                                              sampling)
    assert [r.generated_tokens for r in a] == [r.generated_tokens for r in b]
    assert [r.unmask_steps for r in a] == [r.unmask_steps for r in b]
    assert chunked.stats()["compiled_programs"]["prefill_chunk_buckets"] > 0
    assert a[0].generated_tokens == diffusion_decoder.generate(
        cold.params, prompts[0], PUBLISHED, 9)[0]


def test_counters_under_the_schedule():
    """Prompts of whole blocks, no threshold: 4 tokens in 4 forwards a
    slot, none of them a commit (a block is stored by the forward that
    starts the next; a reply's last block by none), 4 + 3 + 2 + 1 of a
    block's 16 row-forwards masked, windows of 2 x 4 rows."""
    engine = engine_for(PUBLISHED, decode_steps_per_dispatch=5)
    engine.generate([prompt_of(16, seed=s) for s in range(4)],
                    SamplingParams(temperature=0.0, max_tokens=20))
    d = engine.stats()["diffusion"]
    assert d["slot_forwards"] == 4 * 5 * 4
    assert d["tokens_fixed"] / d["slot_forwards"] == pytest.approx(1.0)
    assert d["commit_slot_forwards"] == 0
    assert d["masked_rows"] / (4 * d["slot_forwards"]) == \
        pytest.approx(0.625)
    assert d["fused_commits"] == d["blocks_committed"] == 4 * (5 - 1)
    assert d["window_rows"] == d["forwards"] * 4 * 8
    assert d["refused"] == {"riding": 0}
    stats = engine.stats()
    assert stats["decode_steps"] == d["forwards"]
    # the slot-step ledger: every forward advanced a live request's block
    # (the replies end with their last block's last forward), no first
    # token, 20 tokens a reply
    assert stats["slot_steps"] == {
        "useful": d["slot_forwards"], "overrun": 0, "prompt_wait": 0,
        "empty": 0, "first_tokens": 0, "tokens_credited": 4 * 20,
        "early_handbacks": 0}
    assert d["slot_forwards"] == 4 * stats["decode_steps"]
    # nothing waits for a prefill program (there is no first token): its
    # routing counts come down with the next dispatch's tokens
    assert "llmctl.engine.prefill.wait" not in stats["phases"]
    moe = stats["moe"]
    assert moe["layer_steps"] - moe["decode_layer_steps"] == 2 * 4
    # the experts see the live rows alone: a block's rows in each of its
    # forwards and once more in the forward that stores it
    assert moe["held_choices"] == 2 * 2 * (4 * 16 + 4 * (
        d["slot_forwards"] + d["blocks_committed"]))
    assert not engine._unfetched_prefills


def test_slots_at_different_phases_share_a_dispatch():
    """Eight slots whose first blocks hold 0 to 3 rows of their prompts, so
    that in one forward some store a finished block while others are
    mid-block, and every reply crosses from a block that ends a page to
    one that starts the next."""
    lengths = (8, 9, 10, 11, 12, 13, 14, 7)
    engine = engine_for(PUBLISHED, max_batch_size=8,
                        decode_steps_per_dispatch=5)
    reqs = engine.generate([prompt_of(n, seed=n) for n in lengths],
                           SamplingParams(temperature=0.0, max_tokens=14))
    for req in reqs:
        n = len(req.prompt_tokens)
        assert n < PS < n + 14 - 4      # [12, 16) ends a page, [16, 20) next
        assert (req.generated_tokens, req.unmask_steps) == \
            diffusion_decoder.generate(engine.params, req.prompt_tokens,
                                       PUBLISHED, 14)
    d = engine.stats()["diffusion"]
    # a forward in which a slot stored a block while another did not
    assert 0 < d["blocks_committed"] < d["slot_forwards"]
    assert d["commit_slot_forwards"] == 0
    assert d["fused_commits"] == d["blocks_committed"]
    # the slot-step ledger: a reply that ends inside a dispatch leaves the
    # forwards behind its last block to nobody
    stats = engine.stats()
    ledger = stats["slot_steps"]
    assert (ledger["useful"] + ledger["overrun"] + ledger["prompt_wait"]
            + ledger["empty"]) == 8 * stats["decode_steps"]
    assert ledger["tokens_credited"] == 8 * 14 and ledger["overrun"] > 0
    assert ledger["first_tokens"] == ledger["prompt_wait"] == 0


@pytest.mark.parametrize("pipelined", [True, False])
def test_a_block_a_forward_under_ondemand_admission(pipelined):
    """A head so sharp that the threshold fixes a whole block in ONE
    forward, block after block: a slot moves on by a block a forward, 8 of
    them a dispatch, and the pages grow ahead of it by that much
    (``_decode_lookahead``, ``_group_span``: a bound of a block every two
    forwards sends rows to the scratch page, and the tokens go wrong)."""
    cfg = model(PUBLISHED)
    params = seeded_params(cfg, head_scale=4000.0)
    engine = engine_for(PUBLISHED, params, decode_steps_per_dispatch=8,
                        admission="ondemand", max_seq_len=256,
                        pipelined_decode=pipelined)
    assert engine._decode_lookahead == engine._group_span == 8 * 4
    reqs = engine.generate([prompt_of(n, seed=n) for n in (8, 13, 30)],
                           SamplingParams(temperature=0.0, max_tokens=96))
    for req in reqs:
        tokens, steps_at = diffusion_decoder.generate(
            params, req.prompt_tokens, PUBLISHED, 96)
        assert (req.generated_tokens, req.unmask_steps) == (tokens, steps_at)
        # eight blocks in a row and more, each fixed whole at step 0
        assert steps_at[:40] == [0] * 40
    d = engine.stats()["diffusion"]
    assert d["tokens_fixed"] > 3.8 * d["slot_forwards"]
    assert d["fused_commits"] == d["blocks_committed"] > 60
    assert engine.total_preemptions == 0


def test_nothing_reads_a_block_before_it_is_stored():
    """Who reads a reply's pages: nobody a finished reply's (the prefix
    cache publishes a prompt's pages alone), and a PREEMPTED request's are
    published up to the last block a forward has stored. The block
    finished last is final on the host, but its K/V are a half-masked
    window's until the slot's next forward: a page that ends with it is
    not published."""
    from distributed_llm_training_and_inference_system_tpu.serve.kv_cache import (
        prefix_page_hashes)
    from distributed_llm_training_and_inference_system_tpu.serve.scheduler import (
        Request)
    engine = engine_for(PUBLISHED, prefix_caching=True, pipelined_decode=False,
                        decode_steps_per_dispatch=4)
    sampling = SamplingParams(temperature=0.0, max_tokens=24)
    prompt = prompt_of(PS, seed=11)
    want, _ = diffusion_decoder.generate(engine.params, prompt, PUBLISHED, 24)
    req = Request(request_id="first", prompt_tokens=list(prompt),
                  sampling=sampling)
    assert engine.scheduler.add_request(req)
    # four blocks in four dispatches of four forwards: [28, 32) was fixed
    # by the last forward, which nothing has followed
    while len(req.generated_tokens) < PS:
        engine.step()
    slot = req.slot
    assert engine.positions[slot] == 2 * PS and engine._win_pending[slot]
    with engine.lock:
        engine._preempt(slot)
    hashes = prefix_page_hashes(prompt + want[:PS], PS)
    assert engine.kv.hashed_pages(hashes) == 1
    engine.run_until_idle()
    assert req.generated_tokens == want and req.preemptions == 1
    # a prompt that goes on from the preempted context: the page of
    # [16, 32) is whoever published it first's (``register_pages``)
    longer = prompt + want[:PS] + prompt_of(5, seed=12)
    other = engine.generate([longer], SamplingParams(temperature=0.0,
                                                     max_tokens=8))[0]
    assert (other.generated_tokens, other.unmask_steps) == \
        diffusion_decoder.generate(engine.params, longer, PUBLISHED, 8)
    assert other.prefix_cached_tokens == 2 * PS     # the RESTART's page
    # the finished replies' pages: the restart published the context it
    # prefilled, [0, 32), and nothing after it was ever published
    assert engine.kv.hashed_pages(
        prefix_page_hashes(prompt + want, PS)) == 2
    assert engine.kv.hashed_pages(prefix_page_hashes(
        longer + other.generated_tokens + [0] * PS, PS)) == 2


def test_sampled_replies_follow_the_seed_and_stop_tokens_end_a_reply():
    engine = engine_for(PUBLISHED)
    prompt = prompt_of(10, seed=2)

    def reply(seed, **kw):
        return engine.generate([prompt], SamplingParams(
            temperature=1.0, top_k=20, max_tokens=12, seed=seed,
            **kw))[0]
    first = reply(7)
    assert reply(7).generated_tokens == first.generated_tokens
    assert reply(8).generated_tokens != first.generated_tokens
    assert MASK not in first.generated_tokens
    stop = first.generated_tokens[5]
    cut = reply(7, stop_token_ids=(stop,))
    at = first.generated_tokens.index(stop)
    assert cut.generated_tokens == first.generated_tokens[:at + 1]
    assert cut.finish_reason == "stop"
    assert len(cut.unmask_steps) == len(cut.generated_tokens)


@pytest.mark.parametrize("serve, words", [
    ({"speculative": "ngram"}, "speculative is refused"),
    ({"preemption": "swap"}, "preemption: swap is refused"),
    ({"tensor_parallel": 2}, "tensor_parallel is refused"),
    ({"kv_block_size": 6}, "page_size % block_length"),
])
def test_what_the_engine_refuses_by_name(serve, words):
    with pytest.raises(ValueError, match=words):
        engine_for(PUBLISHED, **serve)


def test_what_else_is_refused_by_name():
    engine = engine_for(PUBLISHED)
    with pytest.raises(ValueError, match="measure_device_times is refused"):
        engine.measure_device_times()
    from distributed_llm_training_and_inference_system_tpu.runtime.engine import (
        TrainingEngine)
    run = RunConfig(model=get_model_config("sdar-test"))
    with pytest.raises(ValueError, match="llmctl train is refused"):
        TrainingEngine(run)
    # a busy batch, where another model's prompt would ride the dispatches
    engine = engine_for(PUBLISHED, decode_steps_per_dispatch=3)
    engine.generate([prompt_of(5 + 3 * s, seed=s) for s in range(7)],
                    SamplingParams(temperature=0.0, max_tokens=8))
    assert engine.stats()["diffusion"]["refused"]["riding"] > 0
    assert engine.stats()["prefill_ride_tokens"] == 0


def test_an_autoregressive_model_holds_no_block_rule():
    cfg = get_model_config("olmoe-test")
    assert not cfg.is_diffusion and cfg.attention_block == 0
    assert can_carry(dataclasses.replace(cfg, qk_norm="none"))
    with pytest.raises(ConfigError, match="uniform layer stack"):
        dataclasses.replace(
            get_model_config("nemotron-h-test"),
            diffusion=DiffusionConfig(4, 4, 1)).validate()


def test_the_server_returns_unmask_steps_when_asked():
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from distributed_llm_training_and_inference_system_tpu.serve.server import (
        InferenceServer)
    cfg = model(PUBLISHED)
    serve_cfg = ServeConfig(model=cfg.name, max_batch_size=2, max_seq_len=64,
                            kv_block_size=PS, dtype="float32",
                            prefill_chunk=PS)
    server = InferenceServer(cfg, serve_cfg, params=seeded_params(cfg))
    other = InferenceServer(get_model_config("gpt-test"), ServeConfig(
        model="gpt-test", max_batch_size=2, max_seq_len=64))
    prompt = prompt_of(9, seed=1)

    async def main():
        server.start_engine()
        async with TestClient(TestServer(server.app)) as client:
            body = {"prompt": prompt, "temperature": 0.0, "max_tokens": 7}
            plain = await (await client.post("/v1/completions",
                                             json=body)).json()
            asked = await (await client.post(
                "/v1/completions",
                json=dict(body, return_unmask_steps=True))).json()
            bad = await client.post(
                "/v1/completions", json=dict(body, return_unmask_steps=1))
        async with TestClient(TestServer(other.app)) as client:
            refused = await client.post("/v1/completions", json={
                "prompt": [1, 2, 3], "return_unmask_steps": True})
        return plain, asked, bad.status, refused.status
    try:
        plain, asked, bad, refused = asyncio.run(main())
    finally:
        server.stop_engine()
    assert "unmask_steps" not in plain["choices"][0]
    choice = asked["choices"][0]
    assert choice["token_ids"] == plain["choices"][0]["token_ids"]
    assert (choice["token_ids"], choice["unmask_steps"]) == \
        diffusion_decoder.generate(server.engine.params, prompt, PUBLISHED, 7)
    assert bad == 400 and refused == 400
