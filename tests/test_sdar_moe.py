"""SDAR on the serve path: generation by diffusion over blocks
(``models/sdar_moe.py``; the block engine of ``serve/hybrid_engine.py``; the
count-yielding step of ``serve/loop.py``; ``BlockSchedule``; the block mask of
the flash forward) at a small size on the CPU, against the plain float32
reference and the reference generator of ``benchmark/families/sdar_moe.py``
(which import nothing of the program) and against loops written here."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.spec import SpecError, load_family
from vescale_tpu.mesh import DeviceMesh
from vescale_tpu.models import sdar_moe as sd
from vescale_tpu.ndtimeline import api as nd
from vescale_tpu.ops.flash_attention import _flash_fwd_pallas, _from3, _to3, flash_attention
from vescale_tpu.resilience import faultsim
from vescale_tpu.serve import (ContinuousBatchingScheduler, DecodeFeed, HybridServeEngine, KVCacheOutOfPages,
                               PagedKVCache, PrefixCache, Request, SlotStateUnsupported, run_serve_resilient)
from vescale_tpu.serve.engine import BlockSchedule
from vescale_tpu.serve.hybrid_engine import BLOCK_COUNTERS, RowsByDemand, hybrid_cache_config

FAMILY = load_family("sdar_moe")
# hidden 64, two layers, 4 query heads over 2 key heads of 16, 8 experts of 32 with 2 a token; blocks of 4 in 4 steps
TOY = {"model": "sdar_moe", "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 16, "hidden_act": "silu",
       "hidden_size": 64, "mlp_only_layers": [], "moe_intermediate_size": 32, "norm_topk_prob": True,
       "num_attention_heads": 4, "num_experts": 8, "num_experts_per_tok": 2, "num_hidden_layers": 2,
       "num_key_value_heads": 2, "rms_norm_eps": 1e-6, "rope_scaling": None, "rope_theta": 1000000,
       "sliding_window": None, "tie_word_embeddings": False, "use_sliding_window": False, "vocab_size": 96,
       "assumed": {"block_length": 4, "denoising_steps": 4, "remasking": "low_confidence_static", "greedy": True,
                   "mask_token_id": 90, "qk_norm": True}}
B, MASK = 4, 90
SLOTS, PAGE, PAGES = 4, 8, 8          # 64 positions a slot: rungs 8, 16, 32, 64
TIGHT = 5e-5                          # float32 program against float32 reference
BF16_AT_TOY_WIDTHS = 2e-2             # a row of 64 rounds coarser than one of 2048: the family's limits are the chip's


def toy_config(dtype=jnp.float32, **changes):
    """The program's config of TOY, computing in float32 so that it can be held tightly to the reference."""
    return dataclasses.replace(FAMILY.program_config(TOY, prefill_chunk=8), dtype=dtype, **changes)


@pytest.fixture(scope="module", params=["xla_legs", "kernels_interpreted_experts_sorted", "bfloat16", "experts_padded"])
def system(request):
    """The toy engine, four times: as a CPU builds it in float32 (the XLA
    decode leg, the dense block-masked attention, all experts on all tokens);
    with the Pallas kernels a TPU compiles run through the interpreter
    (``paged_decode`` over a block's grouped query rows, the flash forward under
    the block mask) and both of the expert layer's limits turned to 0 while the
    programs are traced, so that a pass takes the sorted form that a long
    prefill takes at the real size, on the leg a TPU takes: the grouped SwiGLU
    kernel, interpreted; in bfloat16, as it is served; and with
    the first limit alone turned to 0, so that every program is a candidate for
    the padded batched product, which a pass of 512 positions takes at the real
    size (few rows an expert: the choice on the device takes it every call)."""
    from vescale_tpu.moe import dropless

    cfg = toy_config(jnp.bfloat16 if request.param == "bfloat16" else jnp.float32)
    mesh = DeviceMesh(("tp",), (1,), devices=jax.devices()[:1])
    params = jax.jit(lambda k: sd.init_params(cfg, k))(jax.random.key(7))
    cache = PagedKVCache(hybrid_cache_config(cfg, num_slots=SLOTS, page_size=PAGE, pages_per_slot=PAGES), mesh)
    with pytest.MonkeyPatch.context() as patch:
        if request.param.startswith("kernels_interpreted"):
            patch.setattr(dropless, "DENSE_MAX_TOKENS", 0)
            patch.setattr(dropless, "PADDED_MAX_MEAN_ROWS", 0)
            patch.setenv("VESCALE_KERNELS", "interpret")
        if request.param == "experts_padded":
            patch.setattr(dropless, "DENSE_MAX_TOKENS", 0)
            patch.setattr(dropless, "PADDED_MIN_MEAN_ROWS", 0)          # (a toy pass is 4 rows an expert)
        engine = HybridServeEngine(cfg, mesh, params, cache).warm()     # every program is traced here
    assert engine.kernel_decode == request.param.startswith("kernels_interpreted")
    assert engine._decode_padded_candidate == (request.param == "experts_padded")
    limit = BF16_AT_TOY_WIDTHS if request.param == "bfloat16" else TIGHT
    return cfg, params, cache, engine, limit


def tokens(seed, n):
    return [int(t) for t in np.random.default_rng(seed).integers(1, MASK - 1, n)]


def rel(got, want):
    return float(np.max(np.abs(np.asarray(got, np.float64) - np.asarray(want, np.float64))) / np.max(np.abs(np.asarray(want))))


# ------------------------------------------------- program against reference
@pytest.mark.parametrize("n", [8, 9, 10, 11, 13], ids=lambda n: f"prompt_{n}_residue_{n % 4}")
def test_prefill_then_teacher_forced_passes_are_the_references_rows_under_the_block_mask(system, n):
    """The runner's check at a prompt of every residue mod 4 (the pad rule and
    the open block a prefill leaves are under it): row r is position r's logits
    with positions <= r holding their tokens and the rest of r's block masked.
    Six forced tokens cross a block's end, so a commit pass and a block read
    back from the cache are among them."""
    _cfg, params, cache, engine, limit = system
    prompt, forced = tokens(n, n), tokens(100 + n, 6)
    cache.reset()
    slot = cache.alloc(n, 8)
    rows = [engine.prefill(prompt, slot)]
    cache.commit_prefill(slot, n)
    for tok in forced:
        toks = np.zeros((cache.num_slots,), np.int32)
        toks[slot] = tok
        step = engine.decode(toks)
        assert step.shape == (SLOTS, B, TOY["vocab_size"]) and step.tokens.shape == (SLOTS, B)
        rows.append(step[slot])
        cache.advance(slot)
    want = FAMILY.logits(params, TOY, prompt + forced, range(n - 1, n + 6))
    assert rel(np.stack(rows), want) < limit
    cache.reset()


PROMPTS = {0: tokens(1, 9), 2: tokens(2, 16), 3: tokens(3, 6)}      # slots at different passes of their blocks


def test_every_pass_of_generated_blocks_is_the_references_and_so_is_every_selection_it_can_vouch_for(system):
    """Three slots, three blocks each, one program call a pass: every pass's B
    rows against a full forward of the reference over the same ids (the later
    blocks against committed GENERATED blocks read back from the cache), every
    selection against the reference's where its margins exceed the tolerance."""
    _cfg, _params, cache, engine, limit = system
    cache.reset()
    out = FAMILY.check_blocks(engine, TOY, PROMPTS, 3)
    assert out["logits_max_abs_diff_over_max"] < limit and out["disagreed"] == []
    assert out["passes"] == 3 + 5 + 5 + 5 + 5 + 5 + 4 + 5 + 5 and out["selections_held"] > 10


@pytest.mark.parametrize("fault", ["causal_mask", "fp8_weights", "no_qk_norm", "skip_commit"])
def test_a_lower_precision_a_causal_mask_and_a_skipped_commit_pass_each_fail_the_check(system, fault):
    """The limits that decide ``correct`` on the chip, at the toy's size: the
    reference computed one precision lower (weights in fp8), under a causal mask
    in place of the block mask, or a program that settles a block without its
    commit pass (its K and V are then those of a pass in which its last position
    was still the mask), reads several times the tolerance (fp8 at the toy's
    two layers: twice it)."""
    _cfg, _params, cache, engine, _limit = system
    cache.reset()
    kw = {"skip_commit_of": 2} if fault == "skip_commit" else {"wrong": fault}
    out = FAMILY.check_blocks(engine, TOY, PROMPTS, 3, **kw)
    assert out["logits_max_abs_diff_over_max"] > 5 * FAMILY.PASS_LOGITS_TOLERANCE, out


def test_one_kept_expert_fewer_moves_the_logits_by_about_the_tolerance_and_no_more():
    """What the check cannot tell from rounding (PERF.md, section 7): one kept
    expert fewer.  By construction the routed part is a few per cent of the
    residual stream (``ROUTED_DOWN_GAIN``), so even the toy's one of TWO kept
    experts moves a row by about the limit, where a wrong mask moves it by its
    own size; at the published widths one of eight reads 1.2e-3 on the chip."""
    params = jax.jit(lambda k: sd.init_params(toy_config(), k))(jax.random.key(7))
    ids = tokens(5, 16)
    right = FAMILY.sequence_logits(params, TOY, ids, range(16))
    moved = rel(FAMILY.sequence_logits(params, TOY, ids, range(16), "top7"), right)
    assert 0 < moved < 2 * FAMILY.SERVE_LOGITS_TOLERANCE < rel(FAMILY.sequence_logits(params, TOY, ids, range(16), "causal_mask"), right) / 10


# ----------------------------------------------------------- the serve loop
def _run(system, arrivals, **kw):
    _cfg, _params, cache, engine, _limit = system
    cache.reset()
    sched = ContinuousBatchingScheduler(cache, max_queue=32)
    res = run_serve_resilient(engine=engine, scheduler=sched, arrivals=arrivals, install_signal_handlers=False,
                              coordinate=False, **kw)
    sched.ledger_check()
    assert cache.free_slot_count() == SLOTS and cache.free_page_count() == cache.num_pages - 1
    cache.reset()
    return res, sched


def _golden(system, req):
    _cfg, _params, cache, engine, _limit = system
    cache.reset()
    return engine.replay_greedy(req.prompt, req.max_new_tokens, eos_id=req.eos_id)


def _vouched(params, req, margin):
    """The reference generator's tokens, or None where one of its own decisions
    had a margin that the program's rounding (``margin``) could close."""
    toks, passes = FAMILY.generate(params, TOY, req.prompt, req.max_new_tokens)
    T = TOY["assumed"]["denoising_steps"]
    k_of = {}
    for start, ids, lg, commit in passes:
        if commit:
            continue
        masked = np.asarray(ids) == MASK
        k = k_of.get(start, 0)
        k_of[start] = k + 1
        _b, _c, _chosen, pm, tm = FAMILY.decide(lg, masked, min(FAMILY.transfers(B, T, k), int(masked.sum())))
        if min(pm, tm) <= margin:
            return None
    return toks


# prompts and budgets of every residue mod 4, a budget of 1, more requests than slots, arrivals between passes
ARRIVALS = [(0, 8, 8), (0, 9, 7), (1, 10, 6), (2, 11, 5), (3, 5, 1), (3, 6, 2), (7, 7, 3), (8, 12, 12), (13, 4, 9), (13, 13, 4)]


def _requests():
    return [(at, Request(rid=rid, prompt=tuple(tokens(200 + rid, n)), max_new_tokens=m)) for rid, (at, n, m) in enumerate(ARRIVALS)]


def test_every_stream_is_replay_greedys_and_the_reference_generators_and_a_request_gets_what_it_asked_for(system):
    """Ten requests over four slots, admitted at different passes of the other
    slots' blocks; tokens are recorded when their block commits, in order, and a
    request gets exactly ``max_new_tokens`` whatever its residue mod 4."""
    _cfg, params, _cache, _engine, limit = system
    reqs = _requests()
    want = {req.rid: _golden(system, req) for _, req in reqs}
    res, sched = _run(system, reqs)
    assert res.status == "completed" and set(res.outcomes) == set(want)
    for _, req in reqs:
        out = res.outcomes[req.rid]
        assert out["status"] == "completed" and len(out["tokens"]) == req.max_new_tokens
        assert out["tokens"] == want[req.rid], req.rid
    assert sched.goodput_tokens == sched.raw_tokens == sum(m for _, _, m in ARRIVALS)
    if limit == TIGHT:      # float32: the program's margins are the reference's
        vouched = {req.rid: _vouched(params, req, 20 * TIGHT) for _, req in reqs}
        assert sum(v is not None for v in vouched.values()) >= 5, "choose prompts whose margins the reference can vouch for"
        assert all(v is None or v == want[rid] for rid, v in vouched.items())


def test_the_pipelined_loop_and_a_loop_that_reads_every_step_at_once_give_the_same_tokens(system, tmp_path):
    """... and the pipelined one launches its passes ahead: under the static
    schedule the host knows what the pass in flight will yield."""
    _cfg, _params, _cache, engine, _limit = system
    reqs = _requests()
    counters = {}
    for name in ("pipelined", "settled"):
        decode = engine.decode
        if name == "settled":
            def read_at_once(tokens, decode=decode):
                step = decode(tokens)
                step.tokens
                return step
            engine.decode = read_at_once
        nd.start_trace_session(str(tmp_path / name), profiler=False)
        try:
            res, _ = _run(system, reqs)
        finally:
            counters[name] = nd.stop_trace_session().counters
            if name == "settled":
                del engine.decode
        counters[name]["tokens"] = {rid: out["tokens"] for rid, out in res.outcomes.items()}
    assert counters["pipelined"]["tokens"] == counters["settled"]["tokens"]
    a, b = counters["pipelined"], counters["settled"]
    assert a["decode_steps"] == b["decode_steps"] and b["decode_steps_ahead"] == 0
    assert a["decode_steps_ahead"] >= 0.9 * a["decode_steps"] and a["backend_compiles"] == 0
    assert all(a[name] == b[name] for name in BLOCK_COUNTERS)
    assert a["block_tokens_emitted"] == sum(m for _, _, m in ARRIVALS) and a["logits_bytes_to_host"] == 0


@pytest.mark.parametrize("fault, step", [("oom", 3), ("oom", 7), ("request_timeout", 4), ("request_timeout", 8),
                                         ("request_timeout", 9)])
def test_an_eviction_and_a_cancel_mid_block_end_all_terminal_and_replay_to_the_same_tokens(system, fault, step):
    """Three requests on free slots from step 0; the boundary that evicts (the
    newest) or cancels (the oldest) first reads the pass in flight, mid-block.
    The evicted request is prefilled again, which opens its block anew; the
    cancelled one keeps the whole blocks it was given."""
    reqs = [(0, Request(rid=rid, prompt=tuple(tokens(40 + rid, 8 + rid)), max_new_tokens=10)) for rid in range(3)]
    want = {req.rid: _golden(system, req) for _, req in reqs}
    faultsim.arm(faultsim.parse_schedule(f"{fault}:step={step}"))
    try:
        res, sched = _run(system, reqs)
    finally:
        faultsim.disarm()
    assert res.status == "completed" and sched.all_terminal()
    if fault == "oom":
        assert res.counts["evicted"] == res.counts["requeued"] == 1
        assert sorted(o["replays"] for o in res.outcomes.values()) == [0, 0, 1]
        assert all(o["status"] == "completed" and o["tokens"] == want[rid] for rid, o in res.outcomes.items())
    else:
        assert res.outcomes[0]["status"] == "timed_out" and "request_timeout" in res.outcomes[0]["reason"]
        # rid 0 (prompt of 8: whole blocks) had ``step`` calls read before the boundary: its first block's 4 tokens
        # with the fifth (4 denoising passes, then the call that commits it and opens the next), 4 more every 4 calls
        assert res.outcomes[0]["tokens"] == want[0][: 4 * ((step - 1) // 4)]
        assert all(res.outcomes[rid]["tokens"] == want[rid] for rid in (1, 2))


def test_an_eos_inside_a_block_ends_the_request_there(system):
    req = Request(rid=0, prompt=tuple(tokens(77, 8)), max_new_tokens=12)
    stream = _golden(system, req)
    position = next((i for i in range(1, 12) if i % 4 != 3 and stream[i] not in stream[:i]), None)
    if position is None:
        pytest.skip("the toy's stream repeats itself: no EOS case can be built from this prompt")
    with_eos = Request(rid=0, prompt=req.prompt, max_new_tokens=12, eos_id=stream[position])
    assert _golden(system, with_eos) == stream[: position + 1]
    res, _ = _run(system, [(0, with_eos)])
    assert res.outcomes[0]["tokens"] == stream[: position + 1]


def test_the_counters_of_one_request_are_the_schedules_arithmetic(system, tmp_path):
    """A prompt of 10 (2 revealed in the first block) and a budget of 7: blocks
    of 2, 4 and 1 of 4 tokens: 3 + 5 + 5 units of 4 rows, 3 of them commits;
    masked query rows 2 + 1 and twice 4 + 3 + 2 + 1.  Two of the commits rode
    with the first pass of the block after them, so the calls are 2 + 4 + 4 + 1."""
    cfg, _params, _cache, _engine, _limit = system
    nd.start_trace_session(str(tmp_path / "one"), profiler=False)
    try:
        res, _ = _run(system, [(0, Request(rid=0, prompt=tuple(tokens(9, 10)), max_new_tokens=7))])
    finally:
        c = nd.stop_trace_session().counters
    assert len(res.outcomes[0]["tokens"]) == 7
    assert (c["block_passes"], c["block_commit_passes"], c["block_tokens_emitted"], c["block_positions_masked"]) == (13, 3, 7, 23)
    assert (c["block_commits_fused"], c["block_commits_deferred"]) == (2, 0)
    assert c["decode_steps"] == 11 and c["moe_layer_steps"] == 11 * cfg.num_hidden_layers
    # every expert layer of a candidate call fit the pad (4 rows a pass against 128 places): the padded form, each time
    assert c["moe_padded_layer_steps"] == (c["moe_layer_steps"] if _engine._decode_padded_candidate else 0)
    assert c["moe_assignments"] == c["moe_assignments_held"] == 13 * B * cfg.num_experts_per_tok * cfg.num_hidden_layers
    assert c["prefill_attn_flops"] == sd.prefill_counters(cfg, 16)["prefill_attn_flops"] \
        == cfg.num_hidden_layers * FAMILY.block_prefill_attention_flops(TOY, 16)
    if _engine.kernel_decode:
        # a page of 8: the slot's rows read 2 pages up to position 16 and 3 past it, a commit place the block's own
        ends = [12] * 2 + [16, 12] + [16] * 3 + [20, 16] + [20] * 4
        assert c["decode_pages_read"] == sum(-(-end // PAGE) for end in ends) + 11 * (SLOTS - 1)


# ------------------------------------------------- a pass keeps no logits
OPEN = {1: tokens(31, 9), 3: tokens(32, 14)}        # two slots, 1 and 2 positions into their open blocks


def _one_pass_unread(system):
    """Both slots prefilled and one pass launched over them: the step, unread,
    and the reference's ``B`` rows of logits for each slot's block as the pass
    found it."""
    _cfg, params, cache, engine, _limit = system
    cache.reset()
    want, plans = {}, {}
    for slot, prompt in OPEN.items():
        assert cache.alloc(len(prompt), 2 * B, slot=slot) == slot
        engine.prefill(prompt, slot)
        cache.commit_prefill(slot, len(prompt))
        plans[slot] = engine.block.plan(engine.block.open(len(prompt)), B)
        before = np.asarray(cache.state["block_ids"][0, slot])
        want[slot] = np.asarray(FAMILY.block_logits(params, TOY, prompt[: len(prompt) // B * B], before))
    return engine.decode(DecodeFeed(None, slots={slot: plan[1] for slot, plan in plans.items()})), want


READS = {     # what a caller reads -> (the rows it gets, the reference's, how many rows were made for it)
    "a_slot": lambda step, want: (step[1], want[1][1], 1),                      # the row of the slot's length: position 9 of 8..11
    "slots": lambda step, want: (step[[3, 1]], np.stack([want[3][2], want[1][1]]), 2),
    "slots_as_an_array": lambda step, want: (step[np.asarray([1, 3])], np.stack([want[1][1], want[3][2]]), 2),
    "a_block": lambda step, want: (step.block(3), want[3], B),
    "every_row": lambda step, want: (np.asarray(step)[[1, 3]], np.stack([want[1], want[3]]), SLOTS * B),
}


@pytest.mark.parametrize("read", ["nothing", *READS])
def test_a_steps_logits_rows_are_made_when_a_caller_reads_them_and_are_the_references(system, read, monkeypatch):
    """The pass's program returns the open rows' hidden state and no logits:
    ``shape`` / ``dtype`` / ``len`` are the configuration's and wait for nothing;
    ``step[slot]``, ``step[[a, b]]``, ``step.block(slot)`` and ``np.asarray(step)``
    run the head over the rows asked for, which are the reference's to the
    fixture's limit, and count their bytes and their rows."""
    _cfg, _params, cache, engine, limit = system
    step, want = _one_pass_unread(system)
    was = engine.trace_counters()
    assert isinstance(step._logits, RowsByDemand) and not step.read
    if read == "nothing":
        monkeypatch.setattr(engine, "_head_fn", None)                           # (a call of it would raise)
        monkeypatch.setattr(step._logits, "_hidden", None)                      # ... and so would a look at the device's rows
        assert step.shape == (SLOTS, B, TOY["vocab_size"]) and step.dtype == np.float32 and len(step) == SLOTS
        assert not step.read and engine.trace_counters() == was
    else:
        got, ref, rows = READS[read](step, want)
        assert isinstance(got, np.ndarray) and got.dtype == np.float32 and got.shape == ref.shape
        assert rel(got, ref) < limit and step.read
        now = engine.trace_counters()
        assert now["logits_rows_made"] - was["logits_rows_made"] == rows
        assert now["logits_bytes_to_host"] - was["logits_bytes_to_host"] == rows * TOY["vocab_size"] * 4
    cache.reset()


def test_a_serve_loop_makes_no_logits_row_and_a_sampling_caller_makes_the_rows_it_reads(system):
    """Nobody reads logits in a greedy loop; ``replay_greedy``'s canary hook
    (``step.block(slot)[j]`` when its fault fires) is a caller of the rows by demand."""
    _cfg, _params, cache, engine, _limit = system
    was = engine.trace_counters()["logits_rows_made"]
    res, _ = _run(system, _requests()[:4])
    assert res.status == "completed" and engine.trace_counters()["logits_rows_made"] == was
    prompt = tokens(41, 9)
    plain = engine.replay_greedy(prompt, 6)
    faultsim.arm(faultsim.parse_schedule("canary_diverge:call=2,count=1"))
    try:
        flipped = engine.replay_greedy(prompt, 6, canary=True)
    finally:
        faultsim.disarm()
    # the one token whose top logit the fault flipped (the block on the device is what it was: the rest agree)
    assert sum(a != b for a, b in zip(flipped, plain)) == 1 and engine.trace_counters()["logits_rows_made"] == was + B
    cache.reset()


def test_the_toy_engine_with_the_head_kernel_interpreted_yields_the_xla_legs_tokens():
    """Two engines over the same weights, one with ``head_select`` interpreted
    in its pass (every other kernel on its XLA leg in both): the same streams,
    token for token, and the same rows by demand."""
    from vescale_tpu import kernels

    cfg = toy_config()
    mesh = DeviceMesh(("tp",), (1,), devices=jax.devices()[:1])
    params = jax.jit(lambda k: sd.init_params(cfg, k))(jax.random.key(7))
    engines = {}
    for leg in ("xla", "kernel"):
        cache = PagedKVCache(hybrid_cache_config(cfg, num_slots=SLOTS, page_size=PAGE, pages_per_slot=PAGES), mesh)
        with pytest.MonkeyPatch.context() as patch:
            if leg == "kernel":
                patch.setattr(kernels, "resolve", lambda name, **kw: True if name == "head_select" else None)
            engines[leg] = HybridServeEngine(cfg, mesh, params, cache).warm()
        assert engines[leg].kernel_head_select == (leg == "kernel") and not engines[leg].kernel_decode
    for seed, n, budget in ((51, 9, 11), (52, 8, 8), (53, 14, 6), (54, 5, 13)):
        streams = [engine.replay_greedy(tokens(seed, n), budget) for engine in engines.values()]
        assert streams[0] == streams[1] and len(streams[0]) == budget


# ------------------------------------------- a commit in the call that opens the next block
@pytest.mark.parametrize("n, budget", [(8, 12), (9, 7), (10, 9), (11, 14), (12, 5), (7, 4), (6, 3)],
                         ids=lambda v: str(v))
def test_a_request_of_n_blocks_takes_n_t_plus_one_calls_and_its_tokens_are_the_reference_generators(system, n, budget):
    """Prompts of every residue mod 4 and budgets that end mid-block, one
    request at a time through the serve loop: every block but the last commits
    in the call that runs the first pass of the block after it, so the calls are
    the published loop's passes less one a block but the last (``n T + 1`` for
    ``n`` whole blocks); the tokens are the unfused replay's, and the reference
    generator's wherever its own margins vouch for them."""
    _cfg, params, _cache, engine, limit = system
    req = Request(rid=0, prompt=tuple(tokens(300 + n, n)), max_new_tokens=budget)
    want = _golden(system, req)
    before = engine.trace_counters()
    res, _ = _run(system, [(0, req)])
    c = {name: value - before[name] for name, value in engine.trace_counters().items()}
    assert res.outcomes[0]["tokens"] == want and len(want) == budget
    reference, passes = FAMILY.generate(params, TOY, req.prompt, budget)
    blocks = sum(commit for *_rest, commit in passes)
    assert blocks == -(-(n % B + budget) // B)
    assert c["decode_steps"] == len(passes) - (blocks - 1) and c["block_passes"] == len(passes)
    assert (c["block_commit_passes"], c["block_commits_fused"], c["block_commits_deferred"]) == (blocks, blocks - 1, 0)
    if n % B == 0 and budget % B == 0:
        assert c["decode_steps"] == blocks * TOY["assumed"]["denoising_steps"] + 1
    if limit == TIGHT and _vouched(params, req, 20 * TIGHT) is not None:
        assert reference == want


def _pool_rows(cache, slot, upto):
    """K and V of ``slot``'s positions ``0 .. upto`` as the pool holds them, (2, layers, upto, KV, hd)."""
    arrays, at = cache.arrays(), np.arange(upto)
    pages = cache.page_table[slot][at // PAGE]
    return np.stack([np.asarray(arrays[name], np.float32)[:, pages, at % PAGE] for name in ("k", "v")])


@pytest.mark.parametrize("n", [8, 10], ids=lambda n: f"prompt_{n}")
def test_a_block_committed_in_a_fused_call_leaves_the_teacher_forced_k_and_v_and_the_next_block_reads_them(system, n):
    """Four blocks generated by hand as the serve loop does it, three of them
    committed in the call that opens the next: every call's open rows are the
    reference's rows over the same ids (``check_blocks``' comparison; the first
    pass of a block reads the block before it as the SAME call wrote it), and at
    the end the pool holds, position for position, what the host-token form
    leaves when it is fed the same tokens one at a time (its commits are calls
    of their own: its commit rows are dead)."""
    _cfg, params, cache, engine, limit = system
    schedule, prompt, budget = engine.block, tokens(400 + n, n), 4 * B - n % B
    cache.reset()
    slot = cache.alloc(n, budget, slot=1)
    engine.prefill(prompt, slot)
    cache.commit_prefill(slot, n)
    state, settled, out, fused, worst = schedule.open(n), list(prompt), [], 0, 0.0
    while len(out) < budget:
        going_in = np.asarray(cache.state["block_ids"][0, slot])
        fuse = schedule.fuses(state, budget - len(out))
        skip, count, positions = schedule.plan(state, budget - len(out), fuse)
        step = engine.decode(DecodeFeed(None, slots={slot: count}, fused=[slot] * fuse))
        cache.advance(slot, positions)
        first = len(settled) // B * B
        if positions:
            assert step.tokens[slot].tolist() == going_in.tolist(), "a commit returns the block it committed"
            settled = settled[:first] + going_in.tolist()
            out += going_in.tolist()[skip: skip + count]
            first, going_in, fused = first + B, np.full((B,), MASK), fused + fuse
        if not positions or fuse:       # the call's open rows: a denoising pass (of the block after the committed one)
            worst = max(worst, rel(step.block(slot), FAMILY.block_logits(params, TOY, settled[:first], going_in)))
    assert fused == 3 and worst < limit
    fused_pool = _pool_rows(cache, slot, len(settled))
    cache.reset()
    slot = cache.alloc(n, budget, slot=1)
    engine.prefill(prompt, slot)
    cache.commit_prefill(slot, n)
    for tok in out:
        toks = np.zeros((cache.num_slots,), np.int32)
        toks[slot] = tok
        engine.decode(toks).tokens
        cache.advance(slot)
    forced_pool = _pool_rows(cache, slot, len(settled))
    assert rel(fused_pool, forced_pool) < (1e-6 if limit == TIGHT else 2.0 ** -7)
    cache.reset()


def test_more_slots_ready_to_commit_than_places_hold_the_extras_one_call_and_give_the_same_tokens(system):
    """Four requests prefilled in one iteration are at the same pass of their
    blocks; the toy's four slots have two places for commit rows, so at the call
    that would fuse all four two are held, and from then on they are a call behind."""
    _cfg, _params, cache, engine, _limit = system
    assert engine.block.commit_places(SLOTS) == 2
    reqs = [(0, Request(rid=rid, prompt=tuple(tokens(500 + rid, 8)), max_new_tokens=12)) for rid in range(4)]
    want = {req.rid: _golden(system, req) for _, req in reqs}
    before = engine.trace_counters()
    res, _ = _run(system, reqs)
    c = {name: value - before[name] for name, value in engine.trace_counters().items()}
    assert {rid: out["tokens"] for rid, out in res.outcomes.items()} == want
    # two slots held at the fifth call; after it the four are two and two, and nobody waits again
    assert (c["block_commits_deferred"], c["block_commits_fused"], c["block_commit_passes"]) == (2, 8, 12)
    assert c["block_passes"] == 4 * 15 and c["decode_steps"] == 3 * 4 + 1 + 1


def test_what_a_block_engine_refuses_it_refuses_by_name(system):
    _cfg, _params, cache, engine, _limit = system
    cache.reset()
    with pytest.raises(NotImplementedError, match="decode_multi.*generates by blocks"):
        engine.decode_multi(np.zeros((SLOTS, 2), np.int32))
    with pytest.raises(NotImplementedError, match="prefill_suffix.*generates by blocks"):
        engine.prefill_suffix([1, 2, 3], 0, 0)
    with pytest.raises(SlotStateUnsupported, match="open block"):
        PrefixCache(cache)
    sched = ContinuousBatchingScheduler(cache, max_queue=4)
    with pytest.raises(NotImplementedError, match="speculative= and a prefix cache.*blocks of 4"):
        run_serve_resilient(engine=engine, scheduler=sched, arrivals=[], install_signal_handlers=False, coordinate=False,
                            speculative=object())
    with pytest.raises(ValueError, match="must divide the page"):
        small = PagedKVCache(hybrid_cache_config(toy_config(), num_slots=2, page_size=2, pages_per_slot=8), engine.mesh)
        HybridServeEngine(toy_config(), engine.mesh, engine.params, small)
    with pytest.raises(SpecError, match="low-confidence"):
        FAMILY.program_config(dict(TOY, assumed=dict(TOY["assumed"], remasking="sequential")))


# ------------------------------------------------------------- the schedule
@pytest.mark.parametrize("block, steps", [(4, 4), (4, 2), (8, 4), (8, 3), (4, 1)])
@pytest.mark.parametrize("prompt_len, budget", [(8, 8), (9, 7), (10, 1), (11, 13), (5, 4)])
def test_the_hosts_mirror_yields_the_budget_in_the_reference_generators_passes(block, steps, prompt_len, budget):
    """``BlockSchedule.plan`` against a loop written as the published one: as
    many passes, the commit passes where it has them, the budget to the token."""
    schedule = BlockSchedule(block, steps)
    state, length, got, passes, commits = schedule.open(prompt_len), prompt_len, 0, 0, []
    while got < budget:
        skip, count, positions = schedule.plan(state, budget - got)
        passes += 1
        got += count
        if positions:
            assert (length + positions) % block == 0 and skip == length % block and 0 < count <= block - skip
            commits.append(passes)
        else:
            assert count == 0
        length += positions
    # the published loop: blocks from the prompt's last partial one, T + 1 passes at most each
    want_passes, want_commits, n = 0, [], prompt_len
    total = -(-(prompt_len + budget) // block) * block
    for start in range(prompt_len // block * block, total, block):
        masked = block - max(0, n - start)
        for k in range(steps + 1):
            want_passes += 1
            if not masked:
                want_commits.append(want_passes)
                break
            masked -= min(block // steps + (k < block % steps), masked)
    assert got == budget and (passes, commits) == (want_passes, want_commits) and length == total


@pytest.mark.parametrize("block, steps", [(4, 4), (4, 2), (8, 4), (8, 3), (4, 1)])
@pytest.mark.parametrize("prompt_len, budget", [(8, 8), (9, 7), (10, 1), (11, 13), (5, 4), (12, 23)])
def test_the_hosts_mirror_of_fused_calls_yields_the_budget_in_a_call_fewer_a_block_but_the_last(block, steps, prompt_len, budget):
    """``plan`` with ``fuse``: the yields sum to the budget, a unit (a pass or a
    commit) is ``block`` live rows and there are as many as the published loop
    has passes, every commit but the request's last rides with the next block's
    first pass, and the cache's length moves a block at a commit."""
    schedule = BlockSchedule(block, steps)
    state, length, got, calls, units, commits, rode = schedule.open(prompt_len), prompt_len, 0, 0, 0, 0, 0
    while got < budget:
        fuse = schedule.fuses(state, budget - got)
        masked_before = state[0]
        skip, count, positions = schedule.plan(state, budget - got, fuse)
        calls, units, got, length = calls + 1, units + 1 + fuse, got + count, length + positions
        assert (positions > 0) == (masked_before == 0) and (count > 0) == (positions > 0)
        if positions:
            commits, rode = commits + 1, rode + fuse
            assert length % block == 0 and skip == (length - positions) % block and count <= block - skip
            # a fused call leaves the block after it one pass on; a commit alone leaves it fresh
            assert state == ([block - schedule.transfers(0), 1, 0] if fuse else [block, 0, 0])
            assert fuse == (got < budget)
    total = -(-(prompt_len + budget) // block) * block
    want_units, n = 0, prompt_len
    for start in range(prompt_len // block * block, total, block):
        masked = block - max(0, n - start)
        for k in range(steps + 1):
            want_units += 1
            if not masked:
                break
            masked -= min(block // steps + (k < block % steps), masked)
    blocks = (total - prompt_len // block * block) // block
    assert got == budget and length == total and (commits, rode) == (blocks, blocks - 1)
    assert units == want_units and calls == units - rode
    assert -(-128 // steps) <= schedule.commit_places(128) <= min(128, 2 * 128 // steps)


def test_the_cache_settles_a_block_at_a_time_inside_the_reserved_pages(system):
    _cfg, _params, cache, _engine, _limit = system
    cache.reset()
    slot = cache.alloc(5, 3)            # 8 positions: one page; the last block ends where the page does
    cache.commit_prefill(slot, 5)
    cache.advance(slot, 3)
    assert int(cache.lengths[slot]) == 8 and cache.fingerprint()[3] == 8
    with pytest.raises(KVCacheOutOfPages):
        cache.advance(slot, 4)
    cache.free(slot)
    assert cache.fingerprint()[3] == 0
    cache.reset()


# (L, KV, hd, page, T, reserved): SDAR's toy rungs over its pages of 8, Falcon-H1's over its pages of 4 (both two layers,
# two key heads of 16), and the cells' own row, 4 key heads of 128 on pages of 16, in bfloat16 as they are served
PAGE_WRITER_CASES = [(2, 2, 16, 8, 8, 1), (2, 2, 16, 8, 16, 2), (2, 2, 16, 8, 32, 3), (2, 2, 16, 8, 64, 5),
                     (2, 2, 16, 4, 8, 2), (2, 2, 16, 4, 16, 3), (2, 2, 16, 4, 32, 5), (6, 4, 128, 16, 128, 3)]


@pytest.mark.parametrize("L, KV, hd, page, T, reserved", PAGE_WRITER_CASES)
def test_the_page_writer_leaves_what_the_scatter_leaves_on_every_page_but_the_null_page(L, KV, hd, page, T, reserved):
    """``serve.kv_cache.write_pages`` (the prefill programs of SDAR and
    Falcon-H1) against ``pool.at[:, page_row].set(...)``: a rung of ``T``
    positions of which the slot has reserved ``reserved`` pages, so the tail of
    ``page_row`` repeats page 0.  The loop writes the null page's slabs one
    after another where the scatter writes them in no order; nobody reads that
    page, and every other page, named or not, is the same."""
    from vescale_tpu.serve.kv_cache import write_pages

    dtype = jnp.bfloat16 if hd == 128 else jnp.float32
    rng = np.random.default_rng(T + page)
    n_pages = 2 * (T // page) + 3
    pool = jnp.asarray(rng.standard_normal((L, n_pages, page, KV, hd)), dtype)
    rows = jnp.asarray(rng.standard_normal((L, T, KV, hd)), jnp.float32)       # cast to the pool's type on the way in
    named = rng.permutation(np.arange(1, n_pages))[:min(reserved, T // page)]
    page_row = jnp.asarray(np.concatenate([named, np.zeros(T // page - len(named), np.int64)]), jnp.int32)
    got = jax.jit(lambda pool, rows, page_row: write_pages(pool, rows, page_row, page), donate_argnums=0)(
        jnp.copy(pool), rows, page_row)
    want = pool.at[:, page_row].set(rows.reshape(L, T // page, page, KV, hd).astype(dtype))
    assert got.shape == pool.shape and got.dtype == pool.dtype
    np.testing.assert_array_equal(np.asarray(got[:, 1:], np.float32), np.asarray(want[:, 1:], np.float32))
    untouched = np.setdiff1d(np.arange(1, n_pages), named)
    np.testing.assert_array_equal(np.asarray(got[:, untouched], np.float32), np.asarray(pool[:, untouched], np.float32))


# --------------------------------------------------- the flash forward's mask
@pytest.mark.parametrize("streaming", [False, True], ids=["resident", "streaming"])
def test_the_flash_forward_under_the_block_mask_is_the_dense_softmax_under_it(streaming):
    """The GQA kernel interpreted, tiles of 16 over 32 positions (so the mask
    crosses tiles on the diagonal only), against the mask as a dense comparison."""
    T, H, KV, hd = 32, 4, 2, 16
    q, k, v = (jax.random.normal(key, (1, T, heads, hd), jnp.float32)
               for key, heads in zip(jax.random.split(jax.random.key(3), 3), (H, KV, KV)))
    of = np.arange(T) // B
    s = jnp.einsum("bqgrd,bkgd->bgrqk", q.reshape(1, T, KV, H // KV, hd), k) * hd ** -0.5
    want = jnp.einsum("bgrqk,bkgd->bqgrd", jax.nn.softmax(jnp.where(of[None, :] <= of[:, None], s, -jnp.inf), -1), v)
    o3, _ = _flash_fwd_pallas(_to3(q), _to3(k), _to3(v), hd ** -0.5, True, 16, 16, True, H, KV, streaming=streaming,
                              mask_block=B)
    assert rel(_from3(o3, 1, H), want.reshape(1, T, H, hd)) < 1e-5
    assert rel(flash_attention(q, k, v, block_q=16, block_k=16, interpret=True, mask_block=B), want.reshape(1, T, H, hd)) < 1e-5
    assert rel(flash_attention(q, k, v, mask_block=B), want.reshape(1, T, H, hd)) < 1e-5       # the dense leg, off the chip
    with pytest.raises(ValueError, match="divide the tiles"):
        _flash_fwd_pallas(_to3(q), _to3(k), _to3(v), 1.0, True, 16, 16, True, H, KV, mask_block=3)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, causal=False, mask_block=B)
