"""What was there before a step could yield a count (PR 36) is what it was:
the ``DecodeStep`` of both engines, the serve loop's one-token path, the stub
engines the tests drive the loop with, and the flash forward, whose new
``mask_block`` parameter at its default must compile and compute the causal
kernel of before, bit for bit.  And since a block engine's step makes its
logits rows by demand (PR 47): every other engine's step still holds the array
its decode program wrote, and a block engine's answers as that array would."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_decode_ahead import LLAMA, PAGE, PAGES, SLOTS, _prompt, _RecordingEngine
from test_deepseek_v2 import toy_config as deepseek_toy
from test_granite_hybrid import toy_config as granite_toy
from test_sdar_moe import toy_config as sdar_toy
from vescale_tpu.mesh import DeviceMesh
from vescale_tpu.models import deepseek_v2 as ds
from vescale_tpu.models import granite_hybrid as gh
from vescale_tpu.models import sdar_moe as sd
from vescale_tpu.models.llama import Llama
from vescale_tpu.ops.flash_attention import _flash_fwd_pallas, _to3, flash_attention
from vescale_tpu.serve import (ContinuousBatchingScheduler, DecodeFeed, DecodeStep, HybridServeEngine, KVCacheConfig,
                               PagedKVCache, Request, ServeEngine, run_serve_resilient)
from vescale_tpu.serve.hybrid_engine import BLOCK_COUNTERS, RowsByDemand, hybrid_cache_config


def _engine(model):
    mesh = DeviceMesh(("tp",), (1,), devices=jax.devices()[:1])
    if model == "llama":
        params = Llama(LLAMA).init(jax.random.key(0), jnp.ones((1, 8), jnp.int32))["params"]
        cache = PagedKVCache(KVCacheConfig(layers=2, kv_heads=2, head_dim=LLAMA.head_dim, num_slots=SLOTS,
                                           page_size=PAGE, pages_per_slot=PAGES), mesh)
        return ServeEngine(LLAMA, mesh, params, cache).warm(), cache
    cfg, module = {"granite": (granite_toy(), gh), "deepseek_v2": (deepseek_toy(), ds), "sdar": (sdar_toy(), sd)}[model]
    params = jax.jit(lambda k: module.init_params(cfg, k))(jax.random.key(7))
    cache = PagedKVCache(hybrid_cache_config(cfg, num_slots=SLOTS, page_size=PAGE, pages_per_slot=PAGES), mesh)
    return HybridServeEngine(cfg, mesh, params, cache).warm(), cache


def _an_engine_of_one_token_a_step(model):
    """No schedule, ids a slot, one logits row a slot; a loop written by hand
    (prefill, then ``decode`` and ``advance`` one position at a time), the
    pipelined serve loop and ``replay_greedy`` give one stream; the cache moves
    a position a step; a hybrid engine keeps no block counters."""
    engine, cache = _engine(model)
    assert engine.block is None and not any(name in engine.trace_counters() for name in BLOCK_COUNTERS)
    prompt, budget = _prompt(11, 6), 7
    slot = cache.alloc(len(prompt), budget)
    tok = engine.greedy(engine.prefill(prompt, slot))
    cache.commit_prefill(slot, len(prompt))
    by_hand = [tok]
    for k in range(budget - 1):
        toks = np.zeros((SLOTS,), np.int32)
        toks[slot] = tok
        step = engine.decode(toks)
        cache.advance(slot)
        assert isinstance(step, DecodeStep) and step._rows is None and step.shape == (SLOTS, LLAMA.vocab_size)
        assert isinstance(step._logits, jax.Array)            # what the decode program wrote: nothing is made by demand
        assert step.tokens.shape == (SLOTS,) and step.tokens.dtype == np.int32
        assert int(np.argmax(step[slot])) == int(step.tokens[slot]) and step[[slot]].shape == (1, LLAMA.vocab_size)
        with pytest.raises(TypeError, match="one position a slot"):
            step.block(slot)
        assert int(cache.lengths[slot]) == len(prompt) + k + 1
        by_hand.append(tok := int(step.tokens[slot]))
    cache.reset()
    assert engine.replay_greedy(prompt, budget) == by_hand
    sched = ContinuousBatchingScheduler(cache, max_queue=8)
    res = run_serve_resilient(engine=engine, scheduler=sched, arrivals=[(0, Request(rid=0, prompt=prompt, max_new_tokens=budget))],
                              install_signal_handlers=False, coordinate=False)
    sched.ledger_check()
    assert res.outcomes[0]["tokens"] == by_hand
    assert engine.trace_counters().get("logits_rows_made", 0) == 0 and engine.trace_counters()["logits_bytes_to_host"] > 0
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    held = (cache.k.data, cache.v.data) if isinstance(engine, ServeEngine) else tuple(cache.arrays().values())
    ids = engine._decode_fn.lower(engine.params, *held, i32(SLOTS, PAGES), i32(SLOTS), i32(SLOTS)).out_info[1]
    assert (ids.shape, ids.dtype) == ((SLOTS,), jnp.int32)
    cache.reset()


def _a_block_engines_step_answers_as_the_array_it_no_longer_holds():
    """A toy block engine in the host-token form (the reference check's): the
    step's logits are ``RowsByDemand``; ``step[slot]`` is one row, ``step[[a,
    b]]`` two, ``step.block(slot)`` the block's and ``np.asarray(step)`` all of
    them, each the same numbers whichever way they are asked for, each counted
    by its bytes and its rows; shape, dtype and length wait for nothing."""
    engine, cache = _engine("sdar")
    B, vocab = engine.block.B, engine.config.vocab_size
    prompt = _prompt(12, 6)
    slot = cache.alloc(len(prompt), 4)
    engine.prefill(prompt, slot)
    cache.commit_prefill(slot, len(prompt))
    toks = np.zeros((SLOTS,), np.int32)
    toks[slot] = 5
    step = engine.decode(toks)
    assert isinstance(step._logits, RowsByDemand) and list(step._rows) == list(cache.lengths_array() % B)
    assert step.shape == (SLOTS, B, vocab) and step.dtype == np.float32 and len(step) == SLOTS and not step.read
    assert engine.trace_counters()["logits_rows_made"] == 0 == engine.trace_counters()["logits_bytes_to_host"]
    whole = np.asarray(step)
    assert whole.shape == (SLOTS, B, vocab) and whole.dtype == np.float32 and step.read
    row, rows, block = step[slot], step[[slot, 0]], step.block(slot)
    assert row.shape == (vocab,) and rows.shape == (2, vocab) and block.shape == (B, vocab)
    at = int(step._rows[slot])
    assert np.array_equal(row, whole[slot, at]) and np.array_equal(rows[0], row) and np.array_equal(rows[1], whole[0, step._rows[0]])
    assert np.array_equal(block, whole[slot]) and int(np.argmax(row)) >= 0
    made = SLOTS * B + 1 + 2 + B
    assert engine.trace_counters()["logits_rows_made"] == made and engine.trace_counters()["logits_bytes_to_host"] == made * vocab * 4
    cache.reset()


def _a_stub_engine_without_a_schedule():
    """A stand-in that has no ``block`` attribute at all (the tests' own, and a
    ``DecodeStep`` made from host ids) is stepped a token a slot as before: the
    first step from the host's tokens, every later one through a ``DecodeFeed``
    whose ``slots`` nobody set."""
    mesh = DeviceMesh(("tp",), (1,), devices=jax.devices()[:1])
    cache = PagedKVCache(KVCacheConfig(layers=1, kv_heads=1, head_dim=1, num_slots=2, page_size=8, pages_per_slot=4), mesh)
    engine = _RecordingEngine(2)
    sched = ContinuousBatchingScheduler(cache, max_queue=8)
    res = run_serve_resilient(engine=engine, scheduler=sched, install_signal_handlers=False, coordinate=False,
                              arrivals=[(0, Request(rid=0, prompt=(1, 2, 3), max_new_tokens=5))])
    assert res.outcomes[0]["tokens"] == [50, 0, 1, 2, 3] and int(cache.lengths.sum()) == 0
    assert isinstance(engine.fed[0], np.ndarray) and all(isinstance(x, DecodeFeed) and x.slots is None for x in engine.fed[1:])
    read = DecodeStep(np.arange(3, dtype=np.int32), np.zeros((3, 5), np.float32))
    assert read.read and list(read.tokens) == [0, 1, 2] and read[1].shape == (5,) and np.asarray(read).shape == (3, 5)


def _the_flash_forward_at_the_mask_parameters_default():
    """``mask_block=1`` is the causal mask: the same jaxpr through the public
    op, and the kernel, interpreted, gives the bits it gave without the
    parameter (resident and streaming); under ``grad`` too, where a block mask
    has no path."""
    T, H, KV, hd = 32, 4, 2, 16
    q, k, v = (jax.random.normal(key, (1, T, heads, hd), jnp.float32)
               for key, heads in zip(jax.random.split(jax.random.key(5), 3), (H, KV, KV)))
    was = lambda q, k, v: flash_attention(q, k, v, causal=True, block_q=16, block_k=16, interpret=True)
    now = lambda q, k, v: flash_attention(q, k, v, causal=True, block_q=16, block_k=16, interpret=True, mask_block=1)
    assert str(jax.make_jaxpr(was)(q, k, v)) == str(jax.make_jaxpr(now)(q, k, v))
    assert np.array_equal(np.asarray(was(q, k, v)), np.asarray(now(q, k, v)))
    grads = [jax.grad(lambda *a: fn(*a).sum(), argnums=(0, 1, 2))(q, k, v) for fn in (was, now)]
    assert all(np.array_equal(np.asarray(a), np.asarray(b)) for a, b in zip(*grads))
    for streaming in (False, True):
        a = _flash_fwd_pallas(_to3(q), _to3(k), _to3(v), hd ** -0.5, True, 16, 16, True, H, KV, streaming=streaming)
        b = _flash_fwd_pallas(_to3(q), _to3(k), _to3(v), hd ** -0.5, True, 16, 16, True, H, KV, streaming=streaming, mask_block=1)
        assert all(np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(a, b))
    # off the chip the default takes the dense reference it took
    assert np.array_equal(np.asarray(flash_attention(q, k, v)), np.asarray(flash_attention(q, k, v, mask_block=1)))


CASES = {"llama": lambda: _an_engine_of_one_token_a_step("llama"),
         "granite": lambda: _an_engine_of_one_token_a_step("granite"),
         "deepseek_v2": lambda: _an_engine_of_one_token_a_step("deepseek_v2"),
         "block_engine_rows_by_demand": _a_block_engines_step_answers_as_the_array_it_no_longer_holds,
         "stub_engine": _a_stub_engine_without_a_schedule,
         "flash_mask_default": _the_flash_forward_at_the_mask_parameters_default}


@pytest.mark.parametrize("what", list(CASES))
def test_what_yielded_one_token_a_step_and_the_causal_flash_forward_are_what_they_were(what):
    CASES[what]()
