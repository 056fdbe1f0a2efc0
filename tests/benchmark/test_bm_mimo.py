"""The ``mimo_v2`` family (keys of 192 beside values of 128, a sink in the window
layers' softmax, folded pages beside folded rings, a share of 256 sigmoid-routed
experts under a selection bias) in the benchmark: a toy configuration and cell
added to a temporary root by files and entries alone, run through ``serve_cell``
to ``correct``, and to not correct with the sink left out of the reference; the
real configuration file against the catalog's row and the issue's bytes, and
against what the program allocates; the traffic file's grid; the table of shapes
over the decode program traced on the CPU at the cell's shapes; the reader's
arithmetic on a made-up session.

As ``test_bm_laguna.py`` did for its entries, this file tells the tests that
were here before of the new cell AT IMPORT: ``test_bm_session.TINY_OF`` gets the
cell's toy stand-in, and ``test_bm_laguna``'s last test (and through its view
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
import test_bm_laguna
import test_bm_session
from bm_fixtures import REPO, make_tiny_root
from test_bm_programs import _trace

from benchmark import serve_cell, trafficgen
from benchmark.harness import discover, result_object
from benchmark.spec import load_benchmark, load_cell, load_family

CELL = "mimov25_serve_reasoning"
CONFIG = "mimo-v2.5.serve-L7-ep16"
REDUCED = ["num_hidden_layers", "hybrid_layer_pattern", "moe_layer_freq", "n_routed_experts", "vocab_size"]
NEW_METRICS = ["qk192_pages_gb_per_step.batch", "qk192_ring_gb_per_step.batch", "qk192_attn_device_share.batch",
               "qk192_paged_decode_roofline.batch", "sink_ring_decode_roofline.batch", "sink_window_flash_roofline.batch",
               "qk192_full_flash_roofline.batch", "experts16of256_device_share.batch", "experts16of256_nowhere_share.batch"]
CLOSED_LOOP = test_bm_laguna.CLOSED_LOOP + (test_bm_laguna.CELL,)

test_bm_session.TINY_OF.setdefault(CELL, "tiny_batch")


def _before_this_pr(root):
    """``BENCHMARK.json`` without what PR 50 appended (its configuration, its cell, its metrics, its list members)."""
    bench = load_benchmark(root)
    bench["configs"] = [c for c in bench["configs"] if c["name"] != CONFIG]
    bench["workloads"] = [w for w in bench["workloads"] if w["name"] != CELL]
    for group in ("end_to_end", "per_layer"):
        bench[group] = [dict(m, workloads=[w for w in m["workloads"] if w != CELL]) if "workloads" in m else m
                        for m in bench[group] if m["name"] not in NEW_METRICS]
    return bench


test_bm_laguna.load_benchmark = _before_this_pr      # the newest link of the chain: each reads through the next

# hidden 64, 8 query heads with keys of 24 (8 rotated) and values of 16 on 2 key heads (full) and 4 (window), window 8,
# 4 held of 16 experts top-4, the dense layer and a period; a pool of 20 pages where the four slots' whole allotment
# would be 32
TOY = {"source": "tests only", "model": "mimo_v2", "model_type": "mimo_v2", "vocab_size": 96, "hidden_size": 64,
       "num_hidden_layers": 7, "num_attention_heads": 8, "swa_num_attention_heads": 8, "num_key_value_heads": 2,
       "swa_num_key_value_heads": 4, "head_dim": 24, "swa_head_dim": 24, "v_head_dim": 16, "swa_v_head_dim": 16,
       "intermediate_size": 96, "moe_intermediate_size": 32, "n_routed_experts": 4, "num_experts_per_tok": 4,
       "sliding_window": 8, "sliding_window_size": 8, "partial_rotary_factor": 0.334, "rope_theta": 10000000,
       "swa_rope_theta": 10000, "attention_value_scale": 0.707, "layernorm_epsilon": 1e-5,
       "hybrid_layer_pattern": [0, 1, 1, 1, 1, 0, 1], "moe_layer_freq": [0, 1, 1, 1, 1, 1, 1], "attention_bias": False,
       "tie_word_embeddings": False, "hidden_act": "silu", "scoring_func": "sigmoid", "topk_method": "noaux_tc", "n_group": 1,
       "topk_group": 1, "norm_topk_prob": True, "n_shared_experts": None, "routed_scaling_factor": None,
       "add_swa_attention_sink_bias": True, "add_full_attention_sink_bias": False,
       "rope_scaling": {"rope_type": "default", "type": "default"},
       "reduced": ["n_routed_experts"], "published": {"n_routed_experts": 16},
       "assumed": {"rotated_entries": "first_half_split", "score_scale": "head_dim**-0.5", "qk_norm": False,
                   "output_gate": False, "attention_chunk_size": "no_term", "routed_scaling_factor_null": 1.0},
       "deployment": "none: a toy", "kind": "serve",
       "serve": {"weight_dtype": "bfloat16", "slots": 4, "positions_per_slot": 64, "page_size": 8, "pool_pages": 21,
                 "prefill_chunk": 8}}
WRAPPER = '''"""The mimo_v2 family with the sink left out of its reference (tests only)."""
import functools

from benchmark import reference
from benchmark.families import mimo_v2 as real

SERVE_LOGITS_TOLERANCE = real.SERVE_LOGITS_TOLERANCE
program_config, build_serve, rehearse_serve = real.program_config, real.build_serve, real.rehearse_serve


def logits(params, config, tokens, rows):
    return real.logits(params, config, tokens, rows, wrong="no_sink")


loss_and_logits = functools.partial(reference.loss_and_logits, logits)
'''


def _run(root, cell, traced=0, seed=77):
    spec = load_cell(cell, root)
    rec, correct, attempted, failed, notes = serve_cell.run_cell(spec, jax.devices()[:1], seed, 1.0, traced,
                                                                 time.perf_counter())
    return spec, rec, correct, attempted, failed, notes


def test_the_family_runs_a_toy_cell_to_correct_by_files_and_entries_alone(tmp_path):
    """The runner as it is: folded rings and pages through the normal path, a
    pool smaller than the slots' whole allotment, and the check's prompt (59 of
    64 positions: seven windows of 8, on the 64 rung) against the reference."""
    root = make_tiny_root(str(tmp_path / "root"))
    cell = test_bm_hybrid._add_cell(root, "toymimo", TOY)
    spec, rec, correct, attempted, failed, notes = _run(root, cell, traced=2)
    family = load_family("mimo_v2", root)
    assert attempted > 0 and failed == 0 and notes["ledger"]["problems"] == [], notes
    assert notes["compiles_in_window"] == 0, "every rung and the decode step were compiled by warm()"
    # (at a hidden size of 64 bfloat16 rounds coarser than at 4,096: the toy's check may read past the limit set on the chip)
    assert 0 < notes["reference"]["logits_max_abs_diff_over_max"] < 3 * family.SERVE_LOGITS_TOLERANCE
    assert correct == (notes["reference"]["logits_max_abs_diff_over_max"] <= family.SERVE_LOGITS_TOLERANCE)
    assert notes["reference"]["tolerance"] == family.SERVE_LOGITS_TOLERANCE and notes["reference"]["prompt_tokens"] == 59
    counters = notes["session_counters"]           # the trace session read the engine's counters
    assert counters["decode_steps"] > 0 and counters["moe_assignments"] > 0 and counters["moe_layer_steps"] == 6 * counters["decode_steps"]
    assert counters["page_positions_read"] > 0 and counters["page_bytes_read"] == counters["page_positions_read"] * 2 * 40 * 2
    assert 0 < counters["ring_positions_read"] and counters["ring_bytes_rw"] > counters["ring_positions_read"] * 4 * 40 * 2
    assert counters["prefill_window_attn_flops"] > 0 and counters["prefill_full_attn_flops"] > 0
    assert 0 < counters["rows_routed_nowhere"] < counters["moe_assignments"] // 4
    line = result_object(spec, rec, jax.devices()[:1], correct=correct, attempted=attempted, failed=failed, traced=2)
    assert {"serve_tokens_per_s", "setup_s", "decode_step_ms_p50.batch"} <= set(line["metrics"])
    assert line["metrics"]["serve_tokens_per_s"]["value"] > 0


def test_the_sink_left_out_of_the_reference_reads_not_correct(tmp_path):
    root = make_tiny_root(str(tmp_path / "root"))
    cell = test_bm_hybrid._add_cell(root, "toymimo_no_sink", dict(TOY, model="mimo_v2_no_sink"), WRAPPER)
    _spec, _rec, correct, _attempted, _failed, notes = _run(root, cell)
    assert not correct
    assert notes["ledger"]["problems"] == [] and notes["compiles_in_window"] == 0
    assert notes["reference"]["logits_max_abs_diff_over_max"] > 5 * notes["reference"]["tolerance"]


# ------------------------------------------------- the real configuration file
def test_the_real_configuration_is_the_catalogs_row_cut_as_the_issue_says():
    spec = load_cell(CELL, REPO)
    c, family = spec.config, spec.family()
    assert spec.chips == 1 and spec.traffic_name == "reasoning_closed320" and spec.traffic["kind"] == "closed_loop"
    assert {m["name"] for m in spec.end_to_end} == {"serve_tokens_per_s", "setup_s"}
    # every key of the catalog's config under its name, but for the depth and the two per-layer lists cut with it,
    # the experts held and the vocabulary's slice
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(path):
        with open(path) as f:
            catalog = next(r for r in map(json.loads, f) if r["name"] == "MiMo-V2.5")
        assert c["source"] == catalog["source_url"]
        differs = {k: v for k, v in catalog["config"].items() if c[k] != v}
        assert sorted(differs) == sorted(REDUCED) and differs == c["published"]
        assert all(c[k] == catalog["config"][k][:7] for k in REDUCED[1:3])
    assert c["reduced"] == REDUCED and c["num_hidden_layers"] == 7 and c["published"]["num_hidden_layers"] == 48
    assert c["hybrid_layer_pattern"] == [0, 1, 1, 1, 1, 0, 1] and c["moe_layer_freq"] == [0, 1, 1, 1, 1, 1, 1]
    assert c["published"]["n_routed_experts"] == 256 and c["n_routed_experts"] == 16 and c["published"]["vocab_size"] == 152576
    assert c["share"] == {"chips": 16, "of": ["n_routed_experts", "vocab_size"]}
    widths = {"hidden_size": 4096, "num_attention_heads": 64, "swa_num_attention_heads": 64, "head_dim": 192, "swa_head_dim": 192,
              "v_head_dim": 128, "swa_v_head_dim": 128, "num_key_value_heads": 4, "swa_num_key_value_heads": 8,
              "sliding_window": 128, "partial_rotary_factor": 0.334, "rope_theta": 10000000, "swa_rope_theta": 10000,
              "attention_value_scale": 0.707, "intermediate_size": 16384, "moe_intermediate_size": 2048,
              "num_experts_per_tok": 8, "vocab_size": 19072}
    assert {k: c[k] for k in widths} == widths
    # the floors of the model-configs guide: the leading dense layer, a whole period of six after it, 8 or more experts
    assert c["moe_layer_freq"][0] == 0 and c["hybrid_layer_pattern"][1:].count(0) == 1 and c["hybrid_layer_pattern"][1:].count(1) == 5
    assert c["n_routed_experts"] >= 8 and c["vocab_size"] * 8 == c["published"]["vocab_size"]
    assert all(key in c["assumed"] for key in ("rotated_entries", "score_scale", "qk_norm", "output_gate", "attention_chunk_size",
                                               "routed_scaling_factor_null", "init", "page_size", "ring", "folded_rows", "slots",
                                               "pool_pages", "left_out"))
    assert "seven pipeline stages of seven layers" in c["deployment"] and "16 v5e chips" in c["deployment"]
    cfg = family.program_config(c)
    assert cfg.layers_of(0) == (0, 5) and cfg.layers_of(1) == (1, 2, 3, 4, 6) and cfg.rotated == 64
    assert (cfg.num_experts, cfg.experts_held, cfg.first_expert_held, cfg.vocab_size) == (256, 16, 0, 19072)
    # ISSUE 50's arithmetic, in millions of parameters and in GB
    M = 1e6
    assert round(family.attention_params(c, 0) / M, 2) == 89.13 and round(family.attention_params(c, 1) / M, 2) == 94.37
    assert round(family.dense_params(c) / M, 2) == 201.33 and round(16 * family.expert_params(c) / M, 2) == 402.65
    assert round(family.router_params(c) / M, 2) == 1.05
    assert round(family.layer_params(c, 0) / M, 2) == 290.46 and round(family.layer_params(c, 1) / M, 2) == 498.07
    assert round(family.layer_params(c, 5) / M, 2) == 492.83 and round(2 * 19072 * 4096 / M, 2) == 156.24
    assert round(family.param_count(c) / M) == 3430 and round(family.weight_bytes(c) / 1e9, 2) == 6.87
    serve = c["serve"]
    assert (serve["slots"], serve["positions_per_slot"], serve["page_size"], serve["pool_pages"]) == (256, 8192, 32, 23552)
    assert family.position_bytes(c, family.FULL) == 2560 and family.position_bytes(c, family.SWA) == 5120
    assert family.page_bytes_per_position(c) == 5120 and round(23552 * 32 * 5120 / 1e9, 2) == 3.86
    assert family.ring_bytes_per_slot(c) == 5 * 128 * 8 * 320 * 2 == 3276800 and round(256 * family.ring_bytes_per_slot(c) / 1e9, 2) == 0.84
    assert round(family.cache_bytes(c, serve) / 1e9, 2) == 4.70
    assert round((family.weight_bytes(c) + family.cache_bytes(c, serve)) / 1e9, 1) == 11.6
    # a decode step's bytes at the traffic's mean live length: the weights, 2.8 GB of pages, 0.84 GB of rings
    moved = family.decode_step_bytes(c, serve, page_positions_read=2 * 256 * 2170, ring_positions_read=5 * 256 * 128)
    assert 10.2e9 < moved < 10.6e9
    assert family.kept_pairs(4096, 128) / family.kept_pairs(4096) < 0.07
    assert family.prefill_rungs(serve) == [128, 256, 512, 1024, 1536, 2048, 3072, 4096, 5120, 6144, 7168, 8192]
    assert family.decode_kernel_of(c, family.FULL) == "paged_decode_kv4" and family.decode_kernel_of(c, family.SWA) == "paged_decode_kv8"


def test_what_the_program_allocates_is_what_the_family_counts():
    spec = load_cell(CELL, REPO)
    c, family = spec.config, spec.family()
    from vescale_tpu.models.mimo_v2 import init_params, prefill_counters
    from vescale_tpu.serve.hybrid_engine import hybrid_cache_config, prefill_buckets

    cfg = family.program_config(c)
    tree = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.key(0))
    assert sum(int(a.size) * a.dtype.itemsize for a in jax.tree_util.tree_leaves(tree)) == family.weight_bytes(c)
    assert sum(int(a.size) for a in jax.tree_util.tree_leaves(tree)) == family.param_count(c)
    assert tree["lm_head"]["kernel"].shape == (4096, 19072) and tree["embed_tokens"]["embedding"].shape == (19072, 4096)
    full, swa = tree["layers_0"]["self_attn"], tree["layers_1"]["self_attn"]
    assert full["q_proj"].shape == swa["q_proj"].shape == (4096, 64 * 192) and full["o_proj"].shape == (64 * 128, 4096)
    assert full["k_proj"].shape == (4096, 4 * 192) and full["v_proj"].shape == (4096, 4 * 128) and "sink" not in full
    assert swa["k_proj"].shape == (4096, 8 * 192) and swa["v_proj"].shape == (4096, 8 * 128) and swa["sink"].shape == (64,)
    assert tree["layers_0"]["mlp"]["gate"].shape == (4096, 16384) and tree["layers_5"]["mlp"]["w_gate"].shape == (16, 4096, 2048)
    assert tree["layers_5"]["mlp"]["router"].shape == (4096, 256) and tree["layers_5"]["mlp"]["router_bias"].shape == (256,)
    kc = hybrid_cache_config(cfg, num_slots=256, page_size=32, pages_per_slot=256, num_pages=23552)
    assert kc == family._cache_config(cfg, c["serve"])
    assert (kc.layers, kc.kv_heads, kc.head_dim, kc.v_head_dim, kc.folded, kc.max_seq_len, kc.pool_pages) == (2, 4, 192, 128, True, 8192, 23552)
    assert kc.pool_row() == (1, 768) and kc.pool_row(values=True) == (1, 512)
    assert [(name, layers, tuple(shape)) for name, layers, shape, _dt in kc.slot_state] == \
        [("ring_k", 5, (128, 1, 1536)), ("ring_v", 5, (128, 1, 1024))], "a folded ring a slot for every window layer"
    state = sum(layers * int(np.prod(shape)) * np.dtype(dt).itemsize for _n, layers, shape, dt in kc.slot_state)
    assert state == family.ring_bytes_per_slot(c)
    pool = kc.layers * kc.pool_pages * kc.page_size * (768 + 512) * 2
    assert pool == 23552 * 32 * 5120 and pool + 256 * state == family.cache_bytes(c, c["serve"])
    assert prefill_buckets(cfg.prefill_chunk, kc.max_seq_len) == family.prefill_rungs(c["serve"])
    for rung in (128, 512, 8192):
        assert prefill_counters(cfg, rung) == {
            "prefill_window_attn_flops": family.prefill_attention_flops(c, rung, family.SWA),
            "prefill_full_attn_flops": family.prefill_attention_flops(c, rung, family.FULL)}
    assert family.prefill_attention_flops(c, 512, family.FULL) == 2 * 320 * 64 * 2 * (512 * 513 // 2)


def test_the_traffic_file_is_the_issues_grid():
    spec = load_cell(CELL, REPO)
    traffic = {k: spec.traffic[k] for k in ("kind", "clients", "first_wave", "lead_in_s", "pool", "pairing_seed", "max_total")}
    assert traffic == {"kind": "closed_loop", "clients": 320, "first_wave": 256, "lead_in_s": 15, "pool": 160, "pairing_seed": 0,
                       "max_total": 8192}
    assert spec.traffic["prompt_len"] == {"dist": "lognormal", "median": 1024, "sigma": 0.8, "min": 128, "max": 4096}
    assert spec.traffic["output_len"] == {"dist": "lognormal", "median": 1024, "sigma": 0.6, "min": 256, "max": 4096}
    assert all(key in spec.traffic for key in ("source", "assumed", "why"))
    vocab = spec.config["vocab_size"]
    pool = trafficgen.closed_loop_requests(spec.traffic, 2**31 + 5, vocab)
    prompts, outputs = np.array([len(r.prompt) for r in pool]), np.array([r.max_new_tokens for r in pool])
    assert len(pool) == 160 and prompts.min() >= 128 and prompts.max() <= 4096 and outputs.min() >= 256 and outputs.max() <= 4096
    assert max(max(r.prompt) for r in pool) < vocab
    assert (prompts + outputs).max() <= spec.traffic["max_total"] == spec.config["serve"]["positions_per_slot"]
    assert abs(prompts.mean() - 1334) < 40 and abs(outputs.mean() - 1216) < 30 and abs(float(np.median(prompts)) - 1024) < 20
    assert abs((prompts + outputs).mean() - 2550) < 60
    # every seed sends the same multiset of lengths: a seed chooses the order
    other = trafficgen.closed_loop_requests(spec.traffic, 12345, vocab)
    assert sorted((len(r.prompt), r.max_new_tokens) for r in other) == sorted((len(r.prompt), r.max_new_tokens) for r in pool)
    # the pool of pages holds what 256 requests reserve with room: the mean and five deviations of 256 draws
    totals = prompts + outputs
    assert 256 * totals.mean() + 5 * np.sqrt(256) * totals.std() < (spec.config["serve"]["pool_pages"] - 1) * 32


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
    assert "decode step, 256 slots x 8192 positions" in title
    signatures = family.mechanism_signatures(c, c["serve"])
    by, largest_other = {}, 0
    for nbytes, text in test_bm_falconh1._ops_as_the_trace_names_them(lowered.as_text(dialect="hlo")):
        mechanism = family.mechanism_of(text, signatures)
        by[mechanism] = by.get(mechanism, 0) + nbytes
        if mechanism == "other":
            largest_other = max(largest_other, nbytes)
    stream = 256 * 4096 * 4
    assert largest_other <= 3.25 * stream, "an op of the stream reads two of its size (a select: a mask beside them) and writes one"
    assert {"head", "moe", "attention", "mlp", "other"} <= set(by) and by["other"] < 0.02 * sum(by.values()), by
    # the names the chip's trace shows for the kernels (this PR's compiles for a described v5e) and for the weights
    of = lambda text, table=signatures: family.mechanism_of(text, table)
    assert of("%paged_decode_kv8.6 = f32[256,64,128]{2,1,0:T(8,128)S(1)} custom-call(%constant.144, %get-tuple-element.56)") == "attention"
    assert of("%paged_decode_kv4.9 = f32[256,64,128]{2,1,0} custom-call(s32[1]{0} %l, s32[256]{0} %n, s32[256,256]{1,0} %table)") == "attention"
    assert of("%fusion.7 = f32[16,256,2048]{2,1,0} fusion(bf16[16,4096,2048]{2,1,0} %w_gate, f32[256,4096] %h)") == "moe"
    assert of("%grouped_swiglu.3 = f32[2048,4096]{1,0} custom-call(s32[17] %tiles, bf16[2048,4096] %xs, bf16[16,4096,2048] %w)") == "moe"
    assert of("%fusion.8 = f32[256,256]{1,0} fusion(f32[4096,256]{1,0} %router, f32[256,4096] %h)") == "moe"
    assert of("%fusion.9 = f32[256,19072]{1,0} fusion(bf16[4096,19072]{1,0} %lm_head, f32[256,4096] %x)") == "head"
    assert of("%fusion.3 = f32[256,16384]{1,0} fusion(bf16[4096,16384]{1,0} %gate, f32[256,4096] %h)") == "mlp"
    assert of("%fusion.4 = f32[256,12288]{1,0} fusion(bf16[4096,12288]{1,0} %q_proj, f32[256,4096] %u)") == "attention"
    assert of("%fusion.5 = bf16[256,1,768]{2,1,0} fusion(bf16[4096,768]{1,0} %k_proj, f32[256,4096] %u)") == "attention"
    assert of("%fusion.11 = f32[256,4096]{1,0} fusion(f32[256,4096] %x)") == "other"
    # a prefill's table is of its rung's rows
    rung = family.mechanism_signatures(c, c["serve"], 512)
    assert of("%window_flash_fwd.3 = (bf16[64,512,128]{2,1,0}, f32[64,512,1]{2,1,0}) custom-call(%a, %b, %c, %s)", rung) == "attention"
    assert of("%causal_flash_fwd.2 = (bf16[64,512,128]{2,1,0}, f32[64,512,1]{2,1,0}) custom-call(%a, %b, %c)", rung) == "attention"
    assert of("%grouped_swiglu.4 = f32[4096,4096]{1,0} custom-call(bf16[4096,4096] %xs, bf16[16,4096,2048] %w)", rung) == "moe"
    assert of("%fusion.2 = f32[512,4096]{1,0} fusion(f32[512,4096] %x)", rung) == "other"
    top = family.mechanism_signatures(c, c["serve"], 4096)
    assert of("%fusion.2 = f32[4096,4096]{1,0} fusion(f32[4096,4096] %x)", top) == "other", "the stream at the rung as wide as it is long"


def test_the_readers_arithmetic_on_a_recorded_session():
    """Microseconds: two decode launches and one prefill of the 1,024 rung, their
    programs on the ``XLA Modules`` line and the ops inside them."""
    (reader,) = [m for m in discover(os.path.join(REPO, "benchmark", "layer_metrics")) if NEW_METRICS[0] in m.METRICS]
    assert list(reader.METRICS) == NEW_METRICS
    spec = load_cell(CELL, REPO)
    c, family = spec.config, spec.family()
    steps, slots = 10, 256
    pages, rings = 2 * slots * 2000 * steps, 5 * slots * 128 * steps
    counters = {"decode_steps": steps, "page_positions_read": pages, "page_bytes_read": pages * 2560, "ring_positions_read": rings,
                "ring_bytes_rw": (rings + 5 * slots * steps) * 5120, "prefill_window_attn_flops": 1, "prefill_full_attn_flops": 1,
                "rows_routed_nowhere": 6 * steps * 150, "moe_assignments": 6 * steps * slots * 8}
    RING = "%paged_decode_kv8.6 = f32[256,64,128]{2,1,0:T(8,128)S(1)} custom-call(%constant.144, %get-tuple-element.56)"
    PAGES = "%paged_decode_kv4.9 = f32[256,64,128]{2,1,0:T(8,128)S(1)} custom-call(%constant.154, %copy-done.65)"
    MOE = "%fusion.7 = f32[16,256,2048]{2,1,0} fusion(bf16[16,4096,2048]{2,1,0} %w_gate, f32[256,4096] %h)"
    HEAD = "%fusion.9 = f32[256,19072]{1,0} fusion(bf16[4096,19072]{1,0} %lm_head, f32[256,4096] %x)"
    NORM = "%fusion.11 = f32[256,4096]{1,0} fusion(f32[256,4096] %x)"
    WINDOW = "%window_flash_fwd.3 = (bf16[64,1024,128]{2,1,0}, f32[64,1024,1]{2,1,0}) custom-call(%a, %b, %c, %s)"
    CAUSAL = "%causal_flash_fwd.2 = (bf16[64,1024,128]{2,1,0}, f32[64,1024,1]{2,1,0}) custom-call(%a, %b, %c)"
    SORTED = "%grouped_swiglu.4 = f32[8192,4096]{1,0} custom-call(bf16[8192,4096] %xs, bf16[16,4096,2048] %w)"
    modules = [(1000, 3000, "jit_decode(1)"), (4000, 6000, "jit_decode(1)"), (7000, 9000, "jit_prefill(9)")]
    host = [(900, 950, "vs.serve-decode.launch", {"launch": 1}), (3100, 3150, "vs.serve-decode.launch", {"launch": 2}),
            (6100, 6150, "vs.serve-prefill.launch", {"launch": 3, "rung": 1024, "slot": 5})]
    ops = [(1000, 1300, RING), (1300, 1500, PAGES), (1500, 2500, MOE), (2500, 2800, HEAD), (2800, 3000, NORM),   # 2000
           (4000, 4300, RING), (4300, 4500, PAGES), (4500, 5500, MOE), (5500, 5800, HEAD), (5800, 6000, NORM),   # 2000
           (7000, 7300, WINDOW), (7300, 7600, CAUSAL), (7600, 8600, SORTED), (8600, 9000, NORM.replace("[256,", "[1024,")),  # 2000
           (9500, 9900, RING)]                                                                                   # outside any program
    run = types.SimpleNamespace(traffic_kind="closed_loop", slots=256, padded_prompt_len=8192, device_kind="TPU v5 lite",
                                session=types.SimpleNamespace(profile=_trace(ops, modules, host)),
                                _session_reduced={"counters": counters})
    got = reader.read(run)
    assert set(got) == set(NEW_METRICS)
    assert got["qk192_pages_gb_per_step.batch"] == pytest.approx(2 * slots * 2000 * 2560 / 1e9)
    assert got["qk192_ring_gb_per_step.batch"] == pytest.approx(5 * slots * 129 * 5120 / 1e9)
    assert got["qk192_attn_device_share.batch"] == pytest.approx(100 * (500 + 500 + 600) / 6000)
    assert got["experts16of256_device_share.batch"] == pytest.approx(100 * 3000 / 6000)
    assert got["experts16of256_nowhere_share.batch"] == pytest.approx(150 / 256)
    assert got["qk192_paged_decode_roofline.batch"] == pytest.approx(100 * (2 * slots * 2000 * 2560 / 819e9) / 200e-6)
    assert got["sink_ring_decode_roofline.batch"] == pytest.approx(100 * (5 * slots * 128 * 5120 / 819e9) / 300e-6)
    for name, kind in (("sink_window_flash_roofline.batch", family.SWA), ("qk192_full_flash_roofline.batch", family.FULL)):
        flops, nbytes = family.prefill_attention_flops(c, 1024, kind), family.prefill_attention_bytes(c, 1024, kind)
        assert got[name] == pytest.approx(100 * max(flops / 197e12, nbytes / 819e9) / 300e-6)
    assert family.prefill_attention_flops(c, 1024, family.SWA) == 2 * 320 * 64 * 5 * (128 * 129 // 2 + 896 * 128)
    assert family.prefill_attention_bytes(c, 1024, family.SWA) == 5 * (64 + 8) * 320 * 1024 * 2
    # a program without the model's counters (this PR's parent; another family's run) leaves them all out
    run._session_reduced = {"counters": {"decode_steps": 5, "ring_positions_read": 7, "ring_bytes_rw": 7, "moe_assignments": 9}}
    assert reader.read(run) == {}
    run._session_reduced = {"counters": dict(counters, decode_steps=0)}
    assert reader.read(run) == {}
    # another cache geometry than the configuration's: the counters' metrics alone
    other = types.SimpleNamespace(traffic_kind="closed_loop", slots=64, padded_prompt_len=8192, device_kind="TPU v5 lite",
                                  session=run.session, _session_reduced={"counters": counters})
    assert set(reader.read(other)) == {"qk192_pages_gb_per_step.batch", "qk192_ring_gb_per_step.batch"}
    assert reader.read(types.SimpleNamespace(traffic_kind="open_loop")) == {}
    assert reader.read(types.SimpleNamespace(traffic_kind="closed_loop", session=None)) == {}


def test_the_new_entries_of_benchmark_json_are_at_the_end_and_name_the_cell():
    bench = load_benchmark(REPO)
    n = len(NEW_METRICS)
    assert bench["workloads"][-1]["name"] == CELL and bench["configs"][-1]["name"] == CONFIG
    assert bench["workloads"][-1]["chips"] == 1 and bench["workloads"][-1]["traffic"] == "reasoning_closed320"
    assert bench["configs"][-1]["reduced"] == REDUCED and bench["configs"][-1]["file"] == f"benchmark/configs/{CONFIG}.json"
    assert bench["configs"][-1]["source"] == "https://huggingface.co/XiaomiMiMo/MiMo-V2.5/blob/main/config.json"
    assert [m["name"] for m in bench["per_layer"][-n:]] == NEW_METRICS
    assert all(m["workloads"] == [CELL] and m["moves"] == "serve_tokens_per_s" for m in bench["per_layer"][-n:])
    (reader,) = [m for m in discover(os.path.join(REPO, "benchmark", "layer_metrics")) if NEW_METRICS[0] in m.METRICS]
    for m in bench["per_layer"][-n:]:
        assert (m["unit"], m["layer"]) == (reader.METRICS[m["name"]]["unit"], reader.METRICS[m["name"]]["layer"])
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert not m["name"].endswith("_roofline.batch") or m["unit"] == "%"
    assert {m["layer"] for m in bench["per_layer"][-n:]} == {"Two-width attention", "Window attention", "Ring cache", "Expert layer"}
    assert all(len(x["why"]) <= 200 for x in bench["workloads"] + bench["configs"])
    listing = [m["name"] for m in bench["end_to_end"] + bench["per_layer"][:-n] if CELL in m.get("workloads", ())]
    for m in bench["end_to_end"] + bench["per_layer"][:-n]:
        lists_all = all(w in m.get("workloads", ()) for w in CLOSED_LOOP)
        assert (CELL in m.get("workloads", ())) == lists_all, m["name"]
        assert not lists_all or m["workloads"][-1] == CELL
    assert listing[0] == "serve_tokens_per_s" and len(listing) == 1 + 22 and all(x.endswith(".batch") for x in listing[1:])
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1 and len(bench["workloads"]) == 10
    assert len(bench["configs"]) == 10 and len(json.dumps(bench)) < 64 * 1024
    # what was there is as it was: the benchmark without this PR's entries is the parent's
    before = _before_this_pr(REPO)
    assert [w["name"] for w in before["workloads"]] == [w["name"] for w in bench["workloads"][:-1]]
    assert before["configs"] == bench["configs"][:-1] and len(before["per_layer"]) == len(bench["per_layer"]) - n
    assert all(before[key] == bench[key] for key in bench if key not in ("configs", "workloads", "end_to_end", "per_layer"))
