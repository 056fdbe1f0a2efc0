"""The ``longcat_flash`` family (two latent-attention sublayers a layer at 64
heads, a shortcut branch of a share of 512 routed experts beside 256 zero-compute
identity experts under a softmax router with a selection bias) in the benchmark:
a toy configuration and cell added to a temporary root by files and entries
alone, run through ``serve_cell`` to ``correct``, and to not correct with one
sign turned in the reference; the real configuration file against the catalog's
row and the issue's bytes, and against what the program allocates; the traffic
file's grid; the table of shapes over the decode program traced on the CPU at the
cell's shapes; the reader's arithmetic on a made-up session, and
``mla_serve_batch.py`` silent on this family.

As ``test_bm_mimo.py`` did for its entries, this file tells the tests that were
here before of the new cell AT IMPORT: ``test_bm_session.TINY_OF`` gets the
cell's toy stand-in, and ``test_bm_prefill_ride``'s last test (the newest link; through its
view every older link's), which holds that its PR's entries are the LAST of
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
import test_bm_mimo
import test_bm_prefill_ride
import test_bm_session
from bm_fixtures import REPO, make_tiny_root
from test_bm_programs import _trace

from benchmark import serve_cell, trafficgen
from benchmark.harness import discover, result_object
from benchmark.spec import load_benchmark, load_cell, load_family

CELL = "longcatflash_serve_reasoning"
CONFIG = "longcat-flash-omni.serve-L4-ep32"
REDUCED = ["num_layers", "n_routed_experts", "vocab_size"]
NEW_METRICS = ["mla64_latent_gb_per_step.batch", "zero_expert_pair_share.batch", "mla64_device_share.batch",
               "scmoe_routed_device_share.batch", "mla64_decode_roofline.batch", "mla64_prefill_roofline.batch",
               "scmoe_step_hbm_roofline_share.batch"]
CLOSED_LOOP = test_bm_mimo.CLOSED_LOOP + (test_bm_mimo.CELL,)
# the general ``.batch`` readers that give no value in this cell's traced run on the chip (PERF.md section 7, after PR 54)
LEFT_OUT = ("idle_unattributed_share.batch",)     # (no value where the traced seconds hold no idle gap: 0.01% idle in one of two traced runs)

test_bm_session.TINY_OF.setdefault(CELL, "tiny_batch")


def _before_this_pr(root):
    """``BENCHMARK.json`` without what PR 54 appended (its configuration, its cell, its metrics, its list members)."""
    bench = load_benchmark(root)
    bench["configs"] = [c for c in bench["configs"] if c["name"] != CONFIG]
    bench["workloads"] = [w for w in bench["workloads"] if w["name"] != CELL]
    for group in ("end_to_end", "per_layer"):
        bench[group] = [dict(m, workloads=[w for w in m["workloads"] if w != CELL]) if "workloads" in m else m
                        for m in bench[group] if m["name"] not in NEW_METRICS]
    return bench


test_bm_prefill_ride.load_benchmark = _before_this_pr   # the newest link of the chain: each reads through the next

# hidden 64, 4 heads of 16 + 8 | 16 over a latent of 32, two model layers (four pool layers), 4 held of 16 real experts
# beside 8 identity ones, 4 of 24 outputs a token; a pool of 21 pages where the four slots' whole allotment would be 32
TOY = {"source": "tests only", "model": "longcat_flash", "vocab_size": 96, "hidden_size": 64, "num_layers": 2,
       "ffn_hidden_size": 96, "expert_ffn_hidden_size": 32, "n_routed_experts": 4, "zero_expert_num": 8,
       "zero_expert_type": "identity", "moe_topk": 4, "routed_scaling_factor": 6, "num_attention_heads": 4,
       "attention_bias": False, "attention_method": "MLA", "q_lora_rank": 32, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
       "qk_rope_head_dim": 8, "v_head_dim": 16, "mla_scale_q_lora": True, "mla_scale_kv_lora": True, "rope_theta": 10000000,
       "rms_norm_eps": 1e-5, "max_position_embeddings": 131072,
       "reduced": ["n_routed_experts"], "published": {"n_routed_experts": 16}, "share": {"chips": 4, "of": ["n_routed_experts"]},
       "assumed": {"mla_scale_values": "(hidden_size/rank)**0.5", "zero_expert": "gate*input", "norm_topk_prob": False,
                   "router_bias_term": False, "score_scale": "qk_head_dim**-0.5", "rotary_pairs": "interleaved"},
       "deployment": "none: a toy", "kind": "serve",
       "serve": {"weight_dtype": "bfloat16", "slots": 4, "positions_per_slot": 64, "page_size": 8, "pool_pages": 21,
                 "prefill_chunk": 8}}
WRAPPER = '''"""The longcat_flash family with the identity part's sign turned in its reference (tests only)."""
import functools

from benchmark import reference
from benchmark.families import longcat_flash as real

SERVE_LOGITS_TOLERANCE = real.SERVE_LOGITS_TOLERANCE
program_config, build_serve, rehearse_serve = real.program_config, real.build_serve, real.rehearse_serve


def logits(params, config, tokens, rows):
    return real.logits(params, config, tokens, rows, wrong="identity_sign")


loss_and_logits = functools.partial(reference.loss_and_logits, logits)
'''


def _run(root, cell, traced=0, seed=77):
    spec = load_cell(cell, root)
    rec, correct, attempted, failed, notes = serve_cell.run_cell(spec, jax.devices()[:1], seed, 1.0, traced,
                                                                 time.perf_counter())
    return spec, rec, correct, attempted, failed, notes


def test_the_family_runs_a_toy_cell_to_correct_by_files_and_entries_alone(tmp_path):
    """The runner as it is: two pool layers a model layer through the normal
    path, a pool smaller than the slots' whole allotment, and the check's prompt
    (59 of 64 positions, on the 64 rung) against the reference."""
    root = make_tiny_root(str(tmp_path / "root"))
    cell = test_bm_hybrid._add_cell(root, "toylongcat", TOY)
    spec, rec, correct, attempted, failed, notes = _run(root, cell, traced=2)
    family = load_family("longcat_flash", root)
    assert attempted > 0 and failed == 0 and notes["ledger"]["problems"] == [], notes
    assert notes["compiles_in_window"] == 0, "every rung and the decode step were compiled by warm()"
    # (at a hidden size of 64 bfloat16 rounds coarser than at 6,144: the toy's check may read past the limit set on the chip)
    assert 0 < notes["reference"]["logits_max_abs_diff_over_max"] < 3 * family.SERVE_LOGITS_TOLERANCE
    assert correct == (notes["reference"]["logits_max_abs_diff_over_max"] <= family.SERVE_LOGITS_TOLERANCE)
    assert notes["reference"]["tolerance"] == family.SERVE_LOGITS_TOLERANCE and notes["reference"]["prompt_tokens"] == 59
    counters = notes["session_counters"]           # the trace session read the engine's counters
    assert counters["decode_steps"] > 0 and counters["moe_assignments"] > 0 and counters["moe_layer_steps"] == 2 * counters["decode_steps"]
    assert counters["latent_bytes_read"] > 0 and counters["latent_bytes_read"] % (4 * 8 * 128 * 2) == 0, "four pool layers' whole pages"
    assert counters["prefill_attn_flops"] > 0
    assert 0 < counters["zero_expert_assignments"] < counters["moe_assignments"]
    line = result_object(spec, rec, jax.devices()[:1], correct=correct, attempted=attempted, failed=failed, traced=2)
    assert {"serve_tokens_per_s", "setup_s", "decode_step_ms_p50.batch"} <= set(line["metrics"])
    assert line["metrics"]["serve_tokens_per_s"]["value"] > 0


def test_a_sign_turned_in_the_reference_reads_not_correct(tmp_path):
    root = make_tiny_root(str(tmp_path / "root"))
    cell = test_bm_hybrid._add_cell(root, "toylongcat_sign", dict(TOY, model="longcat_flash_sign"), WRAPPER)
    _spec, _rec, correct, _attempted, _failed, notes = _run(root, cell)
    assert not correct
    assert notes["ledger"]["problems"] == [] and notes["compiles_in_window"] == 0
    assert notes["reference"]["logits_max_abs_diff_over_max"] > 5 * notes["reference"]["tolerance"]


# ------------------------------------------------- the real configuration file
def test_the_real_configuration_is_the_catalogs_row_cut_as_the_issue_says():
    spec = load_cell(CELL, REPO)
    c, family = spec.config, spec.family()
    assert spec.chips == 1 and spec.traffic_name == "reasoning2k_closed160" and spec.traffic["kind"] == "closed_loop"
    assert {m["name"] for m in spec.end_to_end} == {"serve_tokens_per_s", "setup_s"}
    # every key of the catalog's config under its name, but for the depth, the experts held and the vocabulary's slice
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(path):
        with open(path) as f:
            catalog = next(r for r in map(json.loads, f) if r["name"] == "LongCat-Flash-Omni")
        assert c["source"] == catalog["source_url"]
        differs = {k: v for k, v in catalog["config"].items() if c[k] != v}
        assert sorted(differs) == sorted(REDUCED) and differs == c["published"]
    assert c["reduced"] == REDUCED and c["num_layers"] == 4 and c["published"] == {"num_layers": 28, "n_routed_experts": 512, "vocab_size": 131072}
    assert c["share"] == {"chips": 32, "of": ["n_routed_experts", "vocab_size"]}
    widths = {"hidden_size": 6144, "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048, "num_attention_heads": 64,
              "kv_lora_rank": 512, "q_lora_rank": 1536, "qk_rope_head_dim": 64, "qk_nope_head_dim": 128, "v_head_dim": 128,
              "moe_topk": 12, "zero_expert_num": 256, "routed_scaling_factor": 6, "rope_theta": 10000000, "rms_norm_eps": 1e-5,
              "n_routed_experts": 16, "vocab_size": 16384}
    assert {k: c[k] for k in widths} == widths
    # the floors of the model-configs guide: four layers (the period is one), 8 or more experts, an eighth of the vocabulary
    assert c["num_layers"] >= 4 and c["n_routed_experts"] >= 8 and c["vocab_size"] * 8 == c["published"]["vocab_size"]
    assert all(key in c["assumed"] for key in (*family.ASSUMED, "init", "left_out", "page_size", "slots", "pool_pages", "cache_row"))
    assert "seven pipeline stages of four layers" in c["deployment"] and "32 v5e chips" in c["deployment"]
    cfg = family.program_config(c)
    assert (cfg.num_experts, cfg.zero_expert_num, cfg.router_outputs, cfg.experts_held, cfg.first_expert_held) == (512, 256, 768, 16, 0)
    assert (cfg.num_layers, cfg.attention_layers, cfg.vocab_size, cfg.num_experts_per_tok) == (4, 8, 16384, 12)
    a = cfg.mla
    assert (a.num_attention_heads, a.latent_row, a.cache_row, a.q_scale) == (64, 576, 640, 2.0)
    assert a.kv_scale == pytest.approx(12 ** 0.5) and a.softmax_scale == pytest.approx(192 ** -0.5) and a.cos_scale == 1.0
    # ISSUE 54's arithmetic, in millions of parameters and in GB
    M = 1e6
    assert round(family.attention_params(c) / M, 2) == 90.57 and round(family.dense_params(c) / M, 2) == 226.49
    assert round(family.router_params(c) / M, 2) == 4.72 and round(family.expert_params(c) / M, 2) == 37.75
    assert round(family.layer_params(c) / M, 2) == 1242.83 and round(2 * 16384 * 6144 / M, 2) == 201.33
    assert round((family.layer_params(c) - 16 * family.expert_params(c)) / M, 2) == 638.85
    assert round(family.weight_bytes(c) / 1e9, 2) == 10.38
    # ... and the whole model's, which the issue adds up to 560.7 B and 27.1 B active at eight real experts a token
    outside = family.layer_params(c) - 16 * family.expert_params(c)
    assert round((28 * (outside + 512 * family.expert_params(c)) + 2 * 131072 * 6144) / 1e9, 1) == 560.7
    assert round((28 * (outside + 8 * family.expert_params(c)) + 131072 * 6144) / 1e9, 1) == 27.1
    serve = c["serve"]
    assert (serve["slots"], serve["positions_per_slot"], serve["page_size"]) == (128, 4096, 16) and serve["pool_pages"] >= 16384
    assert family.sublayers(c) == 8 and family.pool_bytes_per_position(c) == 10240 and family.latent_bytes_per_position(c) == 1152
    assert family.cache_bytes(c, serve) == serve["pool_pages"] * 16 * 10240
    assert 13.0 < (family.weight_bytes(c) + family.cache_bytes(c, serve)) / 1e9 < 14.0, "over 80% of the chip's 16 GB in arguments"
    # a decode step's bytes at the traffic's mean live length, 14 of 16 experts touched a layer: 12-13 GB
    moved = family.decode_step_bytes(c, serve, latent_positions_read=8 * 128 * 1830, experts_touched=4 * 14)
    assert 11.5e9 < moved < 13.0e9
    assert family.mla_decode_flops_per_position(c) == 2 * 64 * (576 + 512) == 139264
    assert round(family.mla_decode_flops_per_position(c) / 1280) == 109, "operations a byte of a padded row read: the memory side of 240"
    assert family.mla_prefill_attention_flops(c, 2048) == 64 * 640 * 2048 ** 2 / 2
    assert family.mla_prefill_attention_bytes(c, 2048) == 2 * 64 * 320 * 2048 * 2
    assert family.prefill_rungs(serve) == [128, 256, 512, 1024, 1536, 2048, 3072, 4096]


def test_what_the_program_allocates_is_what_the_family_counts():
    spec = load_cell(CELL, REPO)
    c, family = spec.config, spec.family()
    from vescale_tpu.models.longcat_flash import init_params, prefill_counters
    from vescale_tpu.serve.hybrid_engine import hybrid_cache_config, prefill_buckets

    cfg = family.program_config(c)
    tree = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.key(0))
    assert sum(int(a.size) * a.dtype.itemsize for a in jax.tree_util.tree_leaves(tree)) == family.weight_bytes(c)
    assert sum(int(a.size) for a in jax.tree_util.tree_leaves(tree)) == family.param_count(c)
    assert tree["lm_head"]["kernel"].shape == (6144, 16384) and tree["embed_tokens"]["embedding"].shape == (16384, 6144)
    layer = tree["layers_3"]
    for i in (0, 1):
        ap = layer[f"self_attn_{i}"]
        assert ap["q_a"].shape == (6144, 1536) and ap["q_b"].shape == (1536, 64 * 192) and ap["kv_a"].shape == (6144, 576)
        assert ap["kv_b_k"].shape == (64, 128, 512) and ap["kv_b_v"].shape == (64, 512, 128) and ap["o"].shape == (8192, 6144)
        assert layer[f"mlps_{i}"]["gate"].shape == (6144, 12288) and layer[f"mlps_{i}"]["down"].shape == (12288, 6144)
    assert layer["mlp"]["w_gate"].shape == (16, 6144, 2048) and layer["mlp"]["w_down"].shape == (16, 2048, 6144)
    assert layer["mlp"]["router"].shape == (6144, 768) and layer["mlp"]["router_bias"].shape == (768,)
    serve = c["serve"]
    kc = hybrid_cache_config(cfg, num_slots=128, page_size=16, pages_per_slot=256, num_pages=serve["pool_pages"])
    assert kc == family._cache_config(cfg, serve)
    assert (kc.layers, kc.kv_heads, kc.head_dim, kc.latent, kc.max_seq_len, kc.pool_pages) == (8, 1, 640, True, 4096, serve["pool_pages"])
    assert kc.layers * kc.pool_pages * kc.page_size * 640 * 2 == family.cache_bytes(c, serve)
    assert prefill_buckets(cfg.prefill_chunk, kc.max_seq_len) == family.prefill_rungs(serve)
    for rung in (128, 512, 4096):
        assert prefill_counters(cfg, rung) == {"prefill_attn_flops": 8 * family.mla_prefill_attention_flops(c, rung)}


def test_the_traffic_file_is_the_issues_grid():
    spec = load_cell(CELL, REPO)
    traffic = {k: spec.traffic[k] for k in ("kind", "clients", "first_wave", "lead_in_s", "pool", "pairing_seed", "max_total")}
    assert traffic == {"kind": "closed_loop", "clients": 160, "first_wave": 128, "lead_in_s": 15, "pool": 64, "pairing_seed": 0,
                       "max_total": 4096}
    assert spec.traffic["prompt_len"] == {"dist": "lognormal", "median": 1024, "sigma": 0.8, "min": 128, "max": 2048}
    assert spec.traffic["output_len"] == {"dist": "lognormal", "median": 1024, "sigma": 0.6, "min": 256, "max": 2048}
    assert all(key in spec.traffic for key in ("source", "assumed", "why"))
    # reasoning_closed320's two distributions to the letter but for the upper clips
    other = load_cell(test_bm_mimo.CELL, REPO).traffic
    for key in ("prompt_len", "output_len"):
        assert {k: v for k, v in spec.traffic[key].items() if k != "max"} == {k: v for k, v in other[key].items() if k != "max"}
    vocab = spec.config["vocab_size"]
    pool = trafficgen.closed_loop_requests(spec.traffic, 2**31 + 5, vocab)
    prompts, outputs = np.array([len(r.prompt) for r in pool]), np.array([r.max_new_tokens for r in pool])
    assert len(pool) == 64 and prompts.min() >= 128 and prompts.max() <= 2048 and outputs.min() >= 256 and outputs.max() <= 2048
    assert max(max(r.prompt) for r in pool) < vocab
    assert (prompts + outputs).max() <= spec.traffic["max_total"] == spec.config["serve"]["positions_per_slot"]
    assert abs(prompts.mean() - 1138) < 2 and abs(outputs.mean() - 1126) < 2
    assert round(float((prompts == 2048).mean()), 2) == 0.19 and round(float((outputs == 2048).mean()), 3) == 0.125
    # every seed sends the same multiset of lengths: a seed chooses the order
    again = trafficgen.closed_loop_requests(spec.traffic, 12345, vocab)
    assert sorted((len(r.prompt), r.max_new_tokens) for r in again) == sorted((len(r.prompt), r.max_new_tokens) for r in pool)
    # the pool of pages holds what 128 requests reserve on the mean; a long draw waits for pages (batch_occupancy says how often)
    assert 128 * (prompts + outputs).mean() < (spec.config["serve"]["pool_pages"] - 1) * 16


# ------------------------------------------------------------------ the readers
def test_the_table_of_shapes_leaves_none_of_the_decode_programs_large_ops_under_other():
    """The decode program traced on the CPU at the cell's shapes (shapes, no
    arrays; the XLA legs).  What stays under ``other`` is of the residual
    stream's own size (its norms and sums): nothing that reads a weight or the pool."""
    spec = load_cell(CELL, REPO)
    c, family = spec.config, spec.family()
    _sizes, programs = family.rehearse_serve(spec.name, c, c["serve"], jax.devices())
    title, lowered = programs[-1]
    assert "decode step, 128 slots x 4096 positions" in title
    signatures = family.mechanism_signatures(c, c["serve"])
    by, largest_other = {}, 0
    for nbytes, text in test_bm_falconh1._ops_as_the_trace_names_them(lowered.as_text(dialect="hlo")):
        mechanism = family.mechanism_of(text, signatures)
        by[mechanism] = by.get(mechanism, 0) + nbytes
        if mechanism == "other":
            largest_other = max(largest_other, nbytes)
    stream = 128 * 6144 * 4
    assert largest_other <= 3.25 * stream, "an op of the stream reads two of its size (a select: a mask beside them) and writes one"
    assert {"head", "routed", "mla", "mlp", "other"} <= set(by) and by["other"] < 0.02 * sum(by.values()), by
    # the names the chip's trace shows for the kernels and for the weights
    of = lambda text, table=signatures: family.mechanism_of(text, table)
    assert of("%paged_decode_latent.6 = f32[128,64,512]{2,1,0:T(8,128)} custom-call(%constant.144, %get-tuple-element.56)") == "mla"
    assert of("%fusion.7 = f32[16,128,2048]{2,1,0} fusion(bf16[16,6144,2048]{2,1,0} %w_gate, f32[128,6144] %h)") == "routed"
    assert of("%fusion.8 = f32[128,768]{1,0} fusion(f32[6144,768]{1,0} %router, f32[128,6144] %h)") == "routed"
    assert of("%fusion.9 = f32[128,16384]{1,0} fusion(bf16[6144,16384]{1,0} %lm_head, f32[128,6144] %x)") == "head"
    assert of("%fusion.3 = f32[128,12288]{1,0} fusion(bf16[6144,12288]{1,0} %gate, f32[128,6144] %h)") == "mlp"
    assert of("%fusion.4 = bf16[128,64,192]{2,1,0} fusion(bf16[1536,64,192]{2,1,0} %q_b, bf16[128,1536] %cq)") == "mla"
    assert of("%fusion.5 = bf16[128,640]{1,0} fusion(bf16[6144,576]{1,0} %kv_a, f32[128,6144] %u)") == "mla"
    assert of("%fusion.6 = f32[128,6144]{1,0} fusion(bf16[8192,6144]{1,0} %o, bf16[128,8192] %y)") == "mla"
    assert of("%fusion.11 = f32[128,6144]{1,0} fusion(f32[128,6144] %x)") == "other"
    # a prefill's table is of its rung's rows; a shape two mechanisms share at a rung is in neither's table there
    rung = family.mechanism_signatures(c, c["serve"], 512)
    assert of("%mla_flash_fwd.3 = (bf16[64,512,128]{2,1,0}, f32[64,512,1]{2,1,0}) custom-call(%a, %b, %c)", rung) == "mla"
    assert of("%grouped_swiglu.4 = f32[10240,6144]{1,0} custom-call(bf16[10240,6144] %xs, bf16[16,6144,2048] %w)", rung) == "routed"
    assert of("%fusion.2 = f32[512,6144]{1,0} fusion(f32[512,6144] %x)", rung) == "other"
    assert of("%fusion.2 = f32[2048,6144]{1,0} fusion(f32[2048,6144] %x)", family.mechanism_signatures(c, c["serve"], 2048)) == "other"
    assert of("%fusion.3 = f32[1536,12288]{1,0} fusion(f32[1536,6144] %h, bf16[6144,12288]{1,0} %up)",
              family.mechanism_signatures(c, c["serve"], 1536)) == "mlp"


def test_the_readers_arithmetic_on_a_recorded_session():
    """Microseconds: two decode launches and one prefill of the 1,024 rung, their
    programs on the ``XLA Modules`` line and the ops inside them."""
    (reader,) = [m for m in discover(os.path.join(REPO, "benchmark", "layer_metrics")) if NEW_METRICS[0] in m.METRICS]
    assert list(reader.METRICS) == NEW_METRICS
    spec = load_cell(CELL, REPO)
    c, family = spec.config, spec.family()
    steps, slots, live = 10, 128, 1800
    counters = {"decode_steps": steps, "latent_bytes_read": 8 * slots * live * 1280 * steps, "prefill_attn_flops": 1,
                "zero_expert_assignments": 4 * steps * slots * 4,
                "moe_assignments": 4 * steps * slots * 12, "moe_experts_touched": 4 * 14 * steps}
    LATENT = "%paged_decode_latent.6 = f32[128,64,512]{2,1,0:T(8,128)S(1)} custom-call(%constant.144, %get-tuple-element.56)"
    MOE = "%fusion.7 = f32[16,128,2048]{2,1,0} fusion(bf16[16,6144,2048]{2,1,0} %w_gate, f32[128,6144] %h)"
    MLP = "%fusion.3 = f32[128,12288]{1,0} fusion(bf16[6144,12288]{1,0} %gate, f32[128,6144] %h)"
    HEAD = "%fusion.9 = f32[128,16384]{1,0} fusion(bf16[6144,16384]{1,0} %lm_head, f32[128,6144] %x)"
    NORM = "%fusion.11 = f32[128,6144]{1,0} fusion(f32[128,6144] %x)"
    FLASH = "%mla_flash_fwd.3 = (bf16[64,1024,128]{2,1,0}, f32[64,1024,1]{2,1,0}) custom-call(%a, %b, %c)"
    SORTED = "%grouped_swiglu.4 = f32[16384,6144]{1,0} custom-call(bf16[16384,6144] %xs, bf16[16,6144,2048] %w)"
    modules = [(1000, 3000, "jit_decode(1)"), (4000, 6000, "jit_decode(1)"), (7000, 9000, "jit_prefill(9)")]
    host = [(900, 950, "vs.serve-decode.launch", {"launch": 1}), (3100, 3150, "vs.serve-decode.launch", {"launch": 2}),
            (6100, 6150, "vs.serve-prefill.launch", {"launch": 3, "rung": 1024, "slot": 5})]
    ops = [(1000, 1500, LATENT), (1500, 2100, MOE), (2100, 2600, MLP), (2600, 2800, HEAD), (2800, 3000, NORM),    # 2000
           (4000, 4500, LATENT), (4500, 5100, MOE), (5100, 5600, MLP), (5600, 5800, HEAD), (5800, 6000, NORM),    # 2000
           (7000, 7600, FLASH), (7600, 8400, SORTED), (8400, 9000, NORM.replace("[128,", "[1024,")),              # 2000
           (9500, 9900, LATENT)]                                                                                  # outside any program
    run = types.SimpleNamespace(traffic_kind="closed_loop", slots=128, padded_prompt_len=4096, device_kind="TPU v5 lite",
                                session=types.SimpleNamespace(profile=_trace(ops, modules, host)),
                                _session_reduced={"counters": counters})
    got = reader.read(run)
    assert set(got) == set(NEW_METRICS)
    assert got["mla64_latent_gb_per_step.batch"] == pytest.approx(8 * slots * live * 1280 / 1e9)
    assert got["zero_expert_pair_share.batch"] == pytest.approx(4 / 12)
    assert got["mla64_device_share.batch"] == pytest.approx(100 * (500 + 500 + 600) / 6000)
    assert got["scmoe_routed_device_share.batch"] == pytest.approx(100 * (600 + 600 + 800) / 6000)
    positions = 8 * slots * live
    assert positions * 1152 / 819e9 > positions * 139264 / 197e12, "at 64 heads the bytes bound it"
    assert got["mla64_decode_roofline.batch"] == pytest.approx(100 * (positions * 1152 / 819e9) / 500e-6)
    flops, nbytes = family.mla_prefill_attention_flops(c, 1024), family.mla_prefill_attention_bytes(c, 1024)
    assert got["mla64_prefill_roofline.batch"] == pytest.approx(100 * 8 * max(flops / 197e12, nbytes / 819e9) / 600e-6)
    moved = family.decode_step_bytes(c, c["serve"], latent_positions_read=positions, experts_touched=4 * 14)
    assert got["scmoe_step_hbm_roofline_share.batch"] == pytest.approx(100 * (moved / 819e9) / 2000e-6)
    # ``mla_serve_batch.py`` answers to ``latent_bytes_read`` too: it finds no configuration of ITS family with this geometry
    (other,) = [m for m in discover(os.path.join(REPO, "benchmark", "layer_metrics")) if "mla_decode_roofline.batch" in m.METRICS]
    assert other._configuration(run) is None and reader._configuration(run)["model"] == "longcat_flash"
    theirs = types.SimpleNamespace(traffic_kind="closed_loop", slots=32, padded_prompt_len=8192, device_kind="TPU v5 lite")
    assert reader._configuration(theirs) is None and other._configuration(theirs)["model"] == "deepseek_v2"
    assert not set(other.METRICS) & {m["name"] for m in spec.per_layer}, "none of its names is listed for this cell"
    # a program without the model's counters (this PR's parent; DeepSeek-V2's run, which counts latent_bytes_read) leaves them all out
    run._session_reduced = {"counters": {"decode_steps": 5, "latent_bytes_read": 7, "moe_assignments": 9, "moe_groups_kept_here": 3}}
    assert reader.read(run) == {}
    run._session_reduced = {"counters": dict(counters, decode_steps=0)}
    assert reader.read(run) == {}
    # another cache geometry than the configuration's: the counters' first two alone
    elsewhere = types.SimpleNamespace(traffic_kind="closed_loop", slots=64, padded_prompt_len=4096, device_kind="TPU v5 lite",
                                      session=run.session, _session_reduced={"counters": counters})
    assert set(reader.read(elsewhere)) == {"mla64_latent_gb_per_step.batch", "zero_expert_pair_share.batch"}
    assert reader.read(types.SimpleNamespace(traffic_kind="open_loop")) == {}
    assert reader.read(types.SimpleNamespace(traffic_kind="closed_loop", session=None)) == {}


def test_the_new_entries_of_benchmark_json_are_at_the_end_and_name_the_cell():
    bench = load_benchmark(REPO)
    n = len(NEW_METRICS)
    assert bench["workloads"][-1]["name"] == CELL and bench["configs"][-1]["name"] == CONFIG
    assert bench["workloads"][-1]["chips"] == 1 and bench["workloads"][-1]["traffic"] == "reasoning2k_closed160"
    assert bench["configs"][-1]["reduced"] == REDUCED and bench["configs"][-1]["file"] == f"benchmark/configs/{CONFIG}.json"
    assert bench["configs"][-1]["source"] == "https://huggingface.co/meituan-longcat/LongCat-Flash-Omni/blob/main/config.json"
    assert [m["name"] for m in bench["per_layer"][-n:]] == NEW_METRICS
    assert all(m["workloads"] == [CELL] and m["moves"] == "serve_tokens_per_s" for m in bench["per_layer"][-n:])
    (reader,) = [m for m in discover(os.path.join(REPO, "benchmark", "layer_metrics")) if NEW_METRICS[0] in m.METRICS]
    for m in bench["per_layer"][-n:]:
        assert (m["unit"], m["layer"]) == (reader.METRICS[m["name"]]["unit"], reader.METRICS[m["name"]]["layer"])
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert not ("roofline" in m["name"]) or m["unit"] == "%"
    # layers that BENCHMARK.json already names, under those names
    assert {m["layer"] for m in bench["per_layer"][-n:]} == {"Latent attention", "Latent cache", "Expert layer", "Device"}
    assert {m["layer"] for m in bench["per_layer"][-n:]} <= {m["layer"] for m in bench["per_layer"][:-n]}
    assert all(len(x["why"]) <= 200 for x in bench["workloads"] + bench["configs"])
    listing = [m["name"] for m in bench["end_to_end"] + bench["per_layer"][:-n] if CELL in m.get("workloads", ())]
    for m in bench["end_to_end"] + bench["per_layer"][:-n]:
        lists_all = all(w in m.get("workloads", ()) for w in CLOSED_LOOP)
        assert (CELL in m.get("workloads", ())) == (lists_all and m["name"] not in LEFT_OUT), m["name"]
        assert CELL not in m.get("workloads", ()) or m["workloads"][-1] == CELL
    assert listing[0] == "serve_tokens_per_s" and len(listing) == 1 + 23 - len(LEFT_OUT) and all(x.endswith(".batch") for x in listing[1:])
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1 and len(bench["workloads"]) == 11
    assert len(bench["configs"]) == 11 and len(json.dumps(bench)) < 64 * 1024
    # the contract's ceiling of 128 per-layer metrics is reached: ISSUE 54's eighth (the share of rows none of whose
    # experts is held, 0.772 in the builder's first traced run) is left out for it, and its counter with it
    assert len(bench["per_layer"]) == 128
    # what was there is as it was: the benchmark without this PR's entries is the parent's
    before = _before_this_pr(REPO)
    assert [w["name"] for w in before["workloads"]] == [w["name"] for w in bench["workloads"][:-1]]
    assert before["configs"] == bench["configs"][:-1] and len(before["per_layer"]) == len(bench["per_layer"]) - n
    assert all(before[key] == bench[key] for key in bench if key not in ("configs", "workloads", "end_to_end", "per_layer"))
