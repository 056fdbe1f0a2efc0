"""The ``ling_hybrid`` family (delta-rule linear attention whose state is a
float32 matrix a head and slot beside ONE latent pool layer in six; one routing
group of 512 sigmoid-routed experts held) in the benchmark: a toy configuration
and cell added to a temporary root by files and entries alone, run through
``serve_cell`` to ``correct``, and to not correct with faults of its reference;
the real configuration file against the catalog's row and the issue's bytes, and
against what the program allocates; the traffic file's parameters; the table of
shapes over the decode program traced on the CPU at the cell's shapes; the
reader's arithmetic on a made-up session, each roofline by hand.

This file changes no other test module.  ``tests/conftest.py`` names this PR's
cell and traffic file for the two tables of older tests that only name what
they knew (``test_bm_session.TINY_OF``, ``test_bm_order_seed.FILES``), and shows
the two older tests that hold lists of ``BENCHMARK.json`` to the cells they knew
(``test_bm_phi4flash.py``: its own cell "the last entry of every list";
``test_bm_mla.py``: four shared names' lists) the benchmark as it was when they
were written.  This file pins ORDER, not
"last": the next cell is added behind this one and changes nothing here.  Six
lists of ``BENCHMARK.json`` are held by older tests to the cells they knew and do
NOT list this cell (``prefill_program_ms_p50.batch``,
``prefill_start_wait_ms_p50.batch``, ``decode_device_ms_p50.batch``,
``decode_program_ms_p50.batch``, ``decode_launch_ms_p50.batch``,
``loop_books_ms_p50.batch``: ``test_bm_programs.py``, ``test_bm_prefill_ride.py``);
their generic readers give the numbers for this cell's run and the harness drops
them by the list."""

import json
import os
import time
import types

import jax
import numpy as np
import pytest

import test_bm_falconh1
import test_bm_hybrid
from bm_fixtures import REPO, declared_entries, family_readers, make_tiny_root
from test_bm_programs import _trace

from benchmark import serve_cell, trafficgen
from benchmark.harness import result_object
from benchmark.spec import load_benchmark, load_cell, load_family

CELL = "ling3flash_serve_longgen"
CONFIG = "ling-3.0-flash.serve-L7-ep8"
TRAFFIC = "longgen_closed320"
OWN = ["kda_state_gb_per_step.batch", "kda_device_share.batch", "kda_step_roofline.batch", "kda_chunk_roofline.batch",
       "held_group_row_share.batch"]
SHARED = ["step_hbm_roofline_share.batch", "mla_device_share.batch", "mla_prefill_roofline.batch", "experts_load_imbalance.batch"]
NAMES = OWN + SHARED
# four shared names the family could read here and does not return: ``test_bm_mla.py`` holds their lists to the cells of PR 59
HELD_BY_AN_OLDER_TEST = ["experts_device_share.batch", "mla_decode_roofline.batch", "latent_gb_per_step.batch", "experts_held_share.batch"]
GENERIC = ["batch_occupancy.batch", "compiles_in_window.batch", "decode_step_ms_p50.batch", "device_idle_share.batch",
           "loop_self_ms_p50.batch", "peak_hbm_gb.batch", "prefill_ms_p50.batch", "kv_live_share.batch", "host_stall_ms_max.batch",
           "prefill_device_ms_p50.batch", "decode_fetch_ms_p50.batch", "decode_host_gap_ms_p50.batch",
           "logits_mb_to_host_per_step.batch", "decode_pages_read_share.batch", "decode_ahead_share.batch", "prefill_ahead_share.batch"]
UNLISTED = ["prefill_program_ms_p50.batch", "prefill_start_wait_ms_p50.batch", "decode_device_ms_p50.batch",
            "decode_program_ms_p50.batch", "decode_launch_ms_p50.batch", "loop_books_ms_p50.batch"]

FAMILY = load_family("ling_hybrid", REPO)
# hidden 64, the cut's seven layers, 4 heads of 16; 32 experts in 4 groups of 8, 2 groups and 4 experts kept, group 0 held
TOY = {"source": "tests only", "model": "ling_hybrid", "model_type": "bailing_hybrid", **FAMILY.FIXED, "vocab_size": 96,
       "hidden_size": 64, "num_hidden_layers": 7, "layer_group_size": 6, "first_k_dense_replace": 1, "intermediate_size": 96,
       "moe_intermediate_size": 32, "moe_shared_expert_intermediate_size": 32, "num_shared_experts": 1, "num_experts": 8,
       "num_experts_per_tok": 4, "n_group": 4, "topk_group": 2, "routed_scaling_factor": 2.5, "num_attention_heads": 4,
       "num_key_value_heads": 4, "head_dim": 16, "short_conv_kernel_size": 4, "kda_lower_bound": -5, "kv_lora_rank": 32,
       "qk_head_dim": 24, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "rotary_dim": 8, "v_head_dim": 16, "rope_theta": 6000000,
       "rms_norm_eps": 1e-6, "expert_swiglu_limit_list": [0] * 7, "share_expert_swiglu_limit_list": [0] * 7,
       "reduced": ["num_experts", "vocab_size"], "published": {"num_experts": 32, "vocab_size": 384},
       "share": {"chips": 4, "of": ["num_experts", "vocab_size"], "index": 0}, "assumed": {**FAMILY.ASSUMED, "first_layer": 1},
       "deployment": "none: a toy", "kind": "serve",
       "serve": {"weight_dtype": "bfloat16", "state_dtype": "float32", "slots": 4, "positions_per_slot": 64, "page_size": 8,
                 "prefill_chunk": 16}}

WRAPPER = '''"""The ling_hybrid family with one fault in its reference (tests only)."""
import functools

from benchmark import reference
from benchmark.families import ling_hybrid as real

SERVE_LOGITS_TOLERANCE = real.SERVE_LOGITS_TOLERANCE
program_config, build_serve, rehearse_serve = real.program_config, real.build_serve, real.rehearse_serve


def logits(params, config, tokens, rows):
    return real.logits(params, config, tokens, rows, wrong="FAULT")


loss_and_logits = functools.partial(reference.loss_and_logits, logits)
'''


def _run(root, cell, traced=0):
    spec = load_cell(cell, root)
    rec, correct, attempted, failed, notes = serve_cell.run_cell(spec, jax.devices()[:1], 2**31 + 63, 1.0, traced, time.perf_counter())
    return spec, rec, correct, attempted, failed, notes


def test_the_family_runs_a_toy_cell_to_correct_by_files_and_entries_alone(tmp_path):
    """The runner as it is: the latent pool, the states and the tails through the
    normal path, and the check's prompt against the reference."""
    root = make_tiny_root(str(tmp_path / "root"))
    cell = test_bm_hybrid._add_cell(root, "toyling", TOY)
    spec, rec, correct, attempted, failed, notes = _run(root, cell, traced=2)
    family = load_family("ling_hybrid", root)
    assert correct and attempted > 0 and failed == 0, notes
    assert notes["compiles_in_window"] == 0, "every rung and the decode step were compiled by warm()"
    assert 0 < notes["reference"]["logits_max_abs_diff_over_max"] < family.SERVE_LOGITS_TOLERANCE
    assert notes["reference"]["tolerance"] == family.SERVE_LOGITS_TOLERANCE
    counters = notes["session_counters"]           # the trace session read the engine's counters
    steps = counters["decode_steps"]
    assert steps > 0 and counters["latent_bytes_read"] > 0 and counters["moe_assignments"] > 0
    assert counters["kda_state_bytes_rw"] == steps * 2 * 4 * 6 * 4 * 16 * 16 * 4, "every slot's six states, read and written"
    rows = counters["moe_assignments"] // 4            # active rows x expert layers
    assert 0 < counters["route_rows_held_group"] <= rows and counters["moe_assignments_held"] <= counters["moe_assignments"]
    line = result_object(spec, rec, jax.devices()[:1], correct=correct, attempted=attempted, failed=failed, traced=2)
    assert {"serve_tokens_per_s", "setup_s", "decode_step_ms_p50.batch"} <= set(line["metrics"])
    assert line["metrics"]["serve_tokens_per_s"]["value"] > 0


# (a routing group swapped reads AT the logits' limit since that was set anew from its two readings, the family's file says
# why: ``tests/test_kda.py`` holds it to the long check's expert rows instead)
@pytest.mark.parametrize("fault", ["decay_after", "no_beta"])
def test_a_fault_in_the_reference_reads_not_correct(tmp_path, fault):
    root = make_tiny_root(str(tmp_path / "root"))
    cell = test_bm_hybrid._add_cell(root, "toyling_" + fault, dict(TOY, model="ling_hybrid_" + fault), WRAPPER.replace("FAULT", fault))
    _spec, _rec, correct, _attempted, _failed, notes = _run(root, cell)
    assert not correct
    assert notes["ledger"]["problems"] == [] and notes["compiles_in_window"] == 0
    assert notes["reference"]["logits_max_abs_diff_over_max"] > 2 * notes["reference"]["tolerance"]


# ------------------------------------------------- the real configuration file
def test_the_real_configuration_is_the_catalogs_row_cut_as_the_issue_says():
    spec = load_cell(CELL, REPO)
    c, family = spec.config, spec.family()
    assert spec.chips == 1 and spec.traffic_name == TRAFFIC and spec.traffic["kind"] == "closed_loop"
    assert {m["name"] for m in spec.end_to_end} == {"serve_tokens_per_s", "setup_s"}
    reduced = ["num_hidden_layers", "first_k_dense_replace", "num_experts", "vocab_size", "num_nextn_predict_layers",
               "expert_swiglu_limit_list", "share_expert_swiglu_limit_list"]
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(path):
        with open(path) as f:
            catalog = next(r for r in map(json.loads, f) if r["name"] == "Ling-3.0-flash")
        assert c["source"] == catalog["source_url"]
        assert {k: c[k] for k in catalog["config"] if k not in reduced} == {k: v for k, v in catalog["config"].items() if k not in reduced}
        assert c["published"] == {k: catalog["config"][k] for k in reduced}
        assert c["expert_swiglu_limit_list"] == catalog["config"]["expert_swiglu_limit_list"][1:8] == [0] * 7
        assert c["share_expert_swiglu_limit_list"] == catalog["config"]["share_expert_swiglu_limit_list"][1:8] == [0] * 7
    assert c["reduced"] == reduced and c["share"] == {"chips": 8, "of": ["num_experts", "vocab_size"], "index": 0}
    cut = {"num_hidden_layers": 7, "first_k_dense_replace": 1, "num_experts": 64, "vocab_size": 19648, "num_nextn_predict_layers": 0}
    assert {k: c[k] for k in cut} == cut
    widths = {"hidden_size": 2560, "num_attention_heads": 32, "head_dim": 128, "qk_head_dim": 192, "qk_nope_head_dim": 128,
              "qk_rope_head_dim": 64, "v_head_dim": 128, "kv_lora_rank": 512, "intermediate_size": 6144, "moe_intermediate_size": 768,
              "moe_shared_expert_intermediate_size": 768, "num_experts_per_tok": 8, "n_group": 8, "topk_group": 4,
              "short_conv_kernel_size": 4, "kda_lower_bound": -5, "q_lora_rank": None, "layer_group_size": 6}
    assert {k: c[k] for k in widths} == widths, "every width as published"
    assumed = c["assumed"]
    assert {k: assumed[k] for k in family.ASSUMED} == family.ASSUMED and assumed["first_layer"] == 1
    assert all(key in assumed for key in ("why_first_layer", "why_kda_rotary", "why_output_gate", "why_use_qk_norm", "why_kda_head_dims",
                                          "why_group_score", "why_max_window_layers", "left_out", "held", "init", "page_size", "pool_pages"))
    assert "ONE routing group a chip" in c["deployment"] and "share 0 of the first stage" in c["deployment"]
    assert c["serve"] == {"weight_dtype": "bfloat16", "state_dtype": "float32", "slots": 256, "positions_per_slot": 16384,
                          "page_size": 32, "pool_pages": 61440}
    assert 256 * (16384 // 32) * 4 == 512 << 10, "the page table, a scalar-prefetch operand: half of what 16 a page would need"
    others = [load_cell(w["name"], REPO).config.get("serve") or {} for w in load_benchmark(REPO)["workloads"] if w["name"] != CELL]
    assert (256, 16384) not in {(s.get("slots"), s.get("positions_per_slot")) for s in others}, "a run's family is found by its geometry"
    assert family.layer_plan(c) == ["kda", "kda", "kda", "kda", "mla", "kda", "kda"], "the source's layers 1 to 7: layer 5 is latent"
    assert family.layer_counts(c) == {"kda": 6, "mla": 1, "dense": 1, "expert": 6}
    # ISSUE 63's arithmetic, in millions of parameters and in GB
    M = 1e6
    assert round(family.kda_params(c) / M, 1) == 52.6 and round(family.mla_params(c) / M, 1) == 32.0
    assert round(family.shared_params(c) / M, 1) == 5.9 and round(family.expert_params(c) / M, 3) == 5.898
    assert round(family.dense_mlp_params(c) / M, 1) == 47.2 and round(64 * family.expert_params(c) * 2 / 1e9, 3) == 0.755
    beside = family.shared_params(c) + 2560 * 512            # the shared expert and the router: 7.2 M a layer beside the experts
    assert abs((5 * (family.kda_params(c) + beside) + family.mla_params(c) + beside) / 6 / M - 56.3) < 0.15, "the catalog's 'about 56M'"
    assert round(2 * 19648 * 2560 * 2 / 1e9, 2) == 0.20 and round(family.weight_bytes(c) / 1e9, 2) == 5.62
    serve = c["serve"]
    assert 32 * 128 * 128 * 4 == 2097152 and family.matrix_state_bytes(c, serve) == 256 * 2097152
    assert round(256 * 6 * 2097152 / 1e9, 2) == 3.22 and round(256 * 6 * 3 * 12288 * 2 / 1e9, 2) == 0.11
    assert family.state_bytes_per_slot(c, serve) == 6 * (2097152 + 3 * 12288 * 2) and round(family.state_bytes_per_slot(c, serve) / M, 1) == 13.0
    assert family.pool_bytes_per_position(c) == 1280 and family.latent_bytes_per_position(c) == 1152
    assert family.pool_pages(serve) * 32 == 122880 * 16 == 256 * 7680, "the issue's pool, in pages of 32: 7,680 positions a slot on the mean"
    assert round(family.pool_pages(serve) * 32 * 1280 / 1e9, 2) == 2.52
    assert round(family.cache_bytes(c, serve) / 1e9, 2) == 5.85
    assert round((family.weight_bytes(c) + family.cache_bytes(c, serve)) / 1e9, 1) == 11.5 and 11.47e9 / 16e9 > 0.70
    # a decode step at 256 slots and 3 k live positions a slot: half of what it moves is the delta rule's state
    moved = family.decode_step_bytes(c, serve, latent_positions_read=256 * 3000, experts_touched=6 * 64 * 0.98)
    state = 2 * 256 * family.state_bytes_per_slot(c, serve)
    assert 12.3e9 < moved < 13.3e9 and 0.49 < state / moved < 0.54 and family.kda_step_bytes(c, serve) == 2 * 256 * 2097152
    assert family.prefill_rungs(serve) == [128, 256, 512, 1024, 1536, 2048, 3072, 4096, 5120, 6144, 7168, 8192, 10240, 12288, 14336, 16384]


def test_what_the_program_allocates_is_what_the_family_counts():
    spec = load_cell(CELL, REPO)
    c, family = spec.config, spec.family()
    from vescale_tpu.models.ling_hybrid import init_params
    from vescale_tpu.serve.hybrid_engine import hybrid_cache_config, prefill_buckets

    cfg = family.program_config(c)
    assert (cfg.first_layer, cfg.latent_layers, cfg.experts_held, cfg.first_expert_held, cfg.num_experts, cfg.groups_held) == (
        1, (4,), 64, 0, 512, (0,))
    tree = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.key(0))
    assert sum(int(a.size) * a.dtype.itemsize for a in jax.tree_util.tree_leaves(tree)) == family.weight_bytes(c)
    assert sum(int(a.size) for a in jax.tree_util.tree_leaves(tree)) == family.param_count(c)
    assert tree["embed_tokens"]["embedding"].shape == (19648, 2560) and tree["lm_head"]["kernel"].shape == (2560, 19648)
    delta, latent = tree["layers_1"]["mixer"], tree["layers_4"]["mixer"]
    assert delta["qkv"].shape == (2560, 12288) and delta["f"].shape == (2560, 4096) and delta["A_log"].shape == (32,)
    assert delta["dt_bias"].shape == (4096,) and delta["beta"].shape == delta["gate"].shape == (2560, 32) and delta["conv"].shape == (4, 12288)
    assert latent["q"].shape == (2560, 32 * 192) and "q_a" not in latent and latent["gate"].shape == (2560, 32)
    assert latent["kv_a"].shape == (2560, 576) and latent["kv_b_k"].shape == (32, 128, 512)
    assert tree["layers_0"]["mlp"]["gate"].shape == (2560, 6144) and tree["layers_1"]["mlp"]["w_gate"].shape == (64, 2560, 768)
    assert tree["layers_1"]["mlp"]["router"].shape == (2560, 512) and tree["layers_1"]["mlp"]["router_bias"].shape == (512,)
    kc = hybrid_cache_config(cfg, num_slots=256, page_size=32, pages_per_slot=512, num_pages=61440)
    assert kc == family._cache_config(cfg, c["serve"])
    assert (kc.layers, kc.kv_heads, kc.head_dim, kc.latent, kc.max_seq_len, kc.pool_pages) == (1, 1, 640, True, 16384, 61440)
    assert [(name, layers, tuple(shape)) for name, layers, shape, _dt in kc.slot_state] == [
        ("kda_state", 6, (32, 128, 128)), ("kda_conv", 6, (3, 12288))]
    state = sum(layers * int(np.prod(shape)) * np.dtype(dt).itemsize for _n, layers, shape, dt in kc.slot_state)
    assert state == family.state_bytes_per_slot(c, c["serve"])
    assert kc.layers * kc.pool_pages * kc.page_size * kc.head_dim * 2 + 256 * state == family.cache_bytes(c, c["serve"])
    assert prefill_buckets(cfg.prefill_chunk, kc.max_seq_len) == family.prefill_rungs(c["serve"])


def test_the_traffic_file_holds_the_issues_parameters_and_one_order():
    spec = load_cell(CELL, REPO)
    t = spec.traffic
    assert {k: t[k] for k in ("kind", "clients", "first_wave", "lead_in_s", "pool", "pairing_seed", "order_seed", "max_total")} == {
        "kind": "closed_loop", "clients": 320, "first_wave": 256, "lead_in_s": 20, "pool": 64, "pairing_seed": 0, "order_seed": 0,
        "max_total": 16384}
    assert t["prompt_len"] == {"dist": "lognormal", "median": 1024, "sigma": 1.2, "min": 128, "max": 8192}
    assert t["output_len"] == {"dist": "lognormal", "median": 4096, "sigma": 0.5, "min": 1024, "max": 8192}
    assert t["first_wave"] == spec.config["serve"]["slots"] and t["max_total"] == spec.config["serve"]["positions_per_slot"]
    vocab = spec.config["vocab_size"]
    a, b = (trafficgen.closed_loop_requests(t, seed, vocab) for seed in (2**31 + 5, 12345))
    lengths = lambda plan: [(len(r.prompt), r.max_new_tokens) for r in plan]
    assert len(a) == 64 and lengths(a) == lengths(b), "every seed sends the same requests in the same order"
    assert all(x.prompt != y.prompt for x, y in zip(a, b)), "the token ids are the seed's"
    assert max(max(r.prompt) for r in a) < vocab
    assert trafficgen.first_wave_done_shares(t, 1) == trafficgen.first_wave_done_shares(t, 2**31 + 7)
    prompts, outputs = np.array([len(r.prompt) for r in a]), np.array([r.max_new_tokens for r in a])
    assert prompts.min() >= 128 and prompts.max() == 8192 and outputs.min() >= 1024 and outputs.max() == 8192
    assert (prompts + outputs).max() <= 16384 and 1600 < prompts.mean() < 2000 and 4300 < outputs.mean() < 4700
    assert 2 <= (prompts == 8192).sum() <= 4, "the long documents: about 4% of prompts sit at the clip"
    # a request reserves prompt + output at admission: the pool holds 256 of the mean, time-weighted (long outputs stay longer)
    assert 256 * (prompts.mean() + (outputs.astype(float) ** 2).mean() / outputs.mean()) < 61440 * 32


# ------------------------------------------------------------------ the readers
def test_the_table_of_shapes_leaves_none_of_the_decode_programs_large_ops_under_other():
    """The decode program traced on the CPU at the cell's shapes (shapes, no
    arrays; the XLA legs).  What stays under ``other`` is of the residual
    stream's own size (its norms and sums): nothing that reads a weight, the
    pool, a state or a tail."""
    spec = load_cell(CELL, REPO)
    c, family = spec.config, spec.family()
    _sizes, programs = family.rehearse_serve(spec.name, c, c["serve"], jax.devices())
    title, lowered = programs[-1]
    assert "decode step, 256 slots x 16384 positions" in title and len(programs) == 17
    signatures = family.mechanism_signatures(c, c["serve"])
    by, largest_other = {}, 0
    for nbytes, text in test_bm_falconh1._ops_as_the_trace_names_them(lowered.as_text(dialect="hlo")):
        mechanism = family.mechanism_of(text, signatures)
        by[mechanism] = by.get(mechanism, 0) + nbytes
        if mechanism == "other":
            largest_other = max(largest_other, nbytes)
    stream = 256 * 2560 * 4
    assert largest_other <= 3.5 * stream, "an op of the stream reads two of its size and writes one (a select a mask beside them)"
    assert {"head", "routed", "kda", "mla", "mlp", "other"} <= set(by) and by["other"] < 0.02 * sum(by.values()), by
    of = lambda text, table=signatures: family.mechanism_of(text, table)
    assert of("%kda_step.3 = (f32[6,256,32,128,128]{4,3,2,1,0}, f32[256,32,128]{2,1,0}) custom-call(s32[1]{0} %l, f32[256,2,128,128]{3,2,1,0} %cols)") == "kda"
    assert of("%paged_decode_latent.1 = f32[256,32,640]{2,1,0} custom-call(s32[1]{0} %l, s32[256,512]{1,0} %t, bf16[1,61440,32,640]{3,2,1,0} %k)") == "mla"
    assert of("%fusion.4 = bf16[256,12288]{1,0} fusion(bf16[2560,12288]{1,0} %qkv, f32[256,2560] %u)") == "kda"
    assert of("%fusion.5 = f32[256,4096]{1,0} fusion(bf16[2560,4096]{1,0} %f, f32[256,2560] %u)") == "kda"
    assert of("%fusion.6 = f32[256,2560]{1,0} fusion(bf16[4096,2560]{1,0} %o, f32[256,4096] %y)") == "kda", "both mixers' W_o: five of six are the delta rule's"
    assert of("%fusion.7 = f32[256,32,192]{2,1,0} fusion(bf16[2560,32,192]{2,1,0} %q, bf16[256,2560] %u)") == "mla"
    assert of("%fusion.8 = f32[256,576]{1,0} fusion(bf16[2560,576]{1,0} %kv_a, f32[256,2560] %u)") == "mla"
    assert of("%fusion.9 = f32[256,512]{1,0} fusion(f32[2560,512]{1,0} %router, f32[256,2560] %h)") == "routed"
    assert of("%fusion.10 = f32[64,128,768]{2,1,0} fusion(bf16[64,2560,768]{2,1,0} %w_gate, bf16[64,128,2560] %xp)") == "routed"
    assert of("%fusion.11 = f32[256,768]{1,0} fusion(bf16[2560,768]{1,0} %shared_gate, f32[256,2560] %h)") == "routed"
    assert of("%fusion.12 = f32[256,6144]{1,0} fusion(bf16[2560,6144]{1,0} %mlp_gate, bf16[2560,6144]{1,0} %mlp_up, f32[256,2560] %h)") == "mlp", \
        "the dense layer's gate and the latent mixer's W_q are one shape (2560 x 6144): that one product reads as the MLP's"
    assert of("%fusion.13 = f32[256,2560]{1,0} fusion(bf16[6144,2560]{1,0} %mlp_down, f32[256,6144] %h)") == "mlp"
    assert of("%fusion.14 = f32[256,19648]{1,0} fusion(bf16[2560,19648]{1,0} %lm_head, f32[256,2560] %x)") == "head"
    assert of("%fusion.15 = f32[256,2560]{1,0} fusion(f32[256,2560] %x)") == "other"
    rung = family.mechanism_signatures(c, c["serve"], 2048)
    assert of("%kda_chunk.3 = (f32[2048,4096]{1,0}, f32[32,128,128]{2,1,0}) custom-call(%a, %b, %c)", rung) == "kda"
    assert of("%mla_flash_fwd.3 = (bf16[32,2048,128]{2,1,0}, f32[32,2048,1]{2,1,0}) custom-call(%a, %b, %c)", rung) == "mla"
    assert of("%grouped_swiglu.2 = f32[17408,2560]{1,0} custom-call(%a, %b, %c)", rung) == "routed"
    assert of("%fusion.2 = f32[2048,2560]{1,0} fusion(f32[2048,2560] %x)", rung) == "other"


def test_the_readers_arithmetic_on_a_recorded_session_each_roofline_by_hand():
    """Microseconds: two decode launches and one prefill of the 2,048 rung, their
    programs on the ``XLA Modules`` line and the ops inside them."""
    reader = family_readers(NAMES, OWN)
    spec = load_cell(CELL, REPO)
    c, family = spec.config, spec.family()
    steps, slots, live = 10, 256, 256 * 3000
    counters = {"decode_steps": steps, "kda_state_bytes_rw": steps * 2 * 6 * 256 * 2097152, "latent_bytes_read": steps * live * 1280,
                "route_rows_held_group": steps * 6 * 120, "moe_assignments": steps * 6 * 240 * 8, "moe_assignments_held": steps * 6 * 240,
                "moe_busiest_expert_tokens": steps * 6 * 9, "moe_layer_steps": steps * 6, "moe_expert_slots": steps * 6 * 64,
                "moe_experts_touched": steps * 6 * 60, "prefill_tokens_real": 1800}
    STEP = "%kda_step.3 = (f32[6,256,32,128,128]{4,3,2,1,0}, f32[256,32,128]{2,1,0}) custom-call(s32[1]{0} %l, f32[256,2,128,128]{3,2,1,0} %cols)"
    QKV = "%fusion.4 = bf16[256,12288]{1,0} fusion(bf16[2560,12288]{1,0} %qkv, f32[256,2560] %u)"
    POOL = "%paged_decode_latent.1 = f32[256,32,640]{2,1,0} custom-call(s32[1]{0} %l, s32[256,512]{1,0} %t, bf16[1,61440,32,640]{3,2,1,0} %k)"
    EXPERTS = "%fusion.10 = f32[64,128,768]{2,1,0} fusion(bf16[64,2560,768]{2,1,0} %w_gate, bf16[64,128,2560] %xp)"
    HEAD = "%fusion.14 = f32[256,19648]{1,0} fusion(bf16[2560,19648]{1,0} %lm_head, f32[256,2560] %x)"
    NORM = "%fusion.15 = f32[256,2560]{1,0} fusion(f32[256,2560] %x)"
    CHUNK = "%kda_chunk.3 = (f32[2048,4096]{1,0}, f32[32,128,128]{2,1,0}) custom-call(%a, %b, %c)"
    FLASH = "%mla_flash_fwd.3 = (bf16[32,2048,128]{2,1,0}, f32[32,2048,1]{2,1,0}) custom-call(%a, %b, %c)"
    GROUPED = "%grouped_swiglu.2 = f32[17408,2560]{1,0} custom-call(%a, %b, %c)"

    def decode(t0):      # 20,000 us: two steps of the delta rule (the reader takes the MEAN of a call), its projection, the rest
        return [(t0, t0 + 1500, STEP), (t0 + 1500, t0 + 1600, QKV), (t0 + 1600, t0 + 3300, STEP), (t0 + 3300, t0 + 4800, POOL),
                (t0 + 4800, t0 + 12800, EXPERTS), (t0 + 12800, t0 + 18800, HEAD), (t0 + 18800, t0 + 20000, NORM)]

    prefill = [(50000, 53000, CHUNK), (53000, 53400, FLASH), (53400, 54000, GROUPED)]
    modules = [(1000, 21000, "jit_decode(1)"), (25000, 45000, "jit_decode(1)"), (50000, 54000, "jit_prefill(9)")]
    host = [(900, 950, "vs.serve-decode.launch", {"launch": 1}), (24000, 24050, "vs.serve-decode.launch", {"launch": 2}),
            (49000, 49050, "vs.serve-prefill.launch", {"launch": 3, "rung": 2048, "slot": 5})]
    ops = decode(1000) + decode(25000) + prefill + [(55000, 55400, POOL)]                # the last outside any program
    run = types.SimpleNamespace(traffic_kind="closed_loop", slots=256, padded_prompt_len=16384, device_kind="TPU v5 lite",
                                session=types.SimpleNamespace(profile=_trace(ops, modules, host)),
                                _session_reduced={"counters": counters})
    got = reader.read(run)
    assert set(got) == set(NAMES)
    rate, peak = 819e9, 197e12
    assert got["kda_state_gb_per_step.batch"] == pytest.approx(2 * 6 * 256 * 2097152 / 1e9) == pytest.approx(6.442, abs=1e-3)
    assert got["held_group_row_share.batch"] == pytest.approx(100 * 120 / 240)
    assert got["experts_load_imbalance.batch"] == pytest.approx(9 / (240 / 64))
    # ONE call's state, read and written, over the HBM rate, against the MEAN of the calls (1,600 us)
    assert got["kda_step_roofline.batch"] == pytest.approx(100 * (2 * 256 * 2097152 / rate) / 1600e-6)
    assert 0 < got["kda_step_roofline.batch"] < 100
    moved = family.decode_step_bytes(c, c["serve"], latent_positions_read=live, experts_touched=6 * 60)
    assert got["step_hbm_roofline_share.batch"] == pytest.approx(100 * (moved / rate) / 20000e-6)
    # the ops inside all three programs, 44,000 us
    assert got["kda_device_share.batch"] == pytest.approx(100 * (2 * 3300 + 3000) / 44000)
    assert got["mla_device_share.batch"] == pytest.approx(100 * (2 * 1500 + 400) / 44000)
    # six mixers' chunks of the rung: the operations as the mathematics has them, which decide against the bytes
    chunk_flops = 2 * 32 * (2048 // 128) * (4 * 128 * 128 * 128 + 2 * 128 * 128 * 128)
    chunk_bytes = 4 * (2048 * (5 * 4096 + 32) + 32 * 128 * 128)
    assert (family.kda_chunk_flops(c, 2048), family.kda_chunk_bytes(c, 2048)) == (chunk_flops, chunk_bytes)
    assert chunk_bytes / rate > chunk_flops / peak, "at the MXU's peak the mathematics would take less than reading its operands"
    assert got["kda_chunk_roofline.batch"] == pytest.approx(100 * 6 * (chunk_bytes / rate) / 3000e-6)
    flash_flops, flash_bytes = 32 * 2 * (192 + 128) * 2048 * 2048 / 2, 2 * 32 * (192 + 128) * 2048 * 2
    assert got["mla_prefill_roofline.batch"] == pytest.approx(100 * max(flash_flops / peak, flash_bytes / rate) / 400e-6)
    assert all(0 < got[name] for name in got)
    assert all(got[name] <= 100 for name in got if "roofline" in name)
    # a program without the model's counters (this PR's parent; another family's run) leaves them all out
    run._session_reduced = {"counters": {"decode_steps": 5, "latent_bytes_read": 7, "moe_assignments": 9}}
    assert reader.read(run) == {}
    run._session_reduced = {"counters": dict(counters, decode_steps=0)}
    assert reader.read(run) == {}
    other = types.SimpleNamespace(traffic_kind="closed_loop", slots=64, padded_prompt_len=16384, device_kind="TPU v5 lite",
                                  session=run.session, _session_reduced={"counters": counters})
    assert reader.read(other) == {}
    assert reader.read(types.SimpleNamespace(traffic_kind="open_loop")) == {}
    assert reader.read(types.SimpleNamespace(traffic_kind="closed_loop", session=None)) == {}


@pytest.mark.parametrize("path", ["benchmark/families/ling_hybrid.py", "benchmark/layer_metrics/ling_serve_longgen.py"])
def test_neither_the_family_nor_its_reader_imports_the_programs_modules_at_import(path, monkeypatch):
    """Every run of every cell executes every reader, and ``_family`` loads each
    family's file to learn its geometry: under these files the parent, whose
    program has no such modules, must still run its own cells.  The modules are
    wanted only where the program is built."""
    import importlib.util
    import sys

    import vescale_tpu.models

    names = {"vescale_tpu.models.ling_hybrid", "vescale_tpu.models.kda", "vescale_tpu.kernels.kda"}

    class NoSuchModule:
        @staticmethod
        def find_spec(fullname, path=None, target=None):
            if fullname in names:
                raise ModuleNotFoundError(f"No module named {fullname!r}", name=fullname)

    for name in names:
        monkeypatch.delitem(sys.modules, name, raising=False)
    for attr in ("ling_hybrid", "kda"):
        monkeypatch.delattr(vescale_tpu.models, attr, raising=False)
    monkeypatch.setattr(sys, "meta_path", [NoSuchModule] + sys.meta_path)
    spec = importlib.util.spec_from_file_location("_alone_" + os.path.basename(path)[:-3], os.path.join(REPO, path))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    assert not names & set(sys.modules)
    if hasattr(module, "read"):
        mimo = types.SimpleNamespace(traffic_kind="closed_loop", slots=256, padded_prompt_len=8192, device_kind="TPU v5 lite",
                                     session=None, _session_reduced={"counters": {"decode_steps": 5}})
        assert module.read(mimo) == {}
    else:
        assert module.layer_counts(load_cell(CELL, REPO).config)["kda"] == 6, "the counts from shapes need no program"
        with pytest.raises(ModuleNotFoundError):
            module.program_config(load_cell(CELL, REPO).config)


def test_the_entries_of_benchmark_json_name_the_cell_behind_what_was_there():
    bench = load_benchmark(REPO)
    cells, configs = [w["name"] for w in bench["workloads"]], [c["name"] for c in bench["configs"]]
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    (config,) = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert cells.index(CELL) == cells.index("phi4miniflash_serve_reasoning") + 1 == 12, "new entries go behind what was there"
    assert configs.index(CONFIG) == configs.index("phi-4-mini-flash-reasoning.serve-L32") + 1
    assert cell["config"] == CONFIG and cell["traffic"] == TRAFFIC and cell["chips"] == 1
    assert config["file"] == f"benchmark/configs/{CONFIG}.json" and config["reduced"] == load_cell(CELL, REPO).config["reduced"]
    names = [m["name"] for m in bench["per_layer"]]
    assert names[102:107] == OWN and names.index("prefill_cross_rows_share.batch") == 101, "five entries behind the 102 that were there"
    entries = declared_entries(CELL, NAMES)
    assert all(m["moves"] == "serve_tokens_per_s" for m in entries)
    assert all(m["workloads"] == [CELL] for m in entries if m["name"] in OWN)
    assert all(CELL in m["workloads"][1:] for m in entries if m["name"] in SHARED)
    assert {m["layer"] for m in entries if m["name"] in OWN} == {"Delta-rule mixer", "Hybrid cache", "Expert layer"}
    assert {m["name"]: m["source"] for m in entries if m["name"] in OWN} == {
        "kda_state_gb_per_step.batch": "program_counter", "held_group_row_share.batch": "program_counter",
        "kda_device_share.batch": "device_trace", "kda_step_roofline.batch": "device_trace", "kda_chunk_roofline.batch": "device_trace"}
    assert callable(FAMILY.layer_readings) and FAMILY.LAYER_COUNTERS == {"kda_state_bytes_rw", "latent_bytes_read", "route_rows_held_group"}
    joined = [m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", ())]
    assert sorted(joined) == sorted(GENERIC + NAMES) and not set(UNLISTED + HELD_BY_AN_OLDER_TEST) & set(joined)
    assert not set(n.rsplit(".", 1)[0] for n in HELD_BY_AN_OLDER_TEST) & set(FAMILY.layer_readings.__code__.co_consts), \
        "a family returns what lists its cell"
    assert len(cells) >= 13 and len(names) >= 107 and sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    (e2e,) = [m for m in bench["end_to_end"] if m["name"] == "serve_tokens_per_s"]
    assert CELL in e2e["workloads"] and e2e["workloads"].index(CELL) == e2e["workloads"].index("phi4miniflash_serve_reasoning") + 1
