"""The ``deepseek_v2`` family in the benchmark: a toy configuration and cell
added to a temporary root by files and entries alone (as ``test_bm_hybrid.py``
does for Granite), run through ``serve_cell`` to ``correct``, and to not
correct with one sign turned in the reference; the real configuration file
against the catalog's numbers and the issue's bytes; the readers' arithmetic on
a made-up session.

Two tables of the tests that were here before name the cells they knew; this
file tells them of the new one AT IMPORT (every worker imports every test
module before it runs one, and they are imported by base name, so this reaches
the same module objects): ``test_bm_session.TINY_OF`` gets the new cell's toy
stand-in, and ``test_bm_hybrid``'s last test, which holds that Granite's
entries are the LAST of ``BENCHMARK.json`` (true when PR 29 wrote it), reads
the benchmark as it stood before this PR's entries were appended."""

import json
import os
import time
import types

import jax
import pytest

import test_bm_hybrid
import test_bm_session
from bm_fixtures import REPO, make_tiny_root

from benchmark import serve_cell
from benchmark.harness import discover, result_object
from benchmark.spec import load_benchmark, load_cell, load_family

CELL = "deepseekv2_serve_longctx"
CONFIG = "deepseek-v2.serve-L5-ep4"
NEW_METRICS = ["mla_device_share.batch", "routed_device_share.batch", "mla_decode_roofline.batch",
               "mla_prefill_roofline.batch", "latent_gb_per_step.batch", "routed_held_share.batch",
               "routed_load_imbalance.batch", "mla_step_hbm_roofline_share.batch"]

test_bm_session.TINY_OF.setdefault(CELL, "tiny_batch")


def _before_this_pr(root):
    """``BENCHMARK.json`` without what PR 34 appended (its configuration, its cell, its metrics, its list members)."""
    bench = load_benchmark(root)
    bench["configs"] = [c for c in bench["configs"] if c["name"] != CONFIG]
    bench["workloads"] = [w for w in bench["workloads"] if w["name"] != CELL]
    for group in ("end_to_end", "per_layer"):
        bench[group] = [dict(m, workloads=[w for w in m["workloads"] if w != CELL]) if "workloads" in m else m
                        for m in bench[group] if m["name"] not in NEW_METRICS]
    return bench


test_bm_hybrid.load_benchmark = _before_this_pr

ROPE = {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707, "mscale_all_dim": 0.707,
        "original_max_position_embeddings": 4096, "type": "yarn"}
# a dense layer and two expert layers; 8 experts in 4 groups, 2 groups and 3 experts kept, 4 held (groups 0 and 1)
TOY = {"source": "tests only", "model": "deepseek_v2", "vocab_size": 96, "hidden_size": 64, "num_hidden_layers": 3,
       "first_k_dense_replace": 1, "intermediate_size": 96, "moe_intermediate_size": 32, "moe_layer_freq": 1,
       "n_shared_experts": 2, "n_routed_experts": 4, "num_experts_per_tok": 3, "n_group": 4, "topk_group": 2,
       "routed_scaling_factor": 16, "norm_topk_prob": False, "scoring_func": "softmax",
       "topk_method": "group_limited_greedy", "num_attention_heads": 4, "num_key_value_heads": 4,
       "attention_bias": False, "q_lora_rank": 32, "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
       "v_head_dim": 16, "rope_theta": 10000, "rope_scaling": ROPE, "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
       "reduced": ["n_routed_experts", "vocab_size"], "published": {"n_routed_experts": 8, "vocab_size": 192},
       "share": {"chips": 2, "of": ["n_routed_experts", "vocab_size"]}, "assumed": {}, "deployment": "none: a toy",
       "kind": "serve",
       "serve": {"weight_dtype": "bfloat16", "slots": 4, "positions_per_slot": 64, "page_size": 8, "prefill_chunk": 8}}

WRAPPER = '''"""The deepseek_v2 family with one sign turned in its reference's rotary (tests only)."""
import functools

from benchmark import reference
from benchmark.families import deepseek_v2 as real

SERVE_LOGITS_TOLERANCE = real.SERVE_LOGITS_TOLERANCE
program_config, build_serve, rehearse_serve = real.program_config, real.build_serve, real.rehearse_serve


def logits(params, config, tokens, rows):
    rotate = real._rotate
    real._rotate = lambda x, cos, sin, **kw: rotate(x, cos, -sin, **kw)     # turns the other way
    real.attention.clear_cache()
    try:
        return real.logits(params, config, tokens, rows)
    finally:
        real._rotate = rotate
        real.attention.clear_cache()


loss_and_logits = functools.partial(reference.loss_and_logits, logits)
'''


def _run(root, cell, traced=0):
    spec = load_cell(cell, root)
    rec, correct, attempted, failed, notes = serve_cell.run_cell(spec, jax.devices()[:1], 2**31 + 29, 1.0, traced,
                                                                 time.perf_counter())
    return spec, rec, correct, attempted, failed, notes


def test_the_family_runs_a_toy_cell_to_correct_by_files_and_entries_alone(tmp_path):
    root = make_tiny_root(str(tmp_path / "root"))
    cell = test_bm_hybrid._add_cell(root, "toymla", TOY)
    spec, rec, correct, attempted, failed, notes = _run(root, cell, traced=2)
    family = load_family("deepseek_v2", root)
    assert correct and attempted > 0 and failed == 0, notes
    assert notes["compiles_in_window"] == 0, "every rung and the decode step were compiled by warm()"
    assert 0 < notes["reference"]["logits_max_abs_diff_over_max"] < family.SERVE_LOGITS_TOLERANCE
    assert notes["reference"]["tolerance"] == family.SERVE_LOGITS_TOLERANCE
    counters = notes["session_counters"]           # the trace session read the engine's counters
    assert counters["decode_steps"] > 0 and counters["latent_bytes_read"] > 0 and counters["moe_assignments"] > 0
    assert counters["prefill_attn_flops"] > 0 and counters["moe_groups_kept_here"] >= 0
    assert counters["prefill_tokens_padded"] == counters["prefill_bucket_tokens"] >= counters["prefill_tokens_real"]
    line = result_object(spec, rec, jax.devices()[:1], correct=correct, attempted=attempted, failed=failed, traced=2)
    assert {"serve_tokens_per_s", "setup_s", "decode_step_ms_p50.batch"} <= set(line["metrics"])


def test_one_sign_turned_in_the_reference_reads_not_correct(tmp_path):
    root = make_tiny_root(str(tmp_path / "root"))
    cell = test_bm_hybrid._add_cell(root, "toymla_turned", dict(TOY, model="deepseek_v2_turned"), WRAPPER)
    _spec, _rec, correct, _attempted, _failed, notes = _run(root, cell)
    assert not correct
    assert notes["ledger"]["problems"] == [] and notes["compiles_in_window"] == 0
    assert notes["reference"]["logits_max_abs_diff_over_max"] > 5 * notes["reference"]["tolerance"]


# ------------------------------------------------- the real configuration file
def test_the_real_configuration_is_the_catalogs_row_cut_as_the_issue_says():
    spec = load_cell(CELL, REPO)
    c, family = spec.config, spec.family()
    assert spec.chips == 1 and spec.traffic["kind"] == "closed_loop"
    assert {m["name"] for m in spec.end_to_end} == {"serve_tokens_per_s", "setup_s"}
    traffic = {k: spec.traffic[k] for k in ("clients", "lead_in_s", "pool", "first_wave", "pairing_seed", "max_total")}
    assert traffic == {"clients": 40, "lead_in_s": 12.0, "pool": 64, "first_wave": 32, "pairing_seed": 0, "max_total": 8192}
    assert spec.traffic["prompt_len"] == {"dist": "lognormal", "median": 4604, "sigma": 1.0, "min": 256, "max": 7680}
    assert spec.traffic["output_len"] == {"dist": "lognormal", "median": 110, "sigma": 1.0, "min": 8, "max": 512}
    # every number of the catalog's config under its key, but for the three reduced
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        catalog = next(r for r in map(json.loads, f) if r["name"] == "DeepSeek-V2") if os.path.exists(f.name) else None
    if catalog is not None:
        assert c["source"] == catalog["source_url"]
        assert {k: v for k, v in catalog["config"].items() if c[k] != v} == \
            {"num_hidden_layers": 60, "n_routed_experts": 160, "vocab_size": 102400} == c["published"]
    assert c["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert (c["num_hidden_layers"], c["n_routed_experts"], c["vocab_size"]) == (5, 40, 25600)
    assert c["share"] == {"chips": 4, "of": ["n_routed_experts", "vocab_size"], "index": 0}
    # the floors of the model-configs guide: four layers after the dense one, 8 experts, an eighth of the vocabulary
    assert c["num_hidden_layers"] - c["first_k_dense_replace"] >= 4 and c["n_routed_experts"] >= 8
    assert 8 * c["vocab_size"] >= c["published"]["vocab_size"]
    cfg = family.program_config(c)
    assert (cfg.num_experts, cfg.experts_held, cfg.first_expert_held, cfg.groups_held) == (160, 40, 0, (0, 1))
    assert (cfg.latent_row, cfg.cache_row, cfg.qk_head_dim) == (576, 640, 192)
    # ISSUE 34's table, in millions of parameters
    M = 1e6
    assert round(family.attention_params(c) / M, 1) == 149.2 and round(family.expert_params(c) / M, 2) == 23.59
    assert round(family.shared_and_router_params(c) / M, 1) == 48.0 and round(family.dense_mlp_params(c) / M, 1) == 188.7
    assert round(family.weight_bytes(c) / 1e9, 2) == 10.33
    assert family.latent_bytes_per_position(c) == 5 * 1152
    serve = c["serve"]
    assert (serve["slots"], serve["positions_per_slot"], serve["page_size"]) == (32, 8192, 16)
    assert round(32 * 8192 * family.latent_bytes_per_position(c) / 1e9, 2) == 1.51
    moved = family.decode_step_bytes(c, serve, latent_pages_read_per_layer=32 * 5000 / 16)
    assert 11.2e9 < moved < 11.3e9, "10.33 GB of weights, 0.92 GB of live latent rows, the logits"
    assert moved - family.decode_step_bytes(c, serve, latent_pages_read_per_layer=32 * 5000 / 16, experts_touched=159) \
        == 2 * family.expert_params(c)
    assert family.mla_decode_flops_per_position(c) == 278528 and family.mla_prefill_attention_flops(c, 8192) == 128 * 640 * 8192 ** 2 / 2
    assert round(family.prefill_matmul_flops_per_token(c) / 1e9, 2) == 2.54      # ISSUE 34 reckoned 2.52, without the routers


def test_what_the_program_allocates_is_what_the_family_counts():
    spec = load_cell(CELL, REPO)
    c, family = spec.config, spec.family()
    from vescale_tpu.models.deepseek_v2 import init_params
    from vescale_tpu.serve.hybrid_engine import hybrid_cache_config, prefill_buckets

    cfg = family.program_config(c)
    tree = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.key(0))
    assert sum(int(a.size) * a.dtype.itemsize for a in jax.tree_util.tree_leaves(tree)) == family.weight_bytes(c)
    kc = hybrid_cache_config(cfg, num_slots=32, page_size=16, pages_per_slot=512)
    assert kc.latent and kc.slot_state == () and (kc.layers, kc.kv_heads, kc.head_dim) == (5, 1, 640)
    assert prefill_buckets(cfg.prefill_chunk, kc.max_seq_len) == family.prefill_rungs(c["serve"])


# ------------------------------------------------------------------ the readers
def _fake_profile(host, device):
    event = lambda a, b, n: types.SimpleNamespace(start_ns=a, duration_ns=b - a, name=n)
    line = lambda name, evs: types.SimpleNamespace(name=name, events=[event(*e) for e in evs])
    return types.SimpleNamespace(planes=[
        types.SimpleNamespace(name="/host:CPU", lines=[line("python", host)]),
        types.SimpleNamespace(name="/device:TPU:0", lines=[line("XLA Ops", device)])])


def test_the_table_of_shapes_names_the_mechanism_of_ops_the_chip_showed():
    """Instruction texts as my chip runs' op tables printed them (PERF.md, section 5, PR 34)."""
    spec = load_cell(CELL, REPO)
    family = spec.family()
    sig = family.mechanism_signatures(spec.config, spec.config["serve"])
    of = lambda text: family.mechanism_of(text, sig)
    assert of("%mla_flash_fwd.5 = bf16[128,8192,128]{2,1,0} custom-call(bf16[128,8192,192] %q)") == "mla"
    assert of("%paged_decode_latent.5 = f32[32,128,512]{2,1,0} custom-call(s32[1] %c, s32[32] %l)") == "mla"
    assert of("%fusion.32 = f32[32,5120]{1,0} fusion(bf16[40,1536,5120]{2,1,0} %w_down, f32[32,40]{0,1} %g)") == "routed"
    assert of("%ragged-dot-none.11 = f32[49152,1536]{1,0} custom-call(s32[1] %a, s32[41] %b)") == "routed"
    assert of("%broadcast_select_fusion.7 = f32[49152,5120]{1,0} fusion(f32[49152,5120] %r, pred[49152] %i)") == "routed"
    assert of("%convolution_convert_fusion.4 = bf16[128,192,8192]{2,1,0} fusion(bf16[1536,128,192] %b, f32[8192,1536] %x)") == "mla"
    assert of("%fusion.86 = f32[8192,3072]{1,0} fusion(bf16[5120,3072]{1,0} %shared_gate, f32[8192,5120] %h)") == "shared"
    assert of("%fusion.88 = f32[8192,12288]{1,0} fusion(bf16[5120,12288]{1,0} %gate, f32[8192,5120] %h)") == "mlp"
    assert of("%fusion.185 = f32[32,25600]{1,0} fusion(bf16[5120,25600]{1,0} %lm_head, f32[32,5120] %x)") == "head"
    assert of("%copy-done.71 = bf16[5120]{0} copy-done((bf16[5120]{0}, bf16[5120]{0}, u32[]) %copy-start.7)") == "other"


def test_the_readers_arithmetic_on_a_made_up_session():
    (reader,) = [m for m in discover(os.path.join(REPO, "benchmark", "layer_metrics")) if NEW_METRICS[0] in m.METRICS]
    assert list(reader.METRICS) == NEW_METRICS
    spec = load_cell(CELL, REPO)
    c, family = spec.config, spec.family()
    steps, live = 10, 32 * 5000                      # every slot at 5,000 positions, ten steps
    counters = {"decode_steps": steps, "moe_assignments": 32 * 6 * 4 * steps, "moe_assignments_held": 1900,
                "moe_busiest_expert_tokens": 200, "moe_expert_slots": 40 * 4 * steps, "moe_layer_steps": 4 * steps,
                "moe_experts_touched": 1000, "latent_bytes_read": live * 1280 * 5 * steps,
                "prefill_attn_flops": int(5 * family.mla_prefill_attention_flops(c, 8192)), "moe_groups_kept_here": 2400}
    profile = _fake_profile(
        host=[(0, 1000, "vs.serve-decode"), (2000, 3000, "vs.serve-decode"), (4000, 9000, "vs.serve-prefill")],
        device=[(10, 310, "%paged_decode_latent.5 = f32[32,128,512]{2,1,0} custom-call(s32[1] %c)"),                   # mla 300
                (400, 900, "%fusion.32 = f32[32,5120]{1,0} fusion(bf16[40,1536,5120]{2,1,0} %w_down, f32[32,40] %g)"),  # routed 500
                (2100, 2200, "%copy-done.2 = bf16[5120]{0} copy-done(bf16[5120] %x)"),                                 # other 100
                (4100, 8100, "%mla_flash_fwd.5 = bf16[128,8192,128]{2,1,0} custom-call(bf16[128,8192,192] %q)"),       # mla 4000
                (8200, 8300, "%fusion.86 = f32[8192,3072]{1,0} fusion(bf16[5120,3072]{1,0} %g, f32[8192,5120] %h)"),    # shared 100
                (9500, 9900, "%paged_decode_latent.6 = f32[32,128,512]{2,1,0} custom-call(s32[1] %c)")])               # outside any call
    run = types.SimpleNamespace(traffic_kind="closed_loop", slots=32, padded_prompt_len=8192, device_kind="TPU v5 lite",
                                session=types.SimpleNamespace(profile=profile),
                                _session_reduced={"counters": counters, "decode_device_ms": [16.0, 18.0, 20.0]})
    got = reader.read(run)
    assert set(got) == set(NEW_METRICS)
    assert got["latent_gb_per_step.batch"] == pytest.approx(live * 1280 * 5 / 1e9)
    assert got["routed_held_share.batch"] == pytest.approx(100 * 1900 / 7680)
    assert got["routed_load_imbalance.batch"] == pytest.approx((200 / 40) / (1900 / 1600))
    moved = family.decode_step_bytes(c, c["serve"], latent_pages_read_per_layer=live / 16, experts_touched=100.0)
    assert got["mla_step_hbm_roofline_share.batch"] == pytest.approx(100 * moved / (18.0e-3 * 819e9))
    assert got["mla_device_share.batch"] == pytest.approx(100 * 4300 / 5000)
    assert got["routed_device_share.batch"] == pytest.approx(100 * 500 / 5000)
    # the decode attention: the larger of its bytes over the HBM rate and its operations over the MXU peak
    positions = live * 5 * steps
    must = max(positions * 1152 / 819e9, positions * 278528 / 197e12)
    assert must == positions * 278528 / 197e12, "241 operations a byte against a ridge of 240.5: the operations, just"
    assert got["mla_decode_roofline.batch"] == pytest.approx(100 * must / 300e-9)
    assert got["mla_prefill_roofline.batch"] == pytest.approx(100 * counters["prefill_attn_flops"] / 197e12 / 4000e-9)
    # a run of another family, of a program without the counters, or without a session leaves them out
    run._session_reduced = {"counters": {"decode_steps": 5, "ssm_state_bytes_rw": 7}, "decode_device_ms": [1.0]}
    assert reader.read(run) == {}
    assert reader.read(types.SimpleNamespace(traffic_kind="open_loop")) == {}
    assert reader.read(types.SimpleNamespace(traffic_kind="closed_loop", session=None)) == {}


def test_the_new_entries_of_benchmark_json_are_at_the_end_and_name_the_cell():
    bench = load_benchmark(REPO)
    assert bench["workloads"][-1]["name"] == CELL and bench["configs"][-1]["name"] == CONFIG
    assert bench["workloads"][-1]["chips"] == 1 and bench["workloads"][-1]["traffic"] == "longctx_closed40"
    assert [m["name"] for m in bench["per_layer"][-8:]] == NEW_METRICS
    assert all(m["workloads"] == [CELL] and m["moves"] == "serve_tokens_per_s" for m in bench["per_layer"][-8:])
    both = ("deepseek7b_serve_batch", "granite4hsmall_serve_batch")
    for m in bench["end_to_end"] + bench["per_layer"][:-8]:
        lists_both = all(w in m.get("workloads", ()) for w in both)
        assert (CELL in m.get("workloads", ())) == lists_both, m["name"]
        assert not lists_both or m["workloads"][-1] == CELL
    # what was there is as it was: the benchmark without this PR's entries is the parent's
    before = _before_this_pr(REPO)
    assert [w["name"] for w in before["workloads"]] == [w["name"] for w in bench["workloads"][:-1]]
    assert len(before["per_layer"]) == len(bench["per_layer"]) - 8
