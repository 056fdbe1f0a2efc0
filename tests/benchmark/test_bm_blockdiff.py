"""The ``sdar_moe`` family (generation by diffusion over blocks) in the
benchmark: a toy configuration and cell added to a temporary root by files and
entries alone, run through ``serve_cell`` to ``correct``, and to not correct
with one sign turned in the reference; the real configuration file against the
catalog's numbers and the issue's bytes, and against what the program
allocates; the new readers' arithmetic on a made-up session; the traffic
file's grid.

As ``test_bm_mla.py`` did for its cell, this file tells the tests that were
here before of the new one AT IMPORT: ``test_bm_session.TINY_OF`` gets the new
cell's toy stand-in, and ``test_bm_decode_ahead``'s last test (and through its
view ``test_bm_mla``'s, and through that one's ``test_bm_hybrid``'s), which hold
that their PR's entries are the LAST of ``BENCHMARK.json``, read the benchmark
as it stood before this PR's entries were appended."""

import json
import os
import time
import types

import jax
import numpy as np
import pytest

import test_bm_decode_ahead
import test_bm_hybrid
import test_bm_session
from bm_fixtures import REPO, make_tiny_root

from benchmark import serve_cell, trafficgen
from benchmark.harness import discover, result_object
from benchmark.spec import SpecError, load_benchmark, load_cell, load_family

CELL = "sdar30b_serve_blockgen"
CONFIG = "sdar-30b-a3b-chat.serve-L6"
NEW_METRICS = ["blockdiff_tokens_per_pass.batch", "blockdiff_commit_pass_share.batch", "blockdiff_masked_row_share.batch",
               "unmask_device_share.batch", "block_attn_device_share.batch", "experts128_device_share.batch",
               "experts128_load_imbalance.batch", "blockdiff_pass_hbm_roofline_share.batch",
               "block_prefill_attn_roofline.batch", "block_decode_attn_roofline.batch"]

test_bm_session.TINY_OF.setdefault(CELL, "tiny_batch")


def _before_this_pr(root):
    """``BENCHMARK.json`` without what PR 36 appended (its configuration, its cell, its metrics, its list members)."""
    bench = load_benchmark(root)
    bench["configs"] = [c for c in bench["configs"] if c["name"] != CONFIG]
    bench["workloads"] = [w for w in bench["workloads"] if w["name"] != CELL]
    for group in ("end_to_end", "per_layer"):
        bench[group] = [dict(m, workloads=[w for w in m["workloads"] if w != CELL]) if "workloads" in m else m
                        for m in bench[group] if m["name"] not in NEW_METRICS]
    return bench


test_bm_decode_ahead.load_benchmark = _before_this_pr      # the newest link of the chain: each reads through the next

# two layers, 4 query heads over 2 key heads of 16, 8 experts of 32 with 2 a token; blocks of 4 in 4 steps
TOY = {"source": "tests only", "model": "sdar_moe", "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 16,
       "hidden_act": "silu", "hidden_size": 64, "mlp_only_layers": [], "moe_intermediate_size": 32, "norm_topk_prob": True,
       "num_attention_heads": 4, "num_experts": 8, "num_experts_per_tok": 2, "num_hidden_layers": 2,
       "num_key_value_heads": 2, "rms_norm_eps": 1e-6, "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
       "tie_word_embeddings": False, "use_sliding_window": False, "vocab_size": 96, "reduced": [], "published": {},
       "assumed": {"block_length": 4, "denoising_steps": 4, "remasking": "low_confidence_static", "greedy": True,
                   "mask_token_id": 90, "qk_norm": True},
       "deployment": "none: a toy", "kind": "serve",
       "serve": {"weight_dtype": "bfloat16", "slots": 4, "positions_per_slot": 64, "page_size": 8, "prefill_chunk": 8}}

WRAPPER = '''"""The sdar_moe family with one sign turned in its reference's rotary (tests only)."""
import functools

from benchmark import reference
from benchmark.families import sdar_moe as real

SERVE_LOGITS_TOLERANCE = real.SERVE_LOGITS_TOLERANCE
program_config, build_serve, rehearse_serve = real.program_config, real.build_serve, real.rehearse_serve


def logits(params, config, tokens, rows):
    half = real._rotate_half
    real._rotate_half = lambda x: -half(x)     # turns the other way
    real.attention.clear_cache()
    try:
        return real.logits(params, config, tokens, rows)
    finally:
        real._rotate_half = half
        real.attention.clear_cache()


loss_and_logits = functools.partial(reference.loss_and_logits, logits)
'''


def _run(root, cell, traced=0):
    spec = load_cell(cell, root)
    rec, correct, attempted, failed, notes = serve_cell.run_cell(spec, jax.devices()[:1], 2**31 + 29, 1.0, traced,
                                                                 time.perf_counter())
    return spec, rec, correct, attempted, failed, notes


def test_the_family_runs_a_toy_cell_to_correct_by_files_and_entries_alone(tmp_path):
    """The runner as it is, with no flag: the loop learns from the engine that a
    step yields a count, every completed request has the tokens it asked for,
    and the check's prompt of 59 (three tokens into a block) is under it."""
    root = make_tiny_root(str(tmp_path / "root"))
    cell = test_bm_hybrid._add_cell(root, "toyblockdiff", TOY)
    spec, rec, correct, attempted, failed, notes = _run(root, cell, traced=2)
    family = load_family("sdar_moe", root)
    assert correct and attempted > 0 and failed == 0, notes
    assert notes["compiles_in_window"] == 0, "every rung and the pass were compiled by warm()"
    assert 0 < notes["reference"]["logits_max_abs_diff_over_max"] < family.SERVE_LOGITS_TOLERANCE
    assert notes["reference"]["prompt_tokens"] == 59 and notes["ledger"]["counts"]["completed"] > 0
    counters = notes["session_counters"]           # the trace session read the engine's counters
    assert counters["decode_steps"] > 0 and counters["block_passes"] >= counters["decode_steps"]
    assert 0 < counters["block_commit_passes"] < counters["block_passes"] and counters["block_positions_masked"] > 0
    assert 0 < counters["block_tokens_emitted"] <= 4 * counters["block_commit_passes"]
    assert counters["moe_assignments"] == counters["moe_assignments_held"] == counters["block_passes"] * 4 * 2 * 2
    assert counters["decode_steps_ahead"] >= 0.8 * counters["decode_steps"], "the pipeline stays one pass deep"
    line = result_object(spec, rec, jax.devices()[:1], correct=correct, attempted=attempted, failed=failed, traced=2)
    assert {"serve_tokens_per_s", "setup_s", "decode_step_ms_p50.batch"} <= set(line["metrics"])
    assert line["metrics"]["serve_tokens_per_s"]["value"] > 0


def test_one_sign_turned_in_the_reference_reads_not_correct(tmp_path):
    root = make_tiny_root(str(tmp_path / "root"))
    cell = test_bm_hybrid._add_cell(root, "toyblockdiff_turned", dict(TOY, model="sdar_moe_turned"), WRAPPER)
    _spec, _rec, correct, _attempted, _failed, notes = _run(root, cell)
    assert not correct
    assert notes["ledger"]["problems"] == [] and notes["compiles_in_window"] == 0
    assert notes["reference"]["logits_max_abs_diff_over_max"] > 5 * notes["reference"]["tolerance"]


# ------------------------------------------------- the real configuration file
def test_the_real_configuration_is_the_catalogs_row_cut_as_the_issue_says():
    spec = load_cell(CELL, REPO)
    c, family = spec.config, spec.family()
    assert spec.chips == 1 and spec.traffic_name == "blockgen_closed160" and spec.traffic["kind"] == "closed_loop"
    assert {m["name"] for m in spec.end_to_end} == {"serve_tokens_per_s", "setup_s"}
    traffic = {k: spec.traffic[k] for k in ("clients", "lead_in_s", "pool", "first_wave", "pairing_seed", "max_total")}
    assert traffic == {"clients": 160, "lead_in_s": 10.0, "pool": 512, "first_wave": 128, "pairing_seed": 0, "max_total": 2048}
    assert spec.traffic["prompt_len"] == {"dist": "lognormal", "median": 192, "sigma": 0.8, "min": 16, "max": 512}
    assert spec.traffic["output_len"] == {"dist": "lognormal", "median": 640, "sigma": 0.7, "min": 64, "max": 1536}
    # every number of the catalog's config under its key, but for the one reduced
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(path):
        with open(path) as f:
            catalog = next(r for r in map(json.loads, f) if r["name"] == "SDAR-30B-A3B-Chat")
        assert c["source"] == catalog["source_url"]
        assert {k: v for k, v in catalog["config"].items() if c[k] != v} == {"num_hidden_layers": 48} == c["published"]
    assert c["reduced"] == ["num_hidden_layers"] and c["num_hidden_layers"] == 6 and "share" not in c
    published = {"hidden_size": 2048, "num_attention_heads": 32, "num_key_value_heads": 4, "head_dim": 128,
                 "moe_intermediate_size": 768, "num_experts": 128, "num_experts_per_tok": 8, "vocab_size": 151936,
                 "rope_theta": 1000000, "rms_norm_eps": 1e-6, "norm_topk_prob": True, "tie_word_embeddings": False}
    assert {k: c[k] for k in published} == published
    # the floors of the model-configs guide: four layers, 8 experts, the whole vocabulary
    assert c["num_hidden_layers"] >= 4 and c["num_experts"] >= 8
    assumed = c["assumed"]
    assert (assumed["block_length"], assumed["denoising_steps"], assumed["remasking"], assumed["greedy"],
            assumed["mask_token_id"], assumed["qk_norm"]) == (4, 4, "low_confidence_static", True, 151669, True)
    assert all(key in assumed for key in ("router_init", "slots", "positions_per_slot", "page_size", "prefill_rungs"))
    cfg = family.program_config(c)
    assert (cfg.num_experts, cfg.experts_held, cfg.first_expert_held) == (128, 128, 0)
    assert (cfg.block_length, cfg.denoising_steps, cfg.mask_token_id, cfg.vocab_size) == (4, 4, 151669, 151936)
    # ISSUE 36's table, in millions of parameters and in GB
    M = 1e6
    assert round(family.attention_params(c) / M, 2) == 18.87 and round(family.expert_params(c) / M, 3) == 4.719
    assert round(family.router_params(c) / M, 2) == 0.26
    layer = family.attention_params(c) + family.router_params(c) + 128 * family.expert_params(c)
    assert round(layer / M, 1) == 623.1 and round(2 * 151936 * 2048 / M, 1) == 622.3
    assert round(family.weight_bytes(c) / 1e9, 2) == 8.73          # the issue reckoned 8.72, without the routers' float32
    assert family.kv_bytes_per_position(c) == 12288
    serve = c["serve"]
    assert (serve["slots"], serve["positions_per_slot"], serve["page_size"]) == (128, 2048, 16)
    assert round(128 * 2048 * family.kv_bytes_per_position(c) / 1e9, 2) == 3.22
    assert round(family.logits_bytes_per_pass(c, serve) / 1e9, 2) == 0.31
    assert 128 * 4 * 8 / 128 == 32, "rows an expert a pass"
    moved = family.pass_bytes(c, serve, kv_pages_read_per_layer=128 * 640 / 16)
    assert 10.3e9 < moved < 10.4e9, "8.73 GB of weights, 1.0 GB of live K and V, the logits written and read"
    assert moved - family.pass_bytes(c, serve, kv_pages_read_per_layer=128 * 640 / 16, experts_touched=6 * 128 - 1) \
        == 2 * family.expert_params(c)
    assert family.block_prefill_attention_flops(c, 512) == 32 * 4 * 128 * 512 * 516 / 2
    assert family.pass_attention_flops_per_position(c) == 4 * 32 * 128 * 4 and family.pass_attention_bytes_per_position(c) == 2048


def test_what_the_program_allocates_is_what_the_family_counts():
    spec = load_cell(CELL, REPO)
    c, family = spec.config, spec.family()
    from vescale_tpu.models.sdar_moe import init_params, prefill_counters
    from vescale_tpu.serve.hybrid_engine import hybrid_cache_config, prefill_buckets

    cfg = family.program_config(c)
    tree = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.key(0))
    assert sum(int(a.size) * a.dtype.itemsize for a in jax.tree_util.tree_leaves(tree)) == family.weight_bytes(c)
    assert tree["lm_head"]["kernel"].shape == (2048, 151936) and tree["layers_5"]["mlp"]["w_gate"].shape == (128, 2048, 768)
    kc = hybrid_cache_config(cfg, num_slots=128, page_size=16, pages_per_slot=128)
    assert not kc.latent and (kc.layers, kc.kv_heads, kc.head_dim, kc.max_seq_len) == (6, 4, 128, 2048)
    pool = 2 * kc.layers * kc.pool_pages * kc.page_size * kc.kv_heads * kc.head_dim * 2
    assert round(pool / 1e9, 2) == 3.22
    assert [name for name, *_ in kc.slot_state] == ["block_ids", "block_masked", "block_pass"]
    state = sum(int(np.prod((layers, 128) + tuple(shape))) * np.dtype(dt).itemsize for _n, layers, shape, dt in kc.slot_state)
    assert state == family.slot_state_bytes(c, c["serve"]) == 3072
    assert prefill_buckets(cfg.prefill_chunk, kc.max_seq_len) == family.prefill_rungs(c["serve"]) == [128, 256, 512, 1024, 1536, 2048]
    assert prefill_counters(cfg, 512)["prefill_attn_flops"] == 6 * family.block_prefill_attention_flops(c, 512)
    with pytest.raises(SpecError, match="low-confidence"):
        family.program_config(dict(c, assumed=dict(c["assumed"], greedy=False)))


def test_the_traffic_files_grid_is_what_its_file_and_the_cells_why_say():
    spec = load_cell(CELL, REPO)
    pool = trafficgen.closed_loop_requests(spec.traffic, 2**31 + 5, 151669)
    prompts, outputs = np.array([len(r.prompt) for r in pool]), np.array([r.max_new_tokens for r in pool])
    assert len(pool) == 512 and prompts.min() >= 16 and prompts.max() <= 512 and outputs.min() >= 64 and outputs.max() <= 1536
    assert round(prompts.mean()) == 232 and round(outputs.mean()) == 742 and (prompts + outputs).max() <= 2048
    assert abs(np.median(prompts) - 192) <= 2 and abs(np.median(outputs) - 640) <= 4
    assert all(0 < t < 151669 for r in pool[:8] for t in r.prompt), "no drawn token is the mask"
    assert "232 in, 742 out" in next(w for w in load_benchmark(REPO)["workloads"] if w["name"] == CELL)["why"]
    # every residue of prompt and budget mod 4 is in the pool, and the first wave's cuts give arbitrary counts
    assert {int(p) % 4 for p in prompts} == {int(o) % 4 for o in outputs} == {0, 1, 2, 3}
    shares = trafficgen.first_wave_done_shares(spec.traffic, 7)
    assert len(shares) == 128 and {trafficgen.cut_first_wave(pool[i], s).max_new_tokens % 4 for i, s in enumerate(shares)} == {0, 1, 2, 3}
    # decode passes dominate: a request is its prompt's one prefill and five passes a block of four tokens
    assert (5 * outputs / 4).mean() > 900


# ------------------------------------------------------------------ the readers
def _fake_profile(host, device, modules):
    event = lambda a, b, n: types.SimpleNamespace(start_ns=a, duration_ns=b - a, name=n)
    line = lambda name, evs: types.SimpleNamespace(name=name, events=[event(*e) for e in evs])
    return types.SimpleNamespace(planes=[
        types.SimpleNamespace(name="/host:CPU", lines=[line("python", host)]),
        types.SimpleNamespace(name="/device:TPU:0", lines=[line("XLA Ops", device), line("XLA Modules", modules)])])


def test_the_table_of_shapes_names_the_mechanism_of_a_passs_ops():
    spec = load_cell(CELL, REPO)
    family = spec.family()
    sig = family.mechanism_signatures(spec.config, spec.config["serve"])
    of = lambda text: family.mechanism_of(text, sig)
    assert of("%paged_decode.3 = f32[128,128,128]{2,1,0} custom-call(s32[1] %l, s32[128] %n)") == "attention"
    assert of("%block_flash_fwd.2 = bf16[32,512,128]{2,1,0} custom-call(bf16[32,512,128] %q)") == "attention"
    assert of("%fusion.7 = f32[512,4096]{1,0} fusion(bf16[2048,4096]{1,0} %q_proj, f32[512,2048] %x)") == "attention"
    assert of("%ragged-dot-none.11 = f32[4096,768]{1,0} custom-call(s32[1] %a, s32[129] %b)") == "experts"
    assert of("%fusion.9 = f32[512,128]{1,0} fusion(f32[2048,128]{1,0} %router, f32[512,2048] %h)") == "experts"
    assert of("%sort.3 = (s32[4096]{0}, s32[4096]{0}) sort(s32[4096] %g, s32[4096] %i)") == "experts"
    assert of("%fusion.185 = f32[512,151936]{1,0} fusion(bf16[2048,151936]{1,0} %lm_head, f32[512,2048] %x)") == "unmask"
    assert of("%reduce.4 = f32[128,4]{1,0} reduce(f32[128,4,151936] %e)") == "unmask"
    assert of("%copy-done.71 = bf16[2048]{0} copy-done((bf16[2048]{0}, bf16[2048]{0}, u32[]) %copy-start.7)") == "other"


def test_the_readers_arithmetic_on_a_made_up_session():
    (reader,) = [m for m in discover(os.path.join(REPO, "benchmark", "layer_metrics")) if NEW_METRICS[0] in m.METRICS]
    assert list(reader.METRICS) == NEW_METRICS
    spec = load_cell(CELL, REPO)
    c, family = spec.config, spec.family()
    steps, slots = 10, 128                          # every slot moved in every pass, at 640 positions
    pages = slots * (640 // 16) * steps
    counters = {"decode_steps": steps, "block_passes": slots * steps, "block_commit_passes": slots * 2,
                "block_tokens_emitted": slots * 8 - 24, "block_positions_masked": slots * 20,
                "moe_assignments": slots * 4 * 8 * 6 * steps, "moe_assignments_held": slots * 4 * 8 * 6 * steps,
                "moe_busiest_expert_tokens": 48 * 6 * steps, "moe_expert_slots": 128 * 6 * steps, "moe_layer_steps": 6 * steps,
                "moe_experts_touched": 128 * 6 * steps - 30, "decode_pages_read": pages, "decode_pages_capacity": slots * 128 * steps,
                "prefill_attn_flops": int(6 * family.block_prefill_attention_flops(c, 256)), "prefill_bucket_tokens": 256}
    profile = _fake_profile(
        host=[(0, 100, "vs.serve-decode")],
        modules=[(1000, 3000, "jit_decode(123)"), (4000, 6000, "jit_decode(123)"), (7000, 9000, "jit_prefill(9)")],
        device=[(1000, 1600, "%fusion.185 = f32[512,151936]{1,0} fusion(bf16[2048,151936]{1,0} %lm_head, f32[512,2048] %x)"),  # unmask 600
                (1700, 2000, "%paged_decode.3 = f32[128,128,128]{2,1,0} custom-call(s32[1] %l)"),                                # attention 300
                (4100, 5100, "%ragged-dot-none.11 = f32[4096,768]{1,0} custom-call(s32[1] %a, s32[129] %b)"),                    # experts 1000
                (5200, 5300, "%copy-done.2 = bf16[2048]{0} copy-done(bf16[2048] %x)"),                                           # other 100
                (7100, 7600, "%block_flash_fwd.2 = bf16[32,256,128]{2,1,0} custom-call(bf16[32,256,128] %q)"),                   # a prefill's
                (9500, 9900, "%fusion.5 = f32[512,4096]{1,0} fusion(bf16[2048,4096]{1,0} %q_proj, f32[512,2048] %x)")])          # outside any program
    run = types.SimpleNamespace(traffic_kind="closed_loop", slots=128, padded_prompt_len=2048, device_kind="TPU v5 lite",
                                session=types.SimpleNamespace(profile=profile), _session_reduced={"counters": counters})
    got = reader.read(run)
    assert set(got) == set(NEW_METRICS)
    assert got["blockdiff_tokens_per_pass.batch"] == pytest.approx((slots * 8 - 24) / (slots * steps))
    assert got["blockdiff_commit_pass_share.batch"] == pytest.approx(20.0)
    assert got["blockdiff_masked_row_share.batch"] == pytest.approx(100 * 20 / (4 * steps))
    assert got["experts128_load_imbalance.batch"] == pytest.approx(48 / 32)
    moved = family.pass_bytes(c, c["serve"], kv_pages_read_per_layer=pages / steps, experts_touched=128 * 6 - 3)
    assert got["blockdiff_pass_hbm_roofline_share.batch"] == pytest.approx(100 * moved / (2000e-9 * 819e9))
    assert got["unmask_device_share.batch"] == pytest.approx(30.0) and got["block_attn_device_share.batch"] == pytest.approx(15.0)
    assert got["experts128_device_share.batch"] == pytest.approx(50.0)
    flops, moved_bytes = counters["prefill_attn_flops"], 6 * family.block_prefill_attention_bytes(c, 256)
    assert flops / 197e12 < moved_bytes / 819e9, "at 256 positions 32 heads' queries and outputs outweigh the pairs: the bytes"
    assert 6 * family.block_prefill_attention_flops(c, 1024) / 197e12 > 6 * family.block_prefill_attention_bytes(c, 1024) / 819e9
    assert got["block_prefill_attn_roofline.batch"] == pytest.approx(100 * moved_bytes / 819e9 / 500e-9)
    positions = pages * 16 * 6
    must = max(positions * 2048 / 819e9, positions * 65536 / 197e12)
    assert must == positions * 2048 / 819e9, "32 operations a byte against a ridge of 240: the bytes"
    assert got["block_decode_attn_roofline.batch"] == pytest.approx(100 * must / 300e-9)
    # a run of another family, of a program without the counters (this PR's parent), or without a session leaves them out
    run._session_reduced = {"counters": {"decode_steps": 5, "latent_bytes_read": 7}}
    assert reader.read(run) == {}
    assert reader.read(types.SimpleNamespace(traffic_kind="open_loop")) == {}
    assert reader.read(types.SimpleNamespace(traffic_kind="closed_loop", session=None)) == {}


def test_the_new_entries_of_benchmark_json_are_at_the_end_and_name_the_cell():
    bench = load_benchmark(REPO)
    n = len(NEW_METRICS)
    assert bench["workloads"][-1]["name"] == CELL and bench["configs"][-1]["name"] == CONFIG
    assert bench["workloads"][-1]["chips"] == 1 and bench["workloads"][-1]["traffic"] == "blockgen_closed160"
    assert bench["configs"][-1]["reduced"] == ["num_hidden_layers"]
    assert [m["name"] for m in bench["per_layer"][-n:]] == NEW_METRICS
    assert all(m["workloads"] == [CELL] and m["moves"] == "serve_tokens_per_s" for m in bench["per_layer"][-n:])
    assert all(len(x["why"]) <= 200 for x in bench["workloads"] + bench["configs"])
    three = ("deepseek7b_serve_batch", "granite4hsmall_serve_batch", "deepseekv2_serve_longctx")
    for m in bench["end_to_end"] + bench["per_layer"][:-n]:
        lists_all = all(w in m.get("workloads", ()) for w in three)
        assert (CELL in m.get("workloads", ())) == lists_all, m["name"]
        assert not lists_all or m["workloads"][-1] == CELL
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1 and len(bench["workloads"]) == 7
    # what was there is as it was: the benchmark without this PR's entries is the parent's
    before = _before_this_pr(REPO)
    assert [w["name"] for w in before["workloads"]] == [w["name"] for w in bench["workloads"][:-1]]
    assert len(before["per_layer"]) == len(bench["per_layer"]) - n
