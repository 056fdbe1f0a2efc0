"""The ``phi4flash`` family (a decoder whose second half keeps no cache: ONE
pool layer that eight layers read, gated memory units that read one layer's
scan, Mamba-1 mixers beside rings under differential attention) in the
benchmark: a toy configuration and cell added to a temporary root by files and
entries alone, run through ``serve_cell`` to ``correct``, and to not correct with
each of the reference's three faults; the real configuration file against the
catalog's row and the issue's bytes, and against what the program allocates; the
traffic file's parameters; the table of shapes over the decode program traced on
the CPU at the cell's shapes; the reader's arithmetic on a made-up session whose
layers lie inside a ``while``.

This file changes no other test module.  Two tables of the tests that were here
before name the cells and traffic files they knew (``test_bm_session.TINY_OF``,
the toy a cell stands for; ``test_bm_order_seed.FILES``, "every serve mix that
WAS THERE"): ``tests/conftest.py`` names this PR's cell and traffic file for
them at collection, as ``tests/benchmark/conftest.py`` (which ``BENCHMARK.json``'s
``paths`` cover, so only a ``benchmark`` PR edits it) does for the cells before,
and every file here runs alone.  Seven lists of ``BENCHMARK.json`` are held by
older tests to the cells they knew and do NOT list this cell:
``prefill_program_ms_p50.batch`` and ``prefill_start_wait_ms_p50.batch``
(``test_bm_prefill_ride.py`` holds both at six cells); with them
``decode_device_ms_p50.batch``, ``decode_program_ms_p50.batch``,
``decode_launch_ms_p50.batch`` and ``loop_books_ms_p50.batch``
(``test_bm_programs.py`` holds all six to ONE list of cells, less the riding
cells for the first two); and ``swa_window_read_share.batch``
(``test_bm_laguna.py`` holds it to Laguna's cell, and ``test_bm_contract.py`` a
family to the names whose entries list its cells, so the family returns no
share).  The six generic readers give their numbers for this cell's run and the
harness drops them by the list (PERF.md section 7, After PR 61 b)."""

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
from benchmark.harness import discover, result_object
from benchmark.spec import load_benchmark, load_cell, load_family

CELL = "phi4miniflash_serve_reasoning"
CONFIG = "phi-4-mini-flash-reasoning.serve-L32"
TRAFFIC = "reasoning2k_closed120"
OWN = ["shared_pool_gb_per_step.batch", "shared_pool_decode_roofline.batch", "s6_scan_roofline.batch", "gmu_device_share.batch",
       "diffattn_combine_device_share.batch", "prefill_cross_rows_share.batch"]
SHARED = ["step_hbm_roofline_share.batch", "attn_device_share.batch", "ssm_device_share.batch", "ssm_step_roofline.batch",
          "ssm_state_gb_per_step.batch", "ring_gb_per_step.batch", "ring_decode_roofline.batch", "window_flash_roofline.batch"]
NAMES = OWN + SHARED
# entries that older tests hold to the cells they knew: the first six are read for this cell too and dropped by the list
UNLISTED = ["prefill_program_ms_p50.batch", "prefill_start_wait_ms_p50.batch", "decode_device_ms_p50.batch",
            "decode_program_ms_p50.batch", "decode_launch_ms_p50.batch", "loop_books_ms_p50.batch", "swa_window_read_share.batch"]


# hidden 64, 4 query pairs on 2 key pairs of heads of 8, N 16, window 8, ten layers (two self-decoder periods, the two
# middle layers, two cross periods)
TOY = {"source": "tests only", "model": "phi4flash", "model_type": "phi4flash", "vocab_size": 96, "hidden_size": 64,
       "intermediate_size": 96, "num_hidden_layers": 10, "num_attention_heads": 8, "num_key_value_heads": 4, "sliding_window": 8,
       "mb_per_layer": 2, "layer_norm_eps": 1e-5, "tie_word_embeddings": True, "mlp_bias": False, "lm_head_bias": False,
       "hidden_act": "silu", "reduced": [], "published": {},
       "assumed": {"mamba_d_state": 16, "mamba_d_conv": 4, "mamba_expand": 2, "mamba_dt_rank": 4,
                   "attention": "differential_adjacent_pairs", "attention_bias": True, "position_embedding": "none",
                   "self_decoder_layers": 4, "memory_from": "scan_output_before_gate", "window_includes_self": True},
       "deployment": "none: a toy", "kind": "serve",
       "serve": {"weight_dtype": "bfloat16", "slots": 4, "positions_per_slot": 64, "state_dtype": "float32", "page_size": 8,
                 "prefill_chunk": 8}}

WRAPPER = '''"""The phi4flash family with one fault in its reference (tests only)."""
import functools

from benchmark import reference
from benchmark.families import phi4flash as real

SERVE_LOGITS_TOLERANCE = real.SERVE_LOGITS_TOLERANCE
program_config, build_serve, rehearse_serve = real.program_config, real.build_serve, real.rehearse_serve


def logits(params, config, tokens, rows):
    return real.logits(params, config, tokens, rows, wrong="FAULT")


loss_and_logits = functools.partial(reference.loss_and_logits, logits)
'''


def _run(root, cell, traced=0):
    spec = load_cell(cell, root)
    rec, correct, attempted, failed, notes = serve_cell.run_cell(spec, jax.devices()[:1], 77, 1.0, traced, time.perf_counter())
    return spec, rec, correct, attempted, failed, notes


def test_the_family_runs_a_toy_cell_to_correct_by_files_and_entries_alone(tmp_path):
    """The runner as it is: pool, rings, states and tails through the normal
    path, and the check's prompt (59 of 64 positions: seven windows of 8, on the
    64 rung) against the reference."""
    root = make_tiny_root(str(tmp_path / "root"))
    cell = test_bm_hybrid._add_cell(root, "toyphi", TOY)
    spec, rec, correct, attempted, failed, notes = _run(root, cell, traced=2)
    family = load_family("phi4flash", root)
    assert correct and attempted > 0 and failed == 0, notes
    assert notes["compiles_in_window"] == 0, "every rung and the decode step were compiled by warm()"
    assert 0 < notes["reference"]["logits_max_abs_diff_over_max"] < family.SERVE_LOGITS_TOLERANCE
    assert notes["reference"]["tolerance"] == family.SERVE_LOGITS_TOLERANCE and notes["reference"]["prompt_tokens"] == 59
    counters = notes["session_counters"]           # the trace session read the engine's counters
    steps = counters["decode_steps"]
    assert steps > 0 and counters["shared_pool_bytes_read"] > 0
    assert 0 < counters["ring_positions_read"] < counters["ring_positions_unwindowed"], "some sequence outgrew the window"
    assert counters["ssm_state_bytes_rw"] == steps * 2 * 4 * 3 * (16 * 128 * 4 + 3 * 128 * 2)
    assert counters["prefill_rows_cross"] > 0 and counters["prefill_tokens_real"] >= 8 * counters["prefill_rows_cross"]
    assert counters.get("moe_assignments", 0) == 0, "a dense model"
    line = result_object(spec, rec, jax.devices()[:1], correct=correct, attempted=attempted, failed=failed, traced=2)
    assert {"serve_tokens_per_s", "setup_s", "decode_step_ms_p50.batch"} <= set(line["metrics"])
    assert line["metrics"]["serve_tokens_per_s"]["value"] > 0


@pytest.mark.parametrize("fault", ["window_minus_1", "pair_swapped", "m_after_gate"])
def test_a_fault_in_the_reference_reads_not_correct(tmp_path, fault):
    root = make_tiny_root(str(tmp_path / "root"))
    cell = test_bm_hybrid._add_cell(root, "toyphi_" + fault, dict(TOY, model="phi4flash_" + fault), WRAPPER.replace("FAULT", fault))
    _spec, _rec, correct, _attempted, _failed, notes = _run(root, cell)
    assert not correct
    assert notes["ledger"]["problems"] == [] and notes["compiles_in_window"] == 0
    assert notes["reference"]["logits_max_abs_diff_over_max"] > 2 * notes["reference"]["tolerance"]


# ------------------------------------------------- the real configuration file
def test_the_real_configuration_is_the_catalogs_row_with_nothing_cut():
    spec = load_cell(CELL, REPO)
    c, family = spec.config, spec.family()
    assert spec.chips == 1 and spec.traffic_name == TRAFFIC and spec.traffic["kind"] == "closed_loop"
    assert {m["name"] for m in spec.end_to_end} == {"serve_tokens_per_s", "setup_s"}
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(path):
        with open(path) as f:
            catalog = next(r for r in map(json.loads, f) if r["name"] == "Phi-4-mini-flash-reasoning")
        assert c["source"] == catalog["source_url"]
        assert {k: c[k] for k in catalog["config"]} == catalog["config"], "every key of the catalog's config, none changed"
    assert c["reduced"] == [] and c["published"] == {} and "share" not in c, "nothing is cut"
    widths = {"hidden_size": 2560, "intermediate_size": 10240, "num_hidden_layers": 32, "num_attention_heads": 40,
              "num_key_value_heads": 20, "sliding_window": 512, "vocab_size": 200064, "mb_per_layer": 2}
    assert {k: c[k] for k in widths} == widths
    assumed = c["assumed"]
    assert {k: assumed[k] for k in family.ASSUMED} == family.ASSUMED
    # ... each with where it is from
    assert all(key in assumed for key in ("why_mamba_sizes", "why_attention", "why_attention_bias", "why_position_embedding",
                                          "why_self_decoder_layers", "why_memory_from", "why_window_includes_self", "init"))
    assert "one 16 GB v5e chip" in c["deployment"] and "nothing is cut" in c["deployment"]
    assert c["serve"] == {"weight_dtype": "bfloat16", "slots": 96, "positions_per_slot": 4096, "state_dtype": "float32",
                          "page_size": 16}
    assert 512 % c["serve"]["page_size"] == 0, "the ring is read as whole pages"
    others = [load_cell(w["name"], REPO).config.get("serve") or {} for w in load_benchmark(REPO)["workloads"] if w["name"] != CELL]
    assert (96, 4096) not in {(s.get("slots"), s.get("positions_per_slot")) for s in others}, "a run's family is found by its geometry"
    assert family.layer_plan(c) == (["mamba", "window"] * 8 + ["mamba", "full"] + ["gmu", "cross"] * 7)
    assert family.layer_counts(c) == {"mamba": 9, "window": 8, "full": 1, "gmu": 7, "cross": 7} and family.pool_readers(c) == 8
    # ISSUE 61's arithmetic, in millions of parameters and in GB
    M = 1e6
    assert round(family.mlp_params(c) / M, 1) == 78.6 and round(32 * family.mlp_params(c) / M) == 2517
    assert round(family.mamba_params(c) / M, 1) == 41.2 and round(9 * family.mamba_params(c) / M) == 371
    assert round(family.attention_params(c) / M, 1) == 19.7 and round(9 * family.attention_params(c) / M) == 177
    assert round(family.attention_params(c, cross=True) / M, 1) == 13.1 and round(7 * family.attention_params(c, cross=True) / M) == 92
    assert round(family.gmu_params(c) / M, 1) == 26.2 and int(7 * family.gmu_params(c) / M) == 183
    assert round(200064 * 2560 / M) == 512
    assert abs(family.param_count(c) / 3.85e9 - 1) < 0.01 and round(family.weight_bytes(c) / 1e9, 2) == 7.71
    # the cache: a slot's rings, states and tails, and ONE layer of pages
    serve = c["serve"]
    assert family.position_bytes(c) == 5120
    assert family.ring_bytes_per_slot(c) == 8 * 512 * 5120 and round(family.ring_bytes_per_slot(c) / 1e6, 2) == 20.97
    assert family.state_bytes_per_slot(c, serve) == 9 * (5120 * 16 * 4 + 3 * 5120 * 2) and round(family.state_bytes_per_slot(c, serve) / 1e6, 2) == 3.23
    assert round(4096 * family.position_bytes(c) / 1e6, 2) == 20.97
    assert round(family.cache_bytes(c, serve) / 1e9, 2) == 4.34
    assert round((family.weight_bytes(c) + family.cache_bytes(c, serve)) / 1e9, 2) == 12.04
    # a decode step at 96 slots and a mean length near 1.9 k: the pool, read eight times, is as much as the weights
    pool = 8 * 96 * 1900 * 5120
    moved = family.decode_step_bytes(c, serve, pool_bytes_read=pool, ring_bytes_rw=96 * 8 * 513 * 5120)
    assert round(pool / 1e9, 1) == 7.5 and 17.5e9 < moved < 18.2e9 and 0.40 < pool / moved < 0.44
    assert family.prefill_rungs(serve) == [128, 256, 512, 1024, 1536, 2048, 3072, 4096]
    # what leaves the prefill: 15 of 32 MLPs, every gated memory unit and cross-attention, layer 17's q and o
    second_half = 15 * family.mlp_params(c) + 7 * family.gmu_params(c) + 7 * family.attention_params(c, cross=True)
    assert round(second_half / 1e9, 2) == 1.45


def test_what_the_program_allocates_is_what_the_family_counts():
    spec = load_cell(CELL, REPO)
    c, family = spec.config, spec.family()
    from vescale_tpu.models.phi4flash import init_params, prefill_counters
    from vescale_tpu.serve.hybrid_engine import hybrid_cache_config, prefill_buckets

    cfg = family.program_config(c)
    tree = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.key(0))
    assert sum(int(a.size) * a.dtype.itemsize for a in jax.tree_util.tree_leaves(tree)) == family.weight_bytes(c)
    assert sum(int(a.size) for a in jax.tree_util.tree_leaves(tree)) == family.param_count(c)
    assert tree["embed_tokens"]["embedding"].shape == (200064, 2560) and "lm_head" not in tree, "a tied head"
    first, second = tree["self"]["first"], tree["self"]["second"]
    assert first["mamba"]["in_proj"].shape == (8, 2560, 10240) and first["mamba"]["A_log"].shape == (8, 16, 5120)
    assert first["mamba"]["x_proj"].shape == (8, 5120, 192) and first["mamba"]["dt_proj"].shape == (8, 160, 5120)
    assert second["attn"]["q_proj"].shape == (8, 2560, 2560) and second["attn"]["k_proj"].shape == (8, 2560, 1280)
    assert second["mlp"]["gate_up"].shape == (8, 2560, 20480) and second["mlp"]["down"].shape == (8, 10240, 2560)
    cross = tree["cross"]
    assert cross["first"]["gmu"]["in_proj"].shape == (7, 2560, 5120) and "k_proj" not in cross["second"]["attn"]
    kc = hybrid_cache_config(cfg, num_slots=96, page_size=16, pages_per_slot=256)
    assert kc == family._cache_config(cfg, c["serve"])
    assert (kc.layers, kc.kv_heads, kc.head_dim, kc.folded, kc.max_seq_len, kc.pool_pages) == (1, 10, 128, True, 4096, 96 * 256 + 1)
    assert [(name, layers, tuple(shape)) for name, layers, shape, _dt in kc.slot_state] == [
        ("ring_k", 8, (512, 1, 1280)), ("ring_v", 8, (512, 1, 1280)), ("ssm", 9, (16, 5120)), ("conv", 9, (3, 5120))]
    state = sum(layers * int(np.prod(shape)) * np.dtype(dt).itemsize for _n, layers, shape, dt in kc.slot_state)
    assert state == family.ring_bytes_per_slot(c) + family.state_bytes_per_slot(c, c["serve"])
    pool = 2 * kc.layers * kc.pool_pages * kc.page_size * kc.kv_heads * kc.head_dim * 2
    assert pool + 96 * state == family.cache_bytes(c, c["serve"])
    assert prefill_buckets(cfg.prefill_chunk, kc.max_seq_len) == family.prefill_rungs(c["serve"])
    assert prefill_counters(cfg, 1536) == {"prefill_rows_cross": 1}


def test_the_traffic_file_holds_the_issues_parameters_and_one_order():
    spec = load_cell(CELL, REPO)
    t = spec.traffic
    assert {k: t[k] for k in ("kind", "clients", "first_wave", "lead_in_s", "pool", "pairing_seed", "order_seed", "max_total")} == {
        "kind": "closed_loop", "clients": 120, "first_wave": 96, "lead_in_s": 15, "pool": 64, "pairing_seed": 0, "order_seed": 0,
        "max_total": 4096}
    with open(os.path.join(REPO, "benchmark", "traffic", "reasoning2k_closed160.json")) as f:
        older = json.load(f)
    assert t["prompt_len"] == older["prompt_len"] == {"dist": "lognormal", "median": 1024, "sigma": 0.8, "min": 128, "max": 2048}
    assert t["output_len"] == older["output_len"] == {"dist": "lognormal", "median": 1024, "sigma": 0.6, "min": 256, "max": 2048}
    assert t["clients"] / t["first_wave"] == older["clients"] / older["first_wave"] == 1.25
    assert t["first_wave"] == spec.config["serve"]["slots"] and t["max_total"] == spec.config["serve"]["positions_per_slot"]
    vocab = spec.config["vocab_size"]
    a, b = (trafficgen.closed_loop_requests(t, seed, vocab) for seed in (2**31 + 5, 12345))
    lengths = lambda plan: [(len(r.prompt), r.max_new_tokens) for r in plan]
    assert len(a) == 64 and lengths(a) == lengths(b), "every seed sends the same requests in the same order"
    assert all(x.prompt != y.prompt for x, y in zip(a, b)), "the token ids are the seed's"
    assert trafficgen.first_wave_done_shares(t, 1) == trafficgen.first_wave_done_shares(t, 2**31 + 7)
    prompts, outputs = np.array([len(r.prompt) for r in a]), np.array([r.max_new_tokens for r in a])
    assert prompts.min() >= 128 and prompts.max() <= 2048 and outputs.min() >= 256 and outputs.max() <= 2048
    assert (prompts + outputs).max() <= 4096 and 1100 < prompts.mean() < 1180 and 1090 < outputs.mean() < 1160
    # the same multiset as reasoning2k_closed160 sends (its grid and pairing), in this file's one order
    assert sorted(lengths(a)) == sorted(lengths(trafficgen.closed_loop_requests(older, 3, vocab)))


# ------------------------------------------------------------------ the readers
def test_the_table_of_shapes_leaves_none_of_the_decode_programs_large_ops_under_other():
    """The decode program traced on the CPU at the cell's shapes (shapes, no
    arrays; the XLA legs).  What stays under ``other`` is of the residual
    stream's own size (its norms and sums): nothing that reads a weight, the
    pool, a ring or a state."""
    spec = load_cell(CELL, REPO)
    c, family = spec.config, spec.family()
    _sizes, programs = family.rehearse_serve(spec.name, c, c["serve"], jax.devices())
    title, lowered = programs[-1]
    assert "decode step, 96 slots x 4096 positions" in title and len(programs) == 9
    signatures = family.mechanism_signatures(c, c["serve"])
    by, largest_other = {}, 0
    for nbytes, text in test_bm_falconh1._ops_as_the_trace_names_them(lowered.as_text(dialect="hlo")):
        mechanism = family.mechanism_of(text, signatures)
        by[mechanism] = by.get(mechanism, 0) + nbytes
        if mechanism == "other":
            largest_other = max(largest_other, nbytes)
    stream = 96 * 2560 * 4
    assert largest_other <= 3 * stream, "an op of the stream reads two of its size and writes one"
    assert {"head", "mlp", "attention", "inner", "other"} <= set(by) and by["other"] < 0.02 * sum(by.values()), by
    of = lambda text, table=signatures: family.mechanism_of(text, table)
    assert of("%paged_decode_kv10.3 = f32[96,40,128]{2,1,0} custom-call(s32[1]{0} %l, s32[96]{0} %n, s32[96,256]{1,0} %t, bf16[1,24577,16,1280]{3,2,1,0} %k)") == "attention"
    assert of("%ssm_step_selective.2 = (f32[9,96,16,5120]{3,2,1,0}, f32[96,1,5120]{2,1,0}) custom-call(%a, %b)") == "inner"
    assert of("%fusion.4 = f32[96,20480]{1,0} fusion(bf16[2560,20480]{1,0} %gate_up, f32[96,2560] %h)") == "mlp"
    assert of("%fusion.5 = f32[96,2560]{1,0} fusion(bf16[10240,2560]{1,0} %down, f32[96,10240] %h)") == "mlp"
    assert of("%fusion.6 = f32[96,10240]{1,0} fusion(bf16[2560,10240]{1,0} %in_proj, f32[96,2560] %h)") == "inner"
    assert of("%fusion.7 = f32[96,5120]{1,0} fusion(bf16[2560,5120]{1,0} %gmu_in, f32[96,2560] %h)") == "inner"
    assert of("%fusion.8 = f32[96,2560]{1,0} fusion(bf16[2560,2560]{1,0} %q_proj, f32[96,2560] %h)") == "attention"
    assert of("%fusion.9 = f32[96,20,128]{2,1,0} fusion(f32[96,20,2,128]{3,2,1,0} %y, f32[4,64]{1,0} %lam)") == "diffattn"
    assert of("%fusion.10 = f32[96,200064]{1,0} fusion(bf16[200064,2560]{1,0} %embedding, f32[96,2560] %x)") == "head"
    assert of("%fusion.11 = f32[96,2560]{1,0} fusion(f32[96,2560] %x)") == "other"
    rung = family.mechanism_signatures(c, c["serve"], 1024)
    assert of("%selective_scan.3 = (f32[1024,5120]{1,0}, f32[16,5120]{1,0}) custom-call(%a, %b, %c)", rung) == "inner"
    assert of("%window_flash_fwd.3 = (bf16[40,1024,128]{2,1,0}, f32[40,1024,1]{2,1,0}) custom-call(%a, %b, %c)", rung) == "attention"
    assert of("%fusion.2 = f32[1024,2560]{1,0} fusion(f32[1024,2560] %x)", rung) == "other"


def test_the_readers_arithmetic_on_a_recorded_session_whose_layers_lie_inside_a_while():
    """Microseconds: two decode launches and one prefill of the 1,024 rung, their
    programs on the ``XLA Modules`` line and the ops inside them, each layer
    loop's ops INSIDE a ``while``'s event as the scanned stack's are."""
    reader = family_readers(NAMES, OWN)
    spec = load_cell(CELL, REPO)
    c, family = spec.config, spec.family()
    steps, slots = 10, 96
    reach = slots * 1900
    ring_read = 8 * slots * 512
    counters = {"decode_steps": steps, "shared_pool_bytes_read": steps * 8 * reach * 5120,
                "ring_positions_read": steps * ring_read, "ring_positions_unwindowed": steps * 8 * reach,
                "ring_bytes_rw": steps * (ring_read + 8 * slots) * 5120,
                "ssm_state_bytes_rw": steps * 2 * slots * family.state_bytes_per_slot(c, c["serve"]),
                "prefill_rows_cross": 1, "prefill_tokens_real": 1000}
    WHILE = "%while.3 = (f32[96,2560]{1,0}, f32[9,96,16,5120]{3,2,1,0}) while(%tuple.1)"
    STEP = "%ssm_step_selective.2 = (f32[9,96,16,5120]{3,2,1,0}, f32[96,1,5120]{2,1,0}) custom-call(%a, %b)"
    RING = "%paged_decode_kv10.6 = f32[96,40,128]{2,1,0} custom-call(s32[96,32]{1,0} %t, bf16[8,3072,16,1280]{3,2,1,0} %ring)"
    POOL = "%paged_decode_kv10.9 = f32[96,40,128]{2,1,0} custom-call(s32[96,256]{1,0} %t, bf16[1,24577,16,1280]{3,2,1,0} %pool)"
    MLP = "%fusion.4 = f32[96,20480]{1,0} fusion(bf16[2560,20480]{1,0} %gate_up, f32[96,2560] %h)"
    OUT = "%fusion.12 = f32[96,2560]{1,0} fusion(bf16[5120,2560]{1,0} %out_proj, f32[96,5120] %m)"       # Mamba's and the GMU's
    DIFF = "%fusion.9 = f32[96,20,128]{2,1,0} fusion(f32[96,20,2,128]{3,2,1,0} %y, f32[4,64]{1,0} %lam)"
    HEAD = "%fusion.10 = f32[96,200064]{1,0} fusion(bf16[200064,2560]{1,0} %embedding, f32[96,2560] %x)"
    NORM = "%fusion.11 = f32[96,2560]{1,0} fusion(f32[96,2560] %x)"
    SCAN = "%selective_scan.3 = (f32[1024,5120]{1,0}, f32[16,5120]{1,0}) custom-call(%a, %b, %c)"
    FLASH = "%window_flash_fwd.3 = (bf16[40,1024,128]{2,1,0}, f32[40,1024,1]{2,1,0}) custom-call(%a, %b, %c)"
    GMU1 = "%fusion.13 = f32[1,2560]{1,0} fusion(bf16[5120,2560]{1,0} %out_proj, f32[1,5120] %m)"

    def decode(t0):      # 2000 us: a while of the self-decoder (800), the middle (300), a while of the cross-decoder (600), the head
        return [(t0, t0 + 800, WHILE), (t0, t0 + 200, STEP), (t0 + 200, t0 + 300, OUT), (t0 + 300, t0 + 500, RING),
                (t0 + 500, t0 + 600, DIFF), (t0 + 600, t0 + 800, MLP),
                (t0 + 800, t0 + 900, STEP), (t0 + 900, t0 + 1100, POOL),
                (t0 + 1100, t0 + 1700, WHILE), (t0 + 1100, t0 + 1300, OUT), (t0 + 1300, t0 + 1600, POOL), (t0 + 1600, t0 + 1700, MLP),
                (t0 + 1700, t0 + 1900, HEAD), (t0 + 1900, t0 + 2000, NORM)]

    prefill = [(7000, 8600, WHILE.replace("[96,", "[1024,")), (7000, 7500, SCAN), (7500, 8000, FLASH),
               (8000, 8600, MLP.replace("[96,", "[1024,")), (8600, 8800, SCAN), (8800, 9000, GMU1)]
    modules = [(1000, 3000, "jit_decode(1)"), (4000, 6000, "jit_decode(1)"), (7000, 9000, "jit_prefill(9)")]
    host = [(900, 950, "vs.serve-decode.launch", {"launch": 1}), (3100, 3150, "vs.serve-decode.launch", {"launch": 2}),
            (6100, 6150, "vs.serve-prefill.launch", {"launch": 3, "rung": 1024, "slot": 5})]
    ops = decode(1000) + decode(4000) + prefill + [(9500, 9900, POOL)]                   # the last outside any program
    run = types.SimpleNamespace(traffic_kind="closed_loop", slots=96, padded_prompt_len=4096, device_kind="TPU v5 lite",
                                session=types.SimpleNamespace(profile=_trace(ops, modules, host)),
                                _session_reduced={"counters": counters})
    got = reader.read(run)
    assert set(got) == set(NAMES)
    rate = 819e9
    assert got["shared_pool_gb_per_step.batch"] == pytest.approx(8 * reach * 5120 / 1e9)
    assert got["ring_gb_per_step.batch"] == pytest.approx((ring_read + 8 * slots) * 5120 / 1e9)
    assert got["ssm_state_gb_per_step.batch"] == pytest.approx(2 * slots * family.state_bytes_per_slot(c, c["serve"]) / 1e9)
    assert got["prefill_cross_rows_share.batch"] == pytest.approx(1 / 1000)
    # the eight readings of a step (here two events a step, 500 us) against the bytes they must read
    assert got["shared_pool_decode_roofline.batch"] == pytest.approx(100 * (8 * reach * 5120 / rate) / 500e-6)
    assert got["ring_decode_roofline.batch"] == pytest.approx(100 * (ring_read * 5120 / rate) / 200e-6)
    assert got["ssm_step_roofline.batch"] == pytest.approx(100 * family.ssm_step_bytes(c, c["serve"]) / (150e-6 * rate))
    moved = family.decode_step_bytes(c, c["serve"], pool_bytes_read=8 * reach * 5120, ring_bytes_rw=(ring_read + 8 * slots) * 5120)
    assert got["step_hbm_roofline_share.batch"] == pytest.approx(100 * moved / (2000e-6 * rate))
    # the leaves of all three programs, 6,000 us: the whiles themselves count nothing
    mamba = 2 * (200 + 100 + 100) + 500 + 200               # the steps and the out-projection BEFORE the pool's first reading; the scans
    gmu = 2 * 200 + 200                                     # the same shape after it; the prefill's one row
    attention = 2 * (200 + 200 + 300 + 100) + 500           # rings, pool, the combination; the flash forward
    assert got["ssm_device_share.batch"] == pytest.approx(100 * mamba / 6000)
    assert got["gmu_device_share.batch"] == pytest.approx(100 * gmu / 6000)
    assert got["attn_device_share.batch"] == pytest.approx(100 * attention / 6000)
    assert got["diffattn_combine_device_share.batch"] == pytest.approx(100 * 200 / 6000)
    scan_bytes = family.scan_bytes(c, 1024)
    assert scan_bytes / rate > family.scan_flops(c, 1024) / 197e12, "the bytes decide"
    assert got["s6_scan_roofline.batch"] == pytest.approx(100 * 9 * scan_bytes / rate / 700e-6)
    flops, nbytes = family.window_attention_flops(c, 1024), family.window_attention_bytes(c, 1024)
    assert flops == 6 * 64 * 40 * (512 * 513 // 2 + 512 * 512) and nbytes == (2 * 40 * 128 + 2 * 20 * 64) * 1024 * 2
    assert got["window_flash_roofline.batch"] == pytest.approx(100 * 8 * max(flops / 197e12, nbytes / rate) / 500e-6)
    assert all(0 < got[name] for name in got)
    # a program without the model's counters (this PR's parent; another family's run) leaves them all out
    run._session_reduced = {"counters": {"decode_steps": 5, "ssm_state_bytes_rw": 7, "ring_bytes_rw": 9}}
    assert reader.read(run) == {}
    run._session_reduced = {"counters": dict(counters, decode_steps=0)}
    assert reader.read(run) == {}
    other = types.SimpleNamespace(traffic_kind="closed_loop", slots=64, padded_prompt_len=4096, device_kind="TPU v5 lite",
                                  session=run.session, _session_reduced={"counters": counters})
    assert reader.read(other) == {}
    assert reader.read(types.SimpleNamespace(traffic_kind="open_loop")) == {}
    assert reader.read(types.SimpleNamespace(traffic_kind="closed_loop", session=None)) == {}


@pytest.mark.parametrize("path", ["benchmark/families/phi4flash.py", "benchmark/layer_metrics/phi4flash_serve_reasoning.py"])
def test_neither_the_family_nor_its_reader_imports_the_programs_module_at_import(path, monkeypatch):
    """Every run of every cell executes every reader, and ``_family`` loads each
    family's file to learn its geometry: under these files the parent, whose
    program has no such module, must still run its own cells.  The module is
    wanted only where the program is built."""
    import importlib.util
    import sys

    import vescale_tpu.models

    name = "vescale_tpu.models.phi4flash"

    class NoSuchModule:
        @staticmethod
        def find_spec(fullname, path=None, target=None):
            if fullname == name:
                raise ModuleNotFoundError(f"No module named {name!r}", name=name)

    monkeypatch.delitem(sys.modules, name, raising=False)
    monkeypatch.delattr(vescale_tpu.models, "phi4flash", raising=False)
    monkeypatch.setattr(sys, "meta_path", [NoSuchModule] + sys.meta_path)
    spec = importlib.util.spec_from_file_location("_alone_" + os.path.basename(path)[:-3], os.path.join(REPO, path))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    assert name not in sys.modules
    if hasattr(module, "read"):
        mimo = types.SimpleNamespace(traffic_kind="closed_loop", slots=256, padded_prompt_len=4096, device_kind="TPU v5 lite",
                                     session=None, _session_reduced={"counters": {"decode_steps": 5}})
        assert module.read(mimo) == {}
    else:
        assert module.layer_counts(load_cell(CELL, REPO).config)["cross"] == 7, "the counts from shapes need no program"
        with pytest.raises(ModuleNotFoundError):
            module.program_config(load_cell(CELL, REPO).config)


def test_the_entries_of_benchmark_json_name_the_cell():
    bench = load_benchmark(REPO)
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    (config,) = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert cell == bench["workloads"][-1] and config == bench["configs"][-1], "new entries go to the end of their lists"
    assert cell["config"] == CONFIG and cell["traffic"] == TRAFFIC and cell["chips"] == 1
    assert config["file"] == f"benchmark/configs/{CONFIG}.json" and config["reduced"] == []
    assert [m["name"] for m in bench["per_layer"][-6:]] == OWN
    entries = declared_entries(CELL, NAMES)
    assert all(m["moves"] == "serve_tokens_per_s" for m in entries)
    assert all(m["workloads"] == [CELL] for m in entries if m["name"] in OWN)
    assert all(m["workloads"][-1] == CELL and len(m["workloads"]) > 1 for m in entries if m["name"] in SHARED)
    assert {m["layer"] for m in entries if m["name"] in OWN} == {"Shared K/V pool", "State-space mixer", "Gated memory unit", "Attention",
                                                                 "Serve engine"}
    family = load_family(load_cell(CELL, REPO).config["model"], REPO)
    assert callable(family.layer_readings) and "shared_pool_bytes_read" in family.LAYER_COUNTERS
    # every .batch entry that all eight closed-loop serve cells before this one list, but the seven that older tests hold to
    # the cells they knew (this file's docstring): a `benchmark` PR's to append
    joined = [m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", ())]
    assert len(joined) == 16 + 8 + 6 and not set(UNLISTED) & set(joined)
    readers = discover(os.path.join(REPO, "benchmark", "layer_metrics"))
    assert all(sum(name in r.METRICS for r in readers) == 1 for name in UNLISTED), "each has its reader"
    assert "swa_window_read_share" not in family.layer_readings.__code__.co_consts, "a family returns what lists its cells"
    assert len(bench["workloads"]) == 12 and len(bench["per_layer"]) == 102 and sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    (e2e,) = [m for m in bench["end_to_end"] if m["name"] == "serve_tokens_per_s"]
    assert e2e["workloads"][-1] == CELL
