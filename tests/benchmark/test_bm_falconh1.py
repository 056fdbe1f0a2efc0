"""The ``falcon_h1`` family (a state-space mixer and an attention mixer side by
side in every layer) in the benchmark: a toy configuration and cell added to a
temporary root by files and entries alone, run through ``serve_cell`` to
``correct``, and to not correct with one multiplier left out of the reference;
the real configuration file against the catalog's row and the issue's bytes,
and against what the program allocates; the traffic file's grid; the table of
shapes over the decode program traced on the CPU at the cell's shapes; the
reader's arithmetic on a made-up session.

As ``test_bm_block_fused.py`` did for its entry, this file tells the tests that
were here before of the new cell AT IMPORT: ``test_bm_session.TINY_OF`` gets the
cell's toy stand-in, and ``test_bm_block_fused``'s last test (and through its
view every older link's), which holds that its PR's entries are the LAST of
``BENCHMARK.json``, reads the benchmark as it stood before this PR's entries
were appended."""

import json
import os
import re
import time
import types

import jax
import numpy as np
import pytest

import test_bm_block_fused
import test_bm_hybrid
import test_bm_session
from bm_fixtures import REPO, make_tiny_root
from test_bm_programs import _trace

from benchmark import serve_cell, trafficgen
from benchmark.harness import discover, result_object
from benchmark.spec import load_benchmark, load_cell, load_family

CELL = "falconh1_34b_serve_batch"
CONFIG = "falcon-h1-34b-instruct.serve-L6-v2"
NEW_METRICS = ["h1_state_gb_per_step.batch", "h1_ssm_device_share.batch", "h1_attn_device_share.batch",
               "h1_mlp_device_share.batch", "h1_scan_prefill_device_share.batch", "h1_ssm_step_roofline.batch",
               "h1_step_hbm_roofline_share.batch"]
CLOSED_LOOP = ("deepseek7b_serve_batch", "granite4hsmall_serve_batch", "deepseekv2_serve_longctx", "sdar30b_serve_blockgen")

test_bm_session.TINY_OF.setdefault(CELL, "tiny_batch")


def _before_this_pr(root):
    """``BENCHMARK.json`` without what PR 43 appended (its configuration, its cell, its metrics, its list members)."""
    bench = load_benchmark(root)
    bench["configs"] = [c for c in bench["configs"] if c["name"] != CONFIG]
    bench["workloads"] = [w for w in bench["workloads"] if w["name"] != CELL]
    for group in ("end_to_end", "per_layer"):
        bench[group] = [dict(m, workloads=[w for w in m["workloads"] if w != CELL]) if "workloads" in m else m
                        for m in bench[group] if m["name"] not in NEW_METRICS]
    return bench


test_bm_block_fused.load_benchmark = _before_this_pr      # the newest link of the chain: each reads through the next

# hidden 64, two layers, 4 state-space heads of 16 in 2 groups with a state of 16, 10 query heads on 2 key heads of 16
# (five a key head, as 20 on 4), an MLP of 96, chunk 8, the published multipliers; half a vocabulary of 192 held
TOY = {"source": "tests only", "model": "falcon_h1", "vocab_size": 96, "hidden_size": 64, "num_hidden_layers": 2,
       "num_attention_heads": 10, "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 96, "mamba_n_heads": 4,
       "mamba_d_head": 16, "mamba_d_ssm": 64, "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_n_groups": 2,
       "mamba_chunk_size": 8, "mamba_expand": 2, "mamba_rms_norm": True, "mamba_norm_before_gate": False,
       "mamba_conv_bias": True, "mamba_proj_bias": False, "attention_bias": False, "mlp_bias": False,
       "projectors_bias": False, "tie_word_embeddings": False, "rope_scaling": None, "attn_layer_indices": None,
       "hidden_act": "silu", "rope_theta": 100000000000, "rms_norm_eps": 1e-5, "embedding_multiplier": 5.656854249492381,
       "lm_head_multiplier": 0.0078125, "ssm_in_multiplier": 0.25, "ssm_out_multiplier": 0.08838834764831845,
       "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5, 0.3535533905932738],
       "attention_in_multiplier": 1, "attention_out_multiplier": 0.0375, "key_multiplier": 0.011048543456039804,
       "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
       "reduced": ["vocab_size"], "published": {"vocab_size": 192}, "share": {"chips": 2, "of": ["vocab_size"]},
       "assumed": {}, "deployment": "none: a toy", "kind": "serve",
       "serve": {"weight_dtype": "bfloat16", "slots": 4, "positions_per_slot": 64, "page_size": 8, "state_dtype": "float32"}}

WRAPPER = '''"""The falcon_h1 family with the keys' multiplier left out of its reference (tests only)."""
import functools

from benchmark import reference
from benchmark.families import falcon_h1 as real

SERVE_LOGITS_TOLERANCE = real.SERVE_LOGITS_TOLERANCE
program_config, build_serve, rehearse_serve = real.program_config, real.build_serve, real.rehearse_serve


def logits(params, config, tokens, rows):
    return real.logits(params, dict(config, key_multiplier=1.0), tokens, rows)


loss_and_logits = functools.partial(reference.loss_and_logits, logits)
'''


def _run(root, cell, traced=0):
    spec = load_cell(cell, root)
    rec, correct, attempted, failed, notes = serve_cell.run_cell(spec, jax.devices()[:1], 2**31 + 29, 1.0, traced,
                                                                 time.perf_counter())
    return spec, rec, correct, attempted, failed, notes


def test_the_family_runs_a_toy_cell_to_correct_by_files_and_entries_alone(tmp_path):
    """The runner as it is: every layer's pages and state through the normal
    path, and the check's prompt (59 of 64 positions: inside a chunk of 8)
    against the reference."""
    root = make_tiny_root(str(tmp_path / "root"))
    cell = test_bm_hybrid._add_cell(root, "toyfalcon", TOY)
    spec, rec, correct, attempted, failed, notes = _run(root, cell, traced=2)
    family = load_family("falcon_h1", root)
    assert correct and attempted > 0 and failed == 0, notes
    assert notes["compiles_in_window"] == 0, "every rung and the decode step were compiled by warm()"
    assert 0 < notes["reference"]["logits_max_abs_diff_over_max"] < family.SERVE_LOGITS_TOLERANCE
    assert notes["reference"]["tolerance"] == family.SERVE_LOGITS_TOLERANCE and notes["reference"]["prompt_tokens"] == 59
    counters = notes["session_counters"]           # the trace session read the engine's counters
    assert counters["decode_steps"] > 0 and counters["ssm_state_bytes_rw"] > 0 and counters["prefill_scan_chunks"] > 0
    assert counters["moe_assignments"] == counters["moe_layer_steps"] == 0, "a dense model: nothing is routed"
    assert counters["prefill_scan_chunks"] * 8 == counters["prefill_bucket_tokens"] >= counters["prefill_tokens_real"]
    line = result_object(spec, rec, jax.devices()[:1], correct=correct, attempted=attempted, failed=failed, traced=2)
    assert {"serve_tokens_per_s", "setup_s", "decode_step_ms_p50.batch"} <= set(line["metrics"])
    assert line["metrics"]["serve_tokens_per_s"]["value"] > 0


def test_the_keys_multiplier_left_out_of_the_reference_reads_not_correct(tmp_path):
    root = make_tiny_root(str(tmp_path / "root"))
    cell = test_bm_hybrid._add_cell(root, "toyfalcon_no_key_multiplier", dict(TOY, model="falcon_h1_no_key_multiplier"), WRAPPER)
    _spec, _rec, correct, _attempted, _failed, notes = _run(root, cell)
    assert not correct
    assert notes["ledger"]["problems"] == [] and notes["compiles_in_window"] == 0
    assert notes["reference"]["logits_max_abs_diff_over_max"] > 5 * notes["reference"]["tolerance"]


# ------------------------------------------------- the real configuration file
def test_the_real_configuration_is_the_catalogs_row_cut_as_the_issue_says():
    spec = load_cell(CELL, REPO)
    c, family = spec.config, spec.family()
    assert spec.chips == 1 and spec.traffic_name == "batch_decode_closed160" and spec.traffic["kind"] == "closed_loop"
    assert {m["name"] for m in spec.end_to_end} == {"serve_tokens_per_s", "setup_s"}
    # every number of the catalog's config under its key, but for the two reduced
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(path):
        with open(path) as f:
            catalog = next(r for r in map(json.loads, f) if r["name"] == "Falcon-H1-34B-Instruct")
        assert c["source"] == catalog["source_url"]
        assert {k: v for k, v in catalog["config"].items() if c[k] != v} == {"num_hidden_layers": 72, "vocab_size": 261120} \
            == c["published"]
    assert c["reduced"] == ["num_hidden_layers", "vocab_size"] and (c["num_hidden_layers"], c["vocab_size"]) == (6, 130560)
    assert c["share"] == {"chips": 2, "of": ["vocab_size"], "index": 0}
    widths = {"hidden_size": 5120, "intermediate_size": 21504, "num_attention_heads": 20, "num_key_value_heads": 4,
              "head_dim": 128, "mamba_d_ssm": 4096, "mamba_n_heads": 32, "mamba_d_head": 128, "mamba_d_state": 256,
              "mamba_n_groups": 2, "mamba_d_conv": 4, "mamba_chunk_size": 128, "mamba_expand": 2, "mlp_expansion_factor": 8}
    assert {k: c[k] for k in widths} == widths
    # the floors of the model-configs guide: four layers (the pattern's period is one), an eighth of the vocabulary
    assert c["num_hidden_layers"] >= 4 and 8 * c["vocab_size"] >= c["published"]["vocab_size"]
    assert all(key in c["assumed"] for key in ("held", "state_dtype", "init", "page_size", "positions_per_slot", "slots"))
    assert "twelve pipeline stages of six layers" in c["deployment"]
    cfg = family.program_config(c)
    assert (cfg.d_inner, cfg.conv_dim, cfg.in_proj_dim, cfg.in_segments) == (4096, 5120, 9248, (4096, 4096, 512, 512, 32))
    assert cfg.ssm_state_shape == (256, 4096) and cfg.conv_tail_shape == (3, 5120) and cfg.vocab_size == 130560
    # ISSUE 43's arithmetic, in millions of parameters and in GB
    M = 1e6
    assert round(family.mamba_params(c) / M, 2) == 68.35 and round(family.attention_params(c) / M, 2) == 31.46
    assert round(family.mlp_params(c) / M, 2) == 330.30 and round(family.layer_params(c) / M, 2) == 430.12
    assert round(6 * family.layer_params(c) / M, 1) == 2580.7 and round(2 * 130560 * 5120 / M, 1) == 1336.9
    assert round(family.weight_bytes(c) / 1e9, 2) == 7.84
    serve = c["serve"]
    assert (serve["slots"], serve["positions_per_slot"], serve["page_size"], serve["state_dtype"]) == (128, 1536, 16, "float32")
    assert family.state_bytes_per_slot(c, serve) == 6 * (256 * 4096 * 4 + 3 * 5120 * 2)
    assert round(128 * family.state_bytes_per_slot(c, serve) / 1e9, 2) == 3.24
    assert family.kv_bytes_per_position(c) == 6 * 2048 and round(128 * 1536 * 6 * 2048 / 1e9, 2) == 2.42
    # a decode step: 5.16 GB of layer weights, 1.34 of head, 6.44 of state read and written, the live pages, the logits
    live = 128 * 400 / 16
    moved = family.decode_step_bytes(c, serve, kv_pages_read_per_layer=live)
    assert round(2 * 128 * family.state_bytes_per_slot(c, serve) / 1e9, 2) == 6.49       # 6.44 of state + 0.05 of tails
    assert 13.5e9 < moved < 13.9e9
    assert moved - family.decode_step_bytes(c, serve, kv_pages_read_per_layer=0) == live * 16 * 6 * 2048
    assert family.ssm_step_bytes(c, serve) == 128 * (2 * 256 * 4096 * 4 + 3 * 4096 * 4 + 2 * 2 * 256 * 4)
    assert family.decode_step_flops(c, 128) < 1e12 < family.prefill_bucket_flops(c, 512)


def test_what_the_program_allocates_is_what_the_family_counts():
    spec = load_cell(CELL, REPO)
    c, family = spec.config, spec.family()
    from vescale_tpu.models.falcon_h1 import init_params, prefill_counters
    from vescale_tpu.serve.hybrid_engine import hybrid_cache_config, prefill_buckets

    cfg = family.program_config(c)
    tree = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.key(0))
    assert sum(int(a.size) * a.dtype.itemsize for a in jax.tree_util.tree_leaves(tree)) == family.weight_bytes(c)
    assert sum(int(a.size) for a in jax.tree_util.tree_leaves(tree)) == family.param_count(c)
    assert tree["lm_head"]["kernel"].shape == (5120, 130560) and tree["embed_tokens"]["embedding"].shape == (130560, 5120)
    assert tree["layers_5"]["mamba"]["in_proj"].shape == (5120, 9248) and tree["layers_0"]["self_attn"]["q_proj"].shape == (5120, 2560)
    kc = hybrid_cache_config(cfg, num_slots=128, page_size=16, pages_per_slot=96)
    assert not kc.latent and (kc.layers, kc.kv_heads, kc.head_dim, kc.max_seq_len) == (6, 4, 128, 1536)
    assert [(name, layers, tuple(shape)) for name, layers, shape, _dt in kc.slot_state] == \
        [("ssm", 6, (256, 4096)), ("conv", 6, (3, 5120))], "every layer owns a row of the state arrays"
    state = sum(layers * int(np.prod(shape)) * np.dtype(dt).itemsize for _n, layers, shape, dt in kc.slot_state)
    assert state == family.state_bytes_per_slot(c, c["serve"])
    pool = 2 * kc.layers * kc.pool_pages * kc.page_size * kc.kv_heads * kc.head_dim * 2
    assert round(pool / 1e9, 2) == 2.42 and pool // kc.pool_pages // kc.page_size == family.kv_bytes_per_position(c)
    assert prefill_buckets(cfg.mamba_chunk_size, kc.max_seq_len) == [128, 256, 512, 1024, 1536]
    assert prefill_counters(cfg, 512) == {"prefill_scan_chunks": 4}


def test_the_traffic_file_is_batch_decode_closed80s_lengths_on_twice_the_slots():
    spec = load_cell(CELL, REPO)
    with open(os.path.join(REPO, "benchmark", "traffic", "batch_decode_closed80.json")) as f:
        older = json.load(f)
    assert all(spec.traffic[k] == older[k] for k in ("kind", "prompt_len", "output_len", "max_total", "pairing_seed"))
    traffic = {k: spec.traffic[k] for k in ("clients", "lead_in_s", "pool", "first_wave")}
    assert traffic == {"clients": 160, "lead_in_s": 8.0, "pool": 256, "first_wave": 128}
    vocab = spec.config["vocab_size"]
    pool = trafficgen.closed_loop_requests(spec.traffic, 2**31 + 5, vocab)
    prompts, outputs = np.array([len(r.prompt) for r in pool]), np.array([r.max_new_tokens for r in pool])
    assert len(pool) == 256 and prompts.min() >= 8 and prompts.max() <= 512 and outputs.min() >= 8 and outputs.max() <= 1024
    assert (prompts + outputs).max() <= spec.traffic["max_total"] == spec.config["serve"]["positions_per_slot"]
    assert all(0 < t < vocab for r in pool for t in r.prompt), "ids lie in the slice of the vocabulary held here"
    # every seed sends the same multiset of lengths: a seed chooses the order
    other = trafficgen.closed_loop_requests(spec.traffic, 12345, vocab)
    assert sorted((len(r.prompt), r.max_new_tokens) for r in other) == sorted((len(r.prompt), r.max_new_tokens) for r in pool)
    # ShareGPT's means as the source gives them (the cell's ``why``), and what the clips to this cache leave on the grid
    assert "161 in, 338 out" in next(w for w in load_benchmark(REPO)["workloads"] if w["name"] == CELL)["why"]
    assert (round(prompts.mean()), round(outputs.mean())) == (145, 301) and "145 and 301" in spec.traffic["assumed"]["clips"]


# ------------------------------------------------------------------ the readers
ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2, "u16": 2, "f32": 4, "s32": 4, "u32": 4, "s64": 8,
            "u64": 8, "f64": 8}


def _shape_bytes(text):
    return sum(int(np.prod([int(d) for d in dims.split(",") if d] or [1])) * ITEMSIZE[dt]
               for dt, dims in re.findall(r"(pred|[suf]\d+|bf16)\[([\d,]*)\]", text))


def _ops_as_the_trace_names_them(hlo_text):
    """``(bytes, text)`` of each instruction of an HLO module, written as the
    chip's trace names a device event: the output's shape, then every operand
    with its shape."""
    shapes, out = {}, []
    for line in hlo_text.splitlines():
        m = re.match(r"\s*(?:ROOT )?([\w.\-]+) = (\([^=]*?\)|\S+) ([\w\-]+)\((.*?)\)(?:, |$)", line)
        if not m:
            continue
        name, shape, op, operands = m.groups()
        shapes[name] = shape
        if op in ("parameter", "get-tuple-element", "tuple", "constant", "bitcast", "broadcast", "iota", "reshape"):
            continue
        text = f"%{name} = {shape} {op}(" + ", ".join(f"{shapes.get(o.strip(), '')} %{o.strip()}" for o in operands.split(",")) + ")"
        out.append((_shape_bytes(text), text))
    return out


def test_the_table_of_shapes_leaves_none_of_the_decode_programs_large_ops_under_other():
    """The decode program traced on the CPU at the cell's shapes (shapes, no
    arrays; the XLA legs: the table names those too).  What stays under
    ``other`` is of the residual stream's own size (its norms and sums): nothing
    that reads a weight, the state or the pool."""
    spec = load_cell(CELL, REPO)
    c, family = spec.config, spec.family()
    _sizes, programs = family.rehearse_serve(spec.name, c, c["serve"], jax.devices())
    title, lowered = programs[-1]
    assert "decode step, 128 slots x 1536 positions" in title
    signatures = family.mechanism_signatures(c, c["serve"])
    by, largest_other = {}, 0
    for nbytes, text in _ops_as_the_trace_names_them(lowered.as_text(dialect="hlo")):
        mechanism = family.mechanism_of(text, signatures)
        by[mechanism] = by.get(mechanism, 0) + nbytes
        if mechanism == "other":
            largest_other = max(largest_other, nbytes)
    stream = 128 * 5120 * 4
    assert largest_other <= 3 * stream, "an op of the stream reads two of its size and writes one"
    assert set(by) == {"mamba", "attention", "mlp", "head", "other"} and by["other"] < 0.02 * sum(by.values()), by
    assert by["mamba"] > by["mlp"] > by["head"], by
    # the names the chip's trace would show for the kernels and for the weights' prefetch slices
    of = lambda text: family.mechanism_of(text, signatures)
    assert of("%ssm_step.3 = (f32[6,128,256,4096]{3,2,1,0}, f32[128,1,4096]{2,1,0}) custom-call(s32[1] %l)") == "mamba"
    assert of("%paged_decode.3 = f32[128,20,128]{2,1,0} custom-call(s32[1] %l, s32[128] %n)") == "attention"
    assert of("%slice-done.4 = bf16[5120,2312]{1,0} slice-done((bf16[5120,2312], bf16[5120,9248], u32[]) %s)") == "mamba"
    assert of("%fusion.7 = f32[128,21504]{1,0} fusion(bf16[5120,21504]{1,0} %gate_proj, f32[128,5120] %h)") == "mlp"
    assert of("%fusion.9 = f32[128,130560]{1,0} fusion(bf16[5120,130560]{1,0} %lm_head, f32[128,5120] %x)") == "head"
    assert of("%copy-done.71 = bf16[5120]{0} copy-done((bf16[5120]{0}, bf16[5120]{0}, u32[]) %copy-start.7)") == "other"
    # a prefill's table is of its rung's rows
    rung = family.mechanism_signatures(c, c["serve"], 512)
    assert family.mechanism_of("%fusion.2 = f32[512,32,128]{2,1,0} fusion(f32[512,4096] %x)", rung) == "mamba"
    assert family.mechanism_of("%flash_fwd.2 = bf16[20,512,128]{2,1,0} custom-call(bf16[20,512,128] %q)", rung) == "attention"


OPS_FILE = os.path.join(REPO, "benchmark", "testdata", "falconh1_decode_ops.json")


def test_the_table_of_shapes_names_the_mechanism_of_the_chips_decode_ops():
    """Names and device times of the ops of one traced decode program (those of
    a microsecond and more), as this PR's chip run recorded them
    (``benchmark/testdata/falconh1_decode_ops.json``; 21.7 ms)."""
    spec = load_cell(CELL, REPO)
    family = spec.family()
    signatures = family.mechanism_signatures(spec.config, spec.config["serve"])
    with open(OPS_FILE) as f:
        ops = json.load(f)
    by = {}
    for op in ops:
        mechanism = family.mechanism_of(op["name"], signatures)
        by[mechanism] = by.get(mechanism, 0.0) + op["ns"]
    whole = sum(by.values())
    assert 21e6 < whole < 22.5e6 and by["other"] / whole < 0.05, by
    assert 0.5 < by["mamba"] / whole < 0.56 and 0.22 < by["mlp"] / whole < 0.27, by
    assert 0.09 < by["attention"] / whole < 0.12 and 0.07 < by["head"] / whole < 0.10, by
    kernels = [op["ns"] for op in ops if op["name"].startswith("%ssm_step")]
    assert len(kernels) == 6 and len([op for op in ops if op["name"].startswith("%paged_decode")]) == 6
    # the roofline share the reader would give this program's kernel: over 75%, under 100%
    share = family.ssm_step_bytes(spec.config, spec.config["serve"]) / (sum(kernels) / 6 * 1e-9 * 819e9)
    assert 0.75 < share < 1.0, share


def test_the_readers_arithmetic_on_a_recorded_session():
    """Microseconds: two decode launches and one prefill of the 256 rung, their
    programs on the ``XLA Modules`` line and the ops inside them."""
    (reader,) = [m for m in discover(os.path.join(REPO, "benchmark", "layer_metrics")) if NEW_METRICS[0] in m.METRICS]
    assert list(reader.METRICS) == NEW_METRICS
    spec = load_cell(CELL, REPO)
    c, family = spec.config, spec.family()
    steps, slots = 10, 128
    pages = slots * (400 // 16) * steps                    # one layer's, as the engine counts them
    counters = {"decode_steps": steps, "ssm_state_bytes_rw": 2 * slots * family.state_bytes_per_slot(c, c["serve"]) * steps,
                "prefill_scan_chunks": 2, "decode_pages_read": pages, "decode_pages_capacity": slots * 96 * steps}
    SSM = "%ssm_step.1 = (f32[6,128,256,4096]{3,2,1,0}, f32[128,1,4096]{2,1,0}) custom-call(s32[1] %l, f32[128,1,4096] %d)"
    PAGED = "%paged_decode.3 = f32[128,20,128]{2,1,0} custom-call(s32[1] %l, s32[128] %n)"
    MLP = "%fusion.7 = f32[128,21504]{1,0} fusion(bf16[5120,21504]{1,0} %gate_proj, f32[128,5120] %h)"
    HEAD = "%fusion.9 = f32[128,130560]{1,0} fusion(bf16[5120,130560]{1,0} %lm_head, f32[128,5120] %x)"
    NORM = "%fusion.11 = f32[128,5120]{1,0} fusion(f32[128,5120] %x)"
    SCAN = "%fusion.2 = f32[256,32,128]{2,1,0} fusion(f32[256,4096] %x)"
    FLASH = "%flash_fwd.2 = bf16[20,256,128]{2,1,0} custom-call(bf16[20,256,128] %q)"
    modules = [(1000, 3000, "jit_decode(1)"), (4000, 6000, "jit_decode(1)"), (7000, 8000, "jit_prefill(9)")]
    host = [(900, 950, "vs.serve-decode.launch", {"launch": 1}), (3100, 3150, "vs.serve-decode.launch", {"launch": 2}),
            (6100, 6150, "vs.serve-prefill.launch", {"launch": 3, "rung": 256, "slot": 5})]
    ops = [(1000, 1400, SSM), (1400, 1600, PAGED), (1600, 2400, MLP), (2400, 2800, HEAD), (2800, 3000, NORM),   # 2000
           (4000, 4600, SSM), (4600, 4800, PAGED), (4800, 5600, MLP), (5600, 5800, HEAD), (5800, 6000, NORM),   # 2000
           (7000, 7300, SCAN), (7300, 7500, FLASH), (7500, 8000, MLP.replace("[128,", "[256,")),                 # a prefill's
           (9000, 9500, SSM)]                                                                                    # outside any program
    run = types.SimpleNamespace(traffic_kind="closed_loop", slots=128, padded_prompt_len=1536, device_kind="TPU v5 lite",
                                session=types.SimpleNamespace(profile=_trace(ops, modules, host)),
                                _session_reduced={"counters": counters})
    got = reader.read(run)
    assert set(got) == set(NEW_METRICS)
    assert got["h1_state_gb_per_step.batch"] == pytest.approx(2 * slots * family.state_bytes_per_slot(c, c["serve"]) / 1e9)
    assert round(got["h1_state_gb_per_step.batch"], 2) == 6.49
    assert got["h1_ssm_device_share.batch"] == pytest.approx(25.0) and got["h1_attn_device_share.batch"] == pytest.approx(10.0)
    assert got["h1_mlp_device_share.batch"] == pytest.approx(40.0)
    assert got["h1_scan_prefill_device_share.batch"] == pytest.approx(30.0)
    assert got["h1_ssm_step_roofline.batch"] == pytest.approx(100 * family.ssm_step_bytes(c, c["serve"]) / (500e-6 * 819e9))
    moved = family.decode_step_bytes(c, c["serve"], kv_pages_read_per_layer=pages / steps)
    assert got["h1_step_hbm_roofline_share.batch"] == pytest.approx(100 * moved / (2000e-6 * 819e9))
    # a program without the model's counters (this PR's parent; another family's run) leaves them all out
    run._session_reduced = {"counters": {"decode_steps": 5, "ssm_state_bytes_rw": 7, "moe_assignments": 9}}
    assert reader.read(run) == {}
    run._session_reduced = {"counters": dict(counters, decode_steps=0)}
    assert reader.read(run) == {}
    # another cache geometry than the configuration's (Granite's 64 slots): the counter's metric alone
    other = types.SimpleNamespace(traffic_kind="closed_loop", slots=64, padded_prompt_len=1536, device_kind="TPU v5 lite",
                                  session=run.session, _session_reduced={"counters": counters})
    assert set(reader.read(other)) == {"h1_state_gb_per_step.batch"}
    assert reader.read(types.SimpleNamespace(traffic_kind="open_loop")) == {}
    assert reader.read(types.SimpleNamespace(traffic_kind="closed_loop", session=None)) == {}


def test_granites_reader_reads_nothing_of_this_cells_run_that_the_cell_does_not_list():
    """Both models report ``ssm_state_bytes_rw``; the harness keeps of a reader's
    answer what the cell's lists name, and Granite's metrics do not list this cell."""
    bench = load_benchmark(REPO)
    (granites,) = [m for m in discover(os.path.join(REPO, "benchmark", "layer_metrics")) if "moe_held_share.batch" in m.METRICS]
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", ())}
    assert not listed & set(granites.METRICS) and set(NEW_METRICS) <= listed


def test_the_new_entries_of_benchmark_json_are_at_the_end_and_name_the_cell():
    bench = load_benchmark(REPO)
    n = len(NEW_METRICS)
    assert bench["workloads"][-1]["name"] == CELL and bench["configs"][-1]["name"] == CONFIG
    assert bench["workloads"][-1]["chips"] == 1 and bench["workloads"][-1]["traffic"] == "batch_decode_closed160"
    assert bench["configs"][-1]["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert bench["configs"][-1]["file"] == f"benchmark/configs/{CONFIG}.json"
    assert [m["name"] for m in bench["per_layer"][-n:]] == NEW_METRICS
    assert all(m["workloads"] == [CELL] and m["moves"] == "serve_tokens_per_s" for m in bench["per_layer"][-n:])
    (reader,) = [m for m in discover(os.path.join(REPO, "benchmark", "layer_metrics")) if NEW_METRICS[0] in m.METRICS]
    for m in bench["per_layer"][-n:]:
        assert (m["unit"], m["layer"]) == (reader.METRICS[m["name"]]["unit"], reader.METRICS[m["name"]]["layer"])
        assert m["layer"] in {x["layer"] for x in bench["per_layer"][:-n]}, "the layer's name as the benchmark already has it"
    assert all(len(x["why"]) <= 200 for x in bench["workloads"] + bench["configs"])
    listing = [m["name"] for m in bench["end_to_end"] + bench["per_layer"][:-n] if CELL in m.get("workloads", ())]
    for m in bench["end_to_end"] + bench["per_layer"][:-n]:
        lists_all = all(w in m.get("workloads", ()) for w in CLOSED_LOOP)
        assert (CELL in m.get("workloads", ())) == lists_all, m["name"]
        assert not lists_all or m["workloads"][-1] == CELL
    assert listing[0] == "serve_tokens_per_s" and len(listing) == 1 + 22 and all(x.endswith(".batch") for x in listing[1:])
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1 and len(bench["workloads"]) == 8
    # what was there is as it was: the benchmark without this PR's entries is the parent's
    before = _before_this_pr(REPO)
    assert [w["name"] for w in before["workloads"]] == [w["name"] for w in bench["workloads"][:-1]]
    assert before["configs"] == bench["configs"][:-1] and len(before["per_layer"]) == len(bench["per_layer"]) - n
    assert all(before[key] == bench[key] for key in bench if key not in ("configs", "workloads", "end_to_end", "per_layer"))
