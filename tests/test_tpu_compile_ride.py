"""The serve cells' whole programs compile for the chip: the steps that carry a prompt.

DeepSeek-LLM's riding step behind ``ServeEngine``, Falcon-H1's and Granite's behind ``HybridServeEngine``
(``serve_ride``), and with them Falcon-H1's decode step and a rung.  Nothing executes:
each case builds the cell's engine and lowers its programs from ``ShapeDtypeStruct``s on one described v5e device, as
``benchmark/rehearse.py`` does (the ``cells_programs`` fixture of ``tests/conftest.py``: ONE build a cell and module,
whatever the number of cases that compile a program of it), and compiles one.  A case holds the compiled text to what
the cell's programs must be: which kernels are in it, no copy of a pool or a state, the bytes of its arguments and
temporaries beside the chip's 16 GB.  The kernels alone at the cells' widths are ``tests/test_tpu_compile.py``'s; a
later family's cases go into the file of the three (this, ``tests/test_tpu_compile_programs.py``, ``tests/test_tpu_compile_latent.py``) that then sums to
the fewest seconds (ROADMAP D19: no test file over 6% of tier-1's summed seconds), all of a family in ONE file.
"""

import pytest

import jax
import jax.numpy as jnp


@pytest.mark.parametrize("program,kernels_in_it", [("decode step", 12), ("bucket of 512", 6)], ids=["decode", "rung512"])
def test_falcon_h1s_decode_program_and_a_rung_compile_at_the_cells_size(chip, cells_programs, program, kernels_in_it):
    """``falconh1_34b_serve_batch``'s decode step (128 slots x 1536 positions:
    six ``ssm_step`` and six ``paged_decode`` kernels) and the 512 rung of its
    prefill ladder (six grouped-query flash forwards at 20 / 4 heads)."""
    family, config, sizes, programs, _engine = cells_programs("falconh1_34b_serve_batch")
    titles = [title for title, _ in programs]
    assert sum("prefill, bucket of" in t for t in titles) == 5 and "decode step, 128 slots x 1536 positions" in titles[-1]
    assert sizes["weights_bytes"] == family.weight_bytes(config)
    assert sizes["slot_state_bytes"] == 128 * family.state_bytes_per_slot(config, config["serve"])
    (lowered,) = [low for title, low in programs if program in title]
    compiled = lowered.compile()
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == kernels_in_it
    cells_programs.assert_in_place_and_fits(compiled, sizes, "bf16[6,12289,16,4,128]")        # 1.2 GB a pool


def test_deepseeks_step_that_carries_a_prompt_compiles_at_the_cells_size_with_one_product_a_weight(chip, cells_programs):
    """``deepseek7b_serve_batch``'s decode step with the 128 rung's prompt in it (PR 53: 32 decode rows and 128
    prompt rows, one array before every weight's product): eight ``paged_decode`` and eight flash forwards, no
    copy of a pool, and every product of the stack over all 160 rows, the head's over the 32 steps' rows and the
    prompt's last: a weight crosses the HBM once for both.  (The engine is the one the cell's family builds for
    ``benchmark/rehearse.py``, from shapes alone.)"""
    import re


    _family, _config, _sizes, programs, engine = cells_programs("deepseek7b_serve_batch")
    cache = engine.cache
    S, page, rung = cache.num_slots, cache.config.page_size, 128
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)
    with cells_programs.on_a_tpu():     # (traced here too, under the same answers: the flash forward asks for its platform while it is traced)
        lowered = engine._ride_fn.lower(
            engine.params, cache.k.data, cache.v.data, i32(S, cache.config.pages_per_slot), i32(S), i32(S), i32(S),
            i32(rung), i32(), i32(rung // page), i32())
    assert engine.rides and engine.kernel_decode and (S, rung) == (32, 128)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 16
    assert not [line for line in text.splitlines() if " copy(" in line and "= bf16[8,3073,16,32,128]" in line]
    products = re.findall(r"= bf16\[(\d+),(\d+)\]\S* convolution\(", text)
    assert len(products) == 7 * 8 + 1 and sorted(set(products)) == [("160", "11008"), ("160", "4096"), ("33", "102400")]
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= 2 * 8 * 3073 * 16 * 32 * 128 * 2 and memory.temp_size_in_bytes < 64 << 20, memory
    # ... and the step without a prompt is the program it was: its products over the 32 rows
    (step,) = [low for title, low in programs if "decode step" in title]
    assert set(re.findall(r"= bf16\[(\d+),\d+\]\S* convolution\(", step.compile().as_text())) == {"32"}


def test_falcon_h1s_step_that_carries_a_prompt_compiles_at_the_cells_size_with_one_product_a_weight(chip, cells_programs):
    """``falconh1_34b_serve_batch``'s decode step with the 128 rung's prompt in it (PR 55: 128 decode rows and 128
    prompt rows, one array before every weight's product): six ``ssm_step``, six ``paged_decode`` and six flash
    forwards, no copy of a pool or of the state, every product of the stack over all 256 rows and none over 128
    beside it, the head's over the 128 steps' rows and the prompt's last: a weight crosses the HBM once for both.
    (The engine is the one the cell's family builds for ``benchmark/rehearse.py``, from shapes alone.)"""
    import re


    _family, _config, sizes, programs, engine = cells_programs("falconh1_34b_serve_batch")
    cache = engine.cache
    S, page, rung = cache.num_slots, cache.config.page_size, 128
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)
    with cells_programs.on_a_tpu():     # (traced here too, under the same answers: the flash forward asks for its platform while it is traced)
        lowered = engine._ride_fn.lower(engine.params, *engine._held(), i32(S, cache.config.pages_per_slot), i32(S), i32(S),
                                        i32(S), i32(rung), i32(), i32(rung // page), i32())
    assert engine.rides and engine.kernel_decode and engine.kernel_ssm_step and (S, rung) == (128, 128)
    assert lowered.as_text().lstrip().startswith("module @jit_decode ")
    compiled = lowered.compile()
    text = compiled.as_text()
    kernel_calls = re.findall(r"%([a-z_.]+?)[.\d]* = [^\n]*custom_call_target=\"tpu_custom_call\"", text)
    assert sorted(kernel_calls) == ["paged_decode"] * 6 + ["ssm_step"] * 6 + ["vs.attn"] * 6      # (the flash forward bears its scope's name)
    cells_programs.assert_in_place_and_fits(compiled, sizes, "bf16[6,12289,16,4,128]")
    assert not [line for line in text.splitlines() if " copy(" in line and "= f32[6,128,256,4096]" in line], "nor of the state"
    products = set(re.findall(r"= \w+\[(\d+),(\d+)\]\S* convolution\(", text))
    # in_proj, q and k/v, o and out_proj and down_proj, gate and up: all 256 rows; the head 128 + 1
    assert products == {("256", "9248"), ("256", "2560"), ("256", "512"), ("256", "5120"), ("256", "21504"), ("129", "130560")}
    assert compiled.memory_analysis().temp_size_in_bytes < 128 << 20
    # ... and the step without a prompt is the program it was: its products over the 128 rows
    (step,) = [low for title, low in programs if "decode step" in title]
    assert set(re.findall(r"= \w+\[(\d+),\d+\]\S* convolution\(", step.compile().as_text())) == {"128"}


def test_granites_step_that_carries_a_prompt_compiles_at_the_cells_size_with_one_product_a_weight(chip, cells_programs):
    """``granite4hsmall_serve_batch``'s decode step with the 256 rung's prompt in it (PR 62: 64 decode rows and 256
    prompt rows, one array before every weight's product): nine ``ssm_step``, one ``paged_decode`` and one flash
    forward, no copy of a pool or of the state, every product of the stack over all 320 rows and none over 64 or 256
    beside it: ``W_in`` nine times, ``W_out`` / ``W_o`` / ``W_q`` / the shared expert's down 21, its gate and up 20,
    the router ten, and the 36 held experts' three matrices ONCE a layer, in the padded form that 320 rows x 10 over
    36 experts make a candidate for; the head's over the 64 steps' rows and the prompt's last.  A weight crosses the
    HBM once for both, the expert layer's 72% of them too.  (The engine is the one the cell's family builds for
    ``benchmark/rehearse.py``, from shapes alone.)"""
    import collections
    import re

    from vescale_tpu.moe import dropless


    _family, _config, sizes, programs, engine = cells_programs("granite4hsmall_serve_batch")
    cache, c = engine.cache, engine.config
    S, page, rung = cache.num_slots, cache.config.page_size, 256
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)
    with cells_programs.on_a_tpu():     # (traced here too, under the same answers: the flash forward asks for its platform while it is traced)
        lowered = engine._ride_fn.lower(engine.params, *engine._held(), i32(S, cache.config.pages_per_slot), i32(S), i32(S),
                                        i32(S), i32(rung), i32(), i32(rung // page), i32())
    assert engine.rides and engine.kernel_decode and engine.kernel_ssm_step and (S, rung) == (64, 256)
    # which form the expert layer holds at each rung's rows, the step's beside them (and the step's alone): by the shapes
    forms = [dropless.expert_form(rows, c.num_experts_per_tok, c.experts_held, c.num_experts) for rows in (S, *engine._prompt_rows.values())]
    assert forms == [dropless.ALL_ON_ALL, dropless.PADDED_OR_SORTED] + [dropless.SORTED] * 3
    assert engine._grouped_layers == {rows: 10 for rows in (S + 512, S + 1024, S + 1536)}
    assert lowered.as_text().lstrip().startswith("module @jit_decode ")
    compiled = lowered.compile()
    text = compiled.as_text()
    kernel_calls = re.findall(r"%([a-z_.]+?)[.\d]* = [^\n]*custom_call_target=\"tpu_custom_call\"", text)
    assert sorted(kernel_calls) == ["paged_decode"] + ["ssm_step"] * 9 + ["vs.attn"]      # (the flash forward bears its scope's name)
    cells_programs.assert_in_place_and_fits(compiled, sizes, "bf16[1,6145,16,8,128]")        # 0.2 GB a pool
    assert not [line for line in text.splitlines() if " copy(" in line and "= f32[9,64,128,8192]" in line], "nor of the state"
    products = collections.Counter(re.findall(r"= \w+\[([\d,]+)\]\S* convolution\(", text))
    weights = {shape: n for shape, n in products.items() if shape.split(",")[0] in ("320", "36", "65")}
    # in_proj; out_proj x9, o, q and the shared down x10; k and v; the shared gate and up; the router; the held experts'
    # down, and their gate and up, over 36 x 128 padded places; the head 64 + 1
    assert weights == {"320,16768": 9, "320,4096": 21, "320,1024": 2, "320,1536": 20, "320,72": 10, "36,128,4096": 10,
                       "36,128,768": 20, "65,50176": 1}
    # ... and nothing over the step's 64 rows or the rung's 256 alone but the chunked scan's own products (C B^T of a
    # chunk and the chunk states: the prompt's rows with each other, no weight in them)
    assert {shape for shape in products if shape not in weights} == {"256,256", "128,64,256", "128,128,64"}
    assert ".remat" not in text, "no product is run anew for a second reader (PERF.md section 6, PR 55)"
    assert compiled.memory_analysis().temp_size_in_bytes < 128 << 20
    # ... and the step without a prompt is the program it was: its products over the 64 rows
    (step,) = [low for title, low in programs if "decode step" in title]
    assert {shape.split(",")[0] for shape in re.findall(r"= \w+\[([\d,]+)\]\S* convolution\(", step.compile().as_text())} <= {"64", "36"}
