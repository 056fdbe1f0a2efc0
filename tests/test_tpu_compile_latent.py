"""The serve cells' whole programs compile for the chip: the families over a latent pool.

LongCat-Flash's decode step and three rungs (eight latent pool layers), Ling's decode step (one latent pool layer
beside the delta rule's states).  Nothing executes:
each case builds the cell's engine and lowers its programs from ``ShapeDtypeStruct``s on one described v5e device, as
``benchmark/rehearse.py`` does (the ``cells_programs`` fixture of ``tests/conftest.py``: ONE build a cell and module,
whatever the number of cases that compile a program of it), and compiles one.  A case holds the compiled text to what
the cell's programs must be: which kernels are in it, no copy of a pool or a state, the bytes of its arguments and
temporaries beside the chip's 16 GB.  The kernels alone at the cells' widths are ``tests/test_tpu_compile.py``'s; a
later family's cases go into the file of the three (this, ``tests/test_tpu_compile_programs.py``, ``tests/test_tpu_compile_ride.py``) that then sums to
the fewest seconds (ROADMAP D19: no test file over 6% of tier-1's summed seconds), all of a family in ONE file.
"""

import pytest



@pytest.mark.parametrize("program", ["decode step", "rung of 512 positions", "rung of 2048 positions", "rung of 4096 positions"],
                         ids=["decode", "rung512", "rung2048", "rung4096"])
def test_longcats_decode_program_and_its_rungs_compile_at_the_cells_size_and_fit_beside_the_weights(chip, cells_programs, program):
    """``longcatflash_serve_reasoning``'s decode step (128 slots: eight
    ``paged_decode_latent`` at 64 heads, one a SUBLAYER; its sixteen experts a
    layer go all on all, no kernel) and three rungs of its prefill ladder (eight
    ``mla_flash_fwd`` and four ``grouped_swiglu`` over experts of 6144 x 2048: a
    rung over 1,024 rows takes the routed branch in pieces, so the kernel is
    there once a layer whatever the rung).  The latent pool is written in place,
    and the 4,096 rung's temporaries are 1.6 GB beside 13.7 GB of weights and
    cache (3.0 GB with the branch whole: read before the pieces, PERF.md section
    6, PR 54)."""
    family, config, sizes, programs, _engine = cells_programs("longcatflash_serve_reasoning")
    titles = [title for title, _ in programs]
    assert sum("prefill, rung of" in t for t in titles) == 8 and "decode step, 128 slots x 4096 positions" in titles[-1]
    assert sizes["weights_bytes"] == family.weight_bytes(config) and sizes["slot_state_bytes"] == 0
    assert sizes["kv_pool_bytes"] == family.cache_bytes(config, config["serve"]) == config["serve"]["pool_pages"] * 16 * 10240
    (lowered,) = [low for title, low in programs if program in title]
    compiled = lowered.compile()
    text = compiled.as_text()
    kernel_calls = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    if "rung" in program:
        assert len(kernel_calls) == 12 and sum("mla_flash_fwd" in line for line in kernel_calls) == 8
        assert sum("grouped_swiglu" in line for line in kernel_calls) == 4
    else:
        assert len(kernel_calls) == 8 and all("paged_decode_latent" in line for line in kernel_calls)
    pages = config["serve"]["pool_pages"]
    assert not [line for line in text.splitlines() if " copy(" in line and f"= bf16[8,{pages},16,1,640]" in line]
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes >= sum(sizes.values()) and memory.alias_size_in_bytes >= sizes["kv_pool_bytes"], memory
    assert memory.argument_size_in_bytes < 1.005 * sum(sizes.values()), "no row of the pool padded: 640 is whole lane tiles"
    assert memory.temp_size_in_bytes < (1.7e9 if "rung" in program else 0.1e9), memory
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 15.4e9, memory


def test_lings_decode_program_compiles_at_the_cells_size_with_no_copy_of_pool_or_state(chip, cells_programs):
    """``ling3flash_serve_longgen``'s decode step (256 slots x 16,384 positions): six ``kda_step`` over the states in place
    and ONE ``paged_decode_latent`` at 32 heads over pages of 32 (a page table of 512 KB: pages of 16 would need 1 MiB of
    scalar memory, which the compiler refuses); the six expert layers are the grouped kernel (PR 64: 256 rows x 8 over 64
    of the 512 experts the router scores are 4 rows an expert, under the pad's lower bound, where ``N k / held`` read 32
    and made the step a padded candidate), and the engine's latches, which repeat the rule on the host, say the same."""
    import re

    from vescale_tpu.moe import dropless


    family, config, sizes, programs, engine = cells_programs("ling3flash_serve_longgen")
    c, S = engine.config, engine.cache.num_slots
    assert (S, c.num_experts_per_tok, c.experts_held, c.num_experts, engine._expert_layers) == (256, 8, 64, 512, 6)
    assert dropless.padded_candidate(S, c.num_experts_per_tok, c.experts_held), "by N k / held it was one"
    assert not engine._decode_padded_candidate and engine._grouped_layers[S] == 6
    # ... and every rung past all-on-all is the kernel's alone: no program of the cell holds the pad
    assert engine._grouped_layers == {rows: 6 for rows in (S, *engine.buckets) if rows > dropless.DENSE_MAX_TOKENS}
    titles = [title for title, _ in programs]
    assert sum("prefill, rung of" in t for t in titles) == 16 and "decode step, 256 slots x 16384 positions" in titles[-1]
    assert sizes["weights_bytes"] == family.weight_bytes(config)
    assert sizes["kv_pool_bytes"] == 61440 * 32 * 1280 and sizes["slot_state_bytes"] == 256 * family.state_bytes_per_slot(config, config["serve"])
    assert sizes["kv_pool_bytes"] + sizes["slot_state_bytes"] == family.cache_bytes(config, config["serve"])
    compiled = programs[-1][1].compile()
    text = compiled.as_text()
    kernel_calls = re.findall(r"%([a-z_.]+?)[.\d]* = [^\n]*custom_call_target=\"tpu_custom_call\"", text)
    assert sorted(kernel_calls) == ["grouped_swiglu"] * 6 + ["kda_step"] * 6 + ["paged_decode_latent"]
    # the router sorts the eight group scores of a row and nothing wider (PR 67: a group's score and the final eight are maxima)
    assert set(re.findall(r"= \((f32\[[\d,]+\])[^\n]* sort\(", text)) == {"f32[256,8]"}
    cells_programs.assert_in_place_and_fits(compiled, sizes, "bf16[1,61440,32,1,640]")
    for held in ("bf16[1,61440,32,640]", "f32[6,256,32,128,128]", "bf16[6,256,3,12288]"):
        assert not [line for line in text.splitlines() if " copy(" in line and f"= {held}" in line], held
