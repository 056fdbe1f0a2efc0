"""The serve cells' whole programs compile for the chip: the expert families whose layers mix kinds of attention.

SDAR's block pass and a rung, Laguna's and MiMo's decode step and a rung, and Laguna's two programs held to
the text they lowered to before the folded pools.  Nothing executes:
each case builds the cell's engine and lowers its programs from ``ShapeDtypeStruct``s on one described v5e device, as
``benchmark/rehearse.py`` does (the ``cells_programs`` fixture of ``tests/conftest.py``: ONE build a cell and module,
whatever the number of cases that compile a program of it), and compiles one.  A case holds the compiled text to what
the cell's programs must be: which kernels are in it, no copy of a pool or a state, the bytes of its arguments and
temporaries beside the chip's 16 GB.  The kernels alone at the cells' widths are ``tests/test_tpu_compile.py``'s; a
later family's cases go into the file of the three (this, ``tests/test_tpu_compile_latent.py``, ``tests/test_tpu_compile_ride.py``) that then sums to
the fewest seconds (ROADMAP D19: no test file over 6% of tier-1's summed seconds), all of a family in ONE file.
"""

import pytest

import jax.numpy as jnp


def test_sdars_rung_of_512_compiles_at_the_cells_size_and_writes_its_pools_in_place(chip, cells_programs):
    """``sdar30b_serve_blockgen``'s 512 rung (six flash forwards under the
    block mask; 32 rows an expert, so each of the six expert layers is a choice
    on the device whose fall-back branch is the sorted form's XLA leg, the
    compiler's own ``ragged-dot``, as before the grouped kernel).  Its pools have Falcon-H1's row, 4 key heads of 128, and
    go through the same page writer: a scatter cost FOUR copies of a 1.6 GB
    pool a prefill here (PERF.md section 6, PR 44)."""
    family, config, sizes, programs, _engine = cells_programs("sdar30b_serve_blockgen")
    titles = [title for title, _ in programs]
    assert sum("prefill, rung of" in t for t in titles) == 6 and "one pass, 128 slots x 4 positions" in titles[-1]
    assert sizes["weights_bytes"] == family.weight_bytes(config)
    (lowered,) = [low for title, low in programs if "rung of 512" in title]
    compiled = lowered.compile()
    kernel_calls = [line for line in compiled.as_text().splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    assert sum("block_flash_fwd" in line for line in kernel_calls) == 6 and not any("grouped_swiglu" in line for line in kernel_calls)
    cells_programs.assert_in_place_and_fits(compiled, sizes, "bf16[6,16385,16,4,128]")        # 1.6 GB a pool


@pytest.mark.parametrize("leg", ["kernel", "xla"])
def test_sdars_pass_compiles_at_the_cells_size_and_holds_no_logits(chip, cells_programs, leg, monkeypatch):
    """``sdar30b_serve_blockgen``'s decode call (128 slots x 4 open rows and 40
    places of 4 commit rows: six ``paged_decode``, six expert layers as choices
    on the device, and ``head_select``).  A pass keeps no logits (PR 47): on the
    kernel's leg no instruction or output of the program has the logits' shape
    in either layout, ``f32[128,4,151936]`` (what the parent's program
    returned, 311 MB, through a 1.5 ms layout copy) or ``f32[512,151936]`` (the
    head's product); on the XLA leg (``VESCALE_KERNELS=off``) the product is a
    temporary, and the three-dimensional array is still never formed.  What the
    program returns in their place is the open rows' hidden state."""
    if leg == "xla":
        monkeypatch.setenv("VESCALE_KERNELS", "off")
    _family, _config, sizes, programs, _engine = cells_programs("sdar30b_serve_blockgen")
    (lowered,) = [low for title, low in programs if "one pass, 128 slots x 4 positions" in title]
    hidden, ids = lowered.out_info[:2]
    assert (hidden.shape, hidden.dtype, ids.shape) == ((512, 2048), jnp.float32, (128, 4))
    compiled = lowered.compile()
    text = compiled.as_text()
    kernel_calls = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    assert "f32[128,4,151936]" not in text
    if leg == "kernel":
        assert "f32[512,151936]" not in text
        assert sum("paged_decode" in line for line in kernel_calls) == 6 and sum("head_select" in line for line in kernel_calls) == 1
        (call,) = [line for line in kernel_calls if "head_select" in line]
        assert "[512,128]" not in call.split("custom_call_target")[0]           # (an expert layer's signature in the cell's op table)
        # the kernel asks for no more VMEM than a kernel has: a call that does makes the compiler build EVERY fusion of the
        # program under another scoped limit (the six expert layers read 0.15 ms slower each: PERF.md section 6, PR 47)
        assert '"scoped_memory_configs":[{' not in text
        cells_programs.assert_in_place_and_fits(compiled, sizes, "bf16[6,16385,16,4,128]")
    else:                                                                       # (every kernel's XLA leg: the gathered pages are 1.1 GB of temporaries)
        assert "f32[512,151936]" in text and not any("head_select" in line or "paged_decode" in line for line in kernel_calls)
        memory = compiled.memory_analysis()
        assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 15.0e9, memory


@pytest.mark.parametrize("program,kernels_in_it", [("decode step", 5), ("rung of 512 positions", 9)], ids=["decode", "rung512"])
def test_lagunas_decode_program_and_a_rung_compile_at_the_cells_size_and_copy_no_pool_of_either_kind(chip, cells_programs, program, kernels_in_it):
    """``lagunaxs2_serve_mixedlen``'s decode step (128 slots: two ``paged_decode``
    at 48 query heads over the pages, three at 64 over the rings read as pages)
    and the 512 rung of its prefill ladder (two causal flash forwards, three
    ``window_flash_fwd``, and the four expert layers' ``grouped_swiglu``: 16
    rows an expert, the sorted form alone).  Neither holds a copy of a pool of EITHER kind: the
    full layers' pages (rows of 8 key heads, through ``write_pages``) or the
    sliding layers' rings (a slot's rows rewritten by one ``dynamic_update_slice``
    a prefill, one row a slot by a scatter a step, read through a reshape)."""
    family, config, sizes, programs, _engine = cells_programs("lagunaxs2_serve_mixedlen")
    titles = [title for title, _ in programs]
    assert sum("prefill, rung of" in t for t in titles) == 12 and "decode step, 128 slots x 8192 positions" in titles[-1]
    assert sizes["weights_bytes"] == family.weight_bytes(config)
    assert sizes["kv_pool_bytes"] + sizes["slot_state_bytes"] == family.cache_bytes(config, config["serve"])
    (lowered,) = [low for title, low in programs if program in title]
    compiled = lowered.compile()
    kernel_calls = [line for line in compiled.as_text().splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    ours = [line for line in kernel_calls if "ragged-dot" not in line.split(" = ")[0]]      # (a sorted form on its XLA leg is the compiler's own)
    assert len(ours) == kernels_in_it
    if "rung" in program:
        assert sum("window_flash_fwd" in line for line in ours) == 3 and sum("grouped_swiglu" in line for line in ours) == 4
        assert ours == kernel_calls                             # ... and on the kernel's leg there is none of those
    else:
        assert sum("f32[128,64,128]" in line for line in ours) == 3 and sum("f32[128,48,128]" in line for line in ours) == 2
    cells_programs.assert_in_place_and_fits(compiled, sizes, "bf16[2,28672,16,8,128]")        # 1.88 GB a pool
    for ring in ("bf16[3,128,512,8,128]", "bf16[3,4096,16,8,128]"):             # 0.40 GB a ring, as the cache and as the kernel see it
        assert not [line for line in compiled.as_text().splitlines() if " copy(" in line and f"= {ring}" in line]


@pytest.mark.parametrize("program,kernels_in_it", [("decode step", 13), ("rung of 512 positions", 13)], ids=["decode", "rung512"])
def test_mimos_decode_program_and_a_rung_compile_at_the_cells_size_with_no_copy_and_no_padding_of_a_pool(chip, cells_programs, program, kernels_in_it):
    """``mimov25_serve_reasoning``'s decode step (256 slots: two
    ``paged_decode_kv4`` over the folded pages, five ``paged_decode_kv8`` with a
    sink over the folded rings read as pages, six ``grouped_swiglu``) and the 512
    rung of its prefill ladder (two ``causal_flash_fwd`` at 192 | 128, five
    ``window_flash_fwd`` with a sink, six ``grouped_swiglu``).  Neither holds a
    copy of a pool of either kind, and the chip lays the folded rows out WITHOUT
    padding: the program's arguments are the weights' and the cache's logical
    bytes (5,120 B a position in the pages, 3.28 MB a slot in the rings), where
    rows of (4, 192) would be padded to 256 lanes a head or turned round."""
    family, config, sizes, programs, _engine = cells_programs("mimov25_serve_reasoning")
    titles = [title for title, _ in programs]
    assert sum("prefill, rung of" in t for t in titles) == 12 and "decode step, 256 slots x 8192 positions" in titles[-1]
    assert sizes["weights_bytes"] == family.weight_bytes(config)
    assert sizes["kv_pool_bytes"] == 23552 * 32 * 5120 and sizes["slot_state_bytes"] == 256 * 3276800
    assert sizes["kv_pool_bytes"] + sizes["slot_state_bytes"] == family.cache_bytes(config, config["serve"])
    (lowered,) = [low for title, low in programs if program in title]
    compiled = lowered.compile()
    text = compiled.as_text()
    kernel_calls = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernel_calls) == kernels_in_it and sum("grouped_swiglu" in line for line in kernel_calls) == 6
    if "rung" in program:
        assert sum("window_flash_fwd" in line for line in kernel_calls) == 5 and sum("causal_flash_fwd" in line for line in kernel_calls) == 2
    else:
        assert sum("paged_decode_kv8" in line for line in kernel_calls) == 5 and sum("paged_decode_kv4" in line for line in kernel_calls) == 2
    cells_programs.assert_in_place_and_fits(compiled, sizes, "bf16[2,23552,32,1,768]")        # 2.3 GB of keys
    for pool in ("bf16[2,23552,32,1,512]", "bf16[2,23552,32,768]", "bf16[2,23552,32,512]", "bf16[5,256,128,1,1536]",
                 "bf16[5,256,128,1,1024]", "bf16[5,1024,32,1,1536]", "bf16[5,1024,32,1,1024]", "bf16[5,1024,32,1536]",
                 "bf16[5,1024,32,1024]"):                                       # as the cache and as the kernel see them
        assert not [line for line in text.splitlines() if " copy(" in line and f"= {pool}" in line]
    # no pool padded past 5% of its logical bytes: the arguments are the weights, the cache and a few small arrays
    assert compiled.memory_analysis().argument_size_in_bytes < 1.005 * sum(sizes.values())
    for row in ("[2,23552,32,1,768]{4,2,3,1,0:T(8,128)(2,1)}", "[5,256,128,1,1536]{4,2,3,1,0:T(8,128)(2,1)}"):
        assert f"bf16{row}" in text, "a folded row is the lanes and a page's positions the sublanes: whole tiles"


# what a program outside its kernels' bodies lowers to, for a described v5e: a digest of the lowered text with every
# kernel's serialized body taken out (it holds the checkout's path and the kernel's line numbers; the bodies' own identity
# is the jaxpr digests of tests/test_program_identity.py).  Taken on the parent of the PR that gave ``paged_decode`` a
# folded sibling and the flash forward a sink and narrower values (8655180, this function on that tree); the rung's anew
# by PR 52, whose prefill program also returns its row's argmax (``bf0426431e3c2841`` before it).
LOWERED_BEFORE_FOLDED_POOLS = {"decode step": "34098600aed73bdf", "rung of 512 positions": "e19c2768c59bc5a3"}


@pytest.mark.parametrize("program", list(LOWERED_BEFORE_FOLDED_POOLS), ids=["decode", "rung512"])
def test_an_existing_cells_programs_lower_to_the_text_they_had(chip, cells_programs, program):
    """``lagunaxs2_serve_mixedlen``'s decode step (``paged_decode`` over pages and
    rings of ONE width) and its 512 rung (the causal and the windowed forward
    without a sink): with ``sink=None``, ``Dv == D``, ``bias=None`` and
    ``v_head_dim=None`` nothing of them changed."""
    import hashlib
    import re

    _family, _config, _sizes, programs, _engine = cells_programs("lagunaxs2_serve_mixedlen")
    (lowered,) = [low for title, low in programs if program in title]
    text = re.sub(r'(backend_config = ")[^\n]*', r"\1<kernel>", lowered.as_text())
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == LOWERED_BEFORE_FOLDED_POOLS[program]
