"""The Pallas kernels compile for the chip — checked here, without one.

The TPU compiler is installed beside the CPU backend and compiles for a
*described* v5e (``topologies.get_topology_desc``): each case lowers one
kernel at the widths the main path runs it at, from ``ShapeDtypeStruct``s on
one described device, and compiles it.  Nothing executes, so this says
nothing about results or times; it catches what interpret mode cannot — a
kernel the compiler refuses (scoped VMEM, tiling, HBM).  The cases call the
kernel entry points below the platform dispatch (``_flash``,
``paged_decode``, ``ssm_step``, ``selective_scan``, ``kda_step``, ``kda_chunk``, ``grouped_swiglu``, ``head_select``, ``_fused_local``, ``fused_xent_parts``): code that asks
``jax.devices()`` still sees the CPU here.
"""

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

bf16, f32 = jnp.bfloat16, jnp.float32


@pytest.fixture(scope="module")
def chip():
    """Sharding on one described v5e device, with the persistent compile
    cache off around the module (an entry written for a described chip
    cannot be read back without one, and warns)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here, or another process holds it
        pytest.skip(f"cannot describe a v5e topology: {str(e)[:200]}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the kernel is in the program
    return compiled


# ------------------------------------------------------------------ flash
# (T, H, KV, D, dtype, backward): the main path (T 4096, MHA and GQA), the
# per-shard heads of a two-way tp split (H 16), the long-context rung
# (streaming kernels), and the lengths between them, which the compiler
# refused before the resident/streaming choice counted what the resident
# kernels hold in VMEM.
FLASH_CASES = [
    (4096, 32, 32, 128, bf16, True),
    (4096, 32, 8, 128, bf16, True),
    (4096, 32, 32, 128, f32, True),
    (4096, 32, 8, 128, f32, True),
    (4096, 16, 16, 128, bf16, True),
    (2048, 16, 16, 128, bf16, True),
    (2048, 32, 32, 128, bf16, False),
    (6144, 32, 32, 128, bf16, True),
    (6144, 32, 8, 128, bf16, True),
    (8192, 32, 32, 128, bf16, True),
    (10240, 32, 8, 128, bf16, True),
    (12800, 32, 32, 128, bf16, False),
    (16384, 12, 12, 64, bf16, False),
    (16384, 12, 12, 64, bf16, True),
    (32768, 16, 8, 64, bf16, True),
]


@pytest.mark.parametrize(
    "T,H,KV,D,dtype,backward", FLASH_CASES,
    ids=[f"T{t}-H{h}kv{kv}-D{d}-{jnp.dtype(dt).name}-{'bwd' if b else 'fwd'}"
         for t, h, kv, d, dt, b in FLASH_CASES],
)
def test_flash_compiles(chip, T, H, KV, D, dtype, backward):
    from vescale_tpu.ops.flash_attention import _flash

    q = jax.ShapeDtypeStruct((1, T, H, D), dtype, sharding=chip)
    kv = jax.ShapeDtypeStruct((1, T, KV, D), dtype, sharding=chip)

    def fwd(q, k, v):
        return _flash(q, k, v, D ** -0.5, True, 512, 512, False, "pallas")

    def fwd_bwd(q, k, v):
        return jax.grad(lambda *a: fwd(*a).astype(f32).sum(), argnums=(0, 1, 2))(q, k, v)

    _compile(fwd_bwd if backward else fwd, q, kv, kv)


# the forward under the mask that is causal over blocks of 4 (generation by diffusion over blocks: SDAR's
# prefill, 32 query heads over 4 key heads), at the first, a middle and the last rung of its ladder
@pytest.mark.parametrize("T", [128, 512, 2048])
def test_block_masked_flash_forward_compiles(chip, T):
    from vescale_tpu.ops.flash_attention import _fit_block, _flash_fwd_pallas, _to3

    q = jax.ShapeDtypeStruct((1, T, 32, 128), bf16, sharding=chip)
    kv = jax.ShapeDtypeStruct((1, T, 4, 128), bf16, sharding=chip)
    block = _fit_block(512, T)
    compiled = _compile(lambda q, k, v: _flash_fwd_pallas(_to3(q), _to3(k), _to3(v), 128 ** -0.5, True, block, block, False,
                                                          32, 4, mask_block=4)[0], q, kv, kv)
    assert "block_flash_fwd" in compiled.as_text()


# ----------------------------------------------------------- paged decode
# (slots, pages_per_slot, page, H, KV, hd, dtype, layers): the two serve cells
# of the benchmark at their depths (Mistral GQA 8 x 4, DeepSeek MHA 32 x 1),
# chip_smoke's serve geometry, a tp shard's heads, fp32 pools, and a head_dim
# the compiled kernel does not take (the engine's XLA leg runs there)
PAGED_CASES = [
    (32, 128, 16, 32, 8, 128, bf16, 16),
    (32, 96, 16, 32, 32, 128, bf16, 8),
    (16, 128, 16, 32, 32, 128, bf16, 4),
    (16, 128, 16, 32, 8, 128, bf16, 4),
    (16, 128, 16, 32, 4, 128, bf16, 1),
    (16, 128, 16, 16, 16, 128, bf16, 1),
    (16, 64, 16, 12, 12, 64, f32, 1),
    (8, 16, 8, 32, 32, 128, f32, 2),
    # ten key heads (no whole number of a bfloat16 page's sublane tiles): declined in bfloat16, taken in float32
    (8, 16, 16, 20, 10, 128, bf16, 1),
    (8, 16, 16, 20, 10, 128, f32, 1),
    # SDAR's pass: a block's 4 x 32 query rows a slot ride as 4 groups (key heads) of 32, 128 slots, six layers
    (128, 128, 16, 128, 4, 128, bf16, 6),
    # Falcon-H1-34B: 20 query rows on 4 key heads, five a key head (not a whole number of 8-row tiles, no power of two)
    (128, 96, 16, 20, 4, 128, bf16, 6),
]


@pytest.mark.parametrize(
    "S,Pmax,page,H,KV,hd,dtype,L", PAGED_CASES,
    ids=[f"S{s}-P{p}x{pg}-H{h}kv{kv}-hd{d}-{jnp.dtype(dt).name}-L{l}"
         for s, p, pg, h, kv, d, dt, l in PAGED_CASES],
)
def test_paged_decode_compiles(chip, S, Pmax, page, H, KV, hd, dtype, L):
    from vescale_tpu.kernels.paged_attention import paged_decode, supports

    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    pool = sds((L, S * Pmax + 1, page, KV, hd), dtype)     # the whole 5-D pool, as the engine hands it over
    args = (sds((S, H, hd), dtype), pool, pool, sds((S, Pmax), jnp.int32), sds((S,), jnp.int32), sds((), jnp.int32))
    fn = lambda q, k, v, table, lengths, layer: paged_decode(
        q, k, v, table, lengths, layer=layer, scale=hd ** -0.5, interpret=False)
    if not supports(dtype, KV, hd, interpret=False):
        # what the engine's dispatch declines (Mosaic's strided load wants 128-lane rows) the entry point refuses
        with pytest.raises(ValueError, match="takes no"):
            _compile(fn, *args)
        return
    compiled = _compile(fn, *args)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20   # no copy of the pool


# (slots, pages a slot, heads, pool layers): DeepSeek-V2's cell (128 heads on one row: the ridge) and LongCat-Flash's (64 heads,
# eight pool layers, 109 operations a byte read: the memory side); rows of 576 padded to 640, the values the first 512, pages of 16
@pytest.mark.parametrize("S,Pmax,H,L", [(32, 512, 128, 5), (128, 256, 64, 8)], ids=["deepseekv2-128-heads", "longcat-64-heads"])
def test_paged_decode_latent_compiles_at_both_head_counts(chip, S, Pmax, H, L):
    from vescale_tpu.kernels.paged_attention import _latent_blocks, paged_decode_latent, supports_latent

    assert supports_latent(bf16, 640, 512, 16, interpret=False)
    sds = lambda shape, dt=bf16: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    args = (sds((S, H, 640)), sds((L, 20480, 16, 1, 640)), sds((S, Pmax), jnp.int32), sds((S,), jnp.int32), sds((), jnp.int32))
    compiled = _compile(lambda q, pool, table, lengths, layer: paged_decode_latent(
        q, pool, table, lengths, layer=layer, scale=192 ** -0.5, latent=512, interpret=False), *args)
    text = compiled.as_text()
    # what the benchmark finds the kernel's events by (benchmark/families/deepseek_v2.py and families/longcat_flash.py: ``DECODE_KERNEL``)
    assert "paged_decode_latent" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20   # no copy of the pool, and the table is not padded
    # blocks of four groups of 8 pages whatever the head count, in two buffers: 1.3 MB beside the softmax's state, well
    # under the 16 MiB a kernel has without asking, and it does not ask (a kernel that sets `vmem_limit_bytes` changes
    # how the compiler builds every other fusion of its program)
    group, groups = _latent_blocks(Pmax, 16, 640, 2)
    assert (group, groups) == (8, 4)
    assert 2 * group * groups * 16 * 640 * 2 + H * (512 + 2 * 128) * 4 < 2 << 20 and '"scoped_memory_configs":[{' not in text


# --------------------------------------------------------------- ssm step
# (layers, slots, state, heads x head width): granite-4.0-h-small's nine Mamba-2 layers at 64 slots (the
# benchmark's cell), and a narrower state whose lanes are one block
def _ssm_step_in_place(ssm_step):
    """The op's kernel leg in a program that donates the state, as a decode program does."""
    return jax.jit(lambda state, decay, dtx, B, C, layer: ssm_step(state, decay, dtx, B, C, layer=layer, interpret=False),
                   donate_argnums=0)


@pytest.mark.parametrize("L,S,N,J", [(9, 64, 128, 8192), (2, 8, 64, 1024)], ids=["granite-64-slots", "narrow"])
def test_ssm_step_compiles_in_place(chip, L, S, N, J):
    from vescale_tpu.kernels.ssm_step import ssm_step

    sds = lambda shape, dt=f32: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    compiled = _ssm_step_in_place(ssm_step).lower(sds((L, S, N, J)), sds((S, J)), sds((S, J)), sds((S, N)), sds((S, N)),
                                                  sds((1,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == L * S * N * J * 4 and memory.temp_size_in_bytes < 1 << 20   # no copy of the state


def test_ssm_step_compiles_in_place_with_two_groups_and_a_state_of_256(chip):
    """Falcon-H1-34B's six layers at 128 slots: a block of (256, 1024) picks the column of its lanes' group."""
    from vescale_tpu.kernels.ssm_step import ssm_step, supports

    L, S, N, J, G = 6, 128, 256, 4096, 2
    assert supports(f32, N, J, interpret=False, groups=G)
    sds = lambda shape, dt=f32: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    compiled = _ssm_step_in_place(ssm_step).lower(sds((L, S, N, J)), sds((S, J)), sds((S, J)), sds((S, G, N)),
                                                  sds((S, G, N)), sds((1,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == L * S * N * J * 4 and memory.temp_size_in_bytes < 1 << 20   # no copy of the state


# Phi-4-mini-flash's nine Mamba-1 layers at 96 slots (the benchmark's cell): the decay formed in VMEM from ``dt`` and ``A``
def test_ssm_step_selective_compiles_in_place(chip):
    from vescale_tpu.kernels.ssm_step import ssm_step_selective

    L, S, N, J = 9, 96, 16, 5120
    sds = lambda shape, dt=f32: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    step = jax.jit(lambda state, dt, A, dtx, B, C, layer: ssm_step_selective(state, dt, A, dtx, B, C, layer=layer, interpret=False),
                   donate_argnums=0)
    compiled = step.lower(sds((L, S, N, J)), sds((S, J)), sds((N, J)), sds((S, J)), sds((S, N)), sds((S, N)),
                          sds((1,), jnp.int32)).compile()
    assert "ssm_step_selective" in compiled.as_text()       # what benchmark/families/phi4flash.py finds its events by
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == L * S * N * J * 4 and memory.temp_size_in_bytes < 1 << 20   # no copy of the state, no decay written out


# ------------------------------------------------------------ selective scan
# (positions, channels): the cell's first rung, its most common one and its last, at d_inner 5120 and a state of 16
@pytest.mark.parametrize("T", [128, 2048, 4096])
def test_selective_scan_compiles_at_the_cells_rungs(chip, T):
    from vescale_tpu.kernels.selective_scan import selective_scan, supports

    N, J = 16, 5120
    assert supports(N, J, T, interpret=False)
    sds = lambda *shape: jax.ShapeDtypeStruct(shape, f32, sharding=chip)
    compiled = _compile(lambda u, dt, A, B, C: selective_scan(u, dt, A, B, C, interpret=False),
                        sds(T, J), sds(T, J), sds(N, J), sds(T, N), sds(T, N))
    assert "selective_scan" in compiled.as_text()           # what benchmark/families/phi4flash.py finds its events by
    assert compiled.memory_analysis().temp_size_in_bytes < 4 << 20     # B and C by eights; no state in HBM but the last


# ------------------------------------------------------------ the delta rule
def test_kda_step_compiles_at_the_cells_state_in_place(chip):
    """Six layers' states of 256 slots x 32 heads of 128 x 128 float32 (3.2 GB), one layer's step: the whole array aliased,
    no copy of it, and beside it only the step's small operands (a tile of columns a slot and block, the rows)."""
    from vescale_tpu.kernels.kda import kda_step, supports_step

    L, S, H, D = 6, 256, 32, 128
    assert supports_step(f32, H, D, D, interpret=False)
    sds = lambda *shape: jax.ShapeDtypeStruct(shape, f32, sharding=chip)
    compiled = jax.jit(lambda state, q, k, v, g, beta: kda_step(state, q, k, v, g, beta, layer=jnp.int32(2), interpret=False),
                       donate_argnums=(0,)).lower(sds(L, S, H, D, D), sds(S, H, D), sds(S, H, D), sds(S, H, D), sds(S, H, D), sds(S, H)).compile()
    text = compiled.as_text()
    assert 'custom_call_target="tpu_custom_call"' in text and "kda_step" in text      # what benchmark/families/ling_hybrid.py finds its events by
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == L * S * H * D * D * 4 and memory.temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize("T", [128, 2048, 8192])
def test_kda_chunk_compiles_at_the_cells_rungs(chip, T):
    from vescale_tpu.kernels.kda import kda_chunk, supports_chunk

    H, D = 32, 128
    assert supports_chunk(H, D, D, T, interpret=False)
    sds = lambda *shape: jax.ShapeDtypeStruct(shape, f32, sharding=chip)
    compiled = _compile(lambda q, k, v, g, beta: kda_chunk(q, k, v, g, beta, interpret=False),
                        sds(T, H, D), sds(T, H, D), sds(T, H, D), sds(T, H, D), sds(T, H))
    assert "kda_chunk" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 6 * T * H * D * 4      # k beta, v beta and the running sums: no state in HBM but the last


def test_lings_decode_program_compiles_at_the_cells_size_with_no_copy_of_pool_or_state(chip):
    """``ling3flash_serve_longgen``'s decode step (256 slots x 16,384 positions): six ``kda_step`` over the states in place
    and ONE ``paged_decode_latent`` at 32 heads over pages of 32 (a page table of 512 KB: pages of 16 would need 1 MiB of
    scalar memory, which the compiler refuses); the six expert layers are the grouped kernel (PR 64: 256 rows x 8 over 64
    of the 512 experts the router scores are 4 rows an expert, under the pad's lower bound, where ``N k / held`` read 32
    and made the step a padded candidate), and the engine's latches, which repeat the rule on the host, say the same."""
    import re
    from unittest import mock

    from vescale_tpu.moe import dropless
    from vescale_tpu.serve import HybridServeEngine

    built, init = [], HybridServeEngine.__init__

    def noted(self, *args, **kwargs):
        init(self, *args, **kwargs)
        built.append(self)

    with mock.patch.object(HybridServeEngine, "__init__", noted):
        family, config, sizes, programs = _cells_programs(chip, "ling3flash_serve_longgen")
    (engine,) = built
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
    _assert_in_place_and_fits(compiled, sizes, "bf16[1,61440,32,1,640]")
    for held in ("bf16[1,61440,32,640]", "f32[6,256,32,128,128]", "bf16[6,256,3,12288]"):
        assert not [line for line in text.splitlines() if " copy(" in line and f"= {held}" in line], held


# Phi-4-mini-flash's folded rows (ten key heads of 128: no whole number of sublane tiles, so not ``paged_decode``'s pool): the
# one pool layer at 96 slots x 256 pages and the eight rings as 32 pages a slot, 40 query rows of 128
@pytest.mark.parametrize("L,pages,Pmax", [(1, 96 * 256 + 1, 256), (8, 96 * 32, 32)], ids=["the-pool", "the-rings-as-pages"])
def test_paged_decode_folded_compiles_at_ten_key_heads(chip, L, pages, Pmax):
    from vescale_tpu.kernels.paged_attention import paged_decode_folded, supports, supports_folded

    S, H, KV, hd = 96, 40, 10, 128
    assert supports_folded(bf16, KV, hd, hd, 16, interpret=False)
    sds = lambda shape, dt=bf16: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    pool = sds((L, pages, 16, 1, KV * hd))
    compiled = _compile(lambda q, k, v, table, lengths, layer: paged_decode_folded(
        q, k, v, table, lengths, layer=layer, scale=0.125, interpret=False),
        sds((S, H, hd)), pool, pool, sds((S, Pmax), jnp.int32), sds((S,), jnp.int32), sds((), jnp.int32))
    assert "paged_decode_kv10" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20   # no copy of the pool
    assert not supports(bf16, KV, hd, interpret=False), "ten bfloat16 heads are no whole sublane tiles: paged_decode declines them"
    assert all(supports(bf16, kv, hd, interpret=False) for kv in (2, 4, 8, 16, 32)) and supports(f32, KV, hd, interpret=False)


# ---------------------------------------------------------- grouped SwiGLU
# (rung, choices a token, held experts, outputs the router scores, hidden, an expert's width): the MoE cells' expert layers at
# the shortest and the longest rung that takes the sorted form (Laguna's 256 and 8192, SDAR's 256 and 2048, Granite's 512,
# DeepSeek-V2's 8192, whose three matrices of 47 MB pass in tiles over the width)
GROUPED_CASES = {"laguna-256": (256, 8, 256, 256, 2048, 512), "laguna-8192": (8192, 8, 256, 256, 2048, 512),
                 "sdar-256": (256, 8, 128, 128, 2048, 768), "sdar-2048": (2048, 8, 128, 128, 2048, 768),
                 "granite-512": (512, 10, 36, 72, 4096, 768), "deepseekv2-8192": (8192, 6, 40, 160, 5120, 1536),
                 # LongCat-Flash's experts of 6144 x 2048, the widest hidden so far (75 MB an expert: tiles of 512 over the width),
                 # at the shortest sorted rung and at the longest piece a rung goes through in (models/longcat_flash.py)
                 "longcat-256": (256, 12, 16, 768, 6144, 2048), "longcat-1024": (1024, 12, 16, 768, 6144, 2048),
                 # Ling's decode step and its 1,024 rung, 64 of 512 experts held (PR 64): 4 and 16 rows an expert, row tiles of
                 # 16 and 32 where ``N k / held`` read 32 and 128 rows (the pad, and a tile of 128)
                 "ling-decode-256": (256, 8, 64, 512, 2560, 768), "ling-1024": (1024, 8, 64, 512, 2560, 768),
                 # ... and the small row tiles a share now gives experts that stream: MiMo's decode step (16 of 256: a tile of 16
                 # where it was 256) and DeepSeek-V2's 1,024 rung (40 of 160: 64)
                 "mimo-decode-256": (256, 8, 16, 256, 4096, 2048), "deepseekv2-1024": (1024, 6, 40, 160, 5120, 1536)}
STREAMED = ("deepseekv2-8192", "longcat-256", "longcat-1024", "mimo-decode-256", "deepseekv2-1024")     # an expert's matrices pass in tiles over ``f``, once a row tile


@pytest.mark.parametrize("case", GROUPED_CASES)
def test_grouped_swiglu_compiles_at_the_cells_widths_and_rungs(chip, case):
    from vescale_tpu.kernels.grouped_swiglu import grouped_swiglu, row_tiles, tiles
    from vescale_tpu.moe import dropless

    N, k, held, E, d, f = GROUPED_CASES[case]
    assert dropless.expert_form(N, k, held, E) == dropless.SORTED
    tm, tf = tiles(d, f, bf16, N * k / E)       # the row tile follows the rows that land, as ``moe/dropless.py`` asks for it
    assert (tf == f) == (case not in STREAMED) and f % tf == 0 and tm % 16 == 0
    assert {"ling-decode-256": 16, "ling-1024": 32, "longcat-256": 16, "longcat-1024": 32, "deepseekv2-8192": 256}.get(case, tm) == tm
    sds = lambda shape, dt=bf16: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    rows = row_tiles(N * k, held, tm) * tm
    compiled = grouped_swiglu.lower(sds((rows, d)), sds((held,), jnp.int32), sds((held, d, f)), sds((held, d, f)), sds((held, f, d)),
                                    tm=tm, tf=tf, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20      # nothing of the rows' or the experts' size beside the operands


# ------------------------------------------------------ head to selection
def test_head_select_compiles_at_sdars_cell_size_and_leaves_three_vectors(chip):
    """512 open rows on the head of 151,936 columns (1,187 x 128: the last of its
    149 tiles of 1,024 is ragged): nothing of the logits' size beside the
    operands, inside the VMEM a kernel has without asking for more (as the
    float32 form is at the most rows ``supports`` lets through)."""
    from vescale_tpu.kernels.head_select import head_select, supports

    sds = lambda shape, dt=bf16: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    assert supports(f32, 256, 2048, interpret=False) and supports(bf16, 512, 2048, interpret=False)
    kernel_leg = jax.jit(lambda x, w: head_select(x, w, interpret=False))
    kernel_leg.lower(sds((256, 2048), f32), sds((2048, 151936), f32)).compile()
    compiled = kernel_leg.lower(sds((512, 2048)), sds((2048, 151936))).compile()
    assert '"scoped_memory_configs":[{' not in compiled.as_text()
    assert "tpu_custom_call" in compiled.as_text() and "151936]" in compiled.as_text()
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 1 << 20 and memory.output_size_in_bytes < 1 << 16


# ------------------------------------------------- whole programs of a cell
class _JaxOnATpu:
    """``jax`` as ``ops/flash_attention.py`` sees it, but for the platform of ``jax.devices()[0]``."""

    def __getattr__(self, name):
        return getattr(jax, name)

    def devices(self, *_args):
        import types

        return [types.SimpleNamespace(platform="tpu")]


def _cells_programs(chip, cell):
    """A serve cell's programs from shapes alone, as ``benchmark/rehearse.py``
    lowers them: ``(family, config, sizes, [(title, lowered)])``.  The program
    asks ``jax.devices()`` for its platform and would take its CPU legs here, so
    this answers for it while the programs are traced."""
    import importlib
    from unittest import mock

    from benchmark.spec import load_cell
    from vescale_tpu import kernels

    flash_ops = importlib.import_module("vescale_tpu.ops.flash_attention")    # (``ops`` exports the function under this name)
    spec = load_cell(cell)
    family, config = spec.family(), spec.config
    (device,) = chip.device_set
    with mock.patch.object(kernels, "on_tpu", lambda: True), mock.patch.object(flash_ops, "jax", _JaxOnATpu()):
        sizes, programs = family.rehearse_serve(spec.name, config, config["serve"], [device])
    return family, config, sizes, programs


def _assert_in_place_and_fits(compiled, sizes, pool):
    """The pools are written in place: no copy of one (``pool``, its shape as
    the compiled text writes it) to another layout and back around a scatter
    over the page axis, the cache's bytes aliased, and 16 GB of HBM hold the
    arguments (weights, pools, state) and the program's temporaries, with room
    for the logits."""
    assert not [line for line in compiled.as_text().splitlines() if " copy(" in line and f"= {pool}" in line]
    memory = compiled.memory_analysis()
    cache_bytes = sizes["kv_pool_bytes"] + sizes["slot_state_bytes"]
    assert memory.argument_size_in_bytes >= sum(sizes.values()) and memory.alias_size_in_bytes >= cache_bytes, memory
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 15.0e9 and memory.temp_size_in_bytes < 0.5e9, memory


@pytest.mark.parametrize("program,kernels_in_it", [("decode step", 12), ("bucket of 512", 6)], ids=["decode", "rung512"])
def test_falcon_h1s_decode_program_and_a_rung_compile_at_the_cells_size(chip, program, kernels_in_it):
    """``falconh1_34b_serve_batch``'s decode step (128 slots x 1536 positions:
    six ``ssm_step`` and six ``paged_decode`` kernels) and the 512 rung of its
    prefill ladder (six grouped-query flash forwards at 20 / 4 heads)."""
    family, config, sizes, programs = _cells_programs(chip, "falconh1_34b_serve_batch")
    titles = [title for title, _ in programs]
    assert sum("prefill, bucket of" in t for t in titles) == 5 and "decode step, 128 slots x 1536 positions" in titles[-1]
    assert sizes["weights_bytes"] == family.weight_bytes(config)
    assert sizes["slot_state_bytes"] == 128 * family.state_bytes_per_slot(config, config["serve"])
    (lowered,) = [low for title, low in programs if program in title]
    compiled = lowered.compile()
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == kernels_in_it
    _assert_in_place_and_fits(compiled, sizes, "bf16[6,12289,16,4,128]")        # 1.2 GB a pool


def test_sdars_rung_of_512_compiles_at_the_cells_size_and_writes_its_pools_in_place(chip):
    """``sdar30b_serve_blockgen``'s 512 rung (six flash forwards under the
    block mask; 32 rows an expert, so each of the six expert layers is a choice
    on the device whose fall-back branch is the sorted form's XLA leg, the
    compiler's own ``ragged-dot``, as before the grouped kernel).  Its pools have Falcon-H1's row, 4 key heads of 128, and
    go through the same page writer: a scatter cost FOUR copies of a 1.6 GB
    pool a prefill here (PERF.md section 6, PR 44)."""
    family, config, sizes, programs = _cells_programs(chip, "sdar30b_serve_blockgen")
    titles = [title for title, _ in programs]
    assert sum("prefill, rung of" in t for t in titles) == 6 and "one pass, 128 slots x 4 positions" in titles[-1]
    assert sizes["weights_bytes"] == family.weight_bytes(config)
    (lowered,) = [low for title, low in programs if "rung of 512" in title]
    compiled = lowered.compile()
    kernel_calls = [line for line in compiled.as_text().splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    assert sum("block_flash_fwd" in line for line in kernel_calls) == 6 and not any("grouped_swiglu" in line for line in kernel_calls)
    _assert_in_place_and_fits(compiled, sizes, "bf16[6,16385,16,4,128]")        # 1.6 GB a pool


@pytest.mark.parametrize("leg", ["kernel", "xla"])
def test_sdars_pass_compiles_at_the_cells_size_and_holds_no_logits(chip, leg, monkeypatch):
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
    _family, _config, sizes, programs = _cells_programs(chip, "sdar30b_serve_blockgen")
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
        _assert_in_place_and_fits(compiled, sizes, "bf16[6,16385,16,4,128]")
    else:                                                                       # (every kernel's XLA leg: the gathered pages are 1.1 GB of temporaries)
        assert "f32[512,151936]" in text and not any("head_select" in line or "paged_decode" in line for line in kernel_calls)
        memory = compiled.memory_analysis()
        assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 15.0e9, memory


@pytest.mark.parametrize("program,kernels_in_it", [("decode step", 5), ("rung of 512 positions", 9)], ids=["decode", "rung512"])
def test_lagunas_decode_program_and_a_rung_compile_at_the_cells_size_and_copy_no_pool_of_either_kind(chip, program, kernels_in_it):
    """``lagunaxs2_serve_mixedlen``'s decode step (128 slots: two ``paged_decode``
    at 48 query heads over the pages, three at 64 over the rings read as pages)
    and the 512 rung of its prefill ladder (two causal flash forwards, three
    ``window_flash_fwd``, and the four expert layers' ``grouped_swiglu``: 16
    rows an expert, the sorted form alone).  Neither holds a copy of a pool of EITHER kind: the
    full layers' pages (rows of 8 key heads, through ``write_pages``) or the
    sliding layers' rings (a slot's rows rewritten by one ``dynamic_update_slice``
    a prefill, one row a slot by a scatter a step, read through a reshape)."""
    family, config, sizes, programs = _cells_programs(chip, "lagunaxs2_serve_mixedlen")
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
    _assert_in_place_and_fits(compiled, sizes, "bf16[2,28672,16,8,128]")        # 1.88 GB a pool
    for ring in ("bf16[3,128,512,8,128]", "bf16[3,4096,16,8,128]"):             # 0.40 GB a ring, as the cache and as the kernel see it
        assert not [line for line in compiled.as_text().splitlines() if " copy(" in line and f"= {ring}" in line]


@pytest.mark.parametrize("program", ["decode step", "rung of 512 positions", "rung of 2048 positions", "rung of 4096 positions"],
                         ids=["decode", "rung512", "rung2048", "rung4096"])
def test_longcats_decode_program_and_its_rungs_compile_at_the_cells_size_and_fit_beside_the_weights(chip, program):
    """``longcatflash_serve_reasoning``'s decode step (128 slots: eight
    ``paged_decode_latent`` at 64 heads, one a SUBLAYER; its sixteen experts a
    layer go all on all, no kernel) and three rungs of its prefill ladder (eight
    ``mla_flash_fwd`` and four ``grouped_swiglu`` over experts of 6144 x 2048: a
    rung over 1,024 rows takes the routed branch in pieces, so the kernel is
    there once a layer whatever the rung).  The latent pool is written in place,
    and the 4,096 rung's temporaries are 1.6 GB beside 13.7 GB of weights and
    cache (3.0 GB with the branch whole: read before the pieces, PERF.md section
    6, PR 54)."""
    family, config, sizes, programs = _cells_programs(chip, "longcatflash_serve_reasoning")
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


@pytest.mark.parametrize("program,kernels_in_it", [("decode step", 13), ("rung of 512 positions", 13)], ids=["decode", "rung512"])
def test_mimos_decode_program_and_a_rung_compile_at_the_cells_size_with_no_copy_and_no_padding_of_a_pool(chip, program, kernels_in_it):
    """``mimov25_serve_reasoning``'s decode step (256 slots: two
    ``paged_decode_kv4`` over the folded pages, five ``paged_decode_kv8`` with a
    sink over the folded rings read as pages, six ``grouped_swiglu``) and the 512
    rung of its prefill ladder (two ``causal_flash_fwd`` at 192 | 128, five
    ``window_flash_fwd`` with a sink, six ``grouped_swiglu``).  Neither holds a
    copy of a pool of either kind, and the chip lays the folded rows out WITHOUT
    padding: the program's arguments are the weights' and the cache's logical
    bytes (5,120 B a position in the pages, 3.28 MB a slot in the rings), where
    rows of (4, 192) would be padded to 256 lanes a head or turned round."""
    family, config, sizes, programs = _cells_programs(chip, "mimov25_serve_reasoning")
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
    _assert_in_place_and_fits(compiled, sizes, "bf16[2,23552,32,1,768]")        # 2.3 GB of keys
    for pool in ("bf16[2,23552,32,1,512]", "bf16[2,23552,32,768]", "bf16[2,23552,32,512]", "bf16[5,256,128,1,1536]",
                 "bf16[5,256,128,1,1024]", "bf16[5,1024,32,1,1536]", "bf16[5,1024,32,1,1024]", "bf16[5,1024,32,1536]",
                 "bf16[5,1024,32,1024]"):                                       # as the cache and as the kernel see them
        assert not [line for line in text.splitlines() if " copy(" in line and f"= {pool}" in line]
    # no pool padded past 5% of its logical bytes: the arguments are the weights, the cache and a few small arrays
    assert compiled.memory_analysis().argument_size_in_bytes < 1.005 * sum(sizes.values())
    for row in ("[2,23552,32,1,768]{4,2,3,1,0:T(8,128)(2,1)}", "[5,256,128,1,1536]{4,2,3,1,0:T(8,128)(2,1)}"):
        assert f"bf16{row}" in text, "a folded row is the lanes and a page's positions the sublanes: whole tiles"


def test_deepseeks_step_that_carries_a_prompt_compiles_at_the_cells_size_with_one_product_a_weight(chip):
    """``deepseek7b_serve_batch``'s decode step with the 128 rung's prompt in it (PR 53: 32 decode rows and 128
    prompt rows, one array before every weight's product): eight ``paged_decode`` and eight flash forwards, no
    copy of a pool, and every product of the stack over all 160 rows, the head's over the 32 steps' rows and the
    prompt's last: a weight crosses the HBM once for both.  (The engine is the one the cell's family builds for
    ``benchmark/rehearse.py``, from shapes alone.)"""
    import re
    from unittest import mock

    from vescale_tpu.serve import ServeEngine

    built, init = [], ServeEngine.__init__

    def noted(self, *args, **kwargs):
        init(self, *args, **kwargs)
        built.append(self)

    with mock.patch.object(ServeEngine, "__init__", noted):
        _family, _config, _sizes, programs = _cells_programs(chip, "deepseek7b_serve_batch")
        (engine,) = built
        cache = engine.cache
        S, page, rung = cache.num_slots, cache.config.page_size, 128
        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)
        # (traced here too, under the same answers: the flash forward asks for its platform while it is traced)
        import importlib

        from vescale_tpu import kernels

        flash_ops = importlib.import_module("vescale_tpu.ops.flash_attention")
        with mock.patch.object(kernels, "on_tpu", lambda: True), mock.patch.object(flash_ops, "jax", _JaxOnATpu()):
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


def test_falcon_h1s_step_that_carries_a_prompt_compiles_at_the_cells_size_with_one_product_a_weight(chip):
    """``falconh1_34b_serve_batch``'s decode step with the 128 rung's prompt in it (PR 55: 128 decode rows and 128
    prompt rows, one array before every weight's product): six ``ssm_step``, six ``paged_decode`` and six flash
    forwards, no copy of a pool or of the state, every product of the stack over all 256 rows and none over 128
    beside it, the head's over the 128 steps' rows and the prompt's last: a weight crosses the HBM once for both.
    (The engine is the one the cell's family builds for ``benchmark/rehearse.py``, from shapes alone.)"""
    import importlib
    import re
    from unittest import mock

    from vescale_tpu import kernels
    from vescale_tpu.serve import HybridServeEngine

    built, init = [], HybridServeEngine.__init__

    def noted(self, *args, **kwargs):
        init(self, *args, **kwargs)
        built.append(self)

    flash_ops = importlib.import_module("vescale_tpu.ops.flash_attention")
    with mock.patch.object(HybridServeEngine, "__init__", noted):
        _family, _config, sizes, programs = _cells_programs(chip, "falconh1_34b_serve_batch")
        (engine,) = built
        cache = engine.cache
        S, page, rung = cache.num_slots, cache.config.page_size, 128
        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)
        # (traced here too, under the same answers: the flash forward asks for its platform while it is traced)
        with mock.patch.object(kernels, "on_tpu", lambda: True), mock.patch.object(flash_ops, "jax", _JaxOnATpu()):
            lowered = engine._ride_fn.lower(engine.params, *engine._held(), i32(S, cache.config.pages_per_slot), i32(S), i32(S),
                                            i32(S), i32(rung), i32(), i32(rung // page), i32())
    assert engine.rides and engine.kernel_decode and engine.kernel_ssm_step and (S, rung) == (128, 128)
    assert lowered.as_text().lstrip().startswith("module @jit_decode ")
    compiled = lowered.compile()
    text = compiled.as_text()
    kernel_calls = re.findall(r"%([a-z_.]+?)[.\d]* = [^\n]*custom_call_target=\"tpu_custom_call\"", text)
    assert sorted(kernel_calls) == ["paged_decode"] * 6 + ["ssm_step"] * 6 + ["vs.attn"] * 6      # (the flash forward bears its scope's name)
    _assert_in_place_and_fits(compiled, sizes, "bf16[6,12289,16,4,128]")
    assert not [line for line in text.splitlines() if " copy(" in line and "= f32[6,128,256,4096]" in line], "nor of the state"
    products = set(re.findall(r"= \w+\[(\d+),(\d+)\]\S* convolution\(", text))
    # in_proj, q and k/v, o and out_proj and down_proj, gate and up: all 256 rows; the head 128 + 1
    assert products == {("256", "9248"), ("256", "2560"), ("256", "512"), ("256", "5120"), ("256", "21504"), ("129", "130560")}
    assert compiled.memory_analysis().temp_size_in_bytes < 128 << 20
    # ... and the step without a prompt is the program it was: its products over the 128 rows
    (step,) = [low for title, low in programs if "decode step" in title]
    assert set(re.findall(r"= \w+\[(\d+),\d+\]\S* convolution\(", step.compile().as_text())) == {"128"}


def test_granites_step_that_carries_a_prompt_compiles_at_the_cells_size_with_one_product_a_weight(chip):
    """``granite4hsmall_serve_batch``'s decode step with the 256 rung's prompt in it (PR 62: 64 decode rows and 256
    prompt rows, one array before every weight's product): nine ``ssm_step``, one ``paged_decode`` and one flash
    forward, no copy of a pool or of the state, every product of the stack over all 320 rows and none over 64 or 256
    beside it: ``W_in`` nine times, ``W_out`` / ``W_o`` / ``W_q`` / the shared expert's down 21, its gate and up 20,
    the router ten, and the 36 held experts' three matrices ONCE a layer, in the padded form that 320 rows x 10 over
    36 experts make a candidate for; the head's over the 64 steps' rows and the prompt's last.  A weight crosses the
    HBM once for both, the expert layer's 72% of them too.  (The engine is the one the cell's family builds for
    ``benchmark/rehearse.py``, from shapes alone.)"""
    import collections
    import importlib
    import re
    from unittest import mock

    from vescale_tpu import kernels
    from vescale_tpu.moe import dropless
    from vescale_tpu.serve import HybridServeEngine

    built, init = [], HybridServeEngine.__init__

    def noted(self, *args, **kwargs):
        init(self, *args, **kwargs)
        built.append(self)

    flash_ops = importlib.import_module("vescale_tpu.ops.flash_attention")
    with mock.patch.object(HybridServeEngine, "__init__", noted):
        _family, _config, sizes, programs = _cells_programs(chip, "granite4hsmall_serve_batch")
        (engine,) = built
        cache, c = engine.cache, engine.config
        S, page, rung = cache.num_slots, cache.config.page_size, 256
        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)
        # (traced here too, under the same answers: the flash forward asks for its platform while it is traced)
        with mock.patch.object(kernels, "on_tpu", lambda: True), mock.patch.object(flash_ops, "jax", _JaxOnATpu()):
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
    _assert_in_place_and_fits(compiled, sizes, "bf16[1,6145,16,8,128]")        # 0.2 GB a pool
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


# what a program outside its kernels' bodies lowers to, for a described v5e: a digest of the lowered text with every
# kernel's serialized body taken out (it holds the checkout's path and the kernel's line numbers; the bodies' own identity
# is the jaxpr digests of tests/test_program_identity.py).  Taken on the parent of the PR that gave ``paged_decode`` a
# folded sibling and the flash forward a sink and narrower values (8655180, this function on that tree); the rung's anew
# by PR 52, whose prefill program also returns its row's argmax (``bf0426431e3c2841`` before it).
LOWERED_BEFORE_FOLDED_POOLS = {"decode step": "34098600aed73bdf", "rung of 512 positions": "e19c2768c59bc5a3"}


@pytest.mark.parametrize("program", list(LOWERED_BEFORE_FOLDED_POOLS), ids=["decode", "rung512"])
def test_an_existing_cells_programs_lower_to_the_text_they_had(chip, program):
    """``lagunaxs2_serve_mixedlen``'s decode step (``paged_decode`` over pages and
    rings of ONE width) and its 512 rung (the causal and the windowed forward
    without a sink): with ``sink=None``, ``Dv == D``, ``bias=None`` and
    ``v_head_dim=None`` nothing of them changed."""
    import hashlib
    import re

    _family, _config, _sizes, programs = _cells_programs(chip, "lagunaxs2_serve_mixedlen")
    (lowered,) = [low for title, low in programs if program in title]
    text = re.sub(r'(backend_config = ")[^\n]*', r"\1<kernel>", lowered.as_text())
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == LOWERED_BEFORE_FOLDED_POOLS[program]


# ------------------------------------------------------------ fused adamw
@pytest.mark.parametrize("shape", [(4096, 14336), (4097,)], ids=["ffn-leaf", "ragged-tail"])
def test_fused_adamw_compiles(chip, shape):
    from vescale_tpu.kernels.fused_adamw import _fused_local

    sds = lambda dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    _compile(
        lambda g, m, v, coef: _fused_local(
            g, m, v, coef, b1=0.9, b2=0.999, eps=1e-8, state_dtype=bf16, interpret=False),
        sds(f32), sds(bf16), sds(bf16), jax.ShapeDtypeStruct((2,), f32, sharding=chip),
    )


# ------------------------------------------------------------- fused xent
@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "bwd"])
@pytest.mark.parametrize("vocab", [32000, 128256])
def test_fused_xent_compiles(chip, vocab, backward):
    from vescale_tpu.kernels.cross_entropy import fused_xent_parts

    rows = 4096
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)

    def loss(lg, idx, gmax):
        se, pk, _ = fused_xent_parts(lg, idx, gmax, False)
        return jnp.mean(gmax + jnp.log(se) - pk)

    _compile(
        jax.grad(loss) if backward else loss,
        sds((rows, vocab), f32), sds((rows,), jnp.int32), sds((rows,), f32),
    )
