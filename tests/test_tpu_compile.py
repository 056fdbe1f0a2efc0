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

import pytest

import jax
import jax.numpy as jnp

bf16, f32 = jnp.bfloat16, jnp.float32


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
