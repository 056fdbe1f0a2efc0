"""Each family's plain reference against the program on the CPU at a small
size: the flax model's logits, and prefill-then-decode through the engine's
cache, for a GQA and an MHA preset with an untied head."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bm_fixtures import REPO, make_tiny_root

from benchmark import reference, serve_cell
from benchmark.spec import load_cell, load_family

BASE = {"model": "llama", "vocab_size": 384, "hidden_size": 64, "intermediate_size": 160, "num_hidden_layers": 3,
        "num_attention_heads": 4, "head_dim": 16, "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
        "sliding_window": None, "tie_word_embeddings": False}
PRESETS = {"gqa": dict(BASE, num_key_value_heads=2), "mha": dict(BASE, num_key_value_heads=4),
           "tied": dict(BASE, num_key_value_heads=1, tie_word_embeddings=True, rope_theta=1e6)}


def _program_module(model, cfg):
    """The program's own module of a family, for the presets above."""
    if model == "llama":
        from vescale_tpu.models.llama import Llama

        return Llama(cfg)
    raise KeyError(model)


@pytest.mark.parametrize("preset", sorted(PRESETS))
def test_reference_matches_the_programs_module_in_float32(preset):
    import dataclasses

    c = PRESETS[preset]
    family = load_family(c["model"], REPO)
    cfg = dataclasses.replace(family.program_config(c, max_positions=48, use_flash_attention=False), dtype=jnp.float32)
    tokens = np.random.default_rng(0).integers(0, c["vocab_size"], (2, 48)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        module = _program_module(c["model"], cfg)
        params = module.init(jax.random.key(1), jnp.asarray(tokens))["params"]
        want = np.asarray(module.apply({"params": params}, jnp.asarray(tokens)))
    for b in range(2):
        got = np.asarray(family.logits(params, c, tokens[b], range(48)))
        # both float32: only the order of sums differs
        assert reference.rel_at_scale(got, want[b]) < 2e-5
    targets = np.roll(tokens, -1, axis=1)
    lse = jax.nn.logsumexp(jnp.asarray(want), axis=-1)
    picked = jnp.take_along_axis(jnp.asarray(want), jnp.asarray(targets)[..., None], axis=-1)[..., 0]
    ref_loss, rows = family.loss_and_logits(params, c, tokens, targets, [0, 47])
    assert ref_loss == pytest.approx(float(jnp.mean(lse - picked)), abs=1e-5)
    assert reference.rel_at_scale(rows, want[0][[0, 47]]) < 2e-5


@pytest.mark.parametrize("workload", ["tiny_chat", "tiny_batch"])   # a GQA and an MHA configuration
def test_reference_matches_prefill_then_decode_through_the_engine(tmp_path, workload):
    spec = load_cell(workload, make_tiny_root(str(tmp_path / "root")))
    cell = serve_cell.ServeCell(spec, jax.devices()[:1])
    cell.build(seed=5)
    ok, detail = cell.check_reference(seed=5)
    assert ok, detail
    # bf16 through two blocks: far inside the tolerance, and not by luck
    tolerance = cell.family.SERVE_LOGITS_TOLERANCE
    assert 0 < detail["logits_max_abs_diff_over_max"] < tolerance == detail["tolerance"]
    # and the comparison can fail: against a reference whose first k_proj is negated, the same
    # prefill is far outside the tolerance
    ref_params = jax.tree_util.tree_map(lambda x: x, cell.engine.params)
    ref_params["layers_0"]["self_attn"]["k_proj"]["kernel"] = -ref_params["layers_0"]["self_attn"]["k_proj"]["kernel"]
    rng = np.random.default_rng([5, 4])
    n = serve_cell.CHECK_PROMPT_TOKENS
    n = min(n, cell.cache.max_seq_len - serve_cell.CHECK_DECODE_STEPS - 1)
    prompt = [int(t) for t in rng.integers(1, cell.vocab - 1, n)]
    slot = cell.cache.alloc(n, 1)
    row = cell.engine.prefill(prompt, slot)
    wrong = np.asarray(cell.family.logits(ref_params, spec.config, prompt, [n - 1]))[0]
    assert reference.rel_at_scale(row, wrong) > tolerance
