"""The three forms of ``moe/dropless.py:dropless_experts`` (all experts on all
tokens, the sorted grouped product on both of its legs, ``jax.lax.ragged_dot``
and the grouped SwiGLU kernel under the interpreter, and the padded batched
product) against a plain float32 loop over tokens and their ``k`` experts, the
rule that chooses among them, and the fall-back on the device when a router
sends one expert more rows than the pad."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vescale_tpu.moe import dropless
from vescale_tpu.moe.dropless import (DENSE_MAX_TOKENS, PADDED_MAX_MEAN_ROWS, PADDED_MIN_MEAN_ROWS, ROW_PAD, dropless_experts,
                                      fits_pad, padded_candidate, route_topk)

D, F = 16, 12


def _loop(x, idx, gates, w_gate, w_up, w_down, first, mask):
    """A token at a time, a kept expert at a time, in float64."""
    held = w_gate.shape[0]
    out = np.zeros((x.shape[0], w_down.shape[-1]))
    counts = np.zeros((held,), np.int64)
    for n in range(x.shape[0]):
        if not mask[n]:
            continue
        for e, g in zip(idx[n], gates[n]):
            if first <= e < first + held:
                a, b = x[n] @ w_gate[e - first], x[n] @ w_up[e - first]
                out[n] += g * ((a / (1.0 + np.exp(-a)) * b) @ w_down[e - first])
                counts[e - first] += 1
    return out, counts


def _problem(N, E, k, held, seed, favourite=None, shunned=None, routes_every=None):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(N, D))
    scores = rng.normal(size=(N, E))
    if favourite is not None:
        scores[:, favourite] += 9.0         # every token keeps it
    if shunned is not None:
        scores[:, shunned] -= 9.0           # ... and none this one
    weights = [rng.normal(size=s) for s in ((held, D, F), (held, D, F), (held, F, D))]
    idx, gates = route_topk(jnp.asarray(scores, jnp.float32), k)
    mask = np.ones((N,), bool)
    mask[::7] = False                       # tokens that route nowhere
    if routes_every is not None:
        mask &= np.arange(N) % routes_every == 1        # ... most of them
    return x, idx, gates, weights, mask


def _run(x, idx, gates, weights, first, mask, scored=None):
    fn = jax.jit(lambda *a: dropless_experts(*a, first_held=first, scored=scored, token_mask=jnp.asarray(mask)))
    args = (jnp.asarray(x, jnp.float32), idx, gates, *(jnp.asarray(w, jnp.float32) for w in weights))
    # the program as traced (a CPU lowers ``ragged_dot`` to plain products, so its name is gone from the lowered text) and as lowered
    return fn(*args), str(jax.make_jaxpr(fn)(*args)) + fn.lower(*args).as_text()


def _form(text):
    """Which forms the program holds: a choice on the device, a grouped product of the compiler's, the grouped kernel
    (whose interpreted body holds choices of its own: with it the text says nothing of the layer's)."""
    assert ("stablehlo.case" in text) == ("cond[" in text)
    kernel = "pallas_call" in text
    return {"cond": None if kernel else "stablehlo.case" in text, "ragged": "ragged_dot" in text, "kernel": kernel}


def rel(got, want):
    return float(np.max(np.abs(np.asarray(got) - want)) / np.max(np.abs(want)))


# N, all experts, k, held, first held, the forms the program must hold (its sorted form on the XLA leg), and what the router is bent to
SHAPES = {
    "all_on_all_64_tokens": (64, 16, 4, 8, 4, {"cond": False, "ragged": False}, {}),
    # 32 rows an expert of the 160 the router scores, at the pad's lower bound (512 tokens, a candidate while the rule read
    # N k / held, are 26: the sorted form alone)
    "padded_640_tokens_128_of_160_held_top8": (640, 160, 8, 128, 16, {"cond": True, "ragged": True}, {}),
    "sorted_2048_tokens_8_held": (2048, 12, 3, 8, 2, {"cond": False, "ragged": True}, {}),
    # 15 rows an expert of the 40 scored, under the pad's lower bound: one expert gets every token (eight row tiles of 32), one none
    "sorted_an_expert_over_a_row_tile_and_one_with_no_row": (300, 40, 2, 32, 4, {"cond": False, "ragged": True}, {"favourite": 9, "shunned": 11}),
    # three pairs in four land on experts held elsewhere: 170 rows an expert of the 24 scored, row tiles of 128
    "sorted_1024_tokens_6_of_24_held": (1024, 24, 4, 6, 10, {"cond": False, "ragged": True}, {}),
    # one token in five routes: 144 by the shapes, 25 by the counts
    "sorted_640_tokens_most_masked": (640, 10, 3, 4, 3, {"cond": False, "ragged": True}, {"routes_every": 5}),
}
# the sorted form's legs: ``jax.lax.ragged_dot``, as a CPU takes it, and the grouped kernel through the interpreter
LEGS = {"xla_leg": None, "grouped_kernel": "interpret"}


@pytest.mark.parametrize("leg", LEGS)
@pytest.mark.parametrize("shape", SHAPES)
def test_each_form_is_the_loop_over_tokens_and_their_experts(shape, leg, monkeypatch):
    N, E, k, held, first, forms, bent = SHAPES[shape]
    assert held < E and first > 0
    if LEGS[leg]:
        monkeypatch.setenv("VESCALE_KERNELS", LEGS[leg])
        if not forms["cond"]:       # the sorted form ALONE is the kernel's; a candidate's program is the same on both legs
            forms = {"cond": None if forms["ragged"] else False, "ragged": False, "kernel": forms["ragged"]}
    x, idx, gates, weights, mask = _problem(N, E, k, held, seed=N, **bent)
    (got, counts), text = _run(x, idx, gates, weights, first, mask, scored=E)
    assert _form(text) == {"kernel": False, **forms}
    want, want_counts = _loop(x, np.asarray(idx), np.asarray(gates), *weights, first, mask)
    assert rel(got, want) < 1e-5
    assert list(np.asarray(counts)) == list(want_counts)
    if forms["cond"]:
        assert bool(fits_pad(np.asarray(counts))), "this case is to take the padded branch"
    if "favourite" in bent:
        assert want_counts[bent["favourite"] - first] == mask.sum() > 4 * ROW_PAD // 2 and want_counts[bent["shunned"] - first] == 0
    assert not np.asarray(got)[~mask].any(), "a masked token routes nowhere"


@pytest.mark.parametrize("leg", LEGS)
def test_an_expert_with_more_rows_than_the_pad_falls_back_on_the_device_to_the_same_result(leg, monkeypatch):
    """Every one of 256 tokens keeps one held expert: a candidate by its shape
    (8 experts of 128 places for 512 pairs), over the pad by its counts; the
    branch it falls back to is the sorted form's XLA leg wherever the kernel is
    the sorted form's own (its program is the same on both legs)."""
    N, E, k, held, first = 256, 12, 2, 8, 2
    assert padded_candidate(N, k, held, E)
    if LEGS[leg]:
        monkeypatch.setenv("VESCALE_KERNELS", LEGS[leg])
    x, idx, gates, weights, mask = _problem(N, E, k, held, seed=1, favourite=first + 3)
    (got, counts), text = _run(x, idx, gates, weights, first, mask, scored=E)
    assert _form(text) == {"cond": True, "ragged": True, "kernel": False}
    counts = np.asarray(counts)
    assert counts[3] == mask.sum() > ROW_PAD and not bool(fits_pad(counts))
    want, want_counts = _loop(x, np.asarray(idx), np.asarray(gates), *weights, first, mask)
    assert rel(got, want) < 1e-5 and list(counts) == list(want_counts)
    # the same router under the pad: the other branch of the same program's text, the same loop
    few = mask & (np.arange(N) < ROW_PAD)
    (got, counts), _ = _run(x, idx, gates, weights, first, few, scored=E)
    assert bool(fits_pad(np.asarray(counts))) and np.asarray(counts)[3] == few.sum()
    assert rel(got, _loop(x, np.asarray(idx), np.asarray(gates), *weights, first, few)[0]) < 1e-5


def test_both_branches_of_a_candidate_call_give_the_same_numbers(monkeypatch):
    """The padded form against the sorted one on the same operands, bfloat16
    products as they are served: the same products, so what differs is the
    order in which a token's k terms are added."""
    N, E, k, held = 384, 32, 4, 32
    x, idx, gates, weights, mask = _problem(N, E, k, held, seed=5)
    args = (jnp.asarray(x, jnp.float32), idx, gates, *(jnp.asarray(w, jnp.bfloat16) for w in weights))
    call = lambda: jax.jit(lambda *a: dropless_experts(*a, token_mask=jnp.asarray(mask)))(*args)
    assert padded_candidate(N, k, held)
    padded, counts = call()
    assert bool(fits_pad(np.asarray(counts)))
    monkeypatch.setattr(dropless, "PADDED_MAX_MEAN_ROWS", 0)
    assert not padded_candidate(N, k, held)
    alone, counts_alone = call()
    assert rel(padded, np.asarray(alone)) < 1e-6 and list(np.asarray(counts)) == list(np.asarray(counts_alone))


def test_both_legs_of_the_sorted_form_give_the_same_numbers(monkeypatch):
    """The grouped kernel against ``jax.lax.ragged_dot`` on the same operands,
    bfloat16 products as they are served: the same products with the hidden
    rounded at the same place, so what differs is the order of a product's
    partial sums."""
    N, E, k, held = 1536, 24, 4, 8
    x, idx, gates, weights, mask = _problem(N, E, k, held, seed=6)
    args = (jnp.asarray(x, jnp.float32), idx, gates, *(jnp.asarray(w, jnp.bfloat16) for w in weights))
    call = lambda: jax.jit(lambda *a: dropless_experts(*a, first_held=8, scored=E, token_mask=jnp.asarray(mask)))(*args)
    assert dropless.expert_form(N, k, held, E) == dropless.SORTED
    xla, counts = call()
    monkeypatch.setenv("VESCALE_KERNELS", "interpret")
    kernel, counts_kernel = call()
    assert rel(kernel, np.asarray(xla)) < 1e-6 and list(np.asarray(counts)) == list(np.asarray(counts_kernel))


@pytest.mark.parametrize("N", [1, 32, 64, DENSE_MAX_TOKENS])
def test_a_call_of_few_tokens_holds_neither_a_choice_nor_a_grouped_product(N):
    """Granite's and DeepSeek-V2's decode steps (64 and 32 tokens) compile as
    they did: all experts on all tokens, nothing else in the program."""
    x, idx, gates, weights, mask = _problem(N, 16, 4, 8, seed=2)
    _, text = _run(x, idx, gates, weights, 4, mask)
    assert _form(text) == {"cond": False, "ragged": False, "kernel": False}
    assert "stablehlo.sort" not in text


@pytest.mark.parametrize("N, k, held, E, candidate", [
    (128, 8, 128, 128, False),      # a decode step of 128 tokens: all on all
    (512, 8, 128, 128, True),       # SDAR's pass: 32 rows an expert
    (1024, 8, 128, 128, True),      # ... its 1024 rung: 64
    (2048, 8, 128, 128, False),     # ... its 2048 rung: 128, the busiest expert never fits
    (256, 10, 36, 72, True),        # Granite's 256 rung alone: 71 pairs a held expert could get, 36 land (half of them elsewhere)
    (512, 10, 36, 72, False),       # ... its 512 rung: 142 could
    (64, 10, 36, 72, False), (32, 6, 40, 160, False),   # both neighbours' decode steps
    (512, 6, 40, 160, False),       # DeepSeek-V2's 512 rung: 77 could land on a held expert and 19 do: under the lower bound
    (4096, 6, 40, 160, False),      # ... and the rungs its long prompts take: 614 could
    (256, 8, 128, 128, False),      # SDAR's 256 rung: 16, under the lower bound: the pad would be seven eighths zeros
    (256, 8, 256, 256, False), (512, 8, 256, 256, False),       # Laguna's 256 and 512 rungs: 8 and 16
    (1024, 8, 256, 256, True), (3072, 8, 256, 256, True),       # ... its 1024 rung, at the bound, to its 3072 rung: 32 to 96
    (4096, 8, 256, 256, False),     # ... and the five above: 128 and more
])
def test_the_static_half_of_the_choice_reads_tokens_choices_held_experts_and_scored_ones(N, k, held, E, candidate):
    assert padded_candidate(N, k, held, E) is candidate
    assert 0 < PADDED_MIN_MEAN_ROWS < PADDED_MAX_MEAN_ROWS <= ROW_PAD, "a mean over the pad can never fit it"
    form = dropless.expert_form(N, k, held, E)
    assert form == (dropless.PADDED_OR_SORTED if candidate else dropless.ALL_ON_ALL if N <= DENSE_MAX_TOKENS else dropless.SORTED)
    if E == held:       # told nothing, a call takes every expert for held: the same answer
        assert padded_candidate(N, k, held) is candidate and dropless.expert_form(N, k, held) == form


A, S, P = dropless.ALL_ON_ALL, dropless.SORTED, dropless.PADDED_OR_SORTED
# the accepted cells' expert layers at the cells' sizes: (k, held, outputs the router scores, d, f) and, by the rows of a call
# (a decode step's, a rung's, a rung's beside the decode rows where prompts ride, a piece's where a rung goes in
# ``row_pieces``), the form and the grouped kernel's row tile with the rule told nothing (every expert taken for held: the
# rule before PR 64) and told the router's outputs.  One entry where both are the same.
CELLS = {
    "ling3flash": ((8, 64, 512, 2560, 768), {
        "decode_256": ((P, None), (S, 16)), "rung_128": (A, None), "rung_512": ((P, None), (S, 16)),
        "rung_1024": ((S, 128), (S, 32)), "rung_1536": ((S, 128), (S, 64)), "rung_2048": ((S, 128), (S, 64)),
        "piece_2560": ((S, 128), (S, 64)), "rung_3072": (S, 128), "rung_4096": (S, 128)}),
    "deepseekv2": ((6, 40, 160, 5120, 1536), {
        "decode_32": (A, None), "rung_128": (A, None), "rung_256": ((P, None), (S, 16)), "rung_512": ((P, None), (S, 32)),
        "rung_1024": ((S, 256), (S, 64)), "rung_1536": ((S, 256), (S, 128)), "rung_2048": ((S, 256), (S, 128)),
        "rung_3072": (S, 256), "rung_8192": (S, 256)}),
    "granite4hsmall": ((10, 36, 72, 4096, 768), {
        "decode_64": (A, None), "ride_192": ((P, None), (S, 64)), "ride_320": (P, None), "ride_576": (S, 128), "ride_1600": (S, 128)}),
    "mimov25": ((8, 16, 256, 4096, 2048), {
        "decode_256": ((S, 256), (S, 16)), "rung_128": (A, None), "rung_512": ((S, 256), (S, 32)),
        "rung_1024": ((S, 256), (S, 64)), "rung_2048": ((S, 256), (S, 128)), "rung_3072": (S, 256), "rung_8192": (S, 256)}),
    "longcatflash": ((12, 16, 768, 6144, 2048), {
        "decode_128": (A, None), "rung_256": ((S, 256), (S, 16)), "rung_512": ((S, 256), (S, 16)),
        "piece_768": ((S, 256), (S, 32)), "piece_1024": ((S, 256), (S, 32))}),
    "sdar30b": ((8, 128, 128, 2048, 768), {
        "pass_672": (P, None), "rung_128": (A, None), "rung_256": (S, 32), "rung_512": (P, None), "rung_1536": (P, None),
        "rung_2048": (S, 128)}),
    "lagunaxs2": ((8, 256, 256, 2048, 512), {
        "decode_128": (A, None), "rung_256": (S, 16), "rung_512": (S, 32), "rung_1024": (P, None), "rung_3072": (P, None),
        "rung_4096": (S, 128), "rung_8192": (S, 128)}),
}


@pytest.mark.parametrize("cell, call", [(cell, call) for cell, (_, calls) in CELLS.items() for call in calls])
def test_told_the_routers_outputs_a_share_leaves_the_pad_and_tiles_for_the_rows_that_land(cell, call):
    """The rule at the accepted cells' sizes.  A tree that holds a share gets ``N k / E`` rows an expert, not ``N k /
    held``: its calls may LEAVE the padded form (Ling's decode step of 256 rows on 64 of 512 experts: 4 rows, not 32) and
    none enters it; none leaves or enters all-on-all (that bound is of tokens); the sorted form's row tile follows the
    same mean.  A tree that holds every expert (SDAR's, Laguna's) gets what it got."""
    from vescale_tpu.kernels import grouped_swiglu

    (k, held, E, d, f), calls = CELLS[cell]
    N = int(call.rsplit("_", 1)[1])
    told_nothing, told = calls[call] if isinstance(calls[call][0], tuple) else (calls[call], calls[call])
    form_of = lambda scored: dropless.expert_form(N, k, held, scored)
    tile_of = lambda scored: grouped_swiglu.tiles(d, f, jnp.bfloat16, N * k / scored)[0] if form_of(scored) == S else None
    assert form_of(held) == dropless.expert_form(N, k, held) and (form_of(held), tile_of(held)) == told_nothing
    assert (form_of(E), tile_of(E)) == told
    assert (told_nothing[0], told[0]) in {(A, A), (S, S), (P, P), (P, S)}, "a call only leaves the pad"
    if told[1] is not None and told_nothing[1] is not None:
        assert told[1] <= told_nothing[1]
    if E == held:
        assert told == told_nothing


# where the router's pairs go: spread over all its outputs (one in eight lands here), ALL on the held range (the row tile is
# sized for the mean, the layout for this), none on it
ROUTERS = {"spread": 0.0, "every_pair_lands_here": 9.0, "no_pair_lands_here": -9.0}
FORMS = {"all_on_all": (A, None), "sorted_xla_leg": (S, None), "sorted_grouped_kernel": (S, True), "padded": (P, None)}


@pytest.mark.parametrize("router", ROUTERS)
@pytest.mark.parametrize("form", FORMS)
def test_the_forms_agree_on_a_share_whatever_part_of_the_pairs_the_router_sends_it(form, router):
    """8 of 64 experts held from id 16 on, 160 tokens with 4 experts each: 10 rows an expert on the mean over the router's
    outputs, so a row tile of 16, and 80 where every pair lands here (five tiles an expert, all in the layout; under the
    pad of 128 too).  Each form, told the router's outputs, is the loop over tokens."""
    N, E, k, held, first = 160, 64, 4, 8, 16
    rng = np.random.default_rng(64)
    x, scores = rng.normal(size=(N, D)), rng.normal(size=(N, E))
    scores[:, first:first + held] += ROUTERS[router]
    weights = [rng.normal(size=s) for s in ((held, D, F), (held, D, F), (held, F, D))]
    idx, gates = route_topk(jnp.asarray(scores, jnp.float32), k)
    mask = np.arange(N) % 7 != 0
    which, grouped = FORMS[form]
    got, counts = dropless._experts(jnp.asarray(x, jnp.float32), idx, gates, *(jnp.asarray(w, jnp.float32) for w in weights),
                                    jnp.asarray(mask), first_held=first, scored=E, dtype=jnp.float32, form=which, grouped=grouped)
    want, want_counts = _loop(x, np.asarray(idx), np.asarray(gates), *weights, first, mask)
    assert list(np.asarray(counts)) == list(want_counts) and bool(fits_pad(np.asarray(counts)))
    if router != "spread":
        assert want_counts.sum() == (k * mask.sum() if router == "every_pair_lands_here" else 0)
    if router == "no_pair_lands_here":
        assert not np.asarray(got).any()
    else:
        assert rel(got, want) < 1e-5
        assert not np.asarray(got)[~mask].any(), "a masked token routes nowhere"
    if router == "every_pair_lands_here":
        from vescale_tpu.kernels import grouped_swiglu
        assert want_counts.max() > 4 * grouped_swiglu.tiles(D, F, jnp.float32, N * k / E)[0] == 4 * 16


def test_the_predicate_is_the_same_on_the_hosts_copy_of_the_counts_layer_by_layer():
    counts = np.array([[0, ROW_PAD, 3], [ROW_PAD + 1, 0, 0], [5, 5, 5]])
    assert list(fits_pad(counts)) == [True, False, True] == [bool(fits_pad(jnp.asarray(row))) for row in counts]


# ----------------------------------------------------------- a long call in row pieces
@pytest.mark.parametrize("k, d, ladder", [
    # 12 x 6144 a row: pieces of at most 1,024 rows; 8 x 2560: of at most 4,096, and the rung no piece count up to
    # ceil(N / most) divides (10,240 in 3) takes the next that does (4 of 2,560), never the whole rung
    (12, 6144, {128: 1, 1024: 1, 1536: 2, 2048: 2, 3072: 3, 4096: 4}),
    (8, 2560, {128: 1, 4096: 1, 5120: 2, 6144: 2, 7168: 2, 8192: 2, 10240: 4, 12288: 3, 14336: 4, 16384: 4}),
], ids=["12_of_6144", "8_of_2560"])
def test_a_rungs_pieces_are_the_fewest_equal_ones_whose_sorted_form_fits(k, d, ladder):
    assert {N: dropless.row_pieces(N, k, d) for N in ladder} == ladder
    for N, pieces in ladder.items():
        assert N % pieces == 0 and 6 * k * d * (N // pieces) <= dropless.SORTED_FORM_BYTES


@pytest.mark.parametrize("masked", [False, True], ids=["every_row", "masked"])
def test_a_call_in_row_pieces_gives_what_the_whole_call_gives(masked, monkeypatch):
    N, E, k, held = 48, 8, 2, 4
    x, _idx, _gates, weights, _mask = _problem(N, E, k, held, seed=21)
    x, w_gate, w_up, w_down = (jnp.asarray(a, jnp.float32) for a in (x, *weights))
    router = jnp.asarray(np.random.default_rng(22).normal(size=(D, E)), jnp.float32)
    mask = jnp.arange(N) < 41 if masked else None

    def rows(h, token_mask):
        out, counts = dropless.routed_experts(h, router, lambda s: route_topk(s, k), w_gate, w_up, w_down, first_held=2,
                                              token_mask=token_mask)
        return out, counts, jnp.sum(counts)

    whole = rows(x, mask)
    monkeypatch.setattr(dropless, "SORTED_FORM_BYTES", 6 * k * D * 20)         # at most 20 rows: 48 are three pieces of 16
    assert dropless.row_pieces(N, k, D) == 3
    out, counts, pairs = jax.jit(lambda h: dropless.in_row_pieces(rows, h, mask, k=k))(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(whole[0]), rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(whole[1]))
    assert int(pairs) == int(whole[2]) and (not masked or not np.asarray(out[41:]).any())
