"""The serve programs of the eight families behind ``HybridServeEngine`` are WHAT
THEIR FUNCTIONS COMPUTE, not where those are written: each family's prefill at
its first two rungs and its decode step, at the toy widths of the family's own
test file in the type they are served in (bfloat16), on both legs
(``VESCALE_KERNELS`` unset: the XLA legs a CPU takes; ``interpret``: the Pallas
kernels a TPU compiles, through the interpreter), traced and lowered, never run.

Pinned per program: a digest of the jaxpr, kernel bodies and all (it holds no
source location), and how many operations of the lowered module lie under each
``jax.named_scope`` the benchmark's per-layer readers match (``vs.attn``,
``vs.moe``, ``vs.mamba``, ``vs.mlp``, ``vs.unmask``, ``vs.kda``, ``vs.mla``, ``vs.routed``): a scope is not in a
jaxpr's text.  Taken on the parent of the PR that gave the shared blocks one
home each (e1809cb: this file on that tree, ``PROGRAMS`` printed by ``python
tests/test_program_identity.py``).  A PR that changes these programs on purpose
takes them anew.  (``mimo_v2``'s six were taken on the tree of the PR that brought
the family, PR 50: they pin it from there on.  The twenty-four prefills were taken
anew by PR 52, whose prefill also returns its row's argmax: the scopes did not move.  ``falcon_h1``'s four were replaced
by PR 55 with its four RIDING rungs, the step that carries a prompt: its engine launches no prefill; ``granite_hybrid``'s four
likewise by PR 62 (its two decode steps held: the expert layer hands its callers the kept ids now, and the step drops them).  ``longcat_flash``'s six were
taken on the tree of the PR that brought the family and moved the latent-attention block from ``models/deepseek_v2.py``
to ``models/mla.py``, PR 54: ``deepseek_v2``'s six held through the move, digest and scopes, as they stand here.
PR 64 told the expert layer how many outputs the router scores (``moe/dropless.py``): ``sdar_moe``'s, ``laguna``'s and
``falcon_h1``'s eighteen HELD untouched, the proof that a tree that holds every expert, and a dense one, runs the programs
it ran; so did every XLA-leg program and every decode step (the toys' rows are all-on-all there, which no count of
experts moves).  THREE were taken anew, each a second rung on the kernels' leg of a toy that holds a SHARE, where the
grouped kernel's row tile now follows ``N k / E``, a tile of 32 -> 16 each: ``granite_hybrid``'s second riding rung (19
rows x 3 over 4 held of 8; at the cell's size no rung of Granite's moves), ``mimo_v2``'s (16 x 4 over 4 of 16) and
``longcat_flash``'s (16 x 4 over 4 of 16 + 8 outputs); ``deepseek_v2``'s and the first rungs sit at the smallest tile
either way.  ``ling_hybrid``'s six are new here (``tests/test_kda.py``'s toy): the family whose decode
step PR 64 moved from the padded candidate to the grouped kernel at the cell's size is pinned from here on.
PR 67 took ``ling_hybrid``'s six anew: ``route_sigmoid_group_limited`` selects by maxima where it sorted (``vs.routed``
and ``vs.moe`` 166 operations more, the unrolled rounds); the forty-two others held, no other family calls the rule.)"""

import collections
import dataclasses
import hashlib
import re

import jax
import jax.numpy as jnp
import pytest

import test_deepseek_v2
import test_falcon_h1
import test_granite_hybrid
import test_kda
import test_laguna
import test_longcat_flash
import test_mimo_v2
import test_sdar_moe
from vescale_tpu.mesh import DeviceMesh
from vescale_tpu.moe import dropless
from vescale_tpu.serve import HybridServeEngine, PagedKVCache
from vescale_tpu.serve.hybrid_engine import hybrid_cache_config

FAMILIES = {"granite_hybrid": test_granite_hybrid, "deepseek_v2": test_deepseek_v2, "sdar_moe": test_sdar_moe,
            "falcon_h1": test_falcon_h1, "laguna": test_laguna, "mimo_v2": test_mimo_v2, "longcat_flash": test_longcat_flash,
            "ling_hybrid": test_kda}
LEGS = {"xla_legs": None, "kernels_interpreted": "interpret"}
WHICH = ("prefill_rung_1", "prefill_rung_2", "decode")
# a family whose engine offers a ride launches no prefill program: its rungs are the step that carries a prompt
RIDING = {"prefill_rung_1": "riding_rung_1", "prefill_rung_2": "riding_rung_2"}

PROGRAMS = {
    "granite_hybrid/xla_legs/riding_rung_1": ('2eed81bdc04d6856', 'vs.attn=172 vs.mamba=240 vs.moe=80'),
    "granite_hybrid/xla_legs/riding_rung_2": ('4ce44932afd9c4ab', 'vs.attn=172 vs.mamba=240 vs.moe=80'),
    "granite_hybrid/xla_legs/decode": ('648f82e75f10971f', 'vs.attn=106 vs.mamba=309 vs.moe=160'),
    "granite_hybrid/kernels_interpreted/riding_rung_1": ('5a75ab3126606170', 'vs.attn=636 vs.mamba=216 vs.moe=80'),
    "granite_hybrid/kernels_interpreted/riding_rung_2": ('fc4d83b3efb4bdd5', 'vs.attn=636 vs.mamba=216 vs.moe=80'),
    "granite_hybrid/kernels_interpreted/decode": ('f67535cadf866b5f', 'vs.attn=69 vs.mamba=255 vs.moe=160'),
    "deepseek_v2/xla_legs/prefill_rung_1": ('00bd63cd35773518', 'vs.attn=408 vs.mlp=9 vs.moe=108'),
    "deepseek_v2/xla_legs/prefill_rung_2": ('592f6fda2c03d3fa', 'vs.attn=408 vs.mlp=9 vs.moe=108'),
    "deepseek_v2/xla_legs/decode": ('ad9c244ea9a2ebe5', 'vs.attn=513 vs.mlp=9 vs.moe=130'),
    "deepseek_v2/kernels_interpreted/prefill_rung_1": ('fe17fcd91d420093', 'vs.attn=1788 vs.mlp=9 vs.moe=108'),
    "deepseek_v2/kernels_interpreted/prefill_rung_2": ('21764e8046f1a272', 'vs.attn=1788 vs.mlp=9 vs.moe=108'),
    "deepseek_v2/kernels_interpreted/decode": ('d4549fe0907634cd', 'vs.attn=402 vs.mlp=9 vs.moe=130'),
    "sdar_moe/xla_legs/prefill_rung_1": ('368d83fff82f4894', 'vs.attn=314 vs.moe=62'),
    "sdar_moe/xla_legs/prefill_rung_2": ('0a77108ffbf534d9', 'vs.attn=314 vs.moe=62'),
    "sdar_moe/xla_legs/decode": ('b85e2370cf46c46e', 'vs.attn=414 vs.moe=62 vs.unmask=78'),
    "sdar_moe/kernels_interpreted/prefill_rung_1": ('f933be4443957656', 'vs.attn=1332 vs.moe=62'),
    "sdar_moe/kernels_interpreted/prefill_rung_2": ('c0e8b8caa1f4e974', 'vs.attn=1332 vs.moe=62'),
    "sdar_moe/kernels_interpreted/decode": ('56e940485455a866', 'vs.attn=340 vs.moe=62 vs.unmask=67'),
    "falcon_h1/xla_legs/riding_rung_1": ('215586b1844b9746', 'vs.attn=232 vs.mamba=275 vs.mlp=28'),
    "falcon_h1/xla_legs/riding_rung_2": ('ebbecbf244a84194', 'vs.attn=232 vs.mamba=275 vs.mlp=28'),
    "falcon_h1/xla_legs/decode": ('c1aa2e3ced5cfdfc', 'vs.attn=302 vs.mamba=200 vs.mlp=56'),
    "falcon_h1/kernels_interpreted/riding_rung_1": ('0ba0b405beb28be4', 'vs.attn=696 vs.mamba=249 vs.mlp=28'),
    "falcon_h1/kernels_interpreted/riding_rung_2": ('cec96f38d98f1d72', 'vs.attn=696 vs.mamba=249 vs.mlp=28'),
    "falcon_h1/kernels_interpreted/decode": ('c6b57d1d7536a78a', 'vs.attn=228 vs.mamba=160 vs.mlp=56'),
    "laguna/xla_legs/prefill_rung_1": ('b146687985cea9d3', 'vs.attn=651 vs.mlp=9 vs.moe=108'),
    "laguna/xla_legs/prefill_rung_2": ('44ac4f53288c04cd', 'vs.attn=651 vs.mlp=9 vs.moe=108'),
    "laguna/xla_legs/decode": ('370af31f6198adea', 'vs.attn=810 vs.mlp=9 vs.moe=108'),
    "laguna/kernels_interpreted/prefill_rung_1": ('9d93d22a8bea5c98', 'vs.attn=3174 vs.mlp=9 vs.moe=108'),
    "laguna/kernels_interpreted/prefill_rung_2": ('6aa99d4b99fd8aa9', 'vs.attn=3174 vs.mlp=9 vs.moe=108'),
    "laguna/kernels_interpreted/decode": ('6b12595f0b880b74', 'vs.attn=625 vs.mlp=9 vs.moe=108'),
    "mimo_v2/xla_legs/prefill_rung_1": ('e2a2d458dda57145', 'vs.attn=990 vs.mlp=9 vs.moe=132'),
    "mimo_v2/xla_legs/prefill_rung_2": ('c605149277dc5904', 'vs.attn=990 vs.mlp=9 vs.moe=132'),
    "mimo_v2/xla_legs/decode": ('46088ea8d8664d65', 'vs.attn=1257 vs.mlp=9 vs.moe=216'),
    "mimo_v2/kernels_interpreted/prefill_rung_1": ('2566616dac8eeeeb', 'vs.attn=4916 vs.mlp=9 vs.moe=132'),
    "mimo_v2/kernels_interpreted/prefill_rung_2": ('a444b9b80f8a4d09', 'vs.attn=4916 vs.mlp=9 vs.moe=132'),
    "mimo_v2/kernels_interpreted/decode": ('e1b4216a59e292bd', 'vs.attn=931 vs.mlp=9 vs.moe=216'),
    "longcat_flash/xla_legs/prefill_rung_1": ('0e64cd6b04187dea', 'vs.attn=564 vs.mlp=36 vs.moe=68'),
    "longcat_flash/xla_legs/prefill_rung_2": ('37d4316b11599d06', 'vs.attn=564 vs.mlp=36 vs.moe=68'),
    "longcat_flash/xla_legs/decode": ('02f28e4b8fa49840', 'vs.attn=704 vs.mlp=36 vs.moe=74'),
    "longcat_flash/kernels_interpreted/prefill_rung_1": ('dc1e4a16dd58625c', 'vs.attn=2404 vs.mlp=36 vs.moe=68'),
    "longcat_flash/kernels_interpreted/prefill_rung_2": ('023cc3800daad893', 'vs.attn=2404 vs.mlp=36 vs.moe=68'),
    "longcat_flash/kernels_interpreted/decode": ('1b33297552cdd061', 'vs.attn=556 vs.mlp=36 vs.moe=74'),
    "ling_hybrid/xla_legs/prefill_rung_1": ('42aee405651da71e', 'vs.kda=1068 vs.mla=120 vs.mlp=9 vs.moe=520 vs.routed=466'),
    "ling_hybrid/xla_legs/prefill_rung_2": ('eb00bee5e49c470f', 'vs.kda=1068 vs.mla=120 vs.mlp=9 vs.moe=520 vs.routed=466'),
    "ling_hybrid/xla_legs/decode": ('70ca317312eca05e', 'vs.kda=1110 vs.mla=152 vs.mlp=9 vs.moe=586 vs.routed=532'),
    "ling_hybrid/kernels_interpreted/prefill_rung_1": ('0df0cbe6fedf37e5', 'vs.kda=864 vs.mla=580 vs.mlp=9 vs.moe=520 vs.routed=466'),
    "ling_hybrid/kernels_interpreted/prefill_rung_2": ('1acaacaa2fb23d21', 'vs.kda=864 vs.mla=580 vs.mlp=9 vs.moe=520 vs.routed=466'),
    "ling_hybrid/kernels_interpreted/decode": ('e9bbc0fd9db6388c', 'vs.kda=942 vs.mla=115 vs.mlp=9 vs.moe=586 vs.routed=532'),
}


def _engine(family: str, leg: str, patch):
    """The family's toy engine as its test file builds it, in bfloat16, over abstract parameters; not warmed."""
    toy = FAMILIES[family]
    cfg = dataclasses.replace(toy.toy_config(), dtype=jnp.bfloat16)
    if LEGS[leg] is None:
        patch.delenv("VESCALE_KERNELS", raising=False)
    else:
        # ... and, as the families' own fixtures do, both of the expert layer's limits at 0 while the programs are traced:
        # every program then holds the sorted form on the leg a TPU takes, the grouped SwiGLU kernel
        patch.setenv("VESCALE_KERNELS", LEGS[leg])
        patch.setattr(dropless, "DENSE_MAX_TOKENS", 0)
        patch.setattr(dropless, "PADDED_MAX_MEAN_ROWS", 0)
    mesh = DeviceMesh(("tp",), (1,), devices=jax.devices()[:1])
    cache = PagedKVCache(hybrid_cache_config(cfg, num_slots=toy.SLOTS, page_size=toy.PAGE, pages_per_slot=toy.PAGES,
                                             num_pages=getattr(toy, "POOL", None)), mesh)
    engine = HybridServeEngine(cfg, mesh, None, cache)
    params = jax.eval_shape(lambda key: engine.model.init_params(cfg, key), jax.random.key(7))
    return engine, params


def _program(engine, params, which: str):
    """One of the engine's jitted programs and the shapes it is called with."""
    cache = engine.cache
    shape = lambda *dims: jax.ShapeDtypeStruct(dims, jnp.int32)
    held = tuple(jax.ShapeDtypeStruct(a.shape, a.dtype) for a in engine._held())
    if which == "decode":
        S = cache.num_slots
        return engine._decode_fn, (params, *held, shape(S, cache.config.pages_per_slot), shape(S), shape(S))
    bucket = engine.buckets[WHICH.index(which)]
    prompt = (shape(bucket), shape(), shape(bucket // cache.config.page_size), shape())
    if engine.rides:
        S = cache.num_slots
        return engine._ride_fn, (params, *held, shape(S, cache.config.pages_per_slot), shape(S), shape(S), shape(S), *prompt)
    return engine._prefill_fn, (params, *held, *prompt)


def _jaxpr_digest(fn, args) -> str:
    text = re.sub(r" at 0x[0-9a-f]+", "", str(jax.make_jaxpr(fn)(*args)))      # (a closure prints its address)
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def _scopes(fn, args) -> str:
    """``"vs.attn=<operations under it> ..."`` of the lowered module: each operation's location resolved to its
    name stack (the locations' table follows the module), the ``vs.*`` scopes on it counted."""
    text = fn.lower(*args).as_text(debug_info=True)
    names = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, flags=re.M))
    counts = collections.Counter(scope for ref in re.findall(r"loc\((#loc\d+)\)$", text, flags=re.M)
                                 for scope in set(re.findall(r"vs\.[a-z_]+", names.get(ref, ""))))
    return " ".join(f"{scope}={n}" for scope, n in sorted(counts.items()))


def _taken(family: str, leg: str, which: str):
    """``(the program's name in PROGRAMS, its digest, its scopes)``."""
    with pytest.MonkeyPatch.context() as patch:
        engine, params = _engine(family, leg, patch)
        fn, args = _program(engine, params, which)
        return RIDING.get(which, which) if engine.rides else which, _jaxpr_digest(fn, args), _scopes(fn, args)


@pytest.mark.parametrize("which", WHICH)
@pytest.mark.parametrize("leg", list(LEGS))
@pytest.mark.parametrize("family", list(FAMILIES))
def test_a_serve_program_traces_to_the_text_and_the_scopes_it_had(family, leg, which):
    name, digest, scopes = _taken(family, leg, which)
    assert (digest, scopes) == PROGRAMS[f"{family}/{leg}/{name}"]


if __name__ == "__main__":
    for family in FAMILIES:
        for leg in LEGS:
            for which in WHICH:
                name, *taken = _taken(family, leg, which)
                print(f'    "{family}/{leg}/{name}": {tuple(taken)!r},', flush=True)
