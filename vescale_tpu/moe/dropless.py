"""A dropless expert layer that is told which experts it holds.

The router scores every expert the model has; this chip (or rank) holds the
contiguous ids ``first_held .. first_held + held``, and computes the part of
the result that its own experts give.  The (token, expert) pairs that fall on
held experts are sorted by expert and go through grouped matrix products (on
TPU one kernel, ``kernels/grouped_swiglu.py``, which brings each expert's three
matrices into VMEM once for that expert's own rows), and come back to their
tokens weighted by the gates.  No capacity, no ``(N, E, C)`` mask, nothing dropped:
work and memory are linear in tokens x experts-per-token.  A pair whose expert
lives elsewhere adds nothing here (the exchange that would carry it there is
not this module's; on one chip the layer runs without it), and tokens masked
out (slots that are not active, pad positions) route nowhere.

A router may have more outputs than the model has experts with weights
(:func:`identity_experts`: zero-compute experts, whose part is the token itself
under its gates): those ids lie past every held range, so the forms below never
see them as work, and a token's pairs on real experts are then FEWER than ``k``
and vary from token to token.

Three forms, one result; the choice reads shapes and counts, never a knob or a
model's name.  What decides is how many ROWS AN EXPERT gets: of ``N`` tokens
with ``k`` experts each, ``N k / E`` on the mean where the router scores ``E``
outputs, whatever share of them is held here, and at most all ``N k`` over the
``held`` (a router may send every pair here).

* *All on all.*  At most ``DENSE_MAX_TOKENS`` tokens (a decode step of one
  position a slot): every held expert runs on every token as one batched
  product and the gates, zero where a token did not keep the expert, weigh the
  sum.  ``ragged_dot`` works in row tiles of 128, so with so few tokens
  each touched expert costs it a whole tile anyway, and the chip's trace
  (PERF.md, PR 29) showed it streaming the expert weights at
  half the memory's rate where the batched product streams them at nine
  tenths: a decode step is those weights' read.
* *Sorted.*  The pairs on held experts are sorted by expert, each expert's
  rows from a multiple of a row tile on, and go through ONE grouped SwiGLU
  kernel (``kernels/grouped_swiglu.py``: a grid over row tiles; a tile's expert
  picks the weight blocks, which stay in VMEM across that expert's tiles while
  the next expert's arrive; the hidden never leaves VMEM; an expert with no row
  costs no read).  Work linear in the pairs that landed here: the form for many
  rows an expert (a long prefill), where all-on-all would grow with tokens x
  held experts, and for very few (a short prefill over many small experts),
  where the padded form is mostly zeros.  The kernel alone streams a layer's
  expert weights at 550-700 GB/s from 8 to 96 rows an expert at four models'
  widths and is bound by the MXU from about 128 rows on; with the gather of the
  rows before it and the un-sort after it, which stay XLA's, the layer reads
  300-660 GB/s there (PERF.md section 6, PR 46, the table of three forms).
  Dispatched as the package's other kernels are (``kernels.resolve``): compiled
  on TPU, and everywhere else, and under ``VESCALE_KERNELS=off``, the XLA leg it
  replaced: three ``jax.lax.ragged_dot`` over the rows in plain sorted order,
  the hidden between them in HBM, 30-350 GB/s on the same table.  That leg is
  also what a padded candidate falls back to, on every backend (the rule,
  below).
* *Padded.*  More tokens than all-on-all can carry but FEW ROWS AN EXPERT (a
  pass of 128 slots x 4 positions over 128 experts: 32; a short prefill):
  each held expert's rows are gathered, in the sorted order, into ``ROW_PAD``
  places (zeros behind its count), the three products run as batched products
  over ``(held, ROW_PAD, .)``, and a token's ``k`` results are gathered back
  from place ``expert x ROW_PAD + (place in the order - the expert's first)``
  under its gates.  The same products on the same operands as the sorted
  form; only the order of a token's ``k`` terms may differ.  The pad is one
  row tile of the MXU: a grouped product spends one on a touched expert whatever
  its count, so up to there the padded rows cost the MXU nothing it was not
  already spending, the form stays bound by the weights' read, and XLA's
  batched product streams them at twice ``ragged_dot``'s rate (PERF.md
  section 6, PR 37, "crossover": 558-665 GB/s against 252-340 at 16 to 64 rows
  an expert, at two models' widths).  Against the grouped kernel (PR 46, the
  same table with a third column) it keeps the middle: from 32 rows an expert
  up to the pad the two lie within a few per cent of each other at most
  widths, and below 32 the pad's zeros cost it a fifth to a third.

The rule.  ``N <= DENSE_MAX_TOKENS``: all on all.  Otherwise, if the pairs
would fill a quarter of the pad on average and fit it with room for a router's
unevenness (:func:`padded_candidate`: ``E x PADDED_MIN_MEAN_ROWS <= N k <=
held x PADDED_MAX_MEAN_ROWS``), the call
holds both other forms and chooses ON THE DEVICE, from the counts it has
(:func:`fits_pad`: the busiest held expert got at most ``ROW_PAD`` rows: the
padded form; else the sorted one on its XLA leg, same result: with the kernel
in the branch that is rarely taken, the compiler built the padded branch
slower at one model's widths).  Otherwise the sorted form alone, the grouped
kernel on TPU.  The two bounds count different rows.  The LOWER one is of
speed, "is the pad mostly zeros?", and so of the rows that really land: the
mean over ALL the ``E`` outputs the router scores, ``N k / E``, which a tree
that holds a share (``held < E``) gets on each held expert as any other tree
does.  The UPPER one is of fit, "has every pair a place?", and so of the most
that may land: all ``N k`` on the ``held``; a candidate that does not fit falls
to the ``ragged_dot`` leg, the slowest there is, so this bound does not move with
the share.  Told ``E``, a call can therefore only LEAVE the padded form for the
sorted one (256 rows x 8 over 64 of 512 experts: 4 rows an expert, not 32), and
none enters it.  The same mean sizes the grouped kernel's row tile; the layout's
SIZE stays the one that has a place for every pair.

``moe.layer.MoEMLP`` / ``TokenDispatcher`` (capacity, one-hot masks, expert
biases) stay as they are for training; ROADMAP D4 moves them here.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = ["route_topk", "route_group_limited", "route_sigmoid_topk", "route_sigmoid_group_limited", "route_softmax_biased", "identity_experts",
           "dropless_experts", "routed_experts", "in_row_pieces", "row_pieces", "SORTED_FORM_BYTES", "padded_candidate", "fits_pad", "expert_form", "grouped_leg", "DENSE_MAX_TOKENS", "ROW_PAD", "PADDED_MIN_MEAN_ROWS", "PADDED_MAX_MEAN_ROWS"]

# one row tile of the MXU: up to here each touched expert costs a grouped product a tile, sorted or not
DENSE_MAX_TOKENS = 128
# the padded form's places an expert: the same row tile, so a touched expert costs no more rows than the grouped product spends
ROW_PAD = 128
# pairs a HELD expert (N k / held: every pair may land here) up to which a call holds the padded form.  PERF.md section 6, PR 37,
# "crossover": at 16 / 32 / 64 / 96 / 128 rows an expert the padded form streamed the weights 2.1-3.4 times as fast as the sorted
# one wherever it fit, so the bound is one of FIT, not of speed: a uniform router's busiest of 128 experts gets mean + 2.6
# sqrt(mean), past the pad from a mean of about 100 on, and such a call would compile a branch it never takes
PADDED_MAX_MEAN_ROWS = 96
# ... and the pairs a SCORED expert (N k / E, the rows that really land on an expert, held or not) from which: below a quarter of
# the pad three quarters of every padded array are zeros, and the grouped kernel, whose row tile follows the rows, streams the
# weights faster (PERF.md section 6, PR 46, the table of three forms; PR 64: 4 rows an expert over 64 of 512)
PADDED_MIN_MEAN_ROWS = 32
# what a call holds: one form, or the padded and the sorted one under a choice on the device
ALL_ON_ALL, SORTED, PADDED_OR_SORTED = "all_on_all", "sorted", "padded_or_sorted"
# What the sorted form may lay out at once (:func:`row_pieces`).  It is sized for EVERY kept pair landing here, ``k`` a row
# (the router could send them all), whatever share of the model's experts is held: ``k x d`` numbers a row in two types (the
# gathered rows in the operands' and the products' results in float32, 6 bytes a number).  At 12 x 6144 a row that is 3.0 GB
# at a rung of 4,096 rows beside 13.7 GB of weights and cache (read on a described v5e, PERF.md section 6, PR 54); under this
# bound 1,024 rows there and 4,096 rows of 8 x 2560 (PR 63) are a piece, 0.45 and 0.5 GB.
SORTED_FORM_BYTES = 512 << 20


def route_topk(scores, k: int) -> Tuple[jax.Array, jax.Array]:
    """The ``k`` largest of each token's router scores (N, E) and their
    gates: a softmax over those ``k`` alone.  Returns ids (N, k) int32 and
    gates (N, k) float32."""
    top, idx = jax.lax.top_k(scores.astype(jnp.float32), k)
    return idx.astype(jnp.int32), jax.nn.softmax(top, axis=-1)


def route_group_limited(scores, k: int, *, n_group: int, topk_group: int, scale: float = 1.0
                        ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Group-limited greedy routing: a softmax over ALL of a token's router
    scores (N, E); the experts lie in ``n_group`` contiguous groups of ``E /
    n_group``, a group scores as its best expert does, the ``topk_group`` best
    groups are kept and the rest zeroed; then the ``k`` largest of what is
    left.  The gates are those probabilities AS THEY ARE (not renormalised
    over the kept), times ``scale``.  Returns ids (N, k) int32 and gates
    (N, k) float32, as :func:`route_topk` does, and which groups each token
    kept (N, n_group) bool."""
    N, E = scores.shape
    if E % n_group or not 0 < topk_group <= n_group or k > topk_group * (E // n_group):
        raise ValueError(f"{E} experts in {n_group} groups, {topk_group} kept, cannot give {k} a token")
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    _, groups = jax.lax.top_k(jnp.max(probs.reshape(N, n_group, E // n_group), axis=-1), topk_group)
    kept = jnp.zeros((N, n_group), bool).at[jnp.arange(N)[:, None], groups].set(True)
    top, idx = jax.lax.top_k(jnp.where(jnp.repeat(kept, E // n_group, axis=1), probs, 0.0), k)
    return idx.astype(jnp.int32), top * scale, kept


def route_sigmoid_topk(scores, k: int, *, scale: float = 1.0, bias=None) -> Tuple[jax.Array, jax.Array]:
    """Sigmoid routing: each of a token's router scores (N, E) through a
    float32 sigmoid on its own (no softmax over the experts), the ``k`` largest,
    their gates renormalised to sum 1 over those ``k``, times ``scale`` (the
    convention a ``routed_scaling_factor`` is published under where the scores
    are sigmoids); no groups.  ``bias`` (E,) float32 is a SELECTION bias (the
    sources' ``e_score_correction_bias``): the ``k`` largest of ``sigmoid(scores)
    + bias`` are kept, and the gates are the kept experts' ``sigmoid(scores)``
    as they are, renormalised: the bias chooses, it does not weigh.  None: the
    program without one.  Returns ids (N, k) int32 and gates (N, k) float32, as
    :func:`route_topk` does."""
    probs = jax.nn.sigmoid(scores.astype(jnp.float32))
    if bias is None:
        top, idx = jax.lax.top_k(probs, k)
    else:
        _, idx = jax.lax.top_k(probs + bias.astype(jnp.float32), k)
        top = jnp.take_along_axis(probs, idx, axis=-1)
    return idx.astype(jnp.int32), top * (scale / jnp.sum(top, axis=-1, keepdims=True))


def route_sigmoid_group_limited(scores, k: int, *, n_group: int, topk_group: int, scale: float = 1.0, bias=None
                                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Sigmoid routing under a group limit (the sources' ``noaux_tc``): each of
    a token's router scores (N, E) through a float32 sigmoid on its own, ``s``;
    the CHOICE is made on ``c = s + bias`` (``bias`` (E,) float32, the sources'
    ``e_score_correction_bias``; None: ``c = s``): the experts lie in ``n_group``
    contiguous groups of ``E / n_group``, a group scores as the sum of its TWO
    largest ``c`` (the one source's rule), the ``topk_group`` best groups are kept, then the
    ``k`` largest ``c`` among the kept groups' experts (ties to the lower id, as
    ``jax.lax.top_k`` breaks them).  The gates are the kept experts' ``s`` (the
    bias chooses, it does not weigh), renormalised to sum 1 over those ``k``,
    times ``scale``.  Returns ids (N, k) int32 and gates (N, k) float32, as
    :func:`route_topk` does, and which groups each token kept (N, n_group)
    bool, as :func:`route_group_limited` does."""
    N, E = scores.shape
    per = E // n_group
    if E % n_group or not 0 < topk_group <= n_group or k > topk_group * per or per < 2:
        raise ValueError(f"{E} experts in {n_group} groups, {topk_group} kept, cannot give {k} a token")
    probs = jax.nn.sigmoid(scores.astype(jnp.float32))
    choice = probs if bias is None else probs + bias.astype(jnp.float32)
    # No sort anywhere a maximum does: on the chip ``top_k(grouped, 2)`` is a full sort of every group (171 us a call at
    # (256, 8, 64)), ``top_k(..., k)`` one of every row, and the scatter that marked the kept groups sorts its indices first;
    # the rule alone took 249 us at 256 rows and takes 34 (PERF.md section 6, PR 67).  ``argmax`` gives the FIRST of equal
    # maxima, so a group whose two largest are equal scores twice that value and the ids come in ``lax.top_k``'s order.
    without = lambda values, at: jnp.where(jnp.arange(values.shape[-1]) == at[..., None], -jnp.inf, values)
    grouped = choice.reshape(N, n_group, per)
    second = jnp.max(without(grouped, jnp.argmax(grouped, axis=-1)), axis=-1)
    _, groups = jax.lax.top_k(jnp.max(grouped, axis=-1) + second, topk_group)
    kept = jnp.any(groups[:, :, None] == jnp.arange(n_group), axis=1)
    left, ids = jnp.where(jnp.repeat(kept, per, axis=1), choice, -jnp.inf), []
    for _ in range(k):
        ids.append(jnp.argmax(left, axis=-1))
        left = without(left, ids[-1])
    idx = jnp.stack(ids, axis=-1)
    top = jnp.take_along_axis(probs, idx, axis=-1)
    return idx.astype(jnp.int32), top * (scale / jnp.sum(top, axis=-1, keepdims=True)), kept


def route_softmax_biased(scores, k: int, *, scale: float = 1.0, bias=None) -> Tuple[jax.Array, jax.Array]:
    """A softmax over ALL of a token's router scores (N, X) in float32 (``X``
    may count more outputs than there are experts with weights:
    :func:`identity_experts`); the ``k`` largest of ``probabilities + bias``
    are kept (``bias`` (X,) float32 is a SELECTION bias, the sources'
    ``e_score_correction_bias``: it chooses, it does not weigh; None: the
    program without one), and the gates are the kept outputs' probabilities AS
    THEY ARE (not renormalised over the kept), times ``scale``; no groups.
    Returns ids (N, k) int32 and gates (N, k) float32, as :func:`route_topk`
    does."""
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    if bias is None:
        top, idx = jax.lax.top_k(probs, k)
    else:
        _, idx = jax.lax.top_k(probs + bias.astype(jnp.float32), k)
        top = jnp.take_along_axis(probs, idx, axis=-1)
    return idx.astype(jnp.int32), top * scale


def identity_experts(x, idx, gates, *, first_identity: int, token_mask: Optional[jax.Array] = None):
    """ZERO-COMPUTE experts: the router's outputs ``first_identity ..`` have no
    weights, and a kept pair on one of them gives the token back under its
    gate, so their part of the layer is ``(sum over a token's kept ids >=
    first_identity of g) x``: no product, no weight, no exchange, and so
    computed WHOLE on every chip of a share for that chip's own tokens (in the
    sum over shares it counts once, as a shared expert does), while
    :func:`dropless_experts` sends the same ids, which lie outside any held
    range, to its trailing group that no product touches.  ``x`` (N, d),
    ``idx`` / ``gates`` (N, k) from a routing rule, ``token_mask`` (N,) bool as
    there.  Returns the part (N, d) float32 and how many kept pairs of the
    tokens that route fell on identity experts (int32)."""
    zero = idx >= first_identity
    if token_mask is not None:
        zero = zero & token_mask[:, None]
    weight = jnp.sum(jnp.where(zero, gates, 0.0), axis=-1, keepdims=True)
    return weight * x.astype(jnp.float32), jnp.sum(zero, dtype=jnp.int32)


def padded_candidate(N: int, k: int, held: int, scored: Optional[int] = None) -> bool:
    """The static half of the choice: may a call of ``N`` tokens with ``k`` experts each over ``held`` held experts of
    the ``scored`` the router has outputs for (None: all of them are held) take the padded form?  (The other half is
    :func:`fits_pad`, of the counts.)"""
    scored = held if scored is None else scored
    return N > DENSE_MAX_TOKENS and scored * PADDED_MIN_MEAN_ROWS <= N * k <= held * PADDED_MAX_MEAN_ROWS


def expert_form(N: int, k: int, held: int, scored: Optional[int] = None) -> str:
    """What a call of these shapes holds: ``ALL_ON_ALL``, ``SORTED``, or ``PADDED_OR_SORTED`` (both, under the
    choice on the device)."""
    return ALL_ON_ALL if N <= DENSE_MAX_TOKENS else PADDED_OR_SORTED if padded_candidate(N, k, held, scored) else SORTED


def grouped_leg(dtype, d: int, f: int) -> Optional[bool]:
    """Which leg the sorted form of experts ``d`` x ``f`` in ``dtype`` takes, by ``kernels.resolve``: the grouped
    kernel's ``interpret`` flag, or None for ``jax.lax.ragged_dot``."""
    from .. import kernels                                      # (Pallas comes with it: imported late, as the models do)
    from ..kernels import grouped_swiglu as _grouped

    return kernels.resolve("grouped_experts", supported=lambda interpret: _grouped.supports(dtype, d, f, interpret=interpret))


def fits_pad(counts):
    """The half on the device: did no held expert get more rows than the pad?  ``counts`` (..., held), the
    device's or the host's copy of them: the serve engine asks this of the same integers."""
    return counts.max(axis=-1) <= ROW_PAD


def dropless_experts(x, idx, gates, w_gate, w_up, w_down, *, first_held: int = 0, scored: Optional[int] = None,
                     token_mask: Optional[jax.Array] = None, dtype=None):
    """``sum over kept and held e of g_e * W_down,e (silu(W_gate,e x) * W_up,e x)``.

    ``x`` (N, d) tokens; ``idx`` / ``gates`` (N, k) from :func:`route_topk`,
    ids over ALL experts; ``w_gate`` / ``w_up`` (held, d, f) and ``w_down``
    (held, f, d) the held experts' SwiGLU weights, no biases; ``scored`` how
    many outputs the router that gave ``idx`` scores (None: the held ones are
    all); ``token_mask`` (N,) bool, False for tokens that route nowhere;
    ``dtype`` the products' operand type (default: the weights').  Returns the
    result (N, d) float32 and the tokens each held expert got (held,) int32.
    """
    held, d, f = w_gate.shape
    N, k = idx.shape
    scored = held if scored is None else scored
    form = expert_form(N, k, held, scored)
    dtype = w_gate.dtype if dtype is None else dtype
    return _experts(x, idx, gates, w_gate, w_up, w_down, token_mask, first_held=first_held, scored=scored, dtype=dtype,
                    form=form, grouped=grouped_leg(dtype, d, f) if form == SORTED else None)


def routed_experts(h, router, route: Callable, w_gate, w_up, w_down, *, first_held: int = 0,
                   token_mask: Optional[jax.Array] = None, dtype=None):
    """A routed expert layer from its tokens on: the router's scores of ALL
    experts, ``h`` (N, d) on ``router`` (d, E) in float32 at the highest
    precision; ``route(scores)``, one of this module's routing rules with its
    numbers bound; :func:`dropless_experts`, whose other arguments these are.
    Returns ``(result, counts, *what route gave beside ids and gates)``."""
    scores = jnp.dot(h.astype(jnp.float32), router.astype(jnp.float32), precision=jax.lax.Precision.HIGHEST)
    idx, gates, *own = route(scores)
    return (*dropless_experts(h, idx, gates, w_gate, w_up, w_down, first_held=first_held, scored=router.shape[-1],
                              token_mask=token_mask, dtype=dtype), *own)


def row_pieces(N: int, k: int, d: int) -> int:
    """In how many equal pieces ``N`` rows of width ``d`` with ``k`` experts each go through the layer so that a piece's
    sorted form stays within ``SORTED_FORM_BYTES``: the fewest that divide ``N``."""
    pieces = -(-N // max(1, SORTED_FORM_BYTES // (6 * k * d)))
    while N % pieces:
        pieces += 1
    return pieces


def in_row_pieces(rows: Callable, h, token_mask: Optional[jax.Array], *, k: int):
    """``rows(h, token_mask)``, a model's routed part of tokens ``h`` (N, d) (a call of :func:`routed_experts` and what
    the model adds to it), over the :func:`row_pieces` of ``h`` one after another (``lax.map``), for one more read of the
    touched experts' weights a piece.  ``rows`` returns the tokens' result (n, .) first and then counts that add up
    over rows; so does this."""
    N, d = h.shape
    pieces = row_pieces(N, k, d)
    if pieces == 1:
        return rows(h, token_mask)
    mask = jnp.ones((N,), bool) if token_mask is None else token_mask
    out, *counts = jax.lax.map(lambda piece: rows(*piece), (h.reshape(pieces, N // pieces, d), mask.reshape(pieces, -1)))
    return (out.reshape(N, -1), *(c.sum(axis=0) for c in counts))


# jitted inside its caller's program: a model's layers have one shape, so the layer is traced and lowered once a
# program and not once a layer (a call that holds two forms is twice the text; warm set-up is tracing and lowering)
@functools.partial(jax.jit, static_argnames=("first_held", "scored", "dtype", "form", "grouped"))
def _experts(x, idx, gates, w_gate, w_up, w_down, token_mask, *, first_held, scored, dtype, form, grouped):
    held, d, f = w_gate.shape
    N, k = idx.shape
    local = idx - first_held
    here = (local >= 0) & (local < held)
    if token_mask is not None:
        here = here & token_mask[:, None]
    # pairs by expert, the held ones first; everything else in one trailing group that no product touches
    group = jnp.where(here, local, held).reshape(N * k)
    counts = jnp.zeros((held + 1,), jnp.int32).at[group].add(1)[:held]
    kept = jnp.where(here, gates, 0.0)                               # (N, k): a pair that adds nothing here weighs 0
    if form == ALL_ON_ALL:
        # every held expert on every token; a token's gate for an expert it did not keep is 0
        weight = jnp.einsum("nk,nke->ne", kept, jax.nn.one_hot(local, held, dtype=jnp.float32))
        xb = jnp.broadcast_to(x.astype(dtype)[None], (held, N, x.shape[-1]))
        batched = lambda w: jnp.einsum("end,edf->enf", xb, w.astype(dtype), preferred_element_type=jnp.float32)
        hidden = (jax.nn.silu(batched(w_gate)) * batched(w_up)).astype(dtype)                           # (held, N, f)
        out = jnp.einsum("enf,efd->end", hidden, w_down.astype(dtype), preferred_element_type=jnp.float32)
        return jnp.einsum("end,ne->nd", out, weight), counts
    order = jnp.argsort(group, stable=True)
    # where each pair went in that order: a pair that is not here lies past the groups
    back = jnp.zeros((N * k,), jnp.int32).at[order].set(jnp.arange(N * k, dtype=jnp.int32)).reshape(N, k)

    def gathered(ys, at):
        # back to the tokens, a choice at a time, under the gates; a pair that is not here is dropped where it is
        # read.  (N, d) at a time: zeroing the rows first and un-sorting them whole passed four times over
        # (N * k, d) in float32, 66 ms of a 396 ms prefill of 8192 tokens (PERF.md, PR 34)
        out = jnp.zeros((N, ys.shape[-1]), jnp.float32)
        for j in range(k):
            out = out + jnp.where(here[:, j, None], jnp.take(ys, at[:, j], axis=0), 0.0) * kept[:, j, None]
        return out

    def ragged_form():
        xs = jnp.take(x, order // k, axis=0).astype(dtype)
        product = lambda a, w: jax.lax.ragged_dot(a, w.astype(dtype), counts, preferred_element_type=jnp.float32)
        hidden = (jax.nn.silu(product(xs, w_gate)) * product(xs, w_up)).astype(dtype)
        return gathered(product(hidden, w_down), back)             # (N * k, d); rows past the groups are undefined

    def grouped_form():
        # the kernel's layout: expert e's rows from a multiple of its row tile on, what is left of e's last tile filled
        # with dummies.  One stable sort lays it out (e's pairs, then e's dummies; every pair that is not here and every
        # unused dummy behind the last expert) and a second inverts it: a sort of a hundred thousand integers is a
        # twentieth of a millisecond where a gather of as many from a table is one (PERF.md section 6, PR 46)
        from ..kernels import grouped_swiglu as _grouped

        # the row tile follows the rows an expert really gets, the mean over all the router scores (at four cells' widths no
        # rung was slower for it, with an expert's matrices whole in VMEM or streamed once a row tile: PERF.md section 6, PR
        # 64); the layout's SIZE is the one with a place for every pair, whatever the router does (the kernel spends
        # nothing behind the last real tile)
        tm, tf = _grouped.tiles(d, f, dtype, N * k / scored)
        rows = _grouped.row_tiles(N * k, held, tm) * tm
        fill = jnp.arange(rows - N * k, dtype=jnp.int32)                    # the dummies: tm an expert, then what rounds N k up
        expert, nth = fill // tm, fill % tm
        filler = jnp.where((expert < held) & (nth < jnp.take(-counts % tm, jnp.minimum(expert, held - 1))), expert, held)
        iota = jnp.arange(rows, dtype=jnp.int32)
        _, at = jax.lax.sort((jnp.concatenate([group, filler]), iota), num_keys=1, is_stable=True)
        _, place = jax.lax.sort((at, iota), num_keys=1)             # where each pair went, the dummies behind
        xs = jnp.take(x.astype(dtype), jnp.where(at < N * k, at // k, 0), axis=0, mode="clip")
        ys = _grouped.grouped_swiglu(xs, counts, w_gate.astype(dtype), w_up.astype(dtype), w_down.astype(dtype),
                                     tm=tm, tf=tf, interpret=grouped)
        return gathered(ys, jnp.where(here, place[:N * k].reshape(N, k), 0))

    def padded_form():
        # expert e's rows are places offsets[e] .. + counts[e] of the order; behind its count, zeros
        offsets = jnp.cumsum(counts) - counts
        place = jnp.arange(ROW_PAD, dtype=jnp.int32)
        token = jnp.take(order, jnp.minimum(offsets[:, None] + place, N * k - 1)) // k                 # (held, P)
        xp = jnp.where((place < counts[:, None])[..., None], jnp.take(x, token, axis=0), 0).astype(dtype)
        product = lambda a, w: jnp.einsum("epa,eab->epb", a, w.astype(dtype), preferred_element_type=jnp.float32)
        hidden = (jax.nn.silu(product(xp, w_gate)) * product(xp, w_up)).astype(dtype)                   # (held, P, f)
        ys = product(hidden, w_down).reshape(held * ROW_PAD, -1)
        inside = back - jnp.take(offsets, jnp.clip(local, 0, held - 1))
        return gathered(ys, jnp.where(here, local * ROW_PAD + inside, 0))

    if form == SORTED:
        return (ragged_form if grouped is None else grouped_form)(), counts     # the sorted form's two legs
    # a candidate falls back to the XLA leg on every backend: with the kernel as the branch it rarely takes, the compiler
    # built the padded branch it does take 0.14 ms a layer slower at 128 experts of 2048 x 768 (PERF.md section 6, PR 46)
    return jax.lax.cond(fits_pad(counts), padded_form, ragged_form), counts
