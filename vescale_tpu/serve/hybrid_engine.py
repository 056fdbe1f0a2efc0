"""Serve engine for decoders whose block is not the Llama block: the block's
mathematics, its share of the cache and its counters come from the MODEL'S
MODULE, and this class keeps what every such model needs once: the prefill
ladder, ``warm()``, donation, the ``vs.serve-*`` spans and ``trace_counters()``;
``decode`` itself, one step deep (a call launches its step and returns the
``DecodeStep`` unread; a ``DecodeFeed`` feeds the next from the device), and
the prompts that wait to RIDE a step, are ``engine.DecodeAhead``'s, shared with
``ServeEngine``.  Nine models plug in today:

  * ``models/granite_hybrid.py`` (the class's name is from it): state-space
    mixers with a per-slot recurrent state beside the paged K/V of their few
    attention layers, a routed expert layer in every layer; it gives a
    ``serve_ride`` body, so its prompts ride, and the expert layer runs once
    over the step's rows and the prompt's;
  * ``models/deepseek_v2.py``: latent attention over a LATENT paged cache
    (one pool, no values, no slot state), prefill in the expanded form and
    decode in the absorbed one, group-limited routed experts after a dense
    first layer;
  * ``models/sdar_moe.py``: generation by DIFFUSION OVER BLOCKS.  A decode
    step is a pass over every slot's open block of ``B`` positions and yields
    none or up to ``B`` tokens a slot ("A block engine", below); grouped-query
    attention with a per-head norm over paged K/V, 128 routed experts a layer;
  * ``models/falcon_h1.py``: a state-space mixer AND a rotary grouped-query
    attention mixer side by side in EVERY layer, so every layer owns a row of
    the state arrays and a layer of the K/V pools; two groups of B and C; a
    dense MLP (no experts: the engine's ``moe_*`` counters stay 0); it gives a
    ``serve_ride`` body too (the first that did), so its prompts ride;
  * ``models/laguna.py``: window and full attention MIXED, with more query
    heads and another rotary term on the window layers: the full layers keep
    pages, a window layer a RING a slot of the newest ``window`` positions (slot
    state, read as pages under an arithmetic table by the same ``paged_decode``);
    256 small sigmoid-routed experts beside a shared one after a dense first layer;
  * ``models/mimo_v2.py``: window and full attention mixed with KEYS OF 192 BESIDE VALUES OF 128 and a learned sink in
    every window layer's softmax: FOLDED pages on 4 key heads and folded rings of 128 on 8 (``KVCacheConfig.folded`` /
    ``v_head_dim``), read by ``paged_decode_folded``; a share of 256 sigmoid-routed experts chosen under a selection bias;
  * ``models/longcat_flash.py``: a layer of TWO latent-attention sublayers (``models/mla.py``'s block, which DeepSeek-V2
    has too, at 64 heads with two LoRA multipliers) and two dense SwiGLUs, so a model layer owns two layers of the latent
    pool; a routed branch that leaves after the first sublayer and returns at the layer's end, over a softmax router some of
    whose outputs are zero-compute identity experts (``moe.dropless.route_softmax_biased`` / ``identity_experts``);
  * ``models/phi4flash.py``: a stack in TWO halves whose second half keeps no cache of its own: Mamba-1 mixers (``ssm_step_selective``,
    ``kernels/selective_scan.py``) beside window layers' rings under differential attention, then ONE pool layer (``cache_config``'s
    ``layers`` = 1) that the layer which writes it and seven cross-attention layers read, and gated memory units that read one layer's
    scan output; its ``serve_prefill`` runs the second half on the last real row alone; both halves are ``lax.scan``s over stacked
    periods, the cache's arrays in the carry; dense (no experts);
  * ``models/ling_hybrid.py``: DELTA-RULE linear attention (``models/kda.py``, ``kernels/kda.py``: a float32 MATRIX state a head
    and slot, decayed a row at a time and corrected by a rank-1 term; ``kda_step`` in a decode step, ``kda_chunk`` over a prompt)
    in five layers of six and latent attention (``models/mla.py`` without a query LoRA, a head-wise gate on its output) in the
    sixth: the first cache that is a LATENT pool (one layer of the cut's seven) AND slot state (six layers' states and
    convolution tails); one routing group of 512 sigmoid-routed experts under a group limit
    (``moe.dropless.route_sigmoid_group_limited``) beside a shared expert.

A second engine class beside :class:`ServeEngine`, behind the same surface
(``prefill(prompt, slot)``, ``decode(tokens)``, ``params``,
``trace_counters()``, ``greedy``), not ``ServeEngine`` taught more blocks:
that class IS the Llama block (its stage split, its ``decode_multi`` and
``prefill_suffix`` all walk ``layers_i.self_attn``), while everything above it
(``ContinuousBatchingScheduler``, ``run_serve_resilient``, sampling, the
``vs.serve-*`` spans, the counters a trace session reads) takes either.

**The seam.**  The model's module (the one that defines ``config``'s class)
gives, as plain functions of the config:

  ``cache_config(config, *, num_slots, page_size, pages_per_slot, num_pages)``
      the ``KVCacheConfig`` of the model's cache;
  ``prefill_chunk(config)``
      the ladder's first rung (a scan's chunk, a flash block);
  ``decode_kernels(config, cache)``
      ``{kernel name: its interpret flag, or None for the XLA leg}``, resolved
      once at build (the program latches it) by each kernel's own ``leg(...)``
      (a kernel owns its XLA leg and the choice; ``serve_decode`` hands the flag
      to its op); ``engine.kernel_<name>`` says which leg each took, and
      ``decode_pages_*`` count with the kernel named ``decode``;
  ``serve_prefill(config, params, arrays, tokens, length, page_row, slot, *,
  page, interpret)`` -> ``(logits row, arrays)``
  ``serve_decode(config, params, arrays, table, lengths, tokens, *, active,
  write_page, write_offset, kernels)`` -> ``(logits, counts, arrays)``
      the bodies of the two programs; ``arrays`` is ``cache.arrays()``, the
      cache's device arrays by name, given and taken back whole (donated);
      ``active`` (S,) the slots that hold a request, ``write_page`` /
      ``write_offset`` (S,) where each slot's new position lands (the null
      page for a slot that may not write: the engine reckons it, once);
      ``counts["experts"]`` (expert layers, held) is the tokens each held
      expert got (a dense model leaves it out), whatever else ``counts`` holds
      is the model's own.  They are of the DECODE rows: a step that carries a
      prompt returns under ``"experts"`` what its ``S`` rows alone gave the
      experts, so that ``moe_*`` mean what they mean of a step without one;
  ``serve_ride(config, params, arrays, table, lengths, tokens, prompt, length,
  page_row, slot, *, active, write_page, write_offset, kernels, page,
  interpret)`` -> ``(logits (S, vocab), the prompt's logits row, counts, arrays)``
      OPTIONAL: the body of a decode step that CARRIES a prompt, ``serve_decode``
      with ``rung`` rows more.  The ``S`` decode rows and the prompt's rows are
      ONE array before every weight's product, so a weight crosses the HBM
      once for both; what is no weight's product runs for each kind of row as
      it does alone; the prompt's share of the cache goes to ``page_row`` and
      to ``slot``'s rows AFTER the step has touched them.  **A row that
      ``active`` does not name leaves its slot's state as it was, bit for
      bit** (and writes the null page): with no row active the program is a
      prompt launched alone, beside slots in the middle of their outputs,
      which ``serve_decode``, whose idle rows hold no request, need not mind.
      The engine OFFERS a ride (``engine.rides``, on the instance) iff the
      module has this function and a step moves one position a slot
      (``block`` is None): nothing tests a model's name, and there is no knob;
  ``STEP_COUNTERS``, ``step_counters(config, cache, lengths, counts)``,
  ``prefill_counters(config, bucket)``
      the names of the model's own counters and what one decode step, and one
      prefill, adds to them (a prompt is counted at the ``prefill`` call,
      whether a step carries it later or it goes alone);
  ``block_schedule(config)`` (only a model that generates by blocks)
      its ``engine.BlockSchedule``; ``serve_decode`` then returns ``(hidden (S x
      B, E), ids (S, B), counts, arrays)``, the open rows' final hidden state
      where another model's returns logits (``head(config, params, hidden
      rows)`` is what makes logits of them: the engine runs it over the rows a
      caller reads, when it reads them), and takes ``write_page`` /
      ``write_offset`` of each slot's open BLOCK, whose first position is
      ``lengths // B * B``, and ``next_page`` / ``next_offset`` of the block
      after it (a call that commits a block and opens the next writes both).

Two kinds of compiled program (where prompts ride, the second alone: below),
all static-shaped and all compiled by ``warm()`` before the engine is handed
over:

  **prefill**, one program a BUCKET: the prompt is padded to the next of
  ``prefill_buckets(chunk, max_seq_len)`` (the rule's home is ``serve/engine.py``,
  whose ``ServeEngine`` pads by it too): ``chunk, 2 chunk, 4 chunk, ...,
  max_seq_len``.  The pad rule is the model's (Granite: the state-space step
  size forced to 0 in the pad, the convolution tail from the last real
  inputs; both: pad positions follow the real ones, so causality keeps them
  out, and route to no expert); the logits row is the last real position's;
  what the bucket's positions leave in the cache goes to the slot's pages
  (what lies past its reserved pages to the null page), and a slot's row of
  every state array is wholly rewritten.

  **decode**, every slot: one token a slot, or one pass over a slot's open
  block (with, in the same call, the commit of the block before it).  The
  cache's arrays are donated: a second copy would not fit.  The step's counts
  (``counts["experts"]`` and the model's own) come to the host with its ids,
  when the step is read.

  **a step that carries a prompt** (``engine.rides``: the model gives
  ``serve_ride``), one program a BUCKET, in place of that bucket's prefill: its
  function is named ``decode`` (the device's module is ``jit_decode``, as the
  step's without a prompt: the benchmark joins a decode launch by that name).
  ``prefill`` then launches nothing and returns a ``PrefillStep`` that waits
  for the ``decode`` whose ``DecodeFeed`` names it as ``rider``; a prompt
  nobody carries goes ALONE through the same program with every decode row
  idle, so no program is a prefill's own (``_prefill_fn`` stays a jitted
  function that is never run: the benchmark's rehearsal lowers it).
  ``warm()`` runs each bucket's program that way, and ends by WAITING for the
  device: an executable read from the compile cache is loaded when it first
  runs, and that is set-up's, not the first request's (an engine that does
  not ride keeps the ``warm()`` it had).  ``prefill_rides`` of
  ``prefill_launches`` says how many prompts a step carried, and only an
  engine that rides reports it.

**A block engine** (``engine.block`` is the model's ``BlockSchedule``; the
serve loop learns from it that a step yields a COUNT a slot, no flag is
passed).  A slot's open block (its ``B`` ids, which of them are masked, the pass
it is at) is slot state of the cache: the prefill of a prompt of ``n`` tokens
writes K/V of its whole blocks, opens the block after them with the prompt's
last ``n mod B`` tokens revealed, and returns the logits row of position ``n -
1`` (NOT shifted: a row predicts its own position); every pass then writes the
block's K/V at the block's own positions, attends ``block start + B`` positions
(all that came before, and the block itself in full), and takes confidence,
selection and the new ids in the program; a block with nothing masked waits for
its COMMIT, which runs its final ids through the stack once more: their K/V are
the block's, its ids the block's tokens.  A PASS KEEPS NO LOGITS: its program
returns the open rows' hidden state (``S x B`` rows of the hidden size, 4 MB
where the logits are 311), and the step's ``(S, B, vocab)`` logits are a
:class:`RowsByDemand`: ``step[slot]``, ``step[[a, b]]``, ``step.block(slot)`` and
``np.asarray(step)`` run the model's ``head`` over the rows asked for (a small
program, compiled once a row count) and copy them out, ``step.shape`` /
``step.dtype`` / ``len(step)`` touch nothing; ``logits_rows_made`` counts the
rows so made (none in a serve loop that samples greedily).  A commit rides in the call that opens
the next block (``BlockSchedule.FUSED``: the final ids go through as B commit
rows in one of the program's ``commit_places`` places, the slot's open rows are
the block after it, all masked, at its first pass, and the state it leaves is
that block one pass on), so a block of ``B`` tokens is ``T`` calls; alone
(``OWN_PASS`` on a block with nothing masked: a request's last block, and every
block of a caller that names no ``fused`` slots) it is a call of its own that
leaves the state a fresh block.  ``decode(tokens)`` is ONE program in two uses:

  * ``decode(DecodeFeed(step before or None, slots={slot: tokens to take},
    fused=[slots]))``, the serve loop's: the named slots run the pass their
    state asks for (those of ``fused`` commit and open the next block), every
    other slot's block is held; nothing is fed, because the state is on the
    device; ``step.tokens`` is ``(S, B)``, and the host, which mirrors the
    static schedule (``BlockSchedule.plan``), takes ``tokens[slot, skip: skip +
    count]`` of a call that committed and moves the length by the block;
  * ``decode(host tokens (S,))``, the host-token form (what a reference check
    and a replay call: one token a slot, then ``cache.advance(slot)``): THE
    SAME PROGRAM teacher-forced.  The fed token is revealed at the slot's
    length ``L``, i.e. at position ``L mod B`` of its open block, the positions
    after it stay masked, nothing else is revealed, and ``step[slot]`` is the
    row of position ``L``: the model's logits there with positions ``<= L``
    holding their tokens and the rest of ``L``'s block masked, under the block
    mask.  At the block's last position that pass is the commit, alone: in
    this form the program's commit rows are dead.

What this engine refuses: ``decode_multi`` and ``prefill_suffix`` (speculation,
prefix sharing).  Over a cache with slot state (a recurrence's, an open
block's) they need a state at an earlier position, which ``PagedKVCache`` does
not keep (:class:`SlotStateUnsupported`); over a cache of pages alone (the
latent form) nothing stands in their way but the programs, which are not
written (``NotImplementedError`` names them); a block engine has neither
program, and a verify step of one token a position is not what its passes are.
``num_stages`` > 1 and a mesh of more than one device have no program here yet.
A block engine offers no ride whatever its module gives (a prompt is more rows
of a pass, which is another program), and neither does a model whose module
gives no ``serve_ride``: seven of the nine today (Falcon-H1's and Granite's do).
"""

from __future__ import annotations

import importlib
from typing import Any, Dict, Optional, Sequence

import numpy as np

from ..ndtimeline import predefined as _p
from ..ndtimeline.api import ndtimeit, register_counter_source
from .engine import BlockSchedule, DecodeAhead, DecodeFeed, PrefillStep, prefill_buckets
from .kv_cache import KVCacheConfig, PagedKVCache

__all__ = ["HybridServeEngine", "RowsByDemand", "hybrid_cache_config", "prefill_buckets"]

# what the engine counts for every model (``trace_counters``); a model's own follow (``STEP_COUNTERS``)
COUNTERS = ("decode_launches", "prefill_launches", "prefill_reads_ahead", "decode_steps", "decode_steps_ahead",
            "logits_bytes_to_host", "logits_rows_made", "prefill_tokens_real", "prefill_tokens_padded",
            "prefill_bucket_tokens", "decode_pages_read", "decode_pages_capacity", "moe_assignments",
            "moe_assignments_held", "moe_busiest_expert_tokens", "moe_expert_slots", "moe_layer_steps",
            "moe_experts_touched", "moe_padded_layer_steps", "moe_expert_layer_calls", "moe_grouped_layer_calls")
# ... and for a model that generates by blocks, in UNITS of B rows that went through the stack for a request (a
# denoising pass or a commit; a slot that fuses in a call is two, its commit and the next block's first pass):
# the units, those of them that were commits, the tokens the host took, the query rows that still had something
# to decide (masked positions at the start of their pass), the commits that rode with a denoising unit of their
# slot, and the slot-calls the host held back because every place for commit rows was taken, each summed over
# the calls read
BLOCK_COUNTERS = ("block_passes", "block_commit_passes", "block_tokens_emitted", "block_positions_masked",
                  "block_commits_fused", "block_commits_deferred")


class RowsByDemand:
    """What a block engine's ``DecodeStep`` holds where the logits lay: the
    ``(S, B, vocab)`` float32 logits of a pass's open rows as an array that is
    NOT THERE until it is read.  ``shape``, ``dtype`` and ``len`` are the
    configuration's and touch no device value; an index (whatever numpy takes
    over the first two axes: a slot, slots, ``(slots, rows)``, ``...``) runs the
    model's ``head``, with the parameters the pass ran on, over those rows of
    the hidden state the pass returned and gives them as a device array, and
    adds them to the engine's ``logits_rows_made``; ``np.asarray`` is every row."""

    __slots__ = ("_engine", "_params", "_hidden", "shape")
    dtype = np.dtype(np.float32)

    def __init__(self, engine: "HybridServeEngine", params, hidden):
        self._engine, self._params, self._hidden = engine, params, hidden
        self.shape = (engine.cache.num_slots, engine.block.B, engine.config.vocab_size)

    def __len__(self) -> int:
        return self.shape[0]

    def __getitem__(self, index):
        S, B, vocab = self.shape
        which = np.arange(S * B, dtype=np.int32).reshape(S, B)[index]
        self._engine.logits_rows_made += which.size
        return self._engine._head_fn(self._params, self._hidden, which.reshape(-1)).reshape(which.shape + (vocab,))

    def __array__(self, dtype=None, copy=None) -> np.ndarray:
        return np.asarray(self[...], dtype=dtype)


def _model_of(config):
    """The module that defines ``config``'s class: the model's own."""
    return importlib.import_module(type(config).__module__)


def hybrid_cache_config(config, *, num_slots: int, page_size: int, pages_per_slot: int,
                        num_pages: Optional[int] = None) -> KVCacheConfig:
    """The cache geometry of ``config``'s model (its module's ``cache_config``)."""
    return _model_of(config).cache_config(config, num_slots=num_slots, page_size=page_size,
                                          pages_per_slot=pages_per_slot, num_pages=num_pages)


class HybridServeEngine(DecodeAhead):
    """Compiled prefill (a program a bucket) and decode over ``cache``, a
    ``PagedKVCache`` built from :func:`hybrid_cache_config`.  ``config`` is the
    model's config object (the module that defines its class is the model's),
    ``params`` the tree of its ``init_params``."""

    def __init__(self, config, mesh, params: Dict[str, Any], cache: PagedKVCache, *, num_stages: int = 1,
                 interpret: Optional[bool] = None):
        c = config
        if num_stages != 1:
            raise NotImplementedError("HybridServeEngine has no stage split: num_stages must be 1")
        if mesh.size() != 1:
            raise NotImplementedError("HybridServeEngine runs on one device: the slot state has no sharded layout yet")
        self.model = _model_of(c)
        want = self.model.cache_config(c, num_slots=cache.num_slots, page_size=cache.config.page_size,
                                       pages_per_slot=cache.config.pages_per_slot, num_pages=cache.config.num_pages)
        if cache.config != want:
            raise ValueError(f"cache geometry {cache.config} is not this model's ({want}): build it from "
                             "hybrid_cache_config")
        self.config = c
        self.mesh = mesh
        self.cache = cache
        self.num_stages = 1
        self.interpret = interpret
        self.params = params
        self.buckets = prefill_buckets(self.model.prefill_chunk(c), cache.max_seq_len)
        if any(b % cache.config.page_size for b in self.buckets):
            raise ValueError(f"page_size {cache.config.page_size} does not divide the prefill buckets {self.buckets}")
        # a model that generates by blocks brings its schedule; a step of any other moves one position a slot
        self.block: Optional[BlockSchedule] = self.model.block_schedule(c) if hasattr(self.model, "block_schedule") else None
        if self.block is not None and cache.config.page_size % self.block.B:
            raise ValueError(f"a block of {self.block.B} positions must divide the page of {cache.config.page_size}: "
                             "a block that straddled a page would be written to two")
        # may a decode call's expert layer take its padded form?  Its rows are static, so this is latched as the programs are
        from ..moe import dropless      # (jax comes with it: imported late, as everywhere in serve/)

        decode_rows = cache.num_slots           # ... a block engine's: every slot's open rows and the commit places'
        if self.block is not None:
            decode_rows = (cache.num_slots + self.block.commit_places(cache.num_slots)) * self.block.B
        # The expert layers of a program (the params' ``w_gate`` leaves, (held, d, f) each) and the outputs their routers
        # score (the ``router`` leaves, (d, E): what ``dropless.routed_experts`` reads the same fact from).
        # (a dense model's config names no experts, its steps return no ``counts["experts"]``, and ``moe_*`` stay 0)
        import jax

        leaves = lambda key: [leaf for path, leaf in jax.tree_util.tree_leaves_with_path(params) if getattr(path[-1], "key", None) == key]
        experts, routers = leaves("w_gate"), leaves("router")
        form = lambda rows: dropless.expert_form(rows, c.num_experts_per_tok, c.experts_held, routers[0].shape[-1])
        self._decode_padded_candidate = bool(experts) and form(decode_rows) == dropless.PADDED_OR_SORTED
        self._fits_pad = dropless.fits_pad
        # ... and, by a program's rows, how many of them are the grouped kernel outside any choice on the device (the
        # sorted form alone, on the kernel's leg): latched here, as the programs latch it
        grouped = bool(experts) and dropless.grouped_leg(c.dtype, *experts[0].shape[1:]) is not None
        self._expert_layers = len(experts)
        self._decode_rows = decode_rows
        # does a prompt ride a decode step here?  The OFFER the serve loop asks for (``getattr(engine, "rides", False)``),
        # made where the model's module gives the body of such a step and a step moves one position a slot
        self.rides = hasattr(self.model, "serve_ride") and self.block is None
        # (the rows of the program a prompt is launched in: its bucket's, beside every decode row where it rides)
        self._prompt_rows = {bucket: bucket + (decode_rows if self.rides else 0) for bucket in self.buckets}
        self._grouped_layers = {rows: len(experts) for rows in (*self._prompt_rows.values(), decode_rows)
                                if grouped and form(rows) == dropless.SORTED}
        # what this engine has done, in plain integers (``trace_counters``); ``prefill_rides`` only where prompts ride
        self.counter_names = (COUNTERS + (("prefill_rides",) if self.rides else ())
                              + (BLOCK_COUNTERS if self.block is not None else ()) + tuple(self.model.STEP_COUNTERS))
        for name in self.counter_names:
            setattr(self, name, 0)
        register_counter_source(self)
        self._build()

    # -------------------------------------------------------------- build
    def _build(self) -> None:
        import jax
        import jax.numpy as jnp

        c, cache, model = self.config, self.cache, self.model
        page, Pmax = cache.config.page_size, cache.config.pages_per_slot
        names = tuple(cache.arrays())       # the cache's device arrays, in the order the programs take them
        n = len(names)
        # the decode step's kernels, or their XLA legs: latched here, as ServeEngine does
        kernels = model.decode_kernels(c, cache)
        for name, interp in kernels.items():
            setattr(self, f"kernel_{name}", interp is not None)

        def prefill(params, *rest):
            tokens, length, page_row, slot = rest[n:]
            logits, arrays = model.serve_prefill(c, params, dict(zip(names, rest[:n])), tokens, length, page_row, slot,
                                                 page=page, interpret=self.interpret)
            # the row's greedy id, in this program as the decode step takes its own (``PrefillStep.token``)
            first = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (logits, first) + tuple(arrays[name] for name in names)

        block = self.block

        def landings(table, lengths):
            """``active`` (S,) and where each slot's new position lands (the first of its open block, where a step
            moves a block), as in ServeEngine.decode: a position past the reserved pages, or a slot that holds
            nothing yet (before its prefill, or free), writes the null page."""
            active = lengths > 0

            def landing(first):
                valid = (first < Pmax * page) & active
                safe = jnp.where(valid, first, 0)
                return jnp.where(valid, jnp.take_along_axis(table, (safe // page)[:, None], axis=1)[:, 0], 0), safe % page

            first = lengths if block is None else lengths // block.B * block.B
            where = dict(zip(("write_page", "write_offset"), landing(first)))
            if block is not None:       # (a call that commits a block and opens the next writes that one too)
                where["next_page"], where["next_offset"] = landing(first + block.B)
            return active, where

        def decode(params, *rest):
            table, lengths, tokens = rest[n:]
            active, where = landings(table, lengths)
            out = model.serve_decode(
                c, params, dict(zip(names, rest[:n])), table, lengths, tokens, active=active, kernels=kernels, **where)
            if block is None:
                logits, counts, arrays = out
                # every slot's greedy token, in this program (``DecodeStep.tokens``)
                next_ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            else:
                # the block as the pass leaves it, the model's own selection; and where the logits would be, the open
                # rows' hidden state: a pass keeps no logits (``RowsByDemand``)
                logits, next_ids, counts, arrays = out
            return (logits, next_ids, counts) + tuple(arrays[name] for name in names)

        # the step that CARRIES a prompt, where the model gives its body: the decode step with ``rung`` rows more, one
        # program a rung; with every decode row idle (lengths of 0) it is a prompt launched alone, so such a model has
        # no prefill program of its own.  (Its name on the device's ``XLA Modules`` line is its function's, ``jit_decode``)
        def decode_carrying(params, *rest):
            table, lengths, tokens, firsts, prompt, length, page_row, slot = rest[n:]
            active, where = landings(table, lengths)
            logits, row, counts, arrays = model.serve_ride(
                c, params, dict(zip(names, rest[:n])), table, lengths, tokens, prompt, length, page_row, slot, active=active,
                kernels=kernels, page=page, interpret=self.interpret, **where)
            next_ids, first = (jnp.argmax(a, axis=-1).astype(jnp.int32) for a in (logits, row))
            # (the first id to its slot's place among the firsts, where a prefill launched alone leaves its own)
            return (logits, next_ids, counts, row, first, firsts.at[slot].set(first)) + tuple(arrays[name] for name in names)

        decode_carrying.__name__ = "decode"

        def head_rows(params, hidden, which):       # the rows of logits a caller reads, when it reads them
            return model.head(c, params, hidden[which])

        donated = tuple(range(1, 1 + n))
        self._array_names = names
        self._prefill_fn = jax.jit(prefill, donate_argnums=donated)     # (never run where prompts ride)
        self._decode_fn = jax.jit(decode, donate_argnums=donated)
        self._ride_fn = jax.jit(decode_carrying, donate_argnums=donated) if self.rides else None
        self._head_fn = jax.jit(head_rows) if block is not None else None
        # (where prompts ride, the sharding the programs' own ids come out with, the cache's mesh's: a rung's program is
        # fed the host's tokens, a step's ids and the firsts in turn, and is ONE executable only if they are all alike)
        self._init_decode_ahead(jax.sharding.NamedSharding(self.mesh.jax_mesh, jax.sharding.PartitionSpec()) if self.rides
                                else jax.sharding.SingleDeviceSharding(self.mesh.jax_mesh.devices.flat[0]))

    def warm(self) -> "HybridServeEngine":
        """Compile and run every program: each prefill bucket (into the null
        page and slot 0's state, which its next prefill rewrites) and the
        decode step (no slot active, in each form of its tokens:
        ``_warm_decode``, which the last bucket's id feeds).  Where prompts
        ride a bucket's program is the step that carries it, run here with
        every decode row idle.  Twice over (``DecodeAhead._warm_ladder`` says
        why).  Nothing compiles after this."""
        self._warm_ladder(lambda toks, n, page_row: self._run_prefill(toks, n, page_row, 0))
        if self.rides:
            # ... and RUN: an executable read from the compile cache is loaded onto the chip when it first runs, seconds
            # for a ladder of programs each as large as the step, and that is set-up's, not the first request's (as
            # ``ServeEngine.warm`` waits; an engine whose prompts do not ride keeps the set-up it had)
            import jax

            jax.block_until_ready(self._held())
        return self

    # ---------------------------------------------------------------- API
    def _held(self):
        arrays = self.cache.arrays()
        return tuple(arrays[name] for name in self._array_names)

    def _run_prefill(self, tokens, n, page_row, slot):
        logits, first, *arrays = self._prefill_fn(self.params, *self._held(), tokens, np.int32(n), page_row, np.int32(slot))
        self.cache.update_arrays(dict(zip(self._array_names, arrays)))
        return logits, first

    def _run_ride(self, table, lengths, tokens, prompt, slot: int):
        """The step that carries ``prompt`` (tokens padded to the bucket, length,
        page row): the decode rows' logits and ids, the step's counts, the
        prompt's row and its greedy id (which the program has put in ``slot``'s
        place among the firsts), all still on the device."""
        logits, next_ids, counts, row, first, self._firsts, *arrays = self._ride_fn(
            self.params, *self._held(), table, lengths, tokens, self._first_ids(), *prompt, np.int32(slot))
        self.cache.update_arrays(dict(zip(self._array_names, arrays)))
        return logits, next_ids, counts, row, first

    def _run_decode(self, table, lengths, tokens):
        params = self.params
        logits, next_ids, counts, *arrays = self._decode_fn(params, *self._held(), table, lengths, tokens)
        self.cache.update_arrays(dict(zip(self._array_names, arrays)))
        if self.block is not None:
            logits = RowsByDemand(self, params, logits)
        return logits, next_ids, counts

    # ------------------------------------------------- a block engine's own
    def _fed(self, tokens):
        if self.block is None or not isinstance(tokens, DecodeFeed):
            return super()._fed(tokens)     # (a block engine's host tokens: each revealed at its slot's length)
        fed = np.full((self.cache.num_slots,), BlockSchedule.HOLD, np.int32)
        fed[list(tokens.slots)] = BlockSchedule.OWN_PASS
        fed[list(tokens.fused)] = BlockSchedule.FUSED
        return self._host_tokens(fed)

    def _note(self, tokens, lengths: np.ndarray):
        if self.block is None:
            return super()._note(tokens, lengths)
        fused, yields, deferred = np.zeros((self.cache.num_slots,), bool), 0, 0
        if isinstance(tokens, DecodeFeed):
            fused[list(tokens.fused)] = True
            yields, deferred = sum(tokens.slots.values()), tokens.deferred
        return lengths % self.block.B, (yields, fused, deferred)

    def _warm_decode(self, first) -> None:
        if self.block is None:
            return super()._warm_decode(first)
        cache = self.cache
        self._note_first(first, 0)      # (a prefill's program, whatever the engine)
        table = np.zeros((cache.num_slots, cache.config.pages_per_slot), np.int32)
        zeros = np.zeros((cache.num_slots,), np.int32)
        for tokens in (zeros, DecodeFeed(None, slots={})):      # both uses are one executable: warmed twice over
            self._run_decode(table, zeros, self._fed(tokens))

    def decode(self, tokens):
        step = super().decode(tokens)
        if getattr(tokens, "rider", None) is None:      # (a step that carries a prompt is that prompt's program, counted at its ``prefill``)
            self._count_expert_layers(self._decode_rows)
        return step

    def _count_expert_layers(self, rows: int) -> None:
        """A launched program of ``rows`` rows: its expert layers, and those that are the grouped kernel."""
        self._add({"moe_expert_layer_calls": self._expert_layers, "moe_grouped_layer_calls": self._grouped_layers.get(rows, 0)})

    def prefill(self, prompt: Sequence[int], slot: int) -> PrefillStep:
        """LAUNCH the prompt through the stack in its bucket: what its
        positions leave in the cache goes into ``slot``'s reserved pages (and
        its state into ``slot``'s rows), and the ``PrefillStep`` returned at
        once, unread, holds the next-token logits row (of a block engine the
        last prompt position's own row) and its greedy id on the device:
        ``.token`` waits for the id, ``np.asarray(step)`` is the fp32 row.  The
        serve loop reads ``.token`` after it has enqueued the decode step that
        takes the id from the device, and of a block engine reads neither.
        Where prompts RIDE (``self.rides``) this call launches nothing: the step
        returned WAITS (``launched`` False) for the ``decode`` whose
        :class:`DecodeFeed` names it as ``rider``, or for whatever needs it
        first (a read of it, a ``decode`` fed from it or from the host's
        tokens), which launches it alone: ``ServeEngine.prefill`` says the
        same of its own.  The prompt is counted here either way."""
        cache = self.cache
        n = len(prompt)
        if not (0 < n <= cache.max_seq_len):
            raise ValueError(f"prompt length {n} not in (0, {cache.max_seq_len}]")
        bucket = next(b for b in self.buckets if b >= n)
        def padded():
            toks = np.zeros((bucket,), np.int32)
            toks[:n] = np.asarray(prompt, np.int32)
            return toks, n, cache.page_table[slot, : bucket // cache.config.page_size].copy()

        with ndtimeit(_p.SERVE_PREFILL_CALL):
            if self.rides:
                out = self._prompt_waits(*padded(), slot)
            else:
                with ndtimeit(_p.SERVE_PREFILL_LAUNCH, launch=self.launches, rung=bucket, slot=slot):   # the enqueue alone
                    out = self._launched_prefill(*self._run_prefill(*padded(), slot), slot)
        self.prefill_tokens_real += n
        self.prefill_tokens_padded += bucket
        self.prefill_bucket_tokens += bucket
        self._count_expert_layers(self._prompt_rows[bucket])
        self._add(self.model.prefill_counters(self.config, bucket))
        return out

    def _add(self, counts: Dict[str, int]) -> None:
        for name, value in counts.items():
            setattr(self, name, getattr(self, name) + value)

    def _count_step(self, lengths: np.ndarray, counts, note=None) -> None:
        c = self.config
        experts = counts.get("experts")             # (expert layers, held): tokens an expert got; a dense model: none
        positions = int((lengths > 0).sum())        # that went through the stack for a request: one an active slot
        if self.block is None:
            super()._count_step(lengths, counts)
        else:
            B = self.block.B
            yields, fused, deferred = note
            units, commits, masked, rode = (int(x) for x in counts["block"])
            positions = units * B                   # ... or B a unit (a pass, a commit) of the slots the call moved
            self._add({"block_passes": units, "block_commit_passes": commits, "block_tokens_emitted": yields,
                       "block_positions_masked": masked, "block_commits_fused": rode, "block_commits_deferred": deferred})
            # the kernel's rows: every slot's, up to the end of its open block (of the block after it where the
            # slot fused), and the commit places', up to the end of the block they commit (an unused one: nothing)
            end = lengths // B * B + B
            self._count_pages(end + B * fused, end[fused])
        if experts is not None:
            self.moe_assignments += positions * c.num_experts_per_tok * experts.shape[0]
            self.moe_assignments_held += int(experts.sum())
            self.moe_busiest_expert_tokens += int(experts.max(axis=1).sum())
            self.moe_expert_slots += int(experts.size)
            self.moe_layer_steps += int(experts.shape[0])
            self.moe_experts_touched += int((experts > 0).sum())
            if self._decode_padded_candidate:           # the device's own predicate, on the integers it read
                self.moe_padded_layer_steps += int(self._fits_pad(experts).sum())
        self._add(self.model.step_counters(c, self.cache, lengths, counts))

    def trace_counters(self) -> Dict[str, int]:
        """The engine's own counts since it was built.  Those ``ServeEngine``
        has mean the same here (``decode_launches`` / ``prefill_launches`` are
        of calls that enqueued, ``prefill_reads_ahead`` of prefills still unread
        when the decode step behind them was enqueued, which of a block engine's
        loop is every one: it never reads a prefill; ``prefill_rides``, which only
        an engine that rides reports, of the prompts among them that a decode
        step carried, the rest having gone alone; ``decode_steps`` /
        ``decode_steps_ahead`` are
        of steps read; ``logits_bytes_to_host`` is what callers copied
        out of ``decode``'s results, ``logits_rows_made`` the rows of logits a
        block engine's steps computed because a caller read them (another
        engine's decode program makes every slot's row, and this stays 0);
        ``prefill_tokens_padded`` is the bucket;
        ``decode_pages_*`` are a layer's, and count only with the kernel leg).
        Of ``decode`` calls alone: ``moe_assignments`` = positions that went
        through the stack for a request (one an active slot; ``B`` a unit, a pass
        or a commit, of a block engine's call) x experts per token x expert layers, ``moe_assignments_held`` those that fell on
        an expert held here; ``moe_busiest_expert_tokens`` the largest count of
        one expert, summed over layers and steps (``moe_layer_steps`` of them),
        and ``moe_expert_slots`` = held experts x layers x steps (busiest /
        layer steps over held / slots = max over mean), ``moe_experts_touched``
        those of them that got a token (their weights are read);
        ``moe_padded_layer_steps`` the layer steps whose expert layer took its
        padded form (``moe.dropless``: the call's shape made it a candidate,
        ``padded_candidate``, and its busiest expert fit the pad, ``fits_pad``:
        the layer's own two functions on the counts the step returned), so over
        ``moe_layer_steps`` the share of expert layers that did.  Of LAUNCHED
        programs, prefills and decode calls both: ``moe_expert_layer_calls``
        the expert layers they hold, ``moe_grouped_layer_calls`` those of them
        that are the grouped kernel outside any choice on the device (by the
        program's rows ``moe.dropless.expert_form`` says the sorted form
        alone, and its leg is the kernel's: ``grouped_leg``; what a
        candidate's ``cond`` took at a prefill is in no counter).  Where
        prompts ride a prompt's program is the step that carries it, the
        bucket's rows beside every slot's, rode or alone, counted at the
        ``prefill`` call and not again at the ``decode`` that carries it.
        ``prefill_bucket_tokens`` the bucket lengths.  A block engine's six
        (``BLOCK_COUNTERS``, above), then the model's own (its module's
        ``STEP_COUNTERS`` says what each counts)."""
        return {k: getattr(self, k) for k in self.counter_names}

    def _refuse(self, what: str, program: str):
        if self.block is not None:
            raise NotImplementedError(
                f"{what}: an engine that generates by blocks (blocks of {self.block.B}, {type(self.config).__name__}) "
                f"has no {program} program, and its open block is slot state that a rewind or a shared prefix "
                "would need at an earlier position")
        self.cache.refuse_slot_state(what)
        raise NotImplementedError(f"{what}: this engine has no {program} program yet (the cache, pages alone, would "
                                  "allow it)")

    def decode_multi(self, tokens: np.ndarray) -> np.ndarray:
        self._refuse("decode_multi (the speculative verify step)", "multi-position decode")

    def prefill_suffix(self, prompt: Sequence[int], slot: int, matched: int) -> np.ndarray:
        self._refuse("prefill_suffix (a prefix-cache hit)", "prefill-from-a-boundary")
