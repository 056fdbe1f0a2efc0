"""Serve engine for hybrid decoders: state-space mixers with a per-slot
recurrent state beside the paged K/V of their few attention layers, and a
routed expert layer in every layer (``models/granite_hybrid.py``).

A second engine class beside :class:`ServeEngine`, behind the same surface
(``prefill(prompt, slot)``, ``decode(tokens)``, ``params``,
``trace_counters()``, ``greedy``), not ``ServeEngine`` taught a second block:
that class IS the Llama block (its stage split, its ``decode_multi`` and
``prefill_suffix`` all walk ``layers_i.self_attn``), while everything above it
(``ContinuousBatchingScheduler``, ``run_serve_resilient``, sampling, the
``vs.serve-*`` spans, the counters a trace session reads) takes either.  The
block's mathematics is not here: both programs below call the model file's
pure functions.

Two kinds of compiled program, all static-shaped and all compiled by
``warm()`` before the engine is handed over:

  **prefill**, one program a BUCKET: the prompt is padded to the next of
  ``prefill_buckets(chunk, max_seq_len)`` (the rule's home is ``serve/engine.py``,
  whose ``ServeEngine`` pads by it too): ``chunk, 2 chunk, 4 chunk, ...,
  max_seq_len`` (the chunked scan wants whole chunks; padding to
  ``max_seq_len`` would cost a short prompt six times its work).  In the pad
  the state-space step size is forced to 0, so the state
  stands where the prompt ends; the convolution tail is the prompt's last real
  inputs; the logits row is the last real position's; K and V of the bucket's
  positions go to the slot's pages (what lies past its reserved pages to the
  null page).  The slot's row of every state array is wholly rewritten.

  **decode**, one token a slot: the recurrence's one step over every slot's
  state (read and written whole: the step's largest traffic beside the expert
  weights; on TPU the ``ssm_step`` kernel, which passes over it once), paged
  attention over a one-layer-a-period pool (the
  ``paged_decode`` kernel on TPU, the XLA leg elsewhere, as ``ServeEngine``
  decides), the expert layer over the active slots.  Pools and states are
  donated: a second copy would not fit.

What this engine refuses, by what the cache is: ``decode_multi`` and
``prefill_suffix`` (speculation, prefix sharing) need a state at an earlier
position, which ``PagedKVCache`` with slot state does not keep; ``num_stages``
> 1 and a mesh of more than one device have no program here yet.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

import numpy as np

from ..ndtimeline import predefined as _p
from ..ndtimeline.api import ndtimeit, register_counter_source
from .engine import DecodeStep, prefill_buckets
from .kv_cache import KVCacheConfig, PagedKVCache, SlotStateUnsupported

__all__ = ["HybridServeEngine", "hybrid_cache_config", "prefill_buckets"]


def hybrid_cache_config(config, *, num_slots: int, page_size: int, pages_per_slot: int,
                        num_pages: Optional[int] = None) -> KVCacheConfig:
    """The cache geometry of a hybrid model: pages for its attention layers
    only, and a recurrent state and a convolution tail a slot for each
    state-space layer."""
    m = len(config.mamba_layers)
    return KVCacheConfig(
        layers=len(config.attention_layers), kv_heads=config.num_key_value_heads, head_dim=config.head_dim,
        num_slots=num_slots, page_size=page_size, pages_per_slot=pages_per_slot, num_pages=num_pages,
        dtype=config.dtype,
        slot_state=(("ssm", m, config.ssm_state_shape, config.state_dtype),
                    ("conv", m, config.conv_tail_shape, config.dtype)))


class HybridServeEngine:
    """Compiled prefill (a program a bucket) and decode over ``cache``, a
    ``PagedKVCache`` built from :func:`hybrid_cache_config`.  ``config`` is a
    ``GraniteHybridConfig``, ``params`` the tree of ``init_params``."""

    def __init__(self, config, mesh, params: Dict[str, Any], cache: PagedKVCache, *, num_stages: int = 1,
                 interpret: Optional[bool] = None):
        c = config
        if num_stages != 1:
            raise NotImplementedError("HybridServeEngine has no stage split: num_stages must be 1")
        if mesh.size() != 1:
            raise NotImplementedError("HybridServeEngine runs on one device: the slot state has no sharded layout yet")
        want = hybrid_cache_config(c, num_slots=cache.num_slots, page_size=cache.config.page_size,
                                   pages_per_slot=cache.config.pages_per_slot, num_pages=cache.config.num_pages)
        if cache.config != want:
            raise ValueError(f"cache geometry {cache.config} is not this model's ({want}): build it from "
                             "hybrid_cache_config")
        if not c.attention_layers or not c.mamba_layers:
            raise ValueError("a hybrid has layers of both kinds")
        self.config = c
        self.mesh = mesh
        self.cache = cache
        self.num_stages = 1
        self.interpret = interpret
        self.params = params
        self.buckets = prefill_buckets(c.mamba_chunk_size, cache.max_seq_len)
        if any(b % cache.config.page_size for b in self.buckets):
            raise ValueError(f"page_size {cache.config.page_size} does not divide the prefill buckets {self.buckets}")
        # what this engine has done, in plain integers (``trace_counters``)
        self.decode_steps = 0
        self.logits_bytes_to_host = 0
        self.prefill_tokens_real = 0
        self.prefill_tokens_padded = 0
        self.prefill_bucket_tokens = 0
        self.decode_pages_read = 0
        self.decode_pages_capacity = 0
        self.moe_assignments = 0
        self.moe_assignments_held = 0
        self.moe_busiest_expert_tokens = 0
        self.moe_expert_slots = 0
        self.moe_layer_steps = 0
        self.moe_experts_touched = 0
        self.ssm_state_bytes_rw = 0
        register_counter_source(self)
        self._build()

    # -------------------------------------------------------------- build
    def _build(self) -> None:
        import jax
        import jax.numpy as jnp

        from .. import kernels as _kernels
        from ..kernels import paged_attention as _paged
        from ..kernels import ssm_step as _ssm
        from ..models import granite_hybrid as gh

        c, cache = self.config, self.cache
        page, Pmax = cache.config.page_size, cache.config.pages_per_slot
        interpret = self.interpret
        # where each layer's share of the cache lies: its row of the state arrays, or its layer of the pools
        row = {l: i for i, l in enumerate(c.mamba_layers)}
        row.update({l: i for i, l in enumerate(c.attention_layers)})

        def prefill(params, kd, vd, ssm, conv, tokens, length, page_row, slot):
            x = gh.embed(c, params, tokens)
            states, tails, ks, vs = [], [], [], []
            for l, kind in enumerate(c.layer_types):
                x, kept = gh.layer_prefill(c, params[f"layers_{l}"], kind, x, length, interpret=interpret)
                if kind == "mamba":
                    states.append(kept[0])
                    tails.append(kept[1])
                else:
                    ks.append(kept[0])
                    vs.append(kept[1])
            last = jax.lax.dynamic_index_in_dim(x, length - 1, axis=0, keepdims=True)
            logits = gh.head(c, params, last)[0]
            pages = lambda stack: jnp.stack(stack).reshape(len(stack), -1, page, *stack[0].shape[1:])
            kd = kd.at[:, page_row].set(pages(ks).astype(kd.dtype))
            vd = vd.at[:, page_row].set(pages(vs).astype(vd.dtype))
            ssm = jax.lax.dynamic_update_slice_in_dim(ssm, jnp.stack(states)[:, None].astype(ssm.dtype), slot, axis=1)
            conv = jax.lax.dynamic_update_slice_in_dim(conv, jnp.stack(tails)[:, None].astype(conv.dtype), slot, axis=1)
            return logits, kd, vd, ssm, conv

        self._prefill_fn = jax.jit(prefill, donate_argnums=(1, 2, 3, 4))

        # ---- decode attention: the kernel on TPU, the XLA leg elsewhere (latched at build, as ServeEngine does)
        kernel_interpret = _kernels.resolve(
            "paged_decode",
            supported=lambda interp: _paged.supports(cache.k.data.dtype, c.num_key_value_heads, c.head_dim,
                                                     interpret=interp))
        self.kernel_decode = kernel_interpret is not None

        def attend(q, kd, vd, table, valid_len, *, layer, scale):
            if self.kernel_decode:
                return _paged.paged_decode(q, kd, vd, table, valid_len, layer=layer, scale=scale,
                                           interpret=kernel_interpret)
            return gh.paged_attention_xla(q, kd, vd, table, valid_len, layer=layer, scale=scale)

        # ---- the state's step: one pass over the state on TPU, two in XLA (latched at build too)
        ssm_interpret = _kernels.resolve(
            "ssm_step",
            supported=lambda interp: _ssm.supports(c.state_dtype, *c.ssm_state_shape, interpret=interp))
        self.kernel_ssm_step = ssm_interpret is not None

        def advance(ssm, decay, dtx, B, C, *, layer):
            if self.kernel_ssm_step:
                return _ssm.ssm_step(ssm, decay, dtx, B, C, layer=layer, interpret=ssm_interpret)
            return gh.ssm_advance_xla(ssm, decay, dtx, B, C, layer=layer)

        def decode(params, kd, vd, ssm, conv, table, lengths, tokens):
            x = gh.embed(c, params, tokens)                 # (S, E)
            active = lengths > 0                            # a slot before its prefill, or free
            # as in ServeEngine.decode: a position past the reserved pages, or a slot that holds
            # nothing yet, writes the null page
            valid = (lengths < Pmax * page) & active
            safe = jnp.where(valid, lengths, 0)
            pg = jnp.where(valid, jnp.take_along_axis(table, (safe // page)[:, None], axis=1)[:, 0], 0)
            off = safe % page
            counts = []
            for l, kind in enumerate(c.layer_types):
                lp, i = params[f"layers_{l}"], row[l]
                if kind == "mamba":
                    step = lambda u, lp=lp, i=i: gh.mamba2_step(c, lp["mixer"], u, ssm, conv[i], layer=i,
                                                                advance=advance)
                else:
                    step = lambda u, lp=lp, i=i: gh.attention_step(
                        c, lp["mixer"], u, kd, vd, layer=i, table=table, page=pg, offset=off,
                        valid_len=lengths + 1, attend=attend)
                x, kept, n = gh.layer_step(c, lp, kind, x, active, step)
                if kind == "mamba":
                    ssm, conv = kept[0], conv.at[i].set(kept[1])
                else:
                    kd, vd = kept
                counts.append(n)
            logits = gh.head(c, params, x)
            # every slot's greedy token, in this program (``DecodeStep.tokens``)
            next_ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return logits, next_ids, jnp.stack(counts), kd, vd, ssm, conv

        self._decode_fn = jax.jit(decode, donate_argnums=(1, 2, 3, 4))

    def warm(self) -> "HybridServeEngine":
        """Compile and run every program: each prefill bucket (into the null
        page and slot 0's state, which its next prefill rewrites) and the
        decode step (no slot active).  Twice over: the first call of all sees
        the cache's arrays as they were allocated, every later one sees them as
        a program returned them, and a program that compiles again for those
        does it here.  Nothing compiles after this."""
        cache = self.cache
        for _ in range(2):
            for bucket in self.buckets:
                self._run_prefill(np.zeros((bucket,), np.int32), 1,
                                  np.zeros((bucket // cache.config.page_size,), np.int32), 0)
            self._run_decode(np.zeros((cache.num_slots, cache.config.pages_per_slot), np.int32),
                             np.zeros((cache.num_slots,), np.int32), np.zeros((cache.num_slots,), np.int32))
        return self

    # ---------------------------------------------------------------- API
    def _run_prefill(self, tokens, n, page_row, slot):
        cache = self.cache
        logits, kd, vd, ssm, conv = self._prefill_fn(
            self.params, cache.k.data, cache.v.data, cache.state["ssm"], cache.state["conv"],
            tokens, np.int32(n), page_row, np.int32(slot))
        cache.update(kd, vd)
        cache.update_state(ssm=ssm, conv=conv)
        return logits

    def _run_decode(self, table, lengths, tokens):
        cache = self.cache
        logits, next_ids, counts, kd, vd, ssm, conv = self._decode_fn(
            self.params, cache.k.data, cache.v.data, cache.state["ssm"], cache.state["conv"], table, lengths, tokens)
        cache.update(kd, vd)
        cache.update_state(ssm=ssm, conv=conv)
        return logits, next_ids, counts

    def prefill(self, prompt: Sequence[int], slot: int) -> np.ndarray:
        """Run the prompt through the stack in its bucket, write its K/V into
        ``slot``'s reserved pages and its state into ``slot``'s rows, and
        return the next-token logits (fp32, host)."""
        cache = self.cache
        n = len(prompt)
        if not (0 < n <= cache.max_seq_len):
            raise ValueError(f"prompt length {n} not in (0, {cache.max_seq_len}]")
        bucket = next(b for b in self.buckets if b >= n)
        with ndtimeit(_p.SERVE_PREFILL_CALL):
            toks = np.zeros((bucket,), np.int32)
            toks[:n] = np.asarray(prompt, np.int32)
            page_row = np.ascontiguousarray(cache.page_table[slot, : bucket // cache.config.page_size])
            logits = self._run_prefill(toks, n, page_row, slot)
            with ndtimeit(_p.SERVE_PREFILL_FETCH):
                out = np.asarray(logits)
        self.prefill_tokens_real += n
        self.prefill_tokens_padded += bucket
        self.prefill_bucket_tokens += bucket
        return out

    def decode(self, tokens: np.ndarray) -> DecodeStep:
        """One decode step for every slot: each active slot's state advances
        by its token, its K/V lands at its current length, and the
        :class:`DecodeStep` is that of the NEXT position (every slot's greedy
        token on the host; the (num_slots, vocab) fp32 logits on the device
        until a caller reads them).  Callers advance lengths via
        ``cache.advance``."""
        import jax

        cache, c = self.cache, self.config
        lengths = cache.lengths_array()
        with ndtimeit(_p.SERVE_DECODE_CALL):
            logits, next_ids, counts = self._run_decode(cache.table_array(), lengths,
                                                        np.asarray(tokens, np.int32).reshape(cache.num_slots))
            with ndtimeit(_p.SERVE_DECODE_FETCH):   # waits for the device, the ids and the experts' counts
                # one get: both copies are started, then waited for; counts (layers, held): tokens an expert got
                next_ids, counts = jax.device_get((next_ids, counts))
            out = DecodeStep(next_ids, logits, self)
        self.decode_steps += 1
        self.moe_assignments += int((lengths > 0).sum()) * c.num_experts_per_tok * c.num_hidden_layers
        self.moe_assignments_held += int(counts.sum())
        self.moe_busiest_expert_tokens += int(counts.max(axis=1).sum())
        self.moe_expert_slots += int(counts.size)
        self.moe_layer_steps += int(counts.shape[0])
        self.moe_experts_touched += int((counts > 0).sum())
        self.ssm_state_bytes_rw += 2 * cache.state_bytes_per_slot() * cache.num_slots
        if self.kernel_decode:
            page, per_slot = cache.config.page_size, cache.config.pages_per_slot
            self.decode_pages_read += int(np.minimum(-(-(lengths + 1) // page), per_slot).sum())
            self.decode_pages_capacity += cache.num_slots * per_slot
        return out

    def trace_counters(self) -> Dict[str, int]:
        """The engine's own counts since it was built.  Those ``ServeEngine``
        has mean the same here (``logits_bytes_to_host`` is what callers copied
        out of ``decode``'s results; ``prefill_tokens_padded`` is the bucket;
        ``decode_pages_*`` are a layer's, and count only with the kernel leg).
        Of ``decode`` calls alone: ``moe_assignments`` = active slots x experts
        per token x layers, ``moe_assignments_held`` those that fell on an
        expert held here; ``moe_busiest_expert_tokens`` the largest count of
        one expert, summed over layers and steps (``moe_layer_steps`` of them),
        and ``moe_expert_slots`` = held experts x layers x steps (busiest /
        layer steps over held / slots = max over mean), ``moe_experts_touched`` those of them that got a token (their
        weights are read); ``ssm_state_bytes_rw`` the slot state read and written (every
        slot's, every step).  ``prefill_bucket_tokens`` the bucket lengths."""
        return {k: getattr(self, k) for k in (
            "decode_steps", "logits_bytes_to_host", "prefill_tokens_real", "prefill_tokens_padded",
            "prefill_bucket_tokens", "decode_pages_read", "decode_pages_capacity", "moe_assignments",
            "moe_assignments_held", "moe_busiest_expert_tokens", "moe_expert_slots", "moe_layer_steps",
            "moe_experts_touched",
            "ssm_state_bytes_rw")}

    def decode_multi(self, tokens: np.ndarray) -> np.ndarray:
        raise SlotStateUnsupported(
            "decode_multi (the speculative verify step) would leave the state past positions that may be rejected: "
            "the missing mechanism is a snapshot of the slot state to rewind to")

    def prefill_suffix(self, prompt: Sequence[int], slot: int, matched: int) -> np.ndarray:
        raise SlotStateUnsupported(
            "prefill_suffix (a prefix-cache hit) needs the state as it was at the shared boundary: the missing "
            "mechanism is a snapshot of the slot state at page boundaries")

    @staticmethod
    def greedy(logits_row: np.ndarray) -> int:
        """Deterministic greedy sample (ties break to the lowest id)."""
        return int(np.argmax(logits_row))
