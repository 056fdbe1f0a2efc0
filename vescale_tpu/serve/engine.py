"""Serve engine — compiled prefill/decode steps over the paged KV cache.

A functional llama-family forward over the SAME param tree the training
stack produces (flax ``Llama`` layout: ``embed_tokens`` / ``layers_i`` /
``norm`` / ``lm_head``), so a training checkpoint restores straight into
the engine through ``checkpoint.load``'s elastic preflight — no weight
conversion, no serving-specific checkpoint format.

Two compiled paths, both STATIC-shaped so XLA never retraces as requests
come and go (``warm()`` compiles every shape of both; the first ``prefill``
runs it if nobody has):

  **prefill** — the prompt padded to the next rung of a short ladder
  (``prefill_buckets`` over the cache's geometry: 128, 256, 512, 1024, 1536,
  ..., ``max_seq_len``; a prompt of 100 tokens runs 128 positions, not the
  cache's 1536) runs the full stack once, reusing the flash-attention kernel
  path (``ops.flash_attention``: Pallas on TPU, the same dense fallback the
  training forward takes off-TPU) and the training ``rotary`` phase math;
  per-layer K/V of the rung's positions land in the slot's first pages via
  one scatter (the mask is causal, so the pad never reaches a real
  position).  ``prefill`` only LAUNCHES those programs: the logits row and
  its greedy id, taken in the program, stay on the device in the
  ``PrefillStep`` it returns until a caller reads them.  Where the stack is
  ONE stage it does not even launch: the prompt waits to RIDE the decode
  step the serve loop is about to launch (below), and goes alone, through
  that same program with every decode row idle, when a reader or another
  call of the engine needs it first.  The layer stack is
  partitioned with the pipe engine's stage-split
  (``pipe.pipe_stage._cuts_by_weight``) into ``num_stages`` separately
  compiled segments — the cut points a prefill/decode-disaggregated
  deployment would place its pipeline boundaries on.

  **decode** — one token per active slot: project q/k/v for the new
  position, scatter k/v into the page the slot's table maps that position
  to, then paged attention.  On TPU that is ONE Pallas kernel per layer
  (``kernels.paged_attention``) over the whole 5-D pool, left in HBM: it
  fetches only the pages each slot holds (through the scalar-prefetched
  page table) and runs an online fp32 softmax over them, so a step costs
  what the cache holds, not what it could hold; a kv-head-sharded cache
  runs the kernel per-shard inside the shard_map shim (zero communication,
  same collective count as the XLA path).  On other backends, or with
  ``VESCALE_KERNELS=off``, it is the XLA chain (slice the layer, gather
  every slot's pages, mask by length, fp32 softmax, matmul), which moves
  the whole pool every step; ``VESCALE_KERNELS=interpret`` runs the kernel
  through the Pallas interpreter anywhere.  The leg is latched when the
  engine is BUILT (compiled programs are static); rebuild to switch.
  ``decode_multi`` keeps the XLA chain.  Inactive
  slots compute too (static shapes) but write only the reserved null page
  and their logits are ignored.  The same program takes every slot's greedy
  token, and ``decode`` returns those ids (``DecodeStep``): the logits stay
  on the device unless a caller reads them.  ``decode`` only LAUNCHES its
  step (``DecodeAhead``, shared with ``HybridServeEngine``): the ids are read
  when somebody reads them, and a ``DecodeFeed`` feeds the next step from
  them as they lie on the device (a slot prefilled since from its unread
  ``PrefillStep``'s id), so the serve loop keeps one step in flight and a
  prefill does not break it.

  **a step that carries a prompt** (a single-stage engine, ``rides``) — the
  decode step AND a prompt padded to its rung, one program a rung: the S
  decode rows and the prompt's rows are one array before every weight's
  product, so a weight crosses the HBM once for both (a step of S rows is
  memory-bound: the prompt's rows ride under the bytes it moves anyway);
  attention apart (the decode rows over their pages, the prompt causally
  over itself), the prompt's K/V into its slot's pages a layer at a time,
  the head over the decode rows and the prompt's last.  The serve loop asks
  for it with ``DecodeFeed(..., rider=step)``; the rider's slot is idle in
  that step, which makes its FIRST token and leaves it where a prefill
  launched alone leaves its own.

Decode is a deterministic function of (params, prompt, cache geometry):
an evicted-and-replayed request regenerates bit-identical tokens in any
slot/page assignment, which is what lets the serve loop promise "completed
or explicitly rejected — never corrupted" under mid-batch faults.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..ndtimeline import predefined as _p
from ..ndtimeline.api import ndtimeit, register_counter_source
from .kv_cache import PagedKVCache

__all__ = ["BlockSchedule", "DecodeAhead", "DecodeFeed", "DecodeStep", "PrefillStep", "ServeEngine", "prefill_buckets",
           "stack_params_check"]

# A prefill under some hundred positions streams the weights and gets little
# faster (the DeepSeek serve cut on a v5e: 6.96 ms of the device at 128
# positions, 8.24 at 256, 12.9 at 512; PERF.md §6, PR 32), so the dense ladder
# starts here.  From _HALF_STEPS_FROM on a compute-bound prefill costs what it
# is padded to, and a ladder that only doubled would pad a prompt of 1025
# tokens to 2048: there the step between (1536, 3072) is a rung too.
_SMALLEST_RUNG = 128
_HALF_STEPS_FROM = 1024
# From here on attention is a third and more of a prefill and grows with the square of the rung: a prompt of
# 4,600 tokens padded to 6144 costs 1.2 x the matrix products and 1.44 x the attention of one padded to 5120
# (PERF.md §6, PR 34), so the quarter steps are rungs too (5120, 7168 under a cache of 8192 positions).
_QUARTER_STEPS_FROM = 4096


def prefill_buckets(chunk: int, max_seq_len: int, smallest: int = 0) -> List[int]:
    """The lengths a prefill is padded to, for both engines: multiples of
    ``chunk`` that double (from 1024 on with the half step between, so no rung
    is over 1.5 x the one below; from 4096 on with the quarter steps, 1.25 x),
    then ``max_seq_len``.  Rungs under ``smallest`` are left out; a cache
    shorter than that has the one rung."""
    if max_seq_len % chunk:
        raise ValueError(f"max_seq_len {max_seq_len} is not a whole number of scan chunks of {chunk}")
    buckets, b = [], chunk
    while b < max_seq_len:
        buckets.append(b)
        steps = (b // 4, b // 2, 3 * b // 4) if b >= _QUARTER_STEPS_FROM else (b // 2,) if b >= _HALF_STEPS_FROM else ()
        buckets.extend(b + step for step in steps if (b + step) % chunk == 0 and b + step < max_seq_len)
        b *= 2
    return [b for b in buckets if b >= smallest] + [max_seq_len]


class DecodeStep:
    """The step one ``decode`` call LAUNCHED: the call enqueues the program and
    returns this at once, and the host waits for the device only when something
    here is read.  ``tokens`` is every slot's greedy token, int32
    ``(num_slots,)`` on the host: the argmax of the slot's logits row, taken
    inside the decode program (ties break to the lowest id, a NaN counts as the
    largest, as ``np.argmax`` has it).  The first read of them (here, or by the
    ``decode`` call that this step feeds through a :class:`DecodeFeed`) waits
    for the program, copies the ids under the ``vs.serve-decode.fetch`` span and
    adds the step to its engine's counters; until then ``read`` is False and the
    ids lie on the device, where the next step can take them as they are.  The
    fp32 ``(num_slots, vocab)`` logits stay where the program wrote them and
    cross to the host only when a caller reads them: ``step[slot]`` copies one
    row (``step[[a, b]]`` those rows, indexed as an ndarray is),
    ``np.asarray(step)`` all of them, ``step.shape``, ``step.dtype`` and
    ``len(step)`` none, and each copy adds its bytes to
    ``owner.logits_bytes_to_host`` (and reads the ids, if nobody has).  A stub
    engine gives the ids as an ndarray: such a step is read from the start.

    **A step that moves a block** (an engine whose ``block`` is a
    :class:`BlockSchedule`: generation by diffusion over blocks of ``B``
    positions).  ``tokens`` is ``(num_slots, B)``: each slot's block as the pass
    left it, of a call that committed a block (alone, or with the first pass
    of the block after it) that block's final tokens, and how
    many of them a slot YIELDS (none, or up to ``B``) is the schedule's to say,
    not the step's.  The logits are ``(num_slots, B, vocab)``, one row a position
    of the block, not shifted; ``step.block(slot)`` copies a slot's ``B`` rows.
    ``step[slot]`` stays ONE row, so that a caller of the host-token form reads
    what it reads of any engine: the row of the position its token was revealed
    at (``rows``, which the launch noted from the lengths).  Such a step's logits
    are not an array the program wrote but one that is made BY DEMAND
    (``hybrid_engine.RowsByDemand``, which answers ``shape`` / ``dtype`` and an
    index as an array does): the rows a caller reads are computed then, from the
    hidden state the pass returned, and no others ever are."""

    __slots__ = ("_tokens", "_ids", "_logits", "_owner", "_launch", "_rows")

    def __init__(self, tokens, logits, owner=None, launch=None, rows=None):
        host = isinstance(tokens, np.ndarray)
        self._tokens = tokens if host else None
        self._ids = None if host else tokens    # the device's copy: what the next step is fed from
        self._logits = logits
        self._owner = owner
        self._launch = launch                   # the engine's own note of the launch, until the step is read
        self._rows = rows                       # (num_slots,) the row of a block that ``step[slot]`` gives, or None

    @property
    def read(self) -> bool:
        return self._tokens is not None

    @property
    def tokens(self) -> np.ndarray:
        if self._tokens is None:
            self._owner._read_step(self)
        return self._tokens

    @property
    def shape(self) -> Tuple[int, ...]:
        return tuple(self._logits.shape)

    @property
    def dtype(self) -> np.dtype:
        return np.dtype(self._logits.dtype)

    def __len__(self) -> int:
        return self._logits.shape[0]

    def _to_host(self, rows) -> np.ndarray:
        self.tokens     # a step that anybody looked at is read, and counted
        out = np.asarray(rows)
        if self._owner is not None:
            self._owner.logits_bytes_to_host += out.nbytes
        return out

    def __getitem__(self, index) -> np.ndarray:
        if isinstance(index, list):   # numpy takes a list of rows; a jax array refuses one
            index = np.asarray(index)
        if self._rows is not None:
            return self._to_host(self._logits[index, self._rows[index]])
        return self._to_host(self._logits[index])

    def block(self, slot: int) -> np.ndarray:
        """Every row of ``slot``'s block, ``(B, vocab)`` (a step that moves blocks)."""
        if self._rows is None:
            raise TypeError("this step moved one position a slot: step[slot] is its row")
        return self._to_host(self._logits[slot])

    def __array__(self, dtype=None, copy=None) -> np.ndarray:
        out = self._to_host(self._logits)
        return out if dtype is None else out.astype(dtype, copy=False)


class PrefillStep:
    """The prefill one ``prefill`` call LAUNCHED, one step deep as a decode step
    is: the call enqueues the prompt's programs and returns this at once, with
    the next-token logits row and its greedy id both still on the device.
    ``token`` is that id on the host: the argmax of the float32 row, taken in
    the prefill's program (ties break to the lowest id, what ``greedy`` gives of
    the row); its first read waits for the device under the
    ``vs.serve-prefill.fetch`` span, tagged with the launch's number.  Until then
    ``read`` is False, and a :class:`DecodeFeed` may name this step for its slot:
    the decode step then takes the id as it lies on the device.  The row itself
    crosses to the host only when a caller asks for it: ``np.asarray(step)`` is
    the fp32 ``(vocab,)`` row (and reads the id, if nobody has), ``step.shape``
    and ``step.dtype`` copy nothing, so a caller that stacked, compared or
    sampled the row ``prefill`` used to return reads this as one.
    Of a block engine the row is the last prompt position's own, and its serve
    loop never reads either.

    **A prompt that waits to RIDE** (an engine whose ``rides`` is True: a
    single-stage :class:`ServeEngine`, and a ``HybridServeEngine`` whose model's
    module gives a ``serve_ride`` body).  Its ``prefill`` launches nothing: the
    step it returns holds the padded prompt (``launched`` False, ``rung`` and
    ``slot`` say how wide and where) until a decode step CARRIES it, which is
    the caller's to ask for (``DecodeFeed(..., rider=step)``: the prompt's rows
    then go through the stack in that step's program, beside the decode rows
    and under the same read of every weight), or until something needs it: a
    read of ``token`` or of the row, a ``decode`` that is fed from it
    (``fresh``) or from the host's tokens, ``decode_multi`` or ``swap_params``
    launch it first, alone, behind every prompt that came before it (prompts
    go in the order they came), so a caller that knows nothing of riding gets
    what it got before.  While it waits its slot is idle in every step.  The
    serve loop decides (``run_serve_resilient``): the engine only offers."""

    __slots__ = ("_row", "_id", "_token", "_owner", "_launch", "_prompt", "rung", "slot", "_ahead")

    def __init__(self, row, first, owner, launch: Optional[int], *, prompt=None, rung: Optional[int] = None,
                 slot: Optional[int] = None):
        self._row, self._id = row, first    # on the device, both (None while the prompt waits)
        self._token: Optional[int] = None
        self._owner = owner
        self._launch = launch               # the launch's number: what the ``.fetch`` that reads it names
        self._prompt = prompt               # (padded tokens, length, page row) of a prompt that waits for its launch
        self.rung, self.slot = rung, slot
        self._ahead = False                 # counted among ``prefill_reads_ahead``

    @property
    def launched(self) -> bool:
        return self._prompt is None

    @property
    def read(self) -> bool:
        return self._token is not None

    @property
    def token(self) -> int:
        if self._token is None:
            self._owner._read_prefill(self)
        return self._token

    @property
    def shape(self) -> Tuple[int, ...]:
        return tuple(self._row.shape) if self.launched else (self._owner.config.vocab_size,)

    @property
    def dtype(self) -> np.dtype:
        return np.dtype(np.float32 if self._row is None else self._row.dtype)

    def __array__(self, dtype=None, copy=None) -> np.ndarray:
        self.token      # the wait for the device, under the span that names it
        out = np.asarray(self._row)
        return out if dtype is None else out.astype(dtype, copy=False)


class DecodeFeed:
    """The argument of ``decode`` that feeds a step FROM THE DEVICE: every slot
    takes the id that ``step`` (the step launched before, read or not) made for
    it, as it lies on the device, but for the slots of ``fresh``, prefilled
    since, which a program of a few bytes merges in.  ``fresh`` is ``{slot:
    first token}``, and a first token is the host's int, or the slot's
    :class:`PrefillStep` still unread: that slot then takes its id from the
    device too (where every prefill leaves it, ``DecodeAhead._firsts``), and
    the host reads the prefill after this step is enqueued behind it.  The
    serve loop passes one whenever a step is in flight; the call that takes it
    waits for ``step``'s ids after it has enqueued its own program, so the
    device goes from one into the next.

    ``rider`` is a :class:`PrefillStep` that waits for its launch (an engine
    whose ``rides`` is True returned it), and asks this step to CARRY it: the
    prompt's rows go through the stack in the step's own program, each weight
    read once for them and the decode rows together.  The rider's slot is NOT
    stepped by the call that carries it (the program sees it empty; the caller
    does not ``advance`` it): the step leaves the rider's first token where a
    launched prefill leaves its own, so the NEXT call names the rider in
    ``fresh`` as it would a prefill launched alone.  One rider a step, and the
    caller's to choose.  A prompt that waits and that ``fresh`` names is
    launched first, alone, and with it every prompt that came before it or
    before the rider; a YOUNGER one goes on waiting, its slot idle in this
    step too (the caller steps no slot whose prompt waits).

    For an engine whose steps move blocks nothing is fed at all: a slot's open
    block lies in the cache's slot state, where the pass before left it.
    ``slots`` (``{slot: tokens the host will take from this call}``) then names
    the slots this call MOVES (every other slot's block is held as it is), and
    ``step`` may be None (no step in flight): it only says what to wait for,
    and ``fresh`` only which prefills were launched since and left unread (for
    the engine's count: a block engine's loop never reads one).
    ``fused`` names those of them whose block has nothing masked and that commit
    it AND run the first pass of the block after it in this one call
    (``BlockSchedule.FUSED``; the others run the one pass their state asks for),
    ``deferred`` how many slots the host held back from this call because every
    place for commit rows was taken (a count for the engine's counters)."""

    __slots__ = ("step", "fresh", "slots", "fused", "deferred", "rider")

    def __init__(self, step: Optional[DecodeStep], fresh: Optional[Dict[int, Any]] = None,
                 slots: Optional[Dict[int, int]] = None, fused: Sequence[int] = (), deferred: int = 0,
                 rider: Optional[PrefillStep] = None):
        self.step = step
        self.fresh = fresh or {}
        self.slots = slots
        self.fused = fused
        self.deferred = deferred
        self.rider = rider


class BlockSchedule:
    """What a call does to a slot's open block under the STATIC schedule of
    generation by diffusion over blocks (``low_confidence_static``): the
    host's mirror of the state an engine's decode program keeps on the device,
    which is what lets the serve loop launch a call before it has read the last
    one: it knows what each will yield without looking.

    A block of ``B`` positions starts masked but for the ``n mod B`` last tokens
    of a prompt of ``n`` (the first block alone); a denoising pass reveals
    ``transfers(k)`` of the masked positions at its ``k``-th pass (``B / T``,
    the first ``B mod T`` passes one more; never more than are masked); when none
    is masked the block waits for its COMMIT, which runs its final ids through
    the stack once more to leave the block's K and V and yields its tokens: ``B``
    less the prompt's, and no more than the request is still owed.  A commit
    rides in the call that opens the next block (``fuse``: the block's ids go
    through as commit rows of the SAME call that runs the first denoising pass of
    the block after it), so a whole block is ``T`` calls for ``B`` tokens; alone
    it is a call of its own, ``T + 1`` a block, which is what a request's LAST
    block takes (nothing comes after it), and every block of a caller that does
    not ask to fuse.  A request of ``n`` blocks is ``n T + 1`` calls.  Either way
    a commit is a unit of ``B`` rows through the stack, as a denoising pass is.

    At most ``commit_places(slots)`` slots fuse in one call (the program has
    that many places for commit rows, not one a slot: about ``slots / T`` commit
    at any call); a slot that finds them taken is held for that call.

    ``OWN_PASS``, ``FUSED`` and ``HOLD`` are what the decode program of such an
    engine reads in a slot's place in ``tokens`` beside a token id (0 or more:
    the host-token form, the token REVEALED at the slot's length,
    teacher-forced): run the one pass the block's state asks for (denoise, or
    commit alone), commit the block and run the first pass of the block after
    it, or leave the slot's block as it is."""

    OWN_PASS, HOLD, FUSED = -1, -2, -3

    def __init__(self, block_length: int, denoising_steps: int):
        if not 0 < denoising_steps <= block_length:
            raise ValueError(f"{denoising_steps} denoising steps for a block of {block_length}")
        self.B, self.T = int(block_length), int(denoising_steps)

    def transfers(self, k: int) -> int:
        return self.B // self.T + (k < self.B % self.T)

    def commit_places(self, slots: int) -> int:
        """How many of ``slots`` slots may fuse in one call: the ``slots / T`` that
        commit at a call when their phases are spread evenly, and a quarter more.
        A place costs every call ``B`` rows whatever commits, a slot held back one
        call of its own, and holding spreads the phases, so the room is small."""
        return min(slots, -(-5 * slots // (4 * self.T)))

    def open(self, prompt_len: int) -> List[int]:
        """The open block of a slot just prefilled: ``[masked, passes done, revealed by the prompt]``."""
        r = prompt_len % self.B
        return [self.B - r, 0, r]

    def fuses(self, state: List[int], owed: int) -> bool:
        """Is ``state`` a block waiting for its commit whose request is owed
        tokens after it?  Its commit may then ride with the next block's first pass."""
        masked, _k, revealed = state
        return not masked and owed > self.B - revealed

    def plan(self, state: List[int], owed: int, fuse: bool = False) -> Tuple[int, int, int]:
        """Advance ``state`` by one call; ``(skip, count, positions)``: the call
        yields ``tokens[skip: skip + count]`` of the block it returns and
        settles ``positions`` more positions of the cache.  With ``fuse`` (and
        :meth:`fuses`) a commit leaves the block after it one pass on."""
        masked, k, revealed = state
        if masked:
            state[0], state[1] = masked - min(self.transfers(k), masked), k + 1
            return 0, 0, 0
        state[:] = [self.B - self.transfers(0), 1, 0] if fuse and self.fuses(state, owed) else [self.B, 0, 0]
        return revealed, min(self.B - revealed, owed), self.B - revealed


class DecodeAhead:
    """``decode`` as both engines have it (:class:`ServeEngine`,
    ``HybridServeEngine``), one step deep: a call enqueues its program and
    returns the :class:`DecodeStep` unread.  What the engine gives:
    ``_run_decode(table, lengths, tokens) -> (logits, ids, counts or None)``,
    the decode program over the cache's arrays (``tokens`` a device array of
    ``_ids_sharding``), ``kernel_decode``, and ``_count_step`` where a read
    step adds to more than the counters kept here.  ``block`` is None where a
    step moves one position a slot and yields its one token, and the engine's
    :class:`BlockSchedule` where it moves a block (the serve loop asks it what
    a pass yields; ``_fed`` and ``_note`` are then the engine's own).

    **A prompt that rides** is kept here too, once for both engines: the
    prompts that wait for a step to carry them (``_waiting``, which
    ``_prompt_waits`` fills from the engine's ``prefill``), the step that
    carries one (``_carry``) and the launch, alone, of those that something
    needs first (``_launch_waiting``).  An engine that OFFERS a ride (its
    ``rides`` is true: each engine says when) gives ``_run_ride(table, lengths,
    tokens, prompt, slot) -> (logits, ids, counts or None, the prompt's row,
    its greedy id)``, the step's program with the prompt's rows in it, which
    leaves that id in ``slot``'s place of ``_firsts``; with lengths of 0 it is
    a prompt launched alone.  An engine that offers none never has one waiting.
    ``_warm_ladder`` is what both ``warm()`` run: every rung, riding or not, and
    the decode step."""

    block: Optional[BlockSchedule] = None

    def _init_decode_ahead(self, ids_sharding) -> None:
        import jax
        import jax.numpy as jnp

        # every form of ``tokens`` reaches the decode program as a device array of the sharding its own ids
        # have (the host's through a device_put): one signature, so one executable, whatever feeds a step
        self._ids_sharding = ids_sharding

        # a step's programs begin ``jit_decode`` and a prefill's ``jit_prefill``: named for the ``XLA Modules`` line
        def decode_merge(ids, tokens, fresh, firsts, unread):
            return jnp.where(unread, firsts, jnp.where(fresh, tokens, ids))

        def prefill_first(firsts, first, slot):
            return firsts.at[slot].set(first)

        self._merge_fn = jax.jit(decode_merge, out_shardings=ids_sharding)
        self._first_fn = jax.jit(prefill_first, out_shardings=ids_sharding)
        # every slot's newest prefill's greedy id, on the device (what a decode step fed an unread ``PrefillStep``
        # takes; made when first asked for: an engine is built without a device to hold it, for its programs'
        # lowering alone), and the number of the launch that left it: a step that is not its slot's newest is
        # read instead
        self._firsts = None
        self._first_launch: Dict[int, int] = {}
        self._waiting: List[PrefillStep] = []   # the prompts that wait for a step to carry them, in the order they came
        # launches (``decode`` calls and prompts whose programs were enqueued; ``warm`` counts none): the enqueues so
        # far are the NUMBER a launch carries to what it causes, one sequence for both kinds (``launches``)
        self.decode_launches = 0
        self.prefill_launches = 0
        self.prefill_rides = 0          # ... those of them that a decode step carried: one enqueue for both
        self.prefill_reads_ahead = 0
        self.decode_steps = 0
        self.decode_steps_ahead = 0
        self.logits_bytes_to_host = 0
        self.decode_pages_read = 0      # counted only where the paged_decode kernel was built
        self.decode_pages_capacity = 0

    @property
    def launches(self) -> int:
        """The number the next launch takes (``launch=<n>`` on its spans): the
        enqueues so far, a step and the prompt it carried being one."""
        return self.decode_launches + self.prefill_launches - self.prefill_rides

    def _host_tokens(self, tokens):
        import jax

        return jax.device_put(np.asarray(tokens, np.int32).reshape(self.cache.num_slots), self._ids_sharding)

    def _first_ids(self):
        if self._firsts is None:
            self._firsts = self._host_tokens(np.zeros((self.cache.num_slots,), np.int32))
        return self._firsts

    def _note_first(self, first, slot: int) -> None:
        self._firsts = self._first_fn(self._first_ids(), first, np.int32(slot))

    def _merged_tokens(self, ids, fresh: Dict[int, Any]):
        S = self.cache.num_slots
        tokens, mask, unread = np.zeros((S,), np.int32), np.zeros((S,), bool), np.zeros((S,), bool)
        for slot, first in fresh.items():
            if isinstance(first, PrefillStep) and not first.read and self._first_launch.get(slot) == first._launch:
                unread[slot] = True     # its id lies in ``_firsts``: the prefill stays unread
            else:
                tokens[slot], mask[slot] = first.token if isinstance(first, PrefillStep) else first, True
        return self._merge_fn(ids, tokens, mask, self._first_ids(), unread)

    def _launched_prefill(self, row, first, slot: int) -> "PrefillStep":
        """A prefill's programs are enqueued: its id goes to the slot's place
        among the firsts, the launch is counted, and what ``prefill`` returns."""
        launch = self.launches
        self._note_first(first, slot)
        self._first_launch[slot] = launch
        self.prefill_launches += 1
        return PrefillStep(row, first, self, launch)

    def _read_prefill(self, step: "PrefillStep") -> None:
        import jax

        if not step.launched:       # nobody carried it: it goes now, alone, behind whatever came before it
            self._launch_waiting((step,))
        # waits for the device, then copies the id; ``launch`` names the span that caused it
        with ndtimeit(_p.SERVE_PREFILL_FETCH, launch=step._launch):
            step._token = int(jax.device_get(step._id))

    def _fed(self, tokens):
        """What the decode program takes for ``tokens``: the host's ids, or a
        :class:`DecodeFeed`'s (the step before's, with the fresh slots' merged in)."""
        if not isinstance(tokens, DecodeFeed):
            return self._host_tokens(tokens)
        return self._merged_tokens(tokens.step._ids, tokens.fresh) if tokens.fresh else tokens.step._ids

    def _prompt_waits(self, toks: np.ndarray, n: int, page_row: np.ndarray, slot: int) -> "PrefillStep":
        """What ``prefill`` returns where prompts ride: nothing is launched, the
        prompt (padded to its rung) waits for the step that carries it, or for a reader."""
        out = PrefillStep(None, None, self, None, prompt=(toks, np.int32(n), page_row), rung=len(toks), slot=slot)
        self._waiting.append(out)
        return out

    def _carry(self, rider: "PrefillStep", launch: int, table, lengths, tokens):
        """Launch the step numbered ``launch`` with ``rider``'s prompt in it;
        the step's ``(logits, ids, counts)``.  The rider is launched from here on."""
        logits, ids, counts, rider._row, rider._id = self._run_ride(table, lengths, tokens, rider._prompt, rider.slot)
        rider._prompt, rider._launch = None, launch
        self._first_launch[rider.slot] = launch
        self._waiting.remove(rider)
        self.prefill_launches += 1
        return logits, ids, counts

    def _launch_waiting(self, needed=None, rider: Optional["PrefillStep"] = None) -> Sequence["PrefillStep"]:
        """The prompts that wait and are ``needed`` now (all, where None), and
        every one that came BEFORE the last of them or before ``rider`` (what
        two prompts leave in one slot's pages depends on their order), go now,
        ALONE and in the order they came, but ``rider`` itself: the step that
        carries a prompt with every decode row idle (lengths of 0: the rows
        write the null page, leave their slots' state as it was, and their ids
        are nobody's), under a launch span of
        that program's kind.  Returns the prompts that still wait."""
        cache = self.cache
        due = list(self._waiting)
        if due and needed is not None:
            named = {id(step) for step in (*needed, rider)}
            due = due[: max((i for i, step in enumerate(due, 1) if id(step) in named), default=0)]
        for step in due:
            if step is not rider:
                zeros = np.zeros((cache.num_slots,), np.int32)
                n = self.launches
                with ndtimeit(_p.SERVE_DECODE_LAUNCH, launch=n, rung=step.rung, slot=step.slot):
                    self._carry(step, n, cache.table_array(), zeros, self._host_tokens(zeros))
        return self._waiting

    def _note(self, tokens, lengths: np.ndarray) -> Tuple[Optional[np.ndarray], Any]:
        """Of a launch: ``(rows, note)``: which row of a block ``step[slot]`` of
        its :class:`DecodeStep` gives (None: the slot's one row), and what
        ``_count_step`` is told of the launch when the step is read (a block
        engine's own: what the host will take from the step, and which slots fused)."""
        return None, None

    def _warm_ladder(self, run_prefill) -> None:
        """Every rung of ``self.buckets`` (a prompt of one token into the null
        page and slot 0: where prompts ride, the step that carries it with every
        decode row idle, fed the ids the step before made; else
        ``run_prefill(tokens, n, page_row)``) and then the decode step
        (``_warm_decode``, which the last rung's id feeds), twice over: the
        first call of all sees the cache's arrays as they were allocated, every
        later one as a program returned them, and a program that compiles again
        for those does it here."""
        cache = self.cache
        page = cache.config.page_size
        zeros = np.zeros((cache.num_slots,), np.int32)
        table = np.zeros((cache.num_slots, cache.config.pages_per_slot), np.int32)
        ids = self._host_tokens(zeros) if self.rides else None
        for _ in range(2):
            for rung in self.buckets:
                prompt = (np.zeros((rung,), np.int32), np.int32(1), np.zeros((rung // page,), np.int32))
                if self.rides:
                    _, ids, _, _, first = self._run_ride(table, zeros, ids, prompt, 0)
                else:
                    _, first = run_prefill(*prompt)
            self._warm_decode(first)

    def _warm_decode(self, first) -> None:
        """The decode step (no slot active) in every form the loop feeds it: the
        host's tokens, the last step's ids as they are, and those with a fresh
        slot's first token merged in (the host's, or ``first``, the id a warmed
        prefill left on the device: one program takes both)."""
        cache = self.cache
        table = np.zeros((cache.num_slots, cache.config.pages_per_slot), np.int32)
        zeros = np.zeros((cache.num_slots,), np.int32)      # every slot's length, and its token
        self._note_first(first, 0)
        ids = self._run_decode(table, zeros, self._host_tokens(zeros))[1]
        ids = self._run_decode(table, zeros, ids)[1]
        self._run_decode(table, zeros, self._merged_tokens(ids, {0: 0}))

    def decode(self, tokens) -> DecodeStep:
        """Launch one decode step for every slot (inactive slots write only the
        null page): each slot's token goes through the stack, what it leaves in
        the cache lands at the slot's current length, and the
        :class:`DecodeStep` returned, unread, is that of the NEXT position.
        ``tokens`` is the host's ``(num_slots,)`` ids, or a :class:`DecodeFeed`
        naming the step launched before: then the ids come from the device (a
        slot prefilled since from the host's first token or, its
        :class:`PrefillStep` unread, from the id the prefill left on the device:
        ``prefill_reads_ahead`` counts those), and
        once this step is enqueued the call waits for that one's ids (inside
        this call's ``vs.serve-decode`` span, under ``.fetch``), so that on
        return the step before is read and this one is in flight; an unread
        prefill it named is the caller's to read, now behind this step.  Callers
        advance lengths via ``cache.advance`` for the slots whose token was
        real, after the call: a launch takes the lengths as they stand.  A feed
        that names a ``rider`` (a :class:`PrefillStep` that waits; an engine
        whose ``rides`` is True) makes this step CARRY that prompt: one program,
        its span tagged ``rung`` and ``slot`` beside ``launch``; the rider's slot
        is idle in it and is not to be advanced.  A prompt that waits and that the
        feed names in ``fresh`` is launched first, alone (with the host's tokens:
        every prompt that waits); one that goes on waiting has its slot idle.  (An
        engine whose steps move blocks says in its own docstring what a pass
        does with ``tokens``, and what the step returned is of.)"""
        cache = self.cache
        lengths = cache.lengths_array()
        feed = tokens if isinstance(tokens, DecodeFeed) else None
        before = feed.step if feed is not None else None
        rider = feed.rider if feed is not None else None
        with ndtimeit(_p.SERVE_DECODE_CALL):
            # a prompt that waits and that this step is fed from goes before it, alone (with the host's tokens: all of
            # them); the slot of one that still waits, the rider's too, is idle in this step: its first token comes first
            for waits in self._launch_waiting(feed.fresh.values() if feed is not None else None, rider):
                lengths[waits.slot] = 0
            n = self.launches
            if rider is None:
                with ndtimeit(_p.SERVE_DECODE_LAUNCH, launch=n):    # the enqueue alone
                    logits, ids, counts = self._run_decode(cache.table_array(), lengths, self._fed(tokens))
            else:
                with ndtimeit(_p.SERVE_DECODE_LAUNCH, launch=n, rung=rider.rung, slot=rider.slot):
                    logits, ids, counts = self._carry(rider, n, cache.table_array(), lengths, self._fed(tokens))
                self.prefill_rides += 1
            self.decode_launches += 1
            if feed is not None:    # the prefills this step went in behind (or carried), unread
                for f in (*feed.fresh.values(), rider):
                    if isinstance(f, PrefillStep) and not (f.read or f._ahead):
                        f._ahead = True
                        self.prefill_reads_ahead += 1
            ahead = before is not None and not before.read
            rows, note = self._note(tokens, lengths)
            out = DecodeStep(ids, logits, self, (lengths, counts, ahead, note, n), rows)
            if ahead:
                self._read_step(before)     # the device goes from that step straight into this one
        return out

    def _read_step(self, step: DecodeStep) -> None:
        import jax

        lengths, counts, ahead, note, n = step._launch
        # waits for the device, then copies the ids (and the step's counts); ``launch`` names the span that caused it
        with ndtimeit(_p.SERVE_DECODE_FETCH, launch=n):
            step._tokens, counts = jax.device_get((step._ids, counts))
        step._launch = None
        self.decode_steps += 1
        self.decode_steps_ahead += ahead
        self._count_step(lengths, counts, note)

    def _count_step(self, lengths: np.ndarray, counts, note=None) -> None:
        self._count_pages(lengths + 1)

    def _count_pages(self, *reaches: np.ndarray) -> None:
        """One decode call's pages: ``reaches`` are the positions each row of the
        kernel's table was read up to (a slot's new token; an inactive slot's one)."""
        if self.kernel_decode:
            # what the kernel fetched, of the table's S x Pmax
            cache = self.cache
            page, per_slot = cache.config.page_size, cache.config.pages_per_slot
            self.decode_pages_read += sum(int(np.minimum(-(-reach // page), per_slot).sum()) for reach in reaches)
            self.decode_pages_capacity += cache.num_slots * per_slot

    @staticmethod
    def greedy(logits_row: np.ndarray) -> int:
        """Deterministic greedy sample (ties break to the lowest id)."""
        return int(np.argmax(logits_row))

    def replay_greedy(self, prompt: Sequence[int], max_new_tokens: int,
                      *, eos_id: Optional[int] = None,
                      canary: bool = False) -> List[int]:
        """Standalone greedy generation through the CURRENT weights on a
        temporarily allocated slot — the rollout canary's replay
        primitive (and the golden-baseline recorder before a swap).  The
        slot is freed before returning, so a drained replica's cache is
        untouched; callers must only run this while the slot can be
        reserved (the rollout path replays after the drain, when the
        whole pool is free).

        ``canary=True`` marks a post-swap verification replay: each
        greedy step consults the ``canary_diverge`` faultsim hook, which
        (when armed and due) flips the sign of the step's top logit — the
        deterministic bad-checkpoint stand-in that proves the
        auto-rollback path without a genuinely corrupt restore."""
        from ..resilience import faultsim as _fs

        cache = self.cache
        slot = cache.alloc(len(prompt), max_new_tokens)

        def _pick(tok: int, row) -> int:
            # ``tok`` is the greedy token of the logits row that ``row()``
            # copies to the host: only a firing fault reads it
            if canary and _fs.fires("canary_diverge", ctx="replay"):
                flipped = np.array(row(), copy=True)
                flipped[tok] = -flipped[tok]
                return self.greedy(flipped)
            return tok

        try:
            first = self.prefill(list(prompt), slot)
            cache.commit_prefill(slot, len(prompt))
            out: List[int] = []
            if self.block is not None:
                # by blocks, as the serve loop generates: passes until the budget is filled (or an EOS is out)
                state = self.block.open(len(prompt))
                while len(out) < max_new_tokens and not (eos_id is not None and out and out[-1] == eos_id):
                    skip, count, positions = self.block.plan(state, max_new_tokens - len(out))
                    step = self.decode(DecodeFeed(None, slots={slot: count}))
                    cache.advance(slot, positions)
                    for j in range(skip, skip + count):
                        out.append(_pick(int(step.tokens[slot, j]), lambda: step.block(slot)[j]))
                        if eos_id is not None and out[-1] == eos_id:
                            break
                return out
            tok = _pick(first.token, lambda: np.asarray(first))
            out.append(tok)
            for _ in range(max_new_tokens - 1):
                if eos_id is not None and tok == eos_id:
                    break
                toks = np.zeros((cache.num_slots,), np.int32)
                toks[slot] = tok
                step = self.decode(toks)
                cache.advance(slot)
                tok = _pick(int(step.tokens[slot]), lambda: step[slot])
                out.append(tok)
            return out
        finally:
            cache.free(slot)

def _rmsnorm(x, w, eps):
    import jax
    import jax.numpy as jnp

    x32 = x.astype(jnp.float32)
    x32 = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + eps)
    return x32 * w  # caller casts


def stack_params_check(params: Dict[str, Any], num_layers: int) -> None:
    """The engine consumes the UNSTACKED per-layer layout (``layers_i.*``);
    a ``scan_layers`` checkpoint (stacked ``layers.block.*``) must be
    unstacked first — fail with the fix named, not a KeyError."""
    if "layers_0" not in params:
        if "layers" in params:
            raise ValueError(
                "params use the scan_layers stacked layout (layers.block.*); "
                "serve the unstacked layout (LlamaConfig.scan_layers=False) or "
                "unstack the leading layer axis before building ServeEngine"
            )
        raise ValueError("params have no layers_0 — not a llama-family tree")
    for l in range(num_layers):
        if f"layers_{l}" not in params:
            raise ValueError(f"params missing layers_{l} (num_hidden_layers={num_layers})")


class ServeEngine(DecodeAhead):
    """Compiled prefill/decode over ``cache``.  ``config`` is the training
    ``LlamaConfig`` (the one the checkpoint was trained with); ``params``
    is the flax ``params`` tree (np / jax / DArray leaves — host leaves are
    replicated onto ``mesh`` once at construction)."""

    def __init__(
        self,
        config,
        mesh,
        params: Dict[str, Any],
        cache: PagedKVCache,
        *,
        num_stages: int = 1,
        interpret: Optional[bool] = None,
    ):
        import jax
        import jax.numpy as jnp

        c = config
        if cache.config.layers != c.num_hidden_layers:
            raise ValueError(
                f"cache has {cache.config.layers} layers, model {c.num_hidden_layers}"
            )
        if cache.config.kv_heads != c.num_key_value_heads:
            raise ValueError(
                f"cache has {cache.config.kv_heads} kv heads, model {c.num_key_value_heads}"
            )
        if cache.config.head_dim != c.head_dim:
            raise ValueError(f"cache head_dim {cache.config.head_dim} != model {c.head_dim}")
        if not (1 <= num_stages <= c.num_hidden_layers):
            raise ValueError(f"num_stages={num_stages} for {c.num_hidden_layers} layers")
        self.config = c
        self.mesh = mesh
        self.cache = cache
        self.num_stages = num_stages
        self.interpret = interpret
        params = _as_tree(params)
        stack_params_check(params, c.num_hidden_layers)
        self.params = jax.tree_util.tree_map(self._replicate, params)
        self.stage_bounds = self._stage_bounds(num_stages)
        # a function of the cache's geometry: whole pages, so a rung's K/V is a whole number of page writes
        self.buckets = prefill_buckets(cache.config.page_size, cache.max_seq_len, smallest=_SMALLEST_RUNG)
        self._warmed = False
        self._positions = np.arange(cache.max_seq_len, dtype=np.int32)[None, :]
        # what this engine has done, in plain integers (a trace session
        # reads them at its two ends: ``trace_counters``)
        self.prefill_calls = 0
        self.prefill_tokens_real = 0
        self.prefill_tokens_padded = 0
        register_counter_source(self)
        self._build()

    # ------------------------------------------------------------- params
    def _replicate(self, leaf):
        """Host leaves -> mesh-replicated global arrays once, up front (a
        per-call host transfer would dominate decode)."""
        import jax
        import numpy as np

        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..darray import DArray

        if isinstance(leaf, DArray):
            return leaf.data
        if isinstance(leaf, jax.Array):
            return leaf
        host = np.asarray(leaf)
        sharding = NamedSharding(self.mesh.jax_mesh, P())
        return jax.make_array_from_callback(host.shape, sharding, lambda idx: host[idx])

    @property
    def rides(self) -> bool:
        """Does a prompt ride a decode step here?  The OFFER the serve loop
        asks for (``HybridServeEngine`` sets its own, by its model): where the stack is one stage,
        ``prefill`` returns a :class:`PrefillStep` that waits, and a ``decode``
        whose :class:`DecodeFeed` names it as ``rider`` carries it."""
        return len(self.stage_bounds) == 1

    def swap_params(self, params: Dict[str, Any]) -> Dict[str, Any]:
        """Hot-swap the weight tree WITHOUT rebuilding: every compiled
        program takes ``params`` as an argument, so a tree with identical
        structure/shapes/dtypes slots straight in — no retrace.  Host leaves are
        replicated exactly as at construction.  Returns the PRIOR tree —
        the rollback handle the rolling-rollout canary swaps back on
        divergence.  Incompatible trees raise before anything is touched
        (the serving tree is never left half-swapped)."""
        import jax

        self._launch_waiting()      # a prompt that waits was given to the tree that is serving now
        new = _as_tree(params)
        stack_params_check(new, self.config.num_hidden_layers)
        new = jax.tree_util.tree_map(self._replicate, new)
        old_leaves, old_def = jax.tree_util.tree_flatten(self.params)
        new_leaves, new_def = jax.tree_util.tree_flatten(new)
        if old_def != new_def:
            raise ValueError(
                "swap_params: new tree structure differs from the serving tree "
                "(compiled programs are static — rebuild the engine instead)"
            )
        for o, n in zip(old_leaves, new_leaves):
            if o.shape != n.shape or o.dtype != n.dtype:
                raise ValueError(
                    f"swap_params: leaf mismatch {n.shape}/{n.dtype} vs serving "
                    f"{o.shape}/{o.dtype} (compiled programs are static)"
                )
        old, self.params = self.params, new
        return old

    def _stage_bounds(self, num_stages: int) -> List[Tuple[int, int]]:
        """Contiguous layer ranges balanced by param count — the pipe
        engine's stage-split math over the decoder stack."""
        from ..pipe.pipe_stage import _cuts_by_weight

        L = self.config.num_hidden_layers
        if num_stages == 1:
            return [(0, L)]
        weights = []
        for l in range(L):
            lp = self.params[f"layers_{l}"]
            import jax

            weights.append(
                float(sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(lp)))
            )
        cuts = _cuts_by_weight(weights, num_stages)
        bounds = []
        lo = 0
        for cut in list(cuts) + [L]:
            bounds.append((lo, cut))
            lo = cut
        return bounds

    # -------------------------------------------------------------- build
    def _build(self) -> None:
        import jax
        import jax.numpy as jnp

        from jax.sharding import NamedSharding, PartitionSpec as P

        c = self.config
        cache = self.cache
        S = cache.num_slots
        Tmax = cache.max_seq_len
        page = cache.config.page_size
        Pmax = cache.config.pages_per_slot
        H, KV, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        dtype = c.dtype
        eps = c.rms_norm_eps
        theta = c.rope_theta
        scale = 1.0 / math.sqrt(hd)
        rep_sharding = NamedSharding(self.mesh.jax_mesh, P())
        cache_sharding = cache.spec.named_sharding()
        interpret = self.interpret

        from ..models.llama import rotary

        def dense(x, kernel):
            return x.astype(dtype) @ kernel.astype(dtype)

        def embed(params, tokens):
            return jnp.take(params["embed_tokens"]["embedding"], tokens, axis=0).astype(dtype)

        def head(params, x):
            xn = _rmsnorm(x, params["norm"]["weight"], eps).astype(dtype)
            if c.tie_word_embeddings:
                logits = xn @ params["embed_tokens"]["embedding"].astype(dtype).T
            else:
                logits = dense(xn, params["lm_head"]["kernel"])
            return logits.astype(jnp.float32)

        def block_prefill(lp, x, positions):
            """One decoder block over the prompt padded to its rung: returns the
            residual stream plus this layer's K/V for the cache."""
            B, T, E = x.shape
            xn = _rmsnorm(x, lp["input_layernorm"]["weight"], eps).astype(dtype)
            q = dense(xn, lp["self_attn"]["q_proj"]["kernel"]).reshape(B, T, H, hd)
            k = dense(xn, lp["self_attn"]["k_proj"]["kernel"]).reshape(B, T, KV, hd)
            v = dense(xn, lp["self_attn"]["v_proj"]["kernel"]).reshape(B, T, KV, hd)
            q, k = rotary(q, k, positions, theta)
            from ..ops.flash_attention import flash_attention

            y = flash_attention(q, k, v, causal=True, interpret=interpret)
            y = y.reshape(B, T, H * hd)
            x = x + dense(y, lp["self_attn"]["o_proj"]["kernel"])
            xn2 = _rmsnorm(x, lp["post_attention_layernorm"]["weight"], eps).astype(dtype)
            g = dense(xn2, lp["mlp"]["gate_proj"]["kernel"])
            u = dense(xn2, lp["mlp"]["up_proj"]["kernel"])
            x = x + dense(jax.nn.silu(g) * u, lp["mlp"]["down_proj"]["kernel"])
            return x, k[0], v[0]

        # every layer has the block's shapes, so jitted the block is traced and
        # lowered once a rung whatever the depth (XLA inlines the calls): with a
        # ladder of rungs to warm, the set-up pays that once a rung, not once a layer
        block = jax.jit(block_prefill)

        def make_stage(lo, hi):
            def prefill_stage(params, x, positions):
                ks, vs = [], []
                for l in range(lo, hi):
                    x, k, v = block(params[f"layers_{l}"], x, positions)
                    ks.append(k)
                    vs.append(v)
                return x, jnp.stack(ks), jnp.stack(vs)

            return jax.jit(prefill_stage)

        # the programs' names tell their kind on the ``XLA Modules`` line: every program of a prefill begins
        # ``jit_prefill``, every program of a decode step ``jit_decode``
        def prefill_embed(p, toks):
            return embed(p, toks)[None]

        self._embed_fn = jax.jit(prefill_embed)
        self._stage_fns = [make_stage(lo, hi) for lo, hi in self.stage_bounds]

        def prefill_head(params, x, length):
            last = jax.lax.dynamic_index_in_dim(x, length - 1, axis=1, keepdims=False)
            logits = jax.lax.with_sharding_constraint(head(params, last)[0], rep_sharding)
            # the row's greedy id, in this program as the decode step takes its own (``PrefillStep.token``)
            first = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return logits, jax.lax.with_sharding_constraint(first, rep_sharding)

        self._head_fn = jax.jit(prefill_head)

        def prefill_commit(kd, vd, k_stack, v_stack, page_row):
            # (L, rung, KV, hd) -> the rung's pages scattered into the pool
            # (page_row is the slot's first rung // page table entries);
            # entries beyond the reserved pages are 0 = the null page
            kp = k_stack.reshape(c.num_hidden_layers, -1, page, KV, hd)
            vp = v_stack.reshape(c.num_hidden_layers, -1, page, KV, hd)
            kd = kd.at[:, page_row].set(kp.astype(kd.dtype))
            vd = vd.at[:, page_row].set(vp.astype(vd.dtype))
            return (
                jax.lax.with_sharding_constraint(kd, cache_sharding),
                jax.lax.with_sharding_constraint(vd, cache_sharding),
            )

        self._commit_fn = jax.jit(prefill_commit, donate_argnums=(0, 1))

        # ---- kernel dispatch (latched at build: the decode program is
        # compiled once; VESCALE_KERNELS is read here, not per step).  Unset,
        # paged_decode is the compiled kernel on TPU and the XLA leg elsewhere
        from ..kernels import paged_attention as _paged

        # mesh axis sharding the pool's kv-head dim (dim 3 of the 5-D cache
        # layout) — the kernel runs per-shard under the shard_map shim there
        kernel_shard_ax, kv_local = None, KV
        for i, p in enumerate(cache.spec.placements):
            if p.is_shard(3) and self.mesh.shape[i] > 1:
                kernel_shard_ax, kv_local = self.mesh.mesh_dim_names[i], KV // self.mesh.shape[i]
                break
        kernel_interpret = _paged.leg(cache.k.data.dtype, kv_local, hd)
        self.kernel_decode = kernel_interpret is not None
        kernel_shard_ax = kernel_shard_ax if self.kernel_decode else None   # (the compiler partitions the XLA leg itself)

        def attend(q, kd, vd, layer, table, valid_len):
            # q (S,H,hd); kd/vd: the WHOLE (L, N, page, KV, hd) pools (the kernel reads only ``layer``'s live
            # pages out of them, the XLA leg gathers every slot's); table (S,Pmax); valid_len (S,)
            from ..collectives import shard_map

            body = lambda *local: _paged.paged_decode(*local, layer=layer, scale=scale, interpret=kernel_interpret)
            if kernel_shard_ax is None:
                out = body(q, kd, vd, table, valid_len)
            else:
                ax = kernel_shard_ax
                pool = P(None, None, None, ax, None)
                out = shard_map(
                    body,
                    mesh=self.mesh.jax_mesh,
                    in_specs=(P(None, ax, None), pool, pool, P(), P()),
                    out_specs=P(None, ax, None),
                    check_vma=False,
                    axis_names=frozenset({ax}),
                )(q, kd, vd, table, valid_len)
            return out.reshape(S, H * hd).astype(dtype)

        def decode(params, kd, vd, table, lengths, tokens):
            x = embed(params, tokens)  # (S, E)
            pos = lengths  # write position of the new token
            # capacity guard: a position past the slot's reserved pages
            # (a speculative drafter running ahead of the token budget)
            # writes the reserved null page instead of aliasing a LIVE
            # page through index clamping.  An UNCOMMITTED slot (length 0
            # — allocated but not yet prefilled; with prefix caching its
            # table may already map SHARED pages) must not write either:
            # no legitimate decode targets a slot before commit_prefill
            valid = (pos < Pmax * page) & (lengths > 0)
            safe = jnp.where(valid, pos, 0)
            pg = jnp.take_along_axis(table, (safe // page)[:, None], axis=1)[:, 0]
            pg = jnp.where(valid, pg, 0)
            off = safe % page
            for l in range(c.num_hidden_layers):
                lp = params[f"layers_{l}"]
                xn = _rmsnorm(x, lp["input_layernorm"]["weight"], eps).astype(dtype)
                q = dense(xn, lp["self_attn"]["q_proj"]["kernel"]).reshape(S, 1, H, hd)
                k = dense(xn, lp["self_attn"]["k_proj"]["kernel"]).reshape(S, 1, KV, hd)
                v = dense(xn, lp["self_attn"]["v_proj"]["kernel"]).reshape(S, 1, KV, hd)
                q, k = rotary(q, k, pos[:, None], theta)
                k1, v1 = k[:, 0], v[:, 0]
                kd = kd.at[l, pg, off].set(k1.astype(kd.dtype))
                vd = vd.at[l, pg, off].set(v1.astype(vd.dtype))
                y = attend(q[:, 0], kd, vd, l, table, pos + 1)
                x = x + dense(y, lp["self_attn"]["o_proj"]["kernel"])
                xn2 = _rmsnorm(x, lp["post_attention_layernorm"]["weight"], eps).astype(dtype)
                gt = dense(xn2, lp["mlp"]["gate_proj"]["kernel"])
                u = dense(xn2, lp["mlp"]["up_proj"]["kernel"])
                x = x + dense(jax.nn.silu(gt) * u, lp["mlp"]["down_proj"]["kernel"])
            logits = jax.lax.with_sharding_constraint(head(params, x), rep_sharding)
            # the greedy token of every slot, in this program: of the replicated
            # logits, so every device of a tp mesh holds the same ids
            next_ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (
                logits,
                jax.lax.with_sharding_constraint(next_ids, rep_sharding),
                jax.lax.with_sharding_constraint(kd, cache_sharding),
                jax.lax.with_sharding_constraint(vd, cache_sharding),
            )

        self._decode_fn = jax.jit(decode, donate_argnums=(1, 2))
        self._init_decode_ahead(rep_sharding)

        # ---- the step that CARRIES a prompt: the decode step above with ``rung`` rows more.  The S decode rows and
        # the prompt's rows are ONE array before every weight's product, so a weight crosses the HBM once for both
        # (a memory-bound step of S rows has room under the bytes it moves anyway); attention apart, each as it is
        # alone: the decode rows over their pages, the prompt's causally over themselves (the whole prompt rides one
        # step, so none of its rows attends to a page); the prompt's K/V into its slot's pages a layer at a time; the
        # head over the decode rows and the prompt's row ``n - 1``.  The same program with every decode row idle
        # (lengths of 0: each writes the null page) is a prompt launched ALONE, so a rung has the one program
        from ..ops.flash_attention import flash_attention

        def ride_layer(lp, x, kd, vd, l, table, pos, pg, off, positions, page_row):
            """One decoder block over the S decode rows and the prompt's R rows, ``x`` (S + R, E): every weight in
            ONE product over all of them; ``l`` is the layer's number as a value, so that the block is traced
            once a rung whatever the depth (a set-up that traced it a layer paid seconds a rung for it)."""
            R = x.shape[0] - S
            xn = _rmsnorm(x, lp["input_layernorm"]["weight"], eps).astype(dtype)
            q = dense(xn, lp["self_attn"]["q_proj"]["kernel"]).reshape(1, S + R, H, hd)
            k = dense(xn, lp["self_attn"]["k_proj"]["kernel"]).reshape(1, S + R, KV, hd)
            v = dense(xn, lp["self_attn"]["v_proj"]["kernel"]).reshape(1, S + R, KV, hd)
            q, k = rotary(q, k, positions, theta)
            kd = kd.at[l, pg, off].set(k[0, :S].astype(kd.dtype))
            vd = vd.at[l, pg, off].set(v[0, :S].astype(vd.dtype))
            # (page_row is the slot's first rung // page table entries; one past its reserved pages is the null page)
            kd = kd.at[l, page_row].set(k[0, S:].reshape(R // page, page, KV, hd).astype(kd.dtype))
            vd = vd.at[l, page_row].set(v[0, S:].reshape(R // page, page, KV, hd).astype(vd.dtype))
            y = jnp.concatenate([
                attend(q[0, :S], kd, vd, l, table, pos + 1),
                flash_attention(q[:, S:], k[:, S:], v[:, S:], causal=True, interpret=interpret).reshape(R, H * hd)])
            x = x + dense(y, lp["self_attn"]["o_proj"]["kernel"])
            xn2 = _rmsnorm(x, lp["post_attention_layernorm"]["weight"], eps).astype(dtype)
            gt = dense(xn2, lp["mlp"]["gate_proj"]["kernel"])
            u = dense(xn2, lp["mlp"]["up_proj"]["kernel"])
            return x + dense(jax.nn.silu(gt) * u, lp["mlp"]["down_proj"]["kernel"]), kd, vd

        ride_layer = jax.jit(ride_layer)    # (inlined where it is called: the pools are the outer program's to donate)

        # (the step's name on the device's ``XLA Modules`` line is its function's: ``jit_decode``, with a prompt or without)
        def decode(params, kd, vd, table, lengths, tokens, firsts, prompt, n, page_row, slot):      # noqa: F811
            pos = lengths
            valid = (pos < Pmax * page) & (lengths > 0)     # the guard of the step without a prompt: an idle row writes the null page
            safe = jnp.where(valid, pos, 0)
            pg = jnp.take_along_axis(table, (safe // page)[:, None], axis=1)[:, 0]
            pg = jnp.where(valid, pg, 0)
            off = safe % page
            x = jnp.concatenate([embed(params, tokens), embed(params, prompt)])     # (S + rung, E)
            positions = jnp.concatenate([pos, jnp.arange(prompt.shape[0], dtype=pos.dtype)])[None]
            for l in range(c.num_hidden_layers):
                x, kd, vd = ride_layer(params[f"layers_{l}"], x, kd, vd, np.int32(l), table, pos, pg, off, positions, page_row)
            last = jax.lax.dynamic_index_in_dim(x, S + n - 1, axis=0, keepdims=True)
            logits = jax.lax.with_sharding_constraint(head(params, jnp.concatenate([x[:S], last])), rep_sharding)
            ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)     # S steps' greedy ids, and the prompt's first
            return (
                logits[:S],
                jax.lax.with_sharding_constraint(ids[:S], rep_sharding),
                logits[S],
                jax.lax.with_sharding_constraint(ids[S], rep_sharding),
                # the first id to its slot's place among the firsts, where a prefill launched alone leaves its own
                jax.lax.with_sharding_constraint(firsts.at[slot].set(ids[S]), rep_sharding),
                jax.lax.with_sharding_constraint(kd, cache_sharding),
                jax.lax.with_sharding_constraint(vd, cache_sharding),
            )

        self._ride_fn = jax.jit(decode, donate_argnums=(1, 2))      # one executable a rung: the prompt's shape is the rung

        # ---- multi-token step factory (speculative verify + prefix-cache
        # suffix prefill): the token width W is a COMPILE-TIME constant —
        # each distinct W lowers once into self._multi_fns and never
        # retraces as requests come and go.  Same attention math as the
        # single-token decode (paged gather, length mask, fp32 softmax)
        # with one extra token axis; token i of a slot's window attends
        # positions <= lengths+i, which includes the window's own earlier
        # tokens because every window K/V is scattered before the gather.
        def make_multi(W):
            def decode_multi(params, kd, vd, table, lengths, tokens):
                x = embed(params, tokens)  # (S, W, E)
                pos = lengths[:, None] + jnp.arange(W, dtype=lengths.dtype)[None, :]
                # same null-page guard as decode: positions past the
                # slot's reserved pages AND slots awaiting their prefill
                # (length 0 — whose tables may already map pages SHARED
                # with live slots) write the null page, never a live one
                valid = (pos < Pmax * page) & (lengths[:, None] > 0)
                safe = jnp.where(valid, pos, 0)
                pg = jnp.take_along_axis(table, safe // page, axis=1)
                pg = jnp.where(valid, pg, 0)
                off = safe % page
                g = H // KV
                for l in range(c.num_hidden_layers):
                    lp = params[f"layers_{l}"]
                    xn = _rmsnorm(x, lp["input_layernorm"]["weight"], eps).astype(dtype)
                    q = dense(xn, lp["self_attn"]["q_proj"]["kernel"]).reshape(S, W, H, hd)
                    k = dense(xn, lp["self_attn"]["k_proj"]["kernel"]).reshape(S, W, KV, hd)
                    v = dense(xn, lp["self_attn"]["v_proj"]["kernel"]).reshape(S, W, KV, hd)
                    q, k = rotary(q, k, pos, theta)
                    kd = kd.at[l, pg, off].set(k.astype(kd.dtype))
                    vd = vd.at[l, pg, off].set(v.astype(vd.dtype))
                    ks = jnp.take(kd[l], table, axis=0).reshape(S, Tmax, KV, hd)
                    vs = jnp.take(vd[l], table, axis=0).reshape(S, Tmax, KV, hd)
                    qg = (q.astype(jnp.float32) * scale).reshape(S, W, KV, g, hd)
                    s = jnp.einsum("swkgd,stkd->swkgt", qg, ks.astype(jnp.float32))
                    mask = (
                        jnp.arange(Tmax, dtype=jnp.int32)[None, None, :]
                        <= pos[:, :, None]
                    )
                    s = jnp.where(mask[:, :, None, None, :], s, -1e30)
                    p = jax.nn.softmax(s, axis=-1)
                    o = jnp.einsum("swkgt,stkd->swkgd", p, vs.astype(jnp.float32))
                    y = o.reshape(S, W, H * hd).astype(dtype)
                    x = x + dense(y, lp["self_attn"]["o_proj"]["kernel"])
                    xn2 = _rmsnorm(
                        x, lp["post_attention_layernorm"]["weight"], eps
                    ).astype(dtype)
                    gt = dense(xn2, lp["mlp"]["gate_proj"]["kernel"])
                    u = dense(xn2, lp["mlp"]["up_proj"]["kernel"])
                    x = x + dense(jax.nn.silu(gt) * u, lp["mlp"]["down_proj"]["kernel"])
                logits = head(params, x)  # (S, W, vocab) fp32
                return (
                    jax.lax.with_sharding_constraint(logits, rep_sharding),
                    jax.lax.with_sharding_constraint(kd, cache_sharding),
                    jax.lax.with_sharding_constraint(vd, cache_sharding),
                )

            return jax.jit(decode_multi, donate_argnums=(1, 2))

        self._make_multi = make_multi
        self._multi_fns: Dict[int, Any] = {}

    def warm(self) -> "ServeEngine":
        """Compile and run every program of the serving path: each rung of the
        prefill ladder (into the null page only: a page row of zeros, so no
        slot's pages or length are touched) and the decode step (no slot
        active, in each form of its tokens: ``_warm_decode``, which the last
        rung's id feeds).  Where prompts ride (``rides``) a rung is ONE
        program, the step that carries it, run here with every decode row
        idle; else the stages' four.  Twice over (``_warm_ladder``, which
        ``HybridServeEngine.warm`` runs too, says why).  The first ``prefill`` of an engine's life runs
        this if nobody has; no ``prefill`` or ``decode`` compiles after it
        (``decode_multi`` lowers a width when it first meets it)."""
        import jax

        cache = self.cache
        self._warmed = True
        self._warm_ladder(self._run_prefill)
        # ... and RUN: what the device still owes of a program's first run (an executable read from the compile cache is
        # loaded when it first runs; on a v5e some ten seconds for a ladder of rungs) is set-up's, not the first request's
        jax.block_until_ready((cache.k.data, cache.v.data))
        return self

    # ---------------------------------------------------------------- API
    def _run_prefill(self, toks: np.ndarray, n: int, page_row: np.ndarray):
        """The programs of one prefill at ``len(toks)`` positions; the logits
        row of position ``n - 1`` and its greedy id, still on the device."""
        import jax.numpy as jnp

        cache = self.cache
        x = self._embed_fn(self.params, toks)
        positions = self._positions[:, : len(toks)]
        ks, vs = [], []
        for fn in self._stage_fns:
            x, k, v = fn(self.params, x, positions)
            ks.append(k)
            vs.append(v)
        logits, first = self._head_fn(self.params, x, np.int32(n))
        k_stack = ks[0] if len(ks) == 1 else jnp.concatenate(ks, axis=0)
        v_stack = vs[0] if len(vs) == 1 else jnp.concatenate(vs, axis=0)
        kd, vd = self._commit_fn(cache.k.data, cache.v.data, k_stack, v_stack, page_row)
        cache.update(kd, vd)
        return logits, first

    def _run_decode(self, table, lengths, tokens):
        cache = self.cache
        logits, next_ids, kd, vd = self._decode_fn(self.params, cache.k.data, cache.v.data, table, lengths, tokens)
        cache.update(kd, vd)
        return logits, next_ids, None

    def _run_ride(self, table, lengths, tokens, prompt, slot: int):
        """The step that carries ``prompt`` (tokens padded to the rung, length,
        page row): the decode rows' logits and ids, no counts, the prompt's row
        and its greedy id (which the program has put in ``slot``'s place among
        the firsts), all still on the device."""
        cache = self.cache
        logits, next_ids, row, first, self._firsts, kd, vd = self._ride_fn(
            self.params, cache.k.data, cache.v.data, table, lengths, tokens, self._first_ids(), *prompt, np.int32(slot))
        cache.update(kd, vd)
        return logits, next_ids, None, row, first

    def prefill(self, prompt: Sequence[int], slot: int) -> PrefillStep:
        """Send the prompt through the stack: its K/V goes into ``slot``'s
        reserved pages, and the :class:`PrefillStep` returned at once, unread,
        holds the next-token logits row and its greedy id on the device
        (``.token`` waits for the id; ``np.asarray(step)`` is the fp32 row, for
        a caller that wants it).  The serve loop reads ``.token`` after it has
        enqueued the decode step that takes the id from the device.
        The prompt is padded to the smallest of ``self.buckets`` that holds
        it, every rung is compiled by ``warm()``, so repeat calls never retrace.

        Where prompts RIDE (``self.rides``: the stack is one stage) this call
        launches NOTHING: the step returned WAITS (``launched`` False) for a
        ``decode`` whose :class:`DecodeFeed` names it as ``rider``, which runs
        the prompt's rows through that step's own program, and the K/V, the row
        and the id exist from then on.  Whether a prompt rides is the CALLER's
        decision (``run_serve_resilient`` takes the offer up where a step is
        about to be launched); a caller that knows nothing of it gets what it
        got before, because a prompt that waits is launched alone, in the order
        the prompts came, by whatever needs it first: a read of its token or
        row, a ``decode`` fed from it or from the host's tokens,
        ``decode_multi``, ``swap_params``.  What does NOT see it is a direct
        read of ``cache.k`` / ``cache.v`` between this call and those.
        With more stages a prefill is a program a stage (and three small ones),
        launched here, as before."""
        cache = self.cache
        n = len(prompt)
        if not (0 < n <= cache.max_seq_len):
            raise ValueError(f"prompt length {n} not in (0, {cache.max_seq_len}]")
        if not self._warmed:
            self.warm()
        rung = next(b for b in self.buckets if b >= n)
        with ndtimeit(_p.SERVE_PREFILL_CALL):
            toks = np.zeros((rung,), np.int32)
            toks[:n] = np.asarray(prompt, np.int32)
            page_row = cache.page_table[slot, : rung // cache.config.page_size].copy()
            if self.rides:
                out = self._prompt_waits(toks, n, page_row, slot)
            else:
                with ndtimeit(_p.SERVE_PREFILL_LAUNCH, launch=self.launches, rung=rung, slot=slot):     # the enqueue alone
                    out = self._launched_prefill(*self._run_prefill(toks, n, page_row), slot)
        self.prefill_calls += 1
        self.prefill_tokens_real += n
        self.prefill_tokens_padded += rung
        return out

    def trace_counters(self) -> Dict[str, int]:
        """The engine's own counts since it was built (a trace session
        reports what was added while it ran).  ``decode_launches`` and
        ``prefill_launches`` count the ``decode`` / ``prefill`` calls that
        enqueued their programs (their sum numbers the launches: the
        ``launch=<n>`` tag of ``vs.serve-decode.launch``,
        ``vs.serve-prefill.launch`` and the ``.fetch`` that reads each); a step
        launched inside a session and read after it is in ``decode_launches``
        and not in ``decode_steps``.  Where prompts ride, ``prefill_launches``
        counts the prompts whose program was enqueued and ``prefill_rides``
        those of them that a ``decode`` call's step CARRIED (one enqueue, one
        number, for both; the rest went alone: that step's program with every
        decode row idle, a ``vs.serve-decode.launch`` of its own that no
        ``decode_launches`` counts and no fetch reads).  ``prefill_reads_ahead`` counts the
        prefills whose id was still unread when the decode step that takes it
        was enqueued (a :class:`DecodeFeed` named the ``PrefillStep``: the
        device went from the prefill into the step; the rest were read first).
        ``decode_steps`` counts the decode steps READ (a ``decode`` call launches its step; whoever reads
        its ids first counts it), ``decode_steps_ahead`` those of them that
        were launched while the step before was still unread (the pipeline
        engaged; the rest started cold).  ``logits_bytes_to_host`` is of
        ``decode`` calls: the bytes of fp32
        logits that callers copied out of their results (none for a step
        read through ``.tokens`` alone, ``vocab x 4`` a row, ``slots x
        vocab x 4`` a whole read; a prefill's row is copied where a
        caller asks for it and ``decode_multi`` copies every row, neither
        counted).  ``prefill_tokens_padded``
        adds the rung each of the ``prefill_calls`` ran at, so padded / calls is
        the mean rung and 1 - real / padded the pad share.  ``decode_pages_read``
        of ``decode_pages_capacity`` says how far the ``paged_decode`` kernel
        engaged: the pages of K (and as many of V) it fetched a layer, summed
        over ``decode`` calls, against the ``slots x pages_per_slot`` the XLA
        leg gathers; both stay 0 on an engine built with the XLA leg."""
        return {"decode_launches": self.decode_launches, "prefill_launches": self.prefill_launches,
                "prefill_rides": self.prefill_rides, "prefill_reads_ahead": self.prefill_reads_ahead,
                "decode_steps": self.decode_steps, "decode_steps_ahead": self.decode_steps_ahead,
                "logits_bytes_to_host": self.logits_bytes_to_host,
                "prefill_calls": self.prefill_calls,
                "prefill_tokens_real": self.prefill_tokens_real,
                "prefill_tokens_padded": self.prefill_tokens_padded,
                "decode_pages_read": self.decode_pages_read,
                "decode_pages_capacity": self.decode_pages_capacity}

    def decode_multi(self, tokens: np.ndarray) -> np.ndarray:
        """One batched MULTI-token paged step (the speculative-verify /
        suffix-prefill program): for every slot, ``tokens[s, i]``'s K/V
        lands at position ``lengths[s] + i`` and ``logits[s, i]`` predicts
        the token AFTER it.  Width is static — one compiled program per
        distinct W, cached.  Lengths do NOT advance (callers commit only
        the accepted positions via ``cache.advance``); positions past a
        slot's reserved pages write the null page and their logits are
        garbage the host must ignore.  Returns (num_slots, W, vocab)
        fp32."""
        cache = self.cache
        self._launch_waiting()      # (a prompt that waits goes before whatever else touches the cache)
        tokens = np.asarray(tokens, np.int32)
        W = int(tokens.shape[-1])
        tokens = tokens.reshape(cache.num_slots, W)
        fn = self._multi_fns.get(W)
        if fn is None:
            fn = self._multi_fns[W] = self._make_multi(W)
        logits, kd, vd = fn(
            self.params,
            cache.k.data,
            cache.v.data,
            cache.table_array(),
            cache.lengths_array(),
            tokens,
        )
        cache.update(kd, vd)
        return np.asarray(logits)

    def prefill_suffix(self, prompt: Sequence[int], slot: int, matched: int) -> np.ndarray:
        """Prefix-cache hit path: the slot's page table already maps
        cached pages covering ``prompt[:matched]`` (page-aligned, via
        ``alloc_shared``) and the cache length sits at ``matched``
        (``commit_prefill(slot, matched)``); run ONLY the suffix through
        chunked multi-token paged steps, appending its K/V after the
        shared prefix, and return the next-token logits row (vocab,)
        fp32 for the last prompt position."""
        cache = self.cache
        n = len(prompt)
        page = cache.config.page_size
        if not (0 < matched < n):
            raise ValueError(f"matched={matched} must be in (0, {n})")
        if matched % page:
            raise ValueError(f"matched={matched} is not page-aligned (page={page})")
        if int(cache.lengths[slot]) != matched:
            raise ValueError(
                f"slot {slot} length {int(cache.lengths[slot])} != matched {matched} "
                "(commit_prefill the shared prefix first)"
            )
        W = page  # chunk width: one page per multi-step
        out: Optional[np.ndarray] = None
        i = matched
        while i < n:
            chunk = [int(t) for t in prompt[i:i + W]]
            toks = np.zeros((cache.num_slots, W), np.int32)
            toks[slot, : len(chunk)] = chunk
            logits = self.decode_multi(toks)
            for _ in chunk:
                cache.advance(slot)
            out = logits[slot, len(chunk) - 1]
            i += len(chunk)
        return np.asarray(out)


def _as_tree(params: Dict[str, Any]) -> Dict[str, Any]:
    """Accept {"params": tree} bundles (the make_train_step convention) or
    the bare tree."""
    if isinstance(params, dict) and "params" in params and "embed_tokens" not in params:
        return params["params"]
    return params
