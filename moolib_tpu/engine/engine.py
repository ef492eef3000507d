"""Continuous-batching decode engine over a paged KV block pool.

The batch-synchronous baseline (``serving.ServeService`` + a jitted
``generate``) decodes every request in a batch until the LONGEST one
finishes, in a dense per-sequence cache sized for the worst case.  This
engine removes both wastes:

- **Slots, not batches.**  Decode is a fixed-shape jitted call over ``S``
  slots.  A sequence joins a free slot the moment its prefill lands and
  retires the moment it emits EOS or exhausts its token budget — no convoy
  behind a long neighbor.  Slot occupancy, lengths, and block tables are
  jit *arguments* updated by donated in-place ops, so join/retire causes
  no recompile and no device cache reshuffle.
- **Blocks, not max_len rows.**  K/V live in a shared device pool of
  fixed-size token blocks (``ops.paged_attention``); a sequence holds only
  the blocks its length needs (``engine.kv_pool.BlockPool`` free list).

Prefill is a separate shape-bucketed jitted path (``serving.bucket`` — the
canonical bucketing policy) over the full prompt, reusing the model's own
``collect_kv`` teacher-forced forward; its K/V rows scatter straight into
pool blocks.

**One decode step ahead.**  The step samples on the device and feeds its
own outputs (tokens, lengths, active flags, budgets) to the next call as
donated arrays, so nothing in it waits for the host.  ``step()`` therefore
dispatches step N+1 BEFORE it waits for step N's tokens: the host's work
between two steps (the fetch's wake-up, replies, the next jit call) runs
under the chip's step instead of beside it.  What the host reads of a step
is its **packet**, a small un-donated ``[3, S]`` int32 output (token
emitted, was the slot active before the step, is it done) whose copy to
the host starts right after the dispatch.  At most one step is in flight
between two calls.  The host's mirrors decide whether a step ahead is
worth dispatching (a slot whose budget the step in flight exhausts is not
counted); a finish by ``eos_id`` cannot be foreseen, so a step may be in
flight in which a slot, or every slot, is already inactive on the device:
it writes to the null block, emits nothing, and its packet says so (the
host books a step from the packet, never from its mirrors).  A join
dispatched while a step is in flight lands behind it in the donated chain;
a slot freed at the fetch of step N is inactive in step N+1, so its blocks
can be handed to a new request at once.

**The rows of a step are the occupied slots', in tiles of 128.**  A product
of ``[S, d]`` activations with a weight matrix is bound by reading the weights
only while ``S`` stays under the chip's ridge (240 operations a byte on a v5e:
at 256 rows the MXU takes as long as the read), and a slot nobody holds is a
row of work nobody asked for.  So the step is compiled once for every ROW
COUNT ``R``: the multiples of 128 below ``S``, and ``S`` itself.  An engine
of at most 128 slots has one row count, and its one program is the step over
every slot, with no gather in it; so has an engine whose model does not say
``decodes_rows`` (below).  Each dispatch picks the smallest ``R`` that holds
the slots the host's mirrors expect the step to advance.  **The invariant
that makes this safe: at every dispatch the mirrors' set is a SUPERSET of
the device's ``active`` at that step.**  A slot leaves the device's set at
the end of the step that finishes it and the mirrors' set only when that
step's packet is booked; a join lights the mirror in ``submit``, before its
jit call is made; a finish by EOS, and a first token that is EOS, are seen
late by the host alone.  In the ``R``-row program the rows are chosen ON THE
DEVICE, ``argsort(~active, stable)[:R]``: the active slots in slot order,
then DISTINCT inactive ones (a fill that named one slot twice would let a
padded row's write-back race a live row's).  Tokens, lengths, flags and table
rows are gathered by it (a few KB), the model decodes ``R`` rows, and the
next tokens are scattered back to ``[S]``; the packet, the lengths, the
budgets and the flags stay ``[S]``, the host sends no slot list and books a
step as before.  Should the invariant ever fail, the active slots past the
first ``R`` simply do not step (their flags, lengths and budgets stand, the
packet says they were not active): a token late, none wrong; and the packet
carries the fact home, ``stats()["row_overflows"]`` and
``serve_engine_row_overflows_total``, which must read 0.
``serve_engine_decode_rows`` and ``stats()["steps_by_rows"]`` say how often
each row count ran.

**An admission the host does not wait for.**  ``submit`` dispatches the
prefill and the join back to back and returns: the join takes the first
token from the prefill's DEVICE output, so the device's queue reads step
N+1, prefill, join and, as soon as the loop reaches ``step()``, step N+2,
with nothing between them (two admissions in one pass queue four programs).
The first token (with the model's prefill counters in the same vector)
starts its copy to the host at the prefill's dispatch and is read where the
wait costs the device nothing: in the next ``step()``, after step N+2 has
been dispatched behind the join and step N+1's packet is home, before that
packet is booked (so a slot's first token always precedes its first decode
token).  Until then the slot's ``emitted`` list is empty.  What the host
cannot know before the token is home the device decides: with ``eos_id``
the join lights the slot only if the token is not EOS; the host learns it
at the read and reports the slot in that ``step()``'s ``finished``.  A
budget of 1 needs no slot, is known before the prefill, and is the one
admission that waits: it is answered at once.  ``joins_ahead`` counts the
joins dispatched without a wait, beside ``joins``.

**An admission that rides a step.**  A prefill of a few hundred rows and a
decode step of a few dozen are both bound by reading the weights (float32
weights multiplied in bfloat16 keep a v5e's MXU under the read up to some 480
rows), so a prefill in a program of its own is a second reading of every
matrix that the step behind it repeats.  Where the model offers
``decode_with_prompt`` (below), ``submit`` takes the slot and the blocks and
lights the mirrors as above but launches nothing: it RECORDS the admission,
and the next dispatch is ``engine_admit_step`` in place of ``engine_decode``:
the step over the slots active on the device and the prompt's forward in the
same products (the rows laid end to end, attention alone apart), then the
join's writes, in one donated chain with one packet.  The device's queue reads
step N+1, admit-step N+2, step N+3.  The new slot does not decode in the
program that carries it (its first decode token hangs on the prompt's argmax):
the step is dispatched with the slot NOT among the slots the mirrors expect it
to advance, so the invariant above holds as for a join, and the slot is active
from the next step on.  Its first token is the packet's token row at the slot,
booked when that packet is booked, before any decode token of the slot: no
copy and no wait of its own, and one ``step()`` later than programs of its own
would have had it.  A step over no active slot carries an admission as well as
any, and a budget of 1 rides like any other (it holds its slot for that one
step: the program leaves it dark, the booking reports it finished): such a
model's admissions take no other program, and warm-up builds
``engine_prefill`` and ``engine_join`` for none of its buckets.  A prompt's
bucket is then at least ``_PROMPT_TILE`` rows.  **One admission a dispatch,
and no first token later than that one ``step()``:** a second ``submit``
before the next dispatch sends the recorded admission's step out at once,
behind those in flight (it is a decode step like any other: nothing in it is
work the slots would not have had), and is recorded in its turn; ``step()``
then books every step in flight but the newest, so of k admissions in one
pass the first k - 1 have their tokens in the ``step()`` that follows, where
their own prefills' tokens would have been read, and the last in the one
after.  The mirrors count every step in flight (``_expected``).  A ``retire``
ahead of the booking still returns the token: an admission recorded and not
yet carried goes out at once, and the packet of the step that carries it is
read there.  ``serve_engine_admissions_total{path}`` and
``stats()["admissions_by_path"]`` count the joins by ``step`` and ``own``, at
their dispatch: all of the one kind under one model.

**What a model offers.**  The engine knows no architecture.  It takes a
``models.TransformerLM`` (wrapped by ``models.transformer.PagedTransformerLM``)
or any object with ``max_len`` (the positions it can address) and one of the
three shapes a model's memory can take, decided by what it offers:

- **paged** (``cache_spec`` alone): ``cache_spec(num_blocks, block_size)`` is a
  pytree of shapes, the block axis first in every leaf: the paged pools are
  allocated from it (per-head K and V, or one latent row a token a layer: the
  layout is the model's), a sequence holds the blocks its length needs, and
  admission is by free slots AND free blocks;
- **paged with a state a slot** (``cache_spec`` and ``state_spec``):
  ``state_spec(slots)`` is a pytree of shapes, the SLOT axis first in every
  leaf: what a sequence holds whatever its length (a linear attention's
  recurrent state, a convolution's tail) beside the rows that grow.  The
  engine allocates it beside the pools and the model's cache is
  ``decoder_parts.SlotCache(blocks, slots)``, one pytree in the donated chain;
- **a state a slot alone** (``state_spec`` and no ``cache_spec``): every layer
  keeps a fixed state and nothing grows.  There is no ``BlockPool``, no block
  table among the step's or the join's arguments and no ``write_rows`` call;
  the model's cache is the ``state_spec`` pytree itself; admission is by a
  free slot alone, ``submit`` allocates a slot and nothing else, ``retire``
  frees nothing, and ``max_len`` bounds positions (what the rotary embedding
  can address), not memory.  ``block_size`` and ``num_blocks`` are unread.
  How many sequences a chip serves is then set by the state's bytes a slot
  (``serve_engine_state_bytes``), not by context length.

and, for whichever leaves it has:

- ``prefill(params, toks [1, Lb], tp, block_size)`` -> (the prompt's cache
  rows as ``ceil(Lb / block_size)`` blocks, with the state after position
  ``tp - 1`` where the model keeps one, in whatever pytree the model's
  ``write_rows`` and ``write_state`` take; logits [V] at ``tp - 1``; int32
  counters or None).  ``tp`` is data: one program a bucket, and a model may
  skip its bucket's padding inside it and say in its counters what it still
  computed (``models/retention_lm.py``);
- with ``cache_spec``, ``write_rows(cache, rows, block_ids)`` -> cache: a
  join's scatter of those blocks into the pools (few, stacked arrays keep a
  join's jit call cheap: a row pytree of one array a layer cost ``submit``
  1.3 ms);
- with ``state_spec``, ``write_state(cache, rows, slot)`` -> cache: the same
  join overwrites the slot's row of every slot-axis leaf with the prefill's,
  whole, so nothing of the slot's last holder is ever read.  It runs in the
  join's jit, in the same donated chain as ``write_rows``: a join dispatched
  behind a step in flight lands behind it.  A retire does no device work;
- ``decode(params, cache, tokens [R], paged)`` -> (logits [R, V], cache,
  int32 counters or None), with ``step_counters`` /
  ``prefill_counters`` their lengths.  ``paged`` is a ``PagedState`` of ``R``
  rows whose ``block_tables`` is None without pools (``lengths`` are the
  positions, ``active`` the rows that step) and whose ``slots`` is the slot
  of each row, or None: row i is slot i, ``R`` is ``S``.  That is all a model
  ever sees unless its class says ``decodes_rows = True`` (beside
  ``step_counters``): only such a model is handed fewer rows than slots.
  Pool leaves need nothing for it, since a row's block table stands between
  it and its K/V; a slot-axis leaf is read and written at ``paged.slots``.
  Slot-axis leaves advance for the slots of the rows ``paged.active`` names
  and for no other: a step dispatched ahead may find a slot inactive, and a
  freed slot's row must stay whatever it is until the next join replaces it.

and, optionally, where the model holds pools alone and reports no counters:

- ``decode_with_prompt(params, cache, tokens [R], paged, toks [1, Lb], tp,
  block_size)`` -> (logits [R, V] of the decode rows, logits [V] of the prompt
  at ``tp - 1``, the cache with this step's K/V written, the prompt's rows as
  ``prefill`` hands them to ``write_rows``): ``decode`` and ``prefill`` of one
  prompt in ONE pass over ``R + Lb`` rows, every weight matrix the operand of
  one product.  What it returns must be what the two return run apart (to
  rounding: a product over more rows may round otherwise), for rows active
  or not.  A model that offers it has its admissions carried by decode steps
  (above); the engine asks nothing else of it, and no model's name;
- ``prompt_attention_kernel(bucket)`` -> a name: the kernel that the rows of a
  prompt padded to ``bucket`` attend through.  The host counts each prompt's
  real tokens under it (``serve_prompt_attention_rows_total{kernel}``), so a
  run can say what share of its prompts' rows took which.

Counters ride what the host fetches anyway (extra rows of a step's packet,
extra entries beside a prefill's first token): no copy is added.  What they
mean is the model's: it receives them back through ``observe_step(counters)``
and ``observe_prefill(counters, prompt_len)``.

Greedy decoding only (temperature sampling would need per-slot rng lanes;
the serving plane is argmax today, matching ``lm_serve``).
"""

from __future__ import annotations

import contextlib
import itertools
import time
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .. import telemetry, utils
from ..telemetry import devmon
from ..models.decoder_parts import SlotCache
from ..models.transformer import PagedTransformerLM, TransformerLM
from ..ops.paged_attention import PagedState
# _M_PAD_TOKENS: the batch-synchronous arm's counter; both arms feed one series.
from ..serving import _M_PAD_TOKENS, _M_PHASE, bucket, bucket_shapes
from .kv_pool import BlockPool, PoolExhausted

_REG = telemetry.get_registry()
_M_TOKENS = _REG.counter(
    "serve_engine_tokens_total", "tokens emitted by engine decode steps"
)
_M_PREFILL_TOKENS = _REG.counter(
    "serve_engine_prefill_tokens_total", "prompt tokens prefilled (unpadded)"
)
_M_JOINS = _REG.counter(
    "serve_engine_joins_total", "sequences joined to a decode slot"
)
_M_JOINS_AHEAD = _REG.counter(
    "serve_engine_joins_ahead_total",
    "joins dispatched behind their prefill without a device wait: the first "
    "token is read in the next step(), with a step queued behind the join "
    "(over the joins: the share of admissions that the host did not wait for)",
)
_M_ADMISSIONS = _REG.counter(
    "serve_engine_admissions_total",
    "joins by the programs that carried them: path=step rode a decode step "
    "(engine_admit_step: the prompt's rows beside the occupied slots' in one "
    "pass over the weights), path=own took engine_prefill and engine_join",
    labelnames=("path",),
)
_M_PROMPT_ATTN_ROWS = _REG.counter(
    "serve_prompt_attention_rows_total",
    "prompt tokens prefilled (unpadded), by the kernel their bucket's rows "
    "attend through as the model names it (``prompt_attention_kernel``: "
    "kernel=flash the blockwise kernel, which writes no scores and does no "
    "work for the padding; kernel=dense scores of bucket x bucket a head); "
    "counted at the dispatch, where the model offers the name",
    labelnames=("kernel",),
)
_M_RETIRES = _REG.counter(
    "serve_engine_retires_total", "sequences retired (EOS or budget)"
)
_M_STEPS_AHEAD = _REG.counter(
    "serve_engine_steps_ahead_total",
    "decode steps dispatched while the previous step was still unfetched "
    "(over the steps: the share of the loop that runs one step ahead)",
)
_M_EMPTY_STEPS = _REG.counter(
    "serve_engine_empty_steps_total",
    "decode steps booked whose packet had no active slot: dispatched ahead "
    "of a finish by EOS that the host could not foresee",
)
_M_DECODE_ROWS = _REG.histogram(
    "serve_engine_decode_rows",
    "per decode step dispatched: the rows of the program chosen, the smallest "
    "compiled row count (multiples of 128, then the slots) that holds the "
    "slots the host's mirrors expect the step to advance",
    buckets=(128, 256, 384, 512, 768, 1024),
)
_M_ROW_OVERFLOWS = _REG.counter(
    "serve_engine_row_overflows_total",
    "decode steps that found more active slots on the device than the rows "
    "they were compiled for: the mirrors were no superset of the device's "
    "set.  Must read 0",
)
_M_SLOTS = _REG.gauge(
    "serve_engine_slots_active", "decode slots currently occupied"
)
_M_OCC = _REG.gauge(
    "serve_engine_slot_occupancy", "occupied fraction of decode slots (0..1)"
)
_M_BLOCKS_FREE = _REG.gauge(
    "serve_engine_blocks_free", "KV pool blocks on the free list"
)
_M_STATE_BYTES = _REG.gauge(
    "serve_engine_state_bytes",
    "bytes of the slot-axis leaves the engine holds (a model's ``state_spec``, "
    "every slot's row): what a sequence costs whatever its length",
)
_M_ROWS_LIVE = _REG.histogram(
    "serve_engine_live_row_share",
    "per decode step: cache positions the step attends over (active slots "
    "only) over slots x positions a slot: the rows the attention kernel "
    "reads, whatever a row holds",
    buckets=(0.01, 0.02, 0.05, 0.1, 0.2, 0.3, 0.5, 0.75, 1.0),
)
_M_KV_LIVE = _REG.histogram(
    "serve_engine_kv_live_share",
    "per decode step: KV blocks holding a position the step attends over "
    "(active slots only), over slots x max_blocks_per_seq — the share of the "
    "capacity that the paged attention kernel reads",
    buckets=(0.01, 0.02, 0.05, 0.1, 0.2, 0.3, 0.5, 0.75, 1.0),
)


_ROWS_AHEAD_BYTES = 1 << 30  # a state's rows that joins dispatched ahead may hold
_ROW_TILE = 128  # a decode step's rows come in tiles of the MXU's 128 (module docstring)
# A prompt carried by a step brings at least a bfloat16 tile's 16 rows: fewer
# are padded to it by the product anyway, and a bucket is a program of every
# layer to build at warm-up.
_PROMPT_TILE = 16


class NoFreeSlot(RuntimeError):
    """Every decode slot is occupied — the request should stay queued."""


class _Admission(NamedTuple):
    """A join ``submit`` recorded for the next dispatch to carry: what the
    program needs of it, as the host holds it."""

    slot: int
    toks: np.ndarray  # [1, Lb], the prompt padded to its bucket
    tp: int
    rem0: int  # the budget past the first token
    row: np.ndarray  # the slot's row of the block table
    written: np.ndarray  # the blocks the prompt's rows go to


class ContinuousBatchingEngine:
    """See module docstring.  Host-side driver owning the device state
    (KV pools, block tables, per-slot lengths/tokens/budgets) and the four
    jitted paths: bucketed prefill, donated join, fixed-shape decode step (one
    program a row count), and the decode step that carries an admission (one a
    row count and a prompt bucket).

    Single-threaded by contract: one loop (``EngineService``) calls
    ``submit``/``step``/``retire``; only ``set_params`` and the read-only
    stats are safe from other threads.
    """

    def __init__(self, model, params, *, slots: int = 8,
                 block_size: int = 16, num_blocks: Optional[int] = None,
                 max_seq_len: Optional[int] = None,
                 max_prompt_len: Optional[int] = None,
                 min_prompt_len: int = 1,
                 eos_id: Optional[int] = None):
        if isinstance(model, TransformerLM):
            model = PagedTransformerLM(model)
        self.model = model
        self.slots = int(slots)
        if self.slots < 1:
            raise ValueError("need at least one decode slot")
        self.block_size = int(block_size)
        self.seq_capacity = int(max_seq_len or model.max_len)
        if self.seq_capacity > model.max_len:
            raise ValueError(
                f"max_seq_len={self.seq_capacity} exceeds the model's "
                f"max_len={model.max_len} (learned-pos table / rotary cap)"
            )
        self.max_blocks_per_seq = -(-self.seq_capacity // self.block_size)
        # What the model's memory is made of (module docstring): pools of
        # blocks, a state a slot, or both.
        self._slot_state = hasattr(model, "state_spec")
        self.pool: Optional[BlockPool] = None
        if hasattr(model, "cache_spec"):
            if num_blocks is None:
                # Worst case: every slot at full capacity, plus the null block.
                num_blocks = 1 + self.slots * self.max_blocks_per_seq
            self.pool = BlockPool(num_blocks, self.block_size)
        self.max_prompt_len = int(max_prompt_len or self.seq_capacity)
        # The shortest prompt the traffic sends: warm-up compiles no prefill
        # bucket below its bucket (a shorter prompt still runs, in that one).
        self.min_prompt_len = max(1, min(int(min_prompt_len), self.max_prompt_len))
        self.eos_id = eos_id
        self._n_step_counters = getattr(model, "step_counters", 0)
        self._n_prefill_counters = getattr(model, "prefill_counters", 0)
        # The row counts the decode step is compiled for, ascending (module
        # docstring): one, the slots, unless the model decodes rows and there
        # are more slots than a tile.
        self._row_counts: Tuple[int, ...] = (self.slots,)
        if getattr(model, "decodes_rows", False):
            self._row_counts = tuple(range(_ROW_TILE, self.slots, _ROW_TILE)) + (self.slots,)
        # With more than one, the engine's own counter rides the packet behind
        # the model's: did the step find more active slots than it had rows.
        self._n_packet_counters = self._n_step_counters + (len(self._row_counts) > 1)
        # Does a decode step carry an admission (module docstring).
        self._rides_step = hasattr(model, "decode_with_prompt")
        self._prompt_kernel = getattr(model, "prompt_attention_kernel", None)
        if self._rides_step:
            self.min_prompt_len = max(self.min_prompt_len, min(_PROMPT_TILE, self.max_prompt_len))

        self.set_params(params)

        S, MB = self.slots, self.max_blocks_per_seq
        # The pools, as the model lays them out (block axis first), and
        # where it keeps one the state a slot owns (slot axis first).
        state = model.state_spec(S) if self._slot_state else None
        spec = state
        if self.pool is not None:
            spec = model.cache_spec(num_blocks, self.block_size)
            if self._slot_state:
                spec = SlotCache(spec, state)
        self.state_bytes = sum(
            leaf.size * leaf.dtype.itemsize for leaf in jax.tree.leaves(state))
        # A join's rows live from its prefill's dispatch until the join has
        # run, and an admission waits for neither: where a slot's state is
        # large, joins dispatched ahead without bound would each hold a row of
        # it (24 x 203 MB in a burst that fills every slot).  At most this
        # many prefills are in flight unread, about a GB of rows; the pools'
        # rows are small and are not bounded.
        self._joins_unread_max = max(1, _ROWS_AHEAD_BYTES * S // max(self.state_bytes, 1))
        if self._slot_state:
            _M_STATE_BYTES.set(self.state_bytes)
            utils.log_info(
                "engine: %d slots hold %.3f GB of state, %.1f MB a slot%s", S,
                self.state_bytes / 1e9, self.state_bytes / S / 1e6,
                "" if self.pool is not None else "; no paged pool")
        self._cache = jax.tree.map(lambda leaf: jnp.zeros(leaf.shape, leaf.dtype), spec)
        # Without pools there is no table: None is an empty pytree, so the
        # step's and the join's programs take no such argument.
        self._tables = jnp.zeros((S, MB), jnp.int32) if self.pool is not None else None
        self._lengths = jnp.zeros((S,), jnp.int32)
        self._active = jnp.zeros((S,), jnp.bool_)
        self._tokens = jnp.zeros((S,), jnp.int32)
        self._remaining = jnp.zeros((S,), jnp.int32)

        # Host mirrors (slot bookkeeping never round-trips device state).
        self._free_slots: List[int] = list(range(S - 1, -1, -1))
        self._slot_blocks: List[List[int]] = [[] for _ in range(S)]
        self._emitted: List[List[int]] = [[] for _ in range(S)]
        self._remaining_host = np.zeros(S, np.int64)
        self._lengths_host = np.zeros(S, np.int64)
        self._active_host = np.zeros(S, bool)
        # First tokens not read yet, by slot IN THE ORDER JOINED (a dict keeps
        # insertion order, ``_submit`` only appends and ``_book_first`` only
        # pops: ``_submit``'s backpressure counts back from the newest): the
        # prefill's device output (its copy to the host under way) and the
        # prompt's length.  The next ``step()`` reads them.
        self._first: Dict[int, Tuple[jax.Array, int]] = {}
        # The join the next dispatch will carry, where one is recorded.
        self._admission: Optional[_Admission] = None
        # The steps dispatched and not yet booked, oldest first; of each its
        # packet, the slots the mirrors expect it to advance ([S] bool), which
        # dispatch of its program it was (its ``seq``), and the slot whose
        # admission it carries and whose first token its packet therefore
        # holds, or None.  One between two calls, and as many more as
        # admissions of one pass dispatched behind it (module docstring).
        self._flights: List[Tuple[jax.Array, np.ndarray, int, Optional[int]]] = []
        self._stats = {
            "joins": 0, "joins_ahead": 0, "admissions_by_path": {"step": 0, "own": 0},
            "retires": 0, "decode_tokens": 0,
            "prefill_tokens": 0, "prefill_pad_tokens": 0, "steps": 0,
            "steps_ahead": 0, "empty_steps": 0, "row_overflows": 0,
            "steps_by_rows": {rows: 0 for rows in self._row_counts},
        }

        # The engine's four device programs, each named once (devmon.jit_program:
        # the profiler's program line, ``jit_compiles_total{fn}`` and the
        # ``program`` of the dispatching span are one string).  The decode
        # step must stay ONE compile A ROW COUNT for the engine's lifetime
        # (``rows`` is static; tests assert _cache_size, which forwards
        # through the wrapper); prefill/join legitimately compile per bucket,
        # and the detector's flight events name any trace beyond that.
        self._step_jit = devmon.jit_program(
            self._step_impl, "engine_decode",
            donate_argnums=(1, 2, 3, 4, 5, 6), static_argnums=(7,))
        # Prefill/join jits cache by shape: one trace per prompt bucket
        # (and per block-count bucket for join) — never per request.
        self._prefill_jit = devmon.jit_program(self._prefill_impl, "engine_prefill")
        self._join_jit = devmon.jit_program(
            self._join_impl, "engine_join", donate_argnums=(0, 1, 2, 3, 4, 5))
        # The step that carries an admission: one trace a row count and a
        # prompt bucket (``rows`` static, the bucket the prompt's shape).
        self._admit_jit = devmon.jit_program(
            self._admit_step_impl, "engine_admit_step",
            donate_argnums=(1, 2, 3, 4, 5, 6), static_argnums=(13,))

    def set_params(self, params) -> None:
        """Install new weights (host or device pytree).  Called between
        iterations by the service's hot-swap hook — the KV pools and slot
        state are untouched, so in-flight sequences continue under the new
        weights (same contract as the baseline's mid-stream swap)."""
        self._params = params

    # ------------------------------------------------------------ jit bodies
    def _step_impl(self, params, cache, tables, lengths, active, tokens,
                   remaining, rows):
        return self._step_over(
            lambda cache, row_tokens, paged: self.model.decode(
                params, cache, row_tokens, paged),
            cache, tables, lengths, active, tokens, remaining, rows)

    def _step_over(self, decode, cache, tables, lengths, active, tokens,
                   remaining, rows):
        """The step over ``rows`` rows (static): every slot, or the first
        ``rows`` of the active slots first (module docstring).  ``stepping``
        [S] are the slots this step advances: the active ones, all of them
        unless the mirrors' invariant failed.  ``decode(cache, tokens [R],
        paged)`` is the model's, with whatever else it runs in the pass."""
        S = self.slots
        slot, stepping, row_tokens = None, active, tokens
        paged = PagedState(tables, lengths, active)
        if rows < S:
            slot = jnp.argsort(~active, stable=True)[:rows].astype(jnp.int32)
            row_tokens = tokens[slot]
            paged = PagedState(None if tables is None else tables[slot],
                               lengths[slot], active[slot], slot)
            stepping = jnp.zeros_like(active).at[slot].set(paged.active, unique_indices=True)
        logits, cache, counters = decode(cache, row_tokens, paged)
        act = stepping.astype(jnp.int32)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        nxt = jnp.where(paged.active, nxt, row_tokens)
        if slot is not None:
            nxt = tokens.at[slot].set(nxt, unique_indices=True)
        lengths = lengths + act
        remaining = remaining - act
        done = stepping & (remaining <= 0)
        if self.eos_id is not None:
            done = done | (stepping & (nxt == self.eos_id))
        # What the host reads of this step.  Not donated: ``nxt`` is consumed
        # by the next step, which may be dispatched before the host looks.
        packet = jnp.stack([nxt, act, done.astype(jnp.int32)])
        if self._n_packet_counters:
            # Counters ride the packet, whole rows after the three: the
            # model's, then with several row counts the engine's own.
            counters = (counters.astype(jnp.int32) if self._n_step_counters
                        else jnp.zeros((0,), jnp.int32))
            if len(self._row_counts) > 1:
                overflow = jnp.sum(active, dtype=jnp.int32) > rows
                counters = jnp.concatenate([counters, overflow.astype(jnp.int32)[None]])
            extra = -(-self._n_packet_counters // S)
            counters = jnp.pad(counters, (0, extra * S - self._n_packet_counters))
            packet = jnp.concatenate([packet, counters.reshape(extra, S)])
        active = active & ~done
        return cache, tables, lengths, active, nxt, remaining, packet

    def _admit_step_impl(self, params, cache, tables, lengths, active, tokens,
                         remaining, toks, tp, slot, row, rem0, block_ids, rows):
        """The step over ``rows`` rows AND the prefill and join of one prompt
        (``toks`` [1, Lb], true length ``tp``) in the same products, then
        ``_join_impl``'s writes: one donated chain, one packet.  ``slot`` is
        inactive on entry, so it does not decode here; the packet's token row
        holds its first token at ``slot``."""
        prompt = []

        def decode(cache, row_tokens, paged):
            logits, at_last, cache, kv = self.model.decode_with_prompt(
                params, cache, row_tokens, paged, toks, tp, self.block_size)
            prompt.append((at_last, kv))
            return logits, cache, None

        *state, packet = self._step_over(
            decode, cache, tables, lengths, active, tokens, remaining, rows)
        (at_last, kv), = prompt
        first = jnp.argmax(at_last, axis=-1).astype(jnp.int32)
        cache, tables, lengths, active, tokens, remaining = self._join_impl(
            *state, slot, row, tp, first, rem0, kv, block_ids)
        # A budget of 1 is spent with the first token: the slot stays dark.
        active = active.at[slot].set(active[slot] & (rem0 > 0))
        return (cache, tables, lengths, active, tokens, remaining,
                packet.at[0, slot].set(first))

    def _prefill_impl(self, params, toks, tp):
        """toks [1, Lb] (bucket-padded prompt), tp the true length.  Returns
        the prompt's cache rows in the pools' own layout (``ceil(Lb /
        block_size)`` blocks a leaf) and the first greedy token (argmax of
        the logits at tp-1 — identical to ``generate()``), followed in the
        same vector by the model's prefill counters where it has any."""
        rows, logits, counters = self.model.prefill(
            params, toks, tp, self.block_size)
        tok0 = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        if self._n_prefill_counters:
            tok0 = jnp.concatenate([tok0[None], counters.astype(jnp.int32)])
        return rows, tok0

    def _join_impl(self, cache, tables, lengths, active, tokens, remaining,
                   slot, row, tp, first, rem0, rows, block_ids):
        """Donated in-place join: scatter the prefilled blocks into the
        pools and light the slot.  ``first`` is the prefill's own output (the
        first token, then the model's counters): the host has not seen it.
        ``slot``/``tp``/``rem0`` are traced scalars and ``row``/``block_ids``
        traced vectors — a join never recompiles (one trace per block-count
        bucket; without pools ``row`` and ``block_ids`` are None and there is
        one trace)."""
        tok0 = first.reshape(-1)[0]
        new_cache = cache
        if self.pool is not None:
            new_cache = self.model.write_rows(cache, rows, block_ids)
            tables = jax.lax.dynamic_update_slice(tables, row[None, :], (slot, 0))
        if self._slot_state:
            with jax.named_scope("engine_state_write"):
                new_cache = self.model.write_state(new_cache, rows, slot)
        lengths = lengths.at[slot].set(tp)
        # A first token that is EOS finished the request: the slot stays dark
        # and the host reports it finished when it reads the token.
        active = active.at[slot].set(
            True if self.eos_id is None else tok0 != self.eos_id)
        tokens = tokens.at[slot].set(tok0)
        remaining = remaining.at[slot].set(rem0)
        return new_cache, tables, lengths, active, tokens, remaining

    # --------------------------------------------------------------- serving
    def _bucket(self, prompt_len: int) -> int:
        """The prefill shape of a prompt: ``serving.bucket``'s, and never one
        below the shortest prompt's (warm-up compiled none there)."""
        return max(bucket(prompt_len, self.max_prompt_len),
                   bucket(self.min_prompt_len, self.max_prompt_len))

    def can_accept(self, prompt_len: int, max_new: int) -> bool:
        """A free slot AND, where the model has pools, enough free blocks for
        the worst case of this request (its bucket-padded prompt or its full
        budget).  A request longer than the engine's positions is not held
        back here: ``submit`` refuses it, and it fails alone."""
        if not self._free_slots:
            return False
        if self.pool is None:
            return True
        lb = self._bucket(int(prompt_len))
        need = self.pool.blocks_for(max(lb, int(prompt_len) + int(max_new)))
        return self.pool.available() >= need

    def pending_decode_tokens(self) -> int:
        """Budgeted-but-unemitted tokens across active slots (the admission
        controller's per-token wait estimate numerator)."""
        # mtlint: allow-host-sync(_remaining_host/_active_host are the host-side numpy mirrors, no device value involved)
        return int(self._remaining_host[self._active_host].sum())

    def active_count(self) -> int:
        return int(self._active_host.sum())  # mtlint: allow-host-sync(host-side numpy mirror)

    def submit(self, prompt, max_new: int) -> Tuple[Optional[int], List[int]]:
        """Prefill ``prompt`` (1-D int tokens) and join a decode slot.

        Returns ``(slot, emitted)``.  With a budget of 1, under a model whose
        admissions take programs of their own, ``slot`` is None
        and ``emitted`` holds the one token: the request is answered and
        never occupied a slot.  Otherwise the prefill and the join are
        dispatched, or the admission is recorded for the next step to carry,
        and nothing is waited for: ``emitted`` is the slot's own list, empty
        until a ``step()`` has booked the first token (the next, or the one
        after where a step carries the admission: module docstring); a first
        token equal to ``eos_id`` comes back in that ``step()``'s
        ``finished``.  Raises :class:`NoFreeSlot` / :class:`PoolExhausted`
        when full (the caller keeps the request queued) and ``ValueError``
        for oversized prompts.
        """
        # mtlint: allow-host-sync(host token staging: the prompt arrives as a python/host sequence; the upload happens inside _join_jit)
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        tp = prompt.shape[0]
        max_new = max(1, int(max_new))
        if tp < 1:
            raise ValueError("empty prompt")
        if tp > self.max_prompt_len:
            raise ValueError(
                f"prompt length {tp} exceeds max_prompt_len={self.max_prompt_len}"
            )
        total = tp + max_new
        if total > self.seq_capacity:
            raise ValueError(
                f"prompt + max_new_tokens = {total} exceeds the engine's "
                f"sequence capacity {self.seq_capacity}"
            )
        with telemetry.span("engine.submit"):
            return self._submit(prompt, tp, max_new)

    def _submit(self, prompt, tp: int, max_new: int):
        """``submit`` past its checks, under its span: one child span for
        each of the host's dispatches."""
        lb = self._bucket(tp)
        toks = np.pad(prompt, (0, lb - tp))[None]
        if lb > tp:
            self._stats["prefill_pad_tokens"] += lb - tp
            _M_PAD_TOKENS.inc(lb - tp)
        if max_new == 1 and not self._rides_step:
            # No slot to join, and known before the prefill: the one
            # admission that waits for its token.
            _, first = self._prefill(toks, tp)
            return None, [self._read_first(first, tp, -1)]
        if not self._free_slots:
            raise NoFreeSlot(f"all {self.slots} slots occupied")
        slot = self._free_slots[-1]
        block_ids, row, written = [], None, None
        if self.pool is not None:
            n_alloc = self.pool.blocks_for(max(lb, tp + max_new))
            block_ids = self.pool.alloc(n_alloc)  # PoolExhausted -> stay queued
            row = np.zeros(self.max_blocks_per_seq, np.int32)
            row[:n_alloc] = block_ids
            written = np.asarray(block_ids[:self.pool.blocks_for(lb)], np.int32)  # mtlint: allow-host-sync(block_ids is the pool's host-side free list)
        self._free_slots.pop()
        admission = _Admission(slot, toks, tp, max_new - 1, row, written)
        if self._rides_step:
            # The next dispatch carries it: nothing is launched for it here.
            # A dispatch carries one, so the admission recorded before it in
            # this pass goes out now, in a step behind those in flight.
            if self._admission is not None:
                self._dispatch(self._expected())
            self._admission = admission
        else:
            self._launch_own(admission)
        self._slot_blocks[slot] = block_ids
        emitted = self._emitted[slot] = []
        self._remaining_host[slot] = max_new - 1
        self._lengths_host[slot] = tp
        # By the mirrors the slot is lit; a first token that is EOS left it
        # dark on the device, which the host learns where it reads the token.
        self._active_host[slot] = True
        for _, stepping, _, _ in self._flights:
            # The join lands behind the steps in flight, which saw the slot
            # inactive whatever the mirrors expected of its last occupant.
            stepping[slot] = False
        self._stats["joins"] += 1
        _M_JOINS.inc()
        self._stats["joins_ahead"] += 1
        _M_JOINS_AHEAD.inc()
        self._update_gauges()
        return slot, emitted

    def _prefill(self, toks: np.ndarray, tp: int):
        """Dispatch the prefill of a bucket-padded prompt; its first token's
        copy to the host starts at once.  Returns (rows, first)."""
        with telemetry.span("engine.prefill_dispatch", program=self._prefill_jit.name,
                            seq=self._prefill_jit.seq, bucket=toks.shape[1], tokens=tp):
            rows, first = self._prefill_jit(self._params, toks, np.int32(tp))
            first.copy_to_host_async()
        self._count_prefill(tp, toks.shape[1])
        return rows, first

    def _count_prefill(self, tp: int, lb: int) -> None:
        """A prompt of ``tp`` tokens in a bucket of ``lb`` went to the device."""
        self._stats["prefill_tokens"] += tp
        _M_PREFILL_TOKENS.inc(tp)
        if self._prompt_kernel is not None:
            _M_PROMPT_ATTN_ROWS.inc(tp, kernel=self._prompt_kernel(lb))

    def _launch_own(self, adm: _Admission) -> None:
        """An admission through programs of its own: the prefill and the join
        back to back, its first token left in ``_first`` for the next
        ``step()`` to read."""
        if len(self._first) >= self._joins_unread_max:
            # That many admissions back, the prefill has to be done before one
            # more is queued (its join, right behind it, then frees its rows).
            back = len(self._first) - self._joins_unread_max
            with telemetry.span("engine.join_backpressure"):
                # mtlint: allow-host-sync(backpressure on joins dispatched ahead, where a slot's state is hundreds of MB: bounds the rows alive on the device; never reached with a step() between two admissions)
                next(itertools.islice(self._first.values(), back, None))[0].block_until_ready()
        rows, first = self._prefill(adm.toks, adm.tp)
        launch = dict(program=self._join_jit.name, seq=self._join_jit.seq, slot=adm.slot)
        # Where the model keeps a state a slot, the join's dispatch is
        # also the state's write: a span of its own says what it costs.
        with telemetry.span("engine.join", **launch), (
                telemetry.span("engine.state_write", **launch) if self._slot_state
                else contextlib.nullcontext()):
            (self._cache, self._tables, self._lengths, self._active,
             self._tokens, self._remaining) = self._join_jit(
                self._cache, self._tables, self._lengths, self._active,
                self._tokens, self._remaining,
                np.int32(adm.slot), adm.row, np.int32(adm.tp), first,
                np.int32(adm.rem0), rows, adm.written,
            )
        self._first[adm.slot] = first, adm.tp
        self._count_admission("own")

    def _count_admission(self, path: str) -> None:
        self._stats["admissions_by_path"][path] += 1
        _M_ADMISSIONS.inc(path=path)

    def _read_first(self, first: jax.Array, tp: int, slot: int) -> int:
        """A prefill's first token, on the host; the model's prefill counters
        ride the same vector and are handed back to it here.  ``slot`` is the
        one the request joined, -1 where it joined none (a budget of 1)."""
        with telemetry.span("engine.first_token_fetch", slot=slot):
            # mtlint: allow-host-sync(the prefill's one D2H: its first token, and the model's prefill counters in the same vector; its copy started at the prefill's dispatch, and but for a budget of 1 the next step is already queued behind the join)
            first = np.asarray(first)
        if self._n_prefill_counters:
            self.model.observe_prefill(first[1:], tp)
        return int(first.reshape(-1)[0])

    def _book_first(self, slot: int) -> bool:
        """Put the slot's first token into its ``emitted`` list, if it is
        still unread.  True if the token is EOS: the join left the slot dark,
        the request is finished."""
        pending = self._first.pop(slot, None)
        if pending is None:
            return False
        return self._book_token(slot, self._read_first(*pending, slot))

    def _book_token(self, slot: int, tok0: int) -> bool:
        """``tok0`` is the slot's first token; True if the request is finished
        with it: it is EOS, or the budget was 1 (which only a step carries
        into a slot)."""
        self._emitted[slot].append(tok0)
        if self._remaining_host[slot] > 0 and (self.eos_id is None or tok0 != self.eos_id):
            return False
        self._active_host[slot] = False
        return True

    def step(self) -> Tuple[Dict[int, int], List[int]]:
        """Book the oldest step in flight (dispatching one first where none
        is).  Returns the tokens it emitted (slot -> token) and the slots that
        finished; ``{}, []`` when nothing is active.  The step after it is
        dispatched before this one's tokens are waited for, and stays in
        flight until the next call (module docstring).  Where admissions of
        one pass put further steps in flight, every step but the newest is
        booked: ``finished`` is theirs together, and a slot that emitted in
        several reads its newest token here (its ``emitted`` list has all)."""
        if not self._flights and self._admission is None and not self._active_host.any():
            return {}, []
        with telemetry.span("engine.step"):
            return self._step()

    def _launch(self, rows: int) -> jax.Array:
        """The decode jit's call over ``rows`` rows, and the start of its
        packet's copy to the host.  Returns the packet."""
        (self._cache, self._tables, self._lengths, self._active,
         self._tokens, self._remaining, packet) = self._step_jit(
            self._params, self._cache, self._tables, self._lengths,
            self._active, self._tokens, self._remaining, rows,
        )
        packet.copy_to_host_async()
        return packet

    def _rows_for(self, stepping: np.ndarray) -> int:
        """The smallest compiled row count that holds ``stepping``, the slots
        the mirrors expect the step about to be dispatched to advance.  They
        are a superset of the device's ``active`` at that step (module
        docstring), so the count bounds the device's."""
        if len(self._row_counts) == 1:
            return self.slots
        need = int(stepping.sum())  # mtlint: allow-host-sync(host-side numpy mirror)
        return next(rows for rows in self._row_counts if rows >= need)

    def _launch_admit(self, adm: _Admission, rows: int) -> jax.Array:
        """``_launch`` for the step that carries ``adm``."""
        (self._cache, self._tables, self._lengths, self._active,
         self._tokens, self._remaining, packet) = self._admit_jit(
            self._params, self._cache, self._tables, self._lengths,
            self._active, self._tokens, self._remaining,
            adm.toks, np.int32(adm.tp), np.int32(adm.slot), adm.row,
            np.int32(adm.rem0), adm.written, rows,
        )
        packet.copy_to_host_async()
        return packet

    def _expected(self) -> np.ndarray:
        """The slots a step dispatched now would advance, by the mirrors:
        lit, and still budgeted once every step in flight is counted.  (A
        finish by EOS is not seen.)"""
        pending = sum(stepping.astype(np.int64) for _, stepping, _, _ in self._flights)
        return self._active_host & (self._remaining_host - pending > 0)

    def _dispatch(self, stepping: np.ndarray) -> None:
        """Put a step in flight that the mirrors expect to advance
        ``stepping``; it carries the recorded admission, where there is one,
        whose slot it does not advance."""
        t0 = time.monotonic()
        if self._flights:
            self._stats["steps_ahead"] += 1
            _M_STEPS_AHEAD.inc()
        adm, self._admission = self._admission, None
        jit, name, said = self._step_jit, "engine.step_dispatch", {}
        if adm is not None:
            stepping[adm.slot] = False
            jit, name = self._admit_jit, "engine.admit_step_dispatch"
            said = dict(bucket=adm.toks.shape[1], tokens=adm.tp, slot=adm.slot)
        rows, seq = self._rows_for(stepping), jit.seq
        with telemetry.span(name, program=jit.name, seq=seq, rows=rows, **said):
            packet = self._launch(rows) if adm is None else self._launch_admit(adm, rows)
        self._flights.append((packet, stepping, seq, None if adm is None else adm.slot))
        if adm is not None:
            self._count_admission("step")
            self._count_prefill(adm.tp, adm.toks.shape[1])
        self._stats["steps_by_rows"][rows] += 1
        _M_DECODE_ROWS.observe(rows)
        _M_PHASE.observe(time.monotonic() - t0, phase="dispatch")

    def _step(self):
        """``step`` with a step to book, under its span."""
        if not self._flights:
            self._dispatch(self._expected())
        if self._admission is not None or len(self._flights) == 1:
            # One step ahead of the one about to be booked, and the step that
            # carries what this pass recorded.
            ahead = self._expected()
            if self._admission is not None or ahead.any():
                self._dispatch(ahead)
        emissions: Dict[int, int] = {}
        finished: List[int] = []
        # All but the newest: one, unless admissions of this pass put steps
        # in flight of their own.
        for _ in range(max(1, len(self._flights) - 1)):
            self._book(self._flights.pop(0), emissions, finished)
        return emissions, finished

    def _book(self, flight, emissions: Dict[int, int], finished: List[int]) -> None:
        """Wait for one step's packet and book it into ``emissions`` and
        ``finished``."""
        packet, _, seq, admitted = flight
        t1 = time.monotonic()
        # The decode loop's D2H wait.
        with telemetry.span("engine.decode_fetch", seq=seq):
            # mtlint: allow-host-sync(the decode loop's one intentional D2H: a step's packet of emitted tokens, was-active and done flags must reach the host to answer requests; its copy started at the dispatch and the next step is already queued)
            packet = np.asarray(packet)
        nxt, was_active, done = packet[:3]
        _M_PHASE.observe(time.monotonic() - t1, phase="fetch")
        # The slots joined by programs of their own since the last call: the
        # step just dispatched lies behind their joins, so the wait for a
        # prefill leaves the device busy; no packet booked before this one
        # holds a token of theirs.
        finished += [s for s in list(self._first) if self._book_first(s)]
        if admitted is not None and self._book_token(admitted, int(nxt[admitted])):
            finished.append(admitted)
        with telemetry.span("engine.step_host"):
            stepped = np.nonzero(was_active)[0]
            if self.pool is not None:
                # The step attended over positions <= length in each active slot.
                live = int((self._lengths_host[stepped] // self.block_size + 1).sum())  # mtlint: allow-host-sync(host-side numpy mirror)
                _M_KV_LIVE.observe(live / (self.slots * self.max_blocks_per_seq))
                _M_ROWS_LIVE.observe(
                    int((self._lengths_host[stepped] + 1).sum())  # mtlint: allow-host-sync(host-side numpy mirror)
                    / (self.slots * self.seq_capacity))
            counters = packet[3:].reshape(-1)
            if self._n_step_counters and len(stepped):
                self.model.observe_step(counters[:self._n_step_counters])
            if len(self._row_counts) > 1 and counters[self._n_step_counters]:
                self._stats["row_overflows"] += 1
                _M_ROW_OVERFLOWS.inc()
            self._lengths_host[stepped] += 1
            for s in stepped:
                tok = int(nxt[s])
                emissions[int(s)] = tok
                self._emitted[s].append(tok)
                self._remaining_host[s] -= 1
                if done[s]:
                    finished.append(int(s))
                    self._active_host[s] = False
            self._stats["steps"] += 1
            self._stats["decode_tokens"] += len(stepped)
            _M_TOKENS.inc(len(stepped))
            if not len(stepped) and admitted is None:
                self._stats["empty_steps"] += 1
                _M_EMPTY_STEPS.inc()

    def retire(self, slot: int) -> List[int]:
        """Free the slot and its blocks, where it holds any, and return its
        emitted tokens.  Pure host bookkeeping: the device state was already
        cleared by the step that finished the slot (donated in-place), nothing
        round-trips; a state a slot owns stays where it is until the next join
        overwrites it."""
        # A slot retired before any step() booked its first token: recorded
        # and not yet carried, its step goes out now;
        if self._admission is not None and self._admission.slot == slot:
            self._dispatch(self._expected())
        for i, (packet, stepping, seq, admitted) in enumerate(self._flights):
            if admitted == slot:
                # carried by a step in flight, whose packet holds the token
                # (and is booked later without it);
                # mtlint: allow-host-sync(a caller that retires a slot ahead of the step() that would book it: outside the decode loop)
                self._book_token(slot, int(np.asarray(packet)[0, slot]))
                self._flights[i] = packet, stepping, seq, None
        self._book_first(slot)  # or joined by programs of its own and unread.
        toks = self._emitted[slot]
        if self.pool is not None:
            self.pool.free(self._slot_blocks[slot])
        self._slot_blocks[slot] = []
        self._emitted[slot] = []
        self._remaining_host[slot] = 0
        self._free_slots.append(slot)
        self._stats["retires"] += 1
        _M_RETIRES.inc()
        self._update_gauges()
        return toks

    def _update_gauges(self) -> None:
        n = int(self._active_host.sum())  # mtlint: allow-host-sync(host-side numpy mirror)
        _M_SLOTS.set(n)
        _M_OCC.set(n / self.slots)
        if self.pool is not None:
            _M_BLOCKS_FREE.set(self.pool.available())

    # ---------------------------------------------------------------- warmup
    def warmup(self) -> int:
        """Compile every shape serving can hit: the decode step at every row
        count, one prefill
        per prompt bucket, one join per block-count bucket (one join in all
        without pools: a state's shape does not follow the prompt's), and
        where admissions ride steps that step at every bucket and row count.  Warmup
        joins target the null block with a zero budget, so the single decode
        step that follows retires them without touching real state (slot 0's
        row of a state is overwritten: the next join replaces it whole).
        Returns the number of distinct compiled shapes."""
        shapes = 0
        seen_nbw = set()
        buckets = sorted({self._bucket(b) for b in bucket_shapes(self.max_prompt_len)})
        if self._rides_step:
            # The step that carries an admission, a bucket and a row count: a
            # join of the same kind as below (slot 0, the null block, a zero
            # budget).  No admission of such a model takes another program.
            for lb, rows in itertools.product(buckets, self._row_counts):
                self._launch_admit(_Admission(
                    0, np.zeros((1, lb), np.int32), lb, 0,
                    np.zeros(self.max_blocks_per_seq, np.int32),
                    np.zeros(self.pool.blocks_for(lb), np.int32)), rows)
                shapes += 1
        for lb in ([] if self._rides_step else buckets):
            rows, first = self._prefill_jit(
                self._params, np.zeros((1, lb), np.int32), np.int32(lb))
            shapes += 1
            nbw = self.pool.blocks_for(lb) if self.pool is not None else None
            if nbw in seen_nbw:
                continue
            seen_nbw.add(nbw)
            row = None if nbw is None else np.zeros(self.max_blocks_per_seq, np.int32)
            (self._cache, self._tables, self._lengths, self._active,
             self._tokens, self._remaining) = self._join_jit(
                self._cache, self._tables, self._lengths, self._active,
                self._tokens, self._remaining,
                np.int32(0), row, np.int32(0), first, np.int32(0),
                rows, None if nbw is None else np.zeros(nbw, np.int32),
            )
            shapes += 1
        # One real step a row count compiles the decode path; the first clears
        # the warmup joins (zero budget -> done immediately; writes landed in
        # the null block).  Each is launched and fetched as the loop does it,
        # booked nowhere, and leaves no step in flight.
        for rows in self._row_counts:
            np.asarray(self._launch(rows))  # mtlint: allow-host-sync(warm-up, outside the decode loop: the packet's first D2H)
        return shapes + len(self._row_counts)

    def close(self) -> None:
        """Drop the step in flight, if any, unbooked: its tokens are never
        emitted, so the sequences in the slots cannot continue.  For a
        service on its way out, before it fails what is in its slots."""
        self._flights, self._admission = [], None

    # ----------------------------------------------------------------- stats
    def stats(self) -> Dict[str, Any]:
        out = dict(self._stats, steps_by_rows=dict(self._stats["steps_by_rows"]),
                   admissions_by_path=dict(self._stats["admissions_by_path"]))
        if self.pool is not None:
            out.update(self.pool.stats())
        if self._slot_state:
            out["state_bytes"] = self.state_bytes
        out["slots"] = self.slots
        out["slots_active"] = self.active_count()
        out["slot_occupancy"] = self.active_count() / self.slots
        return out
