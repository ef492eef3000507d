"""What the decoders built from a published configuration file share
(``latent_moe``, ``hybrid_kda``, ``retention_lm``, ``swa_moe``, ``jamba``, ``ssd_moe``):
everything that is not a mixer, as plain functions, and the pieces that two
of them run: the grouped-query layer's (its projections, its paged decode),
the counters of an expert layer of which this chip holds a share, and what
the two decoders with state-space layers have in common (the runs of such
layers between attention layers, the short convolution over a bucket and
over a step with its tail, the prefill scan's count of real positions).  Nothing here knows the serving engine: ``engine/``
imports ``models/``, never the other way.  Matmul inputs are in the model's
``dtype`` with float32 accumulation; RMSNorm, RoPE and the logits float32.
"""

from __future__ import annotations

import json
import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from .. import telemetry
from ..ops import selective_scan as ssm
from ..ops.paged_attention import paged_attention, paged_kv_write

_REG = telemetry.get_registry()
_M_HELD_PAIRS = _REG.histogram(
    "serve_engine_held_pair_share",
    "per decode step and expert layer: (token, expert) pairs of active slots' "
    "tokens whose expert is held here, over all their pairs",
    buckets=(0.01, 0.02, 0.05, 0.1, 0.2, 0.3, 0.5, 0.75, 1.0),
)
_M_HELD_TOUCHED = _REG.histogram(
    "serve_engine_held_experts_touched",
    "per decode step and expert layer: held experts that at least one active "
    "slot's token chose (the expert matrices the step reads)",
    buckets=(1, 2, 4, 8, 16, 24, 32, 40, 48, 56, 64, 128, 256),
)
_M_HELD_PREFILL_LOAD = _REG.histogram(
    "serve_engine_held_prefill_expert_load",
    "per prefill and expert layer: the fullest held expert's tokens over the "
    "mean (prompt tokens x experts a token / the router's experts)",
    buckets=(1.0, 1.25, 1.5, 2.0, 3.0, 4.0, 8.0, 16.0),
)
_M_PREFILL_ROWS_PER_EXPERT = _REG.histogram(
    "serve_moe_prefill_rows_per_expert",
    "per prefill and expert layer: the prompt's (token, expert) pairs whose "
    "expert is here, over the experts here that hold rows: the rows a grouped "
    "matmul multiplies by one expert's matrix",
    buckets=(1, 2, 4, 8, 16, 32, 64, 128, 192, 256, 384, 512, 768, 1024, 2048, 4096),
)
_M_PREFILL_PAIRS = _REG.histogram(
    "serve_moe_prefill_pairs",
    "per prefill and expert layer: the prompt's (token, expert) pairs whose "
    "expert is here (pad tokens not counted): the live rows of the layer's two "
    "grouped matmuls",
    buckets=(64, 256, 1024, 2048, 4096, 8192, 12288, 16384, 24576, 32768, 65536),
)
_M_STATE_LIVE = _REG.histogram(
    "serve_engine_state_live_slots",
    "per decode step: slots holding live recurrent state (the active ones: "
    "the states the model's decode kernel reads and writes, a layer)",
    buckets=(1, 2, 4, 8, 16, 24, 32, 48, 64, 96, 128, 256),
)
_M_SCAN_POSITIONS = _REG.histogram(
    "serve_engine_scan_prefill_positions",
    "per prefill of a model with state-space layers: the prompt's real "
    "positions, the `length` its prefill scan was told (the bucket's padding "
    "past them holds the state still; chunks wholly in it do not run)",
    buckets=(32, 64, 128, 256, 512, 1024, 2048, 4096),
)
_M_PREFILL_ROWS = _REG.counter(
    "serve_prefill_rows_computed_total",
    "bucket positions whose position-wise work a prefill program ran (whole "
    "row tiles up to the prompt's length: over serve_engine_prefill_tokens_total "
    "+ the engine arm's serve_pad_tokens_total, the share of the buckets still paid for)",
)


class SlotCache(NamedTuple):
    """The device cache of a model that keeps both kinds of leaf, one pytree
    in the engine's donated chain (``engine/engine.py``, "What a model offers").

    blocks: the paged pools, block axis first (``model.cache_spec``).
    slots: what a decode SLOT owns, slot axis first (``model.state_spec``).  It
        needs no allocator: the slot's id is its address, a join overwrites
        the row whole, a retire leaves it.
    """

    blocks: Any
    slots: Any


def load_config(config, overrides):
    """A configuration (a dict, or the path of its JSON file) with
    ``overrides`` laid over it (a test's depth, the engine's ``max_len``).
    Returns (the merged dict, the ``dtype`` popped from them: bfloat16 without one)."""
    if not isinstance(config, dict):
        with open(config) as f:
            config = json.load(f)
    dtype = overrides.pop("dtype", jnp.bfloat16)
    return {**config, **overrides}, dtype


def refuse(name: str, refused) -> None:
    """Raise one ``ValueError`` naming every key of ``refused`` whose value is
    true: what the file asks for and the model ``name`` cannot honour."""
    bad = sorted(k for k, v in refused.items() if v)
    if bad:
        raise ValueError(f"{name} does not implement the file's {', '.join(bad)}")


def weight_drawer(key, count: int, dtype):
    """``init``'s random draws: (an iterator over ``jax.random.split(key,
    count)``, ``w``).  ``w`` takes the iterator's next key and draws normal
    with deviation ``scale * fan_in ** -0.5`` in float32, cast to ``dtype``
    (None: the model's); ``init`` takes keys from the iterator itself for a
    bias or a decay.  A tree's values follow from the ORDER of the draws."""
    keys = iter(jax.random.split(key, count))
    model_dtype = dtype

    def w(shape, fan_in, dtype=None, scale=1.0):
        def draw(key, shape):
            x = jax.random.normal(key, shape, jnp.float32) * (scale * fan_in ** -0.5)
            return x.astype(dtype or model_dtype)

        if len(shape) < 3:
            return draw(next(keys), shape)
        # A slice of the leading axis at a time: the float32 draw of a
        # whole stack of experts would not fit beside the weights.
        return jax.lax.map(lambda k: draw(k, shape[1:]),
                           jax.random.split(next(keys), shape[0]))

    return keys, w


def rms_norm(x, scale, eps):
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def rope_half_split(x, pos, theta):
    """Rotary embedding, half-split pairs (i, i + r/2); x [..., r] with
    ``pos`` broadcastable against its leading axes."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.asarray(pos, jnp.float32)[..., None] * freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x = x.astype(jnp.float32)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def yarn_inv_freq(rotated: int, theta: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float):
    """YaRN's table of ``rotated // 2`` inverse frequencies, fixed whatever the
    length: ``theta ** (-2i / rotated)`` where a pair turns more than
    ``beta_fast`` times over the ``original`` positions, that over ``factor``
    where it turns fewer than ``beta_slow`` times, and a linear ramp between
    the two correction dimensions (rounded outwards) in between."""
    def correction_dim(turns):
        return rotated * math.log(original / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), rotated - 1)
    plain = theta ** (-jnp.arange(0, rotated, 2, dtype=jnp.float32) / rotated)
    ramp = jnp.clip((jnp.arange(rotated // 2, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    return plain / factor * ramp + plain * (1.0 - ramp)


def rope_table(x, pos, inv_freq, scale: float = 1.0):
    """:func:`rope_half_split` over the FIRST ``2 len(inv_freq)`` entries of x
    [..., r] at the given table of inverse frequencies, cos and sin times
    ``scale``; the rest of the row passes unrotated.  float32."""
    half = inv_freq.shape[0]
    ang = jnp.asarray(pos, jnp.float32)[..., None] * inv_freq
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    x = x.astype(jnp.float32)
    a, b, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest], axis=-1)


def dot(x, w, dtype):
    return jnp.dot(x.astype(dtype), w, preferred_element_type=jnp.float32)


def head_logits(h, final_norm, head, eps, dtype, tied: bool = False):
    """The final norm and the head [D, V] over h [..., D]: float32 logits
    [..., V].  ``tied``: ``head`` is the embedding table [V, D] and the
    contraction runs over its second axis where it lies (no transpose of it
    is made)."""
    x = rms_norm(h, final_norm, eps)
    if not tied:
        return dot(x, head, dtype)
    return jax.lax.dot_general(x.astype(dtype), head, (((x.ndim - 1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def rows_to_blocks(x, block_size: int, axis: int):
    """A prompt's rows along ``axis`` (its bucket's length Lb) as ``ceil(Lb /
    block_size)`` blocks: the axis padded to whole blocks and split in two."""
    nbw = -(-x.shape[axis] // block_size)
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, nbw * block_size - x.shape[axis])
    return jnp.pad(x, pad).reshape(
        x.shape[:axis] + (nbw, block_size) + x.shape[axis + 1:])


def over_live_rows(fn, xs, live, tile: int, held=()):
    """A row-wise ``fn(xs, *held)`` (``xs`` a pytree of [rows, ...] -> a pytree
    of [rows, ...]: row i of every output follows from row i of every input)
    over the leading-axis tiles of ``xs`` that hold one of the first ``live``
    rows, and over no other: a prefill's position-wise work without its
    bucket's padding.  ``live`` is a traced scalar, 0 < live <= rows, so the
    loop's trip count ``ceil(live / tile)`` is data and the tiles past it are
    never computed; their rows of every output are 0.  Inside the last live
    tile ``fn`` sees padding like any row.  ``held``: small values that do not
    change from tile to tile (a layer's index) and that a barrier ties to the
    tile all the same, so that what ``fn`` makes of them stays inside the
    loop: a layer's weights sliced out of their stacks stay operands of the
    products, where XLA would else slice them once in front of the loop and
    copy them whole.  ``live`` None, or at most one tile of rows: ``fn`` once
    over the whole arrays, no loop and no barrier."""
    rows = jax.tree.leaves(xs)[0].shape[0]
    if live is None or rows <= tile:
        return fn(xs, *held)
    if rows % tile:
        raise ValueError(f"over_live_rows: {rows} rows are not whole tiles of {tile}")

    def tile_of(i):
        return jax.tree.map(lambda x: jax.lax.dynamic_slice_in_dim(x, i * tile, tile), xs)

    def body(i, out):
        ys = fn(*jax.lax.optimization_barrier((tile_of(i), *held)))
        return jax.tree.map(
            lambda o, y: jax.lax.dynamic_update_slice_in_dim(o, y, i * tile, 0), out, ys)

    out = jax.tree.map(lambda s: jnp.zeros((rows,) + s.shape[1:], s.dtype),
                       jax.eval_shape(fn, tile_of(0), *held))
    return jax.lax.fori_loop(0, rows_over_live_tiles(rows, live, tile) // tile, body, out)


def rows_over_live_tiles(rows: int, live, tile: int):
    """The rows :func:`over_live_rows` computes: all where it does not loop,
    else whole tiles up to ``live``."""
    if live is None or rows <= tile:
        return rows
    return (live + tile - 1) // tile * tile


def observe_state_live(live) -> None:
    """A decode step's count of slots holding live state, back on the host
    (a model that keeps a state a slot hands it back in its step counters)."""
    _M_STATE_LIVE.observe(int(live))


def observe_scan_positions(positions) -> None:
    """The ``length`` a prefill's scan was told, back on the host beside the
    prompt's first token (a model with state-space layers hands it back in its
    prefill counters)."""
    _M_SCAN_POSITIONS.observe(int(positions))


def observe_prefill_rows(rows) -> None:
    """The rows a prefill's :func:`over_live_rows` loops computed, back on the
    host (the model hands them back in its prefill counters)."""
    _M_PREFILL_ROWS.inc(int(rows))


def step_bias(key, shape, low: float = 0.001, high: float = 0.1):
    """A softplus'd step's bias as the Mamba lineage's initialiser draws it:
    the inverse softplus of a step log-uniform in ``low`` .. ``high``, float32."""
    step = jnp.exp(jax.random.uniform(key, shape, jnp.float32, jnp.log(low), jnp.log(high)))
    return step + jnp.log(-jnp.expm1(-step))


def runs_between(kinds, marker: str):
    """The lengths of the runs of layers of another kind than ``marker`` in
    ``kinds`` (a list of a layer's kind by depth): before the first ``marker``
    layer, between two, after the last.  Each run's weights are a stack of
    their own under one scan; the ``marker`` layers are a Python loop between
    them."""
    runs = [0]
    for kind in kinds:
        if kind == marker:
            runs.append(0)
        else:
            runs[-1] += 1
    return tuple(runs)


CONV_TAPS = 4  # the state-space layers' short convolution; its tail is the last 3 inputs


def conv_prefill(raw, taps, bias, last):
    """The causal depthwise convolution in front of a state-space scan over a
    whole bucket: raw [T, C] float32 (zeros before the prompt), taps [4, C]
    (tap i weighs the input 3 - i positions back), bias [C].  Returns
    (silu(convolution + bias) [T, C], the TAIL [3, C]: the three inputs up to
    position ``last`` - 1, what a decode step at position ``last`` convolves
    with its own)."""
    T = raw.shape[0]
    raw = jnp.pad(raw, ((CONV_TAPS - 1, 0), (0, 0)))
    u = jax.nn.silu(sum(raw[i:i + T] * taps[i] for i in range(CONV_TAPS)) + bias)
    return u, jax.lax.dynamic_slice_in_dim(raw, last, CONV_TAPS - 1, axis=0)


def conv_step(conv, wide, first: int, taps, bias, layer, active, slots=None):
    """The same convolution for one token a row: conv the tails' leaf [slots,
    layers, 3 C / 128, 128] float32 whole (``ops.selective_scan``: tap t in rows
    ``t C / 128`` onwards); this step's inputs are columns ``first .. first +
    C`` of wide [R, ..] (the in-projection's output, C the taps' width);
    ``layer`` the index into the leaf, ``active`` [R] the rows that step,
    ``slots`` [R] each row's slot (None: row i is slot i).  Returns
    (silu(convolution + bias) [R, C], the leaf with the active rows' tails
    shifted by this input, written back in place by ``conv_tail_write``)."""
    R, C = wide.shape[0], taps.shape[-1]
    # The tails the step needs, in the leaf's own tiles ([.., 3, channels /
    # 128, 128]: nothing of them is laid out anew): the layer's, or with
    # fewer rows than slots the rows' slots' alone.
    if slots is None:
        tail = jax.lax.dynamic_index_in_dim(conv, layer, 1, keepdims=False)
    else:
        tail = conv[slots, layer]
    rows = C // ssm.LANES  # of a tap
    tiles = lambda x, *lead: x.reshape(lead + (rows, ssm.LANES))
    if tail.shape[1] != (CONV_TAPS - 1) * rows:
        # The leaf has spare rows (conv_tail_spec: a tap's rows are not whole
        # (8, 128) tiles, 66 at 8,448 channels), so [R, 3 x rows] -> [R, 3,
        # rows] is no bitcast: the taps stay side by side in one row axis, tap
        # t a slice of it, in front of the spare rows.
        held = (CONV_TAPS - 1) * rows
        window = jnp.concatenate([tail[:, :held], tiles(wide[:, first:first + C], R)], axis=1)
        u = sum(window[:, t * rows:(t + 1) * rows] * tiles(taps[t]) for t in range(CONV_TAPS))
        u = jax.nn.silu(u + tiles(bias)).reshape(R, C)
        return u, ssm.conv_tail_write(conv, tail_rows(window[:, rows:]), layer, active, slots)
    window = jnp.concatenate(  # [R, 4, channels / 128, 128]
        [tiles(tail, R, CONV_TAPS - 1), tiles(wide[:, first:first + C], R, 1)], axis=1)
    u = jax.nn.silu(jnp.sum(window * tiles(taps, CONV_TAPS), axis=1) + tiles(bias))
    u = u.reshape(R, C)
    return u, ssm.conv_tail_write(conv, window[:, 1:].reshape(tail.shape), layer, active, slots)


def conv_tail_spec(slots: int, layers: int, channels: int):
    """The tails' leaf of ``state_spec``: ``[slots, layers, 3 channels / 128,
    128]`` float32, the rows rounded up to whole (8, 128) tiles (198 -> 200 at
    8,448 channels).  Why the spare rows: the chip lays an array out so that
    its tiles hold the least padding, and for 198 rows that puts the SLOTS on
    the sublanes; ``conv_tail_write`` wants a slot's tail contiguous, and the
    compiler then laid the whole leaf out anew on either side of every decode
    step (two copies of 117 MB; ``tests/test_chip_compile.py`` holds it)."""
    rows = (CONV_TAPS - 1) * channels // ssm.LANES
    return jax.ShapeDtypeStruct((slots, layers, -(-rows // 8) * 8, ssm.LANES), jnp.float32)


def tail_rows(tail):
    """Tails [.., 3 channels / 128, 128] (or a prefill's [.., 3, channels]) as
    the rows of :func:`conv_tail_spec`'s leaf: the spare rows are 0."""
    tail = tail.reshape(tail.shape[0], -1, ssm.LANES)
    return jnp.pad(tail, ((0, 0), (0, -tail.shape[1] % 8), (0, 0)))


def write_slot_rows(leaves, rows, slot):
    """A join's ``write_state``: the slot's row of every slot-axis leaf
    becomes the prefill's, whole, whatever the slot's last holder left."""
    return jax.tree.map(
        lambda leaf, new: jax.lax.dynamic_update_index_in_dim(
            leaf, new.astype(leaf.dtype), slot, 0),
        leaves, rows)


# ------------------------------------------------- the grouped-query layer
def gqa_qkv(xn, w_q, w_kv, heads: int, kv_heads: int, head_dim: int, dtype,
            q_norm=None, k_norm=None, eps: float = 1e-6):
    """Normed inputs xn [T, D] through W_q and W_k | W_v (side by side in
    ``w_kv``): q [T, heads, hd], k and v [T, kv_heads, hd], float32.
    ``q_norm`` / ``k_norm`` [hd]: the learned scales of an RMSNorm over each
    head of q and of k (the qwen3 lineage's, in front of the rotation); None:
    no such norm."""
    T = xn.shape[0]
    # The barrier keeps the products as [T, heads x hd]: left to itself XLA
    # folds the attention kernels' head-major reshapes into the dots and
    # transposes W_q and W_kv (84 MB) in every decode step instead.
    q, kv = jax.lax.optimization_barrier((dot(xn, w_q, dtype), dot(xn, w_kv, dtype)))
    k, v = jnp.split(kv.reshape(T, 2 * kv_heads, head_dim), 2, axis=1)
    q = q.reshape(T, heads, head_dim)
    if q_norm is not None:
        q, k = rms_norm(q, q_norm, eps), rms_norm(k, k_norm, eps)
    return q, k, v


def paged_gqa_decode(pool_k, pool_v, q, k, v, paged):
    """One decode step of a layer over paged K/V: this step's k and v [S, Hk,
    hd] written at ``paged.lengths`` (an inactive slot's into the null block),
    then q [S, H, hd] against the pools.  Returns (attention [S, H, hd] in the
    pools' dtype, pool_k, pool_v)."""
    pool_k = paged_kv_write(pool_k, k, paged.block_tables, paged.lengths, paged.active)
    pool_v = paged_kv_write(pool_v, v, paged.block_tables, paged.lengths, paged.active)
    att = paged_attention(q[:, None].astype(pool_k.dtype), pool_k, pool_v,
                          paged.block_tables, paged.lengths, paged.active)[:, 0]
    return att, pool_k, pool_v


def write_pool_blocks(pools, rows, block_ids):
    """A join's ``write_rows``: a prompt's blocks into the pools, leaf by leaf."""
    return jax.tree.map(
        lambda pool, new: pool.at[block_ids].set(new.astype(pool.dtype)), pools, rows)


def write_cache_rows(cache: SlotCache, rows, block_ids) -> SlotCache:
    """``write_rows`` of a model whose cache is a :class:`SlotCache` and whose
    prefill hands back ``{"blocks": ..., "slots": ...}``."""
    return cache._replace(blocks=write_pool_blocks(cache.blocks, rows["blocks"], block_ids))


def write_cache_state(cache: SlotCache, rows, slot) -> SlotCache:
    """``write_state`` of such a model: the join's other half."""
    return cache._replace(slots=write_slot_rows(cache.slots, rows["slots"], slot))


# --------------------------------------- an expert layer's share, counted
def held_experts(params):
    """The held experts' matrices, stacked over the expert layers, as
    ``dropless_moe`` takes them beside a layer's own weights."""
    return {k: params[k] for k in ("experts_gu", "experts_down")}


def held_step_counters(load):
    """From tokens a held expert by layer ``load`` [L, G]: by layer the held
    (token, expert) pairs, then the held experts touched, int32 [2 L]."""
    return jnp.concatenate([jnp.sum(load, axis=-1, dtype=jnp.int32),
                            jnp.sum(load > 0, axis=-1, dtype=jnp.int32)])


def observe_held_step(counters, live: int, top_k: int) -> None:
    """:func:`held_step_counters`, back on the host, of a step with ``live``
    active slots."""
    pairs, touched = counters[:len(counters) // 2], counters[len(counters) // 2:]
    for n_pairs, n_touched in zip(pairs, touched):
        _M_HELD_PAIRS.observe(int(n_pairs) / max(1, live * top_k))
        _M_HELD_TOUCHED.observe(int(n_touched))


def observe_prefill_rows_per_expert(pairs, touched) -> None:
    """By expert layer of one prefill: its (token, expert) pairs here, and
    those over the experts here that hold rows: the rows a grouped matmul
    multiplies by one expert's matrix (which side of the kernel's ridge the
    call ran on)."""
    for n_pairs, n_touched in zip(pairs, touched):
        _M_PREFILL_PAIRS.observe(int(n_pairs))
        if n_touched:
            _M_PREFILL_ROWS_PER_EXPERT.observe(int(n_pairs) / int(n_touched))


def observe_held_prefill(counters, prompt_len: int, top_k: int, router_experts: int) -> None:
    """The fullest held expert's tokens by layer, over the mean."""
    mean = prompt_len * top_k / router_experts
    for fullest in counters:
        _M_HELD_PREFILL_LOAD.observe(float(fullest) / mean)
