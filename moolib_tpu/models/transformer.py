"""Causal Transformer LM with pluggable attention: dense / pallas flash /
ring (sequence-parallel over a mesh axis).

Long-context model surface for the framework's SP capability (the reference
has no attention models at all, SURVEY.md §5.7). Attention selection:

- ``attention="dense"`` — XLA dense (small T, debugging);
- ``attention="flash"`` — pallas blockwise kernel; per chip, batch-split
  over ``dp`` when a mesh is passed at apply time;
- ``attention="ring"`` — ring attention over the ``sp`` axis of a mesh
  passed at apply time (``model.apply(params, tokens, mesh=mesh)``), for
  sequences longer than one chip's HBM.

Sparse capacity via ``moe_num_experts > 0``: every ``moe_every``-th block
swaps its dense FFN for a :class:`..parallel.moe.SwitchMoE` whose expert
weights shard over an ``ep`` mesh axis (``parallel.moe_shardings``).  The
router's load-balancing aux losses are sowed into the ``losses`` collection:
``logits, col = model.apply(params, tokens, mutable=["losses"])``.

bfloat16 compute, f32 params/logits; pre-LN blocks.
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from .decoder_parts import rows_to_blocks


def apply_rotary(x: jax.Array, base: float = 10000.0, offset=0) -> jax.Array:
    """Rotary position embedding (RoPE, Su et al. 2021) on [B, T, H, D].

    Rotates feature pairs by position-proportional angles so attention scores
    depend on *relative* offsets — the standard long-context choice (no
    learned table capping the usable length, graceful extrapolation).
    Computed in float32 and cast back (bf16 angles visibly distort long-range
    phases).  ``offset`` shifts the positions (the cache index during
    autoregressive decoding); it may be a traced scalar, or a traced [B]
    vector when each row sits at its own position (continuous-batching decode
    slots).  The scalar and vector paths compute identical angles for equal
    offsets, so they are bit-exact against each other.
    """
    B, T, H, D = x.shape
    half = D // 2
    if D % 2:
        raise ValueError(f"rotary needs an even head dim, got {D}")
    freqs = base ** (-jnp.arange(0, half, dtype=jnp.float32) / half)  # [half]
    off = jnp.asarray(offset)
    if off.ndim == 0:
        positions = off + jnp.arange(T, dtype=jnp.float32)
        angles = positions[:, None] * freqs[None, :]  # [T, half]
        cos = jnp.cos(angles)[None, :, None, :]
        sin = jnp.sin(angles)[None, :, None, :]
    else:
        positions = off[:, None] + jnp.arange(T, dtype=jnp.float32)[None, :]
        angles = positions[..., None] * freqs  # [B, T, half]
        cos = jnp.cos(angles)[:, :, None, :]
        sin = jnp.sin(angles)[:, :, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


REMAT_POLICIES = ("full", "dots", "dots_no_batch")


def _remat_policy(name: str):
    """Resolve a TransformerLM.remat_policy name to a jax.checkpoint policy
    (None = save nothing, jax.checkpoint's default)."""
    if name not in REMAT_POLICIES:
        raise ValueError(
            f"remat_policy must be one of {'|'.join(REMAT_POLICIES)}, got {name!r}"
        )
    if name == "full":
        return None
    return {
        "dots": jax.checkpoint_policies.checkpoint_dots,
        "dots_no_batch": jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
    }[name]


class Block(nn.Module):
    d_model: int
    num_heads: int
    attention: str
    dtype: Any
    moe_num_experts: int = 0  # 0 = dense FFN; >0 = SwitchMoE FFN (EP-shardable)
    moe_capacity_factor: float = 1.25
    rotary: bool = False
    decode: bool = False  # single-token steps against a KV cache (generation)
    max_len: int = 8192  # cache capacity in decode mode
    collect_kv: bool = False  # sow K/V into a "kv" collection (prefill)
    num_kv_heads: Optional[int] = None  # GQA: KV heads < query heads
    # Paged KV cache (continuous-batching decode; see ops.paged_attention):
    # >0 switches the decode cache from dense [B, max_len, Hk, hd] to a
    # shared block pool [kv_num_blocks, kv_block_size, Hk, hd] addressed via
    # the PagedState passed at apply time.
    kv_num_blocks: int = 0
    kv_block_size: int = 16
    # Paged decode with ONE prompt in the same pass (the engine's admission
    # that rides a step): x is [R + prompt_rows, 1, D], the decode rows, then
    # the rows of a bucket-padded prompt in order.  Everything row-wise runs
    # once over all of them, so a weight matrix is read once; attention alone
    # splits: the first R rows as in paged decode, the rest as one causal
    # sequence, sowing its K/V as ``collect_kv`` does.
    prompt_rows: int = 0

    @nn.compact
    def __call__(self, x, mesh=None, paged=None, length=None):
        """``length``: the real positions of a bucket-padded prompt (a traced
        scalar; the serving forms pass it): the flash kernel does no work past
        it and writes the padding's rows as zeros (dense and ring attention do
        not read it)."""
        B, T, D = x.shape
        H = self.num_heads
        hd = D // H
        # Grouped-query attention (Ainslie et al. 2023): Hk KV heads are
        # shared by groups of H/Hk query heads — the KV cache (the HBM
        # bottleneck at serve time) shrinks by that factor.  Hk == H is
        # exactly multi-head attention (identical params and math).
        Hk = self.num_kv_heads or H
        if H % Hk:
            raise ValueError(f"num_heads={H} must be divisible by num_kv_heads={Hk}")
        group = H // Hk
        y = nn.LayerNorm(dtype=jnp.float32)(x)
        # [B, T, (H + 2 Hk) * hd], columns [q heads | k heads | v heads]
        packed = nn.Dense((H + 2 * Hk) * hd, dtype=self.dtype, name="qkv")(y)
        qkv = packed.reshape(B, T, H + 2 * Hk, hd)
        q, k, v = qkv[:, :, :H], qkv[:, :, H : H + Hk], qkv[:, :, H + Hk :]

        def cached(q, k, v):
            # Autoregressive step: one position a row; append this position's
            # K/V to the cache and attend over everything cached so far.
            # The cache holds Hk heads; query heads address their group's
            # KV head through a grouped einsum — no repeat materializes.
            from ..ops.paged_attention import (
                gathered_decode_attention,
                paged_attention,
                paged_kv_write,
            )

            if self.kv_num_blocks:
                # Paged layout: K/V live in a pool shared by all decode
                # slots; each slot addresses its blocks through the block
                # table in ``paged``.  Same math as the dense branch below
                # (gathered_decode_attention defines it), computed by a
                # fused kernel that reads only the live blocks.
                if paged is None:
                    raise ValueError("kv_num_blocks > 0 needs paged= at apply time")
                pk = self.variable(
                    "cache", "pool_k", jnp.zeros,
                    (self.kv_num_blocks, self.kv_block_size, Hk, hd), self.dtype,
                )
                pv = self.variable(
                    "cache", "pool_v", jnp.zeros,
                    (self.kv_num_blocks, self.kv_block_size, Hk, hd), self.dtype,
                )
                t = paged.lengths
                if self.rotary:
                    q = apply_rotary(q, offset=t)
                    k = apply_rotary(k, offset=t)
                pk.value = paged_kv_write(
                    pk.value, k[:, 0], paged.block_tables, t, paged.active
                )
                pv.value = paged_kv_write(
                    pv.value, v[:, 0], paged.block_tables, t, paged.active
                )
                return paged_attention(
                    q, pk.value, pv.value, paged.block_tables, t, paged.active,
                ).astype(x.dtype)
            ck = self.variable(
                "cache", "k", jnp.zeros, (B, self.max_len, Hk, hd), self.dtype
            )
            cv = self.variable(
                "cache", "v", jnp.zeros, (B, self.max_len, Hk, hd), self.dtype
            )
            idx = self.variable(
                "cache", "idx", lambda: jnp.zeros((), jnp.int32)
            )
            t = idx.value
            if self.rotary:
                q = apply_rotary(q, offset=t)
                k = apply_rotary(k, offset=t)
            ck.value = jax.lax.dynamic_update_slice(
                ck.value, k.astype(self.dtype), (0, t, 0, 0)
            )
            cv.value = jax.lax.dynamic_update_slice(
                cv.value, v.astype(self.dtype), (0, t, 0, 0)
            )
            idx.value = t + 1
            return gathered_decode_attention(q, ck.value, cv.value, t).astype(
                x.dtype
            )

        def causal(packed, q, k, v):
            # Whole sequences, positions from 0: training, and a prefill.
            if self.rotary:
                q, k = apply_rotary(q), apply_rotary(k)
            if self.collect_kv:
                # One-pass prefill: generate() reads these to seed the cache
                # (unrepeated — the cache stays Hk heads).
                self.sow("kv", "k", k.astype(self.dtype))
                self.sow("kv", "v", v.astype(self.dtype))
            if self.attention == "flash":
                # The kernels read a shared K/V head where it lies; without
                # rotary they index the projection itself, and q, k, v above
                # are never cut out of it.
                from ..ops.flash_attention import (
                    flash_attention,
                    flash_attention_packed,
                )

                if self.rotary:
                    return flash_attention(
                        q, k, v, causal=True, mesh=mesh, length=length)
                return flash_attention_packed(
                    packed, H, Hk, causal=True, mesh=mesh, length=length)
            if group > 1:
                # Ring and dense attention take equal head counts —
                # repeat KV across each group (transient; the cache and
                # the params stay at Hk heads).
                k = jnp.repeat(k, group, axis=2)
                v = jnp.repeat(v, group, axis=2)
            if self.attention == "ring":
                from ..parallel.ring_attention import ring_attention

                if mesh is None:
                    raise ValueError("attention='ring' needs mesh= at apply time")
                return ring_attention(q, k, v, mesh, axis_name="sp", causal=True)
            from ..parallel.ring_attention import full_attention

            return full_attention(q, k, v, causal=True)

        if self.decode and T != 1:
            raise ValueError(f"decode mode steps one token at a time, got T={T}")
        if self.prompt_rows:
            if not (self.decode and self.kv_num_blocks and self.collect_kv):
                raise ValueError("prompt_rows is a mode of paged decode with collect_kv")
            R, Lb = B - self.prompt_rows, self.prompt_rows

            def sequence(a):  # the prompt's rows [Lb, 1, ...] as ONE sequence
                return a[R:].reshape(1, Lb, *a.shape[2:])

            att = jnp.concatenate([
                cached(q[:R], k[:R], v[:R]).reshape(R, 1, D),
                causal(sequence(packed), sequence(q), sequence(k), sequence(v))
                .astype(x.dtype).reshape(Lb, 1, D),
            ])
        elif self.decode:
            att = cached(q, k, v)
        else:
            att = causal(packed, q, k, v)
        att = att.reshape(B, T, D)
        x = x + nn.Dense(D, dtype=self.dtype, name="proj")(att)

        y = nn.LayerNorm(dtype=jnp.float32)(x)
        if self.moe_num_experts:
            from ..parallel.moe import SwitchMoE

            y, aux = SwitchMoE(
                num_experts=self.moe_num_experts,
                ffn_dim=4 * D,
                capacity_factor=self.moe_capacity_factor,
                dtype=self.dtype,
                residual=False,
                name="moe",
            )(y)
            # Collected by callers via apply(..., mutable=["losses"]) and
            # added to the task loss (Switch Transformer eq. 4 weight ~1e-2).
            self.sow("losses", "moe_aux", aux)
        else:
            y = nn.Dense(4 * D, dtype=self.dtype)(y)
            y = nn.gelu(y)
            y = nn.Dense(D, dtype=self.dtype)(y)
        return x + y


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def take_rows(table: jax.Array, ids: jax.Array, dtype) -> jax.Array:
    """``table[ids]`` in ``dtype``: the rows are gathered from the table as
    stored and the ROWS are converted, where ``take(table.astype(dtype), ids)``
    converts the whole table to gather from the copy (618 MB of traffic at
    50,257 x 2,048, float32 to bfloat16, for a decode step's 32 rows).  A
    conversion is elementwise, so the result is the same bit for bit.

    The backward pass is that other form's: the rows' cotangents are summed
    into a table of ``dtype`` and the sum is converted.  Left to autodiff the
    sum would be made in the table's own dtype, and under data parallelism a
    float32 table's gradient would be all-reduced at twice the bytes."""
    return jnp.take(table, ids, axis=0).astype(dtype)


def _take_rows_fwd(table, ids, dtype):
    return take_rows(table, ids, dtype), (table, ids)


def _take_rows_bwd(dtype, res, g):
    table, ids = res  # the table for its shape and dtype alone
    grad = jnp.zeros_like(table, dtype).at[ids].add(g)
    return grad.astype(table.dtype), None


take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


class RowEmbed(nn.Embed):
    """``nn.Embed`` whose lookup is :func:`take_rows`: same parameter, same
    initialiser, same values out, and no copy of the table in ``dtype``."""

    def __call__(self, inputs: jax.Array) -> jax.Array:
        return take_rows(self.embedding, inputs, self.dtype or self.embedding.dtype)


class TransformerLM(nn.Module):
    vocab_size: int
    d_model: int = 256
    num_heads: int = 4
    num_kv_heads: Optional[int] = None  # GQA (None = num_heads: plain MHA)
    num_layers: int = 4
    max_len: int = 8192
    attention: str = "flash"  # dense | flash | ring
    dtype: Any = jnp.bfloat16
    moe_num_experts: int = 0  # >0: MoE FFN on every ``moe_every``-th block
    moe_every: int = 2  # blocks i with i % moe_every == moe_every - 1 use MoE
    moe_capacity_factor: float = 1.25
    pos_embedding: str = "learned"  # learned (table, capped at max_len) | rotary
    decode: bool = False  # single-token KV-cache steps (see generate())
    collect_kv: bool = False  # sow per-block K/V (generate()'s prefill)
    # Paged decode (engine.ContinuousBatchingEngine): >0 makes every block's
    # cache a shared pool addressed by the PagedState passed via paged=.
    kv_num_blocks: int = 0
    kv_block_size: int = 16
    # Paged decode with ONE prompt prefilled in the same pass (``Block``):
    # tokens are [R + prompt_rows, 1], the decode rows then the prompt's.
    prompt_rows: int = 0
    remat: bool = False  # checkpoint each block: O(L) -> O(1) activations
    # What the per-block checkpoint SAVES (only meaningful with remat=True):
    #   "full"          — save nothing: every op recomputed in the backward
    #                     (max memory saving, ~1/3 extra FLOPs)
    #   "dots"          — save every dot/matmul output, recompute only the
    #                     cheap elementwise/norm work: the MXU never re-runs,
    #                     at higher memory than "full" (pallas flash calls
    #                     are not dots, so attention is still recomputed —
    #                     its own kernel already keeps residuals O(T))
    #   "dots_no_batch" — like "dots" but only matmuls with no batch dims
    #                     (weight@activation, not activation@activation)
    remat_policy: str = "full"

    @nn.compact
    def __call__(
        self, tokens: jax.Array, mesh=None, return_features: bool = False,
        paged=None, length=None,
    ) -> jax.Array:
        """Logits [B, T, V] — or pre-head features [B, T, D] with
        ``return_features=True``, for ``ops.xent.lm_head_xent``'s chunked
        loss (the lm_head params still come from the same init: flax only
        materializes params on the default path, and ``apply`` ignores the
        unused head when features are requested).  ``length`` (a traced
        scalar) is the number of real tokens of ONE bucket-padded prompt, which
        the serving forms pass: flash attention does no work past it
        (``Block``).  With ``prompt_rows`` the logits are [R + 1, 1, V]: the
        decode rows', then the prompt's at its row ``length - 1`` (the row is
        taken before the final norm and the head, which never see the rest of
        the bucket)."""
        B, T = tokens.shape
        # Validate even when remat/decode makes the policy a no-op: bench
        # rows are keyed by this string, so a typo must never run silently.
        _remat_policy(self.remat_policy)
        x = RowEmbed(self.vocab_size, self.d_model, dtype=self.dtype, name="embed")(
            tokens
        )
        if self.pos_embedding == "learned":
            pos_idx = jnp.arange(T)[None, :]
            if self.decode and paged is not None:
                # Paged decode: each slot sits at its own position — the
                # per-slot lengths ARE the position counter.
                pos = paged.lengths
                if self.prompt_rows:
                    pos = jnp.concatenate([pos, jnp.arange(self.prompt_rows)])
                pos_idx = pos_idx + pos[:, None]
            elif self.decode:
                # The LM owns its position counter (how many tokens have
                # been decoded) rather than peeking at a child block's cache.
                ctr = self.variable(
                    "cache", "pos_idx", lambda: jnp.zeros((), jnp.int32)
                )
                pos_idx = pos_idx + ctr.value
                ctr.value = ctr.value + T
            x = x + RowEmbed(
                self.max_len, self.d_model, dtype=self.dtype, name="pos"
            )(pos_idx)
        elif self.pos_embedding != "rotary":
            raise ValueError(f"unknown pos_embedding {self.pos_embedding!r}")
        # remat trades ~1/3 extra FLOPs for O(1)-in-depth activation memory
        # (HBM is the usual TPU bottleneck): each block's activations are
        # recomputed during the backward instead of stored.  Bigger batches
        # then fit at long T (``examples/lm.py --remat``).  mesh is a
        # static argument (index 2 counting self), not a traced operand.
        if self.remat and not self.decode:
            block_cls = nn.remat(
                Block, static_argnums=(2,), policy=_remat_policy(self.remat_policy)
            )
        else:
            block_cls = Block
        for i in range(self.num_layers):
            use_moe = self.moe_num_experts and i % self.moe_every == self.moe_every - 1
            block = block_cls(
                self.d_model,
                self.num_heads,
                self.attention,
                self.dtype,
                moe_num_experts=self.moe_num_experts if use_moe else 0,
                moe_capacity_factor=self.moe_capacity_factor,
                rotary=self.pos_embedding == "rotary",
                decode=self.decode,
                max_len=self.max_len,
                collect_kv=self.collect_kv,
                num_kv_heads=self.num_kv_heads,
                kv_num_blocks=self.kv_num_blocks,
                kv_block_size=self.kv_block_size,
                prompt_rows=self.prompt_rows,
                name=f"block{i}",
            )
            # paged stays out of the remat-wrapped call (remat only wraps
            # the non-decode path, where paged is always None).
            if paged is None and length is None:
                x = block(x, mesh)
            else:
                x = block(x, mesh, paged, length)
        if self.prompt_rows:
            R = B - self.prompt_rows
            x = jnp.concatenate(
                [x[:R], jax.lax.dynamic_slice_in_dim(x, R + length - 1, 1)])
        x = nn.LayerNorm(dtype=jnp.float32, name="ln_f")(x)
        head = nn.Dense(self.vocab_size, dtype=jnp.float32, name="lm_head")
        if return_features:
            if self.is_initializing():
                # Materialize the head's params even on the features path so
                # init(..., return_features=True) yields the same tree as the
                # default path (lm_head_xent reads params["params"]["lm_head"]).
                head(x.astype(jnp.float32)[:, :1])
            return x.astype(jnp.float32)
        return head(x.astype(jnp.float32))


# The largest bucket whose rows attend through XLA's dense scores in the
# serving forms; a larger one takes the flash kernel.  Read on a TPU v5e (PR
# 60; 16 heads of 128, the whole admit step of 24 layers beside 32 decode
# rows, dense against kernel): 128 rows 11.82 -> 12.07 ms, 256 12.21 -> 12.63,
# 512 14.39 -> 14.80, 1,024 22.87 -> 23.14 (up to 64 MB of scores a layer,
# which XLA's fusions pass over about once: they beat a kernel launch and the
# slice of the prompt's rows out of the projection); 1,984 rows 64.1 -> 41.7
# (252 MB of scores a layer, passed over three and a half times).
_DENSE_PROMPT_ROWS = 1024


class PagedTransformerLM:
    """What ``engine.ContinuousBatchingEngine`` asks of a model, for a
    :class:`TransformerLM`: pools ``pool_k`` / ``pool_v`` of ``[num_blocks,
    block_size, Hk, hd]`` a layer, prefill through the ``collect_kv`` twin
    (the whole prompt, teacher-forced), one-token decode through the
    ``decode=True`` twin over the paged pools.

    What the prompt's rows attend through, in ``prefill`` and in
    ``decode_with_prompt``, is the serving form's own choice, whatever
    ``model.attention`` says (that names the attention of training,
    ``generate()`` and ``sharded_generator``): it is picked from the bucket's
    static row count (:meth:`prompt_attention_kernel`).  A bucket of more
    than ``_DENSE_PROMPT_ROWS`` rows, whether or not it tiles, takes the flash
    kernel with the prompt's true length as data, so the bucket's padding
    costs the attention nothing and no ``[H, Lb, Lb]`` scores are written; a
    smaller one takes XLA's dense scores, which are cheaper there."""

    step_counters = 0
    prefill_counters = 0

    def __init__(self, model: "TransformerLM"):
        if model.moe_num_experts:
            raise ValueError(
                "TransformerLM's capacity-dropping SwitchMoE has no decode "
                "form; models.latent_moe holds the dropless expert layer")
        self.model = model
        self.max_len = model.max_len

    def _twin(self, **kw):
        m = self.model
        return TransformerLM(
            vocab_size=m.vocab_size, d_model=m.d_model, num_heads=m.num_heads,
            num_kv_heads=m.num_kv_heads, num_layers=m.num_layers,
            max_len=m.max_len, dtype=m.dtype, pos_embedding=m.pos_embedding,
            **kw)

    def cache_spec(self, num_blocks: int, block_size: int):
        m = self.model
        pool = jax.ShapeDtypeStruct(
            (num_blocks, block_size, m.num_kv_heads or m.num_heads,
             m.d_model // m.num_heads), m.dtype)
        return {f"block{i}": {"pool_k": pool, "pool_v": pool}
                for i in range(m.num_layers)}

    def _rows(self, kv, block_size: int):
        """A prompt's sown K/V as ``write_rows`` takes them: K and V of all
        layers stacked, ``[L, nbw, block_size, Hk, hd]`` each."""
        def blocks(which):
            x = jnp.stack([kv[f"block{i}"][which][0][0]
                           for i in range(self.model.num_layers)])
            return rows_to_blocks(x, block_size, axis=1).astype(self.model.dtype)

        return blocks("k"), blocks("v")

    @staticmethod
    def prompt_attention_kernel(bucket: int) -> str:
        """``"flash"`` or ``"dense"``: what the rows of a prompt padded to
        ``bucket`` attend through: the twins' ``attention``, and the label the
        engine counts the prompt's tokens under."""
        from ..ops.flash_attention import length_call_rides_kernel

        rides = bucket > _DENSE_PROMPT_ROWS and length_call_rides_kernel(bucket)
        return "flash" if rides else "dense"

    def prefill(self, params, toks, tp, block_size: int):
        pre = self._twin(
            attention=self.prompt_attention_kernel(toks.shape[1]), collect_kv=True)
        logits, col = pre.apply(
            {"params": params["params"]}, toks, length=tp, mutable=["kv"])
        return self._rows(col["kv"], block_size), jnp.take(logits[0], tp - 1, axis=0), None

    def write_rows(self, cache, rows, block_ids):
        ks, vs = rows
        new_cache = {}
        for i in range(self.model.num_layers):
            c = cache[f"block{i}"]
            new_cache[f"block{i}"] = {
                "pool_k": c["pool_k"].at[block_ids].set(ks[i].astype(c["pool_k"].dtype)),
                "pool_v": c["pool_v"].at[block_ids].set(vs[i].astype(c["pool_v"].dtype)),
            }
        return new_cache

    def decode(self, params, cache, tokens, paged):
        pool = cache["block0"]["pool_k"]
        dec = self._twin(
            attention="dense",  # unused: decode attention is the paged kernel
            decode=True, kv_num_blocks=pool.shape[0], kv_block_size=pool.shape[1])
        logits, upd = dec.apply(
            {"params": params["params"], "cache": cache}, tokens[:, None],
            paged=paged, mutable=["cache"])
        return logits[:, 0], upd["cache"], None

    def decode_with_prompt(self, params, cache, tokens, paged, toks, tp, block_size: int):
        """``decode`` of the ``R`` rows and ``prefill`` of ONE prompt in one
        pass over ``R + Lb`` rows (``Block.prompt_rows``): every weight matrix
        is an operand of one product.  Returns (the decode rows' logits [R, V],
        the prompt's logits [V] at ``tp - 1``, the cache with this step's K/V
        written, the prompt's rows as ``prefill`` hands them to
        ``write_rows``)."""
        pool = cache["block0"]["pool_k"]
        both = self._twin(
            attention=self.prompt_attention_kernel(toks.shape[1]), decode=True,
            collect_kv=True, prompt_rows=toks.shape[1], kv_num_blocks=pool.shape[0],
            kv_block_size=pool.shape[1])
        logits, upd = both.apply(
            {"params": params["params"], "cache": cache},
            jnp.concatenate([tokens, toks[0]])[:, None],
            paged=paged, length=tp, mutable=["cache", "kv"])
        return logits[:-1, 0], logits[-1, 0], upd["cache"], self._rows(upd["kv"], block_size)


def generate(
    model: TransformerLM,
    params,
    prompt: jax.Array,
    max_new_tokens: int,
    temperature: float = 0.0,
    rng: Optional[jax.Array] = None,
) -> jax.Array:
    """Autoregressive sampling with a per-block KV cache.

    ``prompt`` is [B, Tp] int32; returns [B, Tp + max_new_tokens] with the
    continuation appended.  Prefill is ONE teacher-forced forward over the
    prompt (each block sows its K/V, which seed the cache); each generated
    token is then a single-position step against the cached K/V — O(T) per
    token instead of O(T²) re-forwarding (the flax ``decode`` pattern, the
    cache collection carried through a scan).  ``temperature=0`` is greedy
    argmax; otherwise softmax sampling with ``rng``.
    """
    B, Tp = prompt.shape
    if Tp + max_new_tokens > model.max_len:
        raise ValueError(
            f"prompt + max_new_tokens = {Tp + max_new_tokens} exceeds the "
            f"cache capacity max_len={model.max_len}"
        )
    if model.moe_num_experts:
        # Per-step Switch capacity is computed over B tokens, not B*T, so
        # cached decoding would drop different tokens than the training
        # forward — refuse rather than silently diverge.
        raise ValueError("generate() does not support MoE models yet")
    if temperature > 0.0 and rng is None:
        raise ValueError("temperature > 0 needs an explicit rng key")
    dec = TransformerLM(
        vocab_size=model.vocab_size,
        d_model=model.d_model,
        num_heads=model.num_heads,
        num_kv_heads=model.num_kv_heads,
        num_layers=model.num_layers,
        max_len=model.max_len,
        attention="dense",  # unused in decode steps (cached attention)
        dtype=model.dtype,
        moe_num_experts=model.moe_num_experts,
        moe_every=model.moe_every,
        moe_capacity_factor=model.moe_capacity_factor,
        pos_embedding=model.pos_embedding,
        decode=True,
    )
    pdict = {"params": params["params"]}

    def step(cache, tok):
        logits, upd = dec.apply(
            {**pdict, "cache": cache}, tok[:, None], mutable=["cache"]
        )
        return upd["cache"], logits[:, 0]

    # Prefill in ONE teacher-forced forward over the whole prompt: the
    # full model sows every block's K/V (collect_kv) and the cache is
    # assembled from them — not Tp sequential single-token steps.
    full = TransformerLM(
        vocab_size=model.vocab_size,
        d_model=model.d_model,
        num_heads=model.num_heads,
        num_kv_heads=model.num_kv_heads,
        num_layers=model.num_layers,
        max_len=model.max_len,
        # Prefill rides the model's own attention kind, so long prompts go
        # through the flash kernel instead of a Tp² dense score matrix.
        # ring needs a mesh at apply time (generate() takes none); its
        # single-chip equivalent is flash.
        attention="flash" if model.attention == "ring" else model.attention,
        dtype=model.dtype,
        pos_embedding=model.pos_embedding,
        collect_kv=True,
    )
    full_logits, col = full.apply(pdict, prompt, mutable=["kv"])
    last_logits = full_logits[:, -1]
    pad = model.max_len - Tp
    cache = {}
    for i in range(model.num_layers):
        kv = col["kv"][f"block{i}"]
        cache[f"block{i}"] = {
            "k": jnp.pad(kv["k"][0], ((0, 0), (0, pad), (0, 0), (0, 0))),
            "v": jnp.pad(kv["v"][0], ((0, 0), (0, pad), (0, 0), (0, 0))),
            "idx": jnp.asarray(Tp, jnp.int32),
        }
    if model.pos_embedding == "learned":
        cache["pos_idx"] = jnp.asarray(Tp, jnp.int32)

    if rng is None:
        rng = jax.random.key(0)  # unused: greedy path (temperature == 0)

    def gen_step(carry, _):
        cache, logits, rng = carry
        if temperature == 0.0:
            tok = jnp.argmax(logits, axis=-1)
        else:
            rng, sub = jax.random.split(rng)
            tok = jax.random.categorical(sub, logits / temperature, axis=-1)
        cache, logits = step(cache, tok)
        return (cache, logits, rng), tok

    (_, _, _), new_toks = jax.lax.scan(
        gen_step, (cache, last_logits, rng), None, length=max_new_tokens
    )
    return jnp.concatenate([prompt, new_toks.T.astype(prompt.dtype)], axis=1)


def sharded_generator(
    model: TransformerLM,
    params,
    max_new_tokens: int,
    mesh,
    params_sharding=None,
    temperature: float = 0.0,
    sample: bool = False,
):
    """Build a REUSABLE tensor-parallel generation function: the whole of
    :func:`generate` (flash prefill + KV-cache decode scan) jitted once over
    ``mesh`` with the params sharded — serving models larger than one chip's
    HBM, with the jit cache hit on every subsequent call.

    ``params_sharding`` defaults to ``parallel.auto_shardings`` (TP on the
    last axis of big kernels + FSDP), the same tree the training step uses,
    so a trained sharded model serves without a resharding hop.  XLA
    propagates the sharding through the per-block KV caches (heads follow
    the attention kernels' TP axis) and inserts the decode-time collectives.
    Prompt and output are replicated (the batch is tiny at serve time).

    Returns ``fn(params, prompt)`` (greedy) or ``fn(params, prompt, rng)``
    when ``sample=True`` (softmax sampling at ``temperature``).
    """
    from jax.sharding import NamedSharding, PartitionSpec

    from ..parallel.train import auto_shardings

    if params_sharding is None:
        params_sharding = auto_shardings(params, mesh)
    rep = NamedSharding(mesh, PartitionSpec())
    n_rng = 1 if sample else 0
    return jax.jit(
        lambda p, t, *r: generate(model, p, t, max_new_tokens, temperature, *r),
        in_shardings=(params_sharding, rep) + (rep,) * n_rng,
        out_shardings=rep,
    )


def generate_sharded(
    model: TransformerLM,
    params,
    prompt: jax.Array,
    max_new_tokens: int,
    mesh,
    params_sharding=None,
    temperature: float = 0.0,
    rng: Optional[jax.Array] = None,
):
    """One-shot form of :func:`sharded_generator` (repeated callers should
    build the generator once and reuse it — each call here re-jits)."""
    fn = sharded_generator(
        model, params, max_new_tokens, mesh, params_sharding, temperature,
        sample=rng is not None,
    )
    return fn(params, prompt, rng) if rng is not None else fn(params, prompt)


def pipeline_lm_apply(
    model: TransformerLM,
    params,
    tokens: jax.Array,
    mesh,
    num_microbatches: int,
    axis_name: str = "pp",
    data_axis: Optional[str] = None,
    circular_repeats: int = 1,
    remat: bool = False,
    remat_policy: str = "full",
) -> jax.Array:
    """Apply ``model`` with its transformer blocks run through
    :func:`..parallel.pipeline.pipeline_apply` over the mesh's ``pp`` axis.

    The blocks of a (non-MoE) TransformerLM are structurally identical, so
    their parameters stack into the leading virtual-stage axis the pipeline
    expects; embeddings and the LM head stay outside the pipeline
    (replicated — they are a sliver of the FLOPs).  Differentiable end to
    end: gradients flow back through the schedule into the *per-block*
    leaves of ``params``, so one optimizer tree serves both the pipelined
    and plain paths.  Attention must be "dense" or "flash" (ring attention's
    own collective axis would have to nest inside the pipeline shard_map).

    With ``circular_repeats=v``, the model's ``num_layers`` must be
    ``v * mesh.shape[axis_name]`` and microbatch count a multiple of the pp
    size (see pipeline_apply).
    """
    from ..parallel.pipeline import pipeline_apply

    if model.attention == "ring":
        raise ValueError("pipeline_lm_apply supports dense/flash attention only")
    if model.moe_num_experts:
        raise ValueError(
            "pipeline_lm_apply needs structurally identical blocks (no MoE)"
        )
    B, T = tokens.shape
    if B % num_microbatches:
        raise ValueError(f"batch {B} not divisible by microbatches {num_microbatches}")
    p = params["params"]
    L = model.num_layers

    emb = RowEmbed(model.vocab_size, model.d_model, dtype=model.dtype)
    x = emb.apply({"params": p["embed"]}, tokens)
    if model.pos_embedding == "learned":
        pos = RowEmbed(model.max_len, model.d_model, dtype=model.dtype)
        x = x + pos.apply({"params": p["pos"]}, jnp.arange(T)[None, :])
    elif model.pos_embedding != "rotary":
        raise ValueError(f"unknown pos_embedding {model.pos_embedding!r}")

    block = Block(
        model.d_model, model.num_heads, model.attention, model.dtype,
        rotary=model.pos_embedding == "rotary",
        num_kv_heads=model.num_kv_heads,
    )
    stage_params = jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *(p[f"block{i}"] for i in range(L))
    )

    def stage_fn(bp, x):
        return block.apply({"params": bp}, x)

    mb = x.reshape(num_microbatches, B // num_microbatches, T, model.d_model)
    out = pipeline_apply(
        stage_fn,
        stage_params,
        mb,
        mesh,
        axis_name=axis_name,
        data_axis=data_axis,
        circular_repeats=circular_repeats,
        remat=remat,
        remat_policy=_remat_policy(remat_policy),
    )
    x = out.reshape(B, T, model.d_model)
    x = nn.LayerNorm(dtype=jnp.float32).apply({"params": p["ln_f"]}, x)
    head = nn.Dense(model.vocab_size, dtype=jnp.float32)
    return head.apply({"params": p["lm_head"]}, x.astype(jnp.float32))
