"""Ring attention: sequence/context-parallel attention over an ICI ring.

New TPU-idiomatic capability (the reference has no attention or sequence
parallelism at all — SURVEY.md §5.7): the sequence axis is sharded over the
``sp`` mesh axis; each device keeps its local Q block resident and the K/V
blocks rotate around the ring with ``ppermute`` (one ICI hop per step) while
a streaming (flash-style) softmax accumulates the output.  Peak memory per
device is O(T/n · T/n) for scores and O(T/n) for K/V — full attention over
sequences n× longer than a single chip could hold, with communication fully
overlappable with the block matmuls.

Layout: [B, T, H, D] ("BTHD"), T sharded on ``sp``.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


_NEG_INF = -1e30


def online_softmax_update(scores, v_blk, acc, l, m, zero_masked_rows: bool):
    """Fold one K/V block into streaming-softmax accumulators.

    The single source of the online-softmax math shared by the pure-jax
    blockwise paths (ring attention's per-hop update and flash attention's
    backward recompute; the pallas kernel hand-writes the same update in its
    memory model).  ``scores`` [B, H, Q, K] f32, already masked with
    ``_NEG_INF``; ``v_blk`` [B, K, H, D]; accumulators ``acc`` [B, H, Q, D],
    ``l``/``m`` [B, H, Q].  ``zero_masked_rows`` keeps fully-masked rows at
    zero weight (avoid exp(-inf - (-inf))).
    """
    m_new = jnp.maximum(m, scores.max(axis=-1))
    corr = jnp.exp(m - m_new)
    p = jnp.exp(scores - m_new[..., None])
    if zero_masked_rows:
        p = jnp.where(scores <= _NEG_INF / 2, 0.0, p)
    l = l * corr + p.sum(axis=-1)
    acc = acc * corr[..., None] + jnp.einsum(
        "bhqk,bkhd->bhqd", p, v_blk.astype(jnp.float32)
    )
    return acc, l, m_new


def full_attention(q, k, v, causal: bool = True):
    """Reference dense attention (single device), for testing parity."""
    return dense_attention_lse(q, k, v, causal=causal)[0]


def dense_attention_lse(q, k, v, causal: bool = True):
    """Dense attention that also returns the row logsumexp ([B, Tq, H], f32)
    — the combinable form (chunk results merge by lse weights).  Pure jax,
    natively differentiable; the small-shape counterpart of
    ``flash_attention(..., return_lse=True)``."""
    scale = q.shape[-1] ** -0.5
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    if causal:
        Tq, Tk = scores.shape[-2], scores.shape[-1]
        mask = jnp.tril(jnp.ones((Tq, Tk), bool))
        scores = jnp.where(mask[None, None], scores, _NEG_INF)
    m = jax.lax.stop_gradient(scores.max(axis=-1))  # shift only; grad via p
    p = jnp.exp(scores - m[..., None])
    l = p.sum(axis=-1)  # [B, H, Tq]
    l_rows = jnp.maximum(l, 1e-30).transpose(0, 2, 1)  # [B, Tq, H]
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32)) / l_rows[..., None]
    lse = m + jnp.log(jnp.maximum(l, 1e-30))  # [B, H, Tq]
    return out.astype(q.dtype), jnp.transpose(lse, (0, 2, 1))


def ring_attention_sharded(
    q, k, v, axis_name: str = "sp", causal: bool = True,
    batch_axis: Optional[str] = None,
):
    """Per-shard body: call inside ``shard_map`` with T sharded on
    ``axis_name`` (and B on ``batch_axis``, if any). q/k/v: [B, T_local, H, D].

    Each ring hop computes attention of the resident Q block against the
    rotating K/V chunk with ``flash_attention(..., return_lse=True)`` — the
    pallas kernel when the local shapes tile, its dense-with-lse fallback
    otherwise — and merges chunk results by logsumexp weights.  The two
    long-context mechanisms compose: ppermute moves O(T/n) K/V per hop, and
    within a hop scores never materialize in HBM.  Under a causal mask the
    chunk is one of three static programs chosen per device by ring
    position: diagonal (locally causal), fully past (no mask), fully future
    (skipped — identity weights).

    When embedding this in your own ``shard_map`` and the chunk shapes tile
    (T_local a 128-multiple), pass ``check_vma=False``: the pallas call
    doesn't yet carry varying-mesh-axes metadata through lax.switch /
    fori_loop (the :func:`ring_attention` wrapper below does this).
    """
    from ..ops.flash_attention import flash_attention

    n = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    B, Tq, H, D = q.shape

    # Mark the accumulators as varying over every axis the inputs vary over
    # (the ring axis, plus the batch axis when B is sharded too) so the
    # fori_loop carry type matches after the updates inside.
    axes = (axis_name,) + ((batch_axis,) if batch_axis else ())
    acc = jax.lax.pcast(jnp.zeros((B, Tq, H, D), jnp.float32), axes, to="varying")
    s = jax.lax.pcast(jnp.zeros((B, Tq, H), jnp.float32), axes, to="varying")
    mx = jax.lax.pcast(jnp.full((B, Tq, H), _NEG_INF, jnp.float32), axes, to="varying")

    def attend(k_c, v_c, causal_flag):
        # flash_attention owns the pallas-vs-dense fallback decision.
        return flash_attention(q, k_c, v_c, causal=causal_flag, return_lse=True)

    def body(i, carry):
        acc, s, mx, k_c, v_c = carry
        src = (my - i) % n  # whose K/V block we hold at step i
        if causal:
            # Chunk-granular causality: diagonal chunk masks locally (the
            # global offsets cancel: both blocks start at src*T_local);
            # past chunks attend fully; future chunks contribute nothing.
            branch = jnp.where(src == my, 1, jnp.where(src < my, 0, 2))
            o_i, lse_i = jax.lax.switch(
                branch,
                [
                    lambda kv: attend(kv[0], kv[1], False),  # past
                    lambda kv: attend(kv[0], kv[1], True),  # diagonal
                    lambda kv: (  # future: zero weight (varying like the rest)
                        jax.lax.pcast(
                            jnp.zeros((B, Tq, H, D), q.dtype), axes, to="varying"
                        ),
                        jax.lax.pcast(
                            jnp.full((B, Tq, H), _NEG_INF, jnp.float32),
                            axes,
                            to="varying",
                        ),
                    ),
                ],
                (k_c, v_c),
            )
        else:
            o_i, lse_i = attend(k_c, v_c, False)
        # Merge by logsumexp weight (chunk outputs are each normalized):
        # out_tot = Σ_i o_i · exp(lse_i − lse_tot).
        m_new = jnp.maximum(mx, lse_i)
        w_acc = jnp.exp(mx - m_new)
        w_i = jnp.exp(lse_i - m_new)
        acc = acc * w_acc[..., None] + o_i.astype(jnp.float32) * w_i[..., None]
        s = s * w_acc + w_i
        mx = m_new
        # Rotate K/V one step around the ring (device j -> j+1).
        perm = [(j, (j + 1) % n) for j in range(n)]
        k_c = jax.lax.ppermute(k_c, axis_name, perm)
        v_c = jax.lax.ppermute(v_c, axis_name, perm)
        return (acc, s, mx, k_c, v_c)

    acc, s, mx, _, _ = jax.lax.fori_loop(0, n, body, (acc, s, mx, k, v))
    return (acc / jnp.maximum(s, 1e-30)[..., None]).astype(q.dtype)


def ring_attention(
    q, k, v, mesh: Mesh, axis_name: str = "sp", causal: bool = True,
    batch_axis: Optional[str] = "auto",
):
    """Global entry point: q/k/v are [B, T, H, D] jax arrays (any sharding);
    runs ring attention with T sharded over ``mesh``'s ``axis_name``.

    ``batch_axis``: mesh axis to shard B over ("auto" = use ``dp`` when the
    mesh has one).  Without it, a dp×sp mesh would all-gather q/k/v over dp
    and replicate the attention compute on every dp replica."""
    if batch_axis == "auto":
        ok = (
            "dp" in mesh.axis_names
            and "dp" != axis_name
            and q.shape[0] % mesh.shape["dp"] == 0
        )
        batch_axis = "dp" if ok else None
    spec = P(batch_axis, axis_name, None, None)
    # check_vma=False: the per-chunk pallas calls (and their interpret-mode
    # emulation) don't carry varying-mesh-axes metadata through lax.switch /
    # fori_loop yet — jax's own suggested workaround.  The pcasts in the
    # sharded body keep the carries consistent when checking IS on (e.g. a
    # future jax default flip).
    fn = jax.shard_map(
        partial(
            ring_attention_sharded,
            axis_name=axis_name,
            causal=causal,
            batch_axis=batch_axis,
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    sharding = NamedSharding(mesh, spec)
    q, k, v = (jax.device_put(x, sharding) for x in (q, k, v))
    return fn(q, k, v)
