"""Mixture-of-Experts with expert parallelism (EP) over a mesh axis.

New TPU-idiomatic capability beyond the reference (SURVEY.md §2.3: expert
parallelism absent).  Switch-style top-1 routing with a capacity factor and
GShard-style dense dispatch/combine einsums — the formulation XLA shards
cleanly: expert-indexed weights carry an ``ep``-shardable leading axis and
the dispatch einsum lowers to an all-to-all over ICI when tokens and experts
live on different devices.

Use :func:`moe_param_spec` for the PartitionSpecs of the expert weights.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P


class SwitchMoE(nn.Module):
    """Top-1 routed MLP block: x [.., S, D] -> [.., S, D].

    Attributes:
      num_experts: number of experts (shard over "ep").
      ffn_dim: expert hidden width.
      capacity_factor: per-expert slots = ceil(S / E * factor); overflowing
        tokens fall through the residual (standard switch behavior).
    """

    num_experts: int
    ffn_dim: int
    capacity_factor: float = 1.25
    dtype: Any = jnp.bfloat16
    residual: bool = True  # False: return only the expert output (caller
    # owns the residual — e.g. a pre-LN transformer block whose skip
    # connection starts from the un-normalized activations)

    @nn.compact
    def __call__(self, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
        orig_shape = x.shape
        D = x.shape[-1]
        x2 = x.reshape(-1, D)  # [T, D] tokens
        T = x2.shape[0]
        E = self.num_experts
        C = max(1, int(T / E * self.capacity_factor))

        router = nn.Dense(E, dtype=jnp.float32, name="router")
        logits = router(x2.astype(jnp.float32))  # [T, E]
        probs = jax.nn.softmax(logits, axis=-1)
        gate, expert = jnp.max(probs, axis=-1), jnp.argmax(probs, axis=-1)  # [T]

        # Position of each token within its expert's capacity (cumsum trick).
        expert_1h = jax.nn.one_hot(expert, E, dtype=jnp.int32)  # [T, E]
        pos_in_expert = jnp.cumsum(expert_1h, axis=0) * expert_1h  # 1-based
        pos = jnp.sum(pos_in_expert, axis=-1) - 1  # [T], -1 if... (>=0 here)
        keep = pos < C  # overflow tokens dropped (residual passthrough)

        # Dense dispatch/combine tensors [T, E, C].
        dispatch = (
            jax.nn.one_hot(expert, E, dtype=self.dtype)[:, :, None]
            * jax.nn.one_hot(jnp.where(keep, pos, 0), C, dtype=self.dtype)[:, None, :]
            * keep[:, None, None].astype(self.dtype)
        )
        combine = dispatch * gate[:, None, None].astype(self.dtype)

        # Expert weights: leading E axis shards over "ep".
        w_in = self.param(
            "w_in", nn.initializers.lecun_normal(), (E, D, self.ffn_dim), jnp.float32
        )
        w_out = self.param(
            "w_out", nn.initializers.lecun_normal(), (E, self.ffn_dim, D), jnp.float32
        )

        xs = jnp.einsum("tec,td->ecd", dispatch, x2.astype(self.dtype))  # [E, C, D]
        h = jax.nn.relu(jnp.einsum("ecd,edf->ecf", xs, w_in.astype(self.dtype)))
        ys = jnp.einsum("ecf,efd->ecd", h, w_out.astype(self.dtype))  # [E, C, D]
        out = jnp.einsum("tec,ecd->td", combine, ys)  # [T, D]

        # Load-balancing auxiliary loss (Switch Transformer eq. 4).
        density = jnp.mean(expert_1h.astype(jnp.float32), axis=0)  # fraction routed
        density_proxy = jnp.mean(probs, axis=0)
        aux_loss = E * jnp.sum(density * density_proxy)

        out = out.astype(x.dtype).reshape(orig_shape)
        if self.residual:
            return x + out, aux_loss  # residual catches dropped tokens
        return out, aux_loss  # dropped tokens contribute zero


def moe_param_spec(ep_axis: str = "ep"):
    """PartitionSpecs for SwitchMoE params: experts sharded over ``ep_axis``."""
    return {
        "router": {"kernel": P(), "bias": P()},
        "w_in": P(ep_axis, None, None),
        "w_out": P(ep_axis, None, None),
    }


def moe_shardings(params, mesh, ep_axis: str = "ep", base=None):
    """NamedShardings for a *whole model's* param tree with SwitchMoE layers
    inside: expert weights (leaves named ``w_in``/``w_out`` with a leading
    expert axis divisible by the ``ep_axis`` size) shard over ``ep_axis``;
    everything else gets ``base`` (default: replicated).

    ``base`` may be a single sharding or a pytree matching ``params`` (e.g.
    the output of :func:`..train.auto_shardings` to compose EP with TP/FSDP
    on one mesh).
    """
    from jax.sharding import NamedSharding, Sharding

    from .mesh import replicated

    if base is None:
        base = replicated(mesh)
    ep = mesh.shape[ep_axis]

    def expert_spec(path, x):
        keys = {str(getattr(p, "key", getattr(p, "name", ""))) for p in path}
        if (
            ("w_in" in keys or "w_out" in keys)
            and getattr(x, "ndim", 0) == 3
            and x.shape[0] % ep == 0
        ):
            return NamedSharding(mesh, P(ep_axis, None, None))
        return None

    overlay = jax.tree_util.tree_map_with_path(expert_spec, params)
    if isinstance(base, Sharding):
        base = jax.tree_util.tree_map(lambda _: base, params)
    return jax.tree_util.tree_map(
        lambda o, b: b if o is None else o, overlay, base,
        is_leaf=lambda x: x is None or isinstance(x, Sharding),
    )


# --------------------------------------------------------------------------
# Dropless top-k routing: sort by expert, grouped matmul, unsort
# --------------------------------------------------------------------------
# ``SwitchMoE`` above dispatches through a dense [tokens, experts, capacity]
# one-hot and drops what overflows a capacity.  The layer below drops nothing
# at any load: the token-expert pairs are sorted by expert, each projection is
# ONE grouped matmul over the sorted rows (group g = the rows that chose
# expert g, multiplied by expert g's matrix), and the rows go back to their
# tokens.  The grouped matmul visits (row tile, expert) pairs that hold rows
# and no others, so the weights of an expert no token chose are never read: a
# decode step's time follows the experts its few tokens touch.


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _group_work(group_sizes, m_tiles: int, tm: int):
    """The (expert, row tile) pairs a grouped matmul visits, in row order.
    Returns int32 vectors of the static length ``m_tiles + G - 1`` (the most
    there can be): expert, row tile, first and one-past-last row of the
    expert, and the count of real pairs.  Entries past the count repeat the
    last real pair, so that the kernel's block indices do not move there and
    nothing is copied for them (no group holds a row: every entry is the last
    group's first tile, and the count is 0)."""
    G = group_sizes.shape[0]
    W = m_tiles + G - 1
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    first = starts // tm
    tiles = jnp.where(group_sizes > 0, (ends - 1) // tm - first + 1, 0)
    upto = jnp.cumsum(tiles)  # pairs of experts 0..g
    n_work = upto[-1]
    i = jnp.minimum(jnp.arange(W, dtype=jnp.int32), jnp.maximum(n_work - 1, 0))
    gid = jnp.minimum(jnp.searchsorted(upto, i, side="right"), G - 1).astype(jnp.int32)
    tile = first[gid] + i - (upto[gid] - tiles[gid])
    return (gid, tile.astype(jnp.int32), starts[gid].astype(jnp.int32),
            ends[gid].astype(jnp.int32), n_work.astype(jnp.int32).reshape(1))


def _gmm_kernel(gid_ref, tile_ref, start_ref, end_ref, n_ref, layer_ref, x_ref,
                w_ref, o_ref, *, tm):
    i = pl.program_id(1)

    @pl.when(i < n_ref[0])
    def _():
        prod = jax.lax.dot_general(
            x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        row = tile_ref[i] * tm + jax.lax.broadcasted_iota(jnp.int32, prod.shape, 0)
        mine = (row >= start_ref[i]) & (row < end_ref[i])
        # A row tile's block stays in VMEM while consecutive experts fill in
        # their rows of it; the first of them clears what it does not own.
        fresh = (i == 0) | (tile_ref[jnp.maximum(i - 1, 0)] != tile_ref[i])
        keep = jnp.where(fresh, jnp.zeros_like(prod), o_ref[...].astype(jnp.float32))
        o_ref[...] = jnp.where(mine, prod, keep).astype(o_ref.dtype)


_GMM_VMEM_LIMIT = 48 << 20  # of a v5e's 128 MiB; Mosaic's default is 16 MiB


class GroupedMatmulPlan(NamedTuple):
    """How :func:`grouped_matmul` blocks a call: the row tile, the width of a
    weight block, the grid steps the call takes and the VMEM its blocks need."""
    tm: int
    tn: int
    steps: int
    vmem_bytes: int


def _gmm_vmem_bytes(tm: int, tn: int, K: int, itemsize: int) -> int:
    """Weight block, row tile and output tile, each double buffered by the
    pipeline, and the float32 product with the two selects over it."""
    return 2 * itemsize * (K * tn + tm * K + tm * tn) + 3 * 4 * tm * tn


def grouped_matmul_plan(M: int, K: int, N: int, G: int, itemsize: int,
                        tm: int | None = None, tn: int | None = None
                        ) -> GroupedMatmulPlan:
    """The blocking of ``[M, K] x [G, K, N]`` from its shapes alone (``tm`` or
    ``tn`` given: the plan of that choice).  A group's matrix is ONE block
    wherever VMEM holds it twice over, so the work list of ``m_tiles + G - 1``
    entries is walked once; a wider matrix is cut into the fewest column
    strips that fit, multiples of the 128 lanes that divide N, and the list is
    walked once a strip (the strips are the OUTER grid dimension: a row tile's
    output block stays in VMEM while consecutive groups fill it)."""
    if tm is None:
        tm = min(256, _round_up(M, 16))
    if tn is None:
        strips = [N] + [n for n in range(N - N % 128, 0, -128) if N % n == 0 and n < N]
        tn = next((n for n in strips
                   if _gmm_vmem_bytes(tm, n, K, itemsize) <= _GMM_VMEM_LIMIT), strips[-1])
    tn = min(tn, N)
    steps = -(-N // tn) * (_round_up(M, tm) // tm + G - 1)
    return GroupedMatmulPlan(tm, tn, steps, _gmm_vmem_bytes(tm, tn, K, itemsize))


@functools.partial(jax.jit, static_argnames=("tm", "tn", "interpret"))
def grouped_matmul(x, w, group_sizes, layer=None, *, tm=None, tn=None,
                   interpret=None):
    """``out[r] = x[r] @ w[g]`` for the rows r of group g, where the rows of
    x [M, K] are sorted by group and ``group_sizes`` [G] int32 sums to M (rows
    past the sum are undefined); w: [G, K, N], or the stacked matrices of
    every layer [L, G, K, N] with ``layer`` a traced index (under a scan a
    sliced ``w[layer]`` would be copied whole, every expert of it, each
    iteration).  One Pallas (Mosaic) kernel, named ``moe_expert_matmul`` in
    the profiler's trace; it reads ``w[g]`` only for groups that hold rows,
    once for every row tile they span.  ``tm`` and ``tn`` default to
    :func:`grouped_matmul_plan`'s: a group's whole matrix a grid step where
    it fits (a decode call of 64 experts is 64 steps; as strips of 512
    columns it was 384 or 256, every strip walking the list's empty entries
    again, and copies of 1.5 MB reached 77% of the HBM's bandwidth where one
    of 6.3 MB reaches 90%).  A jit of its own, so that layers share one
    lowering."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if w.ndim == 3:
        w, layer = w[None], 0
    M, K = x.shape
    _, G, _, N = w.shape
    tm, tn, _, _ = grouped_matmul_plan(M, K, N, G, x.dtype.itemsize, tm, tn)
    if N % tn or (not interpret and (tn % 128 or K % 128)):
        raise ValueError(f"grouped_matmul: N={N} must tile by tn={tn}, and K={K} "
                         "and tn by the 128 lanes")
    Mp = _round_up(M, tm)
    if Mp != M:
        x = jnp.pad(x, ((0, Mp - M), (0, 0)))
    m_tiles = Mp // tm
    work = _group_work(group_sizes.astype(jnp.int32), m_tiles, tm)
    W = m_tiles + G - 1
    with jax.named_scope("moe_expert_matmul"):
        out = pl.pallas_call(
            functools.partial(_gmm_kernel, tm=tm),
            out_shape=jax.ShapeDtypeStruct((Mp, N), x.dtype),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=6,
                grid=(N // tn, W),
                in_specs=[
                    pl.BlockSpec((tm, K), lambda n, i, g, t, s, e, c, l: (t[i], 0)),
                    pl.BlockSpec((None, None, K, tn),
                                 lambda n, i, g, t, s, e, c, l: (l[0], g[i], 0, n)),
                ],
                out_specs=pl.BlockSpec((tm, tn), lambda n, i, g, t, s, e, c, l: (t[i], n)),
            ),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
                vmem_limit_bytes=_GMM_VMEM_LIMIT),
            interpret=interpret,
            name="moe_expert_matmul",
        )(*work, jnp.asarray(layer, jnp.int32).reshape(1), x, w)
    return out[:M]


def _silu_gate(gu, dtype):
    """``silu(gate) * up`` of gate | up side by side, in float32."""
    gu = gu.astype(jnp.float32)
    f = gu.shape[-1] // 2
    return (jax.nn.silu(gu[..., :f]) * gu[..., f:]).astype(dtype)


def swiglu(x, w_gate_up, w_down):
    """``(silu(x W_g) * (x W_u)) W_d`` with W_g | W_u side by side in one
    matrix [D, 2F]; products accumulate in float32."""
    gu = jnp.dot(x, w_gate_up, preferred_element_type=jnp.float32)
    return jnp.dot(_silu_gate(gu, x.dtype), w_down, preferred_element_type=jnp.float32)


def sigmoid_topk_route(x32, w_router, bias, top_k: int, scale: float):
    """The ``noaux_tc`` router with one group: scores ``sigmoid(x W_g)`` in
    float32 at the highest matmul precision (a near tie decides which expert
    runs); the ``top_k`` largest of ``score + bias`` are chosen, and weighed
    by their scores WITHOUT the bias, normalised to sum to 1, times ``scale``.
    Returns (experts [T, k] int32, weights [T, k] float32)."""
    s = jax.nn.sigmoid(jnp.dot(
        x32.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, chosen = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    weights = picked / jnp.sum(picked, axis=-1, keepdims=True) * scale
    return chosen.astype(jnp.int32), weights


def softmax_topk_route(x32, w_router, bias, top_k: int, scale: float):
    """The router of the qwen2_moe / qwen3_moe lineage: a softmax over the
    router's whole width in float32 at the highest matmul precision, then the
    ``top_k`` largest (of ``probability + bias``: a zero bias leaves the
    choice to the probabilities), weighed by their probabilities WITHOUT the
    bias, renormalised over the chosen to sum to 1, times ``scale``.
    :func:`sigmoid_topk_route`'s signature and return."""
    s = jax.nn.softmax(jnp.dot(
        x32.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST), axis=-1)
    _, chosen = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    weights = picked / jnp.sum(picked, axis=-1, keepdims=True) * scale
    return chosen.astype(jnp.int32), weights


def dropless_moe(x32, p, *, top_k: int, scale: float, valid=None,
                 layer=None, held_from=None, interpret=None,
                 route=sigmoid_topk_route):
    """One expert layer over tokens x32 [T, D] (float32, already normed).
    ``route`` scores and chooses: :func:`sigmoid_topk_route` or
    :func:`softmax_topk_route`.

    ``p``: ``router`` [D, E] and ``router_bias`` [E] (float32), ``experts_gu``
    [E, D, 2F], ``experts_down`` [E, F, D] (or both stacked over layers, with
    ``layer`` the index: see :func:`grouped_matmul`), and where the layer has a
    shared expert ``shared_gu`` [D, 2F] and ``shared_down`` [F, D].  Returns (y [T, D] float32, tokens an expert
    [E] int32).  ``valid`` [T] bool leaves pad tokens (a prompt's bucket past
    its length, a decode slot nobody holds) out of the count AND out of the
    groups: their rows take the shared expert alone (nothing without one), so the experts only a pad
    token chose are not read (an idle slot keeps its last token: at half
    occupancy a decode step read 46 experts a layer where its active slots'
    tokens had chosen 27).

    ``held_from``: this chip's share of an expert-parallel layer.  The router
    keeps its whole width E and its ``top_k``; ``experts_gu`` / ``experts_down``
    hold the G matrices of experts ``held_from .. held_from + G - 1`` alone.
    A pair whose expert is absent goes to the same bin past every group as a
    pad token's, so nothing is read or computed for it, and what the absent
    experts would add is left out of ``y``; the weights stay normalised over
    all ``top_k`` chosen, held or not.  The count is then of the held experts
    [G].  On one chip the layer runs without its exchange.  ``None``: every
    expert of the router is here."""
    T, D = x32.shape
    E = p["experts_gu"].shape[-3]  # the groups: the experts whose matrices are here
    dtype = p["experts_gu"].dtype
    experts, weights = route(x32, p["router"], p["router_bias"], top_k, scale)
    flat = experts.reshape(-1)  # pair j belongs to token j // top_k
    if held_from is not None:
        local = flat - held_from
        flat = jnp.where((local >= 0) & (local < E), local, E)
    elif p["router"].shape[-1] != E:
        raise ValueError("the router is wider than the experts given: say which are held")
    if valid is not None:
        # A pad token's pairs sort last, under a bin of their own past every
        # group: no expert's matrix is read for a row nobody will look at.
        flat = jnp.where(jnp.repeat(valid, top_k), flat, E)
    order = jnp.argsort(flat, stable=True)
    load = jnp.bincount(flat, length=E + 1)[:E].astype(jnp.int32)
    x = x32.astype(dtype)
    rows = x[order // top_k]  # [T k, D], sorted by expert
    gu = grouped_matmul(rows, p["experts_gu"], load, layer, interpret=interpret)
    down = grouped_matmul(_silu_gate(gu, dtype), p["experts_down"], load, layer,
                          interpret=interpret)
    back = jnp.argsort(order)  # where pair j went
    pairs = down[back].reshape(T, top_k, D).astype(jnp.float32)
    if held_from is not None:  # rows past the groups' sum are undefined
        pairs = jnp.where((flat < E).reshape(T, top_k, 1), pairs, 0.0)
    elif valid is not None:
        pairs = jnp.where(valid[:, None, None], pairs, 0.0)
    routed = jnp.sum(pairs * weights[..., None], axis=1)
    if "shared_gu" in p:
        routed = routed + swiglu(x, p["shared_gu"], p["shared_down"])
    return routed, load
