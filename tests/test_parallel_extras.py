"""MoE (expert parallel), pipeline parallel, flash attention, checkpoint."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from moolib_tpu import parallel
from moolib_tpu.checkpoint import Checkpointer
from moolib_tpu.ops.flash_attention import flash_attention, flash_attention_packed


def test_switch_moe_routing_and_shapes():
    model = parallel.SwitchMoE(num_experts=4, ffn_dim=32, dtype=jnp.float32)
    x = jax.random.normal(jax.random.key(0), (2, 16, 8))
    params = model.init(jax.random.key(1), x)
    (out, aux), _ = jax.jit(lambda p, x: (model.apply(p, x), 0))(params, x)
    assert out.shape == x.shape
    assert np.isfinite(float(aux))
    # Routed output must differ from the residual input.
    assert not np.allclose(np.asarray(out), np.asarray(x))


def test_switch_moe_expert_parallel_on_mesh():
    mesh = parallel.make_mesh({"ep": 4, "dp": 2})
    model = parallel.SwitchMoE(num_experts=8, ffn_dim=64, dtype=jnp.float32)
    x = jax.random.normal(jax.random.key(0), (4, 32, 16))
    params = model.init(jax.random.key(1), x)
    spec = parallel.moe_param_spec("ep")
    sharded = {
        "params": {
            "router": jax.tree_util.tree_map(
                lambda p: jax.device_put(p, NamedSharding(mesh, P())),
                params["params"]["router"],
            ),
            "w_in": jax.device_put(
                params["params"]["w_in"], NamedSharding(mesh, spec["w_in"])
            ),
            "w_out": jax.device_put(
                params["params"]["w_out"], NamedSharding(mesh, spec["w_out"])
            ),
        }
    }
    out_sharded, aux = jax.jit(model.apply)(sharded, x)
    out_plain, aux2 = model.apply(params, x)
    np.testing.assert_allclose(
        np.asarray(out_sharded), np.asarray(out_plain), rtol=1e-4, atol=1e-4
    )


def test_pipeline_matches_sequential():
    mesh = parallel.make_mesh({"pp": 4, "dp": 2})
    S, M, Dim = 4, 6, 8
    rng = np.random.default_rng(0)
    ws = jnp.asarray(rng.normal(size=(S, Dim, Dim)).astype(np.float32) * 0.5)
    xs = jnp.asarray(rng.normal(size=(M, 3, Dim)).astype(np.float32))

    def stage_fn(w, x):
        return jnp.tanh(x @ w)

    out = parallel.pipeline_apply(stage_fn, ws, xs, mesh, axis_name="pp")
    expected = xs
    for s in range(S):
        expected = jnp.tanh(expected @ ws[s])
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected), rtol=1e-5, atol=1e-5)


def test_pipeline_circular_schedule_matches_sequential():
    """Circular (interleaved) schedule: L = v*S virtual stages laid
    round-robin over the ring; forward must equal applying all L layers in
    execution order.  v*M + S - 1 ticks vs GPipe's v*(M + S - 1)."""
    mesh = parallel.make_mesh({"pp": 4}, devices=jax.devices()[:4])
    S, V, M, Dim = 4, 2, 8, 8  # M % S == 0 required for circular
    L = V * S
    rng = np.random.default_rng(3)
    ws = jnp.asarray(rng.normal(size=(L, Dim, Dim)).astype(np.float32) * 0.4)
    xs = jnp.asarray(rng.normal(size=(M, 3, Dim)).astype(np.float32))

    def stage_fn(w, x):
        return jnp.tanh(x @ w)

    out = parallel.pipeline_apply(
        stage_fn, ws, xs, mesh, axis_name="pp", circular_repeats=V
    )
    expected = xs
    for j in range(L):
        expected = jnp.tanh(expected @ ws[j])
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected), rtol=1e-5, atol=1e-5)


def test_pipeline_circular_differentiable_with_remat_and_dp():
    """Circular schedule composes with dp in one mesh, trains (grads match
    the sequential composition), and remat=True doesn't change values."""
    mesh = parallel.make_mesh({"pp": 2, "dp": 2}, devices=jax.devices()[:4])
    S, V, M, B, Dim = 2, 3, 4, 4, 8
    L = V * S
    rng = np.random.default_rng(4)
    ws = jnp.asarray(rng.normal(size=(L, Dim, Dim)).astype(np.float32) * 0.4)
    xs = jnp.asarray(rng.normal(size=(M, B, Dim)).astype(np.float32))
    tgt = jnp.asarray(rng.normal(size=(M, B, Dim)).astype(np.float32))

    def stage_fn(w, x):
        return jnp.tanh(x @ w)

    def piped_loss(ws, remat, policy=None):
        out = parallel.pipeline_apply(
            stage_fn, ws, xs, mesh, axis_name="pp", data_axis="dp",
            circular_repeats=V, remat=remat, remat_policy=policy,
        )
        return jnp.mean((out - tgt) ** 2)

    def seq_loss(ws):
        out = xs
        for j in range(L):
            out = jnp.tanh(out @ ws[j])
        return jnp.mean((out - tgt) ** 2)

    g_seq = jax.grad(seq_loss)(ws)
    # remat_policy selects what the stage checkpoint saves; like remat
    # itself it must never change gradients.
    dots = jax.checkpoint_policies.checkpoint_dots
    for remat, policy in ((False, None), (True, None), (True, dots)):
        g_pipe = jax.grad(lambda w: piped_loss(w, remat, policy))(ws)
        np.testing.assert_allclose(
            np.asarray(g_pipe), np.asarray(g_seq), rtol=1e-4, atol=1e-5
        )


def test_pipeline_circular_rejects_bad_shapes():
    mesh = parallel.make_mesh({"pp": 4}, devices=jax.devices()[:4])
    ws = jnp.zeros((8, 4, 4))
    with pytest.raises(ValueError, match="microbatches % pp"):
        parallel.pipeline_apply(
            lambda w, x: x, ws, jnp.zeros((6, 2, 4)), mesh, circular_repeats=2
        )
    with pytest.raises(ValueError, match="leading axis"):
        parallel.pipeline_apply(
            lambda w, x: x, ws, jnp.zeros((8, 2, 4)), mesh, circular_repeats=3
        )


def test_flash_attention_gradients_match_dense():
    """flash_attention is differentiable (custom_vjp: pallas forward, pallas
    dq + dk/dv backward kernels) and its q/k/v cotangents match the dense
    path.  Regression: jax.grad through the raw pallas_call used to crash, so
    any model training with attention='flash' was broken."""
    rngs = jax.random.split(jax.random.key(7), 4)
    B, T, H, D = 2, 256, 2, 64
    q, k, v, g = (jax.random.normal(r, (B, T, H, D)) for r in rngs)
    for causal in (True, False):
        _, vjp_f = jax.vjp(lambda *a: flash_attention(*a, causal=causal), q, k, v)
        _, vjp_r = jax.vjp(lambda *a: parallel.full_attention(*a, causal=causal), q, k, v)
        for a, b, name in zip(vjp_f(g), vjp_r(g), "qkv"):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4,
                err_msg=f"causal={causal} d{name}",
            )


def test_flash_backward_pallas_matches_blockwise_oracle(monkeypatch):
    """The pallas backward kernels against the blockwise-jax VJP they
    replaced (kept as the selectable oracle via MOOLIB_TPU_FLASH_BWD)."""
    rngs = jax.random.split(jax.random.key(3), 4)
    B, T, H, D = 1, 256, 2, 64
    q, k, v, g = (jax.random.normal(r, (B, T, H, D)) for r in rngs)
    grads = {}
    for mode in ("pallas", "jax"):
        monkeypatch.setenv("MOOLIB_TPU_FLASH_BWD", mode)
        _, vjp = jax.vjp(lambda *a: flash_attention(*a, causal=True), q, k, v)
        grads[mode] = vjp(g)
    for a, b, name in zip(grads["pallas"], grads["jax"], "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4, err_msg=f"d{name}"
        )


def test_flash_attention_rejects_bad_explicit_blocks():
    """Caller-supplied block sizes that can't tile the sequence raise instead
    of silently rerouting to the dense path (ADVICE round-2)."""
    q = jnp.zeros((1, 256, 2, 64))
    with pytest.raises(ValueError, match="block_q"):
        flash_attention(q, q, q, block_q=64)
    with pytest.raises(ValueError, match="divide"):
        flash_attention(q, q, q, block_q=192, block_k=128)
    # Non-multiple-of-128 blocks are rejected even when they divide T: the
    # backward's block re-derivation scans 128-multiples only.
    q192 = jnp.zeros((1, 192, 2, 64))
    with pytest.raises(ValueError, match="multiples of 128"):
        flash_attention(q192, q192, q192, block_q=192)
    # But an unusable AUTO-selected block keeps the silent dense fallback,
    # even when the *other* block was passed explicitly and is fine.
    k = jnp.zeros((1, 160, 2, 64))  # no 128-multiple divides 160
    out = flash_attention(q, k, k, block_q=128, causal=False)
    assert out.shape == q.shape


def test_flash_backward_with_block_not_dividing_cap():
    """T whose auto block exceeds the backward's 512 cap but isn't divisible
    by 512 (e.g. 1280 -> forward block_k 640): the backward must re-derive a
    dividing block instead of dropping the tail kv block."""
    rngs = jax.random.split(jax.random.key(5), 4)
    B, T, H, D = 1, 1280, 1, 64
    q, k, v, g = (jax.random.normal(r, (B, T, H, D)) for r in rngs)
    _, vjp_f = jax.vjp(lambda *a: flash_attention(*a, causal=True), q, k, v)
    _, vjp_r = jax.vjp(lambda *a: parallel.full_attention(*a, causal=True), q, k, v)
    for a, b, name in zip(vjp_f(g), vjp_r(g), "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4, err_msg=f"d{name}"
        )


def test_flash_attention_matches_dense():
    rng = np.random.default_rng(0)
    B, T, H, D = 2, 256, 2, 32
    q = jnp.asarray(rng.normal(size=(B, T, H, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, T, H, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, T, H, D)).astype(np.float32))
    for causal in (True, False):
        out = flash_attention(q, k, v, causal=causal)
        ref = parallel.full_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def _attention_case(B, T, H, Hk, D, seed):
    """q, K and V at ``Hk`` heads, and cotangents of the result and of the
    row logsumexp."""
    rngs = jax.random.split(jax.random.key(seed), 5)
    q, g = (jax.random.normal(r, (B, T, H, D)) for r in rngs[:2])
    k, v = (jax.random.normal(r, (B, T, Hk, D)) for r in rngs[2:4])
    return q, k, v, g, jax.random.normal(rngs[4], (B, T, H))


def _packed(*arrays):
    """[B, T, heads, D] arrays side by side as [B, T, columns]."""
    return jnp.concatenate([x.reshape(*x.shape[:2], -1) for x in arrays], axis=-1)


def _traces():
    from moolib_tpu import telemetry

    values = telemetry.get_registry().counter_values()
    return {p: values.get('flash_attention_traces_total{path="%s"}' % p, 0.0)
            for p in ("in_place", "head_major")}


# A head size of 128: the kernels index q, k, v as [B, T, H * D] where they
# lie.  Every batch size, head count and length of the list at least twice,
# T = 1,280 (forward blocks of 640, backward of 256) and 2,048 (512 x 1,024,
# 512 x 512), both masks.
@pytest.mark.parametrize("B,H,T,causal", [
    (1, 1, 1280, True), (1, 3, 2048, False), (3, 1, 2048, True), (3, 3, 1280, False),
    (1, 16, 2048, True), (3, 16, 1280, True), (1, 16, 1280, False), (3, 3, 2048, True)])
def test_flash_in_place_matches_the_blockwise_oracle(B, H, T, causal):
    """Forward, the row logsumexp, dq, dk and dv of the in-place kernels,
    with a cotangent on the logsumexp too (it folds into ``delta``), against
    the pure-jax streaming softmax at the same blocks."""
    from moolib_tpu.ops.flash_attention import _auto_blocks, _blockwise_attention

    q, k, v, g, g_lse = _attention_case(B, T, H, H, 128, seed=B * H + T)
    before = _traces()
    (out, lse), vjp = jax.vjp(
        lambda *a: flash_attention(*a, causal=causal, return_lse=True), q, k, v)
    assert _traces()["in_place"] > before["in_place"]
    assert _traces()["head_major"] == before["head_major"]
    (want, want_lse), want_vjp = jax.vjp(
        lambda *a: _blockwise_attention(*a, causal, *_auto_blocks(T, T), return_lse=True),
        q, k, v)
    assert lse.shape == (B, T, H) and lse.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want_lse), rtol=2e-5, atol=2e-5)
    for a, b, name in zip(vjp((g, g_lse)), want_vjp((g, g_lse)), "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4, err_msg=f"d{name}")


# ``length``: the first positions of a bucket are real (ISSUE 60).  T = 256
# tiles, 320 and 200 do not (one partial block of 384 and of 256 rows; with
# blocks of 128, three blocks the last partial); a length of 1, a block's edge,
# one past it, and T.
@pytest.mark.parametrize("entry,T,length,H,Hk,blocks", [
    ("packed", 256, 1, 2, 2, None), ("packed", 256, 128, 2, 2, None),
    ("packed", 256, 129, 2, 2, None), ("packed", 256, 256, 2, 2, None),
    ("packed", 320, 128, 2, 2, None), ("packed", 320, 320, 2, 2, None),
    ("packed", 200, 129, 4, 2, None), ("packed", 200, 200, 2, 2, None),
    ("arrays", 256, 129, 2, 2, None), ("arrays", 320, 1, 2, 2, None),
    ("arrays", 320, 129, 4, 2, 128), ("arrays", 320, 320, 2, 2, 128),
    ("arrays", 200, 128, 2, 2, 128), ("arrays", 200, 200, 2, 1, None),
])
def test_flash_with_a_length_attends_over_the_real_rows_and_writes_zeros_past_them(
        entry, T, length, H, Hk, blocks):
    """Rows below ``length`` equal dense attention over the first ``length``
    positions; rows at or past it are exactly zero and nothing is NaN, though
    the operands past ``length`` hold NaN; the length is data (a traced scalar
    of one jitted program); no gradient is offered."""
    q, k, v, _, _ = _attention_case(1, T, H, Hk, 128, seed=T + length)
    real = (jnp.arange(T) < length)[None, :, None, None]
    qn, kn, vn = (jnp.where(real, x, jnp.nan) for x in (q, k, v))
    if entry == "packed":
        call = lambda q, k, v, n: flash_attention_packed(
            _packed(q, k, v), H, Hk, length=n).reshape(1, T, H, 128)
    else:
        call = lambda q, k, v, n: flash_attention(
            q, k, v, block_q=blocks, block_k=blocks, length=n)
    before = _traces()
    out = np.asarray(jax.jit(call)(qn, kn, vn, jnp.int32(length)))
    assert _traces()["in_place"] == before["in_place"] + 1
    want = parallel.full_attention(
        q[:, :length], *(jnp.repeat(x[:, :length], H // Hk, axis=2) for x in (k, v)),
        causal=True)
    assert not np.isnan(out).any()
    np.testing.assert_allclose(out[:, :length], np.asarray(want), rtol=2e-5, atol=2e-5)
    assert (out[:, length:] == 0).all()
    with pytest.raises(NotImplementedError, match="length=...\\) has no backward"):
        jax.grad(lambda q: call(q, k, v, jnp.int32(length)).sum())(q)


def test_flash_with_a_length_takes_the_dense_path_under_128_rows_and_refuses_the_rest():
    """A bucket of 16-64 rows keeps the dense path (counted), with the same
    contract: zeros past the length, NaN there unread.  ``length`` comes
    without ``return_lse``, ``window``, a mesh, or a mask that is not causal."""
    from moolib_tpu import telemetry
    from moolib_tpu.ops.flash_attention import length_call_rides_kernel

    assert [length_call_rides_kernel(t) for t in (16, 64, 127, 128, 192, 1984)] == [
        False, False, False, True, True, True]
    reroutes = lambda: telemetry.get_registry().counter_values().get(
        "flash_dense_reroutes_total", 0.0)
    q, k, v, _, _ = _attention_case(2, 64, 4, 2, 8, seed=3)
    real = (jnp.arange(64) < 11)[None, :, None, None]
    before = reroutes()
    out = np.asarray(flash_attention(
        *(jnp.where(real, x, jnp.nan) for x in (q, k, v)), length=jnp.int32(11)))
    assert reroutes() == before + 1
    want = parallel.full_attention(
        q[:, :11], *(jnp.repeat(x[:, :11], 2, axis=2) for x in (k, v)), causal=True)
    np.testing.assert_allclose(out[:, :11], np.asarray(want), rtol=2e-5, atol=2e-5)
    assert (out[:, 11:] == 0).all()
    for kw in (dict(return_lse=True), dict(window=8), dict(causal=False)):
        with pytest.raises(ValueError, match="length=...\\) is causal self-attention"):
            flash_attention(q, k, v, length=jnp.int32(11), **kw)


@pytest.mark.parametrize("Tq,Tk", [(256, 512), (512, 256), (384, 384)])
def test_flash_causal_copies_no_block_it_skips_at_unequal_lengths(Tq, Tk):
    """Causal, blocks of 128: a key block above the diagonal is neither
    computed nor copied (its step's index map points at the last block that
    is), in the dk/dv sweep a query block before the key block likewise, and
    with more keys than queries the last key blocks see no query at all: dk
    and dv are zero there.  Against the oracle at the same blocks."""
    from moolib_tpu.ops.flash_attention import _blockwise_attention

    rngs = jax.random.split(jax.random.key(Tq + Tk), 4)
    q, g = (jax.random.normal(r, (2, Tq, 2, 128)) for r in rngs[:2])
    k, v = (jax.random.normal(r, (2, Tk, 2, 128)) for r in rngs[2:])
    out, vjp = jax.vjp(
        lambda *a: flash_attention(*a, causal=True, block_q=128, block_k=128), q, k, v)
    want, want_vjp = jax.vjp(lambda *a: _blockwise_attention(*a, True, 128, 128), q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-5, atol=2e-5)
    for a, b, name in zip(vjp(g), want_vjp(g), "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4, err_msg=f"d{name}")
    if Tk > Tq:
        assert not np.asarray(vjp(g)[1][:, Tq:]).any()


def _pallas_calls(jaxpr):
    """Every ``pallas_call`` equation under ``jaxpr``, and every primitive's
    name outside the kernels' own bodies."""
    calls, names = [], []
    for eqn in jaxpr.eqns:
        names.append(eqn.primitive.name)
        if eqn.primitive.name == "pallas_call":
            calls.append(eqn)
            continue
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    more, inner = _pallas_calls(sub)
                    calls += more
                    names += inner
    return calls, names


@pytest.mark.parametrize("H,Hk,D,causal", [
    (16, 4, 128, True), (2, 2, 128, False), (4, 1, 256, True), (4, 2, 64, True)])
def test_flash_packed_equals_three_arrays(H, Hk, D, causal):
    """``flash_attention_packed`` of [q heads | k heads | v heads] against
    ``flash_attention`` of the three arrays with K and V repeated a group:
    the result, and the projection's cotangent as the three side by side
    with a shared head's summed over its group.  D = 64 is the three slices
    through the head-major form."""
    B, T = 2, 256
    q, k, v, g, _ = _attention_case(B, T, H, Hk, D, seed=H + Hk)
    qkv = _packed(q, k, v)
    out, vjp = jax.vjp(lambda x: flash_attention_packed(x, H, Hk, causal=causal), qkv)
    assert out.shape == (B, T, H * D)

    def repeated(q, k, v):
        k, v = (jnp.repeat(x, H // Hk, axis=2) for x in (k, v))
        return flash_attention(q, k, v, causal=causal)

    want, want_vjp = jax.vjp(repeated, q, k, v)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(want.reshape(B, T, H * D)), rtol=2e-6, atol=2e-6)
    (d_qkv,) = vjp(g.reshape(B, T, H * D))
    np.testing.assert_allclose(
        np.asarray(d_qkv), np.asarray(_packed(*want_vjp(g))), rtol=2e-5, atol=2e-5)
    # K and V at their own head count take the same kernels.
    grouped, grouped_vjp = jax.vjp(lambda *a: flash_attention(*a, causal=causal), q, k, v)
    np.testing.assert_array_equal(np.asarray(grouped.reshape(B, T, H * D)), np.asarray(out))
    np.testing.assert_allclose(
        np.asarray(_packed(*grouped_vjp(g))), np.asarray(d_qkv), rtol=1e-6, atol=1e-6)


def test_flash_packed_hands_the_kernels_the_projection_itself():
    """16 query heads over 4 K/V heads, forward and backward: each of the
    three kernels takes the one packed array as q, k and v, and outside the
    kernels nothing slices, repeats or gathers; the backward's one copy is the
    concatenation of dq, dk and dv (which XLA fuses into its consumers)."""
    B, T, H, Hk, D = 1, 256, 16, 4, 128
    qkv = jnp.zeros((B, T, (H + 2 * Hk) * D), jnp.bfloat16)
    g = jnp.zeros((B, T, H * D), jnp.bfloat16)
    pulled_back = lambda x, g: jax.vjp(lambda x: flash_attention_packed(x, H, Hk), x)[1](g)
    calls, names = _pallas_calls(jax.make_jaxpr(pulled_back)(qkv, g).jaxpr)
    assert len(calls) == 3
    for call in calls:
        q, k, v = call.invars[:3]
        assert q is k is v and q.aval.shape == qkv.shape
    assert names.count("concatenate") == 1
    assert not {"slice", "gather", "dynamic_slice", "broadcast_in_dim"} & set(names), names
    # forward, dq: a program a query head; dk/dv: a K/V head, its group's q blocks innermost
    assert [call.params["grid_mapping"].grid for call in calls] == [
        (16, 1, 1), (16, 1, 1), (4, 1, 4)]
    assert [[o.aval.shape for o in call.outvars] for call in calls] == [
        [(B, T, H * D), (B * H, 1, T)], [(B, T, H * D)], [(B, T, Hk * D)] * 2]


@pytest.mark.parametrize("entry,D,window,path", [
    ("arrays", 128, None, "in_place"), ("arrays", 256, None, "in_place"),
    ("arrays", 64, None, "head_major"), ("arrays", 128, 128, "head_major"),
    ("packed", 128, None, "in_place"), ("packed", 64, None, "head_major")])
def test_flash_path_is_chosen_by_head_size_and_counted(entry, D, window, path):
    """``flash_attention_traces_total{path}``: a head size on the 128 lanes is
    indexed in place; any other, and every windowed call, goes through
    head-major copies (``interpret=False`` is traced and lowered for no
    platform: nothing runs)."""
    x = jnp.zeros((2, 256, 2, D), jnp.bfloat16)
    before = _traces()
    if entry == "packed":
        jax.make_jaxpr(lambda x: flash_attention_packed(x, 2, interpret=False))(_packed(x, x, x))
    else:
        jax.make_jaxpr(lambda x: flash_attention(x, x, x, window=window, interpret=False))(x)
    after = _traces()
    assert {p: after[p] - before[p] for p in after} == {p: float(p == path) for p in after}


@pytest.mark.parametrize("D", [128, 64])
@pytest.mark.parametrize("entry", ["arrays", "packed"])
def test_flash_grouped_backward_matches_the_oracle(monkeypatch, entry, D):
    """4 query heads over 2 K/V heads through both forms and both entries:
    the pallas backward, which sums a shared head's gradient in its float32
    scratch, against the blockwise-jax VJP, which repeats K and V and lets
    autodiff sum."""
    B, T, H, Hk = 2, 256, 4, 2
    q, k, v, g, g_lse = _attention_case(B, T, H, Hk, D, seed=D)
    grads = {}
    for mode in ("pallas", "jax"):
        monkeypatch.setenv("MOOLIB_TPU_FLASH_BWD", mode)
        if entry == "packed":
            _, vjp = jax.vjp(lambda x: flash_attention_packed(x, H, Hk), _packed(q, k, v))
            grads[mode] = vjp(g.reshape(B, T, H * D))
        else:
            _, vjp = jax.vjp(lambda *a: flash_attention(*a, return_lse=True), q, k, v)
            grads[mode] = vjp((g, g_lse))
    for a, b in zip(grads["pallas"], grads["jax"]):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4)


def test_flash_attention_refuses_heads_that_do_not_group():
    q = jnp.zeros((1, 256, 4, 128))
    with pytest.raises(ValueError, match="4 query heads over K/V heads 3"):
        flash_attention(q, q[:, :, :3], q[:, :, :3])
    with pytest.raises(ValueError, match="are not 4 \\+ 2 x 3 heads"):
        flash_attention_packed(jnp.zeros((1, 256, 10 * 128)), 4, 3)


def test_checkpointer_roundtrip(tmp_path):
    ck = Checkpointer(str(tmp_path / "ckpt"), max_to_keep=2)
    state = {"params": {"w": jnp.arange(6.0).reshape(2, 3)}, "step": 7}
    ck.save(7, state)
    ck.save(10, {"params": {"w": jnp.zeros((2, 3))}, "step": 10})
    ck.save(12, {"params": {"w": jnp.ones((2, 3))}, "step": 12})
    assert ck.all_steps() == [10, 12]  # gc keeps 2
    restored = ck.restore()
    np.testing.assert_allclose(np.asarray(restored["params"]["w"]), 1.0)
    assert int(restored["step"]) == 12
    old = ck.restore(step=10)
    np.testing.assert_allclose(np.asarray(old["params"]["w"]), 0.0)


def test_checkpointer_pickle_fallback(tmp_path):
    ck = Checkpointer(str(tmp_path / "ckpt2"), use_orbax=False)
    ck.save(1, {"x": np.arange(3)})
    out = ck.restore()
    np.testing.assert_array_equal(out["x"], np.arange(3))


def test_pipeline_dp_composed_in_one_mesh():
    """VERDICT round-1 ask #5 (PP combined-mesh story): pp and dp in ONE
    mesh, with each dp slice streaming its own microbatch batch shard."""
    mesh = parallel.make_mesh({"pp": 4, "dp": 2})
    S, M, B, Dim = 4, 5, 4, 8
    rng = np.random.default_rng(1)
    ws = jnp.asarray(rng.normal(size=(S, Dim, Dim)).astype(np.float32) * 0.5)
    xs = jnp.asarray(rng.normal(size=(M, B, Dim)).astype(np.float32))

    def stage_fn(w, x):
        return jnp.tanh(x @ w)

    out = parallel.pipeline_apply(
        stage_fn, ws, xs, mesh, axis_name="pp", data_axis="dp"
    )
    expected = xs
    for s in range(S):
        expected = jnp.tanh(expected @ ws[s])
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected), rtol=1e-5, atol=1e-5)


def test_pipeline_is_differentiable_gpipe_training():
    """The tick loop is a lax.scan, so jax.grad flows through the schedule:
    GPipe *training*, not just inference. Gradients must match the
    sequential composition's."""
    mesh = parallel.make_mesh({"pp": 4}, devices=jax.devices()[:4])
    S, M, Dim = 4, 3, 8
    rng = np.random.default_rng(2)
    ws = jnp.asarray(rng.normal(size=(S, Dim, Dim)).astype(np.float32) * 0.5)
    xs = jnp.asarray(rng.normal(size=(M, 2, Dim)).astype(np.float32))
    tgt = jnp.asarray(rng.normal(size=(M, 2, Dim)).astype(np.float32))

    def stage_fn(w, x):
        return jnp.tanh(x @ w)

    def piped_loss(ws):
        out = parallel.pipeline_apply(stage_fn, ws, xs, mesh, axis_name="pp")
        return jnp.mean((out - tgt) ** 2)

    def seq_loss(ws):
        out = xs
        for s in range(S):
            out = jnp.tanh(out @ ws[s])
        return jnp.mean((out - tgt) ** 2)

    g_pipe = jax.grad(piped_loss)(ws)
    g_seq = jax.grad(seq_loss)(ws)
    np.testing.assert_allclose(np.asarray(g_pipe), np.asarray(g_seq), rtol=1e-4, atol=1e-5)
