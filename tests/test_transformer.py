"""Transformer LM: attention-mode parity, causality, ring over the mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from moolib_tpu import parallel
from moolib_tpu.models.transformer import TransformerLM
from moolib_tpu.utils.batchsize import find_batch_size


def _model(attention, dtype=jnp.float32, moe_num_experts=0):
    return TransformerLM(
        vocab_size=64, d_model=64, num_heads=2, num_layers=2,
        attention=attention, dtype=dtype, moe_num_experts=moe_num_experts,
    )


def test_dense_and_flash_agree():
    tokens = jax.random.randint(jax.random.key(0), (2, 128), 0, 64)
    dense = _model("dense")
    flash = _model("flash")
    params = dense.init(jax.random.key(1), tokens)
    out_d = dense.apply(params, tokens)
    out_f = flash.apply(params, tokens)
    assert out_d.shape == (2, 128, 64)
    np.testing.assert_allclose(np.asarray(out_d), np.asarray(out_f), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("pos", ["learned", "rotary"])
@pytest.mark.parametrize("kv_heads", [None, 1])
@pytest.mark.parametrize("mesh_spec", [None, "dp=2"])
def test_flash_block_at_a_lane_aligned_head_size_agrees_with_dense(pos, kv_heads, mesh_spec):
    """d_model 256 as 2 heads of 128: ``Block`` hands the kernels its ``qkv``
    projection as it leaves the matmul (learned positions) or the rotated q
    and k beside v at their own head counts (rotary), one or two K/V heads,
    alone and under ``shard_map`` over ``dp``.  Logits and the parameters'
    gradients against the dense model, which repeats K and V."""
    def mk(attention):
        return TransformerLM(
            vocab_size=64, d_model=256, num_heads=2, num_layers=1, num_kv_heads=kv_heads,
            attention=attention, dtype=jnp.float32, pos_embedding=pos, max_len=128)

    mesh = mesh_spec and parallel.make_mesh({"dp": 2}, devices=jax.devices()[:2])
    tokens = jax.random.randint(jax.random.key(0), (2, 128), 0, 64)
    params = mk("dense").init(jax.random.key(1), tokens)

    def loss(params, model, **kw):
        logits = model.apply(params, tokens, **kw)
        return jnp.mean(jax.nn.log_softmax(logits)[..., 0]), logits

    (_, want), want_grads = jax.value_and_grad(loss, has_aux=True)(params, mk("dense"))
    kw = {"mesh": mesh} if mesh else {}
    (_, got), grads = jax.jit(jax.value_and_grad(
        lambda p: loss(p, mk("flash"), **kw), has_aux=True))(params)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)
    for a, b in zip(jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(want_grads)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-5)


def test_causality():
    """Future tokens must not affect past logits."""
    model = _model("flash")
    t1 = jax.random.randint(jax.random.key(0), (1, 128), 0, 64)
    params = model.init(jax.random.key(1), t1)
    t2 = t1.at[0, 100:].set((t1[0, 100:] + 7) % 64)
    o1 = model.apply(params, t1)
    o2 = model.apply(params, t2)
    np.testing.assert_allclose(
        np.asarray(o1[0, :100]), np.asarray(o2[0, :100]), rtol=1e-4, atol=1e-4
    )
    assert not np.allclose(np.asarray(o1[0, 100:]), np.asarray(o2[0, 100:]))


def test_ring_attention_model_on_mesh():
    mesh = parallel.make_mesh({"sp": 8})
    tokens = jax.random.randint(jax.random.key(0), (1, 64), 0, 64)
    dense = _model("dense")
    ring = _model("ring")
    params = dense.init(jax.random.key(1), tokens)
    out_d = dense.apply(params, tokens)
    out_r = ring.apply(params, tokens, mesh=mesh)
    np.testing.assert_allclose(np.asarray(out_d), np.asarray(out_r), rtol=2e-4, atol=2e-4)


def test_ring_attention_with_fused_loss_trains_on_mesh():
    """The full long-context training step: ring attention over the sp mesh
    composed with the chunked-vocab head loss — value and gradients must
    match the dense model with the naive materialized loss."""
    from moolib_tpu.ops.xent import lm_head_xent

    mesh = parallel.make_mesh({"sp": 8})
    tokens = jax.random.randint(jax.random.key(0), (2, 64), 0, 64)
    dense = _model("dense")
    ring = _model("ring")
    params = dense.init(jax.random.key(1), tokens)

    def naive_loss(p):
        logits = dense.apply(p, tokens)
        logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), -1)
        return -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1).mean()

    def fused_ring_loss(p, t):
        return lm_head_xent(ring, p, t, chunk_size=16, mesh=mesh)

    want, gwant = jax.value_and_grad(naive_loss)(params)
    # Mesh-consistent placement, as the lm example's mesh path does: the
    # ring shard_map yields mesh-committed arrays, which must not mix with
    # single-device operands.
    from jax.sharding import NamedSharding, PartitionSpec as P

    rep = NamedSharding(mesh, P())
    got, ggot = jax.jit(jax.value_and_grad(fused_ring_loss))(
        jax.device_put(params, rep), jax.device_put(tokens, rep)
    )
    np.testing.assert_allclose(float(got), float(want), rtol=2e-4)
    flat_want = dict(jax.tree_util.tree_leaves_with_path(gwant))
    for path, leaf in jax.tree_util.tree_leaves_with_path(ggot):
        np.testing.assert_allclose(
            np.asarray(leaf), np.asarray(flat_want[path]), rtol=5e-3,
            atol=1e-4, err_msg=jax.tree_util.keystr(path),
        )


def test_rotary_dense_flash_parity_and_causality():
    """RoPE applies to q/k before attention, so dense and flash must still
    agree; causality must still hold; and a rotary model runs past max_len
    (no learned table to exhaust — the long-context point of RoPE)."""
    from moolib_tpu.models.transformer import TransformerLM

    def mk(attention):
        return TransformerLM(
            vocab_size=64, d_model=64, num_heads=2, num_layers=2,
            attention=attention, dtype=jnp.float32, pos_embedding="rotary",
            max_len=64,
        )

    dense, flash = mk("dense"), mk("flash")
    tokens = jax.random.randint(jax.random.key(0), (2, 128), 0, 64)  # T > max_len
    params = dense.init(jax.random.key(1), tokens)
    assert "pos" not in params["params"]  # no learned table
    out_d = dense.apply(params, tokens)
    out_f = flash.apply(params, tokens)
    np.testing.assert_allclose(np.asarray(out_d), np.asarray(out_f), rtol=2e-4, atol=2e-4)
    # Causality: edits after position 100 cannot change earlier logits.
    t2 = tokens.at[0, 100:].set((tokens[0, 100:] + 7) % 64)
    o2 = dense.apply(params, t2)
    np.testing.assert_allclose(
        np.asarray(out_d[0, :100]), np.asarray(o2[0, :100]), rtol=1e-4, atol=1e-4
    )


def test_rotary_scores_are_relative():
    """The RoPE invariant: rotating q and k leaves q·k dependent only on the
    relative offset, so shifting a sequence shifts the (non-edge) attention
    pattern rather than changing it."""
    from moolib_tpu.models.transformer import apply_rotary

    x = jax.random.normal(jax.random.key(0), (1, 16, 1, 8))
    q, k = apply_rotary(x), apply_rotary(x)
    # score(i, j) for the original at (i, j) equals score(i+s, j+s) when the
    # inputs are shifted by s positions.
    s = 4
    xs = jnp.roll(x, s, axis=1)
    qs, ks = apply_rotary(xs), apply_rotary(xs)
    orig = jnp.einsum("bqhd,bkhd->bqk", q, k)
    shif = jnp.einsum("bqhd,bkhd->bqk", qs, ks)
    np.testing.assert_allclose(
        np.asarray(orig[0, : 16 - s, : 16 - s]),
        np.asarray(shif[0, s:, s:]),
        rtol=1e-4,
        atol=1e-5,
    )


def test_kv_cache_generate_matches_full_reforwarding():
    """Greedy decoding against the KV cache must produce exactly the tokens
    that naive full re-forwarding (O(T^2) per token) produces — for both
    position encodings."""
    from moolib_tpu.models.transformer import generate

    for pos in ("learned", "rotary"):
        model = TransformerLM(
            vocab_size=64, d_model=32, num_heads=2, num_layers=2,
            attention="dense", dtype=jnp.float32, pos_embedding=pos, max_len=64,
        )
        prompt = jax.random.randint(jax.random.key(0), (2, 12), 0, 64)
        params = model.init(jax.random.key(1), prompt)

        toks = prompt
        for _ in range(8):
            logits = model.apply(params, toks)
            nxt = jnp.argmax(logits[:, -1], axis=-1)
            toks = jnp.concatenate([toks, nxt[:, None].astype(toks.dtype)], axis=1)

        out = generate(model, params, prompt, max_new_tokens=8)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(toks), err_msg=pos)


def test_generate_respects_cache_capacity_and_samples():
    from moolib_tpu.models.transformer import generate

    model = TransformerLM(
        vocab_size=64, d_model=32, num_heads=2, num_layers=2,
        attention="dense", dtype=jnp.float32, max_len=16,
    )
    prompt = jax.random.randint(jax.random.key(0), (1, 8), 0, 64)
    params = model.init(jax.random.key(1), prompt)
    import pytest

    with pytest.raises(ValueError, match="cache capacity"):
        generate(model, params, prompt, max_new_tokens=9)
    out = generate(
        model, params, prompt, max_new_tokens=8, temperature=1.0,
        rng=jax.random.key(2),
    )
    assert out.shape == (1, 16)
    assert (np.asarray(out) >= 0).all() and (np.asarray(out) < 64).all()


def test_moe_forward_sows_aux_loss():
    model = _model("dense", moe_num_experts=4)
    tokens = jax.random.randint(jax.random.key(0), (2, 32), 0, 64)
    params = model.init(jax.random.key(1), tokens)
    # block1 (every 2nd) has a SwitchMoE FFN; block0 keeps the dense FFN.
    assert "moe" in params["params"]["block1"]
    assert "moe" not in params["params"]["block0"]
    logits, col = model.apply(params, tokens, mutable=["losses"])
    assert np.isfinite(np.asarray(logits)).all()
    aux = jax.tree_util.tree_leaves(col["losses"])
    assert aux and float(sum(jnp.sum(a) for a in aux)) > 0.0  # ~E*sum(d*p) >= 1


def test_moe_sharded_over_ep_matches_single_device():
    mesh = parallel.make_mesh({"dp": 2, "ep": 4})
    model = _model("dense", moe_num_experts=4)
    tokens = jax.random.randint(jax.random.key(0), (4, 32), 0, 64)
    params = model.init(jax.random.key(1), tokens)
    ref = model.apply(params, tokens)

    from jax.sharding import NamedSharding, PartitionSpec as P

    p_sh = parallel.moe_shardings(params, mesh, "ep")
    # Expert leaves got the ep spec, the rest stayed replicated.
    moe_sh = params["params"]["block1"]["moe"]
    assert parallel.moe_shardings(moe_sh, mesh, "ep")["w_in"].spec == P("ep", None, None)
    tok_sh = NamedSharding(mesh, P("dp", None))
    out = jax.jit(model.apply, in_shardings=(p_sh, tok_sh))(params, tokens)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), rtol=2e-4, atol=2e-4)


def test_moe_shardings_compose_with_tp_fsdp_base():
    """EP over ep + TP/FSDP over tp/dp from auto_shardings in ONE mesh: the
    expert leaves take the ep spec, everything else keeps the base spec, and
    the jitted sharded apply still matches single-device numerics."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = parallel.make_mesh({"dp": 2, "tp": 2, "ep": 2})
    model = _model("dense", moe_num_experts=4)
    tokens = jax.random.randint(jax.random.key(0), (4, 32), 0, 64)
    params = model.init(jax.random.key(1), tokens)
    base = parallel.auto_shardings(params, mesh)
    p_sh = parallel.moe_shardings(params, mesh, "ep", base=base)
    flat = jax.tree_util.tree_leaves_with_path(p_sh)
    specs = {
        "/".join(str(getattr(k, "key", k)) for k in path): s.spec
        for path, s in flat
    }
    assert specs["params/block1/moe/w_in"] == P("ep", None, None)
    assert specs["params/block1/moe/w_out"] == P("ep", None, None)
    # Non-expert leaves keep the auto_shardings TP spec (last axis over tp).
    assert specs["params/block0/qkv/kernel"][-1] == "tp"
    tok_sh = NamedSharding(mesh, P("dp", None))
    out = jax.jit(model.apply, in_shardings=(p_sh, tok_sh))(params, tokens)
    ref = model.apply(params, tokens)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), rtol=2e-4, atol=2e-4)


def test_find_batch_size_runs():
    def make_batch(n):
        return (jnp.zeros((n, 16), jnp.float32),)

    def fn(x):
        return (x @ jnp.ones((16, 16))).sum()

    bs = find_batch_size(make_batch, fn, start=4, max_batch=64, iters=2)
    assert 4 <= bs <= 64


def test_remat_matches_no_remat_gradients():
    """remat=True recomputes block activations in the backward; outputs and
    gradients must match the stored-activation path exactly (same params
    tree — nn.remat preserves module structure)."""
    tokens = jax.random.randint(jax.random.key(0), (2, 128), 0, 64)
    base = _model("flash")
    remat = TransformerLM(
        vocab_size=64, d_model=64, num_heads=2, num_layers=2,
        attention="flash", dtype=jnp.float32, remat=True,
    )
    params = base.init(jax.random.key(1), tokens)

    def loss(m):
        def f(p):
            logits = m.apply(p, tokens)
            logp = jax.nn.log_softmax(logits[:, :-1], -1)
            return -jnp.take_along_axis(logp, tokens[:, 1:, None], -1).mean()
        return f

    l0, g0 = jax.value_and_grad(loss(base))(params)
    l1, g1 = jax.value_and_grad(loss(remat))(params)
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-6)
    assert jax.tree_util.tree_structure(g0) == jax.tree_util.tree_structure(g1)
    for a, b in zip(
        jax.tree_util.tree_leaves(g0), jax.tree_util.tree_leaves(g1)
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5
        )


def test_remat_policies_match_no_remat_gradients():
    """Selective policies ("dots" saves matmul outputs so the MXU never
    re-runs; "dots_no_batch" saves only weight@activation dots) change what
    the backward recomputes, never what it computes: loss and gradients must
    match the stored-activation path.  An unknown policy must fail loudly —
    bench rows are keyed by the policy string."""
    import pytest

    tokens = jax.random.randint(jax.random.key(0), (2, 128), 0, 64)
    base = _model("flash")
    params = base.init(jax.random.key(1), tokens)

    def loss(m):
        def f(p):
            logits = m.apply(p, tokens)
            logp = jax.nn.log_softmax(logits[:, :-1], -1)
            return -jnp.take_along_axis(logp, tokens[:, 1:, None], -1).mean()
        return f

    l0, g0 = jax.value_and_grad(loss(base))(params)
    for policy in ("dots", "dots_no_batch"):
        m = TransformerLM(
            vocab_size=64, d_model=64, num_heads=2, num_layers=2,
            attention="flash", dtype=jnp.float32, remat=True,
            remat_policy=policy,
        )
        l1, g1 = jax.value_and_grad(loss(m))(params)
        np.testing.assert_allclose(float(l0), float(l1), rtol=1e-6)
        for a, b in zip(
            jax.tree_util.tree_leaves(g0), jax.tree_util.tree_leaves(g1)
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5
            )
    bad = TransformerLM(
        vocab_size=64, d_model=64, num_heads=2, num_layers=2,
        attention="flash", dtype=jnp.float32, remat=True, remat_policy="nope",
    )
    with pytest.raises(ValueError, match="remat_policy"):
        bad.apply(params, tokens)


def test_remat_with_ring_attention_mesh_is_static():
    """remat passes the mesh as a static argument (a Mesh is not a pytree of
    arrays); the ring+remat combination must trace and match dense."""
    mesh = parallel.make_mesh({"sp": 8})
    tokens = jax.random.randint(jax.random.key(0), (1, 64), 0, 64)
    dense = _model("dense")
    ring_remat = TransformerLM(
        vocab_size=64, d_model=64, num_heads=2, num_layers=2,
        attention="ring", dtype=jnp.float32, remat=True,
    )
    params = dense.init(jax.random.key(1), tokens)
    out_d = dense.apply(params, tokens)
    out_r = ring_remat.apply(params, tokens, mesh=mesh)
    np.testing.assert_allclose(
        np.asarray(out_d), np.asarray(out_r), rtol=2e-4, atol=2e-4
    )

    # And BACKWARD: jax.checkpoint's re-trace must handle the static Mesh
    # and the ring ppermutes under grad — the composition's fragile case.
    def loss(m, kwargs):
        def f(p):
            logits = m.apply(p, tokens, **kwargs)
            logp = jax.nn.log_softmax(logits[:, :-1], -1)
            return -jnp.take_along_axis(logp, tokens[:, 1:, None], -1).mean()
        return f

    # jit is required: remat's closed_call can't evaluate eagerly inside
    # shard_map (and real train steps are always jitted anyway).
    g_d = jax.jit(jax.grad(loss(dense, {})))(params)
    g_r = jax.jit(jax.grad(loss(ring_remat, {"mesh": mesh})))(params)
    assert jax.tree_util.tree_structure(g_d) == jax.tree_util.tree_structure(g_r)
    for a, b in zip(jax.tree_util.tree_leaves(g_d), jax.tree_util.tree_leaves(g_r)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-4
        )


def test_generate_sharded_tp_matches_single_device():
    """TP-sharded generation: the whole KV-cache generate jitted over a
    tp mesh with auto_shardings params must emit exactly the tokens of the
    single-device path (greedy decode is deterministic), with the big
    kernels actually sharded over tp."""
    from jax.sharding import PartitionSpec as P

    from moolib_tpu.models.transformer import generate, generate_sharded
    from moolib_tpu.parallel.train import auto_shardings

    mesh = parallel.make_mesh({"tp": 8})
    model = TransformerLM(
        vocab_size=64, d_model=64, num_heads=2, num_layers=2,
        max_len=64, attention="dense", dtype=jnp.float32,
    )
    prompt = jax.random.randint(jax.random.key(0), (2, 16), 2, 64)
    params = model.init(jax.random.key(1), prompt)
    specs = {str(s.spec) for s in jax.tree_util.tree_leaves(auto_shardings(params, mesh))}
    assert any("tp" in s for s in specs), specs  # kernels really shard
    want = generate(model, params, prompt, 8)
    got = generate_sharded(model, params, prompt, 8, mesh)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # Sampling path (explicit rng) also runs sharded.
    got_s = generate_sharded(
        model, params, prompt, 4, mesh, temperature=1.0, rng=jax.random.key(2)
    )
    assert got_s.shape == (2, 20)


def test_gqa_matches_repeated_kv_reference():
    """Grouped-query attention: a GQA forward must equal plain attention
    with the KV heads explicitly repeated across each group (same params),
    and num_kv_heads == num_heads must be byte-identical to the default
    MHA parameterization."""
    H, Hk = 4, 2
    gqa = TransformerLM(
        vocab_size=64, d_model=64, num_heads=H, num_kv_heads=Hk,
        num_layers=2, attention="dense", dtype=jnp.float32, max_len=64,
    )
    tokens = jax.random.randint(jax.random.key(0), (2, 32), 0, 64)
    params = gqa.init(jax.random.key(1), tokens)
    # qkv kernel carries H + 2*Hk head projections.
    kshape = params["params"]["block0"]["qkv"]["kernel"].shape
    assert kshape == (64, (H + 2 * Hk) * (64 // H)), kshape
    out = gqa.apply(params, tokens)
    assert np.isfinite(np.asarray(out)).all()

    # Reference: build the repeated-KV weights explicitly as an MHA model.
    def widen(p):
        import copy

        p2 = copy.deepcopy(jax.tree_util.tree_map(np.asarray, p))
        hd = 64 // H
        for blk in ("block0", "block1"):
            kern = p2["params"][blk]["qkv"]["kernel"]
            bias = p2["params"][blk]["qkv"]["bias"]
            kq, kk, kv = (
                kern[:, : H * hd],
                kern[:, H * hd : (H + Hk) * hd],
                kern[:, (H + Hk) * hd :],
            )
            rep = lambda a: np.repeat(
                a.reshape(-1, Hk, hd), H // Hk, axis=-2
            ).reshape(a.shape[0], H * hd)
            p2["params"][blk]["qkv"]["kernel"] = np.concatenate(
                [kq, rep(kk), rep(kv)], axis=1
            )
            bq, bk, bv = (
                bias[: H * hd],
                bias[H * hd : (H + Hk) * hd],
                bias[(H + Hk) * hd :],
            )
            repb = lambda a: np.repeat(
                a.reshape(1, Hk, hd), H // Hk, axis=-2
            ).reshape(H * hd)
            p2["params"][blk]["qkv"]["bias"] = np.concatenate(
                [bq, repb(bk), repb(bv)]
            )
        return jax.tree_util.tree_map(jnp.asarray, p2)

    mha = TransformerLM(
        vocab_size=64, d_model=64, num_heads=H, num_layers=2,
        attention="dense", dtype=jnp.float32, max_len=64,
    )
    out_ref = mha.apply(widen(params), tokens)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(out_ref), rtol=2e-5, atol=2e-5
    )

    # Hk == H is plain MHA: parameter tree matches the default exactly.
    same = TransformerLM(
        vocab_size=64, d_model=64, num_heads=H, num_kv_heads=H,
        num_layers=2, attention="dense", dtype=jnp.float32, max_len=64,
    )
    p_same = same.init(jax.random.key(1), tokens)
    for a, b in zip(
        jax.tree_util.tree_leaves(p_same),
        jax.tree_util.tree_leaves(mha.init(jax.random.key(1), tokens)),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_gqa_generate_cache_is_small_and_token_exact():
    """The decode cache stores num_kv_heads heads (the GQA serving win),
    and cached grouped-einsum decoding emits exactly the tokens of naive
    re-forwarding."""
    from moolib_tpu.models.transformer import generate

    model = TransformerLM(
        vocab_size=64, d_model=64, num_heads=4, num_kv_heads=2,
        num_layers=2, attention="dense", dtype=jnp.float32, max_len=64,
    )
    prompt = jax.random.randint(jax.random.key(0), (2, 12), 0, 64)
    params = model.init(jax.random.key(1), prompt)

    toks = prompt
    for _ in range(8):
        logits = model.apply(params, toks)
        nxt = jnp.argmax(logits[:, -1], axis=-1)
        toks = jnp.concatenate([toks, nxt[:, None].astype(toks.dtype)], axis=1)
    out = generate(model, params, prompt, max_new_tokens=8)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(toks))

    # Cache shape check through the decode model's init.
    dec = TransformerLM(
        vocab_size=64, d_model=64, num_heads=4, num_kv_heads=2,
        num_layers=2, attention="dense", dtype=jnp.float32, max_len=64,
        decode=True,
    )
    vars_ = dec.init(jax.random.key(2), prompt[:, :1])
    assert vars_["cache"]["block0"]["k"].shape == (2, 64, 2, 16)  # Hk=2 heads


def test_gqa_through_pipeline_matches_direct_apply():
    """pipeline_lm_apply rebuilds blocks itself; it must forward
    num_kv_heads or GQA params fail the stage's shape check."""
    from moolib_tpu.models.transformer import pipeline_lm_apply

    mesh = parallel.make_mesh({"pp": 4, "dp": 2})
    model = TransformerLM(
        vocab_size=64, d_model=64, num_heads=4, num_kv_heads=2,
        num_layers=4, attention="dense", dtype=jnp.float32, max_len=32,
    )
    tokens = jax.random.randint(jax.random.key(0), (8, 32), 0, 64)
    params = model.init(jax.random.key(1), tokens)
    direct = model.apply(params, tokens)
    out = jax.jit(
        lambda p, t: pipeline_lm_apply(
            model, p, t, mesh, num_microbatches=4, data_axis="dp"
        )
    )(params, tokens)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(direct), rtol=2e-4, atol=2e-4
    )
    # remat + dots policy through the pipeline: same values.
    out_r = jax.jit(
        lambda p, t: pipeline_lm_apply(
            model, p, t, mesh, num_microbatches=4, data_axis="dp",
            remat=True, remat_policy="dots",
        )
    )(params, tokens)
    np.testing.assert_allclose(
        np.asarray(out_r), np.asarray(direct), rtol=2e-4, atol=2e-4
    )


def test_generate_sharded_composes_with_gqa():
    """TP-sharded generation of a GQA model: the qkv projection shards over
    tp while the KV cache keeps num_kv_heads heads; tokens must still match
    the single-device path exactly."""
    from moolib_tpu.models.transformer import generate, generate_sharded

    mesh = parallel.make_mesh({"tp": 8})
    model = TransformerLM(
        vocab_size=64, d_model=64, num_heads=4, num_kv_heads=2,
        num_layers=2, max_len=64, attention="dense", dtype=jnp.float32,
    )
    prompt = jax.random.randint(jax.random.key(0), (2, 16), 2, 64)
    params = model.init(jax.random.key(1), prompt)
    want = generate(model, params, prompt, 8)
    got = generate_sharded(model, params, prompt, 8, mesh)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# The lookups gather rows from the table as stored, then convert the rows
# (RowEmbed); nn.Embed converts the whole table first.  Distinct row counts
# tell the two tables apart.
_V, _M, _D = 96, 48, 64


def _lookup_path(path, dtype):
    """``(fn, params)``: ``fn(params)`` runs ``path`` over a model of compute
    dtype ``dtype`` with float32 parameters."""
    from moolib_tpu.models.transformer import PagedTransformerLM, pipeline_lm_apply
    from moolib_tpu.ops.paged_attention import PagedState

    model = TransformerLM(
        vocab_size=_V, d_model=_D, num_heads=2, num_layers=2, max_len=_M,
        attention="dense", dtype=dtype,
    )
    # Repeated ids, and the table's last row.
    tokens = jnp.asarray([[5, 5, 7, _V - 1, 0, 5, 9, 9]] * 2, jnp.int32)
    params = model.init(jax.random.key(1), tokens)
    if path == "apply":
        return lambda p: model.apply(p, tokens), params
    if path == "pipeline":
        mesh = parallel.make_mesh({"pp": 2}, devices=jax.devices()[:2])
        return lambda p: pipeline_lm_apply(
            model, p, tokens, mesh, num_microbatches=2), params
    paged = PagedTransformerLM(model)
    if path == "prefill":
        return lambda p: paged.prefill(p, tokens[:1], jnp.int32(6), 4)[:2], params
    assert path == "decode"
    slots, per, block = 2, 4, 4
    cache = jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype), paged.cache_spec(1 + slots * per, block))
    state = PagedState(
        jnp.arange(1, 1 + slots * per, dtype=jnp.int32).reshape(slots, per),
        jnp.asarray([3, _M - 1], jnp.int32), jnp.ones((slots,), bool))
    return lambda p: paged.decode(
        p, cache, jnp.asarray([5, _V - 1], jnp.int32), state)[:2], params


def _table_conversions(jaxpr, shape):
    """``convert_element_type`` equations over an operand of ``shape``, in
    ``jaxpr`` and every jaxpr nested in it."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "convert_element_type" and eqn.invars[0].aval.shape == shape:
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _table_conversions(sub, shape)
    return found


@pytest.mark.parametrize("path", ["apply", "prefill", "decode", "pipeline"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bfloat16", "float32"])
@pytest.mark.parametrize("table", ["token", "position"])
def test_lookup_gathers_rows_before_it_converts_them(monkeypatch, table, dtype, path):
    """Against ``take(table.astype(dtype), ids)``, what ``nn.Embed`` does: the
    same bits out, the same parameter tree, and no conversion of anything of
    the table's shape in the traced call."""
    import flax.linen as nn

    from moolib_tpu.models import transformer

    rows = {"token": _V, "position": _M}[table]
    fn, params = _lookup_path(path, dtype)
    new = jax.jit(fn)(params)
    jaxpr = jax.make_jaxpr(fn)(params).jaxpr
    assert not _table_conversions(jaxpr, (rows, _D))

    # The old form for this table alone; the other keeps the new.
    row_embed = transformer.RowEmbed
    monkeypatch.setattr(
        transformer, "RowEmbed",
        lambda n, d, **kw: (nn.Embed if n == rows else row_embed)(n, d, **kw))
    old_fn, old_params = _lookup_path(path, dtype)
    assert jax.tree.structure(old_params) == jax.tree.structure(params)
    # Same names, shapes, dtypes and draws: checkpoints load.
    jax.tree.map(np.testing.assert_array_equal, old_params, params)
    jax.tree.map(np.testing.assert_array_equal, jax.jit(old_fn)(params), new)
    if dtype == jnp.bfloat16:  # the check above can see what it looks for
        assert _table_conversions(jax.make_jaxpr(old_fn)(params).jaxpr, (rows, _D))


def test_lookup_gradient_is_the_old_forms():
    """The train step's arithmetic does not change: the table's gradient is
    ``nn.Embed``'s bit for bit (the rows' bfloat16 cotangents summed into a
    bfloat16 table, repeated ids included, and the sum converted), which lies
    within bfloat16 round-off of the float32 sum.  Plain autodiff of the
    gather-first form would make the float32 sum, and under ``dp`` all-reduce
    a float32 ``[vocab, d]`` gradient where the step has a bfloat16 one."""
    import flax.linen as nn

    from moolib_tpu.models.transformer import RowEmbed

    ids = jnp.asarray([[3, 3, 3, 3, 0, 7, 3, 7]] * 4, jnp.int32)  # row 3: 20 times
    table = jax.random.normal(jax.random.key(0), (_V, _D), jnp.float32)
    weight = jax.random.normal(jax.random.key(1), (*ids.shape, _D), jnp.float32)

    def grad_of(cls, dtype):
        layer = cls(_V, _D, dtype=dtype)
        loss = lambda t: (layer.apply({"params": {"embedding": t}}, ids)
                          * weight.astype(dtype)).astype(jnp.float32).sum()
        jaxpr = jax.make_jaxpr(jax.grad(loss))(table).jaxpr
        return np.asarray(jax.jit(jax.grad(loss))(table)), jaxpr

    new, jaxpr = grad_of(RowEmbed, jnp.bfloat16)
    assert new.dtype == np.float32
    np.testing.assert_array_equal(new, grad_of(nn.Embed, jnp.bfloat16)[0])
    np.testing.assert_array_equal(
        grad_of(RowEmbed, jnp.float32)[0], grad_of(nn.Embed, jnp.float32)[0])
    # The backward converts the float32 table no more than the forward does.
    assert not [e for e in _table_conversions(jaxpr, (_V, _D))
                if e.invars[0].aval.dtype == jnp.float32]
    g = weight.astype(jnp.bfloat16).astype(jnp.float32)  # the rows' cotangent
    want = np.asarray(jnp.zeros((_V, _D), jnp.float32).at[ids].add(g))
    # bfloat16 keeps 8 bits, so each of row 3's 20 partial sums rounds by up
    # to 2**-9 of its size: the sum reads 0.75% off the largest entry.
    off = np.abs(new - want).max() / np.abs(want).max()
    assert 2.0**-12 < off < 2.0**-6
