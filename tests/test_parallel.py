"""parallel/ tests on the virtual 8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from moolib_tpu import parallel


def test_eight_devices():
    assert len(jax.devices()) == 8


def test_make_mesh_shapes():
    mesh = parallel.make_mesh({"dp": 4, "tp": 2})
    assert mesh.shape == {"dp": 4, "tp": 2}
    mesh = parallel.make_mesh({"dp": -1, "sp": 2})
    assert mesh.shape["dp"] == 4
    mesh = parallel.make_mesh()
    assert mesh.shape == {"dp": 8}
    with pytest.raises(ValueError):
        parallel.make_mesh({"dp": 3})


def test_tree_pmean_shard_map():
    mesh = parallel.make_mesh({"dp": 8})

    def f(x):
        return parallel.tree_pmean({"v": x}, "dp")["v"]

    fn = jax.shard_map(f, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"))
    x = jnp.arange(8.0)
    out = fn(x)
    np.testing.assert_allclose(np.asarray(out), np.full(8, 3.5))


def test_ring_attention_matches_full_causal():
    mesh = parallel.make_mesh({"sp": 8})
    rng = np.random.default_rng(0)
    B, T, H, D = 2, 64, 2, 8
    q = jnp.asarray(rng.normal(size=(B, T, H, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, T, H, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, T, H, D)).astype(np.float32))
    expected = parallel.full_attention(q, k, v, causal=True)
    got = parallel.ring_attention(q, k, v, mesh, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected), rtol=2e-4, atol=2e-4)


def test_ring_attention_matches_full_noncausal():
    mesh = parallel.make_mesh({"sp": 4, "dp": 2})
    rng = np.random.default_rng(1)
    B, T, H, D = 1, 32, 4, 16
    q = jnp.asarray(rng.normal(size=(B, T, H, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, T, H, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, T, H, D)).astype(np.float32))
    expected = parallel.full_attention(q, k, v, causal=False)
    got = parallel.ring_attention(q, k, v, mesh, axis_name="sp", causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected), rtol=2e-4, atol=2e-4)


def test_sharded_train_step_dp_equals_single():
    """DP over the mesh must give identical updates to single-device math."""
    mesh = parallel.make_mesh({"dp": 8})
    rng = np.random.default_rng(2)
    params = {"w": jnp.asarray(rng.normal(size=(4, 3)).astype(np.float32))}
    batch = {
        "x": jnp.asarray(rng.normal(size=(1, 16, 4)).astype(np.float32)),
        "y": jnp.asarray(rng.normal(size=(1, 16, 3)).astype(np.float32)),
    }

    def loss_fn(params, batch, rng_key):
        pred = batch["x"] @ params["w"]
        return jnp.mean((pred - batch["y"]) ** 2), {}

    opt = optax.sgd(0.1)
    opt_state = opt.init(params)

    step = parallel.make_train_step(loss_fn, opt, mesh, batch_spec=P(None, "dp"), donate=False)
    p1, _, loss1, _ = step(params, opt_state, batch, jax.random.key(0))

    plain = parallel.make_train_step(loss_fn, opt, mesh=None, donate=False)
    p2, _, loss2, _ = plain(params, opt_state, batch, jax.random.key(0))

    np.testing.assert_allclose(float(loss1), float(loss2), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(p1["w"]), np.asarray(p2["w"]), rtol=1e-5)


# The rule (parallel/train.py:fsdp_spec): shape, what another mesh axis
# already holds of the leaf, and the spec on a mesh of dp=4 x ep=2.
@pytest.mark.parametrize("shape,held,want", [
    ((1024, 256), P(), P("dp", None)),  # the largest axis, dp divides it
    ((50257, 2048), P(), P(None, "dp")),  # only a smaller one: the table
    ((2048, 50257), P(), P("dp", None)),  # ... and the head
    ((50257, 1023), P(), P()),  # none: whole
    ((255, 256), P(), P()),  # under 2^16 elements: whole
    ((256, 256), P(), P("dp", None)),  # 2^16: cut, the first of two equals
    ((4,), P(), P()),
    ((), P(), P()),
    ((8, 512, 64), P("ep", None, None), P("ep", "dp", None)),  # ep keeps its axis
    ((1024, 64, 4), P("ep"), P("ep", "dp", None)),  # ... even the largest
    ((1024, 63, 5), P("ep", None, None), P("ep", None, None)),  # nothing left for dp
])
def test_fsdp_param_shardings(shape, held, want):
    from jax.sharding import NamedSharding

    mesh = parallel.make_mesh({"dp": 4, "ep": 2})
    params = {"w": jax.ShapeDtypeStruct(shape, jnp.float32)}
    base = {"w": NamedSharding(mesh, held)}
    assert parallel.param_shardings(params, mesh, "fsdp", base=base)["w"].spec == want
    if held == P():  # no base: replicated is what fsdp cuts
        assert parallel.param_shardings(params, mesh, "fsdp")["w"].spec == want
    # A mesh with no dp to cut over, or a dp of one, leaves the base.
    for spec in ("ep=2", "dp=1,ep=2"):
        other = parallel.parse_mesh_spec(spec)
        base = {"w": NamedSharding(other, held)}
        assert parallel.param_shardings(params, other, "fsdp", base=base) == base


def test_optimizer_moments_mirror_their_parameters_shardings():
    import optax

    mesh = parallel.parse_mesh_spec("dp=4")
    params = {"a": {"w": jnp.zeros((1024, 256)), "b": jnp.zeros((4,))}}
    p_sh = parallel.param_shardings(params, mesh, "fsdp")
    state = jax.eval_shape(optax.adamw(1e-3).init, params)
    o_sh = parallel.mirror_shardings(state, params, p_sh, mesh)
    assert jax.tree_util.tree_structure(o_sh) == jax.tree_util.tree_structure(state)
    assert o_sh[0].mu == o_sh[0].nu == p_sh
    assert o_sh[0].count.spec == P()


def test_fsdp_train_step_runs():
    mesh = parallel.make_mesh({"dp": 8})
    rng = np.random.default_rng(3)
    params = {"w": jnp.asarray(rng.normal(size=(1024, 128)).astype(np.float32) * 0.01)}
    batch = {
        "x": jnp.asarray(rng.normal(size=(1, 8, 1024)).astype(np.float32)),
        "y": jnp.asarray(rng.normal(size=(1, 8, 128)).astype(np.float32)),
    }

    def loss_fn(params, batch, rng_key):
        pred = batch["x"] @ params["w"]
        return jnp.mean((pred - batch["y"]) ** 2), {}

    opt = optax.adam(1e-3)
    opt_state = opt.init(params)
    step = parallel.make_train_step(
        loss_fn, opt, mesh, params_sharding="fsdp", batch_spec=P(None, "dp"), donate=False
    )
    p, o, loss, _ = step(params, opt_state, batch, jax.random.key(0))
    assert np.isfinite(float(loss))
    # Updated params keep the FSDP sharding.
    assert p["w"].sharding.spec == P("dp", None)


def test_ring_permute():
    mesh = parallel.make_mesh({"dp": 8})

    def f(x):
        return parallel.ring_permute(x, "dp")

    fn = jax.shard_map(f, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"))
    out = fn(jnp.arange(8.0))
    np.testing.assert_allclose(np.asarray(out), np.roll(np.arange(8.0), 1))


def test_ring_attention_flash_chunks_match_full():
    """With T_local >= 128 each ring hop rides the pallas flash kernel
    (interpret mode here) and chunk results merge by logsumexp weights —
    forward must match dense over the full sequence, both maskings."""
    mesh = parallel.make_mesh({"sp": 4, "dp": 2})
    rng = np.random.default_rng(3)
    B, T, H, D = 1, 1024, 2, 64  # T_local = 256 -> flash path
    q = jnp.asarray(rng.normal(size=(B, T, H, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, T, H, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, T, H, D)).astype(np.float32))
    for causal in (True, False):
        expected = parallel.full_attention(q, k, v, causal=causal)
        got = jax.jit(
            lambda q, k, v: parallel.ring_attention(q, k, v, mesh, causal=causal)
        )(q, k, v)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(expected), rtol=2e-4, atol=2e-4,
            err_msg=f"causal={causal}",
        )


def test_ring_attention_flash_chunks_gradients():
    """Gradients through the flash-chunked ring: the lse outputs are
    differentiable (their cotangent folds into the backward kernels'
    delta), so ring+flash training must match dense-attention gradients."""
    mesh = parallel.make_mesh({"sp": 4, "dp": 2})
    rng = np.random.default_rng(4)
    B, T, H, D = 1, 512, 2, 64  # T_local = 128 -> flash path
    q = jnp.asarray(rng.normal(size=(B, T, H, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, T, H, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, T, H, D)).astype(np.float32))
    g = jnp.asarray(rng.normal(size=(B, T, H, D)).astype(np.float32))
    _, vjp_r = jax.vjp(
        jax.jit(lambda q, k, v: parallel.ring_attention(q, k, v, mesh, causal=True)),
        q, k, v,
    )
    _, vjp_d = jax.vjp(
        lambda q, k, v: parallel.full_attention(q, k, v, causal=True), q, k, v
    )
    for a, b, name in zip(vjp_r(g), vjp_d(g), "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-4, err_msg=f"d{name}"
        )


def test_flash_attention_return_lse():
    """flash_attention(return_lse=True) returns the row logsumexp matching a
    direct dense computation, and its dense fallback does too."""
    from moolib_tpu.ops.flash_attention import flash_attention

    rng = np.random.default_rng(5)
    B, T, H, D = 1, 256, 2, 64
    q = jnp.asarray(rng.normal(size=(B, T, H, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, T, H, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, T, H, D)).astype(np.float32))
    scores = np.einsum("bqhd,bkhd->bhqk", q, k) * D**-0.5
    mask = np.tril(np.ones((T, T), bool))
    scores = np.where(mask[None, None], scores, -1e30)
    want_lse = np.transpose(
        np.log(np.exp(scores - scores.max(-1, keepdims=True)).sum(-1))
        + scores.max(-1),
        (0, 2, 1),
    )
    out, lse = flash_attention(q, k, v, causal=True, return_lse=True)
    assert lse.shape == (B, T, H)
    np.testing.assert_allclose(np.asarray(lse), want_lse, rtol=1e-4, atol=1e-4)
    # Dense fallback (non-tileable T) has the same contract.
    q2, k2, v2 = q[:, :160], k[:, :160], v[:, :160]
    out2, lse2 = flash_attention(q2, k2, v2, causal=True, return_lse=True)
    np.testing.assert_allclose(
        np.asarray(lse2), want_lse[:, :160], rtol=1e-4, atol=1e-4
    )


# ---------------------------------------------------------------- mesh specs
def test_parse_mesh_spec_multi_axis():
    mesh = parallel.parse_mesh_spec("dp=2,tp=2")
    assert mesh.shape == {"dp": 2, "tp": 2}
    # Whitespace and trailing commas are operator input, not wire protocol.
    mesh = parallel.parse_mesh_spec(" dp=2 , sp=2 ,")
    assert mesh.shape == {"dp": 2, "sp": 2}
    # -1 absorbs every remaining device (8 on the virtual CPU mesh).
    mesh = parallel.parse_mesh_spec("tp=2,dp=-1")
    assert mesh.shape == {"tp": 2, "dp": 4}
    assert parallel.parse_mesh_spec("") is None


def test_parse_mesh_spec_non_power_of_two():
    # prod(sizes) < device count: the spec takes the FIRST prod devices, so
    # odd cohort shapes (3 of 8) are legal without -1 arithmetic.
    mesh = parallel.parse_mesh_spec("dp=3")
    assert mesh.shape == {"dp": 3}
    assert len(list(mesh.devices.flat)) == 3
    mesh = parallel.parse_mesh_spec("dp=3,tp=2")
    assert mesh.shape == {"dp": 3, "tp": 2}
    # -1 with a non-dividing known axis must error loudly, not truncate.
    with pytest.raises(ValueError):
        parallel.parse_mesh_spec("dp=-1,tp=3")
    # At most one axis may absorb.
    with pytest.raises(ValueError):
        parallel.parse_mesh_spec("dp=-1,tp=-1")


def test_split_mesh_non_power_of_two():
    # 8 devices, 3 actors: learner keeps the odd remainder as pure dp.
    actor, learner = parallel.split_mesh(parallel.make_mesh({"dp": 8}), 3)
    assert actor.shape == {"dp": 3}
    assert learner.shape == {"dp": 5}
    # Non-dp axes survive when they still divide the remainder...
    actor, learner = parallel.split_mesh(parallel.make_mesh({"dp": 4, "tp": 2}), 2)
    assert learner.shape == {"dp": 3, "tp": 2}
    # ...and collapse into dp when they no longer fit.
    actor, learner = parallel.split_mesh(parallel.make_mesh({"dp": 4, "tp": 2}), 3)
    assert learner.shape == {"dp": 5}
    for bad in (0, 8, 9):
        with pytest.raises(ValueError):
            parallel.split_mesh(parallel.make_mesh({"dp": 8}), bad)


def test_check_disjoint_overlap_error_names_flags():
    devs = jax.devices()
    a = parallel.make_mesh({"dp": 4}, devs[:4])
    b = parallel.make_mesh({"dp": 4}, devs[4:])
    parallel.check_disjoint(a, b)  # disjoint: no error
    overlap = parallel.make_mesh({"dp": 4}, devs[2:6])
    with pytest.raises(ValueError) as ei:
        parallel.check_disjoint(a, overlap, what_a="--mesh", what_b="--actor_mesh")
    msg = str(ei.value)
    # The operator must see which flags collided and on which device ids.
    assert "--mesh" in msg and "--actor_mesh" in msg
    assert "2" in msg and "3" in msg
    # split_mesh output always passes by construction.
    actor, learner = parallel.split_mesh(parallel.make_mesh({"dp": 8}), 2)
    parallel.check_disjoint(learner, actor)


# ------------------------------------------------------- grad_spec train step
def test_grad_step_matches_direct_grad():
    """The hierarchical learner's in-mesh half (DESIGN.md §6d): the
    grad_spec= path must return the same dp-reduced gradients as unsharded
    single-device autodiff, with the requested output sharding."""
    mesh = parallel.make_mesh({"dp": 4}, jax.devices()[:4])
    rng = np.random.default_rng(11)
    params = {"w": jnp.asarray(rng.normal(size=(512, 512)).astype(np.float32) * 0.02)}
    batch = {
        "x": jnp.asarray(rng.normal(size=(1, 8, 512)).astype(np.float32)),
        "y": jnp.asarray(rng.normal(size=(1, 8, 512)).astype(np.float32)),
    }

    def loss_fn(params, batch, rng_key):
        pred = batch["x"] @ params["w"]
        return jnp.mean((pred - batch["y"]) ** 2), {}

    gstep = parallel.make_train_step(
        loss_fn, mesh=mesh, grad_spec="replicated", batch_spec=P(None, "dp")
    )
    loss, _, grads = gstep(params, batch, jax.random.key(0))
    want = jax.grad(lambda p: loss_fn(p, batch, None)[0])(params)
    np.testing.assert_allclose(
        np.asarray(grads["w"]), np.asarray(want["w"]), rtol=1e-5, atol=1e-6
    )
    assert np.isfinite(float(loss))

    # grad_spec="params" mirrors the fsdp param sharding: XLA lowers the dp
    # reduction to a reduce-scatter and the grads come back shard-laid-out.
    fstep = parallel.make_train_step(
        loss_fn, mesh=mesh, params_sharding="fsdp", grad_spec="params",
        batch_spec=P(None, "dp"),
    )
    _, _, fgrads = fstep(params, batch, jax.random.key(0))
    assert fgrads["w"].sharding.spec == P("dp", None)
    np.testing.assert_allclose(
        np.asarray(fgrads["w"]), np.asarray(want["w"]), rtol=1e-5, atol=1e-6
    )


def test_grad_spec_validation():
    def loss_fn(params, batch, rng_key):
        return jnp.float32(0.0), {}

    with pytest.raises(ValueError, match="requires mesh"):
        parallel.make_train_step(loss_fn, grad_spec="replicated")
    with pytest.raises(ValueError, match="needs an optimizer"):
        parallel.make_train_step(loss_fn)
    mesh = parallel.make_mesh({"dp": 8})
    with pytest.raises(ValueError, match="unknown grad_spec"):
        parallel.make_train_step(loss_fn, mesh=mesh, grad_spec="zero")(
            {"w": jnp.zeros(4)}, {"x": jnp.zeros((1, 8))}, jax.random.key(0)
        )
