"""Long-context LM example: sequence parallelism in TRAINING, end to end.

The recall task (second half of each sequence repeats the first) is only
solvable by attending T/2 positions back — a broken ring schedule or broken
gradients through it cannot beat chance (~1/62)."""

import pytest

from moolib_tpu.examples.lm import make_flags, train


def test_batched_generation_served_over_rpc(free_port):
    """Inference batching on the new model family: concurrent single-prompt
    RPC calls stack into one dynamic batch, run one jitted KV-cache
    generate, and each caller's continuation token-matches a direct local
    generate with the same params (greedy = deterministic)."""
    import asyncio

    import jax
    import jax.numpy as jnp
    import numpy as np

    from moolib_tpu.examples.lm_serve import make_model, serve
    from moolib_tpu.rpc import Rpc

    flags = type("F", (), dict(
        vocab=64, d_model=32, heads=2, layers=2, seq_len=12, max_new_tokens=6,
    ))()
    model = make_model(flags)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(2, 64, 12).astype(np.int32) for _ in range(5)]
    params = model.init(jax.random.key(0), jnp.asarray(prompts[0][None]))

    server = Rpc()
    server.set_name("lm_server")
    server.listen(f"127.0.0.1:{free_port}")
    client = Rpc()
    client.set_name("lm_client")
    client.set_timeout(60)
    client.connect(f"127.0.0.1:{free_port}")
    try:
        # serve() defines the queue synchronously — BEFORE any call goes out
        # (calls to undefined functions error immediately, no buffering).
        coro = serve(server, model, params, flags.max_new_tokens, total=5)
        futs = [client.async_("lm_server", "generate", p) for p in prompts]
        iterations = asyncio.run(asyncio.wait_for(coro, 120))
        # Dynamic batching must actually stack concurrent callers: the first
        # call may be served alone, but the rest queue up behind the jit
        # compile and arrive together.
        assert iterations < 5, f"no batching happened ({iterations} iterations)"
        from moolib_tpu.models.transformer import generate

        for p, fut in zip(prompts, futs):
            got = np.asarray(fut.result(60))
            want = np.asarray(
                generate(model, params, jnp.asarray(p[None]), flags.max_new_tokens)
            )[0]
            np.testing.assert_array_equal(got, want)

        # A bad request (prompt too long for the cache) errors THAT caller
        # and the server keeps serving; serialize the two calls so they land
        # in separate batches (stacking needs matching shapes).
        import threading

        import pytest

        from moolib_tpu.rpc import RpcError

        coro2 = serve(
            server, model, params, flags.max_new_tokens, name="generate2", total=2
        )
        t = threading.Thread(target=lambda: asyncio.run(coro2))
        t.start()
        bad = client.async_(
            "lm_server", "generate2", np.zeros(64, np.int32)  # 64 + 6 > max_len
        )
        with pytest.raises(RpcError, match="generate failed"):
            bad.result(60)
        ok = client.async_("lm_server", "generate2", prompts[0])
        np.testing.assert_array_equal(
            np.asarray(ok.result(60)),
            np.asarray(
                generate(
                    model, params, jnp.asarray(prompts[0][None]), flags.max_new_tokens
                )
            )[0],
        )
        t.join(120)
        assert not t.is_alive()
    finally:
        client.close()
        server.close()


def test_lm_trains_with_ring_attention_over_dp_sp_mesh():
    out = train(
        make_flags(
            [
                "--mesh",
                "dp=2,sp=4",
                "--seq_len",
                "32",
                "--batch_size",
                "16",
                "--steps",
                "150",
                "--quiet",
            ]
        )
    )
    assert out["acc"] > 0.9, out
    assert out["loss"] < 0.5, out


def test_lm_trains_remat_ring_over_dp_sp_mesh():
    """--remat composes with ring attention over the mesh: per-block
    gradient checkpointing (static mesh arg through nn.remat) while the
    recall task still trains to high accuracy."""
    out = train(
        make_flags(
            [
                "--mesh",
                "dp=2,sp=4",
                "--seq_len",
                "32",
                "--batch_size",
                "16",
                "--steps",
                "150",
                "--remat",
                "--quiet",
            ]
        )
    )
    assert out["acc"] > 0.9, out
    assert out["loss"] < 0.5, out


def test_lm_trains_moe_over_dp_ep_mesh():
    """Expert parallelism end to end: SwitchMoE FFN blocks, experts sharded
    over ep, router aux loss in the objective — and the model still learns."""
    out = train(
        make_flags(
            [
                "--mesh",
                "dp=2,ep=4",
                "--attention",
                "dense",
                "--moe_experts",
                "4",
                "--seq_len",
                "32",
                "--batch_size",
                "16",
                "--steps",
                "200",
                "--quiet",
            ]
        )
    )
    assert out["acc"] > 0.8, out


def test_lm_trains_pipelined_over_dp_pp_mesh():
    """Pipeline parallelism end to end in a real model: transformer blocks
    streamed through the circular schedule (pp=2, v=2) with the batch
    sharded over dp — and the model still learns the recall task."""
    out = train(
        make_flags(
            [
                "--mesh",
                "dp=2,pp=2",
                "--attention",
                "dense",
                "--layers",
                "4",
                "--pp_repeats",
                "2",
                "--microbatches",
                "4",
                "--seq_len",
                "32",
                "--batch_size",
                "16",
                "--steps",
                "150",
                "--quiet",
            ]
        )
    )
    assert out["acc"] > 0.9, out


def test_lm_trains_dense_single_device():
    out = train(
        make_flags(
            [
                "--mesh",
                "",
                "--attention",
                "dense",
                "--seq_len",
                "32",
                "--batch_size",
                "16",
                "--steps",
                "120",
                "--quiet",
            ]
        )
    )
    assert out["acc"] > 0.9, out


def test_tp_sharded_serving_matches_local_generate(free_port):
    """serve(mesh=...): the dynamic-batching server runs generation
    tensor-parallel over a tp mesh; clients see exactly the tokens of the
    single-device path."""
    import asyncio

    import jax
    import jax.numpy as jnp
    import numpy as np

    from moolib_tpu import parallel
    from moolib_tpu.examples.lm_serve import make_model, serve
    from moolib_tpu.models.transformer import generate
    from moolib_tpu.rpc import Rpc

    flags = type("F", (), dict(
        vocab=64, d_model=64, heads=2, layers=2, seq_len=12, max_new_tokens=6,
    ))()
    model = make_model(flags)
    mesh = parallel.make_mesh({"tp": 8})
    rng = np.random.default_rng(0)
    prompts = [rng.integers(2, 64, 12).astype(np.int32) for _ in range(3)]
    params = model.init(jax.random.key(0), jnp.asarray(prompts[0][None]))

    server = Rpc()
    server.set_name("lm_server")
    server.listen(f"127.0.0.1:{free_port}")
    client = Rpc()
    client.set_name("lm_client")
    client.set_timeout(120)
    client.connect(f"127.0.0.1:{free_port}")
    try:
        coro = serve(server, model, params, flags.max_new_tokens, total=3, mesh=mesh)
        futs = [client.async_("lm_server", "generate", p) for p in prompts]
        asyncio.run(asyncio.wait_for(coro, 180))
        for p, fut in zip(prompts, futs):
            want = generate(model, params, jnp.asarray(p[None]), flags.max_new_tokens)
            np.testing.assert_array_equal(np.asarray(fut.result(60)), np.asarray(want)[0])
    finally:
        client.close()
        server.close()


# ---- the train state is donated (ISSUE 28): the step updates it in place;
# under a mesh it lives cut over dp (ISSUE 36) ----

# d_model 128 makes the FFN kernels 2^16 elements, the least the rule cuts;
# no power of two divides a vocabulary of 521, so table and head are cut on
# d_model, as the train cells' 50,257 x 2,048 are.
_SMALL = ["--seq_len", "16", "--batch_size", "8", "--seed", "7", "--quiet",
          "--d_model", "128", "--vocab", "521"]
_PATHS = {
    "plain": ["--mesh", "", "--attention", "dense"],
    "dp2": ["--mesh", "dp=2", "--attention", "flash"],
    "dp4": ["--mesh", "dp=4", "--attention", "flash"],
}


class _StepSpy:
    """Stands where ``devmon.instrument_jit`` puts its wrapper around the
    step ``train`` built: after every call, the bytes of the ``params`` and
    ``opt_state`` passed in that one device held, the devices that held
    them, and whether the call consumed every leaf."""

    def __init__(self, fn):
        self.fn = fn
        self.calls = []

    def __call__(self, params, opt_state, tokens):
        import jax

        from moolib_tpu.examples import lm

        state = jax.tree_util.tree_leaves((params, opt_state))
        nbytes = lm.state_device_bytes(params, opt_state)
        out = self.fn(params, opt_state, tokens)
        self.calls.append((nbytes, all(x.is_deleted() for x in state)))
        return out

    def __getattr__(self, name):  # .lower, for the ahead-of-time compile
        return getattr(self.fn, name)


def _train_spied(monkeypatch, argv):
    from moolib_tpu.telemetry import devmon

    devmon.reset_for_tests()  # its cost cache is keyed by shapes alone
    spies = []
    real = devmon.instrument_jit

    def instrument(fn, name):
        spies.append(_StepSpy(real(fn, name)))  # the loop reads the wrapper's ``seq``
        return spies[-1]

    monkeypatch.setattr(devmon, "instrument_jit", instrument)
    out = train(make_flags(argv))
    (spy,) = spies
    return out, spy


def _state_shapes(argv):
    """The flags, and the shapes of the ``params`` and ``opt_state`` they describe."""
    import jax
    import jax.numpy as jnp
    import optax

    from moolib_tpu.examples import lm

    flags = make_flags(argv)
    model, opt = lm.make_model(flags), optax.adamw(flags.learning_rate)
    tokens = jax.ShapeDtypeStruct((flags.batch_size, flags.seq_len), jnp.int32)
    params = jax.eval_shape(lambda t: model.init(jax.random.key(0), t), tokens)
    return flags, params, jax.eval_shape(opt.init, params)


@pytest.mark.parametrize("path", list(_PATHS))
def test_lm_step_consumes_params_and_opt_state(monkeypatch, path):
    """Every leaf of the state passed to the step is gone after the call,
    from the first call on; every call gets the state as the one before
    returned it, so under a mesh too the step compiles once; and what the
    compiled step aliases is all a device holds: the whole state, or under
    ``dp`` that device's cut of it."""
    argv = _SMALL + _PATHS[path] + ["--steps", "4", "--log_interval", "2"]
    out, spy = _train_spied(monkeypatch, argv)
    assert len(spy.calls) == 4  # no executed warm-up: a loop step is a call
    assert all(consumed for _, consumed in spy.calls), spy.calls
    assert spy.fn._cache_size() == 1  # one program: the state was placed first
    device_bytes = spy.calls[0][0]
    assert {n for n, _ in spy.calls} == {device_bytes}
    assert out["donated_bytes"] == out["state_device_bytes"] == device_bytes > 0
    dp = {"plain": 1, "dp2": 2, "dp4": 4}[path]
    assert out["param_placement"]["devices_per_array"] == dp
    # Table, head, FFN kernels and their moments are cut; qkv (128 x 384),
    # proj, the positions, every vector and the count are whole.
    import jax

    whole = sum(x.size * x.dtype.itemsize
                for x in jax.tree_util.tree_leaves(_state_shapes(argv)[1:]))
    small = 3 * 4 * (2 * (128 * 384 + 128 * 128 + 13 * 128) + 16 * 128 + 2 * 128 + 521) + 4
    assert device_bytes == small + (whole - small) // dp


@pytest.mark.parametrize("path", list(_PATHS))
def test_lm_train_reports_the_losses_of_the_replicated_undonated_step(monkeypatch, path):
    """``train``'s losses equal those of the plain step (every device holds
    and updates the whole state, gradients all-reduced) jitted with nothing
    donated and driven by the same batches: the first batch is drawn and
    feeds the compile alone, and no weight moves before step 1.  To the bit
    on one device and at ``dp=2``, where the sum of two commutes; at
    ``dp=4`` the reduce-scatter may add in another order."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from moolib_tpu import parallel
    from moolib_tpu.examples import lm

    argv = _SMALL + _PATHS[path] + ["--steps", "5", "--log_interval", "1"]
    out, _ = _train_spied(monkeypatch, argv)

    flags = make_flags(argv)
    mesh = parallel.parse_mesh_spec(flags.mesh)
    model, opt = lm.make_model(flags), optax.adamw(flags.learning_rate)
    rng = np.random.default_rng(flags.seed)
    first = jnp.asarray(lm.make_batch(rng, flags))
    params = model.init(
        jax.random.key(flags.seed), first, **lm._apply_kwargs(flags, mesh))
    opt_state = opt.init(params)
    loss_fn, cut_step = lm.make_step(flags, model, opt, mesh)
    _, put = lm.jit_step(cut_step, params, opt_state, flags, mesh)

    def step(params, opt_state, tokens):
        (loss, acc), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, tokens)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss, acc

    # Nothing donated: params and opt_state stay readable.
    if mesh is None:
        plain = jax.jit(step)
    else:
        rep = parallel.replicated(mesh)
        plain = jax.jit(
            step,
            in_shardings=(rep, None, put(first).sharding),
            out_shardings=(rep, None, rep, rep),
        )
    want = []
    for i in range(flags.steps):
        old = params
        params, opt_state, loss, _ = plain(
            params, opt_state, put(jnp.asarray(lm.make_batch(rng, flags))))
        want.append((i + 1, float(loss)))
        assert not jax.tree_util.tree_leaves(old)[0].is_deleted()
    if path == "dp4":
        assert [s for s, _ in out["losses"]] == [s for s, _ in want]
        np.testing.assert_allclose(
            [v for _, v in out["losses"]], [v for _, v in want], rtol=2e-6)
    else:
        assert out["losses"] == want


def test_lm_dp4_step_gives_the_one_device_steps_loss_and_parameters():
    """Two steps of the ``dp=4`` step on four CPU devices, as ``jit_step``
    jits it there (with no compile option: this compiler knows none of the
    TPU's), against two of the one-device step from the same state and
    batches: the losses to the tolerance of the mesh tests above, and the
    matrices (what the step reduce-scatters) to a thousandth of the learning
    rate but for a few weights in ten thousand.  AdamW's first steps move a
    weight by about the learning rate whatever its gradient's size: where a
    gradient is rounding away from nothing (the keys' bias, which softmax
    cannot see, is all such), a sum added in another order moves the weight
    the other way, by at most the two steps' learning rates."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from moolib_tpu import parallel
    from moolib_tpu.examples import lm

    got = {}
    for path, mesh_spec in (("plain", ""), ("dp4", "dp=4")):
        flags = make_flags(_SMALL + ["--mesh", mesh_spec, "--attention", "dense"])
        mesh = parallel.parse_mesh_spec(flags.mesh)
        # float32 compute: in bfloat16 this backend's matmuls round by the
        # rows a device holds, 3e-4 of the loss between 2 and 8 sequences
        model = lm.make_model(flags).clone(dtype=jnp.float32)
        opt = optax.adamw(flags.learning_rate)
        rng = np.random.default_rng(flags.seed)
        batches = [jnp.asarray(lm.make_batch(rng, flags)) for _ in range(2)]
        params = model.init(
            jax.random.key(flags.seed), batches[0], **lm._apply_kwargs(flags, mesh))
        opt_state = opt.init(params)
        if mesh is not None:
            params, opt_state = jax.device_put(
                (params, opt_state), lm.state_shardings(params, opt_state, flags, mesh))
        _, step = lm.make_step(flags, model, opt, mesh)
        jstep, put = lm.jit_step(step, params, opt_state, flags, mesh)
        losses = []
        for tokens in batches:
            params, opt_state, loss, _ = jstep(params, opt_state, put(tokens))
            losses.append(float(loss))
        got[path] = losses, jax.device_get(params), flags.learning_rate
    (want_losses, want, lr), (losses, params, _) = got["plain"], got["dp4"]
    np.testing.assert_allclose(losses, want_losses, rtol=2e-6)
    assert losses[1] != losses[0]
    for a, b in zip(jax.tree_util.tree_leaves(params), jax.tree_util.tree_leaves(want)):
        off = np.abs(np.asarray(a) - np.asarray(b))
        assert off.max() <= 2 * lr, (off.max(), a.shape)
        if a.ndim >= 2:
            assert (off > 1e-3 * lr).mean() < 3e-4, ((off > 1e-3 * lr).mean(), a.shape)


def test_lm_state_is_cut_on_d_model_where_dp_cannot_divide_the_vocabulary():
    """A vocabulary no power of two divides (101) leaves table and head
    their other axis: both are cut on ``d_model``, moments with them; the
    count, the vectors and the small position table stay whole."""
    from jax.sharding import PartitionSpec as P

    from moolib_tpu import parallel
    from moolib_tpu.examples import lm

    flags, params, opt_state = _state_shapes(
        ["--vocab", "101", "--d_model", "1024", "--heads", "8", "--layers", "1",
         "--seq_len", "32", "--mesh", "dp=4", "--attention", "flash"])
    mesh = parallel.parse_mesh_spec(flags.mesh)
    p_sh, o_sh = lm.state_shardings(params, opt_state, flags, mesh)
    for tree in (p_sh, o_sh[0].mu, o_sh[0].nu):
        tree = tree["params"]
        assert tree["embed"]["embedding"].spec == P(None, "dp")  # [101, 1024]
        assert tree["lm_head"]["kernel"].spec == P("dp", None)  # [1024, 101]
        assert tree["block0"]["qkv"]["kernel"].spec == P(None, "dp")  # [1024, 3072]
        assert tree["block0"]["Dense_1"]["kernel"].spec == P("dp", None)  # [4096, 1024]
        assert tree["pos"]["embedding"].spec == P()  # [32, 1024]: under 2^16
        assert tree["lm_head"]["bias"].spec == P()
    assert o_sh[0].count.spec == P()


@pytest.mark.parametrize("path", ["plain", "dp2"])
def test_lm_state_bytes_gauges_and_result(monkeypatch, path):
    from moolib_tpu import telemetry

    out, spy = _train_spied(
        monkeypatch, _SMALL + _PATHS[path] + ["--steps", "2", "--log_interval", "1"])
    snapshot = telemetry.get_registry().snapshot()
    for gauge, key in (("lm_step_donated_bytes", "donated_bytes"),
                       ("lm_state_device_bytes", "state_device_bytes")):
        series = snapshot[gauge]["series"]
        assert [s["value"] for s in series] == [out[key]] == [spy.calls[0][0]]


@pytest.mark.parametrize("argv, sentence", [
    (["--listen", "127.0.0.1:1", "--engine", "--mesh", "tp=2"],
     "--engine serves from one device"),
    (["--listen", "127.0.0.1:1", "--config", "some.json"],
     "--config builds a model only the engine serves"),
])
def test_lm_serve_refuses_at_start_what_the_engine_does_not_serve(argv, sentence):
    """Before anything is built: the engine runs on one device (a ``--mesh``
    is the batch-synchronous arm's), and a configuration file builds a model
    that only the engine serves."""
    from moolib_tpu.examples import lm_serve

    with pytest.raises(SystemExit, match=sentence):
        lm_serve.main(argv)


@pytest.mark.parametrize("heads,path", [(2, "in_place"), (4, "head_major")])
def test_lm_train_says_how_the_flash_kernels_address_their_operands(capsys, heads, path):
    """d_model 256 as 2 heads of 128: ``Block`` hands the kernels its packed
    projection and they index it in place; as 4 heads of 64 they go through
    head-major copies.  ``flash_attention_traces_total{path}`` by path in
    ``train``'s result and in its log line, beside ``donated=``."""
    from moolib_tpu import telemetry

    prefix = 'flash_attention_traces_total{path="%s"}'
    before = telemetry.get_registry().counter_values()
    out = train(make_flags([
        "--seq_len", "128", "--batch_size", "2", "--seed", "7", "--d_model", "256",
        "--heads", str(heads), "--layers", "1", "--vocab", "64", "--mesh", "",
        "--attention", "flash", "--steps", "1", "--log_interval", "1"]))
    after = telemetry.get_registry().counter_values()
    other = {"in_place": "head_major", "head_major": "in_place"}[path]
    assert after[prefix % path] > before.get(prefix % path, 0.0)
    assert after.get(prefix % other, 0.0) == before.get(prefix % other, 0.0)
    assert out["flash_traces"] == {path: after[prefix % path] - before.get(prefix % path, 0.0)}
    assert out["flash_dense_reroutes"] == before.get("flash_dense_reroutes_total", 0.0)
    (line,) = [l for l in capsys.readouterr().out.splitlines() if l.startswith("step=1 ")]
    assert " donated=" in line and line.split(" flash=")[1].split(":")[0] == path
