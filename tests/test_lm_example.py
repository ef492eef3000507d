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


# ---- the train state is donated (ISSUE 28): the step updates it in place ----

_SMALL = ["--seq_len", "16", "--batch_size", "8", "--seed", "7", "--quiet"]
_PATHS = {
    "plain": ["--mesh", "", "--attention", "dense"],
    "dp2": ["--mesh", "dp=2", "--attention", "flash"],
}


class _StepSpy:
    """Stands where ``devmon.instrument_jit`` puts its wrapper around the
    step ``train`` built: after every call, the bytes of the ``params`` and
    ``opt_state`` passed in and whether the call consumed every leaf."""

    def __init__(self, fn):
        self.fn = fn
        self.calls = []

    def __call__(self, params, opt_state, tokens):
        import jax

        state = jax.tree_util.tree_leaves((params, opt_state))
        nbytes = sum(x.nbytes for x in state)
        out = self.fn(params, opt_state, tokens)
        self.calls.append((nbytes, all(x.is_deleted() for x in state)))
        return out

    def __getattr__(self, name):  # .lower, for the ahead-of-time compile
        return getattr(self.fn, name)


def _train_spied(monkeypatch, argv):
    from moolib_tpu.telemetry import devmon

    devmon.reset_for_tests()  # its cost cache is keyed by shapes alone
    spies = []

    def instrument(fn, name):
        spies.append(_StepSpy(fn))
        return spies[-1]

    monkeypatch.setattr(devmon, "instrument_jit", instrument)
    out = train(make_flags(argv))
    (spy,) = spies
    return out, spy


@pytest.mark.parametrize("path", list(_PATHS))
def test_lm_step_consumes_params_and_opt_state(monkeypatch, path):
    """Every leaf of the state passed to the step is gone after the call,
    from the first call on (under a mesh the second call runs a second
    program, compiled for the sharding the first one returned), and what the
    compiled step aliases is the whole state."""
    out, spy = _train_spied(
        monkeypatch, _SMALL + _PATHS[path] + ["--steps", "4", "--log_interval", "2"])
    assert len(spy.calls) == 4  # no executed warm-up: a loop step is a call
    assert all(consumed for _, consumed in spy.calls), spy.calls
    state_bytes = spy.calls[0][0]
    assert {n for n, _ in spy.calls} == {state_bytes}
    assert out["donated_bytes"] == state_bytes > 0
    assert out["param_placement"]["devices_per_array"] == (2 if path == "dp2" else 1)


@pytest.mark.parametrize("path", list(_PATHS))
def test_lm_train_reports_the_losses_of_the_undonated_step(monkeypatch, path):
    """``train``'s losses equal, to the bit, those of the same ``step`` jitted
    with nothing donated and driven by the same batches: the first batch is
    drawn and feeds the compile alone, and no weight moves before step 1."""
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
    _, step = lm.make_step(flags, model, opt, mesh)
    _, put = lm.jit_step(step, params, flags, mesh)
    # The same shardings, nothing donated: params and opt_state stay readable.
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
    assert out["losses"] == want


def test_lm_step_donated_bytes_gauge_and_result(monkeypatch):
    from moolib_tpu import telemetry

    out, spy = _train_spied(
        monkeypatch, _SMALL + _PATHS["plain"] + ["--steps", "2", "--log_interval", "1"])
    series = telemetry.get_registry().snapshot()["lm_step_donated_bytes"]["series"]
    assert [s["value"] for s in series] == [out["donated_bytes"]] == [spy.calls[0][0]]
