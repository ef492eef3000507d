"""On-chip validation of the pallas flash-attention kernels: numerics vs the
XLA dense path on REAL TPU hardware, across sequence lengths up to 8k, and at
the LM's head shape (8 heads of 128, T=2048 — the case ``chip_smoke.py`` runs).

Every test skips by itself where jax has no accelerator, so the file is part
of tier 1 (all skipped on the CPU) and runs on the chip with one command —
``tests/conftest.py`` pins the CPU only where ``JAX_PLATFORMS`` is unset:

    JAX_PLATFORMS=tpu python -m pytest tests/test_flash_attention_tpu.py -v

The kernel's share of a training step is the benchmark's
``flash_attn_share.train``; ``benchmarks/flash_bwd_tune.py`` sweeps the
backward's blocks.
"""

import os

import numpy as np
import pytest


def _tpu_device():
    import jax

    devs = [d for d in jax.devices() if d.platform != "cpu"]
    if not devs:
        pytest.skip("no accelerator device present")
    return devs[0]


@pytest.mark.parametrize("t", [512, 1024, 2048, 4096, 8192])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_dense_on_chip(t, causal):
    import jax
    import jax.numpy as jnp

    from moolib_tpu.ops.flash_attention import flash_attention
    from moolib_tpu.parallel.ring_attention import full_attention

    dev = _tpu_device()
    B, H, D = 2, 4, 64
    rng = np.random.default_rng(t)
    mk = lambda: jax.device_put(
        jnp.asarray(rng.normal(size=(B, t, H, D)).astype(np.float32) * 0.5), dev
    )
    q, k, v = mk(), mk(), mk()
    out = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=causal))(q, k, v)
    ref = jax.jit(lambda q, k, v: full_attention(q, k, v, causal=causal))(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3
    )


@pytest.mark.parametrize("t", [512, 2048, 4096])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_backward_matches_dense_on_chip(t, causal):
    """Pallas backward kernels (dq + dk/dv passes) vs dense-attention VJP."""
    import jax
    import jax.numpy as jnp

    from moolib_tpu.ops.flash_attention import flash_attention
    from moolib_tpu.parallel.ring_attention import full_attention

    dev = _tpu_device()
    B, H, D = 2, 4, 64
    rng = np.random.default_rng(t)
    mk = lambda: jax.device_put(
        jnp.asarray(rng.normal(size=(B, t, H, D)).astype(np.float32) * 0.5), dev
    )
    q, k, v, g = mk(), mk(), mk(), mk()
    # The reference must run with f32 matmuls forced: XLA's default TPU
    # einsum precision feeds bf16 into the MXU, and for causal attention the
    # early rows' concentrated probabilities (p ~ 1) turn single bf16-rounded
    # products into ~6e-3 absolute dv errors (dv only, causal only, 50-80
    # elements).  The pallas kernels accumulate through f32 dots, so the
    # *reference* is the noisy side.
    with jax.default_matmul_precision("highest"):
        _, vjp = jax.vjp(
            lambda q, k, v: flash_attention(q, k, v, causal=causal), q, k, v
        )
        _, vjp_ref = jax.vjp(
            lambda q, k, v: full_attention(q, k, v, causal=causal), q, k, v
        )
        got_all, want_all = vjp(g), vjp_ref(g)
    for got, want, name in zip(got_all, want_all, ("dq", "dk", "dv")):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-3, atol=2e-3, err_msg=name
        )


def test_flash_fwd_bwd_at_lm_head_shape_on_chip():
    """Forward and backward at the width the LM trains at (d=1024 as 8 heads
    of 128, T=2048, causal, bf16 activations) against dense attention."""
    import jax
    import jax.numpy as jnp

    from moolib_tpu.ops.flash_attention import flash_attention
    from moolib_tpu.parallel.ring_attention import full_attention

    dev = _tpu_device()
    B, T, H, D = 2, 2048, 8, 128
    rng = np.random.default_rng(1)
    mk = lambda: jax.device_put(
        jnp.asarray(rng.normal(size=(B, T, H, D)).astype(np.float32) * 0.5), dev
    )
    q, k, v, g = mk(), mk(), mk(), mk()
    # f32 operands with f32 matmuls forced on the reference side, for the
    # reason given in test_flash_backward_matches_dense_on_chip.
    with jax.default_matmul_precision("highest"):
        out, vjp = jax.vjp(
            lambda q, k, v: flash_attention(q, k, v, causal=True), q, k, v
        )
        ref, vjp_ref = jax.vjp(
            lambda q, k, v: full_attention(q, k, v, causal=True), q, k, v
        )
        got_all, want_all = (out, *vjp(g)), (ref, *vjp_ref(g))
    for got, want, name in zip(got_all, want_all, ("out", "dq", "dk", "dv")):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-3, atol=2e-3, err_msg=name
        )
    # The LM feeds the kernel bf16: same shape, the bf16 tolerances.
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    out = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))(qb, kb, vb)
    ref = jax.jit(lambda q, k, v: full_attention(q, k, v, causal=True))(qb, kb, vb)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), rtol=2e-2, atol=2e-2
    )


def test_flash_backward_matches_blockwise_oracle_on_chip():
    """Pallas backward vs the blockwise-jax VJP it replaced (the oracle)."""
    import jax
    import jax.numpy as jnp

    from moolib_tpu.ops import flash_attention as fa

    dev = _tpu_device()
    B, T, H, D = 2, 1024, 4, 64
    rng = np.random.default_rng(7)
    mk = lambda: jax.device_put(
        jnp.asarray(rng.normal(size=(B, T, H, D)).astype(np.float32) * 0.5), dev
    )
    q, k, v, g = mk(), mk(), mk(), mk()
    grads = {}
    for mode in ("pallas", "jax"):
        os.environ["MOOLIB_TPU_FLASH_BWD"] = mode
        try:
            # f32 matmuls forced for the same reason as the dense comparison
            # above: the blockwise-jax oracle's einsums otherwise ride the
            # MXU at bf16 input precision and the oracle becomes the noisy
            # side of the comparison.
            with jax.default_matmul_precision("highest"):
                _, vjp = jax.vjp(
                    lambda q, k, v: fa.flash_attention(q, k, v, causal=True), q, k, v
                )
                grads[mode] = vjp(g)
        finally:
            os.environ.pop("MOOLIB_TPU_FLASH_BWD", None)
    for got, want, name in zip(grads["pallas"], grads["jax"], ("dq", "dk", "dv")):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-3, atol=2e-3, err_msg=name
        )


def test_flash_bf16_on_chip():
    import jax
    import jax.numpy as jnp

    from moolib_tpu.ops.flash_attention import flash_attention
    from moolib_tpu.parallel.ring_attention import full_attention

    dev = _tpu_device()
    B, T, H, D = 2, 2048, 4, 64
    rng = np.random.default_rng(0)
    mk = lambda: jax.device_put(
        jnp.asarray(rng.normal(size=(B, T, H, D)).astype(np.float32)).astype(
            jnp.bfloat16
        ),
        dev,
    )
    q, k, v = mk(), mk(), mk()
    out = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))(q, k, v)
    ref = jax.jit(lambda q, k, v: full_attention(q, k, v, causal=True))(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), rtol=2e-2, atol=2e-2
    )


@pytest.mark.parametrize("kv_heads", [16, 4])
def test_flash_packed_in_place_at_the_train_cells_shape_on_chip(kv_heads):
    """The train cells' call (B=4 here 2, T=2048, 16 heads of 128) as
    ``Block`` makes it: the packed projection through Mosaic's in-place
    blocks, the logsumexp transposed to a row in VMEM, and with 4 K/V heads
    the grouped dk/dv sweep.  The result and the projection's cotangent
    against dense attention of the three slices, K and V repeated."""
    import jax
    import jax.numpy as jnp

    from moolib_tpu.ops.flash_attention import flash_attention, flash_attention_packed
    from moolib_tpu.parallel.ring_attention import dense_attention_lse

    dev = _tpu_device()
    B, T, H, D = 2, 2048, 16, 128
    rng = np.random.default_rng(kv_heads)
    mk = lambda *shape: jax.device_put(
        jnp.asarray(rng.normal(size=shape).astype(np.float32) * 0.5), dev)
    qkv, g = mk(B, T, (H + 2 * kv_heads) * D), mk(B, T, H * D)

    def slices(qkv):
        x = qkv.reshape(B, T, H + 2 * kv_heads, D)
        return x[:, :, :H], x[:, :, H:H + kv_heads], x[:, :, H + kv_heads:]

    def dense(qkv):
        q, k, v = slices(qkv)
        k, v = (jnp.repeat(x, H // kv_heads, axis=2) for x in (k, v))
        out, lse = dense_attention_lse(q, k, v, causal=True)
        return out.reshape(B, T, H * D), lse

    with jax.default_matmul_precision("highest"):
        out, vjp = jax.vjp(lambda x: flash_attention_packed(x, H, kv_heads), qkv)
        (ref, ref_lse), vjp_ref = jax.vjp(dense, qkv)
        (got,), (want,) = vjp(g), vjp_ref((g, jnp.zeros_like(ref_lse)))
        # the row logsumexp and its cotangent, through the three arrays' entry
        g_lse = mk(B, T, H)
        (_, lse), vjp_lse = jax.vjp(
            lambda x: flash_attention(*slices(x), return_lse=True), qkv)
        (got_lse,) = vjp_lse((g.reshape(B, T, H, D), g_lse))
        (want_lse,) = vjp_ref((g, g_lse))
    for a, b, name in ((out, ref, "out"), (got, want, "dqkv"), (lse, ref_lse, "lse"),
                       (got_lse, want_lse, "dqkv with a cotangent on lse")):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-3, err_msg=name)


@pytest.mark.parametrize("t", [1984, 2048])
@pytest.mark.parametrize("length", [1025, 1416, 1984])
@pytest.mark.parametrize("entry", ["packed", "arrays"])
def test_flash_with_a_length_on_chip(t, length, entry):
    """``lm_serve_longprompt``'s call: ONE sequence in a bucket of 1,984 rows
    (31 x 64: it does not tile, the last query block holds 448 rows and the
    last key block 960) or of 2,048, 16 heads of 128, bfloat16, as the packed
    projection and as three operands over 4 K/V heads.  Rows below ``length``
    against dense attention over the first ``length`` positions, rows at or
    past it exactly zero, though the operands there hold NaN; one program for
    the three lengths (the length is data)."""
    import jax
    import jax.numpy as jnp

    from moolib_tpu.ops.flash_attention import flash_attention, flash_attention_packed
    from moolib_tpu.parallel.ring_attention import full_attention

    dev = _tpu_device()
    H, D = 16, 128
    Hk = 16 if entry == "packed" else 4
    rng = np.random.default_rng(t + length)
    qkv = jnp.asarray(rng.normal(size=(1, t, H + 2 * Hk, D)).astype(np.float32) * 0.5)
    real = (jnp.arange(t) < length)[None, :, None, None]
    q, k, v = (jax.device_put(x.astype(jnp.bfloat16), dev)
               for x in (qkv[:, :, :H], qkv[:, :, H:H + Hk], qkv[:, :, H + Hk:]))
    poisoned = jax.device_put(jnp.where(real, qkv, jnp.nan).astype(jnp.bfloat16), dev)
    if entry == "packed":
        call = jax.jit(lambda x, n: flash_attention_packed(
            x.reshape(1, t, -1), H, Hk, length=n).reshape(1, t, H, D))
    else:
        call = jax.jit(lambda x, n: flash_attention(
            x[:, :, :H], x[:, :, H:H + Hk], x[:, :, H + Hk:], length=n))
    out = np.asarray(call(poisoned, jnp.int32(length)), np.float32)
    assert call._cache_size() == 1
    ref = jax.jit(lambda q, k, v: full_attention(
        q, *(jnp.repeat(x, H // Hk, axis=2) for x in (k, v)), causal=True))(
            q[:, :length], k[:, :length], v[:, :length])
    assert not np.isnan(out).any()
    np.testing.assert_allclose(out[:, :length], np.asarray(ref, np.float32), rtol=2e-2, atol=2e-2)
    assert (out[:, length:] == 0).all()
