"""Long-context TransformerLM training throughput on real hardware.

Times the full jitted train step (forward + backward + adamw) of the
framework's TransformerLM with the pallas flash-attention kernel, bf16
compute, at sequence lengths up to 8k, and reports tokens/s and MFU
(6*N*tokens/step approximation vs the chip's dense bf16 peak).  The
reference has no long-context capability (SURVEY.md §5.7) — this bench
documents the new one on hardware.  One process; timing is a host clock
around ``block_until_ready``, compilation outside the window.

    python benchmarks/lm_bench.py
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    import jax
    import jax.numpy as jnp
    import optax

    from moolib_tpu.models.transformer import TransformerLM
    from moolib_tpu.utils import init_compile_cache

    init_compile_cache()
    if jax.default_backend() == "cpu" and os.environ.get("MOOLIB_ALLOW_CPU") != "1":
        raise SystemExit(
            "lm_bench needs an accelerator backend "
            "(MOOLIB_ALLOW_CPU=1 for a labeled plumbing-proof run)"
        )
    from moolib_tpu.telemetry import devmon

    dev = jax.devices()[0]
    # Per-chip peak from devmon's table: None on the CPU (plumbing runs
    # report mfu as null), an error for a kind the table lacks.
    peak = devmon.peak_flops(dev.device_kind)
    # Model scale is env-tunable; the default (d=1024, L=12, ~220M params)
    # keeps per-layer matmuls at 1024x4096 — big enough to fill the MXU,
    # where the earlier d=512 draft would cap MFU well below the 35% target.
    D = int(os.environ.get("MOOLIB_LM_DMODEL", 1024))
    L = int(os.environ.get("MOOLIB_LM_LAYERS", 12))
    H = max(4, D // 128)
    KV = int(os.environ.get("MOOLIB_LM_KV_HEADS", 0)) or None  # GQA sweeps
    # fused = chunked-vocab cross-entropy (ops/xent.py): the [B,T,32768] f32
    # logits tensor never materializes.  naive = materialized log_softmax,
    # kept as the comparison row (MOOLIB_LM_XENT=naive).
    xent_mode = os.environ.get("MOOLIB_LM_XENT", "fused")
    if xent_mode not in ("fused", "fused_bf16", "naive"):
        # Rows are keyed by this string downstream: a typo'd mode must fail
        # loudly, not label a row wrongly.
        raise SystemExit(
            f"MOOLIB_LM_XENT must be fused|fused_bf16|naive, got {xent_mode!r}"
        )
    xent_chunk = (
        int(os.environ.get("MOOLIB_LM_XENT_CHUNK", 4096))
        if xent_mode.startswith("fused") else None
    )
    # What the per-block checkpoint saves on remat rows; "dots" keeps matmul
    # outputs so the MXU never re-runs in the backward (models/transformer.py).
    from moolib_tpu.models.transformer import REMAT_POLICIES

    remat_policy = os.environ.get("MOOLIB_LM_REMAT_POLICY", "full")
    if remat_policy not in REMAT_POLICIES:
        raise SystemExit(
            f"MOOLIB_LM_REMAT_POLICY must be one of {'|'.join(REMAT_POLICIES)}, "
            f"got {remat_policy!r}"
        )
    print(f"# platform={dev.platform} device={dev.device_kind} "
          f"count={len(jax.devices())} d_model={D} layers={L} kv_heads={KV or H} xent={xent_mode}"
          + (f" chunk={xent_chunk}" if xent_chunk else "")
          + (f" remat_policy={remat_policy}" if remat_policy != "full" else ""))
    print(f"{'T':>6} {'B':>3} {'remat':>5} {'step_ms':>9} {'tokens_s':>10} {'mfu':>6} {'mfu_att':>7}")

    rows = []
    # (T, B, remat): constant 16k-token steps, plus remat rows at long T
    # where checkpointing lets the batch double within the same HBM.
    # MOOLIB_LM_CONFIGS="T,B,remat;..." overrides (CPU plumbing runs).
    cfg_env = os.environ.get("MOOLIB_LM_CONFIGS")
    if cfg_env:
        configs = [
            (int(t), int(b), r.strip().lower() in ("1", "true"))
            for t, b, r in (c.split(",") for c in cfg_env.split(";") if c.strip())
        ]
    else:
        configs = [
            (1024, 16, False), (2048, 8, False), (4096, 4, False),
            (4096, 8, True), (8192, 2, False), (8192, 4, True),
        ]
    for T, B, remat in configs:
        # On remat=False rows the policy is a no-op: stamp them "full" so a
        # policy-sweep run can't fold duplicate keys for identical configs.
        row_policy = remat_policy if remat else "full"
        # MOOLIB_LM_ATTENTION=dense for CPU plumbing runs: pallas interpret
        # mode is orders of magnitude too slow to even smoke-test there.
        model = TransformerLM(
            vocab_size=32768, d_model=D, num_heads=H, num_kv_heads=KV,
            num_layers=L, max_len=8192,
            attention=os.environ.get("MOOLIB_LM_ATTENTION", "flash"),
            dtype=jnp.bfloat16, remat=remat, remat_policy=remat_policy,
        )
        rng = np.random.default_rng(T)
        toks = jnp.asarray(rng.integers(0, 32768, size=(B, T), dtype=np.int32))
        try:
            params = model.init(jax.random.key(0), toks)
            # MFU convention: 6N counts matmul-participating params only.
            # The embed/pos tables are gathers (0 matmul FLOPs per token);
            # the lm_head Dense IS a matmul and stays counted.
            n_params = sum(
                leaf.size
                for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]
                if not any(getattr(p, "key", None) in ("embed", "pos") for p in path)
            )
            opt = optax.adamw(1e-4)
            opt_state = opt.init(params)

            if xent_mode.startswith("fused"):
                from moolib_tpu.ops.xent import lm_head_xent

                cdt = jnp.bfloat16 if xent_mode == "fused_bf16" else None

                def loss_fn(p, t):
                    return lm_head_xent(model, p, t, chunk_size=xent_chunk,
                                        compute_dtype=cdt)
            else:
                def loss_fn(p, t):
                    logits = model.apply(p, t)
                    logp = jax.nn.log_softmax(
                        logits[:, :-1].astype(jnp.float32), -1
                    )
                    return -jnp.take_along_axis(
                        logp, t[:, 1:, None], axis=-1
                    ).mean()

            from functools import partial

            @partial(jax.jit, donate_argnums=(0, 1))
            def step(p, s, t):
                loss, g = jax.value_and_grad(loss_fn)(p, t)
                up, s = opt.update(g, s, p)
                return optax.apply_updates(p, up), s, loss

            # XLA-counted step cost (lower() only — runs nothing, so the
            # donated param/opt buffers below are still intact afterwards).
            sc = devmon.step_cost(
                f"lm_bench.step.T{T}.B{B}", step, params, opt_state, toks
            )

            # step donates its param/opt buffers: thread the state through.
            for _ in range(2):  # compile + warm
                params, opt_state, loss = step(params, opt_state, toks)
            jax.block_until_ready(loss)
            iters = 8
            t0 = time.perf_counter()
            for _ in range(iters):
                params, opt_state, loss = step(params, opt_state, toks)
            jax.block_until_ready(loss)
            sec = (time.perf_counter() - t0) / iters
        except Exception as e:  # noqa: BLE001 — backend-specific OOM types
            msg = str(e)
            if "RESOURCE_EXHAUSTED" not in msg and "out of memory" not in msg.lower():
                raise  # only real OOMs become rows; compile errors must fail
            print(f"{T:>6} {B:>3} {str(remat):>5} {'OOM':>9}")
            rows.append(
                {"T": T, "B": B, "remat": remat, "remat_policy": row_policy,
                 "xent": xent_mode, "xent_chunk": xent_chunk, "oom": True}
            )
            continue
        tokens_s = B * T / sec
        # Standard 6*N*D transformer FLOPs (fwd+bwd) + attention term
        # 12*L*H*hd*T^2... keep the 6ND convention and report it as such.
        flops = 6.0 * n_params * B * T
        # The 6ND convention omits attention's O(T²) score matmuls — real
        # model FLOPs that reach L·T·d/N = 54.5% of 6ND at T=8192/d=1024
        # (N = the matmul-only ~185M computed above, not the ~220M total),
        # so the apparent long-T "MFU drop" is partly accounting.  Causal fwd
        # QK^T+PV ≈ 2·B·T²·d_model FLOPs per layer (half the full 4·B·T²·d),
        # backward 2× that: 6·L·B·T²·d_model total.  GQA shrinks K/V
        # projections (already in 6ND via n_params), not these.  Remat
        # recompute stays excluded from both fields: hardware work, not
        # useful model FLOPs.
        attn_flops = 6.0 * L * B * T * T * D
        # None (json null) when no peak is known (CPU plumbing runs): NaN
        # would make the JSON line unparseable for strict consumers.
        mfu = flops / sec / peak if peak else None
        mfu_attn = (flops + attn_flops) / sec / peak if peak else None
        # XLA's own count of the compiled step (includes attention scores,
        # excludes nothing the compiler sees) — the column the always-on
        # step_mfu gauge would report, alongside the 6ND convention rows.
        mfu_xla = (
            sc.flops / sec / peak if (peak and sc is not None) else None
        )
        print(f"{T:>6} {B:>3} {str(remat):>5} {sec * 1e3:>9.2f} "
              f"{tokens_s:>10.0f} {'n/a' if mfu is None else round(mfu, 3):>6} "
              f"{'n/a' if mfu_attn is None else round(mfu_attn, 3):>7}")
        rows.append(
            {"T": T, "B": B, "remat": remat, "remat_policy": row_policy,
             "xent": xent_mode, "xent_chunk": xent_chunk,
             "step_ms": round(sec * 1e3, 2),
             "tokens_per_s": round(tokens_s, 1),
             "mfu_6nd": None if mfu is None else round(mfu, 4),
             "mfu_attn": None if mfu_attn is None else round(mfu_attn, 4),
             "mfu_xla": None if mfu_xla is None else round(mfu_xla, 4)}
        )
    print(json.dumps({"lm_train": {
        "platform": dev.platform, "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
        "d_model": D, "layers": L, "kv_heads": KV or H, "rows": rows}}))


if __name__ == "__main__":
    main()
