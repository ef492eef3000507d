"""Runner ``train``: the LM trainer, ``moolib_tpu.examples.lm.train``, as a
user starts it, for ``--seconds`` of steady steps.

The program's own loop runs; the benchmark sees it through ``on_stats``, which
``train`` calls after every ``log_interval`` steps once it has fetched the
loss, so each call marks a point where the host waited for the device.  After
the reference's steps (below), the ``warm_ticks``-th call ends set-up and opens
the window (under a mesh
the program compiles its step a second time at step 2, when its own outputs
come back sharded; that is set-up, as a user pays it); the first call at or past
``--seconds`` closes it, and the rate is all the window's tokens over all its
time.  In a traced run the profiler then records ``trace_seconds`` more of the
same loop, so tracing costs the rate nothing.  An exception from the callback
is what ends ``train`` (its ``finally`` flushes and closes as on any exit).

Correctness, outside the window: ``train`` starts at ``--log_interval 1``, so
the first losses it reports are those of its second batch under the initial
weights, of its third after one optimizer step, and so on; the plain reference
computes the same losses from the same seed in float32, with its own backward
pass and its own AdamW, for ``reference_updates`` steps.  So a wrong forward
pass shows in the first loss, and a wrong gradient or optimizer step in the
later ones.  The callback then sets ``flags.log_interval`` (which ``train``
reads at every step) to the traffic file's, so that the measured steps are
dispatched back to back and the host waits once in ``log_interval`` steps.
"""

from __future__ import annotations

import math
import time
from typing import Dict, List

from chipbench import harness
from chipbench.reference import gpt


class _WindowDone(Exception):
    pass


def lm_argv(config: Dict, traffic: Dict, chips: int, seed: int) -> List[str]:
    use = config["uses"][traffic["use"]]
    return [
        "--vocab", str(config["vocab_size"]), "--d_model", str(config["n_embd"]),
        "--heads", str(config["n_head"]), "--layers", str(use["n_layer"]),
        "--seq_len", str(traffic["seq_len"]),
        "--batch_size", str(traffic["batch_per_chip"] * chips),
        "--attention", use["attention"], "--pos", use["position"],
        "--mesh", traffic["mesh"], "--learning_rate", str(traffic["learning_rate"]),
        "--log_interval", "1",  # for the first loss; the window then sets the file's
        "--steps", str(10 ** 9), "--seed", str(seed), "--quiet",
    ]


def reference_losses(flags, n_head: int, traffic: Dict):
    """The losses of ``train``'s first logged steps, by the plain reference:
    the weights ``TransformerLM.init`` draws from the seed (the initialisers
    depend on the key and the parameter's path, not on the attention kind or
    the input's shape, so one jitted init on a short input gives them), the
    batches of the program's own generator from its second on (the first is
    the warm-up step's, whose result ``train`` drops), and between two batches
    one AdamW step with the numbers the traffic file gives: what
    ``optax.adamw(learning_rate)`` computes by default.  Beside them, the same
    batches' losses under the initial weights: how far the updates moved each
    loss is what a wrong gradient could get wrong."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from moolib_tpu.examples import lm
    from moolib_tpu.models.transformer import TransformerLM

    model = TransformerLM(
        vocab_size=flags.vocab, d_model=flags.d_model, num_layers=flags.layers,
        num_heads=flags.heads, max_len=flags.seq_len, attention="dense",
        pos_embedding=flags.pos)
    params = jax.jit(model.init)(jax.random.key(flags.seed), jnp.zeros((1, 8), jnp.int32))
    rng = np.random.default_rng(flags.seed)
    lm.make_batch(rng, flags)
    batches = [jnp.asarray(lm.make_batch(rng, flags))
               for _ in range(int(traffic["reference_updates"]) + 1)]
    adamw = {"learning_rate": traffic["learning_rate"], **traffic["adamw"]}
    initial = [gpt.copy_task_losses(params, [b], flags.layers, n_head, adamw)[0] for b in batches]
    return gpt.copy_task_losses(params, batches, flags.layers, n_head, adamw), initial


class _Window:
    def __init__(self, flags, seconds: float, traffic: Dict, traced: bool, workload: str,
                 compiles: harness.CompileCounter):
        self.flags = flags
        self.log_interval = int(traffic["log_interval"])
        self.seconds = seconds
        self.trace_seconds = float(traffic["trace_seconds"]) if traced else 0.0
        self.tracer = harness.TraceWindow(workload) if traced else None
        self.compiles = compiles
        self.warm_ticks = int(traffic["warm_ticks"])
        self.n_reference = int(traffic["reference_updates"]) + 1
        self.t0 = self.t_end = None
        self.step0 = self.step_end = None
        self.losses: List[float] = []
        self.ticks: List[float] = []
        self.compiles_at = []
        self._loop_span = None  # held open between two callbacks of a traced tail

    def _swap_span(self, name=None):
        if self._loop_span is not None:
            self._loop_span.__exit__(None, None, None)
        self._loop_span = harness.span(name) if name is not None else None
        if self._loop_span is not None:
            self._loop_span.__enter__()

    def __call__(self, stats: Dict) -> None:
        now = time.monotonic()
        self.losses.append(float(stats["loss"]))
        if len(self.losses) == self.n_reference:
            # train() reads flags.log_interval at every step: from here on it
            # fetches the loss, and so waits for the device, as rarely as a
            # user's run does.
            self.flags["log_interval"] = self.log_interval
        if len(self.losses) < self.n_reference + self.warm_ticks:
            return  # the reference's steps, then ticks that may still compile: set-up
        if self.t0 is None:
            self.t0, self.step0 = now, stats["step"]
            self.compiles_at.append(self.compiles.snapshot())
            return
        if self.t_end is None:
            self.ticks.append(now)
            if now - self.t0 < self.seconds:
                return
            self.t_end, self.step_end = now, stats["step"]
            self.compiles_at.append(self.compiles.snapshot())
            if self.tracer is None:
                raise _WindowDone
            self.tracer.start()
            self._window_span = harness.span("trace_window")
            self._window_span.__enter__()
            self._swap_span("train.program_loop")
            return
        if now - self.tracer.started_at < self.trace_seconds:
            self._swap_span("train.program_loop")
            return
        self._swap_span()
        self._window_span.__exit__(None, None, None)
        self.tracer.stop()
        raise _WindowDone


def run(*, cell, config, traffic, seed, seconds, traced, devices, setup) -> harness.Measured:
    from moolib_tpu.examples import lm

    compiles = harness.CompileCounter()
    flags = lm.make_flags(lm_argv(config, traffic, cell["chips"], harness.fold_seed(seed)))
    with setup.phase("reference_check"):
        want, want_initial = reference_losses(flags, config["n_head"], traffic)
    window = _Window(flags, seconds, traffic, traced, cell["name"], compiles)
    t_train = time.monotonic()
    try:
        lm.train(flags, on_stats=window)
    except _WindowDone:
        pass
    setup.phases["program_init_compile_warm"] = window.t0 - t_train
    setup_s = window.t0 - setup.t_start
    steps = window.step_end - window.step0
    tokens = steps * flags.batch_size * flags.seq_len
    window_s = window.t_end - window.t0
    periods = [(b - a) / window.log_interval
               for a, b in zip([window.t0] + window.ticks, window.ticks)]
    got = window.losses[:len(want)]
    rel = [abs(g - w) / abs(w) for g, w in zip(got, want)]
    tol = config["tolerance"]
    in_window = window.compiles_at[1]["programs"] - window.compiles_at[0]["programs"]
    finite = all(math.isfinite(x) for x in window.losses)
    compared = {"loss_rel_first": [rel[0], tol["train_loss_rel"]],
                "loss_rel_after_updates_max": [max(rel[1:]), tol["train_loss_after_updates_rel"]],
                "losses_not_finite": [sum(not math.isfinite(x) for x in window.losses), 0],
                "compiles_in_window": [in_window, 0]}
    correct = harness.within(compared)
    harness.say("SETUP", setup.report(setup_s, compiles))
    measured = harness.Measured(
        attempted=steps, failed=0 if finite else 1, correct=correct,
        values={"tokens": float(tokens), "window_s": window_s, "steps": float(steps),
                "setup_s": setup_s, "n_layer": flags.layers, "seq_len": flags.seq_len},
        lists={"step_period_s": periods},
        notes={"compared": compared, "first_losses": got, "reference_losses": want, "loss_rel_err": rel,
               "reference_update_effect_rel": [abs(w - i) / abs(w) for w, i in zip(want, want_initial)],
               "last_loss": window.losses[-1], "compiles_in_window": in_window,
               "steps": steps, "window_s": window_s,
               "step_period_ms_by_tick": [round(p * 1e3, 2) for p in periods]},
    )
    if window.tracer is not None:
        measured.trace = window.tracer.reduce(len(devices))
    return measured
