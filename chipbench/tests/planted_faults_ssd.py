"""Faults planted in the Mamba-2 / NoPE grouped-query model with experts, as
subclasses that a configuration's ``"model"`` can name
(``chipbench.tests.planted_faults_ssd:<class>``).  The first three are what
the cell's ``correct`` MUST refuse; the last three are read and reported, each
as told apart or not (the configuration's ``tolerance`` has the readings).  The
tests run them through ``run.main`` at a tiny size; ``tools/variant.py --config
model=...`` runs them through ``run.py`` on the chip at the cell's own size."""

import jax
import jax.numpy as jnp

from moolib_tpu.models.ssd_moe import SsdGqaMoELM
from moolib_tpu.ops import ssd


class Bf16State(SsdGqaMoELM):
    """(a) The recurrence's state and the convolution's tail kept in bfloat16:
    rounded at the join and after every decode step.  ``reduce_precision``, not
    a pair of converts: inside a jitted step XLA elides float32 -> bfloat16 ->
    float32 on the chip."""

    def _rounded(self, cache):
        return cache._replace(slots=jax.tree.map(
            lambda x: jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7), cache.slots))

    def write_state(self, cache, rows, slot):
        return self._rounded(super().write_state(cache, rows, slot))

    def decode(self, params, cache, tokens, paged, mesh=None):
        logits, cache, counters = super().decode(params, cache, tokens, paged, mesh)
        return logits, self._rounded(cache), counters


class ChunksFromEmptyState(SsdGqaMoELM):
    """(b) A prefill whose chunks each start from an empty state: the products
    inside a chunk are right, nothing is carried from one chunk to the next,
    and the state handed back is the last live chunk's own."""

    def _scan_prefill(self, x, dt, A, B, C, last):
        T, Q = x.shape[0], min(ssd.CHUNK, x.shape[0])
        parts = lambda a: a.reshape((T // Q, Q) + a.shape[1:])

        def one(xs):
            x, dt, B, C, first = xs
            return ssd.ssd_prefill(x, dt, A, B, C, length=jnp.clip(last - first, 0, Q))

        y, state = jax.lax.map(
            one, (parts(x), parts(dt), parts(B), parts(C), jnp.arange(T // Q) * Q))
        return y.reshape(x.shape), state[(last - 1) // Q]


class NoTailWrite(SsdGqaMoELM):
    """(c) A join that writes no convolution tail: the slot's tails stay as
    its last holder left them (zeros, in a fresh engine); the state is
    written."""

    def write_state(self, cache, rows, slot):
        written = super().write_state(cache, rows, slot)
        return written._replace(slots={**written.slots, "conv": cache.slots["conv"]})


class ResidualOne(SsdGqaMoELM):
    """(d) ``residual_multiplier`` 1: every branch is added whole."""

    def _branch(self, h, y):
        return h + y


class SqrtScores(SsdGqaMoELM):
    """(e) Scores over ``sqrt(128)`` for 128: ``attention_multiplier`` read as
    the kernels' own ``head_dim ** -0.5``."""

    @property
    def q_scale(self):
        return 1.0


class GateAfterNorm(SsdGqaMoELM):
    """(f) The gate after the norm: ``RMSNorm(y) * silu(z)`` for ``RMSNorm(y *
    silu(z))``."""

    def _gated_out(self, p, y, x, z):
        y = (y + p["d"][:, None] * x).reshape(z.shape)
        return self._dot(self._norm(y, p["gate_norm"]) * jax.nn.silu(z), p["w_out"])
