"""Faults planted in the Mamba / multi-query model, as subclasses that a
configuration's ``"model"`` can name
(``chipbench.tests.planted_faults_ssm:<class>``): what the cell's ``correct``
has to refuse (on the chip, at the file's requests, all five).  The tests run
them through ``run.main`` at a tiny size; ``tools/variant.py --config
model=...`` runs them through ``run.py`` on the chip at the cell's own size
(``tools/planted.py`` names ``planted_faults`` alone)."""

import jax
import jax.numpy as jnp

from moolib_tpu.models.jamba import JambaLM


class Bf16State(JambaLM):
    """The scan's state and the convolution's tail kept in bfloat16: rounded
    at the join and after every decode step.  ``reduce_precision``, not a pair
    of converts: inside a jitted step XLA elides float32 -> bfloat16 -> float32
    on the chip."""

    def _rounded(self, cache):
        return cache._replace(slots=jax.tree.map(
            lambda x: jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7), cache.slots))

    def write_state(self, cache, rows, slot):
        return self._rounded(super().write_state(cache, rows, slot))

    def decode(self, params, cache, tokens, paged, mesh=None):
        logits, cache, counters = super().decode(params, cache, tokens, paged, mesh)
        return logits, self._rounded(cache), counters


class NoStateWrite(JambaLM):
    """A join leaves the slot's state and tail as the slot's last holder left
    them (zeros, in a fresh engine)."""

    def write_state(self, cache, rows, slot):
        return cache


class StateAtBucketEnd(JambaLM):
    """The prefill's state and tail taken at the bucket's end: the padding is
    scanned as if it were prompt, not held still."""

    def _mamba_prefill(self, p, h, last):
        return super()._mamba_prefill(p, h, h.shape[0])


class NoInnerNorms(JambaLM):
    """The three inner RMSNorms left out: the x-projection's outputs go to the
    step's projection and to the scan as they are (Mamba-1 without the Jamba
    family's addition)."""

    def _scan_inputs(self, p, u):
        R, N = self.mamba_dt_rank, self.mamba_d_state
        x = self._dot(u, p["w_x"])
        dt = jax.nn.softplus(self._dot(x[:, :R], p["w_dt"]) + p["dt_bias"])
        return dt, x[:, R:R + N], x[:, R + N:], -jnp.exp(p["a_log"])


class NoTailShift(JambaLM):
    """A decode step that does not shift the convolution's tail: every step
    convolves its input with the three the prefill left."""

    def _mamba_decode(self, p, h, state, conv, layer, active):
        h, state, _shifted = super()._mamba_decode(p, h, state, conv, layer, active)
        return h, state, conv
