"""Faults planted in the power-retention model, as subclasses that a
configuration's ``"model"`` can name
(``chipbench.tests.planted_faults_retention:<class>``): what the cell's
``correct`` has to refuse (on the chip, at the file's requests, all four).  The tests run them through
``run.main`` at a tiny size; ``tools/variant.py --config model=...`` runs them through
``run.py`` on the chip at the cell's own size."""

import jax

from moolib_tpu.models.retention_lm import PowerRetentionLM
from moolib_tpu.ops import retention


class NoStateWrite(PowerRetentionLM):
    """A join leaves the slot's state and normaliser as the slot's last
    holder left them (zeros, in a fresh engine)."""

    def write_state(self, cache, rows, slot):
        return cache


class NoDecay(PowerRetentionLM):
    """A decode step that skips the decay: the kernel is handed a log-gate of
    0, so after its prompt a sequence forgets nothing.  The step is traced
    once, with the kernel's entry replaced while it is."""

    def decode(self, params, cache, tokens, paged, mesh=None):
        real = retention.retention_decode
        retention.retention_decode = lambda q, k, v, lam, *a, **kw: real(
            q, k, v, 0.0 * lam, *a, **kw)
        try:
            return super().decode(params, cache, tokens, paged, mesh)
        finally:
            retention.retention_decode = real


class Bf16State(PowerRetentionLM):
    """The state and its normaliser kept in bfloat16: rounded at the join and
    after every decode step.  ``reduce_precision``, not a pair of converts:
    inside a jitted step XLA elides float32 -> bfloat16 -> float32 on the chip."""

    exponent_bits, mantissa_bits = 8, 7

    def _rounded(self, cache):
        return jax.tree.map(lambda x: jax.lax.reduce_precision(
            x, exponent_bits=self.exponent_bits, mantissa_bits=self.mantissa_bits), cache)

    def write_state(self, cache, rows, slot):
        return self._rounded(super().write_state(cache, rows, slot))

    def decode(self, params, cache, tokens, paged, mesh=None):
        logits, cache, counters = super().decode(params, cache, tokens, paged, mesh)
        return logits, self._rounded(cache), counters


class Fp8State(Bf16State):
    """The precision below that: the state rounded to float8 e4m3."""

    exponent_bits, mantissa_bits = 4, 3
