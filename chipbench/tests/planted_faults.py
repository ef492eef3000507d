"""Faults planted in the hybrid linear-attention model, as subclasses that a
configuration's ``"model"`` can name (``chipbench.tests.planted_faults:<class>``):
what the cell's ``correct`` has to refuse, or is known not to see.  The tests
run them through ``run.main`` at a tiny size; ``tools/planted.py`` runs them
through ``run.py`` on the chip at the cell's own size."""

import jax

from moolib_tpu.models.hybrid_kda import HybridKdaMoELM


class NoStateWrite(HybridKdaMoELM):
    """A join leaves the slot's recurrent state and convolution tail as the
    slot's last holder left them (zeros, in a fresh engine)."""

    def write_state(self, cache, rows, slot):
        return cache


class Bf16State(HybridKdaMoELM):
    """The recurrent state kept in bfloat16: rounded at the join and after
    every decode step.  ``reduce_precision``, not a pair of converts: inside a
    jitted step XLA elides float32 -> bfloat16 -> float32 on the chip."""

    exponent_bits, mantissa_bits = 8, 7

    def _rounded(self, cache):
        kda = jax.lax.reduce_precision(
            cache.slots["kda"], exponent_bits=self.exponent_bits, mantissa_bits=self.mantissa_bits)
        return cache._replace(slots={**cache.slots, "kda": kda})

    def write_state(self, cache, rows, slot):
        return self._rounded(super().write_state(cache, rows, slot))

    def decode(self, params, cache, tokens, paged, mesh=None):
        logits, cache, counters = super().decode(params, cache, tokens, paged, mesh)
        return logits, self._rounded(cache), counters


class Fp8State(Bf16State):
    """The precision below that: the state rounded to float8 e4m3."""

    exponent_bits, mantissa_bits = 4, 3
