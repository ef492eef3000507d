"""Test configuration: run everything on a virtual 8-device CPU mesh.

Multi-chip sharding is validated without TPU hardware by forcing the host
platform to expose 8 XLA CPU devices (the moolib-reference analogue is the
one-process-many-peers loopback pattern, SURVEY.md §4).
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

# The suite is written for the 8 forced host devices.  An explicit
# JAX_PLATFORMS wins, which is how tests/test_flash_attention_tpu.py is run
# on the chip; set before jax is imported, the variable is all it takes.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402


@pytest.fixture
def free_port():
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def grab_port() -> int:
    """Module-level port helper for subprocess tests (the free_port fixture
    covers in-process uses); one definition so a strategy change (e.g.
    SO_REUSEADDR) lands everywhere."""
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def subprocess_env(root: str) -> dict:
    """Env for spawning repo entry points: repo on PYTHONPATH, CPU pinned."""
    import os

    return dict(
        os.environ,
        PYTHONPATH=root + os.pathsep + os.environ.get("PYTHONPATH", ""),
        JAX_PLATFORMS="cpu",
    )


def own_programs(model):
    """``model`` (a ``TransformerLM``) as the engine wraps it, hiding the form
    that lets an admission ride a decode step (``decode_with_prompt``): every
    join takes ``engine_prefill`` and ``engine_join``, as under every decoder
    that does not offer the form."""
    from moolib_tpu.models.transformer import PagedTransformerLM

    class OwnPrograms:
        def __init__(self, inner):
            self._inner = inner

        def __getattr__(self, name):
            if name == "decode_with_prompt":
                raise AttributeError(name)
            return getattr(self._inner, name)

    return OwnPrograms(PagedTransformerLM(model))
