"""What the three file-built decoders share (``models/decoder_parts.py``), and
that sharing it moved nothing: the weights each draws from a key and the tokens
the engine decodes from them are those of the commit before the module existed.

``python tests/test_decoder_parts.py`` prints the constants of ``GOLDEN`` for
the tree it runs in.
"""

import ast
import hashlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from moolib_tpu.engine import ContinuousBatchingEngine  # noqa: E402
from moolib_tpu.models import decoder_parts as parts  # noqa: E402
from moolib_tpu.models import hybrid_kda, latent_moe, retention_lm  # noqa: E402

DECODERS = {
    "latent": (latent_moe.LatentMoELM, latent_moe.tiny_config),
    "hybrid": (hybrid_kda.HybridKdaMoELM, hybrid_kda.tiny_config),
    "retention": (retention_lm.PowerRetentionLM, retention_lm.tiny_config),
}
REQUESTS = ((40, 6), (64, 5))  # (prompt length, budget): both in the bucket of 64


def _draws_and_tokens(name):
    """(sha256 over the leaves ``init`` draws from key 0: path, dtype, shape
    and bytes, in tree order; the tokens a four-slot engine emits for the two
    prompts of ``REQUESTS``)."""
    cls, tiny = DECODERS[name]
    # float32: this CPU's dot takes no bfloat16 (the other suites do the same).
    model = cls.from_config(tiny(), dtype=jnp.float32)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    digest = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        leaf = np.asarray(leaf)
        digest.update(f"{jax.tree_util.keystr(path)} {leaf.dtype} {leaf.shape}".encode())
        digest.update(leaf.tobytes())
    eng = ContinuousBatchingEngine(
        model, params, slots=4, block_size=16, max_seq_len=128, max_prompt_len=64,
        min_prompt_len=33)
    rng = np.random.default_rng(0)
    live, out = {}, {}
    for i, (n, budget) in enumerate(REQUESTS):
        slot, _emitted = eng.submit(rng.integers(0, model.vocab_size, n).astype(np.int32), budget)
        live[slot] = i
    while live:
        _emissions, finished = eng.step()
        for slot in finished:
            out[live.pop(slot)] = eng.retire(slot)
    return digest.hexdigest(), [out[i] for i in range(len(REQUESTS))]


GOLDEN = {
    'hybrid': ('1cd259f3e01c9ea48163a2a91c8e54d4cdc9a1ab45f9c72f6de3130f2a3cfa96', [[383, 372, 152, 276, 1, 101], [317, 139, 266, 101, 100]]),
    'latent': ('eb3b09637d9b6cdf5b304174eaabe84c6dd7380f3a466583647058334a49356e', [[158, 92, 306, 359, 436, 307], [400, 334, 2, 419, 447]]),
    'retention': ('3939e24327971daa9911b2975879aa65db4afa001d1f816aa9d58842d55722d8', [[98, 148, 55, 26, 52, 344], [53, 183, 20, 357, 148]]),
}


@pytest.mark.parametrize("name", sorted(DECODERS))
def test_init_draws_and_greedy_tokens_are_the_parents(name):
    """``GOLDEN`` was recorded by this file's ``__main__`` at the parent commit
    (0fe79e6, PR 46), before any decoder was edited: each decoder's own
    ``w`` closure, ``_norm``, ``_rope``, ``_dot`` and ``write_state`` then.
    The same tree leaf for leaf (names, shapes, dtypes, values) and the same
    greedy tokens now say the shared parts draw and compute what those did."""
    assert _draws_and_tokens(name) == GOLDEN[name]


def _former_mla_rope(x, pos, theta):
    """``LatentMoELM._rope`` as it stood at the parent commit."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.asarray(pos, jnp.float32)[..., None] * freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x = x.astype(jnp.float32)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _former_retention_rope(x, pos, theta):
    """``PowerRetentionLM._rope`` as it stood at the parent commit: x [T,
    heads, d] at positions pos [T]."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.asarray(pos, jnp.float32)[:, None, None] * freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


@pytest.mark.parametrize("caller", ["mla", "retention"])
def test_rope_half_split_serves_both_callers(caller):
    """The one rotary embedding against each decoder's former formula, at the
    shapes that decoder passes it, bit for bit, jitted as the models run it."""
    rng = np.random.default_rng(3)
    T, theta = 37, 1e6
    pos = jnp.asarray(rng.integers(0, 8192, T), jnp.int32)
    draw = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    if caller == "mla":  # the queries' rope part [T, H, r], the latent row's [T, r]
        cases = [(draw(T, 4, 32), pos[:, None], _former_mla_rope, pos[:, None]),
                 (draw(T, 32), pos, _former_mla_rope, pos)]
    else:  # q [T, H, d] and k [T, G, d] at pos [T]
        cases = [(draw(T, 4, 128), pos[:, None], _former_retention_rope, pos),
                 (draw(T, 2, 128), pos[:, None], _former_retention_rope, pos)]
    for x, pos_now, former, pos_then in cases:
        got = jax.jit(lambda x, p: parts.rope_half_split(x, p, theta))(x, pos_now)
        want = jax.jit(lambda x, p: former(x, p, theta))(x, pos_then)
        assert got.dtype == jnp.float32 and got.shape == x.shape
        assert np.array_equal(np.asarray(got), np.asarray(want))


def test_models_import_nothing_from_the_engine():
    """``engine/`` imports ``models/``; no module of ``models/`` imports
    ``engine``, absolutely or relatively."""
    root = os.path.dirname(os.path.abspath(parts.__file__))
    found = []
    for name in sorted(os.listdir(root)):
        if not name.endswith(".py"):
            continue
        with open(os.path.join(root, name)) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                modules = [alias.name for alias in node.names]
            elif isinstance(node, ast.ImportFrom):
                # ``from .. import engine`` names it among what it imports.
                modules = [node.module or ""] + [
                    f"{node.module or ''}.{alias.name}" for alias in node.names]
            else:
                continue
            found += [(name, node.lineno, m) for m in modules if "engine" in m.split(".")]
    assert not found


@pytest.mark.parametrize("name", sorted(DECODERS))
def test_from_config_refuses_every_key_it_cannot_honour_by_name(name):
    """One sentence, the model's name and every bad key, sorted; a path and a
    dict build the same model; ``dtype`` is an override, not a key."""
    cls, tiny = DECODERS[name]
    bad = {"latent": {"rope_scaling": {"factor": 2}, "n_group": 2},
           "hybrid": {"use_rope": True, "n_group": 2},
           "retention": {"rope_scaling": {"factor": 2}, "attention_bias": True}}[name]
    with pytest.raises(ValueError) as e:
        cls.from_config({**tiny(), **bad})
    assert str(e.value) == (
        f"{cls.__name__} does not implement the file's {', '.join(sorted(bad))}")
    model = cls.from_config(tiny(), dtype=jnp.float32, max_len=96)
    assert model.dtype == jnp.float32 and model.max_len == 96
    assert cls.from_config(tiny()).dtype == jnp.bfloat16


def test_load_config_reads_a_path_and_leaves_the_configuration_alone(tmp_path):
    import json

    path = tmp_path / "config.json"
    path.write_text(json.dumps({"hidden_size": 8, "max_len": 4}))
    config, dtype = parts.load_config(str(path), {"dtype": jnp.float32, "max_len": 16})
    assert config == {"hidden_size": 8, "max_len": 16} and dtype == jnp.float32
    given = {"hidden_size": 8}
    assert parts.load_config(given, {"max_len": 2}) == (
        {"hidden_size": 8, "max_len": 2}, jnp.bfloat16)
    assert given == {"hidden_size": 8}


def test_weight_drawer_draws_in_call_order_and_a_stack_slice_by_slice():
    """Key i of the split is the i-th draw's, whoever takes it; a stack of
    three or more axes splits its key again over the leading axis."""
    key = jax.random.PRNGKey(5)
    split = jax.random.split(key, 4)
    keys, w = parts.weight_drawer(key, 4, jnp.bfloat16)
    flat = w((6, 8), 16)
    taken = next(keys)
    stack = w((3, 6, 8), 4, jnp.float32, scale=0.25)
    assert flat.dtype == jnp.bfloat16 and stack.dtype == jnp.float32
    want = jax.random.normal(split[0], (6, 8), jnp.float32) * 16 ** -0.5
    assert np.array_equal(np.asarray(flat), np.asarray(want.astype(jnp.bfloat16)))
    assert np.array_equal(np.asarray(taken), np.asarray(split[1]))
    for i, k in enumerate(jax.random.split(split[2], 3)):
        want = jax.random.normal(k, (6, 8), jnp.float32) * (0.25 * 4 ** -0.5)
        assert np.array_equal(np.asarray(stack[i]), np.asarray(want))
    assert np.array_equal(np.asarray(next(keys)), np.asarray(split[3]))


@pytest.mark.parametrize("axis, shape", [(0, (37, 2, 4)), (1, (3, 32, 5)), (1, (2, 7, 2, 4))])
def test_rows_to_blocks_pads_the_axis_to_whole_blocks(axis, shape):
    x = jnp.arange(np.prod(shape), dtype=jnp.float32).reshape(shape) + 1.0
    got = np.asarray(parts.rows_to_blocks(x, 8, axis))
    nbw = -(-shape[axis] // 8)
    assert got.shape == shape[:axis] + (nbw, 8) + shape[axis + 1:]
    flat = got.reshape(shape[:axis] + (nbw * 8,) + shape[axis + 1:])
    rows = np.moveaxis(flat, axis, 0)
    assert np.array_equal(rows[:shape[axis]], np.moveaxis(np.asarray(x), axis, 0))
    assert not rows[shape[axis]:].any()


def test_write_slot_rows_overwrites_one_slot_whole_in_the_leafs_dtype():
    leaves = {"a": jnp.full((3, 2, 4), 7.0, jnp.float32), "b": jnp.full((3, 5), 7, jnp.int32)}
    rows = {"a": jnp.ones((2, 4), jnp.bfloat16), "b": jnp.arange(5, dtype=jnp.int32)}
    out = jax.jit(parts.write_slot_rows)(leaves, rows, jnp.int32(1))
    assert out["a"].dtype == jnp.float32 and out["b"].dtype == jnp.int32
    assert np.array_equal(np.asarray(out["a"][1]), np.ones((2, 4)))
    assert np.array_equal(np.asarray(out["b"][1]), np.arange(5))
    for name in leaves:
        assert (np.asarray(out[name])[[0, 2]] == 7).all()


def _row_wise(xs, scale=2.0):
    """A pytree in, a pytree out, every row from its own row."""
    a, b = xs
    return {"sum": a * scale + b[:, None], "parts": (jnp.sum(a, axis=-1), b * 3)}


@pytest.mark.parametrize("rows, tile, whiles", [(8, 8, 0), (8, 16, 0), (16, 8, 1), (32, 8, 1)])
def test_over_live_rows_computes_the_live_tiles_and_no_other(rows, tile, whiles):
    """A bucket of at most one tile is ``fn`` once, no loop in the lowered
    text; of several, ONE ``while`` whose trip count is data: every ``live``
    runs the same compiled program, the tiles past it come back 0 and the last
    live tile whole (its padding is ``fn``'s to see)."""
    rng = np.random.default_rng(rows + tile)
    xs = (jnp.asarray(rng.standard_normal((rows, 3)), jnp.float32),
          jnp.asarray(rng.integers(1, 9, rows), jnp.int32))
    run = jax.jit(lambda xs, live: parts.over_live_rows(_row_wise, xs, live, tile))
    assert run.lower(xs, jnp.int32(1)).as_text().count("stablehlo.while") == whiles
    whole = _row_wise(xs)
    for live in range(1, rows + 1):
        computed = rows if rows <= tile else -(-live // tile) * tile
        got = run(xs, jnp.int32(live))
        assert jax.tree.structure(got) == jax.tree.structure(whole)
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(whole)):
            assert g.dtype == w.dtype and g.shape == w.shape
            assert np.array_equal(np.asarray(g[:computed]), np.asarray(w[:computed]))
            assert not np.asarray(g[computed:]).any()
    assert run._cache_size() == 1


def test_over_live_rows_without_a_length_or_with_a_ragged_bucket():
    xs = (jnp.ones((24, 3)), jnp.arange(24, dtype=jnp.int32))
    once = jax.jit(lambda xs: parts.over_live_rows(_row_wise, xs, None, 8))  # logits(): all rows
    assert once.lower(xs).as_text().count("stablehlo.while") == 0
    assert np.array_equal(np.asarray(once(xs)["sum"]), np.asarray(_row_wise(xs)["sum"]))
    # what is held from tile to tile reaches ``fn`` behind the tiles, with or without a loop
    for tile in (8, 24):
        held = jax.jit(lambda xs, s: parts.over_live_rows(_row_wise, xs, jnp.int32(24), tile, (s,)))
        assert np.array_equal(np.asarray(held(xs, jnp.float32(5.0))["sum"]),
                              np.asarray(_row_wise(xs, 5.0)["sum"]))
    with pytest.raises(ValueError, match="24 rows are not whole tiles of 16"):
        parts.over_live_rows(_row_wise, xs, jnp.int32(3), 16)


if __name__ == "__main__":
    for name in sorted(DECODERS):
        print(f"    {name!r}: {_draws_and_tokens(name)!r},")
