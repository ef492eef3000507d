"""Generate the markdown API reference from live docstrings (no sphinx in
the toolchain; stdlib inspect is enough for a faithful reference).

Counterpart of the reference's Sphinx tree (``/root/reference/docs/``,
``docs/source/``): the reference writes its pybind docstrings for a docs
build, this walks the real import surface.  The pages are generated on
demand into ``docs/api/``, which git ignores: nothing tracked is the output
of this script, and ``tests/test_docs_api.py`` holds every page to rendering.

    python docs/gen_api.py            # (re)write docs/api/*.md
"""

from __future__ import annotations

import inspect
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
OUT = os.path.join(ROOT, "docs", "api")

# (module path, page title): the public surface, in reading order.
MODULES = [
    ("moolib_tpu", "Package exports"),
    ("moolib_tpu.rpc.core", "RPC core"),
    ("moolib_tpu.broker", "Broker"),
    ("moolib_tpu.group", "Group / AllReduce"),
    ("moolib_tpu.accumulator", "Accumulator"),
    ("moolib_tpu.buckets", "Flat-bucket gradient data plane"),
    ("moolib_tpu.envpool", "EnvPool"),
    ("moolib_tpu.batcher", "Batcher"),
    ("moolib_tpu.rollout", "Device-resident actor rollout"),
    ("moolib_tpu.replay", "Replay (package)"),
    ("moolib_tpu.replay.host", "Replay: host reference store"),
    ("moolib_tpu.replay.device", "Replay: device-resident shard"),
    ("moolib_tpu.replay.ingest", "Replay: memfd-multicast ingest"),
    ("moolib_tpu.replay.distributed", "Replay: two-level cohort sampling"),
    ("moolib_tpu.checkpoint", "Checkpointing"),
    ("moolib_tpu.watchdog", "Watchdog (run-loop deadman)"),
    ("moolib_tpu.autoscaler", "Autoscaler (elastic fleet supervision)"),
    ("moolib_tpu.serving", "Serving (replicated inference plane)"),
    ("moolib_tpu.engine", "Engine: continuous batching (package)"),
    ("moolib_tpu.engine.kv_pool", "Engine: paged KV block pool"),
    ("moolib_tpu.engine.engine", "Engine: slot scheduler + decode step"),
    ("moolib_tpu.engine.service", "Engine: serving-contract adapter"),
    ("moolib_tpu.ops.paged_attention", "Ops: paged decode attention"),
    ("moolib_tpu.testing.faults", "Testing: seeded fault injection"),
    ("moolib_tpu.testing.lockgraph", "Testing: lock-order race detection"),
    ("moolib_tpu.analysis", "Analysis: contract lint (mtlint)"),
    ("moolib_tpu.analysis.checks", "Analysis: check catalog"),
    ("moolib_tpu.parallel", "Parallelism (package)"),
    ("moolib_tpu.parallel.mesh", "Parallelism: mesh + shardings"),
    ("moolib_tpu.parallel.collectives", "Parallelism: collectives"),
    ("moolib_tpu.parallel.ring_attention", "Parallelism: ring attention"),
    ("moolib_tpu.parallel.pipeline", "Parallelism: pipeline (GPipe/circular)"),
    ("moolib_tpu.parallel.moe", "Parallelism: mixture-of-experts"),
    ("moolib_tpu.parallel.train", "Parallelism: train-step assembly"),
    ("moolib_tpu.models.impala", "Models: IMPALA ResNet"),
    ("moolib_tpu.models.qnet", "Models: recurrent Q-network (R2D2)"),
    ("moolib_tpu.models.transformer", "Models: Transformer LM"),
    ("moolib_tpu.models.decoder_parts", "Models: what the file-built decoders share"),
    ("moolib_tpu.models.latent_moe", "Models: latent-attention decoder with dropless experts"),
    ("moolib_tpu.models.retention_lm", "Models: power-retention decoder (a state a slot, no paged cache)"),
    ("moolib_tpu.models.jamba", "Models: Mamba-1 / multi-query decoder (a scan state a slot beside paged K/V)"),
    ("moolib_tpu.models.ssd_moe", "Models: Mamba-2 / NoPE grouped-query decoder with experts behind every layer"),
    ("moolib_tpu.ops.vtrace", "Ops: V-trace"),
    ("moolib_tpu.ops.flash_attention", "Ops: Flash attention (pallas)"),
    ("moolib_tpu.ops.retention", "Ops: power retention, degree 2 (pallas)"),
    ("moolib_tpu.ops.selective_scan", "Ops: selective state-space scan, Mamba-1 (pallas)"),
    ("moolib_tpu.ops.ssd", "Ops: state-space recurrence with a scalar decay a head, Mamba-2 (pallas)"),
    ("moolib_tpu.ops.returns", "Ops: returns / losses"),
    ("moolib_tpu.ops.xent", "Ops: chunked cross-entropy (LM head)"),
    ("moolib_tpu.telemetry", "Telemetry (package)"),
    ("moolib_tpu.telemetry.metrics", "Telemetry: metrics registry"),
    ("moolib_tpu.telemetry.tracing", "Telemetry: span tracer"),
    ("moolib_tpu.telemetry.exporters", "Telemetry: exporters"),
    ("moolib_tpu.telemetry.cohort", "Telemetry: cohort aggregation"),
    ("moolib_tpu.telemetry.aggregator", "Telemetry: RPC cohort aggregator"),
    ("moolib_tpu.telemetry.devmon", "Telemetry: device performance plane"),
    ("moolib_tpu.telemetry.flightrec", "Telemetry: flight recorder"),
    ("moolib_tpu.telemetry.hostmon", "Telemetry: host monitor (did the process stand still)"),
    ("moolib_tpu.telemetry.profiling", "Telemetry: on-demand device profiling"),
    ("moolib_tpu.telemetry.recovery", "Telemetry: recovery-phase accounting"),
    ("moolib_tpu.utils", "Utilities"),
    ("moolib_tpu.utils.nest", "Utilities: nest"),
    ("moolib_tpu.utils.config", "Utilities: config"),
    ("moolib_tpu.utils.batchsize", "Utilities: batch-size finder"),
    ("moolib_tpu.utils.profiling", "Utilities: profiling"),
    ("moolib_tpu.utils.stats", "Utilities: running stats"),
    ("moolib_tpu.utils.compile_cache", "Utilities: persistent compile cache"),
    ("moolib_tpu.envs.atari", "Envs: Atari preprocessing"),
    ("moolib_tpu.envs.jax_envs", "Envs: pure-JAX on-device family (Anakin)"),
]

# Operator-facing entry points that live outside the package (scripts/ is
# not importable).  Loaded by file path; pages land as mt_scripts_<name>.md.
SCRIPTS = [
    ("scripts/mtop.py", "Scripts: live cohort console (mtop)"),
    ("scripts/trace_merge.py", "Scripts: cohort trace merge"),
]


def _scrub(text: str) -> str:
    import re

    # Reprs can embed memory addresses (e.g. flax's module _Sentinel default
    # in dataclass-generated signatures AND docstrings); scrub them so that
    # two renders of one tree are the same text.  The flax-internal
    # parent/name dataclass parameters are collapsed entirely: their repr
    # changes with the installed flax version.
    text = re.sub(r" at 0x[0-9a-fA-F]+", " at 0x...", text)
    return re.sub(
        r"parent: Union\[flax[^=]*= <flax[^>]*>,\s*name: Optional\[str\] = None",
        "**flax_module_kwargs",
        text,
    )


def _sig(obj) -> str:
    try:
        return _scrub(str(inspect.signature(obj)))
    except (ValueError, TypeError):
        return "(...)"


def _doc(obj) -> str:
    d = inspect.getdoc(obj)
    return _scrub(d.strip()) if d else ""


def _public_names(mod):
    names = getattr(mod, "__all__", None)
    if names is None:
        names = [n for n in vars(mod) if not n.startswith("_")]
    out = []
    for n in names:
        obj = getattr(mod, n, None)
        if inspect.ismodule(obj):
            continue
        # Only document what this module defines (re-exports are documented
        # at their home, except in the package root where the export list
        # IS the documented surface).
        home = getattr(obj, "__module__", mod.__name__)
        if mod.__name__ != "moolib_tpu" and home != mod.__name__:
            continue
        if inspect.isclass(obj) or callable(obj):
            out.append((n, obj))
    return out


def _render_callable(name, obj, level="###") -> list:
    lines = [f"{level} `{name}{_sig(obj)}`", ""]
    doc = _doc(obj)
    if doc:
        lines += [doc, ""]
    return lines


def _render_class(name, cls) -> list:
    lines = [f"### class `{name}`", ""]
    doc = _doc(cls)
    if doc:
        lines += [doc, ""]
    for mname, m in sorted(vars(cls).items()):
        if mname.startswith("_") and mname != "__call__":
            continue
        if isinstance(m, property):
            lines += [f"#### `{name}.{mname}` (property)", ""]
            pdoc = _doc(m.fget) if m.fget else ""
            if pdoc:
                lines += [pdoc, ""]
            continue
        if isinstance(m, (classmethod, staticmethod)):
            m = m.__func__
        if not callable(m):
            continue
        mdoc = _doc(m)
        lines += [f"#### `{name}.{mname}{_sig(m)}`", ""]
        if mdoc:
            lines += [mdoc, ""]
    return lines


def render_module(modpath: str, title: str) -> str:
    __import__(modpath)
    mod = sys.modules[modpath]
    lines = [f"# {title}", "", f"``{modpath}``", ""]
    mdoc = _doc(mod)
    if mdoc:
        lines += [mdoc, ""]
    for name, obj in _public_names(mod):
        if inspect.isclass(obj):
            lines += _render_class(name, obj)
        else:
            lines += _render_callable(name, obj)
    return "\n".join(lines).rstrip() + "\n"


def render_script(relpath: str, title: str) -> str:
    """A scripts/ entry point: same rendering as a module, loaded by file
    path (scripts/ is intentionally not a package).  Public surface =
    module docstring + non-underscore top-level callables."""
    import importlib.util

    name = "mt_" + relpath.replace("/", "_").removesuffix(".py")
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, relpath)
    )
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    lines = [f"# {title}", "", f"``{relpath}``", ""]
    mdoc = _doc(mod)
    if mdoc:
        lines += [mdoc, ""]
    for oname in vars(mod):
        if oname.startswith("_"):
            continue
        obj = getattr(mod, oname)
        if inspect.ismodule(obj) or getattr(obj, "__module__", name) != name:
            continue
        if inspect.isclass(obj):
            lines += _render_class(oname, obj)
        elif callable(obj):
            lines += _render_callable(oname, obj)
    return "\n".join(lines).rstrip() + "\n"


def render_all() -> dict:
    pages = {}
    entries = []  # (display path, title, fname) in index order
    for modpath, title in MODULES:
        fname = modpath.replace("moolib_tpu", "mt").replace(".", "_") + ".md"
        try:
            pages[fname] = render_module(modpath, title)
        except Exception as e:  # noqa: BLE001 — a missing optional dep must
            # not take down the whole reference build
            pages[fname] = f"# {title}\n\n``{modpath}``\n\nimport failed: {e}\n"
        entries.append((modpath, title, fname))
    for relpath, title in SCRIPTS:
        fname = "mt_" + relpath.replace("/", "_").removesuffix(".py") + ".md"
        try:
            pages[fname] = render_script(relpath, title)
        except Exception as e:  # noqa: BLE001
            pages[fname] = f"# {title}\n\n``{relpath}``\n\nimport failed: {e}\n"
        entries.append((relpath, title, fname))
    index = ["# API reference", "",
             "Generated from live docstrings by `docs/gen_api.py`.", ""]
    for modpath, title, fname in entries:
        index.append(f"- [{title}]({fname}) — ``{modpath}``")
    pages["README.md"] = "\n".join(index) + "\n"
    return pages


def main() -> int:
    import jax

    # Docs generation must never claim an accelerator.
    jax.config.update("jax_platforms", "cpu")

    pages = render_all()
    os.makedirs(OUT, exist_ok=True)
    for fname, content in pages.items():
        with open(os.path.join(OUT, fname), "w") as f:
            f.write(content)
    print(f"{len(pages)} pages written -> {OUT}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
