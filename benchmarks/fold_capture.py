"""Fold benchmark logs into a JSON record.

``--local <log>`` is the mode in use: ``scripts/ci.sh`` folds each CPU
plumbing bench's log into ``BENCH_LOCAL.json``, which ``scripts/bench_gate.py``
then gates.  The directory mode (a capture directory of per-bench logs into
one record file) belonged to a capture harness that is gone; it stays, with
its tests, until ROADMAP D5 removes both.  Only sections whose log actually
produced a result are replaced; everything else in the record is preserved.

    python benchmarks/fold_capture.py --local <log> [bench_local_json]
    python benchmarks/fold_capture.py <capture_dir> [record_json]
"""

from __future__ import annotations

import datetime
import json
import os
import re
import sys


def parse_impala(path):
    """bench.py child mode prints 'MOOLIB_BENCH_RESULT {json}'."""
    try:
        with open(path) as f:
            for line in reversed(f.read().splitlines()):
                if line.startswith("MOOLIB_BENCH_RESULT "):
                    row = json.loads(line[len("MOOLIB_BENCH_RESULT "):])
                    return row if row.get("platform") != "cpu" else None
    except (OSError, json.JSONDecodeError):
        return None  # truncated/garbled line (killed mid-write): skip section
    return None


def parse_lm(path):
    """lm_bench prints one {'lm_train': {...}} JSON line at the end.  CPU
    plumbing runs (MOOLIB_ALLOW_CPU=1) are refused — same gate as every
    other parser here (older captures without a platform field predate the
    CPU escape hatch and are genuine chip rows)."""
    try:
        with open(path) as f:
            for line in reversed(f.read().splitlines()):
                if line.startswith("{") and "lm_train" in line:
                    row = json.loads(line)["lm_train"]
                    return row if row.get("platform", "tpu") != "cpu" else None
    except (OSError, json.JSONDecodeError, KeyError):
        return None
    return None


def parse_flash(path):
    """flash_bench prints fixed-width tables; keep ONLY table content (the
    log also carries warnings/tracebacks via 2>&1)."""
    try:
        with open(path) as f:
            txt = f.read()
    except OSError:
        return None
    keep = re.compile(r"^(#|\s*T\s|\s*\d+\s)")  # headers + data rows
    lines = [l for l in txt.splitlines() if l.strip() and keep.match(l)]
    return lines if any(re.match(r"\s*\d+\s", l) for l in lines) else None


def _split_flash_tables(lines):
    """Group flash table lines into sections keyed by their header row.

    A section starts at a ``T dense_ms ...`` header; comment lines
    *leading into* a header (the backend banner, the ``# fwd+bwd`` title —
    which flash_bench prints after the previous table's last data row) are
    the next section's preamble, data rows key by T, and other comment
    lines after a data row (the per-T estimate notes) ride with that row.
    Classified by lookahead: a comment belongs to the next header if only
    comments stand between it and that header."""

    def leads_to_header(i):
        while i < len(lines) and lines[i].startswith("#"):
            i += 1
        return i < len(lines) and re.match(r"\s*T\s", lines[i])

    sections = []
    pre = []
    cur = None
    last_t = None
    for i, l in enumerate(lines):
        if re.match(r"\s*T\s", l):
            cur = {"pre": pre, "header": l, "rows": {}}
            pre = []
            last_t = None
            sections.append(cur)
            continue
        m = re.match(r"\s*(\d+)\s", l)
        if m and cur is not None:
            last_t = int(m.group(1))
            cur["rows"][last_t] = [l]
        elif leads_to_header(i) or cur is None or last_t is None:
            pre.append(l)
        else:
            cur["rows"][last_t].append(l)
    return sections


def _merge_flash_tables(old_lines, new_lines):
    """Row-preservation merge, the same shape as the lm_train rows merge:
    seed from the committed ``bench_tables``, overlay fresh rows keyed by
    (section header, T).  A capture that wedged early (e.g. before the
    fwd+bwd T=8192 row) keeps the committed measurement — the README's
    headline numbers never silently lose provenance to a partial table."""
    old = _split_flash_tables(old_lines or [])
    new = _split_flash_tables(new_lines or [])
    new_by_header = {s["header"].strip(): s for s in new}
    merged = []
    seen = set()
    for osec in old:
        key = osec["header"].strip()
        nsec = new_by_header.get(key)
        if nsec is None:
            merged.append(osec)  # section absent from the fresh capture
            continue
        seen.add(key)
        rows = dict(osec["rows"])
        rows.update(nsec["rows"])  # fresh rows win per T
        # Drop any stale carried-rows note inherited from a prior fold; the
        # current merge re-derives it from what actually carried this time.
        pre = [
            l for l in (nsec["pre"] or osec["pre"])
            if not l.startswith("# rows T in")
        ]
        carried = sorted(set(osec["rows"]) - set(nsec["rows"]))
        if carried:
            # The fresh banner (backend/device) and the section timestamp
            # describe the new capture; rows it didn't re-measure keep
            # older provenance — say so rather than silently mixing.
            pre.append(
                "# rows T in %s carried from an earlier capture (not re-measured)"
                % carried
            )
        merged.append({"pre": pre, "header": nsec["header"], "rows": rows})
    for nsec in new:
        if nsec["header"].strip() not in seen:
            merged.append(nsec)  # brand-new section (e.g. a new table)
    out = []
    for sec in merged:
        out.extend(sec["pre"])
        out.append(sec["header"])
        for t in sorted(sec["rows"]):
            out.extend(sec["rows"][t])
    return out


def _parse_json_line(path, marker, cpu_gate=True):
    """Last JSON line in ``path`` containing ``marker``; chip-gated unless
    ``cpu_gate=False`` (host-side rows are valid wherever the battery ran)."""
    try:
        with open(path) as f:
            for line in reversed(f.read().splitlines()):
                if line.startswith("{") and marker in line:
                    row = json.loads(line)
                    if cpu_gate and row.get("platform") == "cpu":
                        return None
                    return row
    except (OSError, json.JSONDecodeError):
        return None
    return None


def parse_agent(path):
    """agent_bench prints one {'metric': 'impala_agent_sps', ...} JSON line
    per rollout mode (legacy/device, plus 'jax' since the Anakin plane).
    The TPU record keeps the fastest plane that ran as the headline — jax
    (zero-crossing) over device over whatever a pre-A/B log printed last."""
    for mode in ("jax", "device"):
        row = _parse_json_lines_by(path, mode)
        if row is not None:
            return row
    return _parse_json_line(path, "impala_agent_sps")


def _parse_json_lines_by(path, rollout):
    """The impala_agent_sps row for a specific rollout mode (chip-gated)."""
    try:
        with open(path) as f:
            for line in reversed(f.read().splitlines()):
                if line.startswith("{") and "impala_agent_sps" in line:
                    row = json.loads(line)
                    if row.get("platform") == "cpu":
                        return None
                    if row.get("rollout") == rollout:
                        return row
    except (OSError, json.JSONDecodeError):
        return None
    return None


def parse_r2d2(path):
    """r2d2_bench prints one {'metric': 'r2d2_learner_sps', ...} JSON line."""
    return _parse_json_line(path, "r2d2_learner_sps")


def parse_envpool(path):
    """envpool_bench prints one {'env': ..., 'env_steps_per_s': ...} line.
    EnvPool runs host-side, so there is no platform gate — the row is valid
    wherever the battery ran (it matters next to the chip's learner rows)."""
    return _parse_json_line(path, "env_steps_per_s", cpu_gate=False)


def parse_serve(path):
    """serve_bench prints one JSON row per config (p50/p99/tokens_per_s).
    CPU rows are refused — 100x-worse latencies must not fold into a chip
    record (same gate as parse_impala)."""
    rows = []
    try:
        with open(path) as f:
            for line in f.read().splitlines():
                if line.startswith("{") and "p99_ms" in line:
                    try:
                        row = json.loads(line)
                    except json.JSONDecodeError:
                        continue
                    if row.get("platform") not in ("cpu", "unknown"):
                        rows.append(row)
    except OSError:
        return None
    return rows or None


def parse_roofline(path):
    try:
        with open(path) as f:
            for line in reversed(f.read().splitlines()):
                if line.startswith("{") and "arithmetic_intensity" in line:
                    row = json.loads(line)
                    # impala_roofline runs on whatever backend exists — a
                    # CPU-fallback row must not pollute the TPU record.
                    return row if row.get("platform") != "cpu" else None
    except (OSError, json.JSONDecodeError):
        return None
    return None


def parse_allreduce(path):
    """allreduce_bench rpc stdout: '#' banner lines + fixed-width data rows
    (and the --smoke mode's 'smoke:' lines).  Anything else — warnings,
    tracebacks riding 2>&1 — is dropped."""
    try:
        with open(path) as f:
            txt = f.read()
    except OSError:
        return None
    keep = re.compile(r"^(#|smoke:|\s*elems\s|\s*\d+\s)")
    lines = [l for l in txt.splitlines() if l.strip() and keep.match(l)]
    # Data rows OR smoke verdict lines qualify: the --sharded --smoke gate
    # prints only ``smoke:`` lines, and its byte-ratio verdict is a capture
    # worth folding (it merges as the banner-keyed ``smoke`` section).
    has_rows = any(re.match(r"\s*\d+\s", l) for l in lines)
    return lines if has_rows or any(l.startswith("smoke:") for l in lines) else None


def _split_allreduce_sections(lines):
    """Group allreduce stdout into banner-keyed sections: a section is a
    ``#`` banner line plus the header/data rows that follow it; lines
    before any banner (the ``--smoke`` modes print no banner) form a
    leading ``smoke`` section."""
    secs = []
    for l in lines or []:
        if l.startswith("#"):
            secs.append((l.strip(), [l]))
        elif l.startswith("smoke:"):
            # Consecutive smoke verdict lines are ONE section regardless of
            # what banner precedes them — a fresh smoke capture must replace
            # the stored verdict, not duplicate it inside a banner section.
            if secs and secs[-1][0] == "smoke":
                secs[-1][1].append(l)
            else:
                secs.append(("smoke", [l]))
        elif not secs:
            secs.append(("smoke", [l]))
        else:
            secs[-1][1].append(l)
    return secs


def merge_allreduce_sections(old_lines, new_lines):
    """allreduce sections MERGE banner-keyed instead of clobbering: a
    ``--sharded`` A/B capture must not erase the committed tree/ring sweep
    rows, and a fresh sweep must not erase the sharded A/B record (the
    sharded arm keys its ratio claim as data rows under a stable banner
    for exactly this reason).  A fresh section replaces the stored section
    with the same banner; every other stored section is kept in its
    original order, fresh sections appended after."""
    new = _split_allreduce_sections(new_lines)
    fresh = {k for k, _ in new}
    out = []
    for key, ls in _split_allreduce_sections(old_lines):
        if key not in fresh:
            out.extend(ls)
    for _, ls in new:
        out.extend(ls)
    return out


def parse_agent_lines(path):
    """agent_bench stdout: one ``impala_agent_sps`` JSON row per rollout
    mode plus the ``impala_agent_rollout_ab`` summary.  Anything else
    (progress prints, tracebacks riding 2>&1) is dropped; garbled JSON
    lines (killed mid-write) are skipped."""
    keep = []
    try:
        with open(path) as f:
            for line in f.read().splitlines():
                if not line.startswith("{"):
                    continue
                try:
                    row = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if row.get("metric") in ("impala_agent_sps",
                                         "impala_agent_rollout_ab",
                                         "impala_agent_jax_vs_device"):
                    keep.append(json.dumps(row))
    except OSError:
        return None
    return keep or None


def _agent_row_key(line):
    """Merge key for an agent_small section row: (metric, rollout, scale).
    Summary rows (rollout_ab / jax_vs_device) carry no rollout field and
    key as one comparison row per scale that each fresh A/B run replaces."""
    try:
        row = json.loads(line)
    except json.JSONDecodeError:
        return line
    return (row.get("metric"), row.get("rollout"), row.get("scale"))


def merge_agent_rows(old_lines, new_lines):
    """agent_small rows MERGE instead of clobber: a single-rollout re-run
    (``--rollout device``) must not erase the committed legacy/jax rows.
    A fresh row replaces the stored row with the same key; derived columns
    a short smoke re-run didn't produce (``mfu`` is null until the learn
    section has run long enough) carry forward from the stored row so a
    quick capture can't blank the devmon MFU record."""
    old_by_key = {}
    for l in old_lines or []:
        old_by_key[_agent_row_key(l)] = l
    fresh = set()
    merged_new = []
    for l in new_lines:
        k = _agent_row_key(l)
        fresh.add(k)
        prev = old_by_key.get(k)
        if prev is not None:
            try:
                row, prow = json.loads(l), json.loads(prev)
            except json.JSONDecodeError:
                merged_new.append(l)
                continue
            if isinstance(row, dict) and isinstance(prow, dict):
                if row.get("mfu") is None and prow.get("mfu") is not None:
                    row["mfu"] = prow["mfu"]
                    row["mfu_carried"] = True  # not re-measured this capture
                l = json.dumps(row)
        merged_new.append(l)
    kept = [l for l in (old_lines or []) if _agent_row_key(l) not in fresh]
    return kept + merged_new


def parse_r2d2_local(path):
    """r2d2_bench stdout: one ``{"metric": "r2d2_learner_sps", "arm": ...}``
    row per replay arm (host / host_rpc / device) plus the
    ``r2d2_replay_ab`` summary (speedups + priority bit-exactness + the
    write-once ingest accounting).  No platform gate — the replay-plane
    A/B is a valid local record wherever it ran; the platform column says
    which chip served it."""
    keep = []
    try:
        with open(path) as f:
            for line in f.read().splitlines():
                if not line.startswith("{"):
                    continue
                try:
                    row = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if row.get("metric") in ("r2d2_learner_sps", "r2d2_replay_ab"):
                    keep.append(json.dumps(row))
    except OSError:
        return None
    return keep or None


def _r2d2_row_key(line):
    """Merge key for an r2d2_learner section row: (metric, arm).  The
    ``r2d2_replay_ab`` summary carries no arm and keys as the single
    comparison row each fresh A/B run replaces."""
    try:
        row = json.loads(line)
    except json.JSONDecodeError:
        return line
    return (row.get("metric"), row.get("arm"))


def merge_r2d2_rows(old_lines, new_lines):
    """r2d2_learner rows merge per arm: a single-arm re-run (``--arms
    device``) must not erase the stored host/host_rpc rows the speedup
    claim is measured against."""
    fresh = {_r2d2_row_key(l) for l in new_lines}
    kept = [l for l in (old_lines or []) if _r2d2_row_key(l) not in fresh]
    return kept + list(new_lines)


def parse_serve_qps(path):
    """serve_bench --qps stdout: the baseline closed-loop row plus one
    ``{"metric": "serve_qps", ...}`` line per target (no platform gate —
    the sustained-QPS record is a local/host capture by design; the chip
    path stays the closed-loop ``lm_serve`` section above)."""
    keep = []
    try:
        with open(path) as f:
            for line in f.read().splitlines():
                if not line.startswith("{"):
                    continue
                try:
                    row = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if (row.get("metric") in ("serve_qps", "serve_phase_breakdown",
                                          "serve_engine_ab")
                        or "p99_ms" in row):
                    keep.append(json.dumps(row))
    except OSError:
        return None
    # Without at least one serve_qps row this is a closed-loop serve log,
    # not a --qps capture — let the other detectors claim it.
    return keep if any('"serve_qps"' in l for l in keep) else None


def _qps_row_key(line):
    """Merge key for a serve_qps section row: (metric, engine-arm,
    qps_target).  ``serve_engine_ab`` rows carry an arm *aggregate* under
    "engine" (a dict, not the bool flag) — they key as a single comparison
    row that each fresh A/B capture replaces."""
    try:
        row = json.loads(line)
    except json.JSONDecodeError:
        return line
    eng = row.get("engine")
    eng = bool(eng) if isinstance(eng, (bool, int)) or eng is None else "ab"
    tgt = row.get("qps_target")
    if tgt is None:
        tgts = row.get("qps_targets")
        tgt = tuple(tgts) if isinstance(tgts, list) else None
    return (row.get("metric"), eng, tgt)


def merge_qps_rows(old_lines, new_lines):
    """serve_qps rows MERGE instead of clobber: an engine A/B capture must
    not erase the plain sustained-QPS record, and vice versa.  A fresh row
    replaces the stored row with the same key; everything else is kept in
    its original order, fresh rows appended after."""
    fresh = {_qps_row_key(l) for l in new_lines}
    kept = [l for l in (old_lines or []) if _qps_row_key(l) not in fresh]
    return kept + list(new_lines)


def fold_local(log_path, json_path):
    """Merge a fresh local capture into BENCH_LOCAL.json: only the section
    the log belongs to — ``allreduce_rpc`` for an allreduce_bench capture,
    ``agent_small`` for an agent_bench one, ``r2d2_learner`` for an
    r2d2_bench replay A/B, ``serve_qps`` for a ``serve_bench --qps`` one
    (detected by content) — has its stdout
    updated; every other section (rpc, envpool, ...) is preserved verbatim.
    The allreduce_rpc, serve_qps, and agent_small sections merge rows
    (banner-keyed / row-keyed) instead of clobbering — same
    row-preservation policy as the BENCH_TPU merges above."""
    if os.path.exists(json_path):
        # A corrupt record must ABORT, not be clobbered (curated history).
        with open(json_path) as f:
            data = json.load(f)
    else:
        data = {}
    agent_lines = parse_agent_lines(log_path)
    r2d2_lines = None if agent_lines else parse_r2d2_local(log_path)
    qps_lines = (
        None if (agent_lines or r2d2_lines) else parse_serve_qps(log_path)
    )
    if agent_lines:
        section, cmd, lines = (
            "agent_small",
            "benchmarks/agent_bench.py --scale small --rollout all",
            agent_lines,
        )
    elif r2d2_lines:
        section, cmd, lines = (
            "r2d2_learner",
            "benchmarks/r2d2_bench.py --check",
            r2d2_lines,
        )
    elif qps_lines:
        # dict.fromkeys: an A/B capture has one row per target per arm.
        targets = list(dict.fromkeys(
            str(json.loads(l)["qps_target"]) for l in qps_lines
            if '"serve_qps"' in l))
        section, cmd, lines = (
            "serve_qps",
            "benchmarks/serve_bench.py --qps " + " ".join(targets),
            qps_lines,
        )
    else:
        lines = parse_allreduce(log_path)
        if not lines:
            raise SystemExit(
                f"no allreduce, agent, or serve_qps rows found in {log_path}"
            )
        section, cmd = "allreduce_rpc", "benchmarks/allreduce_bench.py rpc"
    sec = dict(data.get(section, {}))
    # The cmd reflects THIS capture (the arm set can grow across rounds);
    # stale run metadata from the replaced capture is dropped with it.
    sec["cmd"] = cmd
    sec.pop("seconds", None)
    sec["rc"] = 0
    if section == "serve_qps":
        lines = merge_qps_rows(sec.get("stdout"), lines)
    elif section == "r2d2_learner":
        lines = merge_r2d2_rows(sec.get("stdout"), lines)
    elif section == "agent_small":
        lines = merge_agent_rows(sec.get("stdout"), lines)
    elif section == "allreduce_rpc":
        lines = merge_allreduce_sections(sec.get("stdout"), lines)
    sec["stdout"] = lines
    sec["stderr"] = []
    try:
        sec["captured_when"] = datetime.date.fromtimestamp(
            os.path.getmtime(log_path)
        ).isoformat()
    except OSError:
        sec["captured_when"] = datetime.date.today().isoformat()
    data[section] = sec
    tmp = f"{json_path}.{os.getpid()}.tmp"
    with open(tmp, "w") as f:
        json.dump(data, f, indent=1)
        f.write("\n")
    os.replace(tmp, json_path)
    print(f"folded {section} rows -> {json_path} ({section}; other sections preserved)")


def main():
    if len(sys.argv) >= 2 and sys.argv[1] == "--local":
        # fold_capture.py --local <allreduce_log> [bench_local_json]
        if len(sys.argv) < 3:
            raise SystemExit(
                "usage: fold_capture.py --local <allreduce_log> [bench_local_json]"
            )
        log = sys.argv[2]
        out = (
            sys.argv[3]
            if len(sys.argv) > 3
            else os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                              "BENCH_LOCAL.json")
        )
        fold_local(log, out)
        return
    if len(sys.argv) < 2:
        # Required: defaulting to a round-suffixed dir would silently re-fold
        # stale artifacts after the round advances (the battery always passes
        # its own OUT).
        raise SystemExit("usage: fold_capture.py <capture_dir> [bench_tpu_json]")
    cap = sys.argv[1]
    out_path = (
        sys.argv[2]
        if len(sys.argv) > 2
        else os.path.join(os.path.dirname(cap.rstrip("/")), "BENCH_TPU.json")
    )
    if os.path.exists(out_path):
        # A corrupt record must ABORT, not be clobbered with {} — it holds
        # curated history.
        with open(out_path) as f:
            data = json.load(f)
    else:
        data = {}

    def stamp(name):
        """Capture time = the log's mtime date.  The watcher re-folds the
        whole dir on every pass, so stamping fold time would falsify the
        record's age."""
        try:
            return datetime.date.fromtimestamp(
                os.path.getmtime(os.path.join(cap, name))
            ).isoformat()
        except OSError:
            return datetime.date.today().isoformat()

    updated = []
    impala = parse_impala(os.path.join(cap, "impala_bench.log"))
    if impala and impala.get("metric") != "impala_learner_sps":
        impala = None  # smoke/wide-labeled rows never fold into the headline
    if impala:
        # Merge over the existing section: curated fields (baseline prose,
        # repro notes, config) survive unless the fresh run overwrote them.
        merged = dict(data.get("impala_learner", {}))
        merged.update(impala)
        merged["captured_when"] = stamp("impala_bench.log")
        data["impala_learner"] = merged
        # Only the headline capture refreshes the record's top-level date.
        data["when"] = merged["captured_when"]
        updated.append("impala_learner")
    # The short-window battery splits the LM sweep into lm_quick/lm_full
    # logs; merge their rows (keyed by config) with the single-log name.
    lm_parts = {n: parse_lm(os.path.join(cap, n))
                for n in ("lm_bench.log", "lm_quick.log", "lm_full.log",
                          "lm_bf16.log", "lm_dots.log")}
    lm_logs = [n for n, part in lm_parts.items() if part]
    if lm_logs:
        rows, meta = {}, None
        # Seed from the already-folded section: a re-armed step's re-run
        # shelves its old log (run() moves it to .log.prev, which fold never
        # reads), so rows that only exist in BENCH_TPU.json — e.g. the naive
        # baseline at the configs lm_quick re-measures fused — must survive
        # the rebuild or the fused-vs-naive comparison loses its baseline.
        def key(r):
            # xent mode and chunk size joined the key in round 5: fused,
            # fused_bf16, naive, and different-chunk rows are distinct
            # measurements and must not overwrite each other; likewise the
            # remat policy (what the per-block checkpoint saves).
            return (r["T"], r["B"], r["remat"], r["xent"],
                    r.get("xent_chunk"), r.get("remat_policy", "full"))

        for r in data.get("lm_train", {}).get("rows", []):
            r = dict(r)
            r.setdefault("xent", "naive")
            rows[key(r)] = r
        for n in lm_logs:
            part = lm_parts[n]
            meta = {k: v for k, v in part.items() if k != "rows"}
            for r in part.get("rows", []):
                # older logs' rows are all the naive path
                r = dict(r)
                r.setdefault("xent", "naive")
                rows[key(r)] = r
        data["lm_train"] = dict(
            meta, rows=sorted(rows.values(), key=lambda r: (r.get("T", 0), r.get("remat", False), r.get("B", 0), r.get("xent", ""))),
            # Freshest log stamps the section: the battery's step order and
            # this tuple's order differ (lm_bf16 runs before lm_full).
            captured_when=max(stamp(n) for n in lm_logs),
        )
        updated.append("lm_train")
    flash = parse_flash(os.path.join(cap, "flash_bench.log"))
    if flash:
        fa = data.setdefault("flash_attention", {})
        # Row-preservation merge (same idea as lm_train's): committed rows
        # a wedged capture didn't re-measure survive, fresh rows win per T.
        fa["bench_tables"] = _merge_flash_tables(fa.get("bench_tables"), flash)
        fa["bench_tables_captured_when"] = stamp("flash_bench.log")
        updated.append("flash_attention.bench_tables")
    # The XL-geometry LM rows fold into their OWN section: lm_train's rows
    # all share one (d_model, layers) meta and the merge key is only
    # (T, B, ...), so mixing geometries there would mislabel rows.
    xl = parse_lm(os.path.join(cap, "lm_xl.log"))
    if xl:
        data["lm_train_xl"] = dict(xl, captured_when=stamp("lm_xl.log"))
        updated.append("lm_train_xl")
    tune = _parse_json_line(
        os.path.join(cap, "flash_bwd_tune.log"), "flash_bwd_tune",
        cpu_gate=False,  # platform field is nested; gated below
    )
    tune = (tune or {}).get("flash_bwd_tune")
    if tune and tune.get("platform") != "cpu":
        data["flash_bwd_tune"] = dict(
            tune, captured_when=stamp("flash_bwd_tune.log")
        )
        updated.append("flash_bwd_tune")
    # roofline_chip.log is the short-window battery's name for the same
    # run; the fresher of the two wins and the section folds once.
    for roof_log in ("roofline_chip.log", "impala_roofline.log"):
        roof = parse_roofline(os.path.join(cap, roof_log))
        if roof:
            data["impala_roofline"] = dict(roof, captured_when=stamp(roof_log))
            updated.append("impala_roofline")
            break
    wide = parse_impala(os.path.join(cap, "impala_wide.log"))
    if wide and wide.get("metric") != "impala_learner_sps_wide":
        wide = None  # a narrow/smoke row must not pose as the falsification datapoint
    if wide:
        data["impala_wide"] = dict(wide, captured_when=stamp("impala_wide.log"))
        updated.append("impala_wide")
    agent = parse_agent(os.path.join(cap, "agent_bench.log"))
    if agent:
        data["impala_agent"] = dict(agent, captured_when=stamp("agent_bench.log"))
        updated.append("impala_agent")
    r2d2 = parse_r2d2(os.path.join(cap, "r2d2_bench.log"))
    if r2d2:
        data["r2d2_learner"] = dict(r2d2, captured_when=stamp("r2d2_bench.log"))
        updated.append("r2d2_learner")
    pool = parse_envpool(os.path.join(cap, "envpool_atari.log"))
    if pool:
        data["envpool_atari"] = dict(pool, captured_when=stamp("envpool_atari.log"))
        updated.append("envpool_atari")
    serve = parse_serve(os.path.join(cap, "serve_bench.log"))
    if serve:
        data["lm_serve"] = {"rows": serve, "captured_when": stamp("serve_bench.log")}
        updated.append("lm_serve")

    if not updated:
        print("fold_capture: nothing to fold (no TPU results in capture dir)")
        return
    data["provenance"] = (
        "auto-folded from the capture directory "
        f"({cap}); sections updated: {', '.join(updated)}"
    )
    tmp = out_path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(data, f, indent=2)
        f.write("\n")
    os.replace(tmp, out_path)  # atomic: a killed fold can't truncate the record
    print(f"fold_capture: updated {out_path}: {', '.join(updated)}")


if __name__ == "__main__":
    main()
