"""Bench regression gate: fresh capture rows vs the committed BENCH_LOCAL.json.

The BENCH_* trajectory had no automated check — a perf-eating bug (the PR-4
buffer-pool leak shape) would only be caught by a human re-reading JSON.
This gate compares a fresh capture section-by-section against the committed
record under per-metric tolerance rules:

- **throughput** fields (steady_sps, tokens_per_s, achieved_qps, MB/s) must
  hold a ratio *floor*: fresh/committed >= ``--throughput-floor``;
- **latency** fields (p99_ms) must hold a ratio *ceiling*:
  fresh/committed <= ``--latency-ceiling``;
- a section present in the capture but absent from the committed record
  fails unless explicitly allow-listed (``--allow-new-section NAME``) — new
  benchmarks enter the record deliberately, not by gate accident.

Rows are keyed the same way ``fold_capture`` merges them (agent rows by
(metric, rollout, scale), r2d2 replay rows by (metric, arm), serve_qps
rows by (metric, engine-arm, target), allreduce rows by (banner, elems)),
so the gate sees exactly the rows a fold would replace.  Rows only in the capture are informational; rows only
in the committed record are skipped (a smoke run measures a subset).

Usage (ci.sh runs the --smoke forms before each fold_capture --local)::

    python scripts/bench_gate.py --smoke --log /tmp/agent_smoke.log
    python scripts/bench_gate.py --smoke                # self-check: the
        # committed record must pass its own gate (ratio 1.0 everywhere)
    python scripts/bench_gate.py --capture fresh.json   # BENCH_LOCAL-shaped

Exit codes: 0 pass, 1 regression (table names every failing row), 2
malformed capture/baseline or usage error.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
from typing import Any, Dict, List, Optional, Tuple

sys.path.insert(
    0,
    os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                 "benchmarks"),
)

import fold_capture  # noqa: E402 — the same parsers the fold uses

THROUGHPUT_FLOOR = 0.85  # a 20% throughput degrade (ratio 0.8) must fail
LATENCY_CEILING = 1.30

# Per-section row rules: how stdout lines become keyed rows, and which
# fields gate as throughput (floor) vs latency (ceiling).
_AGENT_METRICS = ("impala_agent_sps",)
_SERVE_THROUGHPUT = ("tokens_per_s", "achieved_qps")
_SERVE_LATENCY = ("p99_ms",)


class GateError(Exception):
    """Malformed input — exit 2, distinct from a measured regression."""


def _json_rows(lines: List[str]) -> List[dict]:
    rows = []
    for line in lines or ():
        if not isinstance(line, str) or not line.startswith("{"):
            continue
        try:
            row = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(row, dict):
            rows.append(row)
    return rows


def parse_agent_rows(lines: List[str]) -> Dict[Tuple, Dict[str, float]]:
    """agent_small rows keyed (metric, rollout, scale); gated field:
    steady_sps (throughput).  The A/B summary rows are provenance, not
    gated measurements."""
    out: Dict[Tuple, Dict[str, float]] = {}
    for row in _json_rows(lines):
        if row.get("metric") not in _AGENT_METRICS:
            continue
        key = (row.get("metric"), row.get("rollout"), row.get("scale"))
        fields: Dict[str, float] = {}
        v = row.get("steady_sps")
        if isinstance(v, (int, float)) and v > 0:
            fields["steady_sps"] = float(v)
        if fields:
            out[key] = {"throughput": fields, "latency": {}}
    return out


def parse_qps_rows(lines: List[str]) -> Dict[Tuple, Dict[str, Any]]:
    """serve_qps rows keyed the way merge_qps_rows keys them; throughput:
    tokens_per_s + achieved_qps, latency: p99_ms."""
    out: Dict[Tuple, Dict[str, Any]] = {}
    for line in lines or ():
        if not isinstance(line, str) or not line.startswith("{"):
            continue
        try:
            row = json.loads(line)
        except json.JSONDecodeError:
            continue
        if row.get("metric") != "serve_qps":
            continue
        key = fold_capture._qps_row_key(line)
        thr = {
            f: float(row[f])
            for f in _SERVE_THROUGHPUT
            if isinstance(row.get(f), (int, float)) and row[f] > 0
        }
        lat = {
            f: float(row[f])
            for f in _SERVE_LATENCY
            if isinstance(row.get(f), (int, float)) and row[f] > 0
        }
        if thr or lat:
            out[key] = {"throughput": thr, "latency": lat}
    return out


def parse_allreduce_rows(lines: List[str]) -> Dict[Tuple, Dict[str, Any]]:
    """allreduce sections: banner-keyed fixed-width tables; gated field is
    the MB/s column per (banner, elems) row."""
    out: Dict[Tuple, Dict[str, Any]] = {}
    for banner, sec_lines in fold_capture._split_allreduce_sections(lines or []):
        header: Optional[List[str]] = None
        for l in sec_lines:
            if re.match(r"\s*elems\s", l):
                header = l.split()
                continue
            m = re.match(r"\s*(\d+)\s", l)
            if not m or header is None:
                continue
            vals = l.split()
            if len(vals) != len(header):
                continue
            row = dict(zip(header, vals))
            try:
                mbs = float(row.get("MB/s", ""))
            except ValueError:
                continue
            if mbs > 0:
                out[(banner, int(m.group(1)))] = {
                    "throughput": {"MB/s": mbs}, "latency": {},
                }
    return out


def parse_r2d2_rows(lines: List[str]) -> Dict[Tuple, Dict[str, Any]]:
    """r2d2_learner rows keyed (metric, arm) — the way merge_r2d2_rows
    keys them; gated field: the per-arm replay-plane SPS (throughput).
    The r2d2_replay_ab summary row is provenance (speedups, bit-exactness,
    ingest accounting), not a gated measurement."""
    out: Dict[Tuple, Dict[str, Any]] = {}
    for row in _json_rows(lines):
        if row.get("metric") != "r2d2_learner_sps":
            continue
        key = (row.get("metric"), row.get("arm"))
        v = row.get("value")
        if isinstance(v, (int, float)) and v > 0:
            out[key] = {"throughput": {"value": float(v)}, "latency": {}}
    return out


SECTION_RULES = {
    "agent_small": parse_agent_rows,
    "r2d2_learner": parse_r2d2_rows,
    "serve_qps": parse_qps_rows,
    "allreduce_rpc": parse_allreduce_rows,
    "allreduce_ici": parse_allreduce_rows,
    "allreduce_rpc_multiproc": parse_allreduce_rows,
}


def load_capture(path: str) -> Dict[str, Any]:
    """A BENCH_LOCAL-shaped JSON file: {section: {..., "stdout": [lines]}}."""
    try:
        with open(path) as f:
            data = json.load(f)
    except OSError as e:
        raise GateError(f"cannot read {path}: {e}")
    except json.JSONDecodeError as e:
        raise GateError(f"malformed JSON in {path}: {e}")
    if not isinstance(data, dict):
        raise GateError(f"{path}: expected a JSON object of sections")
    for name, sec in data.items():
        if not isinstance(sec, dict) or not isinstance(sec.get("stdout", []), list):
            raise GateError(
                f"{path}: section {name!r} is not {{..., 'stdout': [lines]}}"
            )
    return data


def capture_from_logs(paths: List[str]) -> Dict[str, Any]:
    """Classify raw smoke logs into sections exactly the way
    ``fold_capture --local`` does (content-detected), without writing
    anything — the gate runs BEFORE the fold mutates the record."""
    data: Dict[str, Any] = {}
    for path in paths:
        if not os.path.exists(path):
            raise GateError(f"log not found: {path}")
        agent = fold_capture.parse_agent_lines(path)
        r2d2 = None if agent else fold_capture.parse_r2d2_local(path)
        qps = (
            None if (agent or r2d2) else fold_capture.parse_serve_qps(path)
        )
        allr = (
            None if (agent or r2d2 or qps)
            else fold_capture.parse_allreduce(path)
        )
        if agent:
            section, lines = "agent_small", agent
        elif r2d2:
            section, lines = "r2d2_learner", r2d2
        elif qps:
            section, lines = "serve_qps", qps
        elif allr:
            section, lines = "allreduce_rpc", allr
        else:
            raise GateError(
                f"no agent, r2d2, serve_qps, or allreduce rows found in "
                f"{path}"
            )
        sec = data.setdefault(section, {"stdout": []})
        sec["stdout"] = list(sec["stdout"]) + lines
    return data


def _fmt_key(key: Tuple) -> str:
    parts = []
    for k in key:
        s = str(k)
        parts.append(s if len(s) <= 48 else s[:45] + "...")
    return "/".join(parts)


def gate(
    baseline: Dict[str, Any],
    fresh: Dict[str, Any],
    throughput_floor: float = THROUGHPUT_FLOOR,
    latency_ceiling: float = LATENCY_CEILING,
    allow_new_sections: Tuple[str, ...] = (),
    sections: Optional[List[str]] = None,
) -> Tuple[List[dict], List[dict]]:
    """Compare fresh capture sections against the committed record.
    Returns (failures, report_rows); empty failures == gate passes."""
    failures: List[dict] = []
    report: List[dict] = []
    for name in fresh:
        if sections and name not in sections:
            continue
        if name not in baseline:
            if name in allow_new_sections or "all" in allow_new_sections:
                report.append({"section": name, "verdict": "NEW (allowed)"})
            else:
                failures.append({
                    "section": name, "key": "-", "field": "-",
                    "reason": "new section not in the committed record "
                              "(pass --allow-new-section to admit it)",
                })
            continue
        rule = SECTION_RULES.get(name)
        if rule is None:
            report.append({"section": name, "verdict": "no gate rules (skipped)"})
            continue
        base_rows = rule(baseline[name].get("stdout") or [])
        fresh_rows = rule(fresh[name].get("stdout") or [])
        if not fresh_rows:
            failures.append({
                "section": name, "key": "-", "field": "-",
                "reason": "capture parsed to zero gateable rows",
            })
            continue
        for key, frow in fresh_rows.items():
            brow = base_rows.get(key)
            if brow is None:
                report.append({
                    "section": name, "key": _fmt_key(key),
                    "verdict": "row not in committed record (informational)",
                })
                continue
            for field, fval in frow["throughput"].items():
                bval = brow["throughput"].get(field)
                if not bval:
                    continue
                ratio = fval / bval
                entry = {
                    "section": name, "key": _fmt_key(key), "field": field,
                    "base": bval, "fresh": fval, "ratio": ratio,
                }
                if ratio < throughput_floor:
                    entry["reason"] = (
                        f"throughput ratio {ratio:.2f} < floor {throughput_floor:.2f}"
                    )
                    failures.append(entry)
                else:
                    entry["verdict"] = "ok"
                    report.append(entry)
            for field, fval in frow["latency"].items():
                bval = brow["latency"].get(field)
                if not bval:
                    continue
                ratio = fval / bval
                entry = {
                    "section": name, "key": _fmt_key(key), "field": field,
                    "base": bval, "fresh": fval, "ratio": ratio,
                }
                if ratio > latency_ceiling:
                    entry["reason"] = (
                        f"latency ratio {ratio:.2f} > ceiling {latency_ceiling:.2f}"
                    )
                    failures.append(entry)
                else:
                    entry["verdict"] = "ok"
                    report.append(entry)
    return failures, report


def _print_table(rows: List[dict], file=sys.stdout) -> None:
    for r in rows:
        base = r.get("base")
        fresh = r.get("fresh")
        ratio = r.get("ratio")
        nums = (
            f" base={base:g} fresh={fresh:g} ratio={ratio:.2f}"
            if isinstance(ratio, float) else ""
        )
        verdict = r.get("verdict") or r.get("reason") or ""
        key = r.get("key")
        loc = f"{r['section']}" + (f" [{key}]" if key and key != "-" else "")
        field = f" {r['field']}" if r.get("field") and r["field"] != "-" else ""
        print(f"  {loc}{field}:{nums} {verdict}", file=file)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--baseline",
        default=os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "BENCH_LOCAL.json",
        ),
        help="committed record to gate against (default: repo BENCH_LOCAL.json)",
    )
    ap.add_argument("--capture", default=None,
                    help="fresh capture as BENCH_LOCAL-shaped JSON")
    ap.add_argument("--log", action="append", default=[],
                    help="fresh smoke log(s); classified like fold_capture --local")
    ap.add_argument("--smoke", action="store_true",
                    help="smoke mode: with --log gate those rows; bare --smoke "
                    "self-checks that the committed record passes its own gate")
    ap.add_argument("--throughput-floor", type=float, default=THROUGHPUT_FLOOR)
    ap.add_argument("--latency-ceiling", type=float, default=LATENCY_CEILING)
    ap.add_argument("--allow-new-section", action="append", default=[],
                    help="section name admitted even if absent from the "
                    "committed record ('all' admits any)")
    ap.add_argument("--sections", default=None,
                    help="comma-separated subset of sections to gate")
    args = ap.parse_args(argv)

    try:
        baseline = load_capture(args.baseline)
        if args.capture:
            fresh = load_capture(args.capture)
        elif args.log:
            fresh = capture_from_logs(args.log)
        elif args.smoke:
            fresh = baseline  # self-comparison: ratio 1.0 everywhere
        else:
            ap.error("need --capture, --log, or --smoke")
        failures, report = gate(
            baseline, fresh,
            throughput_floor=args.throughput_floor,
            latency_ceiling=args.latency_ceiling,
            allow_new_sections=tuple(args.allow_new_section),
            sections=args.sections.split(",") if args.sections else None,
        )
    except GateError as e:
        print(f"bench_gate: malformed input: {e}", file=sys.stderr)
        return 2
    ok_rows = [r for r in report if r.get("verdict") == "ok"]
    info_rows = [r for r in report if r.get("verdict") != "ok"]
    if ok_rows:
        print(f"bench_gate: {len(ok_rows)} row(s) within tolerance:")
        _print_table(ok_rows)
    for r in info_rows:
        _print_table([r])
    if failures:
        print(f"bench_gate: REGRESSION — {len(failures)} failing row(s):",
              file=sys.stderr)
        _print_table(failures, file=sys.stderr)
        return 1
    print("bench_gate: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
