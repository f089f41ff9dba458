"""Bench-trend gate: aggregate the committed BENCH_*.json artifacts.

Usage:
    python tools/bench_history.py [DIR | FILES...]
        [--json PATH|-] [--markdown PATH|-]
        [--threshold 0.25] [--check] [--allow kind:round[,kind:round]]

The perf trajectory lives in per-round artifacts (``BENCH_r01.json``,
``BENCH_r02.json``, ...) that nothing aggregated: BENCH_r05 shipped
*empty* (rc=1, ``parsed: null`` — the backend died at init) and only a
human reviewer noticed. This tool is the machine that notices:

* **trend table** — one row per workload (names normalized across
  cap changes: ``tpu paxos3 capped 500k`` and ``... capped 40000`` are
  the same trend line), one column per round, each cell the best rate
  with its tags (``degraded``, ``init_fallback``, ``multihost``) —
  so a round whose number was
  measured on a degraded mesh, a CPU fallback, or a DCN-spanning
  fleet mesh can never silently ride the trajectory as a
  single-host device number;
* **flags** — machine-readable problems: empty artifacts (rc != 0,
  ``parsed: null``), partial contract lines, per-workload error rows,
  workloads that vanished between rounds, and regressions (best rate
  dropping more than ``--threshold``, default 25%, round over round on
  comparable tags);
* **outputs** — a markdown report (default: stdout) and a JSON
  document (``--json -`` for stdout, ``--json PATH`` to write); with
  ``--check`` the exit code is 1 when any flag fired — the CI gate.
  ``--allow kind:round`` (repeatable, comma-separable) acknowledges a
  KNOWN-bad artifact (e.g. ``--allow empty_artifact:r05`` for the
  round-5 rc=1 hole) so the gate stays red only for NEW problems; the
  allowed flags are still reported, marked ``(allowed)``.

The contract line itself rides the table as workload ``<contract>``.
This output is the single source of truth for trajectory numbers —
README and NOTES quote it rather than hand-copied rates.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys
from typing import Any, Dict, List, Optional

#: tokens stripped from workload names so trend lines survive cap
#: changes between rounds ("capped 500k" vs "capped 40000"); the size
#: token FOLLOWING one of these is stripped too ("capped 1M-gen") —
#: but model-size tokens like "2pc7" stay, they ARE the workload
_CAP_TOKENS = {"capped", "full"}

CONTRACT = "<contract>"


def normalize_workload(name: str) -> str:
    """Collapse run-size tokens out of a workload name."""
    out = []
    skip_next = False
    for tok in name.split():
        if skip_next:
            skip_next = False
            continue
        if tok in _CAP_TOKENS:
            skip_next = True
            continue
        out.append(tok)
    return " ".join(out) or name


def _round_key(path: str) -> str:
    m = re.search(r"BENCH_(r\d+)", os.path.basename(path))
    return m.group(1) if m else os.path.basename(path)


def parse_round(path: str) -> Dict[str, Any]:
    """One artifact -> {round, rc, contract, workloads, errors}.

    Workload rows are the JSON lines bench.py printed to stderr
    (captured in the artifact's ``tail``); rounds before the
    structured rows (r01-r03) simply contribute no per-workload data.
    """
    with open(path) as f:
        art = json.load(f)
    rnd: Dict[str, Any] = {
        "round": _round_key(path),
        "path": os.path.basename(path),
        "rc": art.get("rc"),
        "contract": art.get("parsed"),
        "workloads": {},
        "errors": [],
    }
    for line in (art.get("tail") or "").splitlines():
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            row = json.loads(line)
        except json.JSONDecodeError:
            continue
        name = row.get("workload")
        if not name:
            continue
        if "error" in row:
            rnd["errors"].append({"workload": name,
                                  "error": row["error"]})
            continue
        if "skipped" in row:
            continue
        if "best" not in row and "jobs_per_min" in row:
            # a --job-storm mode row: jobs/min IS the trend number
            # (one line per mode, so a batched regression can never
            # hide behind an unbatched improvement)
            rnd["workloads"][normalize_workload(name)] = {
                "name": name,
                "best": row["jobs_per_min"],
                "median": None,
                "unit": "jobs/min",
                "uniq": None,
                "gen_per_uniq": None,
                "tags": sorted(t for t, on in (
                    ("storm", True),
                    ("partial", bool(row.get("failed"))),
                ) if on),
            }
            continue
        if "best" not in row:
            continue
        metrics = row.get("metrics") or {}
        rnd["workloads"][normalize_workload(name)] = {
            "name": name,
            "best": row.get("best"),
            "median": row.get("median"),
            "unit": row.get("unit"),
            "uniq": row.get("uniq"),
            "gen_per_uniq": row.get("gen_per_uniq"),
            # span attribution (PR 18): the top stall buckets and the
            # pipeline-bubble fraction bench.py embeds per workload —
            # optional (pre-span rounds simply lack them), trended as
            # the dominant-stall column
            "stalls": metrics.get("stalls"),
            "bubble_frac": metrics.get("bubble_frac"),
            # duplicate-expansion factor AFTER the cross-chunk dedup
            # ring's in-register kills (PR 13) — the g/u vs g/u_cc gap
            # is the cache's measured bite, tracked as its own trend
            "gen_per_uniq_cc": row.get("gen_per_uniq_cc"),
            "tags": sorted(
                t for t, on in (
                    ("degraded", bool(metrics.get("degrades"))),
                    ("retried", bool(metrics.get("retries"))),
                    ("spilled", bool(row.get("spilled"))
                     or bool(metrics.get("spills"))),
                ) if on),
        }
    contract = rnd["contract"]
    if isinstance(contract, dict) and contract.get("value") is not None:
        tags = sorted(
            t for t, on in (
                ("partial", bool(contract.get("partial"))),
                ("degraded", bool(contract.get("degraded"))),
                ("spilled", bool(contract.get("spilled"))),
                ("init_fallback", bool(contract.get("init_fallback"))),
                ("cpu", contract.get("backend") == "cpu"),
                # a --service-smoke round: the value is aggregate
                # job-service throughput, not a device engine rate
                ("service", bool(contract.get("service"))),
                # a --job-storm round: the value is batched jobs/min
                # through the lane engine (jobs_per_min rides the
                # per-mode rows as their own trend lines)
                ("storm", bool(contract.get("storm"))),
                # a --multihost-smoke round: the value is uniq/s of a
                # multi-process fleet mesh spanning DCN — not
                # comparable to single-host device rates
                ("multihost", bool(contract.get("hosts"))),
                # a --burnin-smoke round: the value is burn-in lane
                # jobs/min with a real checking job preempting through
                # — a fleet-behavior number, not an engine rate
                ("burnin", bool(contract.get("burnin"))),
                # a --flex-smoke round: a job storm under a rolling
                # host join/leave with the elastic flex controller on
                # — promote/demote behavior, not an engine rate
                ("flex", bool(contract.get("flex"))),
                # an --audit-smoke round: a lying chip caught by the
                # chunk auditor and replayed to oracle parity — a
                # defense-behavior number, not an engine rate
                ("audit", bool(contract.get("audit"))),
            ) if on)
        rnd["workloads"][CONTRACT] = {
            "name": contract.get("metric", "contract"),
            "best": contract["value"],
            "median": None,
            "unit": contract.get("unit"),
            "uniq": None,
            "gen_per_uniq": None,
            "tags": tags,
        }
    return rnd


def compute_flags(rounds: List[Dict[str, Any]],
                  threshold: float) -> List[Dict[str, Any]]:
    flags: List[Dict[str, Any]] = []
    for rnd in rounds:
        if rnd["rc"] not in (0, None) or rnd["contract"] is None:
            flags.append({
                "kind": "empty_artifact", "round": rnd["round"],
                "detail": f"rc={rnd['rc']}, "
                          f"parsed={'null' if rnd['contract'] is None else 'ok'}"
                          " — no trajectory numbers landed"})
            continue
        c = rnd["contract"]
        if c.get("partial"):
            flags.append({"kind": "partial", "round": rnd["round"],
                          "detail": f"failed={c.get('failed')}"})
        if c.get("init_fallback"):
            flags.append({
                "kind": "init_fallback", "round": rnd["round"],
                "detail": f"backend init failed "
                          f"(cause={c.get('init_cause')}); round ran "
                          "on the CPU fallback — not comparable to "
                          "device rounds"})
        if c.get("degraded"):
            flags.append({
                "kind": "degraded", "round": rnd["round"],
                "detail": f"primary metric finished on "
                          f"{c.get('final_shards')} shard(s)"})
        if c.get("spilled"):
            flags.append({
                "kind": "spilled", "round": rnd["round"],
                "detail": "primary metric hit its HBM budget and "
                          f"finished via host-tier spills "
                          f"({c.get('host_tier_keys')} keys host-"
                          "resident) — not comparable to all-HBM "
                          "rounds"})
        for err in rnd["errors"]:
            flags.append({"kind": "workload_error",
                          "round": rnd["round"],
                          "workload": err["workload"],
                          "detail": err["error"][:200]})
    # span-attribution coverage (PR 18): rounds BEFORE the first
    # attribution-carrying round predate the span profiler — flagged
    # informationally (never fatal, so the committed pre-span
    # artifacts keep the gate green). A LATER round with workload rows
    # but no attribution anywhere regressed the instrument: fatal.
    attr_idx = [i for i, r in enumerate(rounds)
                if _has_attribution(r)]
    if attr_idx:
        first = attr_idx[0]
        for i, rnd in enumerate(rounds):
            if _has_attribution(rnd) or not rnd["workloads"]:
                continue
            if i < first:
                flags.append({
                    "kind": "pre_span", "round": rnd["round"],
                    "info": True,
                    "detail": "round predates the span profiler — no "
                              "attribution fields (informational, "
                              "not fatal)"})
            else:
                flags.append({
                    "kind": "missing_attribution",
                    "round": rnd["round"],
                    "detail": "no workload row carries span "
                              "attribution (stalls/bubble_frac) in a "
                              "round AFTER the profiler landed in "
                              f"{rounds[first]['round']}"})
    # regressions / disappearances: compare each data round against the
    # PREVIOUS round that carried per-workload rows
    data_rounds = [r for r in rounds if r["workloads"]]
    for prev, cur in zip(data_rounds, data_rounds[1:]):
        comparable = (
            "init_fallback" not in _round_tags(prev)
            and "init_fallback" not in _round_tags(cur)
            and _round_backend(prev) == _round_backend(cur))
        for wname, pw in prev["workloads"].items():
            cw = cur["workloads"].get(wname)
            if cw is None:
                if not comparable:
                    # a backend switch legitimately changes the matrix
                    # (a CPU-fallback round skips the device-budget
                    # context workloads) — a "missing" flag there is
                    # noise, same reasoning as the regression gate
                    continue
                flags.append({
                    "kind": "missing_workload", "round": cur["round"],
                    "workload": wname,
                    "detail": f"present in {prev['round']}, absent in "
                              f"{cur['round']}"})
                continue
            if not comparable or pw["unit"] != cw["unit"]:
                continue
            if pw["unit"] == "s":  # latency: higher is worse
                if pw["best"] and cw["best"] > pw["best"] * (
                        1 + threshold):
                    flags.append(_regression(cur, wname, pw, cw,
                                             cw["best"] / pw["best"] - 1,
                                             prev))
            elif pw["best"] and cw["best"] < pw["best"] * (1 - threshold):
                flags.append(_regression(cur, wname, pw, cw,
                                         1 - cw["best"] / pw["best"],
                                         prev))
    return flags


def _has_attribution(rnd) -> bool:
    """True when any workload row of the round carries the span
    profiler's fields (``stalls``/``bubble_frac``)."""
    return any(w.get("stalls") or w.get("bubble_frac") is not None
               for w in rnd["workloads"].values())


def _round_tags(rnd) -> set:
    c = rnd.get("contract") or {}
    return {t for t, on in (
        ("init_fallback", c.get("init_fallback")),) if on}


def _round_backend(rnd) -> Optional[str]:
    c = rnd.get("contract") or {}
    return c.get("backend")


def _regression(cur, wname, pw, cw, drop, prev) -> Dict[str, Any]:
    return {"kind": "regression", "round": cur["round"],
            "workload": wname,
            "detail": f"{pw['best']} -> {cw['best']} {cw['unit']} "
                      f"({drop:.0%} worse than {prev['round']}; "
                      f"tags {pw['tags']} -> {cw['tags']})",
            "drop": round(drop, 4)}


def build_report(paths: List[str],
                 threshold: float = 0.25) -> Dict[str, Any]:
    rounds = [parse_round(p) for p in sorted(paths, key=_round_key)]
    flags = compute_flags(rounds, threshold)
    workloads = sorted({w for r in rounds for w in r["workloads"]})
    trend = {
        w: [{"round": r["round"], **r["workloads"][w]}
            for r in rounds if w in r["workloads"]]
        for w in workloads}
    return {"rounds": rounds, "trend": trend, "flags": flags,
            "threshold": threshold}


def render_markdown(report: Dict[str, Any], out) -> None:
    rounds = report["rounds"]
    out.write("# Bench trend (" + ", ".join(
        r["round"] for r in rounds) + ")\n\n")
    names = sorted(report["trend"])
    if names:
        heads = ["workload"] + [r["round"] for r in rounds]
        out.write("| " + " | ".join(heads) + " |\n")
        out.write("|" + "---|" * len(heads) + "\n")
        for w in names:
            cells = [w]
            by_round = {e["round"]: e for e in report["trend"][w]}
            for r in rounds:
                e = by_round.get(r["round"])
                if e is None:
                    cells.append("—")
                    continue
                cell = f"{e['best']:,} {e['unit']}" \
                    if isinstance(e["best"], (int, float)) else "?"
                if e.get("gen_per_uniq"):
                    cell += f", g/u={e['gen_per_uniq']}"
                if e.get("gen_per_uniq_cc"):
                    cell += f", g/u_cc={e['gen_per_uniq_cc']}"
                if e.get("stalls"):
                    # the dominant-stall trend: the bucket the next
                    # perf PR should target, round over round
                    cell += f", stall={e['stalls'][0][0]}"
                if e.get("bubble_frac") is not None:
                    cell += f", bubble={e['bubble_frac']}"
                if e["tags"]:
                    cell += " [" + ",".join(e["tags"]) + "]"
                cells.append(cell)
            out.write("| " + " | ".join(cells) + " |\n")
        out.write("\n")
    else:
        out.write("(no per-workload rows in any round)\n\n")
    out.write("## Flags\n\n")
    if not report["flags"]:
        out.write("none — every round landed numbers and no workload "
                  "regressed past the threshold\n")
    for f in report["flags"]:
        where = f.get("workload", "")
        out.write(f"* **{f['kind']}** {f['round']}"
                  + (f" `{where}`" if where else "")
                  + f": {f['detail']}"
                  + (" (allowed)" if f.get("allowed") else "")
                  + (" (informational)" if f.get("info") else "")
                  + "\n")


def allowed(flag: Dict[str, Any], allow: List[str]) -> bool:
    """``kind:round`` acknowledgement match for one flag."""
    return f"{flag.get('kind')}:{flag.get('round')}" in allow


def main(argv) -> int:
    if argv and argv[0] in ("-h", "--help"):
        print(__doc__)
        return 0
    threshold = 0.25
    if "--threshold" in argv:
        threshold = float(argv[argv.index("--threshold") + 1])
    json_to = (argv[argv.index("--json") + 1]
               if "--json" in argv else None)
    md_to = (argv[argv.index("--markdown") + 1]
             if "--markdown" in argv else None)
    allow: List[str] = []
    for i, a in enumerate(argv):
        if a == "--allow":
            allow.extend(argv[i + 1].split(","))
    consumed = set(allow) | ({",".join(allow)} if allow else set())
    positional = [a for a in argv if not a.startswith("--")
                  and a not in (str(threshold), json_to, md_to)
                  and a not in consumed]
    if not positional:
        positional = ["."]
    paths: List[str] = []
    for p in positional:
        if os.path.isdir(p):
            paths.extend(glob.glob(os.path.join(p, "BENCH_*.json")))
        else:
            paths.append(p)
    if not paths:
        print("bench_history.py: no BENCH_*.json artifacts found",
              file=sys.stderr)
        return 2
    report = build_report(paths, threshold)
    for f in report["flags"]:
        if allowed(f, allow):
            f["allowed"] = True
    if json_to == "-":
        json.dump(report, sys.stdout, indent=1, default=str)
        sys.stdout.write("\n")
    elif json_to:
        with open(json_to, "w") as f:
            json.dump(report, f, indent=1, default=str)
    if md_to and md_to != "-":
        with open(md_to, "w") as f:
            render_markdown(report, f)
    elif json_to is None or md_to == "-":
        render_markdown(report, sys.stdout)
    if "--check" in argv:
        # informational flags (pre-span rounds) never fail the gate
        hard = [f for f in report["flags"]
                if not allowed(f, allow) and not f.get("info")]
        if hard:
            return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
