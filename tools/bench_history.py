"""Perf-regression sentinel over the bench ledger.

Every bench run appends its record to ``BENCH_onchip_history.jsonl``
(bench.py does this at end of run, plus per-stage records for the
platform-neutral ``degraded`` and ``coldboot`` stages, so a run whose
device stages fail still leaves its CPU-side evidence). This tool
turns that ledger from an archive into a tripwire:

* records are grouped by their ``metric`` field; within a group every
  numeric leaf is flattened to a dotted path
  (``stages.tpu_run.sigs_per_sec``, ``stages.cpu_p50.verify_commit_
  p50_ms_150_cpu``, ...);
* the **rolling baseline** per path is the median over the last
  ``--window`` records BEFORE the newest one;
* the **noise band** per path is the widest of three estimates: the
  relative deviation of ``BENCH_onchip_variance.json`` (a full re-run
  record of the same bench — what same-machine run-to-run noise
  actually looks like) from the baseline, the observed relative spread
  of the prior records themselves (a path that historically swings 2×
  between runs must not alarm at 1.1×), and a ``--min-band`` floor
  (default 10%) so a stable path still gets a sane band;
* direction is inferred from the path: ``sigs_per_sec`` (and a
  ``sigs/sec``-unit headline ``value``) regress DOWN, ``*_ms`` / ``*_s``
  latencies regress UP; paths with no inferable direction (ratios,
  counts, flags) are ignored;
* a path is flagged only when the last ``--confirm`` records (default
  2) are ALL outside the band in the regressing direction — one noisy
  run on a loaded machine is a blip, the same path out of band twice
  running is a regression (the ledger spans heterogeneous driver hosts,
  so single-record alarms would be pure noise);
* ``--check`` exits non-zero on any confirmed regression — wire it
  after a bench run and CI turns red the day a change eats the
  throughput.

``--append FILE`` adds a record to the ledger (``--stage NAME`` wraps a
bare stage dict the way bench.py does); ``--self-test`` proves the
sentinel on a synthetic ledger (clean tail must pass, an injected 20%
regression must flag) and is run as a tier-1 test.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from statistics import median
from typing import Dict, List, Optional, Tuple

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_LEDGER = os.path.join(_ROOT, "BENCH_onchip_history.jsonl")
DEFAULT_VARIANCE = os.path.join(_ROOT, "BENCH_onchip_variance.json")
DEFAULT_WINDOW = 5
DEFAULT_MIN_BAND = 0.10
DEFAULT_CONFIRM = 2

HIGHER_IS_BETTER = "higher"
LOWER_IS_BETTER = "lower"


def load_ledger(path: str) -> List[dict]:
    """Parse the JSONL ledger, skipping unparseable lines (a crashed
    writer must not brick the sentinel)."""
    records = []
    try:
        with open(path, encoding="utf-8") as fh:
            for line in fh:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except Exception:  # noqa: BLE001 - torn write, skip
                    continue
                if isinstance(rec, dict):
                    records.append(rec)
    except OSError:
        return []
    return records


def flatten(doc: dict, prefix: str = "") -> Dict[str, float]:
    """Numeric leaves of ``doc`` as dotted paths. Bools and non-finite
    values are not measurements; lists are positional."""
    out: Dict[str, float] = {}
    items = (
        doc.items() if isinstance(doc, dict)
        else enumerate(doc) if isinstance(doc, list)
        else ()
    )
    for key, val in items:
        path = f"{prefix}.{key}" if prefix else str(key)
        if isinstance(val, bool):
            continue
        if isinstance(val, (int, float)):
            v = float(val)
            if v == v and abs(v) != float("inf"):
                out[path] = v
        elif isinstance(val, (dict, list)):
            out.update(flatten(val, path))
    return out


def direction(path: str, unit: Optional[str] = None) -> Optional[str]:
    """Which way this path regresses, or None when the name carries no
    direction (ratios, counts, config echoes)."""
    leaf = path.rsplit(".", 1)[-1]
    if path == "value":
        if unit and "sigs/sec" in unit:
            return HIGHER_IS_BETTER
        return None
    if "vs_" in leaf or leaf.startswith(("n_", "num_")):
        return None
    if "sigs_per_sec" in leaf or "per_sec" in leaf:
        return HIGHER_IS_BETTER
    # wire-path phases (bench.py tpu_breakdown → host prepare + H2D
    # transfer): explicit so a suffix-rule rework can't silently drop
    # the link-regression guard
    if leaf.endswith(("_transfer_ms", "_prepare_ms")):
        return LOWER_IS_BETTER
    # compact-wire guards (PR 13): payload size per signature lane must
    # only ever shrink, and the dispatch loops' designed transfer/
    # compute overlap must not regress toward exposed H2D
    if leaf.endswith("_bytes_per_lane"):
        return LOWER_IS_BETTER
    if leaf.endswith("_overlap_ratio"):
        return HIGHER_IS_BETTER
    # decision-plane guards (PR 15): routing-prediction accuracy and
    # counterfactual regret must only ever improve; explicit because
    # "mape" is a ratio (the generic ratio rule would drop it) and the
    # regret guard must survive a suffix-rule rework
    if leaf.endswith("_mape"):
        return LOWER_IS_BETTER
    if leaf.endswith("_regret_ms"):
        return LOWER_IS_BETTER
    # live-router guards (PR 16): taken-vs-argmin divergence of the
    # priced router and the steady-state indexed wire's bytes/lane are
    # both one-way ratchets (a ratio and a _steady suffix the generic
    # rules would drop)
    if leaf.endswith("_route_divergence"):
        return LOWER_IS_BETTER
    if leaf.endswith("_bytes_per_lane_steady"):
        return LOWER_IS_BETTER
    # verify-as-a-service guards (PR 17): what cross-client coalescing
    # buys over isolated per-client dispatch is a ratio (the generic
    # rules would drop it) and must only grow; the coalesced service's
    # per-request p99 is already covered by the generic _ms rule but is
    # pinned here so a suffix-rule rework can't silently drop it
    if leaf.endswith("_coalesce_gain"):
        return HIGHER_IS_BETTER
    if leaf.endswith("_service_p99_ms") or leaf == "service_p99_ms":
        return LOWER_IS_BETTER
    # adversarial-committee guards (PR 18): wrong verdicts are a
    # zero-tolerance one-way ratchet (a bare count the n_-prefix/count
    # conventions would otherwise drop), and the per-committee-size
    # storm p99 leaves are pinned so a suffix-rule rework can't
    # silently drop the committee-scale latency guard
    if leaf.endswith("_wrong_verdicts"):
        return LOWER_IS_BETTER
    if leaf.startswith("adversary_") and leaf.endswith("_p99_ms"):
        return LOWER_IS_BETTER
    # cross-process observability guards (PR 19): what end-to-end trace
    # propagation (v2 wire extension + adopted server spans) costs per
    # request is a percentage the generic rules would drop, and typed
    # service refusal counts are bare counters — both are one-way
    # ratchets that must only ever shrink
    if leaf.endswith("_trace_overhead_pct"):
        return LOWER_IS_BETTER
    if leaf.endswith("_refusals"):
        return LOWER_IS_BETTER
    # HA verify-fleet guards (PR 20): the failover verdict gap is
    # already covered by the generic _ms rule but is pinned here so a
    # suffix-rule rework can't silently drop the availability guard,
    # and CPU fallbacks during a ROLLING restart are a zero-tolerance
    # bare counter (the count conventions would otherwise drop it)
    if leaf.endswith("_failover_gap_ms"):
        return LOWER_IS_BETTER
    if leaf.endswith("_cpu_fallbacks"):
        return LOWER_IS_BETTER
    if leaf.endswith(("_ms", "_s", "_us", "_ns")) or "_ms_" in leaf:
        return LOWER_IS_BETTER
    return None


def _group_by_metric(records: List[dict]) -> Dict[str, List[dict]]:
    groups: Dict[str, List[dict]] = {}
    for rec in records:
        metric = rec.get("metric")
        if isinstance(metric, str) and metric:
            groups.setdefault(metric, []).append(rec)
    return groups


def _noise_bands(
    variance_path: Optional[str],
    baseline: Dict[str, float],
    min_band: float,
    metric: Optional[str] = None,
) -> Dict[str, float]:
    """Per-path relative noise band: |variance_rec − baseline| /
    baseline, floored at ``min_band``. The variance record is ONE full
    re-run of the bench on the same machine — the honest measurement of
    what run-to-run jitter looks like per path. It only informs the
    metric group it belongs to; other groups keep the floor."""
    bands = {path: min_band for path in baseline}
    if not variance_path:
        return bands
    try:
        with open(variance_path, encoding="utf-8") as fh:
            var_rec = json.load(fh)
    except (OSError, ValueError):
        return bands
    if not isinstance(var_rec, dict):
        return bands
    if metric is not None and var_rec.get("metric") not in (None, metric):
        return bands
    var_flat = flatten(var_rec)
    for path, base in baseline.items():
        v = var_flat.get(path)
        if v is None or base == 0:
            continue
        bands[path] = max(min_band, abs(v - base) / abs(base))
    return bands


def check_group(
    metric: str,
    records: List[dict],
    window: int,
    min_band: float,
    variance_path: Optional[str],
    confirm: int = DEFAULT_CONFIRM,
) -> Tuple[List[dict], int]:
    """→ (regressions, paths_compared) for one metric group. The last
    ``confirm`` records are the candidates; the rolling-median baseline
    comes from the up-to-``window`` records before them. A path is a
    regression only when EVERY candidate is out of band in the
    regressing direction — confirmation hysteresis against one-off
    noisy runs."""
    confirm = max(1, min(confirm, len(records) - 1))
    if len(records) < 2:
        return [], 0
    candidates = records[-confirm:]
    prior = records[:-confirm][-window:]
    if not prior:
        return [], 0
    latest_flat = flatten(candidates[-1])
    cand_flats = [flatten(r) for r in candidates]
    prior_flats = [flatten(r) for r in prior]
    baseline: Dict[str, float] = {}
    spread: Dict[str, float] = {}
    for path in latest_flat:
        vals = [f[path] for f in prior_flats if path in f]
        if not vals:
            continue
        base = median(vals)
        baseline[path] = base
        if base != 0:
            # historical run-to-run swing of this path: the worst
            # relative excursion of any prior record from the median
            spread[path] = max(
                abs(v - base) / abs(base) for v in vals
            )
    bands = _noise_bands(variance_path, baseline, min_band, metric)
    for path, s in spread.items():
        bands[path] = max(bands.get(path, min_band), s)
    latest = candidates[-1]
    unit = latest.get("unit") if isinstance(latest.get("unit"), str) else None
    regressions = []
    compared = 0
    for path, base in sorted(baseline.items()):
        direc = direction(path, unit)
        if direc is None or base == 0:
            continue
        compared += 1
        band = bands.get(path, min_band)

        def _out(flat: Dict[str, float]) -> bool:
            cur = flat.get(path)
            if cur is None:
                return False
            d = (cur - base) / abs(base)
            return d < -band if direc == HIGHER_IS_BETTER else d > band

        if all(_out(f) for f in cand_flats):
            cur = latest_flat[path]
            delta = (cur - base) / abs(base)
            regressions.append({
                "metric": metric,
                "path": path,
                "baseline": round(base, 3),
                "latest": round(cur, 3),
                "delta_pct": round(delta * 100.0, 1),
                "band_pct": round(band * 100.0, 1),
                "direction": direc,
                "baseline_n": len(prior),
                "confirmed_over": len(cand_flats),
            })
    return regressions, compared


def run_check(
    ledger: str,
    variance: Optional[str],
    window: int,
    min_band: float,
    confirm: int = DEFAULT_CONFIRM,
) -> Tuple[int, dict]:
    """→ (exit_code, report). Non-zero when any group's last ``confirm``
    records all regressed outside their noise band."""
    records = load_ledger(ledger)
    if not records:
        return 0, {"ledger": ledger, "records": 0, "groups": {},
                   "regressions": [], "note": "empty ledger — nothing "
                   "to compare"}
    groups = _group_by_metric(records)
    all_regressions: List[dict] = []
    group_report = {}
    for metric, recs in sorted(groups.items()):
        regs, compared = check_group(
            metric, recs, window, min_band, variance, confirm
        )
        group_report[metric] = {
            "records": len(recs),
            "paths_compared": compared,
            "regressions": len(regs),
        }
        all_regressions.extend(regs)
    report = {
        "ledger": ledger,
        "records": len(records),
        "window": window,
        "confirm": confirm,
        "min_band_pct": round(min_band * 100.0, 1),
        "groups": group_report,
        "regressions": all_regressions,
    }
    return (1 if all_regressions else 0), report


def append_record(
    record: dict, ledger: str, stage: Optional[str] = None
) -> dict:
    """Append ``record`` to the ledger as one JSON line. With ``stage``,
    a bare stage dict is wrapped the way bench.py wraps its per-stage
    appends, so the sentinel groups it under ``bench_stage_<stage>``."""
    if stage:
        record = {
            "metric": f"bench_stage_{stage}",
            "unit": "mixed",
            "stages": {stage: record},
        }
    line = json.dumps(record, sort_keys=True)
    with open(ledger, "a", encoding="utf-8") as fh:
        fh.write(line + "\n")
    return record


def _self_test() -> int:
    """Prove the sentinel on a synthetic ledger: a stable tail must
    pass, a single out-of-band blip must NOT page, and a sustained
    injected 20% regression MUST flag. → process exit code."""
    import tempfile

    def rec(sps: float, p50: float, adv_p99: float = 80.0,
            trace_ovh: float = 1.0) -> dict:
        return {
            "metric": "selftest_throughput",
            "value": round(sps, 1),
            "unit": "sigs/sec",
            "stages": {
                "run": {"sigs_per_sec": round(sps, 1)},
                "p50": {"verify_commit_p50_ms": round(p50, 2)},
                "adversary": {
                    "adversary_512_p99_ms": round(adv_p99, 2),
                    "adversary_wrong_verdicts": 0,
                },
                "service": {
                    "service_trace_overhead_pct": round(trace_ovh, 2),
                    "service_refusals": 0,
                },
            },
        }

    stable = [
        rec(1000.0 + 3 * i, 50.0 + 0.05 * i, 80.0 + 0.2 * i,
            1.0 + 0.01 * i)
        for i in range(5)
    ]
    cases = {
        # newest within ~1% of the rolling median: must NOT flag
        "clean": (stable + [rec(1010.0, 50.3)], 0),
        # one noisy run, then back in band: a blip, must NOT flag
        "blip": (stable + [rec(800.0, 62.0, 101.0, 1.4),
                           rec(1011.0, 50.3)], 0),
        # injected 20% throughput drop + 24% latency bump (storm p99
        # and a 40% trace-propagation-overhead creep included),
        # sustained over the confirmation window: MUST flag
        "regressed": (stable + [rec(801.0, 61.8, 100.5, 1.41),
                                rec(800.0, 62.0, 101.0, 1.4)], 1),
    }
    failures = []
    # the adversary wrong-verdict leaf's healthy baseline is 0, which
    # the band math skips (base == 0) — so prove the direction rules
    # themselves: a wrong-verdict increase and a storm-p99 increase are
    # both regressions, and the spelled-out leaves carry a direction
    for path, want in (
        ("stages.adversary.adversary_wrong_verdicts", LOWER_IS_BETTER),
        ("stages.adversary.adversary_512_p99_ms", LOWER_IS_BETTER),
        ("stages.adversary.adversary_1024_p50_ms", LOWER_IS_BETTER),
        # PR 19 ratchets: refusal counts' healthy baseline is 0 (band
        # math skips it), so the direction rule is the whole guard
        ("stages.service.service_trace_overhead_pct", LOWER_IS_BETTER),
        ("stages.service.service_refusals", LOWER_IS_BETTER),
        ("stages.service.service_tenant_refusals", LOWER_IS_BETTER),
        # PR 20 ratchets: the HA failover verdict gap is pinned past
        # any suffix-rule rework, and rolling-restart CPU fallbacks
        # (healthy baseline 0 — band math skips it) regress on any rise
        ("stages.ha.ha_failover_gap_ms", LOWER_IS_BETTER),
        ("stages.ha.ha_rolling_cpu_fallbacks", LOWER_IS_BETTER),
        ("stages.ha.ha_wrong_verdicts", LOWER_IS_BETTER),
        ("stages.ha.ha_fleet_sigs_per_sec", HIGHER_IS_BETTER),
    ):
        got = direction(path)
        ok = got == want
        print(f"self-test direction {path}: {got} "
              f"{'ok' if ok else 'FAIL (want %s)' % want}")
        if not ok:
            failures.append(path)
    with tempfile.TemporaryDirectory() as td:
        for name, (rows, want_rc) in cases.items():
            ledger = os.path.join(td, f"{name}.jsonl")
            with open(ledger, "w", encoding="utf-8") as fh:
                for r in rows:
                    fh.write(json.dumps(r) + "\n")
            rc, report = run_check(
                ledger, variance=None, window=DEFAULT_WINDOW,
                min_band=DEFAULT_MIN_BAND, confirm=DEFAULT_CONFIRM,
            )
            ok = rc == want_rc
            if name == "regressed" and ok:
                flagged = {r["path"] for r in report["regressions"]}
                ok = (
                    "stages.run.sigs_per_sec" in flagged
                    and "stages.p50.verify_commit_p50_ms" in flagged
                    and "stages.adversary.adversary_512_p99_ms" in flagged
                    and "stages.service.service_trace_overhead_pct"
                    in flagged
                )
            print(f"self-test {name}: rc={rc} (want {want_rc}) "
                  f"{'ok' if ok else 'FAIL'}")
            if not ok:
                failures.append(name)
                print(json.dumps(report, indent=2))
    print("BENCH-HISTORY SELF-TEST", "PASS" if not failures else "FAIL")
    return 0 if not failures else 1


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--ledger", default=DEFAULT_LEDGER,
                    help="bench history JSONL (default "
                         "BENCH_onchip_history.jsonl at the repo root)")
    ap.add_argument("--variance", default=DEFAULT_VARIANCE,
                    help="variance record JSON used to derive per-path "
                         "noise bands (default BENCH_onchip_variance."
                         "json; missing file = --min-band everywhere)")
    ap.add_argument("--window", type=int, default=DEFAULT_WINDOW,
                    help="rolling-baseline depth: median over the last "
                         "N records before the newest (default 5)")
    ap.add_argument("--min-band", type=float, default=DEFAULT_MIN_BAND,
                    help="noise-band floor as a fraction (default 0.10 "
                         "= 10%%)")
    ap.add_argument("--confirm", type=int, default=DEFAULT_CONFIRM,
                    help="consecutive out-of-band records required "
                         "before a path counts as regressed (default 2;"
                         " 1 = alarm on the newest record alone)")
    ap.add_argument("--check", action="store_true",
                    help="exit non-zero if the newest record of any "
                         "metric group regressed outside its band "
                         "(this is also the default action)")
    ap.add_argument("--append", metavar="FILE",
                    help="append the JSON record in FILE ('-' = stdin) "
                         "to the ledger, then exit")
    ap.add_argument("--stage", metavar="NAME",
                    help="with --append: wrap the record as a "
                         "bench_stage_<NAME> per-stage entry")
    ap.add_argument("--self-test", action="store_true",
                    help="prove the sentinel on a synthetic ledger "
                         "(clean passes, injected 20%% regression "
                         "flags) and exit")
    args = ap.parse_args()

    if args.self_test:
        return _self_test()

    if args.append:
        raw = (
            sys.stdin.read() if args.append == "-"
            else open(args.append, encoding="utf-8").read()
        )
        record = json.loads(raw)
        if not isinstance(record, dict):
            print("record must be a JSON object", file=sys.stderr)
            return 2
        written = append_record(record, args.ledger, stage=args.stage)
        print(json.dumps({
            "appended": written.get("metric"), "ledger": args.ledger,
        }))
        return 0

    variance = args.variance if os.path.exists(args.variance) else None
    rc, report = run_check(
        args.ledger, variance, args.window, args.min_band, args.confirm
    )
    print(json.dumps(report, indent=2))
    print("BENCH-HISTORY CHECK", "PASS" if rc == 0 else "FAIL")
    return rc


if __name__ == "__main__":
    sys.exit(main())
