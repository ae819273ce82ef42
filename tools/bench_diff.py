"""Compare two bench artifacts and flag regressions.

    python tools/bench_diff.py OLD NEW [--threshold PCT] [--watch KEY]...
    python tools/bench_diff.py --selftest

Accepts any pairing of the bench pipeline's JSON artifacts and
autodetects each side:

- a driver trajectory capture (``BENCH_rXX.json``: ``{"rc", "tail",
  "parsed"}`` — the metric line rides ``parsed``),
- a raw bench metric line (the last stdout line of ``bench.py``),
- a telemetry registry snapshot (``bench_telemetry.json``,
  ``kind == "mvtpu.metrics.v1"`` — counters/gauges become
  ``counter:...`` / ``gauge:...`` keys; step-time histograms become
  ``hist_mean_s:...``).

- a client-pipeline micro-bench line (``client_bench.json`` from
  ``benchmarks/client_pipeline.py`` — same flat metric-line shape),

- a windowed-series doc (a ``/vars?window=`` capture or the merged
  fleet doc ``report --fleet --vars-out`` writes,
  ``kind == "mvtpu.series.v1"`` — counter rates become ``rate:...``
  / ``delta:...`` keys, gauges ``gauge:...``, windowed histogram
  quantiles ``win_p99_s:...`` etc.), so a CI gate can diff "ops/s
  over the last 30 seconds" instead of lifetime cumulative counts.

Prints every shared numeric key with old/new/delta%, plus keys present
on only one side. Exit status is the CI contract: 0 when every watched
key holds, 1 when a watched key REGRESSED by more than ``--threshold``
percent, 2 on unusable input. Watched keys carry a DIRECTION:
``--watch`` keys are higher-is-better (throughputs — a drop regresses)
and ``--watch-lower`` keys are lower-is-better (tail latencies — a
RISE regresses); improvements never fail either way. Default watch
list: the metrics of record, the e2e tier, the client-pipeline /
kernel micro-bench throughputs, and the serving bench's p99 latency
(each applied when present; any ``--watch``/``--watch-lower`` replaces
the whole default list).

Pure stdlib, no jax — like the report CLI it must run without taking
the chip, and in CI (``make bench-diff`` /
``make ci``'s selftest hook).
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Dict, List, Tuple

SNAPSHOT_KIND = "mvtpu.metrics.v1"
SERIES_KIND = "mvtpu.series.v1"
DEFAULT_WATCH = ("value", "e2e_words_per_sec", "lda_doc_tokens_per_sec",
                 # client-pipeline micro-bench (benchmarks/
                 # client_pipeline.py): the coalesced-add and cached-get
                 # throughputs are the PR's metrics of record
                 "kv_add_ops_per_sec_coalesced",
                 "kv_add_ops_per_sec_staged",
                 # ...and the training-health lane: the same direct adds
                 # with the fused numerics audit ON — a regression here
                 # means the health layer crept back onto the hot path
                 "kv_add_ops_per_sec_health",
                 "get_ops_per_sec_cached",
                 # checkpoint micro-bench (benchmarks/
                 # checkpoint_bench.py): run-level store throughput —
                 # a regression here makes every checkpoint cadence
                 # steal more training wall-clock
                 "ckpt_store_mb_per_sec",
                 # table-kernel micro-bench (benchmarks/
                 # table_kernels.py): the Pallas engine's KV probe and
                 # COO scatter dispatch rates — the server-side hot
                 # path's metrics of record
                 "kv_probe_ops_per_sec_pallas",
                 "coo_scatter_ops_per_sec_pallas",
                 # ...and the sharded-mesh lane (model=2 shard_map
                 # engines vs flat GSPMD XLA): the per-shard Pallas
                 # dispatch rates the sharded engine ships for
                 "kv_probe_ops_per_sec_pallas_sharded",
                 "coo_scatter_ops_per_sec_pallas_sharded",
                 # serving bench (benchmarks/serving.py) throughput —
                 # its tail latencies ride DEFAULT_WATCH_LOWER below
                 "serving_ops_per_sec",
                 # tiered KV storage bench (benchmarks/tiered_kv.py):
                 # get throughput under fault-in churn with the device
                 # budget a fraction of the table
                 "tiered_kv_get_ops_per_sec",
                 # multi-process wire bench (benchmarks/serving_mp.py):
                 # bytes-on-wire throughput across worker processes —
                 # its step tail rides DEFAULT_WATCH_LOWER below
                 "wire_mb_per_sec",
                 # ...and its fused ops lane: cross-client adds per
                 # second with dispatch-cycle request fusion ON — a
                 # regression here means the fusion drain stopped
                 # batching the dispatch hot path
                 "serving_mp_ops_per_sec",
                 # fleet lane (serving_mp --servers N): aggregate
                 # range-read rate against the sharded fleet, and the
                 # per-server scaling efficiency (speedup / N) — a drop
                 # in either means the scatter-gather router or the
                 # partitioned servers stopped turning N processes into
                 # served throughput
                 "serving_fleet_ops_per_sec",
                 "fleet_scaling_efficiency",
                 # tracing-on ops lane (serving_mp): add throughput
                 # with the wire trace context stamped on every frame —
                 # a drop here means distributed tracing stopped being
                 # cheap enough to leave on
                 "serving_mp_traced_ops_per_sec",
                 # attribution lane (serving_mp): add throughput with
                 # the heavy-hitter accounting plane ON — a drop means
                 # usage attribution stopped being cheap enough to
                 # leave on in the dispatch loop
                 "serving_mp_attributed_ops_per_sec",
                 # autotune lane (serving.py --autotune): protected
                 # throughput AFTER the controller converges a mistuned
                 # server — a drop means the closed loop stopped
                 # recovering the hand-tuned operating point, while the
                 # mistuned starting floor rides along unwatched
                 "autotune_converged_ops_per_sec",
                 # replica lane (serving_mp --replicas): follower-routed
                 # bounded-staleness read rate under a primary write
                 # storm — a drop means follower reads fell back onto
                 # the primary's dispatch queue (routing, the snapshot
                 # fast path, or the staleness ledger broke)
                 "replica_read_ops_per_sec",
                 # ...and the delta-stream economy: full-precision
                 # bytes per replicated byte — a drop toward 1.0 means
                 # the tap started re-encoding (or raw-syncing) instead
                 # of forwarding the original encoded frames
                 "replication_bytes_ratio",
                 # reshard lane (serving_mp --reshard): migration
                 # throughput over the grow's closed-form moved set —
                 # a drop means the chunk stream (or the admin wave
                 # around it) got slower at moving the SAME bytes,
                 # stretching the window where donors relay
                 "reshard_moved_mb_per_sec")

# LOWER-is-better watches: a rise past the threshold regresses
DEFAULT_WATCH_LOWER = ("serving_p99_ms",
                       # a rising miss ratio means the EWMA placement
                       # stopped keeping the hot set device-resident
                       "tiered_kv_miss_ratio",
                       # cold-start miss-storm tail (serving bench's
                       # tiered lane)
                       "serving_tiered_p99_ms",
                       # multi-process wire bench worker step tail —
                       # a rise means the socket transport crept onto
                       # the training step's critical path
                       "serving_mp_p99_ms",
                       # same-host shm-ring round trip (serving_mp's
                       # staleness-read probe) — a rise means the ring
                       # transport lost its edge over tcp loopback
                       "shm_rtt_us",
                       # flood lane (serving_mp --flood): protected-
                       # class p999 under a deliberate flooder — a rise
                       # means admission control stopped insulating
                       # well-behaved clients from the flood
                       "serving_protected_p999_ms",
                       # reshard lane: worst-case client step stall
                       # while the fleet grows under the write storm —
                       # a rise means live resharding stopped being
                       # live (a lock hold, an unthrottled stream, or
                       # the relay path blocking the client)
                       "reshard_p999_stall_ms")


def _flatten(prefix: str, obj, out: Dict[str, float]) -> None:
    if isinstance(obj, bool):
        return
    if isinstance(obj, (int, float)):
        out[prefix] = float(obj)
    elif isinstance(obj, dict):
        for k, v in obj.items():
            _flatten(f"{prefix}.{k}" if prefix else str(k), v, out)


def load_metrics(path: str) -> Dict[str, float]:
    """One artifact → flat {key: number} (see module docstring)."""
    with open(path) as f:
        try:
            doc = json.load(f)
        except ValueError as e:
            raise SystemExit(f"bench_diff: {path}: not JSON ({e})")
    if not isinstance(doc, dict):
        raise SystemExit(f"bench_diff: {path}: expected a JSON object")
    if doc.get("kind") == SNAPSHOT_KIND:
        out: Dict[str, float] = {}
        for k, v in doc.get("counters", {}).items():
            out[f"counter:{k}"] = float(v)
        for k, v in doc.get("gauges", {}).items():
            out[f"gauge:{k}"] = float(v)
        for k, h in doc.get("histograms", {}).items():
            if h.get("count"):
                out[f"hist_mean_s:{k}"] = h["sum"] / h["count"]
                out[f"hist_count:{k}"] = float(h["count"])
        return out
    if doc.get("kind") == SERIES_KIND:
        out = {}
        for k, v in doc.get("rates", {}).items():
            out[f"rate:{k}"] = float(v)
        for k, v in doc.get("deltas", {}).items():
            out[f"delta:{k}"] = float(v)
        for k, v in doc.get("gauges", {}).items():
            out[f"gauge:{k}"] = float(v)
        for k, h in doc.get("histograms", {}).items():
            if h.get("count"):
                out[f"win_count:{k}"] = float(h["count"])
                for q in ("p50", "p99", "p999"):
                    if h.get(q) is not None:
                        out[f"win_{q}_s:{k}"] = float(h[q])
        return out
    if "parsed" in doc:                       # driver trajectory capture
        parsed = doc.get("parsed")
        if not isinstance(parsed, dict):
            raise SystemExit(
                f"bench_diff: {path}: capture has no parsed metric line "
                f"(rc={doc.get('rc')}) — nothing to compare")
        doc = parsed
    out = {}
    _flatten("", doc, out)
    out.pop("ts", None)
    return out


def diff(old: Dict[str, float], new: Dict[str, float],
         watch: Dict[str, str], threshold_pct: float
         ) -> Tuple[List[List[str]], List[str], List[str]]:
    """(table rows, regressions, only-one-side notes). ``watch`` maps
    key -> direction ("higher" = a drop regresses, "lower" = a rise
    regresses)."""
    rows: List[List[str]] = []
    regressions: List[str] = []
    for k in sorted(set(old) | set(new)):
        if k not in old or k not in new:
            continue
        o, n = old[k], new[k]
        pct = (n - o) / abs(o) * 100.0 if o else (0.0 if n == o
                                                  else float("inf"))
        direction = watch.get(k)
        mark = ""
        if direction == "higher" and pct < -threshold_pct:
            mark = "REGRESSED"
            regressions.append(
                f"{k}: {o:g} -> {n:g} ({pct:+.1f}% < -{threshold_pct:g}%)")
        elif direction == "lower" and pct > threshold_pct:
            mark = "REGRESSED"
            regressions.append(
                f"{k}: {o:g} -> {n:g} ({pct:+.1f}% > +{threshold_pct:g}%"
                f", lower is better)")
        elif direction:
            mark = "watched" if direction == "higher" \
                else "watched (lower)"
        rows.append([k, f"{o:g}", f"{n:g}",
                     f"{pct:+.1f}%" if pct == pct else "?", mark])
    notes = [f"only in old: {k} = {old[k]:g}"
             for k in sorted(set(old) - set(new))]
    notes += [f"only in new: {k} = {new[k]:g}"
              for k in sorted(set(new) - set(old))]
    return rows, regressions, notes


def _render(rows: List[List[str]]) -> str:
    header = ["key", "old", "new", "delta", ""]
    widths = [max(len(r[i]) for r in [header] + rows)
              for i in range(len(header))]
    fmt = "  ".join(f"{{:<{w}}}" for w in widths)
    return "\n".join([fmt.format(*header).rstrip()]
                     + [fmt.format(*r).rstrip() for r in rows])


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="python tools/bench_diff.py",
        description="Diff two bench artifacts; nonzero exit on a "
                    "watched-metric regression past the threshold.")
    p.add_argument("old", nargs="?", help="baseline artifact (JSON)")
    p.add_argument("new", nargs="?", help="candidate artifact (JSON)")
    p.add_argument("--threshold", type=float, default=10.0,
                   metavar="PCT", help="regression tolerance in percent "
                                       "(default 10)")
    p.add_argument("--watch", action="append", default=[], metavar="KEY",
                   help="higher-is-better key that must not drop "
                        "(repeatable; any --watch/--watch-lower "
                        "replaces the default watch list)")
    p.add_argument("--watch-lower", action="append", default=[],
                   metavar="KEY",
                   help="LOWER-is-better key (tail latency) that must "
                        "not rise (repeatable)")
    p.add_argument("--selftest", action="store_true",
                   help="run the built-in self-check and exit")
    args = p.parse_args(argv)
    if args.selftest:
        return selftest()
    if not args.old or not args.new:
        p.error("OLD and NEW artifacts are required (or --selftest)")
    try:
        old = load_metrics(args.old)
        new = load_metrics(args.new)
    except SystemExit as e:
        print(e.code if isinstance(e.code, str) else e, file=sys.stderr)
        return 2
    if args.watch or args.watch_lower:
        watch = {k: "higher" for k in args.watch}
        watch.update({k: "lower" for k in args.watch_lower})
    else:
        watch = {k: "higher" for k in DEFAULT_WATCH}
        watch.update({k: "lower" for k in DEFAULT_WATCH_LOWER})
    rows, regressions, notes = diff(old, new, watch, args.threshold)
    if rows:
        print(_render(rows))
    for n in notes:
        print(n)
    if regressions:
        print("\nREGRESSIONS past threshold "
              f"{args.threshold:g}%:", file=sys.stderr)
        for r in regressions:
            print(f"  {r}", file=sys.stderr)
        return 1
    if not rows:
        print("no shared numeric keys — nothing compared",
              file=sys.stderr)
    return 0


def selftest() -> int:
    """Hermetic check of the load/diff/exit contract (the `make ci`
    hook): builds artifacts of each accepted shape in a temp dir and
    asserts the comparisons and exit codes."""
    import os
    import tempfile

    with tempfile.TemporaryDirectory() as tmp:
        def put(name, doc):
            path = os.path.join(tmp, name)
            with open(path, "w") as f:
                json.dump(doc, f)
            return path

        line_old = {"metric": "w2v_words_per_sec_per_chip",
                    "value": 1000.0, "unit": "words/s",
                    "e2e_words_per_sec": 500.0,
                    "lda_doc_tokens_per_sec": 2e6,
                    "w2v_roofline": {"mxu_util_pct": 0.5}}
        line_ok = dict(line_old, value=980.0,
                       e2e_words_per_sec=505.0)         # -2%: inside
        line_bad = dict(line_old, value=500.0)          # -50%: regressed
        cap_old = put("cap_old.json", {"rc": 0, "tail": "",
                                       "parsed": line_old})
        raw_ok = put("ok.json", line_ok)
        raw_bad = put("bad.json", line_bad)
        assert main([cap_old, raw_ok]) == 0, "within-threshold must pass"
        assert main([cap_old, raw_bad]) == 1, "regression must fail"
        assert main([cap_old, raw_bad, "--threshold", "60"]) == 0, \
            "a loose threshold must pass"
        assert main([cap_old, raw_bad, "--watch",
                     "lda_doc_tokens_per_sec"]) == 0, \
            "--watch replaces the default list"
        # nested keys flatten (roofline rides along, unwatched)
        assert "w2v_roofline.mxu_util_pct" in load_metrics(raw_ok)
        # snapshot shape: counters/gauges/histograms flatten + compare
        snap = {"kind": SNAPSHOT_KIND,
                "counters": {"table.add.bytes{table=0:t}": 100.0},
                "gauges": {"w2v.words_per_sec": 10.0},
                "histograms": {"dispatch.seconds": {
                    "bounds": [1.0], "counts": [2, 0], "count": 2,
                    "sum": 0.5}}}
        snap2 = json.loads(json.dumps(snap))
        snap2["gauges"]["w2v.words_per_sec"] = 5.0
        s_old, s_new = put("s_old.json", snap), put("s_new.json", snap2)
        assert main([s_old, s_new]) == 0, "unwatched gauge drop passes"
        assert main([s_old, s_new, "--watch",
                     "gauge:w2v.words_per_sec"]) == 1, \
            "watched snapshot gauge regression must fail"
        m = load_metrics(s_old)
        assert m["hist_mean_s:dispatch.seconds"] == 0.25
        # client-pipeline micro-bench lines: the coalesced/cached
        # throughputs are watched by default
        cl_old = put("cl_old.json", {
            "metric": "client_kv_add_ops_per_sec", "value": 1000.0,
            "unit": "adds/s", "kv_add_ops_per_sec_coalesced": 1000.0,
            "kv_add_ops_per_sec_staged": 400.0,
            "kv_add_ops_per_sec_health": 380.0,
            "get_ops_per_sec_cached": 5000.0,
            "kv_apply_dispatches_coalesced": 8.0})
        cl_doc = json.loads(json.dumps(json.load(open(cl_old))))
        cl_doc["get_ops_per_sec_cached"] = 2000.0           # -60%
        cl_bad = put("cl_bad.json", cl_doc)
        assert main([cl_old, cl_old]) == 0, "identical client line passes"
        assert main([cl_old, cl_bad]) == 1, \
            "cached-get throughput regression must fail"
        # the health lane is watched: the audit creeping back onto the
        # hot path (throughput collapse) must fail the diff
        hl_doc = json.loads(json.dumps(json.load(open(cl_old))))
        hl_doc["kv_add_ops_per_sec_health"] = 80.0          # -79%
        hl_bad = put("hl_bad.json", hl_doc)
        assert main([cl_old, hl_bad]) == 1, \
            "health-lane throughput regression must fail"
        # table-kernel micro-bench lines: the Pallas probe/COO dispatch
        # rates are watched by default
        tk_old = put("tk_old.json", {
            "metric": "kv_probe_ops_per_sec_pallas", "value": 900.0,
            "unit": "dispatch/s", "kv_probe_ops_per_sec_pallas": 900.0,
            "kv_probe_ops_per_sec_xla": 500.0,
            "coo_scatter_ops_per_sec_pallas": 1200.0,
            "kv_probe_ops_per_sec_pallas_sharded": 700.0,
            "coo_scatter_ops_per_sec_pallas_sharded": 1100.0})
        tk_doc = json.loads(json.dumps(json.load(open(tk_old))))
        tk_doc["coo_scatter_ops_per_sec_pallas"] = 300.0    # -75%
        tk_bad = put("tk_bad.json", tk_doc)
        assert main([tk_old, tk_old]) == 0, "identical kernel line passes"
        assert main([tk_old, tk_bad]) == 1, \
            "pallas COO throughput regression must fail"
        # the sharded-lane twins are watched too
        sh_doc = json.loads(json.dumps(json.load(open(tk_old))))
        sh_doc["kv_probe_ops_per_sec_pallas_sharded"] = 100.0  # -86%
        sh_bad = put("sh_bad.json", sh_doc)
        assert main([tk_old, sh_bad]) == 1, \
            "sharded pallas probe regression must fail"
        # serving bench lines: serving_p99_ms is LOWER-is-better — a
        # latency RISE regresses, a drop (faster) always passes, and
        # the throughput key still regresses on a drop
        sv_old = put("sv_old.json", {
            "metric": "serving_ops_per_sec", "value": 800.0,
            "unit": "ops/s", "serving_ops_per_sec": 800.0,
            "serving_p50_ms": 1.0, "serving_p99_ms": 5.0,
            "serving_p999_ms": 9.0})
        sv_doc = json.loads(json.dumps(json.load(open(sv_old))))
        sv_doc["serving_p99_ms"] = 20.0                 # 4x slower
        sv_slow = put("sv_slow.json", sv_doc)
        sv_doc2 = json.loads(json.dumps(json.load(open(sv_old))))
        sv_doc2["serving_p99_ms"] = 2.0                 # faster
        sv_doc2["serving_p999_ms"] = 200.0              # unwatched rise
        sv_fast = put("sv_fast.json", sv_doc2)
        assert main([sv_old, sv_old]) == 0, "identical serving line"
        assert main([sv_old, sv_slow]) == 1, \
            "p99 latency rise must fail (lower is better)"
        assert main([sv_old, sv_fast]) == 0, \
            "a faster p99 must pass; unwatched p999 rides along"
        sv_doc3 = json.loads(json.dumps(json.load(open(sv_old))))
        sv_doc3["serving_ops_per_sec"] = 100.0          # -87%
        sv_doc3["value"] = 100.0
        assert main([sv_old, put("sv_thr.json", sv_doc3)]) == 1, \
            "serving throughput drop must fail"
        assert main([sv_old, sv_slow, "--watch-lower",
                     "serving_p999_ms"]) == 0, \
            "--watch-lower replaces the default list"
        assert main([sv_old, sv_fast, "--watch-lower",
                     "serving_p999_ms"]) == 1, \
            "explicit lower-is-better watch catches the p999 rise"
        # multi-process wire bench lines: wire_mb_per_sec is the
        # higher-is-better headline, serving_mp_p99_ms the
        # lower-is-better worker step tail — both watched by default
        mp_old = put("mp_old.json", {
            "metric": "wire_mb_per_sec", "value": 10.0,
            "unit": "MiB/s", "wire_mb_per_sec": 10.0,
            "serving_mp_p50_ms": 4.0, "serving_mp_p99_ms": 12.0,
            "wire_bytes_ratio": 9.5})
        mp_doc = json.loads(json.dumps(json.load(open(mp_old))))
        mp_doc["wire_mb_per_sec"] = 3.0                 # -70%
        mp_doc["value"] = 3.0
        mp_bad = put("mp_bad.json", mp_doc)
        assert main([mp_old, mp_old]) == 0, "identical mp line passes"
        assert main([mp_old, mp_bad]) == 1, \
            "wire throughput drop must fail"
        mp_doc2 = json.loads(json.dumps(json.load(open(mp_old))))
        mp_doc2["serving_mp_p99_ms"] = 60.0             # 5x slower
        mp_slow = put("mp_slow.json", mp_doc2)
        assert main([mp_old, mp_slow]) == 1, \
            "mp step-tail rise must fail (lower is better)"
        mp_doc3 = json.loads(json.dumps(json.load(open(mp_old))))
        mp_doc3["serving_mp_p99_ms"] = 6.0              # faster
        mp_doc3["wire_bytes_ratio"] = 4.1               # unwatched drop
        assert main([mp_old, put("mp_fast.json", mp_doc3)]) == 0, \
            "a faster mp tail passes; bytes ratio rides along unwatched"
        # ...the hot-path lanes: fused ops/s is higher-is-better, the
        # shm-ring round trip lower-is-better — both watched by default
        hp_old = put("hp_old.json", {
            "metric": "wire_mb_per_sec", "value": 10.0,
            "unit": "MiB/s", "wire_mb_per_sec": 10.0,
            "serving_mp_ops_per_sec": 5000.0,
            "serving_mp_ops_per_sec_unfused": 900.0,
            "serving_mp_fuse_ratio": 5.5,
            "shm_rtt_us": 300.0, "tcp_rtt_us": 450.0})
        hp_doc = json.loads(json.dumps(json.load(open(hp_old))))
        hp_doc["serving_mp_ops_per_sec"] = 1000.0       # -80%
        assert main([hp_old, put("hp_fuse.json", hp_doc)]) == 1, \
            "fused ops/s drop must fail (fusion drain regressed)"
        hp_doc2 = json.loads(json.dumps(json.load(open(hp_old))))
        hp_doc2["shm_rtt_us"] = 1200.0                  # 4x slower
        assert main([hp_old, put("hp_rtt.json", hp_doc2)]) == 1, \
            "shm round-trip rise must fail (lower is better)"
        hp_doc3 = json.loads(json.dumps(json.load(open(hp_old))))
        hp_doc3["shm_rtt_us"] = 150.0                   # faster
        hp_doc3["tcp_rtt_us"] = 900.0                   # unwatched rise
        assert main([hp_old, put("hp_fast.json", hp_doc3)]) == 0, \
            "a faster shm ring passes; tcp baseline rides unwatched"
        # flood lane lines: the protected-class p999 under a deliberate
        # flood is LOWER-is-better — admission control losing its grip
        # shows up as a tail rise, while the shed rate rides unwatched
        fl_old = put("fl_old.json", {
            "metric": "serving_protected_slo_margin", "value": 6.2,
            "unit": "x", "serving_protected_slo_margin": 6.2,
            "serving_protected_p999_ms": 40.0,
            "server_shed_per_sec": 900.0, "slo_violations": 0.0})
        fl_doc = json.loads(json.dumps(json.load(open(fl_old))))
        fl_doc["serving_protected_p999_ms"] = 160.0     # 4x slower
        fl_doc["serving_protected_slo_margin"] = 1.6
        fl_doc["value"] = 1.6
        assert main([fl_old, put("fl_slow.json", fl_doc)]) == 1, \
            "protected p999 rise under flood must fail (lower is better)"
        fl_doc2 = json.loads(json.dumps(json.load(open(fl_old))))
        fl_doc2["serving_protected_p999_ms"] = 10.0     # faster
        fl_doc2["serving_protected_slo_margin"] = 25.0
        fl_doc2["value"] = 25.0
        fl_doc2["server_shed_per_sec"] = 100.0          # unwatched drop
        assert main([fl_old, put("fl_fast.json", fl_doc2)]) == 0, \
            "a faster protected tail passes; shed rate rides unwatched"
        # fleet lane lines: the sharded-fleet aggregate read rate and
        # the scaling efficiency are both higher-is-better — either
        # collapsing means the partitioned serving path regressed,
        # while the single-server baseline rate rides unwatched
        fe_old = put("fe_old.json", {
            "metric": "serving_fleet_ops_per_sec", "value": 400.0,
            "unit": "ops/s", "serving_fleet_ops_per_sec": 400.0,
            "serving_fleet_single_ops_per_sec": 200.0,
            "fleet_speedup": 2.0, "fleet_scaling_efficiency": 1.0,
            "fleet_servers": 2.0})
        fe_doc = json.loads(json.dumps(json.load(open(fe_old))))
        fe_doc["serving_fleet_ops_per_sec"] = 120.0     # -70%
        fe_doc["value"] = 120.0
        assert main([fe_old, put("fe_slow.json", fe_doc)]) == 1, \
            "fleet aggregate read-rate drop must fail"
        fe_doc2 = json.loads(json.dumps(json.load(open(fe_old))))
        fe_doc2["fleet_scaling_efficiency"] = 0.4       # -60%
        fe_doc2["fleet_speedup"] = 0.8
        assert main([fe_old, put("fe_eff.json", fe_doc2)]) == 1, \
            "fleet scaling-efficiency collapse must fail"
        fe_doc3 = json.loads(json.dumps(json.load(open(fe_old))))
        fe_doc3["serving_fleet_single_ops_per_sec"] = 60.0  # unwatched
        assert main([fe_old, put("fe_base.json", fe_doc3)]) == 0, \
            "the single-server baseline rides along unwatched"
        # traced ops lane: the tracing-on throughput is watched — a
        # collapse means the trace context stopped being cheap, while
        # the untraced twin and the ratio ride along unwatched
        tr_old = put("tr_old.json", {
            "metric": "wire_mb_per_sec", "value": 10.0,
            "unit": "MiB/s", "wire_mb_per_sec": 10.0,
            "serving_mp_traced_ops_per_sec": 4800.0,
            "serving_mp_untraced_ops_per_sec": 5000.0,
            "serving_mp_trace_ratio": 0.96})
        tr_doc = json.loads(json.dumps(json.load(open(tr_old))))
        tr_doc["serving_mp_traced_ops_per_sec"] = 1400.0    # -70%
        tr_doc["serving_mp_trace_ratio"] = 0.28
        assert main([tr_old, put("tr_bad.json", tr_doc)]) == 1, \
            "traced ops/s drop must fail (tracing got expensive)"
        tr_doc2 = json.loads(json.dumps(json.load(open(tr_old))))
        tr_doc2["serving_mp_untraced_ops_per_sec"] = 1000.0  # unwatched
        assert main([tr_old, put("tr_base.json", tr_doc2)]) == 0, \
            "the untraced twin rides along unwatched"
        # autotune lane: the converged protected throughput is watched
        # — the closed loop failing to recover the operating point
        # shows up as a drop, while the mistuned floor and the decision
        # count ride along unwatched
        at_old = put("at_old.json", {
            "metric": "autotune_converged_ops_per_sec", "value": 130.0,
            "unit": "ops/s", "autotune_converged_ops_per_sec": 130.0,
            "autotune_handtuned_ops_per_sec": 125.0,
            "autotune_mistuned_ops_per_sec": 2.0,
            "autotune_frac_of_handtuned": 1.04,
            "autotune_decisions": 20.0})
        at_doc = json.loads(json.dumps(json.load(open(at_old))))
        at_doc["autotune_converged_ops_per_sec"] = 40.0     # -69%
        at_doc["value"] = 40.0
        assert main([at_old, put("at_bad.json", at_doc)]) == 1, \
            "converged-throughput drop must fail (loop stopped tuning)"
        at_doc2 = json.loads(json.dumps(json.load(open(at_old))))
        at_doc2["autotune_mistuned_ops_per_sec"] = 0.5      # unwatched
        at_doc2["autotune_decisions"] = 35.0
        assert main([at_old, put("at_base.json", at_doc2)]) == 0, \
            "the mistuned floor and decision count ride unwatched"
        # attribution lane: the attributed ops/s is watched — a
        # collapse means the accounting sketches got expensive, while
        # the unattributed twin and the ratio ride along unwatched
        ab_old = put("ab_old.json", {
            "metric": "wire_mb_per_sec", "value": 10.0,
            "unit": "MiB/s", "wire_mb_per_sec": 10.0,
            "serving_mp_attributed_ops_per_sec": 4900.0,
            "serving_mp_unattributed_ops_per_sec": 5000.0,
            "serving_mp_attr_ratio": 0.98})
        ab_doc = json.loads(json.dumps(json.load(open(ab_old))))
        ab_doc["serving_mp_attributed_ops_per_sec"] = 1500.0  # -69%
        ab_doc["serving_mp_attr_ratio"] = 0.3
        assert main([ab_old, put("ab_bad.json", ab_doc)]) == 1, \
            "attributed ops/s drop must fail (accounting got expensive)"
        ab_doc2 = json.loads(json.dumps(json.load(open(ab_old))))
        ab_doc2["serving_mp_unattributed_ops_per_sec"] = 900.0
        assert main([ab_old, put("ab_base.json", ab_doc2)]) == 0, \
            "the unattributed twin rides along unwatched"
        # replica lane: the follower-routed read rate and the
        # delta-stream bytes economy are both watched — either
        # collapsing means the replication plane regressed, while the
        # primary-pinned baseline and the speedup ride along unwatched
        rp_old = put("rp_old.json", {
            "metric": "replica_read_ops_per_sec", "value": 500.0,
            "unit": "ops/s", "replica_read_ops_per_sec": 500.0,
            "replica_baseline_ops_per_sec": 250.0,
            "replica_read_speedup": 2.0,
            "replication_bytes_ratio": 28.0})
        rp_doc = json.loads(json.dumps(json.load(open(rp_old))))
        rp_doc["replica_read_ops_per_sec"] = 150.0      # -70%
        rp_doc["value"] = 150.0
        assert main([rp_old, put("rp_bad.json", rp_doc)]) == 1, \
            "follower read-rate drop must fail (replica routing broke)"
        rp_doc2 = json.loads(json.dumps(json.load(open(rp_old))))
        rp_doc2["replication_bytes_ratio"] = 1.1        # re-encoding
        assert main([rp_old, put("rp_bytes.json", rp_doc2)]) == 1, \
            "bytes-ratio collapse must fail (tap re-encoding frames)"
        rp_doc3 = json.loads(json.dumps(json.load(open(rp_old))))
        rp_doc3["replica_baseline_ops_per_sec"] = 80.0  # unwatched
        rp_doc3["replica_read_speedup"] = 6.2
        assert main([rp_old, put("rp_base.json", rp_doc3)]) == 0, \
            "the primary-pinned baseline rides along unwatched"
        # reshard lane: migration throughput is watched higher, the
        # under-storm stall tail lower — either regressing means live
        # resharding got less live, while the moved-bytes accounting
        # and the quiet baseline ride along unwatched
        rs_old = put("rs_old.json", {
            "metric": "reshard_moved_mb_per_sec", "value": 40.0,
            "unit": "MB/s", "reshard_moved_mb_per_sec": 40.0,
            "reshard_p999_stall_ms": 20.0,
            "reshard_moved_bytes": 527484.0,
            "reshard_quiet_p99_ms": 4.0})
        rs_doc = json.loads(json.dumps(json.load(open(rs_old))))
        rs_doc["reshard_moved_mb_per_sec"] = 10.0       # -75%
        rs_doc["value"] = 10.0
        assert main([rs_old, put("rs_slow.json", rs_doc)]) == 1, \
            "migration throughput drop must fail (stream got slower)"
        rs_doc2 = json.loads(json.dumps(json.load(open(rs_old))))
        rs_doc2["reshard_p999_stall_ms"] = 400.0        # 20x stall
        assert main([rs_old, put("rs_stall.json", rs_doc2)]) == 1, \
            "under-reshard stall-tail rise must fail (not live anymore)"
        rs_doc3 = json.loads(json.dumps(json.load(open(rs_old))))
        rs_doc3["reshard_moved_bytes"] = 1000.0         # unwatched
        rs_doc3["reshard_quiet_p99_ms"] = 9.0
        assert main([rs_old, put("rs_ride.json", rs_doc3)]) == 0, \
            "moved-bytes accounting rides along unwatched"
        # windowed-series docs (/vars?window= captures): rates,
        # gauges, and windowed quantiles flatten with their own
        # prefixes and diff like any snapshot
        sr = {"kind": SERIES_KIND, "window": 30.0,
              "rates": {"server.ops{server=a}": 120.0},
              "deltas": {"server.ops{server=a}": 3600.0},
              "gauges": {"queue.depth{worker=0}": 4.0},
              "histograms": {"server.latency.seconds": {
                  "bounds": [0.001, 0.01], "counts": [50, 5, 0],
                  "count": 55, "sum": 0.2, "p50": 0.0006,
                  "p99": 0.009, "p999": None}}}
        sr2 = json.loads(json.dumps(sr))
        sr2["rates"]["server.ops{server=a}"] = 30.0        # -75%
        sr_old = put("sr_old.json", sr)
        sr_new = put("sr_new.json", sr2)
        m = load_metrics(sr_old)
        assert m["rate:server.ops{server=a}"] == 120.0
        assert m["win_p99_s:server.latency.seconds"] == 0.009
        assert "win_p999_s:server.latency.seconds" not in m, \
            "a None quantile must not flatten"
        assert main([sr_old, sr_new]) == 0, \
            "unwatched windowed rate drop rides along"
        assert main([sr_old, sr_new, "--watch",
                     "rate:server.ops{server=a}"]) == 1, \
            "watched windowed rate regression must fail"
        assert main([sr_old, sr_new, "--watch-lower",
                     "win_p99_s:server.latency.seconds"]) == 0, \
            "an unchanged windowed p99 passes a lower-is-better watch"
        # unusable inputs exit 2, not a traceback
        hung = put("hung.json", {"rc": 124, "tail": "...", "parsed": None})
        assert main([hung, raw_ok]) == 2, "no parsed line -> exit 2"
    print("bench_diff selftest: ok")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
