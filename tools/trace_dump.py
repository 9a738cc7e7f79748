"""Convert span-tracer JSONL dumps to Chrome trace_event JSON.

The span tracer (paddle_tpu/obs/trace.py) archives spans as JSON-lines —
one span per line: {"seq", "name", "track", "ts", "dur", "attrs"?,
"instant"?}.  This tool turns that into the Chrome trace_event format
that Perfetto (https://ui.perfetto.dev) and chrome://tracing load
directly: every track becomes a named thread lane, complete spans render
as bars, instants (preempt/done/cancelled/deadline) as markers.

  # server side: record a serving run's request lifecycles
  python tools/serve.py ... --trace-out spans.jsonl     # drain writes it
  # convert + eyeball
  python tools/trace_dump.py spans.jsonl -o trace.json
  python tools/trace_dump.py spans.jsonl --summary      # per-name table,
                                  # per-lane counts, flight- and compile-lane breakdowns

Distributed traces (docs/observability.md "Distributed tracing"): a
fleet request crosses router and replica processes — and a training
window crosses trainer and pserver-shard processes — each with its own
span ring and its own perf_counter epoch.  `--merge` stitches several
span FILES into ONE Chrome trace with a named process track group per
file (a file's first line may be a `{"meta": {"process": ..., an
"offset_s"}}` identity record — serve.py/fleet_router.py/pserver.py/
train_dist.py --trace-out all write one); `--pull HOST:PORT`
(repeatable) collects spans LIVE over the `trace` RPC instead —
replica, router, or pserver shard — measuring each process's clock
offset by ping-RTT midpointing so the tracks align:

  python tools/trace_dump.py --pull 127.0.0.1:8440 \\
      --pull 127.0.0.1:8431 --pull 127.0.0.1:8432 -o fleet.trace.json

  # training fleet: pull both pserver shards live, merge the trainers'
  # --trace-out files — one Perfetto trace, role-named tracks
  python tools/trace_dump.py --pull 127.0.0.1:8571 \\
      --pull 127.0.0.1:8572 --merge t0.jsonl t1.jsonl -o dist.trace.json

Exit codes: 0 ok, 2 on unreadable/empty input or an unreachable --pull.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from paddle_tpu.obs.trace import merge_chrome, spans_to_chrome  # noqa: E402


def load_trace_file(path: str) -> tuple[dict, list[dict]]:
    """Read a JSONL span file as (meta, spans).  `meta` is the optional
    leading identity record ({"process": ..., "offset_s": ...}; {} when
    the file has none — plain Tracer.export_jsonl output)."""
    meta: dict = {}
    spans = []
    with open(path) as f:
        for i, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError as e:
                raise ValueError(f"{path}:{i}: not JSON: {e}") from e
            if isinstance(rec, dict) and "meta" in rec and \
                    "name" not in rec:
                meta = rec["meta"] if isinstance(rec["meta"], dict) else {}
                continue
            if not isinstance(rec, dict) or "name" not in rec \
                    or "ts" not in rec:
                raise ValueError(f"{path}:{i}: not a span record "
                                 f"(need name/ts fields): {rec!r}")
            if not rec.get("instant") and "dur" not in rec:
                raise ValueError(f"{path}:{i}: complete span without a "
                                 f"dur field: {rec!r}")
            spans.append(rec)
    return meta, spans


def load_spans(path: str) -> list[dict]:
    """Read a JSONL span file; skips blank lines (and a meta identity
    line), raises on garbage."""
    return load_trace_file(path)[1]


def pull_source(addr: str, timeout: float = 60.0) -> dict:
    """One live `trace` RPC pull -> a merge_chrome() source: spans +
    process identity + the ping-RTT-measured clock offset mapping that
    process's perf_counter timebase onto this tool's."""
    from paddle_tpu.serving.client import ServingClient

    host, _, port = addr.rpartition(":")
    with ServingClient(host or "127.0.0.1", int(port),
                       timeout=timeout) as c:
        msg = c.trace()
    return {"spans": msg.get("spans") or [],
            "process": msg.get("process"),
            "offset_s": msg.get("offset_s", 0.0),
            "recorded": msg.get("recorded"),
            "dropped": msg.get("dropped")}


def summarize(spans: list[dict]) -> str:
    """Per-name span table, per-lane counts, and a compile-lane
    breakdown (signatures × compile time) — a recompile storm is visible
    from the trace file alone, no Perfetto needed."""
    agg: dict[str, list] = {}
    for s in spans:
        a = agg.setdefault(s["name"], [0, 0.0, 0.0])
        a[0] += 1
        a[1] += float(s.get("dur", 0.0))
        a[2] = max(a[2], float(s.get("dur", 0.0)))
    lines = [f"{'span':<16} {'count':>7} {'total_ms':>10} {'max_ms':>9}"]
    for name in sorted(agg, key=lambda n: -agg[n][1]):
        c, tot, mx = agg[name]
        lines.append(f"{name:<16} {c:>7} {tot * 1e3:>10.2f} {mx * 1e3:>9.2f}")

    # per-lane counts: request lanes collapse to one `req:*` row so a
    # thousand-request trace still summarizes in a screenful
    lanes: dict[str, int] = {}
    for s in spans:
        track = s.get("track", "main")
        if track.startswith("req:"):
            track = "req:*"
        lanes[track] = lanes.get(track, 0) + 1
    lines.append("")
    lines.append(f"{'lane':<16} {'spans':>7}")
    for track in sorted(lanes, key=lambda t: -lanes[t]):
        lines.append(f"{track:<16} {lanes[track]:>7}")

    lines.append(f"{len(spans)} spans on {len(lanes)} lanes")
    for part in (flight_breakdown(spans), compile_breakdown(spans)):
        if part:
            lines.append("")
            lines.append(part)
    return "\n".join(lines)


def flight_breakdown(spans: list[dict]) -> str:
    """The flight lane, by step kind: a compiled step from its launch to
    its tokens on the host (`pt.step.flight`, docs/observability.md "The
    step clock"), how many began before the one before had landed, and
    how many pair with a `pt.step.readback` of the same `step=`.  Empty
    string when the trace holds no flight."""
    flights = sorted((s for s in spans if s.get("track") == "flight"),
                     key=lambda s: s["ts"])
    if not flights:
        return ""
    landed = {(s.get("attrs") or {}).get("step") for s in spans
              if s["name"] == "pt.step.readback"}
    kinds: dict[str, list] = {}      # kind -> [n, seconds, max, overlapped]
    paired, prev_end = 0, None
    for f in flights:
        attrs = f.get("attrs") or {}
        dur = float(f.get("dur", 0.0))
        a = kinds.setdefault(str(attrs.get("kind", "?")), [0, 0.0, 0.0, 0])
        a[0] += 1
        a[1] += dur
        a[2] = max(a[2], dur)
        if prev_end is not None and f["ts"] < prev_end:
            a[3] += 1
        prev_end = f["ts"] + dur
        paired += attrs.get("step") in landed
    lines = [f"flight lane ({len(flights)} steps, {paired} paired with "
             f"their read-back by step=):",
             f"  {'kind':<8} {'steps':>7} {'mean_ms':>9} {'max_ms':>9} "
             f"{'overlapped':>10}"]
    for kind in sorted(kinds, key=lambda k: -kinds[k][1]):
        n, tot, mx, over = kinds[kind]
        lines.append(f"  {kind:<8} {n:>7} {tot / n * 1e3:>9.2f} "
                     f"{mx * 1e3:>9.2f} {over:>10}")
    return "\n".join(lines)


def compile_breakdown(spans: list[dict]) -> str:
    """The compile lane, by site: compiles × distinct signatures × wall
    time, plus any recompile-storm markers.  Empty string when the trace
    holds no compile-lane spans (tracing predates the compile watcher,
    or nothing compiled while the ring retained)."""
    sites: dict[str, list] = {}      # site -> [compiles, sigs, seconds]
    storms: dict[str, int] = {}
    for s in spans:
        if s.get("track") != "compile":
            continue
        attrs = s.get("attrs") or {}
        if s.get("instant"):
            if s["name"] == "recompile_storm":
                site = str(attrs.get("site", "?"))
                storms[site] = storms.get(site, 0) + 1
            continue
        a = sites.setdefault(s["name"], [0, set(), 0.0])
        a[0] += 1
        a[1].add(attrs.get("sig", a[0]))   # no sig recorded: count as new
        a[2] += float(s.get("dur", 0.0))
    if not sites and not storms:
        return ""
    lines = [f"compile lane ({sum(a[0] for a in sites.values())} compiles):",
             f"  {'site':<24} {'compiles':>8} {'sigs':>5} {'total_ms':>10}"]
    for site in sorted(sites, key=lambda n: -sites[n][2]):
        c, sigs, tot = sites[site]
        storm = (f"  STORMS={storms.pop(site)}" if site in storms else "")
        lines.append(f"  {site:<24} {c:>8} {len(sigs):>5} "
                     f"{tot * 1e3:>10.2f}{storm}")
    for site, n in sorted(storms.items()):  # storm without retained spans
        lines.append(f"  {site:<24} {'?':>8} {'?':>5} {'?':>10}  STORMS={n}")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("jsonl", nargs="*",
                    help="span JSONL file(s) (tools/serve.py --trace-out, "
                         "or Tracer.export_jsonl); several need --merge")
    ap.add_argument("-o", "--out", default="",
                    help="write Chrome trace_event JSON here "
                         "(default: <input>.trace.json)")
    ap.add_argument("--summary", action="store_true",
                    help="print per-span-name and per-lane tables (plus a "
                         "compile-lane breakdown when present) instead of "
                         "writing")
    ap.add_argument("--merge", action="store_true",
                    help="stitch several span files (and any --pull "
                         "sources) into ONE Chrome trace with a process "
                         "track group per source, applying each file's "
                         "meta offset_s")
    ap.add_argument("--pull", action="append", default=[],
                    metavar="HOST:PORT",
                    help="collect spans live over the `trace` RPC from a "
                         "replica server or fleet router (repeatable; "
                         "clock offset measured per pull via ping RTT); "
                         "implies --merge")
    args = ap.parse_args(argv)

    if len(args.jsonl) > 1 and not (args.merge or args.pull):
        print("error: several input files need --merge (one Chrome trace "
              "with a process group per file)", file=sys.stderr)
        return 2
    if not args.jsonl and not args.pull:
        ap.error("need a span JSONL file or --pull HOST:PORT")

    sources = []
    try:
        for path in args.jsonl:
            meta, spans = load_trace_file(path)
            sources.append({"spans": spans,
                            "process": meta.get("process"),
                            "offset_s": float(meta.get("offset_s", 0.0)),
                            "label": os.path.basename(path)
                            if not meta.get("process") else None})
        for addr in args.pull:
            sources.append(pull_source(addr))
    except (OSError, ValueError, ConnectionError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    all_spans = [s for src in sources for s in src["spans"]]
    if not all_spans:
        print(f"error: {', '.join(args.jsonl + args.pull)} holds no spans "
              f"(tracing never enabled, or the ring was cleared)",
              file=sys.stderr)
        return 2

    if args.summary:
        print(summarize(all_spans))
        return 0

    if args.merge or args.pull or len(sources) > 1:
        out = args.out or ((args.jsonl[0] if args.jsonl
                            else "fleet") + ".trace.json")
        with open(out, "w") as f:
            json.dump(merge_chrome(sources), f)
        names = [(src.get("process") or {}).get("role") or
                 src.get("label") or "?" for src in sources]
        print(f"wrote {out}: {len(all_spans)} spans across "
              f"{len(sources)} processes ({', '.join(names)}) — load in "
              f"https://ui.perfetto.dev or chrome://tracing")
        return 0

    out = args.out or args.jsonl[0] + ".trace.json"
    with open(out, "w") as f:
        json.dump(spans_to_chrome(all_spans), f)
    print(f"wrote {out}: {len(all_spans)} spans — load in "
          f"https://ui.perfetto.dev or chrome://tracing")
    return 0


if __name__ == "__main__":
    sys.exit(main())
