"""Pretty-print a flight-recorder postmortem bundle.

A bundle is the atomic directory the serving front end freezes on pump
death, on the watchdog-wedge threshold, or on an operator `dump` frame
(obs/flight.py; armed via `tools/serve.py --postmortem-dir`) — or the
parameter server freezes on an update-thread wedge / `dump` frame
(tools/pserver.py --snapshot-dir).  The renderer is role-aware: a
pserver bundle (engine.json role "pserver") shows the membership table,
update-thread state and window/commit counters instead of the serving
slots/queue layout:

  python tools/postmortem.py runs/postmortems/postmortem-20260803-101500-123/
  python tools/postmortem.py ... --events 50      # more of the event tail
  python tools/postmortem.py ... --json           # machine-readable dump

Prints: the meta header (reason, when, versions, the error if one was
captured), the engine snapshot (slots, queue, page occupancy), compile
and HBM accounting, headline metrics, and the tail of the structured
event ring.  The bundle's spans.jsonl is tools/trace_dump.py food:

  python tools/trace_dump.py <bundle>/spans.jsonl --summary

Exit codes: 0 ok, 2 on a missing/incomplete bundle (e.g. a `.tmp`
straggler from a dump that crashed mid-write).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from paddle_tpu.obs.flight import load_bundle  # noqa: E402


def _fmt_bytes(n) -> str:
    if not isinstance(n, (int, float)):
        return str(n)
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(n) < 1024 or unit == "GiB":
            return f"{n:.1f}{unit}" if unit != "B" else f"{int(n)}B"
        n /= 1024
    return f"{n:.1f}GiB"


#: eight-level sparkline ramp (tools/obs_top.py's, newest-right)
_SPARK = "▁▂▃▄▅▆▇█"


def _sparkline(values, width: int = 24) -> str:
    vals = [float(v) for v in values][-width:]
    if not vals:
        return ""
    lo, hi = min(vals), max(vals)
    if hi <= lo:
        return _SPARK[0] * len(vals)
    return "".join(_SPARK[min(len(_SPARK) - 1,
                              int((v - lo) / (hi - lo)
                                  * (len(_SPARK) - 1)))] for v in vals)


def _render_history(bundle: dict) -> list:
    """The health-plane section: ring accounting from history.json plus —
    for an SLO-triggered bundle — each firing objective's offending
    series as a sparkline (the slo_fire flight events name their series
    keys, frozen BEFORE anything died)."""
    hist = bundle.get("history") or {}
    series = hist.get("series") or {}
    if not series:
        return []
    out = [f"history: {len(series)} series, "
           f"{hist.get('samples_taken')} samples at "
           f"{hist.get('resolution_s')}s resolution — history.json"]
    for ev in bundle.get("events") or []:
        if ev.get("kind") != "slo_fire":
            continue
        d = ev.get("data") or {}
        out.append(f"  SLO {d.get('slo', '?')}: value={d.get('value')} "
                   f"{d.get('op', '?')} objective={d.get('objective')}  "
                   f"burn short={d.get('short_burn')} "
                   f"long={d.get('long_burn')}")
        for key in str(d.get("series") or "").split(","):
            ser = series.get(key)
            pts = (ser or {}).get("points") or []
            if not pts:
                continue
            vals = [v for _t, v in pts]
            out.append(f"    {key}")
            out.append(f"      {_sparkline(vals)}  "
                       f"min={min(vals):g} max={max(vals):g} "
                       f"last={vals[-1]:g} n={len(vals)}")
    return out


def _render_pserver(eng: dict) -> list:
    """The pserver half of render(): membership table, update-thread
    state, window/commit/snapshot counters — the engine.json a
    parameter-server bundle carries is its stats frame."""
    out = [f"pserver: shard {eng.get('shard')}/{eng.get('n_shards')} "
           f"mode={eng.get('mode')} "
           f"{'initialized' if eng.get('initialized') else 'UNINITIALIZED'}",
           f"  window={eng.get('window')} version={eng.get('version')} "
           f"pass={eng.get('pass_id')}  blocks={eng.get('blocks')} "
           f"({_fmt_bytes(eng.get('block_bytes'))})"]
    lag = eng.get("update_lag_s")
    alive = eng.get("update_alive")
    state = "alive" if alive else "DEAD"
    if alive and isinstance(lag, (int, float)) and \
            isinstance(eng.get("wedge_threshold_s"), (int, float)) and \
            lag > eng["wedge_threshold_s"]:
        state = "WEDGED"
    out.append(f"  update thread: {state} lag={lag}s "
               f"(wedge threshold {eng.get('wedge_threshold_s')}s)")
    if eng.get("update_error"):
        out.append(f"    error: {eng['update_error']}")
    out.append(f"  pending: {eng.get('pending_grads')} grads, "
               f"{eng.get('pending_barriers')} barriers, "
               f"{eng.get('pending_pass_barriers')} pass barriers"
               + ("  DRAINING" if eng.get("draining") else ""))
    out.append(f"  last window skew: {eng.get('last_skew_ms')}ms "
               f"(straggler threshold {eng.get('straggler_ms')}ms)")
    trainers = eng.get("trainers") or []
    out.append(f"  trainers: {eng.get('trainers_active')} active, "
               f"{eng.get('trainers_draining')} draining")
    for t in trainers:
        out.append(f"    rank {t.get('rank')}  {t.get('tid'):<6} "
                   f"{t.get('state'):<9} grads={t.get('grads_sent')} "
                   f"windows={t.get('windows_joined')}")
    snap = eng.get("snapshot") or {}
    if snap.get("dir"):
        out.append(f"  snapshots: {snap.get('written')} written "
                   f"(every {snap.get('every')} commits) "
                   f"last={snap.get('last_path')}"
                   + ("  IN PROGRESS" if snap.get("in_progress") else ""))
    return out


def render(bundle: dict, n_events: int = 20) -> str:
    meta = bundle["meta"]
    out = [f"postmortem bundle: {bundle['path']}",
           f"  reason:   {meta.get('reason', '?')}",
           f"  when:     {meta.get('ts_iso', '?')} "
           f"(pid {meta.get('pid', '?')} on {meta.get('host', '?')})",
           f"  versions: " + " ".join(
               f"{k}={v}" for k, v in meta.get("versions", {}).items())]
    if meta.get("error"):
        first = str(meta["error"]).strip().splitlines()
        out.append(f"  error:    {first[0]}")
        for line in first[1:6]:
            out.append(f"            {line}")
        if len(first) > 6:
            out.append(f"            ... ({len(first) - 6} more lines)")

    eng = bundle.get("engine") or {}
    if eng.get("role") == "pserver" and "snapshot_error" not in eng:
        out.extend(_render_pserver(eng))
    elif eng and "snapshot_error" not in eng:
        slots = eng.get("slots") or []
        live = [s for s in slots if isinstance(slots, list) and s]
        out.append("engine:")
        out.append(f"  steps={eng.get('n_decode_steps')} "
                   f"tokens={eng.get('tokens_generated')} "
                   f"preempts={eng.get('n_preemptions')} "
                   f"cancelled={eng.get('n_cancelled')} "
                   f"expired={eng.get('n_expired')}"
                   + ("  (a step in flight: slots are one step behind "
                      "the device)" if eng.get("step_in_flight") else ""))
        if isinstance(slots, list):
            out.append(f"  slots: {len(live)}/{len(slots)} occupied")
            for s in live:
                out.append(f"    [{s['slot']}] {s['req_id']} "
                           f"pos={s['pos']} gen={s['generated']}"
                           f"/{s['max_new']}")
        q = eng.get("queued")
        if isinstance(q, list):
            out.append(f"  queued ({len(q)}): "
                       + (", ".join(map(str, q[:8]))
                          + (" …" if len(q) > 8 else "") if q else "-"))
        out.append(f"  pages: {eng.get('pages_in_use')} in use, "
                   f"{eng.get('free_pages')} free of "
                   f"{eng.get('num_pages')} (page_size "
                   f"{eng.get('page_size')})")
        cw = eng.get("compile_watch") or {}
        if cw:
            out.append("  compile watch:")
            for site, st in cw.items():
                storm = (f"  STORMS={st['storms']}" if st.get("storms")
                         else "")
                out.append(f"    {site:<24} {st['compiles']:>3} compiles "
                           f"{st['signatures']:>3} sigs "
                           f"{st['seconds'] * 1e3:>9.1f}ms{storm}")
        hbm = eng.get("hbm") or {}
        if hbm:
            parts = []
            for k in ("kv_pool_bytes", "param_bytes", "live_array_bytes"):
                if k in hbm:
                    parts.append(f"{k.replace('_bytes', '')}="
                                 f"{_fmt_bytes(hbm[k])}")
            dm = hbm.get("device_memory_stats") or {}
            if "bytes_in_use" in dm:
                parts.append(f"device={_fmt_bytes(dm['bytes_in_use'])}"
                             + (f"/{_fmt_bytes(dm['bytes_limit'])}"
                                if "bytes_limit" in dm else ""))
            if parts:
                out.append("  hbm: " + " ".join(parts))

    metrics = bundle.get("metrics") or {}
    if metrics and "snapshot_error" not in metrics:
        heads = [k for k in ("serving_requests_accepted_total",
                             "serving_overload_total", "pump_alive",
                             "pump_last_step_age_s",
                             "trace_spans_recorded_total",
                             "flight_events_recorded_total")
                 if k in metrics]
        if heads:
            out.append("metrics: " + "  ".join(
                f"{k}={metrics[k]:g}" for k in heads)
                + f"  ({len(metrics)} total — metrics.json)")

    out.extend(_render_history(bundle))

    events = bundle.get("events") or []
    out.append(f"events: {len(events)} retained "
               f"({meta.get('events_dropped', 0)} dropped); last "
               f"{min(n_events, len(events))}:")
    t_ref = meta.get("ts", time.time())
    for ev in events[-n_events:]:
        dt = ev.get("ts", t_ref) - t_ref
        data = ev.get("data") or {}
        kv = " ".join(f"{k}={v}" for k, v in data.items())
        out.append(f"  {dt:>8.3f}s  {ev.get('kind', '?'):<16} {kv}")
    spans = bundle.get("spans") or []
    out.append(f"spans: {len(spans)} in spans.jsonl — "
               f"`python tools/trace_dump.py {bundle['path']}/spans.jsonl "
               f"--summary`")
    return "\n".join(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("bundle", help="postmortem-<ts>-<pid> directory")
    ap.add_argument("--events", type=int, default=20,
                    help="how many tail events to print (default 20)")
    ap.add_argument("--json", action="store_true",
                    help="print the whole bundle as one JSON object")
    args = ap.parse_args(argv)

    try:
        bundle = load_bundle(args.bundle)
    except (OSError, ValueError, json.JSONDecodeError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    if args.json:
        print(json.dumps(bundle, indent=2, default=str))
        return 0
    print(render(bundle, n_events=args.events))
    return 0


if __name__ == "__main__":
    sys.exit(main())
