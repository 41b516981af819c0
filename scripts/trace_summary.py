"""Summarize training telemetry: XLA traces and Recorder JSONL files.

Two subcommands:

  xplane (default)   top ops by device time from the xplane protobuf
                     that `jax.profiler.trace(dir)` writes (normally
                     needs TensorBoard's profile plugin):

        python scripts/trace_summary.py /tmp/tpu_trace [top_n]
        python scripts/trace_summary.py xplane /tmp/tpu_trace [top_n]

  steps              step-time breakdown from an observability
                     JsonlSink telemetry file: per-span mean/total
                     milliseconds and share of step time, the
                     checkpoint blocking-copy vs async-write split,
                     plus scalar summaries (loss, grad-norm,
                     throughput) and the dataloader/collective
                     counters:

        python scripts/trace_summary.py steps /tmp/telemetry.jsonl [last_n]

  health             health events and crash flight-recorder dumps as
                     a table (condition, step, offending metric, action
                     taken).  Accepts telemetry JSONL files,
                     flight_<ts>.json dumps, or directories (scanned
                     for both):

        python scripts/trace_summary.py health /tmp/telemetry.jsonl
        python scripts/trace_summary.py health /tmp/flight_dir

  profile            cost/memory attribution from the observability.
                     profile capture: compiled FLOPs and peak-HBM per
                     train step against the device peaks, measured MFU
                     and HBM-bandwidth utilization over the step
                     records, and per-bucket serving compute cost:

        python scripts/trace_summary.py profile /tmp/telemetry.jsonl

  input              input-pipeline breakdown from the data/* telemetry
                     of the sharded streaming loader: stall fraction
                     (consumer blocked on an empty staging queue vs
                     step time), decode throughput across the worker
                     pool, h2d wire bytes per step, records read,
                     salvage-resync bytes, and the staging queue depth
                     — the one-command view of "is input feeding the
                     roofline":

        python scripts/trace_summary.py input /tmp/telemetry.jsonl [last_n]

  comm               per-step collective volume and count, pre/post
                     compression, from the trace-time collective
                     accounting gauges: per-op raw vs on-the-wire
                     bytes (the fp16/bf16 compression ratio), the
                     gradient-bucket count, cumulative exchange
                     totals, and the sharding-coverage counters
                     (comm/unsharded_leaves) — the one-command view of
                     a bucketing/compression/zero1 delta:

        python scripts/trace_summary.py comm /tmp/telemetry.jsonl [last_n]

  embedding          sharded-embedding lookup economics from the
                     embedding/* family: exchange wire bytes and id
                     slots per step, host-dedup reduction (unique vs
                     raw ids), bucket-ladder padding waste, and the
                     touched-rows fraction sparse gradient application
                     pays vs a dense step:

        python scripts/trace_summary.py embedding /tmp/telemetry.jsonl [last_n]

  serving            per-replica health transitions from a ReplicaSet's
                     telemetry JSONL: one chronological
                     eject → probe → readmit / canary_stage →
                     promote/reject / brownout enter/exit /
                     stream:published/rejected table, plus the
                     per-replica transition sequence and the final
                     resilience counters — and, when decode-engine
                     telemetry is present, the per-token SLO table
                     (TTFT vs inter-token split) with the
                     slot-occupancy/KV-fill timeline:

        python scripts/trace_summary.py serving /tmp/serving.jsonl

  fleet              per-job fleet/elastic event timelines from one or
                     more telemetry JSONL streams (each job usually has
                     its own recorder/sink): one chronological
                     admit → place → preempt/displace → shrink →
                     regrow → complete table across the pool, plus the
                     per-job event sequence — the one-command view of
                     "what did the scheduler do to my job":

        python scripts/trace_summary.py fleet /tmp/fleet.jsonl /tmp/job_*.jsonl

  slo                service-level-objective verdicts from the SLO
                     engine's telemetry: the objective table
                     (compliance %, error budget remaining, fast/slow
                     burn rates, breach state) from the latest
                     ``slo_summary`` record, plus the chronological
                     breach/recovery timeline from ``slo_event``
                     records — the one-command answer to "did we blow
                     the TTFT budget, and when":

        python scripts/trace_summary.py slo /tmp/slo.jsonl

  autoscale          the autoscaler's decision timeline from
                     ``autoscale_event`` records: replica count (as a
                     bar) tracking the load signals each decision saw
                     (occupancy, queue depth, burn rate), SLO breach
                     markers inline, the decision counters, and the
                     flap verdict (direction reversals closer than the
                     flap window — zero when the policy's cooldowns
                     are doing their job):

        python scripts/trace_summary.py autoscale /tmp/serve.jsonl [flap_window_s]

  goodput            the goodput waterfall from ledger telemetry:
                     total owned device-seconds, one loss row per
                     badput bucket (compile/warmup, input stall,
                     checkpoint blocking, preemption drain/replan/
                     reshard, failover, probe, queue wait, brownout,
                     autoscale transfer), pool-idle when a fleet
                     roll-up is given, the goodput fraction, and a
                     named verdict on the largest untraced gap.
                     Accepts telemetry JSONL (the attached per-step
                     ledger snapshot or the goodput/* gauge mirror)
                     and /goodput JSON documents:

        python scripts/trace_summary.py goodput /tmp/telemetry.jsonl
        curl -s localhost:9300/goodput > /tmp/g.json
        python scripts/trace_summary.py goodput /tmp/g.json

  critical-path      per-trace latency attribution from a merged
                     Perfetto/Chrome-trace JSON document (the fleet
                     aggregator's ``/trace`` endpoint, or
                     ``merge_perfetto`` written to disk): for each
                     trace id, the innermost-span boundary sweep
                     splits end-to-end wall time across named spans,
                     with an ``(untraced)`` row for uncovered gaps and
                     a coverage fraction per trace — the one-command
                     answer to "where did this request's / this
                     shrink's latency go":

        curl -s localhost:9300/trace > /tmp/trace.json
        python scripts/trace_summary.py critical-path /tmp/trace.json [trace_id]

CPU-only (no device access): it never competes for the chip.
"""
import collections
import glob
import json
import os
import sys


def load_xspace(path):
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    if os.path.isdir(path):
        cands = sorted(glob.glob(os.path.join(
            path, "**", "*.xplane.pb"), recursive=True))
        if not cands:
            raise SystemExit(f"no .xplane.pb under {path}")
        path = cands[-1]
    xs = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        xs.ParseFromString(f.read())
    return xs, path


def summarize(xs, top_n=25):
    """Per-plane totals of event duration grouped by event name."""
    out = []
    for plane in xs.planes:
        ev_names = dict(plane.event_metadata)
        totals = collections.Counter()
        counts = collections.Counter()
        span_lo, span_hi = None, None
        for line in plane.lines:
            for ev in line.events:
                md = ev_names.get(ev.metadata_id)
                name = md.name if md else f"#{ev.metadata_id}"
                totals[name] += ev.duration_ps
                counts[name] += 1
                lo = ev.offset_ps
                hi = ev.offset_ps + ev.duration_ps
                span_lo = lo if span_lo is None else min(span_lo, lo)
                span_hi = hi if span_hi is None else max(span_hi, hi)
        if not totals:
            continue
        wall_ms = (span_hi - span_lo) / 1e9 if span_hi else 0.0
        out.append((plane.name, wall_ms, totals, counts))
    return out


def iter_jsonl(path):
    """Yield parsed records from a JsonlSink file; blank and corrupt
    lines (a crashed writer's torn tail) are skipped."""
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                yield json.loads(line)
            except json.JSONDecodeError:
                continue


def expand_jsonl_paths(paths, extra_glob=None):
    """Expand directory arguments into their ``*.jsonl`` files (plus
    ``extra_glob`` matches, listed first), keeping explicit file paths
    as-is — the shared bootstrap of every multi-stream subcommand."""
    expanded = []
    for p in paths:
        if os.path.isdir(p):
            if extra_glob:
                expanded += sorted(glob.glob(os.path.join(p, extra_glob)))
            expanded += sorted(glob.glob(os.path.join(p, "*.jsonl")))
        else:
            expanded.append(p)
    return expanded


def load_events(paths, types, counter_prefixes=None):
    """``(events, counters)``: source-tagged records of the given
    ``types`` chronologically merged across streams, plus the last
    counter snapshot filtered by prefix — the shared load path of the
    serving/fleet/autoscale subcommands."""
    events, counters = [], {}
    for p in expand_jsonl_paths(paths):
        src = os.path.basename(p)
        for rec in iter_jsonl(p):
            if rec.get("type") in types:
                events.append((src, rec))
            if counter_prefixes:
                for k, v in (rec.get("counters") or {}).items():
                    if k.startswith(counter_prefixes):
                        counters[k] = v
    events.sort(key=lambda sr: sr[1].get("time") or 0.0)
    return events, counters


def steps_argv(argv, sub):
    """Usage-checked ``(path, last_n)`` preamble shared by the
    step-table subcommands (steps/input/comm/embedding)."""
    if not argv:
        raise SystemExit(f"usage: trace_summary.py {sub} "
                         "<telemetry.jsonl> [last_n]")
    last_n = int(argv[1]) if len(argv) > 1 else None
    print(f"telemetry: {argv[0]}")
    return argv[0], last_n


def load_steps(path, last_n=None):
    """(steps, checkpoint_summary) from a JsonlSink telemetry file.

    ``checkpoint_summary`` holds the post-drain writer-thread counter
    totals (commits finishing after the last step record was cut would
    otherwise be invisible); None when the run didn't emit one."""
    steps, ck_summary = [], None
    for rec in iter_jsonl(path):
        if rec.get("type") == "step":
            steps.append(rec)
        elif rec.get("type") == "checkpoint_summary":
            ck_summary = rec
    return (steps[-last_n:] if last_n else steps), ck_summary


def _fmt_bytes(b):
    for unit in ("B", "KB", "MB", "GB"):
        if abs(b) < 1024 or unit == "GB":
            return f"{b:.1f} {unit}"
        b /= 1024.0


def summarize_steps(steps, out=print, ck_summary=None):
    """Render the step-time breakdown table for a list of step records."""
    if not steps:
        out("no step records")
        return
    n = len(steps)
    total_dur = sum(s.get("dur") or 0.0 for s in steps)
    out(f"steps: {n}   wall {total_dur:.3f} s   "
        f"mean step {1e3 * total_dur / n:.2f} ms")

    # per-span totals across steps
    span_tot = collections.Counter()
    span_cnt = collections.Counter()
    for s in steps:
        for k, v in s.get("spans", {}).items():
            span_tot[k] += v
            span_cnt[k] += s.get("span_counts", {}).get(k, 1)
    if span_tot:
        out("\n== step-time breakdown ==")
        out(f"  {'span':<22} {'total ms':>10} {'mean ms':>9} "
            f"{'% step':>7} {'count':>6}")
        for k, tot in span_tot.most_common():
            pct = 100.0 * tot / max(total_dur, 1e-12)
            out(f"  {k:<22} {1e3 * tot:>10.2f} "
                f"{1e3 * tot / max(span_cnt[k], 1):>9.2f} "
                f"{pct:>6.1f}% {span_cnt[k]:>6d}")
        other = total_dur - sum(span_tot.values())
        if other > 0:
            out(f"  {'(unattributed)':<22} {1e3 * other:>10.2f} "
                f"{1e3 * other / n:>9.2f} "
                f"{100.0 * other / max(total_dur, 1e-12):>6.1f}%")

    # scalar summaries: first/last/mean for the training-health signals
    keys = []
    for s in steps:
        for k in s.get("scalars", {}):
            if k not in keys:
                keys.append(k)
    if keys:
        out("\n== scalars (first -> last, mean) ==")
        for k in keys:
            vals = [s["scalars"][k] for s in steps
                    if isinstance(s.get("scalars", {}).get(k), (int, float))]
            if not vals:
                continue
            out(f"  {k:<22} {vals[0]:>12.5g} -> {vals[-1]:>12.5g}   "
                f"mean {sum(vals) / len(vals):>12.5g}")

    # checkpoint split: the blocking device→host copy rides the step
    # loop (a span); serialize+write+commit run on the async writer
    # thread (counters) — healthy async checkpointing shows a large
    # off-loop share
    last = steps[-1]
    counters = last.get("counters", {})
    if ck_summary is not None:          # post-drain totals supersede the
        counters = dict(counters)       # last step's mid-write snapshot
        counters.update(ck_summary.get("counters", {}))
    ck_block = span_tot.get("checkpoint.blocking", 0.0)
    ck_write = counters.get("checkpoint/write_seconds", 0.0)
    if ck_block or ck_write:
        out("\n== checkpoint (blocking copy vs async write) ==")
        out(f"  blocking device→host copy (on step loop)  "
            f"{1e3 * ck_block:>10.2f} ms")
        out(f"  serialize+write+commit (writer thread)    "
            f"{1e3 * ck_write:>10.2f} ms")
        tot = ck_block + ck_write
        if tot > 0:
            out(f"  off-loop share {100.0 * ck_write / tot:.1f}%   "
                f"committed {counters.get('checkpoint/committed', 0):.0f}   "
                f"written "
                f"{_fmt_bytes(counters.get('checkpoint/bytes_written', 0))}"
                + (f"   FAILED {counters.get('checkpoint/failed', 0):.0f}"
                   if counters.get("checkpoint/failed") else ""))

    if counters:
        out("\n== cumulative counters (at last step) ==")
        for k in sorted(counters):
            v = counters[k]
            shown = _fmt_bytes(v) if "bytes" in k else f"{v:.6g}"
            out(f"  {k:<34} {shown}")
    gauges = last.get("gauges", {})
    if gauges:
        out("\n== gauges (at last step) ==")
        for k in sorted(gauges):
            v = gauges[k]
            shown = _fmt_bytes(v) if "bytes" in k else f"{v:.6g}"
            out(f"  {k:<34} {shown}")


def load_health(paths):
    """-> (events, flights) from telemetry JSONL files and
    flight_<ts>.json dumps; a directory argument is scanned for both.
    ``events`` are (source, record) health_event pairs — standalone
    records from JSONL streams plus the ones embedded in each flight
    dump's ring; ``flights`` are (path, dump) pairs."""
    expanded = expand_jsonl_paths(paths, extra_glob="flight_*.json")
    events, flights = [], []
    for p in expanded:
        src = os.path.basename(p)
        if p.endswith(".jsonl"):
            events += [(src, rec) for rec in iter_jsonl(p)
                       if rec.get("type") == "health_event"]
            continue
        try:
            with open(p) as f:
                dump = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            print(f"  (skipping {p}: {e})")
            continue
        if dump.get("type") != "flight":
            continue
        flights.append((p, dump))
        for ev in dump.get("events", []):
            events.append((src, ev))
        for rec in dump.get("records", []):
            if rec.get("type") == "health_event":
                events.append((src, rec))
    return events, flights


def summarize_health(events, flights, out=print):
    """Render the health-event table and flight-dump summaries."""
    if not events and not flights:
        out("no health events or flight dumps found")
        return
    if events:
        # one event can appear both standalone and inside a dump's
        # ring: dedupe on (condition, step, value) — value stringified,
        # since NaN != NaN would defeat the dedupe for exactly the
        # non_finite_loss events this table exists for
        seen, rows = set(), []
        for src, ev in events:
            key = (ev.get("condition"), ev.get("step"),
                   str(ev.get("value")))
            if key in seen:
                continue
            seen.add(key)
            rows.append((src, ev))
        out("== health events ==")
        out(f"  {'step':>6} {'condition':<18} {'metric':<16} "
            f"{'value':>12} {'threshold':>12} {'action':<9} source")
        for src, ev in rows:
            step = ev.get("step")
            thr = ev.get("threshold")
            val = ev.get("value")
            extra = (f"  straggler host {ev['straggler']} "
                     f"({ev.get('skew', 0):.2f}x)"
                     if "straggler" in ev else "")
            out(f"  {'-' if step is None else step:>6} "
                f"{ev.get('condition', '?'):<18} "
                f"{ev.get('metric', '?'):<16} "
                f"{'-' if val is None else format(val, '>12.5g'):>12} "
                f"{'-' if thr is None else format(thr, '>12.5g'):>12} "
                f"{ev.get('action', '?'):<9} {src}{extra}")
    if flights:
        out("\n== flight-recorder dumps ==")
        for p, d in flights:
            n_rec = len(d.get("records", []))
            out(f"  {os.path.basename(p)}: reason={d.get('reason')}  "
                f"last_step={d.get('last_step')}  "
                f"ring_records={n_rec}  "
                f"health_events={d.get('counters', {}).get('health/events', 0):.0f}")


def load_fleet(paths):
    """Chronologically-merged ``fleet_event`` + ``elastic_event``
    records from telemetry JSONL files (directories are scanned for
    ``*.jsonl``).  Several streams merge into one timeline — in a
    fleet each job usually writes through its own recorder/sink."""
    events, _ = load_events(paths, ("fleet_event", "elastic_event"))
    return events


def _fmt_axes(axes):
    if not isinstance(axes, dict):
        return "?"
    return "x".join(f"{k}{v}" for k, v in axes.items())


def summarize_fleet(events, out=print):
    """Render the pool timeline and per-job event sequences."""
    if not events:
        out("no fleet or elastic events found")
        return
    t0 = min(ev.get("time") or 0.0 for _, ev in events)
    jobs, seen = [], {}
    out("== fleet timeline ==")
    out(f"  {'t':>8}  {'job':<10} {'event':<12} detail")
    for src, ev in events:
        job = ev.get("job") or "-"
        if job not in seen:
            seen[job] = []
            jobs.append(job)
        kind = ev.get("kind", "?")
        seen[job].append(kind)
        parts = []
        if ev.get("from_axes") is not None:
            parts.append(f"{_fmt_axes(ev['from_axes'])} -> "
                         f"{_fmt_axes(ev.get('to_axes'))}")
        elif ev.get("axes") is not None:
            parts.append(_fmt_axes(ev["axes"]))
        elif ev.get("template") is not None:
            parts.append(f"template {_fmt_axes(ev['template'])}")
        if ev.get("devices") is not None:
            parts.append(f"devices={ev['devices']:g}")
        if ev.get("from_devices") is not None:
            parts.append(f"(was {ev['from_devices']:g})")
        if ev.get("step") is not None:
            parts.append(f"step={ev['step']:g}")
        if ev.get("steps") is not None:
            parts.append(f"steps={ev['steps']:g}")
        if ev.get("priority") is not None:
            parts.append(f"prio={ev['priority']:g}")
        if ev.get("reason"):
            parts.append(f"[{ev['reason']}]")
        if ev.get("error"):
            parts.append(f"error={ev['error']}")
        dt = (ev.get("time") or 0.0) - t0
        out(f"  {dt:>+7.2f}s  {job:<10} {kind:<12} {' '.join(parts)}")
    out("\n== per-job event sequence ==")
    for job in jobs:
        out(f"  {job}: {' -> '.join(seen[job])}")


def load_slo(paths):
    """``slo_event`` transitions (chronological, source-tagged) plus
    the LATEST ``slo_summary`` objective table from telemetry JSONL
    files (directories are scanned for ``*.jsonl``)."""
    events, summaries = [], []
    for p in expand_jsonl_paths(paths):
        src = os.path.basename(p)
        for rec in iter_jsonl(p):
            if rec.get("type") == "slo_event":
                events.append((src, rec))
            elif rec.get("type") == "slo_summary":
                summaries.append(rec)
    events.sort(key=lambda sr: sr[1].get("time") or 0.0)
    summaries.sort(key=lambda r: r.get("time") or 0.0)
    return events, (summaries[-1] if summaries else None)


def _slo_cells(r):
    """compliance/budget/burn-fast/burn-slow cells for one objective
    verdict (shared by the table and the timeline)."""
    if r.get("no_data") or r.get("compliance") is None:
        return ("no data", "-", "-", "-")
    bf = r.get("burn_fast")
    return (f"{100.0 * r['compliance']:.2f}%",
            f"{100.0 * r['budget_remaining']:.1f}%",
            "-" if bf is None else f"{bf:.2f}",
            f"{r['burn_slow']:.2f}")


def summarize_slo(events, summary, out=print):
    """Render the objective table (from the latest ``slo_summary``)
    and the breach/recovery timeline (from ``slo_event`` records)."""
    if not events and summary is None:
        out("no slo events or summaries found")
        return
    if summary is not None:
        out("== SLO objectives ==")
        out(f"  {'objective':<24} {'compliance':>10} {'budget':>8} "
            f"{'burn(fast':>9}{'/slow)':<7} state")
        for r in summary.get("objectives", []):
            comp, budget, bf, bs = _slo_cells(r)
            state = ("NO DATA" if r.get("no_data")
                     else "BREACH" if r.get("breach") else "ok")
            out(f"  {r.get('objective', '?'):<24} {comp:>10} "
                f"{budget:>8} {bf:>9}/{bs:<6} {state}")
    if events:
        if summary is not None:
            out("")
        out("== breach timeline ==")
        t0 = min(ev.get("time") or 0.0 for _, ev in events)
        out(f"  {'t':>8}  {'objective':<24} {'event':<10} detail")
        for _, ev in events:
            comp, budget, bf, bs = _slo_cells(ev)
            dt = (ev.get("time") or 0.0) - t0
            out(f"  {dt:>+7.2f}s  {ev.get('objective', '?'):<24} "
                f"{ev.get('kind', '?'):<10} compliance={comp} "
                f"budget={budget} burn={bf}/{bs}")


def load_autoscale(paths):
    """Chronologically-merged ``autoscale_event`` records plus
    ``slo_event`` breach markers and the last ``autoscale/*`` counter
    snapshot from telemetry JSONL files (directories are scanned for
    ``*.jsonl``)."""
    return load_events(paths, ("autoscale_event", "slo_event"),
                       ("autoscale/",))


def count_flaps(scalings, window):
    """Direction reversals (up→down or down→up) closer than ``window``
    seconds apart — the flapping the policy's asymmetric cooldowns
    must make impossible.  ``scalings`` is ``[(t, direction), ...]``
    chronological."""
    flaps = 0
    for (t_prev, d_prev), (t, d) in zip(scalings, scalings[1:]):
        if d != d_prev and (t - t_prev) < window:
            flaps += 1
    return flaps


def _autoscale_load_cell(ev):
    """Compact load annotation from the decision's signal snapshot."""
    sig = ev.get("signals") or {}
    parts = []
    if sig.get("occupancy") is not None:
        parts.append(f"occ={sig['occupancy']:.2f}")
    if sig.get("queue_depth") is not None:
        parts.append(f"queue={sig['queue_depth']:.0f}")
    if sig.get("burn_fast") is not None:
        parts.append(f"burn={sig['burn_fast']:.2f}")
    if sig.get("breached"):
        parts.append("breach=" + ",".join(sig["breached"]))
    return " ".join(parts) or "-"


def summarize_autoscale(events, counters, flap_window=30.0, out=print):
    """Render the autoscale timeline — replica count (as a bar)
    tracking load, with SLO breach markers inline — plus the decision
    counters and the flap verdict."""
    if not events and not counters:
        out("no autoscale_event records found (no AutoscaleController "
            "attached, or nothing happened)")
        return
    scalings = []
    if events:
        out("== autoscale timeline ==")
        t0 = min(ev.get("time") or 0.0 for _, ev in events)
        out(f"  {'t':>8}  {'replicas':<12} {'event':<12} "
            "load / reason")
        for _, ev in events:
            dt = (ev.get("time") or 0.0) - t0
            if ev.get("type") == "slo_event":
                out(f"  {dt:>+7.2f}s  {'':<12} "
                    f"{'slo_' + str(ev.get('kind', '?')):<12} "
                    f"{ev.get('objective', '?')}")
                continue
            kind = ev.get("kind", "?")
            n_after = ev.get("replicas_after")
            bar = "#" * int(n_after or 0)
            if kind in ("scale_up", "scale_down"):
                scalings.append(
                    (ev.get("time") or 0.0,
                     "up" if kind == "scale_up" else "down"))
            detail = _autoscale_load_cell(ev)
            if ev.get("replica") is not None:
                detail += f" replica={ev['replica']:g}"
            if ev.get("reason"):
                detail += f" [{ev['reason']}]"
            if ev.get("error"):
                detail += f" error={ev['error']}"
            n_cell = (f"{bar:<8} {n_after:g}" if n_after is not None
                      else "?")
            out(f"  {dt:>+7.2f}s  {n_cell:<12} {kind:<12} {detail}")
    out("\n== autoscale summary ==")
    if counters:
        out("  " + "  ".join(
            f"{k.split('/', 1)[1]}={counters[k]:g}"
            for k in sorted(counters)))
    flaps = count_flaps(scalings, flap_window)
    out(f"  scalings={len(scalings)}  flaps (direction reversal "
        f"< {flap_window:g}s apart): {flaps}")


def load_serving(paths):
    """Chronologically-merged ``replica_event`` + ``fault_event`` +
    ``decode_event`` + ``stream_event`` records from telemetry JSONL
    files (directories are scanned for ``*.jsonl``), plus the last
    record's counter snapshot per stream."""
    return load_events(paths, ("replica_event", "fault_event",
                               "decode_event", "stream_event"),
                       ("replica/", "serving/", "decode/",
                        "kv/", "stream/"))


def summarize_serving(events, counters, out=print):
    """Render the replica-set timeline, per-replica sequences, and —
    when a decode engine's telemetry is present — the per-token SLO
    table (TTFT vs inter-token split) and the occupancy timeline."""
    if not events and not counters:
        out("no replica_event records found (not a ReplicaSet "
            "telemetry stream, or nothing happened)")
        return
    decode_events = [(s, e) for s, e in events
                     if e.get("type") == "decode_event"]
    events = [(s, e) for s, e in events
              if e.get("type") != "decode_event"]
    _summarize_decode(decode_events, counters, out)
    if not events:
        # counters-only stream (a healthy run with zero transitions):
        # the counter block below must still render
        if counters:
            out("== resilience counters (at last record) ==")
            for k in sorted(counters):
                out(f"  {k:<34} {counters[k]:.6g}")
        return
    t0 = min((ev.get("time") or 0.0 for _, ev in events), default=0.0)
    replicas, seen = [], {}
    out("== serving resilience timeline ==")
    out(f"  {'t':>8}  {'replica':<8} {'event':<15} detail")
    for src, ev in events:
        if ev.get("type") == "fault_event":
            kind = f"fault:{ev.get('mode', '?')}"
            rep = "-"
            parts = [ev.get("site", "?")]
        elif ev.get("type") == "stream_event":
            kind = f"stream:{ev.get('kind', '?')}"
            rep = "-"
            parts = []
            if ev.get("model"):
                parts.append(f"model={ev['model']}")
            if ev.get("version"):
                parts.append(f"version={ev['version']}")
            if ev.get("reason"):
                parts.append(f"[{ev['reason']}]")
            if ev.get("error"):
                parts.append(f"error={ev['error']}")
        else:
            kind = ev.get("kind", "?")
            rep = ev.get("replica")
            rep = "-" if rep is None else str(rep)
            parts = []
            if ev.get("reason"):
                parts.append(f"[{ev['reason']}]")
            if ev.get("model"):
                parts.append(f"model={ev['model']}")
            if ev.get("version"):
                parts.append(f"version={ev['version']}")
            if ev.get("replicas") is not None:
                parts.append(f"replicas={ev['replicas']:g}")
            if ev.get("saturation") is not None:
                parts.append(f"saturation={ev['saturation']:.2f}")
        if rep not in seen:
            seen[rep] = []
            replicas.append(rep)
        seen[rep].append(kind)
        dt = (ev.get("time") or 0.0) - t0
        out(f"  {dt:>+7.2f}s  {rep:<8} {kind:<15} {' '.join(parts)}")
    if replicas:
        out("\n== per-replica transition sequence ==")
        for rep in replicas:
            out(f"  {rep}: {' -> '.join(seen[rep])}")
    if counters:
        out("\n== resilience counters (at last record) ==")
        for k in sorted(counters):
            out(f"  {k:<34} {counters[k]:.6g}")


def _summarize_decode(decode_events, counters, out):
    """Decode-engine view: per-token SLO split and occupancy timeline
    (from the engine's periodic ``decode_event`` records)."""
    has_counters = any(k.startswith(("decode/", "kv/"))
                       for k in counters)
    if not decode_events and not has_counters:
        return
    out("== decode per-token SLO ==")
    last = decode_events[-1][1] if decode_events else {}
    ttft = last.get("ttft") or {}
    inter = last.get("intertoken") or {}

    def q(d, key):
        v = d.get(key)
        return f"{v:8.2f}" if isinstance(v, (int, float)) else "       -"

    out(f"  ttft        p50 {q(ttft, 'p50')} ms   p99 "
        f"{q(ttft, 'p99')} ms     (submit -> first token: queue + "
        "prefill)")
    out(f"  inter-token p50 {q(inter, 'p50')} ms   p99 "
        f"{q(inter, 'p99')} ms     (steady-state decode cadence)")
    keys = ("decode/requests", "decode/tokens", "decode/prefills",
            "decode/readmissions", "decode/shed_deadline",
            "decode/shed_queue_full", "kv/evictions")
    present = [(k, counters[k]) for k in keys if k in counters]
    if present:
        out("  " + "  ".join(f"{k}={v:.6g}" for k, v in present))
    if decode_events:
        t0 = decode_events[0][1].get("time") or 0.0
        out("\n== decode occupancy timeline ==")
        out(f"  {'t':>8}  {'step':>6}  {'live':>7}  {'occ':>5}  "
            f"{'kv_fill':>7}  {'queued':>6}")
        for _, ev in decode_events:
            dt = (ev.get("time") or 0.0) - t0
            out(f"  {dt:>+7.2f}s  {ev.get('step', 0):>6.0f}  "
                f"{ev.get('live', 0):>3.0f}/{ev.get('slots', 0):<3.0f} "
                f"{ev.get('occupancy', 0.0):>5.2f}  "
                f"{ev.get('kv_fill', 0.0):>7.2f}  "
                f"{ev.get('queue_depth', 0):>6.0f}")
    out("")


def load_profile(path):
    """(profile_records, steps) from a JsonlSink telemetry file."""
    profiles, steps = [], []
    for rec in iter_jsonl(path):
        if rec.get("type") == "profile":
            profiles.append(rec)
        elif rec.get("type") == "step":
            steps.append(rec)
    return profiles, steps


def _pct(x):
    return f"{100.0 * x:5.1f}%"


def summarize_profile(profiles, steps, out=print):
    """Render the cost/memory attribution: compiled per-step cost vs
    device peaks, measured efficiency over the step records, and the
    per-bucket serving cost table."""
    if not profiles and not steps:
        out("no profile or step records")
        return
    train = [p for p in profiles if p.get("kind") == "train_step"]
    if train:
        p = train[-1]           # the newest program is the live one
        cost = p.get("cost", {}) or {}
        out("== train step (compiled cost) ==")
        out(f"  device {p.get('device', '?')}   peak "
            + (f"{p['peak_flops'] / 1e12:.0f} TFLOP/s"
               if p.get("peak_flops") else "FLOP/s unknown")
            + (f"   HBM {p['peak_hbm_bw'] / 1e9:.0f} GB/s"
               if p.get("peak_hbm_bw") else "")
            + (f"   capacity {_fmt_bytes(p['hbm_capacity'])}"
               if p.get("hbm_capacity") else ""))
        if cost.get("flops") is not None:
            out(f"  flops/step         {cost['flops'] / 1e9:12.3f} GFLOP")
        if cost.get("bytes_accessed") is not None:
            out(f"  bytes accessed     "
                f"{_fmt_bytes(cost['bytes_accessed']):>12}")
        if cost.get("peak_hbm_bytes") is not None:
            line = (f"  peak HBM           "
                    f"{_fmt_bytes(cost['peak_hbm_bytes']):>12}")
            if p.get("hbm_capacity"):
                line += (" ("
                         + _pct(cost["peak_hbm_bytes"]
                                / p["hbm_capacity"]).strip()
                         + " of device)")
            out(line)
            for k in ("argument_bytes", "output_bytes", "temp_bytes",
                      "generated_code_bytes"):
                if cost.get(k) is not None:
                    out(f"    {k[:-6]:<16} {_fmt_bytes(cost[k]):>12}")
        if cost.get("unavailable"):
            out(f"  unavailable: {', '.join(cost['unavailable'])}")

    # measured efficiency: the per-step scalars end_step derived
    mfu = [s["scalars"]["perf/mfu"] for s in steps
           if isinstance(s.get("scalars", {}).get("perf/mfu"),
                         (int, float))]
    bw = [s["scalars"]["perf/hbm_bw_util"] for s in steps
          if isinstance(s.get("scalars", {}).get("perf/hbm_bw_util"),
                        (int, float))]
    if mfu or bw:
        out("\n== measured efficiency (over step records) ==")
        if mfu:
            out(f"  MFU            mean {_pct(sum(mfu) / len(mfu))}   "
                f"best {_pct(max(mfu))}   over {len(mfu)} steps")
        if bw:
            out(f"  HBM bw util    mean {_pct(sum(bw) / len(bw))}   "
                f"best {_pct(max(bw))}")
    elif steps:
        marks = sorted({k for s in steps
                        for k in s.get("scalars", {})
                        if k.endswith("_unavailable")})
        if marks:
            out("\n== measured efficiency ==")
            out(f"  unavailable on this backend: {', '.join(marks)}")

    buckets = [p for p in profiles if p.get("kind") == "serving_bucket"]
    if buckets:
        out("\n== serving buckets (compiled cost per execution) ==")
        out(f"  {'model':<14} {'bucket':>6} {'GFLOP':>10} "
            f"{'peak HBM':>12}")
        seen = {}
        for p in buckets:       # newest capture per (model, bucket) wins
            seen[(p.get("model"), p.get("bucket"))] = p
        for (model, bucket), p in sorted(
                seen.items(), key=lambda kv: (str(kv[0][0]),
                                              kv[0][1] or 0)):
            cost = p.get("cost", {}) or {}
            flops = cost.get("flops")
            peak = cost.get("peak_hbm_bytes")
            out(f"  {str(model):<14} {bucket:>6} "
                f"{flops / 1e9 if flops is not None else float('nan'):>10.4f} "
                f"{_fmt_bytes(peak) if peak is not None else '-':>12}")


def summarize_comm(steps, out=print):
    """Render the collective-exchange table: per-op raw vs wire bytes
    per step (compression observable as the ratio), bucket count, and
    cumulative totals — all from the trace-time accounting the
    allreduce/bucketer/zero1 paths report into the step records."""
    if not steps:
        out("no step records")
        return
    last = steps[-1]
    gauges = last.get("gauges", {})
    counters = last.get("counters", {})
    n = len(steps)
    out(f"steps: {n}")

    ops = sorted({k[len("collective/"):-len("_bytes")]
                  for k in gauges
                  if k.startswith("collective/") and k.endswith("_bytes")
                  and not k.endswith("_wire_bytes")
                  and not k.endswith("_per_step")})
    if ops:
        out("\n== collectives per step (trace-time accounting, ring "
            "wire bytes per chip) ==")
        out(f"  {'op':<16} {'raw':>12} {'wire':>12} {'wire/raw':>9}")
        for op in ops:
            raw = gauges.get(f"collective/{op}_bytes", 0.0)
            wire = gauges.get(f"collective/{op}_wire_bytes", 0.0)
            ratio = wire / raw if raw else float("nan")
            out(f"  {op:<16} {_fmt_bytes(raw):>12} {_fmt_bytes(wire):>12} "
                f"{ratio:>8.2f}x")
        tot_raw = gauges.get("collective/bytes_per_step", 0.0)
        tot_wire = gauges.get("collective/wire_bytes_per_step", 0.0)
        if tot_raw:
            out(f"  {'TOTAL':<16} {_fmt_bytes(tot_raw):>12} "
                f"{_fmt_bytes(tot_wire):>12} "
                f"{tot_wire / tot_raw:>8.2f}x")
    if gauges.get("collective/buckets"):
        out(f"\n  gradient buckets/step: "
            f"{gauges['collective/buckets']:.0f} "
            "(per-bucket collectives — overlappable with backward)")

    # per-axis-group breakdown (composed meshes): which parallelism
    # group pays which wire bytes — comm/group.<axis>.<op>_* gauges
    # from the trace-time accounting (manual paths) or the HLO
    # replica-group attribution (SpmdTrainer.account_collectives)
    pre = "comm/group."
    group_names = sorted({k[len(pre):].split(".", 1)[0]
                          for k in gauges if k.startswith(pre)})
    if group_names:
        out("\n== per-axis-group exchange (one bucket/collective "
            "stream per parallelism group) ==")
        out(f"  {'group':<8} {'op':<18} {'raw':>12} {'wire':>12} "
            f"{'wire/raw':>9}")
        for g in group_names:
            gpre = f"{pre}{g}."
            gops = sorted({k[len(gpre):-len("_wire_bytes")]
                           for k in gauges
                           if k.startswith(gpre)
                           and k.endswith("_wire_bytes")
                           and not k.endswith("bytes_per_step")})
            for op in gops:
                raw = gauges.get(f"{gpre}{op}_bytes", 0.0)
                wire = gauges.get(f"{gpre}{op}_wire_bytes", 0.0)
                ratio = wire / raw if raw else float("nan")
                out(f"  {g:<8} {op:<18} {_fmt_bytes(raw):>12} "
                    f"{_fmt_bytes(wire):>12} {ratio:>8.2f}x")
            tot = gauges.get(f"{gpre}wire_bytes_per_step", 0.0)
            extra = ""
            if gauges.get(f"{gpre}buckets"):
                extra = (f"   ({gauges[f'{gpre}buckets']:.0f} "
                         "buckets/step)")
            out(f"  {g:<8} {'TOTAL wire':<18} {'':>12} "
                f"{_fmt_bytes(tot):>12}{extra}")

    raw_tot = counters.get("collective/bytes_total", 0.0)
    wire_tot = counters.get("collective/wire_bytes_total", 0.0)
    if raw_tot:
        # mean/step from the per-step gauges over the RETAINED window —
        # the cumulative counters cover the whole run, so total/len()
        # would inflate the mean when a last_n window is shown
        raws = [s["gauges"]["collective/bytes_per_step"] for s in steps
                if isinstance(s.get("gauges", {}).get(
                    "collective/bytes_per_step"), (int, float))]
        wires = [s["gauges"]["collective/wire_bytes_per_step"]
                 for s in steps
                 if isinstance(s.get("gauges", {}).get(
                     "collective/wire_bytes_per_step"), (int, float))]
        raw_mean = sum(raws) / len(raws) if raws else raw_tot / n
        wire_mean = sum(wires) / len(wires) if wires else wire_tot / n
        out("\n== cumulative exchange (counters: whole run; mean: shown "
            "steps) ==")
        out(f"  raw  {_fmt_bytes(raw_tot):>12}   "
            f"mean/step {_fmt_bytes(raw_mean)}")
        out(f"  wire {_fmt_bytes(wire_tot):>12}   "
            f"mean/step {_fmt_bytes(wire_mean)}"
            + (f"   saved {_pct(1 - wire_tot / raw_tot)} on the wire"
               if wire_tot and wire_tot < raw_tot else ""))

    unsh = counters.get("comm/unsharded_leaves", 0.0)
    ungath = counters.get("comm/ungathered_leaves", 0.0)
    if unsh or ungath:
        out("\n== sharding coverage ==")
        if unsh:
            out(f"  comm/unsharded_leaves  {unsh:.0f}  (leaves dense-"
                "all-reduced instead of reduce-scattered; names in the "
                "debug log of bigdl_tpu.parallel.allreduce)")
        if ungath:
            out(f"  comm/ungathered_leaves {ungath:.0f}  (replicated "
                "leaves skipped by allgather_params)")
    if not ops and not raw_tot:
        out("no collective accounting in these step records (single "
            "device, or the GSPMD path — see SpmdTrainer."
            "account_collectives)")


def summarize_input(steps, out=print):
    """Render the input-pipeline breakdown: the data/* counters are
    cumulative, so per-window deltas come from consecutive step records
    (the first shown step is the baseline and is excluded from the
    window — its own delta is unknowable from the records alone)."""
    if not steps:
        out("no step records")
        return
    have = [s for s in steps
            if "data/input_stall_seconds" in s.get("counters", {})]
    if not have:
        out("no data/* input telemetry in these step records (not the "
            "sharded streaming loader, or telemetry disabled)")
        return
    if len(have) < 2:
        out("need >= 2 step records with data/* counters for a window")
        return

    def c(s, k):
        return s.get("counters", {}).get(k, 0.0)

    first, last = have[0], have[-1]
    n = len(have) - 1
    dur = sum(s.get("dur") or 0.0 for s in have[1:])
    keys = ("data/input_stall_seconds", "data/decode_seconds",
            "data/h2d_bytes", "data/records_read", "data/batches",
            "data/resync_skipped_bytes")
    d = {k: c(last, k) - c(first, k) for k in keys}
    stall_frac = d["data/input_stall_seconds"] / max(dur, 1e-12)
    out(f"steps in window: {n}   wall {dur:.3f} s   "
        f"mean step {1e3 * dur / max(n, 1):.2f} ms")
    out("\n== input pipeline (window deltas) ==")
    out(f"  input stall        {1e3 * d['data/input_stall_seconds']:>10.2f}"
        f" ms   {100.0 * stall_frac:5.2f}% of step time"
        + ("   <- INPUT-BOUND" if stall_frac > 0.10 else ""))
    out(f"  host decode        {1e3 * d['data/decode_seconds']:>10.2f} ms"
        f"   (worker-pool total; overlaps the step)")
    if d["data/records_read"]:
        dec = d["data/decode_seconds"]
        out(f"  decode throughput  "
            f"{d['data/records_read'] / max(dec, 1e-12):>10.0f} rec/s "
            f"of decode time   ({d['data/records_read']:.0f} records)")
    out(f"  h2d wire           {_fmt_bytes(d['data/h2d_bytes']):>10}   "
        f"({_fmt_bytes(d['data/h2d_bytes'] / max(n, 1))}/step)")
    if d["data/resync_skipped_bytes"]:
        out(f"  salvage resync     "
            f"{_fmt_bytes(d['data/resync_skipped_bytes']):>10} skipped "
            "over corrupt regions")
    depths = [s["gauges"]["data/queue_depth"] for s in have
              if isinstance(s.get("gauges", {}).get("data/queue_depth"),
                            (int, float))]
    if depths:
        out(f"  staging queue      depth mean {sum(depths) / len(depths):.2f}"
            f"   min {min(depths):.0f}  max {max(depths):.0f}   "
            "(0 at pull = the step waited)")
    out(f"\n  totals at last step: "
        f"{c(last, 'data/records_read'):.0f} records, "
        f"{c(last, 'data/batches'):.0f} batches, "
        f"stall {c(last, 'data/input_stall_seconds'):.3f} s")


def main_input(argv):
    path, last_n = steps_argv(argv, "input")
    steps, _ = load_steps(path, last_n)
    summarize_input(steps)


def main_comm(argv):
    path, last_n = steps_argv(argv, "comm")
    steps, _ = load_steps(path, last_n)
    summarize_comm(steps)


def summarize_embedding(steps, out=print):
    """Render the sharded-embedding lookup economics: exchange wire
    volume, dedup reduction, padding waste, touched-rows fraction —
    the embedding/* family from dedup/pad/exchange/sparse-apply sites."""
    if not steps:
        out("no step records")
        return
    last = steps[-1]
    g = last.get("gauges", {})
    c = last.get("counters", {})
    n = len(steps)
    out(f"steps: {n}")

    ex_bytes = g.get("embedding/lookup_exchange_bytes", 0.0)
    ex_ids = g.get("embedding/exchange_ids", 0.0)
    if ex_bytes or ex_ids:
        out("\n== lookup exchange (per step, trace-time accounting) ==")
        out(f"  wire            {_fmt_bytes(ex_bytes):>12}  "
            f"(both all-to-all legs: ids out + embeddings back)")
        out(f"  id slots        {ex_ids:12.0f}  (capacity x shards, "
            "padding included)")

    din = c.get("embedding/dedup_in_ids", 0.0)
    dout = c.get("embedding/dedup_out_ids", 0.0)
    if din:
        out("\n== host dedup ==")
        out(f"  ids in          {din:12.0f}")
        out(f"  unique out      {dout:12.0f}   "
            f"({100.0 * (1.0 - dout / din):.1f}% of the wire saved)")
        out(f"  last-batch ratio {g.get('embedding/dedup_ratio', 0.0):.3f}")

    slots = c.get("embedding/pad_slots", 0.0)
    idsn = c.get("embedding/pad_ids", 0.0)
    if slots:
        out("\n== bucket-ladder padding ==")
        out(f"  slots emitted   {slots:12.0f}   real ids {idsn:.0f}   "
            f"cumulative waste {100.0 * (1.0 - idsn / slots):.1f}%")
        out(f"  last-batch waste {g.get('embedding/padding_waste', 0.0):.3f}")

    tf = g.get("embedding/touched_rows_fraction")
    if tf is not None:
        out("\n== sparse gradient application ==")
        out(f"  touched rows    {100.0 * tf:11.2f}%  of the table — a "
            f"dense step overpays {1.0 / max(tf, 1e-12):.0f}x")


def main_embedding(argv):
    path, last_n = steps_argv(argv, "embedding")
    steps, _ = load_steps(path, last_n)
    summarize_embedding(steps)


def main_profile(argv):
    if not argv:
        raise SystemExit("usage: trace_summary.py profile "
                         "<telemetry.jsonl>")
    profiles, steps = load_profile(argv[0])
    print(f"telemetry: {argv[0]}")
    summarize_profile(profiles, steps)


def main_serving(argv):
    if not argv:
        raise SystemExit("usage: trace_summary.py serving "
                         "<telemetry.jsonl | dir>...")
    events, counters = load_serving(argv)
    summarize_serving(events, counters)


def main_fleet(argv):
    if not argv:
        raise SystemExit("usage: trace_summary.py fleet "
                         "<telemetry.jsonl | dir>...")
    events = load_fleet(argv)
    summarize_fleet(events)


def main_slo(argv):
    if not argv:
        raise SystemExit("usage: trace_summary.py slo "
                         "<telemetry.jsonl | dir>...")
    events, summary = load_slo(argv)
    summarize_slo(events, summary)


def main_autoscale(argv):
    if not argv:
        raise SystemExit("usage: trace_summary.py autoscale "
                         "<telemetry.jsonl | dir>... [flap_window_s]")
    flap_window = 30.0
    try:
        flap_window = float(argv[-1])
        argv = argv[:-1]
    except ValueError:
        pass
    if not argv:
        raise SystemExit("trace_summary.py autoscale: no paths given")
    events, counters = load_autoscale(argv)
    summarize_autoscale(events, counters, flap_window=flap_window)


def load_goodput(paths):
    """Per-source ledger snapshots for the goodput waterfall.

    Accepts telemetry JSONL streams (the LAST record carrying an
    attached ``goodput`` snapshot wins; streams without one fall back
    to their last ``goodput/*`` gauge mirror) and plain JSON documents
    from a ``/goodput`` endpoint (a single ledger snapshot or a fleet
    roll-up).  Returns ``(jobs, pool)`` — ``pool`` is the ownership
    snapshot when a roll-up document carried one."""
    jobs, pool = {}, None
    for p in expand_jsonl_paths(paths, extra_glob="*.json"):
        src = os.path.basename(p)
        if not p.endswith(".jsonl"):
            try:
                with open(p) as f:
                    doc = json.load(f)
            except (OSError, json.JSONDecodeError) as e:
                print(f"  (skipping {p}: {e})")
                continue
            if not isinstance(doc, dict):
                continue
            if "jobs" in doc:           # a rollup(): unpack its jobs
                for name, snap in (doc.get("jobs") or {}).items():
                    jobs[name] = snap
                if doc.get("pool"):
                    pool = doc["pool"]
            elif "buckets" in doc:      # a single ledger snapshot
                jobs[doc.get("name") or src] = doc
            continue
        snap, gauges = None, {}
        for rec in iter_jsonl(p):
            if isinstance(rec.get("goodput"), dict):
                snap = rec["goodput"]
            for k, v in (rec.get("gauges") or {}).items():
                if k.startswith("goodput/"):
                    gauges[k] = v
        if snap is None and gauges:
            # rebuild from the gauge mirror GoodputLedger.publish wrote
            snap = {
                "name": src,
                "devices": gauges.get("goodput/devices", 1),
                "owned_s": gauges.get("goodput/owned_s", 0.0),
                "goodput_fraction": gauges.get("goodput/fraction", 0.0),
                "buckets": {k[len("goodput/"):-2]: v
                            for k, v in gauges.items()
                            if k.endswith("_s")
                            and k != "goodput/owned_s"},
            }
        if snap is not None:
            jobs[snap.get("name") or src] = snap
    return jobs, pool


def summarize_goodput(jobs, pool=None, out=print):
    """Render the goodput waterfall: total owned device-seconds at the
    top, one loss row per non-empty badput bucket, the goodput line at
    the bottom — and a named verdict on the top untraced gap (the
    largest non-goodput bucket, ``idle`` meaning unattributed)."""
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), ".."))
    from bigdl_tpu.observability.goodput import BUCKETS, rollup
    if not jobs:
        out("no goodput ledger snapshots found (no ledger attached, or "
            "telemetry predates the goodput family)")
        return
    roll = rollup(jobs, pool)
    owned = roll["owned_s"]
    if owned <= 0.0:
        out("ledger present but zero owned device-seconds")
        return
    out(f"== goodput waterfall ({len(jobs)} job"
        f"{'s' if len(jobs) != 1 else ''}"
        + (", pool ownership" if pool else "") + ") ==")
    out(f"  {'':<2}{'bucket':<22} {'dev-s':>12} {'% owned':>8}")
    out(f"  {'':<2}{'owned':<22} {owned:>12.3f} {100.0:>7.1f}%")
    losses = []
    for b in BUCKETS:
        if b == "goodput":
            continue
        v = roll["buckets"].get(b, 0.0)
        if v > 0.0:
            losses.append((b, v))
            out(f"  - {b:<22} {v:>12.3f} "
                f"{100.0 * v / owned:>7.1f}%")
    if pool and roll["pool_idle_s"] > 0.0:
        losses.append(("pool_idle", roll["pool_idle_s"]))
        out(f"  - {'pool_idle':<22} {roll['pool_idle_s']:>12.3f} "
            f"{100.0 * roll['pool_idle_s'] / owned:>7.1f}%")
    good = roll["buckets"].get("goodput", 0.0)
    out(f"  = {'goodput':<22} {good:>12.3f} "
        f"{100.0 * roll['goodput_fraction']:>7.1f}%")
    out(f"  conservation error: "
        f"{100.0 * roll['conservation_error']:.3f}%")
    if losses:
        top, v = max(losses, key=lambda kv: kv[1])
        what = ("unattributed owned time — instrument the producer"
                if top == "idle" else
                "devices claimed by no job — a scheduling gap"
                if top == "pool_idle" else "attributed badput")
        out(f"  top gap: {top} ({v:.3f} dev-s, "
            f"{100.0 * v / owned:.1f}% of owned) — {what}")
    if len(jobs) > 1:
        out("\n== per-job ledgers ==")
        out(f"  {'job':<18} {'devices':>7} {'owned':>12} "
            f"{'goodput':>8} {'top badput':<22}")
        for name in sorted(jobs):
            s = jobs[name]
            bk = {b: v for b, v in (s.get("buckets") or {}).items()
                  if b != "goodput" and v > 0.0}
            top = max(bk, key=bk.get) if bk else "-"
            out(f"  {name:<18} {s.get('devices', 0):>7g} "
                f"{s.get('owned_s', 0.0):>12.3f} "
                f"{100.0 * s.get('goodput_fraction', 0.0):>7.1f}% "
                f"{top:<22}")


def main_goodput(argv):
    if not argv:
        raise SystemExit("usage: trace_summary.py goodput "
                         "<telemetry.jsonl | goodput.json | dir>...")
    jobs, pool = load_goodput(argv)
    summarize_goodput(jobs, pool)


def load_trace_doc(path):
    """Parsed Chrome-trace document from a file written by the fleet
    aggregator's ``/trace`` endpoint or by ``merge_perfetto``."""
    with open(path) as f:
        doc = json.load(f)
    if not isinstance(doc, dict) or "traceEvents" not in doc:
        raise SystemExit(f"{path}: not a Chrome-trace JSON document "
                         "(no traceEvents key)")
    return doc


def summarize_critical_path(doc, trace_id=None, out=print):
    """Render per-trace critical-path attribution: every trace id in
    the merged document gets a table splitting its end-to-end wall
    time across the innermost covering spans, plus the coverage
    fraction (share of the window attributed to NAMED spans)."""
    # repo-rooted import so the script works from a checkout without
    # installation, matching the other subcommands' zero-dep stance
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), ".."))
    from bigdl_tpu.observability.tracing import (critical_path,
                                                 spans_from_chrome)
    per_trace = spans_from_chrome(doc)
    if trace_id is not None:
        if trace_id not in per_trace:
            raise SystemExit(f"trace {trace_id} not in document "
                             f"({len(per_trace)} traces present)")
        per_trace = {trace_id: per_trace[trace_id]}
    if not per_trace:
        out("no spans with trace ids in this document")
        return
    for tid in sorted(per_trace):
        cp = critical_path(per_trace[tid])
        total = cp["total"]
        out(f"== trace {tid}  (end-to-end {1e3 * total:.2f} ms, "
            f"{len(per_trace[tid])} spans) ==")
        out(f"  {'span':<28} {'ms':>10} {'% e2e':>7}")
        rows = sorted(cp["attribution"].items(),
                      key=lambda kv: -kv[1])
        for name, sec in rows:
            pct = 100.0 * sec / max(total, 1e-12)
            out(f"  {name:<28} {1e3 * sec:>10.3f} {pct:>6.1f}%")
        out(f"  coverage: {100.0 * cp['coverage']:.1f}% of the "
            "end-to-end window attributed to named spans")
        out("")


def main_critical_path(argv):
    if not argv:
        raise SystemExit("usage: trace_summary.py critical-path "
                         "<trace.json> [trace_id]")
    doc = load_trace_doc(argv[0])
    trace_id = argv[1] if len(argv) > 1 else None
    print(f"trace document: {argv[0]}")
    summarize_critical_path(doc, trace_id)


def main_health(argv):
    if not argv:
        raise SystemExit("usage: trace_summary.py health "
                         "<telemetry.jsonl | flight.json | dir>...")
    events, flights = load_health(argv)
    summarize_health(events, flights)


def main_xplane(argv):
    path = argv[0] if argv else "/tmp/tpu_trace"
    top_n = int(argv[1]) if len(argv) > 1 else 25
    xs, src = load_xspace(path)
    print(f"trace: {src}")
    for name, wall_ms, totals, counts in summarize(xs, top_n):
        busy_ms = sum(totals.values()) / 1e9
        print(f"\n== plane: {name}  (wall {wall_ms:.2f} ms, "
              f"busy {busy_ms:.2f} ms) ==")
        for op, ps in totals.most_common(top_n):
            ms = ps / 1e9
            pct = 100.0 * ps / max(sum(totals.values()), 1)
            print(f"  {ms:9.3f} ms {pct:5.1f}%  x{counts[op]:<5d} "
                  f"{op[:90]}")


def main_steps(argv):
    path, last_n = steps_argv(argv, "steps")
    steps, ck_summary = load_steps(path, last_n)
    summarize_steps(steps, ck_summary=ck_summary)


SUBCOMMANDS = {
    "steps": main_steps,
    "input": main_input,
    "comm": main_comm,
    "embedding": main_embedding,
    "profile": main_profile,
    "health": main_health,
    "serving": main_serving,
    "fleet": main_fleet,
    "slo": main_slo,
    "autoscale": main_autoscale,
    "goodput": main_goodput,
    "critical-path": main_critical_path,
    "xplane": main_xplane,
}


def main():
    argv = sys.argv[1:]
    if argv and argv[0] in SUBCOMMANDS:
        SUBCOMMANDS[argv[0]](argv[1:])
    else:           # back-compat: bare path = xplane trace dir
        main_xplane(argv)


if __name__ == "__main__":
    try:
        main()
    except BrokenPipeError:     # `... | head` closed the pipe mid-table
        sys.exit(0)
