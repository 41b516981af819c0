"""Render a directory of BENCH_r* driver results into one table.

The driver banked one ``BENCH_rNN.json`` per round.  The shapes are
heterogeneous — it kept whatever the round produced:

  * chip rounds carry ``parsed`` (the final JSON line of ``bench.py``:
    metric/value/unit/vs_baseline),
  * failed rounds carry ``rc != 0`` and a log tail,
  * CPU rounds (``"proxy": true``) carry per-smoke result objects
    (perf_proxy_smoke, input_smoke, compose, decode, rec) — counts,
    not device numbers.

(Most rounds of that era were deleted in PR 21 — NOTES.md summarises
them; the measured state of the system is PERF.md / PERF_LEDGER.jsonl.)

This script folds all of them into one chronological table — round,
mode (hardware / proxy / FAILED), and a one-line headline metric —
so the performance trajectory reads at a glance instead of ten ad-hoc
``jq`` invocations.  ``--markdown`` emits the same table as GitHub
markdown for docs/performance.md; ``--json`` emits the NORMALIZED rows
(:func:`normalize_rounds` — every schema, r01 hardware through the
divergent r08 ``configs`` / r09 ``decode_throughput`` / r10
``lookup_exchange`` shapes, flattened to one ``{round, date, mode,
metrics}`` form) for the regression sentinel
(``bigdl_tpu/observability/regress.py``).

    python scripts/bench_trend.py                # repo-root BENCH_r*.json
    python scripts/bench_trend.py --markdown
    python scripts/bench_trend.py --json
    python scripts/bench_trend.py /path/with/benches

CPU-only, stdlib-only.
"""
import glob
import json
import os
import re
import sys


def load_rounds(root):
    """[(round_number, path, doc)] sorted by round number; corrupt
    files become (n, path, None) rows rather than aborting the table."""
    out = []
    for p in sorted(glob.glob(os.path.join(root, "BENCH_r*.json"))):
        m = re.search(r"BENCH_r(\d+)\.json$", p)
        if not m:
            continue
        n = int(m.group(1))
        try:
            with open(p) as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError):
            doc = None
        out.append((n, p, doc))
    out.sort(key=lambda r: r[0])
    return out


def _tail_date(doc):
    """Window date scraped from the log tail's timestamps (the only
    place wedged rounds record when they ran); '' when absent."""
    m = re.search(r"(\d{4}-\d{2}-\d{2})", str(doc.get("tail", "")))
    return m.group(1) if m else ""


def headline(doc):
    """One-line summary of whatever this round measured."""
    if doc is None:
        return "unreadable result file"
    if doc.get("rc", 0) != 0:
        tail = doc.get("tail", "")
        if "liveness probe" in tail:
            return "backend unreachable (liveness-probe timeout)"
        return f"FAILED rc={doc.get('rc')}"
    parsed = doc.get("parsed")
    if isinstance(parsed, dict) and parsed.get("metric") \
            and parsed.get("value") is not None:
        line = f"{parsed['metric']} {parsed['value']:g}"
        if parsed.get("unit"):
            line += f" {parsed['unit']}"
        if parsed.get("vs_baseline") is not None:
            line += f" ({parsed['vs_baseline']:g}x vs baseline)"
        return line
    if isinstance(parsed, dict) and parsed.get("metric"):
        keys = [k for k in ("bucketed_drop", "zero1_drop", "ok")
                if k in parsed]
        return parsed["metric"] + (
            " " + " ".join(f"{k}={parsed[k]}" for k in keys)
            if keys else "")
    dt = doc.get("decode_throughput")
    if isinstance(dt, dict):
        return (f"decode {dt.get('continuous_tokens_per_s', 0):g} tok/s "
                f"continuous ({dt.get('speedup', 0):g}x vs static), "
                f"recompiles={dt.get('recompiles')}")
    if doc.get("bench") == "compose_proxy_smoke":
        cfgs = doc.get("configs", {})
        blocked = sum(1 for c in cfgs.values()
                      if isinstance(c, dict) and c.get("status"))
        return (f"compose_proxy_smoke: {len(cfgs)} configs, "
                f"{len(cfgs) - blocked} measured, {blocked} blocked")
    if doc.get("metric") == "rec_smoke":
        lx = doc.get("lookup_exchange", {})
        return (f"rec_smoke dedup_ratio="
                f"{lx.get('dedup_ratio', 0):.3f} "
                f"int8_table_ratio="
                f"{doc.get('table_bytes', {}).get('ratio', 0):g}x "
                f"ok={doc.get('ok')}")
    # note-only proxy rounds (e.g. input_smoke): first clause of the note
    note = doc.get("note", "")
    m = re.search(r"input-stall fraction ([\d.]+%)", note)
    if m:
        return f"input_smoke stall={m.group(1)} (vs baseline in note)"
    if note:
        return note.split(";")[0][:72]
    return os.path.basename(str(doc.get("cmd", "?")))


def mode(doc):
    if doc is None:
        return "?"
    if doc.get("rc", 0) != 0:
        return "FAILED"
    return "proxy" if doc.get("proxy") else "hardware"


def _flat_metrics(doc):
    """Pull the numeric measurements out of ONE round doc, whatever its
    schema, as a flat ``{name: value}`` dict.  This is where the
    divergent r08/r09/r10 shapes stop being special: ``configs``
    (compose_proxy_smoke), ``decode_throughput``/``churn``/
    ``weight_stream`` (decode_smoke) and ``lookup_exchange``/
    ``table_bytes``/``two_tower``/``grad_update_bytes`` (rec_smoke)
    all flatten to dotted keys next to the r01–r07 ``parsed`` ones."""
    out = {}

    def take(prefix, obj):
        if isinstance(obj, dict):
            for k, v in obj.items():
                take(f"{prefix}.{k}" if prefix else str(k), v)
        elif isinstance(obj, bool):
            out[prefix] = 1.0 if obj else 0.0
        elif isinstance(obj, (int, float)):
            out[prefix] = float(obj)

    parsed = doc.get("parsed")
    if isinstance(parsed, dict):
        take("", {k: v for k, v in parsed.items()
                  if k not in ("metric", "unit", "proxy")})
    for section in ("decode_throughput", "churn", "weight_stream",
                    "lookup_exchange", "table_bytes", "two_tower",
                    "grad_update_bytes"):
        if isinstance(doc.get(section), dict):
            take(section, doc[section])
    cfgs = doc.get("configs")
    if isinstance(cfgs, dict):        # r08: per-config sub-docs
        out["configs.total"] = float(len(cfgs))
        out["configs.blocked"] = float(sum(
            1 for c in cfgs.values()
            if isinstance(c, dict) and c.get("status")))
        out["configs.measured"] = out["configs.total"] \
            - out["configs.blocked"]
        for cname, c in cfgs.items():
            if isinstance(c, dict):
                take(f"configs.{cname}",
                     {k: v for k, v in c.items()
                      if k not in ("status", "detail")})
    if "ok" in doc:
        out["ok"] = 1.0 if doc.get("ok") else 0.0
    return out


def normalize_rounds(rounds):
    """Fold heterogeneous ``load_rounds`` output into one row shape per
    round: ``{"round", "date", "mode", "metric", "headline",
    "metrics"}`` — the trajectory schema the regression sentinel
    consumes.  Wedged/corrupt rounds keep a row (``mode`` FAILED/?, an
    empty metrics dict) so the trajectory shows the gap instead of
    silently skipping it."""
    rows = []
    for n, path, doc in rounds:
        if doc is None:
            rows.append({"round": n, "date": "", "mode": "?",
                         "metric": None, "headline":
                         "unreadable result file", "metrics": {}})
            continue
        parsed = doc.get("parsed")
        metric = (parsed.get("metric") if isinstance(parsed, dict)
                  else None) or doc.get("metric") or doc.get("bench")
        if metric is None and doc.get("cmd"):
            # r09 shape: no metric key anywhere; the smoke script's
            # basename is the stable identity ("decode_smoke")
            metric = os.path.splitext(
                os.path.basename(str(doc["cmd"]).split()[-1]))[0]
        rows.append({
            "round": n,
            "date": _tail_date(doc),
            "mode": mode(doc),
            "metric": metric,
            "headline": headline(doc),
            "metrics": {} if doc.get("rc", 0) != 0 else _flat_metrics(doc),
        })
    return rows


def render(rounds, markdown=False, out=print):
    if not rounds:
        out("no BENCH_r*.json files found")
        return
    rows = [(f"r{n:02d}", _tail_date(doc) if doc else "",
             mode(doc), headline(doc)) for n, _, doc in rounds]
    if markdown:
        out("| round | date | mode | headline |")
        out("|-------|------|------|----------|")
        for r, d, m, h in rows:
            out(f"| {r} | {d or '-'} | {m} | {h} |")
    else:
        out(f"{'round':<6} {'date':<11} {'mode':<9} headline")
        for r, d, m, h in rows:
            out(f"{r:<6} {d or '-':<11} {m:<9} {h}")
        n_hw = sum(1 for _, _, m, _ in rows if m == "hardware")
        n_px = sum(1 for _, _, m, _ in rows if m == "proxy")
        n_bad = sum(1 for _, _, m, _ in rows if m == "FAILED")
        out(f"\n{len(rows)} rounds: {n_hw} hardware, {n_px} proxy, "
            f"{n_bad} failed (proxy = counts from a CPU run, not "
            "device numbers)")


def main():
    argv = sys.argv[1:]
    markdown = "--markdown" in argv
    as_json = "--json" in argv
    argv = [a for a in argv if a not in ("--markdown", "--json")]
    root = argv[0] if argv else os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "..")
    rounds = load_rounds(root)
    if as_json:
        print(json.dumps(normalize_rounds(rounds), indent=2,
                         sort_keys=True))
    else:
        render(rounds, markdown=markdown)


if __name__ == "__main__":
    try:
        main()
    except BrokenPipeError:
        sys.exit(0)
