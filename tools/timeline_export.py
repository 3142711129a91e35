#!/usr/bin/env python
"""Export the unified run timeline as Chrome-trace JSON.

Merges every wall-clock stream a run left behind — span trace, round
ledger, request trace, ingest pipeline events, sweep sub-fleet rounds —
onto one monotonic clock (obs/timeline.py) and writes a
``trace_events`` document that Perfetto (https://ui.perfetto.dev) and
``chrome://tracing`` open directly.

  --trace-dir DIR   a tpu_trace directory; scanned for
                    spans-/ledger-/reqtrace-/events-*.jsonl
  --ledger PATH     one explicit round-ledger JSONL (added to the scan)
  --out PATH        output path (default: <trace-dir>/timeline.json,
                    or ./timeline.json without a trace dir)
  --pretty          indent the JSON (bigger file, diffable)

Exit code 0 iff at least one lane folded data; 2 when every input was
empty or missing (nothing to look at — the artifact is still written
so a pipeline step stays idempotent).
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="merge run telemetry into Chrome-trace JSON")
    ap.add_argument("--trace-dir", default="")
    ap.add_argument("--ledger", default="")
    ap.add_argument("--out", default="")
    ap.add_argument("--pretty", action="store_true")
    args = ap.parse_args(argv)

    from lightgbm_tpu.obs import timeline

    doc = timeline.build_timeline(args.trace_dir or None,
                                  args.ledger or None)
    out = args.out or os.path.join(args.trace_dir or ".",
                                   "timeline.json")
    if args.pretty:
        tmp = out + ".tmp"
        with open(tmp, "w") as fh:
            json.dump(doc, fh, indent=1, sort_keys=True, default=str)
        os.replace(tmp, out)
    else:
        timeline.write_timeline(out, doc)

    lanes = timeline.lane_counts(doc)
    populated = {k: v for k, v in sorted(lanes.items()) if v}
    n_ev = len(doc.get("traceEvents", []))
    log(f"# timeline: {out} ({n_ev} events; lanes: "
        f"{populated or 'NONE'})")
    if not timeline.has_data(doc):
        log("# no lane has data (need --trace-dir/--ledger "
            "pointing at a traced run)")
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
