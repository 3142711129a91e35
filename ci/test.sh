#!/usr/bin/env bash
# CI harness (the reference's .ci/test.sh analogue): native build, package
# install smoke test, then the fast test tier on a virtual 8-device CPU
# mesh. Usage: ci/test.sh [fast|full|install]
set -euo pipefail
cd "$(dirname "$0")/.."

MODE="${1:-fast}"

echo "== native build =="
make -C src/native
python - <<'EOF'
from lightgbm_tpu import native
assert native.native_available(), "native .so failed to load"
print("native helpers: ok")
EOF

if [ "$MODE" = "install" ] || [ "$MODE" = "full" ]; then
    echo "== pip install smoke test (wheel build + target install) =="
    TGT="$(mktemp -d)"
    # --no-build-isolation: CI images are airgapped; setuptools is baked in
    pip install -q . --target "$TGT" --no-deps --no-build-isolation
    # the build hook must stage native sources into build_lib only — an
    # in-tree lightgbm_tpu/_native_src/ means staging leaked into the
    # checkout (regression guard for the setup.py staging path)
    if [ -e lightgbm_tpu/_native_src ]; then
        echo "FAIL: pip install staged lightgbm_tpu/_native_src in-tree" >&2
        exit 1
    fi
    PKGTEST_TARGET="$TGT" python - <<'EOF'
import os
import sys
sys.path.insert(0, os.environ["PKGTEST_TARGET"])
import numpy as np
import lightgbm_tpu as lgb
assert os.environ["PKGTEST_TARGET"] in lgb.__file__, lgb.__file__
rng = np.random.RandomState(0)
X = rng.rand(400, 5)
y = (X[:, 0] + 0.2 * rng.randn(400) > 0.5).astype(float)
bst = lgb.train({"objective": "binary", "num_leaves": 7, "verbosity": -1},
                lgb.Dataset(X, label=y), num_boost_round=10)
p = bst.predict(X)
assert p.shape == (400,) and np.all((p >= 0) & (p <= 1))
s = bst.model_to_string()
p2 = lgb.Booster(model_str=s).predict(X)
np.testing.assert_allclose(p, p2, rtol=1e-6)
from lightgbm_tpu import native
assert native.native_available(), "installed package lost native helpers"
print("install smoke test: ok")
EOF
    rm -rf "$TGT"
fi

echo "== telemetry smoke (5 traced rounds -> schema-validated ledger) =="
TRACE_DIR="${CI_ARTIFACT_DIR:-$(mktemp -d)}/lgbt_trace"
LGBT_SMOKE_TRACE_DIR="$TRACE_DIR" python - <<'EOF'
import glob
import os

import numpy as np

import lightgbm_tpu as lgb
from lightgbm_tpu.obs import ledger as obs_ledger

tdir = os.environ["LGBT_SMOKE_TRACE_DIR"]
rng = np.random.RandomState(7)
X = rng.rand(600, 8)
y = (X[:, 0] + 0.3 * rng.randn(600) > 0.5).astype(float)
bst = lgb.train({"objective": "binary", "num_leaves": 15, "verbosity": -1,
                 "tpu_trace": True, "tpu_trace_dir": tdir},
                lgb.Dataset(X, label=y), num_boost_round=5)
paths = sorted(glob.glob(os.path.join(tdir, "ledger-*.jsonl")))
assert paths, f"no ledger written under {tdir}"
recs = obs_ledger.read_ledger(paths[-1])
for rec in recs:
    obs_ledger.validate_record(rec)
rounds = [r for r in recs if r["kind"] == "round"]
assert [r["round"] for r in rounds] == list(range(5)), rounds
assert recs[0]["kind"] == "run" and "config_sig" in recs[0], recs[0]
print(f"telemetry smoke: ok ({len(recs)} records, 5 rounds, "
      f"ledger at {paths[-1]})")
EOF
if [ -n "${CI_ARTIFACT_DIR:-}" ]; then
    echo "telemetry ledger kept under $TRACE_DIR for artifact upload"
else
    rm -rf "$(dirname "$TRACE_DIR")"
fi

echo "== kill-and-resume smoke (SIGTERM mid-run -> exit 75 -> resume) =="
RES_DIR="${CI_ARTIFACT_DIR:-$(mktemp -d)}/lgbt_resume"
mkdir -p "$RES_DIR"
python - <<EOF
import numpy as np
rng = np.random.RandomState(11)
X = rng.rand(20000, 20).astype(np.float32)
y = (X[:, 0] + 0.3 * rng.randn(20000) > 0.5).astype(np.float32)
np.savetxt("$RES_DIR/train.tsv",
           np.column_stack([y, X]), delimiter="\t", fmt="%.6g")
EOF
CLI_ARGS="task=train data=$RES_DIR/train.tsv objective=binary
          num_leaves=31 num_iterations=30 verbosity=-1
          output_model=$RES_DIR/model.txt
          tpu_checkpoint_dir=$RES_DIR/ckpt tpu_checkpoint_freq=5
          tpu_trace=true tpu_trace_dir=$RES_DIR/trace"
# shellcheck disable=SC2086
python -m lightgbm_tpu $CLI_ARGS > "$RES_DIR/run1.log" 2>&1 &
CLI_PID=$!
# wait until the round loop is demonstrably running (>=3 committed round
# records), then preempt it with a real external SIGTERM
for _ in $(seq 1 240); do
    N=$(grep -hc '"kind": "round"' "$RES_DIR"/trace/ledger-*.jsonl \
        2>/dev/null || true)
    [ "${N:-0}" -ge 3 ] && break
    sleep 0.25
done
kill -TERM "$CLI_PID"
set +e
wait "$CLI_PID"
RC1=$?
set -e
if [ "$RC1" -ne 75 ]; then
    echo "FAIL: preempted CLI run exited $RC1 (want 75)" >&2
    cat "$RES_DIR/run1.log" >&2
    exit 1
fi
# rerun the SAME command: it must auto-resume and finish cleanly
# shellcheck disable=SC2086
python -m lightgbm_tpu $CLI_ARGS > "$RES_DIR/run2.log" 2>&1
RES_SMOKE_DIR="$RES_DIR" python - <<'EOF'
import glob
import os

from lightgbm_tpu.obs import ledger as obs_ledger

tdir = os.path.join(os.environ["RES_SMOKE_DIR"], "trace")
paths = sorted(glob.glob(os.path.join(tdir, "ledger-*.jsonl")),
               key=os.path.getmtime)
assert len(paths) >= 2, f"want two run ledgers, got {paths}"
rounds = []
for p in paths[-2:]:
    rounds.extend(r["round"] for r in obs_ledger.read_ledger(p)
                  if r["kind"] == "round")
assert sorted(rounds) == list(range(30)), \
    f"killed+resumed ledgers must cover rounds 0..29 exactly once: " \
    f"{sorted(rounds)}"
resumed = [r for r in obs_ledger.read_ledger(paths[-1])
           if r.get("kind") == "note" and r.get("note") == "resume"]
assert resumed, "resumed run's ledger lacks the resume note"
first_run = [r["round"] for r in obs_ledger.read_ledger(paths[-2])
             if r["kind"] == "round"]
print(f"kill-and-resume smoke: ok (killed after round {max(first_run)}, "
      f"two ledgers cover 30 rounds exactly once)")
EOF
if [ -n "${CI_ARTIFACT_DIR:-}" ]; then
    echo "resume artifacts kept under $RES_DIR for artifact upload"
else
    rm -rf "$(dirname "$RES_DIR")"
fi

echo "== distributed smoke (8 emulated devices, tree_learner=data, byte-equal vs serial) =="
DIST_DIR="${CI_ARTIFACT_DIR:-$(mktemp -d)}/lgbt_dist"
mkdir -p "$DIST_DIR"
python - <<EOF
import numpy as np
rng = np.random.RandomState(23)
X = rng.rand(4000, 12).astype(np.float32)
y = (X[:, 0] + 0.3 * rng.randn(4000) > 0.5).astype(np.float32)
np.savetxt("$DIST_DIR/train.tsv",
           np.column_stack([y, X]), delimiter="\t", fmt="%.6g")
EOF
# the shared leg of both runs; tpu_use_f64_hist pins histogram
# accumulation to order-independent f64 — the byte-equal topology contract
DIST_ARGS="task=train data=$DIST_DIR/train.tsv objective=binary
           num_leaves=15 num_iterations=5 tpu_use_f64_hist=true"
# serial reference on the plain 1-device backend
# shellcheck disable=SC2086
python -m lightgbm_tpu $DIST_ARGS verbosity=-1 tree_learner=serial \
    output_model="$DIST_DIR/serial.txt" > "$DIST_DIR/serial.log" 2>&1
# 4-shard data-parallel run on an 8-device virtual mesh; traced so the
# ledger can be schema-validated, verbose so the dist_* events land in
# the log (the event channel is INFO-level)
# shellcheck disable=SC2086
XLA_FLAGS="--xla_force_host_platform_device_count=8" JAX_PLATFORMS=cpu \
    python -m lightgbm_tpu $DIST_ARGS verbosity=2 tree_learner=data \
    num_machines=4 output_model="$DIST_DIR/dist.txt" tpu_trace=true \
    tpu_trace_dir="$DIST_DIR/trace" > "$DIST_DIR/dist.log" 2>&1
if ! cmp -s "$DIST_DIR/serial.txt" "$DIST_DIR/dist.txt"; then
    echo "FAIL: 4-shard model is not byte-equal to the serial model" >&2
    diff "$DIST_DIR/serial.txt" "$DIST_DIR/dist.txt" | head -20 >&2
    exit 1
fi
DIST_SMOKE_DIR="$DIST_DIR" python - <<'EOF'
import glob
import os

from lightgbm_tpu.obs import ledger as obs_ledger
from lightgbm_tpu.utils.log import parse_event

d = os.environ["DIST_SMOKE_DIR"]
paths = sorted(glob.glob(os.path.join(d, "trace", "ledger-*.jsonl")))
assert paths, f"no ledger written under {d}/trace"
recs = obs_ledger.read_ledger(paths[-1])
for rec in recs:
    obs_ledger.validate_record(rec)
rounds = [r for r in recs if r["kind"] == "round"]
assert [r["round"] for r in rounds] == list(range(5)), rounds
# the dist runtime announced its topology on the event channel
events = [e for e in (parse_event(ln.strip())
                      for ln in open(os.path.join(d, "dist.log")))
          if e]
kinds = {e["event"] for e in events}
assert {"dist_init", "dist_shard"} <= kinds, kinds
init = next(e for e in events if e["event"] == "dist_init")
assert init["shards"] == 4 and init["tree_learner"] == "data", init
print(f"distributed smoke: ok (4-shard model byte-equal, "
      f"{len(recs)} schema-valid ledger records, events={sorted(kinds)})")
EOF
if [ -n "${CI_ARTIFACT_DIR:-}" ]; then
    echo "distributed artifacts kept under $DIST_DIR for artifact upload"
else
    rm -rf "$(dirname "$DIST_DIR")"
fi

echo "== serving smoke (2 models, hot swap under threaded load) =="
SERVE_DIR="${CI_ARTIFACT_DIR:-$(mktemp -d)}/lgbt_serve"
mkdir -p "$SERVE_DIR"
LGBT_SERVE_SMOKE_DIR="$SERVE_DIR" python - <<'EOF'
import json
import os

from lightgbm_tpu.obs import ledger as obs_ledger
from tools.bench_serve_traffic import run

sdir = os.environ["LGBT_SERVE_SMOKE_DIR"]
led_path = os.path.join(sdir, "serve-ledger.jsonl")
trace_dir = os.path.join(sdir, "reqtrace")
ledger = obs_ledger.RoundLedger(led_path, {"smoke": "serving"})
# two resident models; the hot-swap leg fires threaded requests on m0
# while a retrained version swaps in; request tracing is on at
# sample=1.0 so EVERY request must land exactly one trace row
res = run(models=2, qps_list=(25, 100), open_secs=1.0, closed_secs=1.0,
          clients=16, train_rows=1500, train_rounds=20, ledger=ledger,
          verbose=True, trace_dir=trace_dir, trace_sample=1.0)
ledger.close()

# zero failed requests anywhere — closed loops, QPS sweep, swap leg
assert res["serve_hot_swap"]["requests_failed"] == 0, res["serve_hot_swap"]
assert res["serve_hot_swap"]["requests_ok"] > 0
assert res["serve_hot_swap"]["version_after"] == "v2"
assert res["serve_closed_failures"] == 0
assert all(q["failures"] == 0 for q in res["serve_qps_sweep"])

# exactly-once swap note on the ledger (schema-validated)
recs = obs_ledger.read_ledger(led_path)
for rec in recs:
    obs_ledger.validate_record(rec)
swaps = [r for r in recs
         if r.get("kind") == "note" and r.get("note") == "serve_swap"]
assert len(swaps) == 1, f"want exactly one serve_swap note, got {swaps}"
loads = [r for r in recs
         if r.get("kind") == "note" and r.get("note") == "serve_load"]
assert len(loads) == 2, f"want two serve_load notes, got {loads}"

# schema-valid traffic record: QPS sweep with latency percentiles on
# both resident models, and coalescing must beat per-request dispatch
assert res["serve_models"] == 2
assert len(res["serve_qps_sweep"]) >= 2
for q in res["serve_qps_sweep"]:
    assert isinstance(q["qps_target"], int)
    assert q["p50_ms"] > 0 and q["p99_ms"] >= q["p50_ms"]
for k in ("serve_direct_rows_s", "serve_coalesced_rows_s",
          "serve_fill_ratio", "serve_resident_bytes"):
    assert isinstance(res[k], (int, float)) and res[k] > 0, (k, res[k])
assert res["coalesced_vs_direct"] > 1.0, res["coalesced_vs_direct"]
assert res["serve_swaps"] == 1

# request tracing: N threaded requests through the live hot swap must
# yield exactly N trace rows — no losses, no duplicates
import glob
tr = res["serve_trace"]
assert tr["started"] == tr["finished"] == res["serve_requests"], tr
trace_files = glob.glob(os.path.join(trace_dir, "reqtrace-*.jsonl"))
assert len(trace_files) == 1, trace_files
rows = [json.loads(ln) for ln in open(trace_files[0])]
reqs = [r for r in rows if r["kind"] == "request"]
assert len(reqs) == res["serve_requests"], \
    (len(reqs), res["serve_requests"])
ids = [r["trace_id"] for r in reqs]
assert len(set(ids)) == len(ids), "duplicate trace rows"
assert all(r["flush_reason"] in ("full", "deadline") for r in reqs)
assert all(r["queue_wait_ms"] is not None and r["queue_wait_ms"] >= 0
           for r in reqs)
assert all(r["status"] == "ok" for r in reqs)
# the swap shows up as a marker row interleaved in the same stream
assert any(r["kind"] == "marker" and r["marker"] == "serve_swap"
           for r in rows)

out_path = os.path.join(sdir, "serve_traffic.json")
with open(out_path, "w") as fh:
    json.dump(res, fh, sort_keys=True)
print(f"serving smoke: ok (coalesced/direct="
      f"{res['coalesced_vs_direct']}x, "
      f"{res['serve_hot_swap']['requests_ok']} requests through the "
      f"swap, {len(reqs)} trace rows exactly-once, record at {out_path})")
EOF
if [ -n "${CI_ARTIFACT_DIR:-}" ]; then
    echo "serving artifacts kept under $SERVE_DIR for artifact upload"
else
    rm -rf "$(dirname "$SERVE_DIR")"
fi

echo "== metrics scrape smoke (task=serve + live /metrics endpoint) =="
MET_DIR="${CI_ARTIFACT_DIR:-$(mktemp -d)}/lgbt_metrics"
mkdir -p "$MET_DIR"
LGBT_MET_DIR="$MET_DIR" python - <<'EOF'
import os

import numpy as np

import lightgbm_tpu as lgb

mdir = os.environ["LGBT_MET_DIR"]
rng = np.random.RandomState(5)
X = rng.rand(900, 6).astype(np.float32)
y = (X[:, 0] + 0.3 * rng.randn(900) > 0.5).astype(np.float32)
bst = lgb.train({"objective": "binary", "num_leaves": 15, "verbosity": -1},
                lgb.Dataset(X, label=y), num_boost_round=10)
bst.save_model(os.path.join(mdir, "model.txt"))
np.savetxt(os.path.join(mdir, "rows.tsv"),
           np.column_stack([y[:500], X[:500]]), delimiter="\t", fmt="%.6g")
EOF
MET_PORT=$(python - <<'EOF'
import socket
s = socket.socket()
s.bind(("127.0.0.1", 0))
print(s.getsockname()[1])
s.close()
EOF
)
# serve the model, score rows through the coalescer, then hold the
# process up so the scrape sees a LIVE endpoint mid-serve. Request
# tracing is on with sample=0 and a deliberately tiny SLO: every
# request breaches, so tail sampling alone must keep 100% of them
# (500 rows / 64-row requests = 8 requests, all slow-injected).
python -m lightgbm_tpu task=serve "input_model=m=$MET_DIR/model.txt" \
    "data=$MET_DIR/rows.tsv" "output_result=$MET_DIR/preds.txt" \
    "tpu_serve_metrics_port=$MET_PORT" tpu_serve_hold_s=60 \
    tpu_serve_trace=true "tpu_serve_trace_dir=$MET_DIR/reqtrace" \
    tpu_serve_trace_sample=0 tpu_serve_slo_ms=0.0001 \
    tpu_serve_max_batch_rows=64 \
    verbosity=-1 > "$MET_DIR/serve.log" 2>&1 &
SERVE_PID=$!
for _ in $(seq 1 240); do
    grep -q '^Holding' "$MET_DIR/serve.log" 2>/dev/null && break
    sleep 0.25
done
LGBT_MET_DIR="$MET_DIR" LGBT_MET_PORT="$MET_PORT" python - <<'EOF'
import glob
import json
import os
import urllib.request

mdir = os.environ["LGBT_MET_DIR"]
port = os.environ["LGBT_MET_PORT"]
base = f"http://127.0.0.1:{port}"
with urllib.request.urlopen(base + "/metrics", timeout=10) as resp:
    assert resp.status == 200
    assert resp.headers["Content-Type"].startswith("text/plain"), \
        resp.headers["Content-Type"]
    text = resp.read().decode()


def series(name):
    vals = [ln.split()[-1] for ln in text.splitlines()
            if ln.startswith(name) and not ln.startswith("#")]
    assert vals, f"{name} missing from /metrics:\n{text[:2000]}"
    return float(vals[0])


# request counters moved during the data pass
assert series("serve_requests_total") > 0
assert series("serve_rows_total") >= 500
assert series("serve_batches_total") > 0
# latency histogram: bucket series + interpolated percentiles per model
assert 'serve_request_latency_ms_bucket{model="m",le="+Inf"}' in text
assert series('serve_request_latency_ms_count{model="m"}') > 0
p50 = series('serve_request_latency_ms_p50{model="m"}')
p99 = series('serve_request_latency_ms_p99{model="m"}')
assert 0 < p50 <= p99, (p50, p99)
assert 0 < series("serve_batch_fill_ratio") <= 1.0
# HBM accountant gauges (claimed/peak always publish; bytes_in_use is
# backend-dependent and absent on the CPU CI backend)
assert series("serve_model_loads_total") >= 1
assert series("serve_model_evictions_total") >= 0   # registered, live
assert series("serve_model_swaps_total") >= 0
assert series("hbm_claimed_total_bytes") > 0
assert series("hbm_peak_claimed_bytes") >= series("hbm_claimed_total_bytes")
assert 'hbm_claimed_bytes{owner="serving/registry_pool"}' in text

# the JSON view carries the same registry under a versioned schema
with urllib.request.urlopen(base + "/metrics.json", timeout=10) as resp:
    doc = json.load(resp)
assert doc["schema"] == 1, doc.get("schema")
assert doc["metrics"]["counters"]["serve_requests_total"] > 0
assert doc["memory"]["claimed_bytes"] > 0
assert "hbm_unattributed_bytes" in doc["memory"]
hist = doc["metrics"]["histograms"]['serve_request_latency_ms{model="m"}']
assert hist["count"] > 0 and hist["p99_ms"] is not None
# per-model AOT/compact detail rides the same JSON view (no artifact
# and no compact plan in this smoke: zeros, but the fields must exist)
srv = doc["serving"]["models"]["m"]
assert srv["compact"]["plan"] == "off", srv
assert srv["compact"]["f32_bytes"] >= srv["compact"]["bytes"] > 0, srv
assert srv["aot"]["buckets"] == 0, srv

# -- request tracing: /debug/requests + tail sampling + exemplars ------
n_req = int(series("serve_requests_total"))
assert n_req == 8, n_req          # 500 rows / 64-row requests
with urllib.request.urlopen(base + "/debug/requests", timeout=10) as resp:
    dbg = json.load(resp)
assert dbg["enabled"] is True
assert dbg["totals"]["started"] == dbg["totals"]["finished"] == n_req
ring_reqs = [r for r in dbg["recent"] if r["kind"] == "request"]
ring_ids = [r["trace_id"] for r in ring_reqs]
# every submitted request appears exactly once in the live ring
assert len(ring_ids) == len(set(ring_ids)) == n_req, ring_ids
assert dbg["slow"], "slow-request table empty"
# the tiny SLO slow-injected every request: tail sampling at sample=0
# must keep 100% of them in the JSONL, flush reason + queue wait set
trace_files = glob.glob(os.path.join(mdir, "reqtrace",
                                     "reqtrace-*.jsonl"))
assert len(trace_files) == 1, trace_files
jrows = [json.loads(ln) for ln in open(trace_files[0])]
jreqs = [r for r in jrows if r["kind"] == "request"]
assert len(jreqs) == n_req, (len(jreqs), n_req)
assert all(r["slo_breach"] for r in jreqs)
assert all(r["flush_reason"] in ("full", "deadline") for r in jreqs)
assert all(r["queue_wait_ms"] is not None for r in jreqs)
assert set(r["trace_id"] for r in jreqs) == set(ring_ids)
# SLO instruments: all-breaching traffic pins the burn gauge at 1.0
assert series('serve_slo_burn_rate{model="m"}') == 1.0
assert series('serve_slo_breaches_total{model="m"}') == n_req
assert series('serve_requests_completed_total{model="m",status="ok"}') \
    == n_req
# p99 histogram exemplars resolve to trace IDs present in the JSONL
assert " # {trace_id=" in text, "no exemplar on any _bucket line"
exemplars = hist.get("exemplars") or {}
assert exemplars, "latency histogram carries no exemplars"
jids = {r["trace_id"] for r in jreqs}
for le, ex in exemplars.items():
    assert ex["trace_id"] in jids, (le, ex)
with open(os.path.join(mdir, "metrics_snapshot.json"), "w") as fh:
    json.dump(doc, fh, sort_keys=True)
print(f"metrics scrape smoke: ok ({n_req} "
      f"requests, p50={p50:.3g}ms p99={p99:.3g}ms, "
      f"{len(jreqs)} tail-kept trace rows, "
      f"{len(exemplars)} exemplars resolved, "
      f"claimed={int(series('hbm_claimed_total_bytes'))}B)")
EOF
kill -INT "$SERVE_PID" 2>/dev/null || true
set +e
wait "$SERVE_PID"
SERVE_RC=$?
set -e
if [ "$SERVE_RC" -ne 0 ]; then
    echo "FAIL: held serve process exited $SERVE_RC (want clean 0)" >&2
    cat "$MET_DIR/serve.log" >&2
    exit 1
fi

# trace_report merges the request JSONL + metrics snapshot into a
# ranked slow-request report (exit 0 with data; 2 would fail the gate)
python tools/trace_report.py --reqtrace "$MET_DIR/reqtrace" \
    --metrics "$MET_DIR/metrics_snapshot.json" \
    --json "$MET_DIR/trace_report.json"
LGBT_MET_DIR="$MET_DIR" python - <<'EOF'
import json
import os

rep = json.load(open(os.path.join(os.environ["LGBT_MET_DIR"],
                                  "trace_report.json")))
assert rep["schema"] == 1
assert rep["totals"]["requests"] == 8, rep["totals"]
assert rep["models"] and rep["models"][0]["model"] == "m"
slow = rep["slow_requests"]
assert slow, "report has no ranked slow requests"
lat = [r["total_ms"] for r in slow]
assert lat == sorted(lat, reverse=True), "slow requests not ranked"
assert all(e["resolved"] for e in rep["exemplars"]), rep["exemplars"]
print(f"trace report: ok ({len(slow)} ranked, "
      f"{len(rep['exemplars'])} exemplars resolved)")
EOF
if [ -n "${CI_ARTIFACT_DIR:-}" ]; then
    echo "metrics artifacts kept under $MET_DIR for artifact upload"
else
    rm -rf "$(dirname "$MET_DIR")"
fi

echo "== front door smoke (task=serve HTTP scoring: QoS shed, hot swap, placement) =="
FD_DIR="${CI_ARTIFACT_DIR:-$(mktemp -d)}/lgbt_frontdoor"
mkdir -p "$FD_DIR"
# two boosters: the checkpoint-served model (gold class, hot-swapped
# live) and a bulk model (bronze) for the forced-overload leg; a v2 of
# the checkpoint model stages the mid-traffic swap
LGBT_FD_DIR="$FD_DIR" python - <<'EOF'
import os

import numpy as np

import lightgbm_tpu as lgb
from lightgbm_tpu.sweep.refresh import write_serving_checkpoint

fdir = os.environ["LGBT_FD_DIR"]
rng = np.random.RandomState(11)
X = rng.rand(1200, 6).astype(np.float32)
y = (X[:, 0] + 0.3 * rng.randn(1200) > 0.5).astype(np.float32)
texts = []
for seed in (0, 1, 2):
    bst = lgb.train({"objective": "binary", "num_leaves": 15,
                     "verbosity": -1, "seed": seed,
                     "feature_fraction": 0.9,
                     "feature_fraction_seed": seed + 1},
                    lgb.Dataset(X, label=y), num_boost_round=10)
    texts.append(bst.model_to_string())
with open(os.path.join(fdir, "bulk.txt"), "w") as fh:
    fh.write(texts[1])
with open(os.path.join(fdir, "v2.txt"), "w") as fh:
    fh.write(texts[2])
assert write_serving_checkpoint(os.path.join(fdir, "ckpt"),
                                texts[0]) == "ckpt_000001"
EOF
FD_PORT=$(python - <<'EOF'
import socket
s = socket.socket()
s.bind(("127.0.0.1", 0))
print(s.getsockname()[1])
s.close()
EOF
)
FD_MET_PORT=$(python - <<'EOF'
import socket
s = socket.socket()
s.bind(("127.0.0.1", 0))
print(s.getsockname()[1])
s.close()
EOF
)
# 4 emulated devices so the placer is live; the tiny SLO makes every
# request an SLO breach, so the bronze model's burn rate saturates and
# admission MUST shed it under overload — while the gold-class
# checkpoint model is never shed by contract
XLA_FLAGS="--xla_force_host_platform_device_count=4" JAX_PLATFORMS=cpu \
python -m lightgbm_tpu task=serve \
    "input_model=bulk_m=$FD_DIR/bulk.txt" \
    "tpu_checkpoint_dir=$FD_DIR/ckpt" \
    "tpu_serve_port=$FD_PORT" \
    "tpu_serve_qos=checkpoint:gold,default:bronze" \
    "tpu_serve_metrics_port=$FD_MET_PORT" \
    tpu_serve_devices=4 tpu_serve_replicas=2 \
    tpu_serve_trace=true tpu_serve_slo_ms=0.0001 \
    tpu_serve_watch_interval_s=0.2 \
    tpu_serve_max_batch_wait_ms=1 tpu_serve_max_batch_rows=2048 \
    tpu_serve_hold_s=300 \
    verbosity=-1 > "$FD_DIR/serve.log" 2>&1 &
FD_PID=$!
for _ in $(seq 1 240); do
    grep -q '^Holding' "$FD_DIR/serve.log" 2>/dev/null && break
    sleep 0.25
done
grep -q '^Scoring: POST' "$FD_DIR/serve.log"
LGBT_FD_DIR="$FD_DIR" LGBT_FD_PORT="$FD_PORT" \
LGBT_FD_MET_PORT="$FD_MET_PORT" python - <<'EOF'
import http.client
import json
import os
import re
import threading
import time
import urllib.request

import numpy as np

fdir = os.environ["LGBT_FD_DIR"]
port = int(os.environ["LGBT_FD_PORT"])
met = f"http://127.0.0.1:{os.environ['LGBT_FD_MET_PORT']}"
rng = np.random.RandomState(3)
body = json.dumps({"rows": rng.rand(16, 6).tolist()}).encode()
one_row = json.dumps({"rows": rng.rand(1, 6).tolist()}).encode()


def post(conn, model, payload=body):
    conn.request("POST", f"/v1/score/{model}", body=payload,
                 headers={"Content-Type": "application/json"})
    resp = conn.getresponse()
    return resp.status, resp.read()


def healthz():
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=10) as resp:
        assert resp.status == 200
        return json.load(resp)


def scrape():
    with urllib.request.urlopen(met + "/metrics", timeout=10) as resp:
        return resp.read().decode()


def closed_loop(model, clients, secs):
    """clients threads, keep-alive connections; returns (n_ok, codes)."""
    stop = time.perf_counter() + secs
    codes = {}
    lock = threading.Lock()

    def worker():
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
        try:
            while time.perf_counter() < stop:
                status, _ = post(conn, model)
                with lock:
                    codes[status] = codes.get(status, 0) + 1
        finally:
            conn.close()

    threads = [threading.Thread(target=worker) for _ in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return codes.get(200, 0), codes


# -- /healthz schema ----------------------------------------------------
doc = healthz()
assert doc["schema"] == 1 and doc["status"] == "ok"
assert sorted(doc["models"]) == ["bulk_m", "checkpoint"]
assert doc["qos"] == {"checkpoint": "gold", "default": "bronze"}
assert doc["devices"] == 4
for key in ("shedding", "admission", "replicas", "placement"):
    assert key in doc, key

# -- coalesced socket throughput >= 3x single-request sockets ----------
n_direct, codes = closed_loop("checkpoint", 1, 1.5)
assert codes == {200: n_direct}, codes
n_coal, codes = closed_loop("checkpoint", 16, 1.5)
assert codes == {200: n_coal}, codes
ratio = (n_coal / 1.5) / max(n_direct / 1.5, 1e-9)
assert ratio >= 3.0, (n_direct, n_coal, ratio)

# -- placement: traffic replicates the hot model across devices --------
deadline = time.time() + 60
while time.time() < deadline:
    if healthz()["replicas"].get("checkpoint", 0) >= 2:
        break
    closed_loop("checkpoint", 8, 0.5)   # keep the route counter moving
doc = healthz()
assert doc["replicas"]["checkpoint"] >= 2, doc["replicas"]
devs = {r["device"] for r in doc["placement"]["models"]["checkpoint"]}
assert len(devs) >= 2, doc["placement"]
text = scrape()
gauge_devs = set(re.findall(r'serve_device_queue_rows\{device="(\d+)"\}',
                            text))
assert len(gauge_devs) >= 2, gauge_devs
assert 'serve_model_replicas{model="checkpoint"}' in text
assert 'serve_http_requests_total{code="200"}' in text

# -- hot swap under threaded HTTP load: zero failures ------------------
conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
status, data = post(conn, "checkpoint", one_row)
conn.close()
assert status == 200
before = json.loads(data)["predictions"]

stop_flag = []
swap_codes = {}
lock = threading.Lock()


def hammer():
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    try:
        while not stop_flag:
            status, _ = post(conn, "checkpoint")
            with lock:
                swap_codes[status] = swap_codes.get(status, 0) + 1
    finally:
        conn.close()


threads = [threading.Thread(target=hammer) for _ in range(8)]
for t in threads:
    t.start()
time.sleep(0.5)
from lightgbm_tpu.sweep.refresh import write_serving_checkpoint
assert write_serving_checkpoint(
    os.path.join(fdir, "ckpt"),
    open(os.path.join(fdir, "v2.txt")).read()) == "ckpt_000002"
deadline = time.time() + 30
while time.time() < deadline:
    if "serve_model_swaps_total 1" in scrape():
        break
    time.sleep(0.2)
time.sleep(0.5)                  # post-swap traffic through new engine
stop_flag.append(True)
for t in threads:
    t.join()
assert "serve_model_swaps_total 1" in scrape(), "swap never landed"
assert set(swap_codes) == {200}, swap_codes
assert swap_codes[200] > 0
conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
status, data = post(conn, "checkpoint", one_row)
conn.close()
assert status == 200
after = json.loads(data)["predictions"]
assert not np.allclose(before, after), "swap did not change scores"

# -- forced overload: bronze sheds with 429s, gold NEVER ---------------
# fill bulk_m's burn window (every request breaches the tiny SLO); the
# shed can trip MID-warm-up once 16 outcomes land, so tally any early
# 429s — the exact-count check below covers them too
warm_429 = 0
conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
ok = 0
for _ in range(100):
    status, _ = post(conn, "bulk_m")
    ok += status == 200
    warm_429 += status == 429
    if ok >= 16:
        break
conn.close()
deadline = time.time() + 15
while time.time() < deadline:    # healthz refreshes the shed state
    if "bulk_m" in healthz()["shedding"]:
        break
    time.sleep(0.1)
assert "bulk_m" in healthz()["shedding"], "shed never tripped"

codes = {"bulk_m": {}, "checkpoint": {}}


def overload(model):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    stop = time.perf_counter() + 2.0
    try:
        while time.perf_counter() < stop:
            status, _ = post(conn, model)
            with lock:
                codes[model][status] = codes[model].get(status, 0) + 1
    finally:
        conn.close()


threads = ([threading.Thread(target=overload, args=("bulk_m",))
            for _ in range(12)]
           + [threading.Thread(target=overload, args=("checkpoint",))
              for _ in range(2)])
for t in threads:
    t.start()
for t in threads:
    t.join()
shed_429 = codes["bulk_m"].get(429, 0) + warm_429
assert codes["bulk_m"].get(429, 0) > 0, codes
assert set(codes["checkpoint"]) == {200}, codes   # gold never shed
doc = healthz()
assert "bulk_m" in doc["shedding"], doc["shedding"]
admission = doc["admission"]
assert admission["sheds"] == shed_429, (admission["sheds"], shed_429)
assert "gold" not in admission["sheds_by_class"], admission
# the Prometheus counter agrees exactly with the client-observed 429s
text = scrape()
shed_series = re.findall(
    r'serve_shed_total\{model="bulk_m",qos="bronze"\} (\d+)', text)
assert shed_series and int(shed_series[0]) == shed_429, \
    (shed_series, shed_429)
m429 = re.findall(r'serve_http_requests_total\{code="429"\} (\d+)', text)
assert m429 and int(m429[0]) == shed_429, (m429, shed_429)

# -- traffic JSON artifact ---------------------------------------------
artifact = {
    "schema": 1,
    "http_direct_rps": round(n_direct / 1.5, 1),
    "http_coalesced_rps": round(n_coal / 1.5, 1),
    "http_vs_direct": round(ratio, 2),
    "replicas": doc["replicas"],
    "swap_codes": {str(k): v for k, v in sorted(swap_codes.items())},
    "overload_codes": {m: {str(k): v for k, v in sorted(c.items())}
                       for m, c in codes.items()},
    "sheds": admission["sheds"],
    "sheds_by_class": admission["sheds_by_class"],
}
with open(os.path.join(fdir, "frontdoor_traffic.json"), "w") as fh:
    json.dump(artifact, fh, sort_keys=True)
chk = json.load(open(os.path.join(fdir, "frontdoor_traffic.json")))
assert chk["schema"] == 1
for key in ("http_vs_direct", "replicas", "swap_codes",
            "overload_codes", "sheds"):
    assert key in chk, key
print(f"front door smoke: ok (coalesced {ratio:.1f}x single-request, "
      f"{chk['replicas']['checkpoint']} replicas, "
      f"{swap_codes[200]} reqs through live swap with 0 failures, "
      f"{shed_429} bronze sheds / 0 gold)")
EOF
kill -INT "$FD_PID" 2>/dev/null || true
set +e
wait "$FD_PID"
FD_RC=$?
set -e
if [ "$FD_RC" -ne 0 ]; then
    echo "FAIL: front-door serve process exited $FD_RC (want clean 0)" >&2
    cat "$FD_DIR/serve.log" >&2
    exit 1
fi
if [ -n "${CI_ARTIFACT_DIR:-}" ]; then
    echo "front-door artifacts kept under $FD_DIR for artifact upload"
else
    rm -rf "$(dirname "$FD_DIR")"
fi

echo "== AOT serving artifact smoke (zero-trace cold start + compact parity) =="
AOT_DIR="${CI_ARTIFACT_DIR:-$(mktemp -d)}/lgbt_aot"
mkdir -p "$AOT_DIR"
LGBT_AOT_DIR="$AOT_DIR" python - <<'EOF'
import os

import numpy as np

import lightgbm_tpu as lgb

adir = os.environ["LGBT_AOT_DIR"]
rng = np.random.RandomState(7)
X = rng.randn(500, 8).astype(np.float32)
y = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2] > 0).astype(np.float32)
bst = lgb.train({"objective": "binary", "num_leaves": 15, "verbosity": -1},
                lgb.Dataset(X, label=y), num_boost_round=10)
bst.save_model(os.path.join(adir, "model.txt"))
np.savetxt(os.path.join(adir, "rows.tsv"),
           np.column_stack([y, X]), delimiter="\t", fmt="%.6g")
EOF
# export the artifact: buckets must cover the warm-up bucket (256) and
# the request bucket (500 rows at max_batch_rows=512 -> 512)
python tools/serve_export.py --model "$AOT_DIR/model.txt" \
    --out "$AOT_DIR/aot" --buckets 256,512 > "$AOT_DIR/export.json"
# cold-compiled twin: traces its programs in-process as usual
python -m lightgbm_tpu task=serve "input_model=m=$AOT_DIR/model.txt" \
    "data=$AOT_DIR/rows.tsv" "output_result=$AOT_DIR/pred_cold.tsv" \
    tpu_serve_max_batch_rows=512 \
    verbosity=1 > "$AOT_DIR/cold.log" 2>&1
# fresh process against the artifact: first score with ZERO new traces
python -m lightgbm_tpu task=serve "input_model=m=$AOT_DIR/model.txt" \
    "data=$AOT_DIR/rows.tsv" "output_result=$AOT_DIR/pred_aot.tsv" \
    "tpu_serve_aot_dir=$AOT_DIR/aot" tpu_serve_max_batch_rows=512 \
    verbosity=1 > "$AOT_DIR/aot.log" 2>&1
cmp "$AOT_DIR/pred_cold.tsv" "$AOT_DIR/pred_aot.tsv"
LGBT_AOT_DIR="$AOT_DIR" python - <<'EOF'
import json
import os

adir = os.environ["LGBT_AOT_DIR"]


def stats(log):
    tag = "Serving stats: "
    lines = [ln for ln in open(os.path.join(adir, log)) if ln.startswith(tag)]
    assert lines, f"{log} has no serving stats line"
    return json.loads(lines[-1][len(tag):])["registry"]["models"]["m"]


cold = stats("cold.log")
aot = stats("aot.log")
assert cold["compile_count"] > 0, cold
assert aot["compile_count"] == 0, \
    f"AOT serve traced {aot['compile_count']} programs before first score"
assert aot["aot_buckets"] == 2 and aot["aot_hits"] > 0, aot
# the artifact hit also lands on the structured event channel
aot_log = open(os.path.join(adir, "aot.log")).read()
assert "serve_aot" in aot_log and '"status": "hit"' in aot_log, \
    aot_log[-2000:]
print(f"AOT smoke: ok (cold compiles={cold['compile_count']}, "
      f"aot compiles=0, buckets={aot['aot_buckets']}, "
      f"byte-identical scores)")
EOF
# compact-parity leg: int8 either passes the parity gate (serve_compact)
# or emits exactly one serve_compact_fallback and serves f32-identical —
# never silent drift
python -m lightgbm_tpu task=serve "input_model=m=$AOT_DIR/model.txt" \
    "data=$AOT_DIR/rows.tsv" "output_result=$AOT_DIR/pred_int8.tsv" \
    tpu_serve_compact=int8 tpu_serve_max_batch_rows=512 \
    verbosity=1 > "$AOT_DIR/int8.log" 2>&1
LGBT_AOT_DIR="$AOT_DIR" python - <<'EOF'
import json
import os

adir = os.environ["LGBT_AOT_DIR"]
log = open(os.path.join(adir, "int8.log")).read()
ok = log.count('"event": "serve_compact"')
fb = log.count('"event": "serve_compact_fallback"')
assert (ok == 1) != (fb == 1), \
    f"want exactly one of serve_compact/serve_compact_fallback, got {ok}/{fb}"
plan = json.loads(
    [ln for ln in open(os.path.join(adir, "int8.log"))
     if ln.startswith("Serving stats: ")][-1][len("Serving stats: "):]
)["registry"]["models"]["m"]["compact"]
if fb:
    assert plan == "off", plan
    cold = open(os.path.join(adir, "pred_cold.tsv"), "rb").read()
    got = open(os.path.join(adir, "pred_int8.tsv"), "rb").read()
    assert got == cold, "fallback engine must score f32-identical"
else:
    assert plan == "int8", plan
print(f"compact parity leg: ok "
      f"({'gate passed (int8 resident)' if ok else 'clean fallback to f32'})")
EOF
if [ -n "${CI_ARTIFACT_DIR:-}" ]; then
    echo "AOT artifacts kept under $AOT_DIR for artifact upload"
else
    rm -rf "$(dirname "$AOT_DIR")"
fi

echo "== lambdarank fused smoke (5 rounds, tpu_rank_fused=on) =="
python - <<'EOF'
import numpy as np

import lightgbm_tpu as lgb

rng = np.random.RandomState(23)
sizes = rng.randint(5, 120, 60)
n = int(sizes.sum())
X = rng.rand(n, 12)
y = rng.randint(0, 5, n).astype(float)
params = {"objective": "lambdarank", "num_leaves": 15, "verbosity": -1,
          "metric": "none", "tpu_rank_fused": "on"}
ds = lgb.Dataset(X, label=y, group=sizes, params=params)
bst = lgb.Booster(params=params, train_set=ds)
for _ in range(5):
    bst.update()
obj = bst._gbdt.objective
# "on" must run the fused kernel (interpret-mode off-TPU) for EVERY
# round with zero wholesale fallbacks and zero oversize-query leftovers
assert obj.rank_fused_active, "tpu_rank_fused=on fell back to buckets"
assert obj.rank_fused_fallback_queries == 0, \
    f"unexpected leftover queries: {obj.rank_fused_fallback_queries}"
print(f"lambdarank fused smoke: ok (5 rounds, {len(sizes)} queries, "
      f"{n} docs, 0 fallbacks)")
EOF

echo "== many-model sweep smoke (M=4 batched, byte-equal vs sequential twins) =="
SWEEP_DIR="${CI_ARTIFACT_DIR:-$(mktemp -d)}/lgbt_sweep"
mkdir -p "$SWEEP_DIR"
SWEEP_SMOKE_DIR="$SWEEP_DIR" python - <<'EOF'
import filecmp
import os

import numpy as np

import lightgbm_tpu as lgb
from lightgbm_tpu.obs.ledger import read_ledger, validate_record
from lightgbm_tpu.sweep import train_many

out = os.environ["SWEEP_SMOKE_DIR"]
tdir = os.path.join(out, "trace")
rng = np.random.RandomState(5)
X = rng.rand(300, 8).astype(np.float32)
y = (X[:, 0] + X[:, 4] * 0.5 + rng.rand(300) * 0.1).astype(np.float32)
base = {"objective": "regression", "num_leaves": 7, "min_data_in_leaf": 5,
        "tpu_use_f64_hist": True, "tpu_grow_mode": "leafwise",
        "verbosity": -1, "tpu_trace": True, "tpu_trace_dir": tdir}
grids = [dict(base, learning_rate=lr, lambda_l2=l2)
         for lr, l2 in [(0.1, 0.0), (0.05, 1.0), (0.2, 0.5), (0.3, 2.0)]]
ROUNDS = 5
fleet = train_many([dict(p) for p in grids], lgb.Dataset(X, label=y),
                   num_boost_round=ROUNDS)
for m, (bst, params) in enumerate(zip(fleet, grids)):
    seq = lgb.train(dict(params, tpu_trace=False),
                    lgb.Dataset(X, label=y), num_boost_round=ROUNDS)
    a = os.path.join(out, f"fleet_{m}.txt")
    b = os.path.join(out, f"seq_{m}.txt")
    bst.save_model(a)
    seq.save_model(b)
    assert filecmp.cmp(a, b, shallow=False), f"model {m} diverged"
# fleet ledger: every record schema-valid, EXACTLY one sweep_init note,
# and the round records partition cleanly by the per-model key
rows = []
for name in sorted(os.listdir(tdir)):
    if name.startswith("ledger-"):
        rows.extend(read_ledger(os.path.join(tdir, name)))
for rec in rows:
    validate_record(rec)
inits = [r for r in rows if r.get("kind") == "note"
         and r.get("note") == "sweep_init"]
assert len(inits) == 1, f"sweep_init notes: {len(inits)}"
assert inits[0]["models"] == 4 and inits[0]["mode"] == "batched", inits
rounds = [r for r in rows if r.get("kind") == "round"
          and r.get("path") == "sweep"]
by_model = {m: sorted(r["round"] for r in rounds if r.get("model") == m)
            for m in range(4)}
assert all(v == list(range(ROUNDS)) for v in by_model.values()), by_model
print(f"sweep smoke: ok (4 models byte-equal over {ROUNDS} rounds, "
      f"{len(rounds)} per-model ledger rounds, 1 sweep_init note)")
EOF
echo "== sweep variant smoke (GOSS + DART M=4, byte-equal vs sequential twins) =="
SWEEP_VAR_DIR="$SWEEP_DIR/variants"
mkdir -p "$SWEEP_VAR_DIR"
SWEEP_SMOKE_DIR="$SWEEP_VAR_DIR" python - <<'EOF'
import filecmp
import os

import numpy as np

import lightgbm_tpu as lgb
from lightgbm_tpu.sweep import train_many

out = os.environ["SWEEP_SMOKE_DIR"]
rng = np.random.RandomState(5)
X = rng.rand(300, 8).astype(np.float32)
y = (X[:, 0] + X[:, 4] * 0.5 + rng.rand(300) * 0.1).astype(np.float32)
base = {"objective": "regression", "num_leaves": 7, "min_data_in_leaf": 5,
        "tpu_use_f64_hist": True, "tpu_grow_mode": "leafwise",
        "verbosity": -1}
ROUNDS = 5
variants = {
    # rates past the 1/lr warm-up ramp so the GOSS select program runs
    "goss": dict(base, boosting="goss", top_rate=0.3, other_rate=0.2),
    "dart": dict(base, boosting="dart", drop_rate=0.5, skip_drop=0.3),
}
for variant, vbase in variants.items():
    grids = [dict(vbase, learning_rate=lr)
             for lr in (0.5, 0.3, 0.25, 0.4)]
    fleet = train_many([dict(p, tpu_sweep_mode="batched") for p in grids],
                       lgb.Dataset(X, label=y), num_boost_round=ROUNDS)
    for m, (bst, params) in enumerate(zip(fleet, grids)):
        seq = lgb.train(dict(params), lgb.Dataset(X, label=y),
                        num_boost_round=ROUNDS)
        a = os.path.join(out, f"{variant}_fleet_{m}.txt")
        b = os.path.join(out, f"{variant}_seq_{m}.txt")
        bst.save_model(a)
        seq.save_model(b)
        assert filecmp.cmp(a, b, shallow=False), \
            f"{variant} model {m} diverged"
    print(f"sweep {variant} smoke: ok (4 models byte-equal over "
          f"{ROUNDS} rounds, batched mode forced)")
EOF
if [ -n "${CI_ARTIFACT_DIR:-}" ]; then
    echo "sweep artifacts kept under $SWEEP_DIR for artifact upload"
else
    rm -rf "$(dirname "$SWEEP_DIR")"
fi

echo "== streaming ingest smoke (chunked CLI load byte-equal + quantized hist) =="
ING_DIR="${CI_ARTIFACT_DIR:-$(mktemp -d)}/lgbt_ingest"
mkdir -p "$ING_DIR"
python - <<EOF
import numpy as np
rng = np.random.RandomState(31)
X = rng.rand(5000, 10).astype(np.float32)
y = (X[:, 0] + 0.3 * rng.randn(5000) > 0.5).astype(np.float32)
np.savetxt("$ING_DIR/train.tsv",
           np.column_stack([y, X]), delimiter="\t", fmt="%.6g")
EOF
ING_ARGS="task=train data=$ING_DIR/train.tsv objective=binary
          num_leaves=15 num_iterations=5"
# classic in-memory load
# shellcheck disable=SC2086
python -m lightgbm_tpu $ING_ARGS verbosity=-1 \
    output_model="$ING_DIR/mem.txt" > "$ING_DIR/mem.log" 2>&1
# streamed load: chunk well under the 5000 rows, so the file goes
# through count/sample/bin passes in 9 chunks; verbose so the
# stream_ingest event and the CLI's ingest summary land in the log
# shellcheck disable=SC2086
python -m lightgbm_tpu $ING_ARGS verbosity=2 tpu_stream_chunk_rows=600 \
    output_model="$ING_DIR/stream.txt" > "$ING_DIR/stream.log" 2>&1
if ! cmp -s "$ING_DIR/mem.txt" "$ING_DIR/stream.txt"; then
    echo "FAIL: streamed model is not byte-equal to the in-memory model" >&2
    diff "$ING_DIR/mem.txt" "$ING_DIR/stream.txt" | head -20 >&2
    exit 1
fi
grep -q '^Streamed ingest:' "$ING_DIR/stream.log" || {
    echo "FAIL: CLI did not print the streamed-ingest summary" >&2
    exit 1
}
ING_SMOKE_DIR="$ING_DIR" python - <<'EOF'
import os

from lightgbm_tpu.utils.log import parse_event

d = os.environ["ING_SMOKE_DIR"]
events = [e for e in (parse_event(ln.strip())
                      for ln in open(os.path.join(d, "stream.log")))
          if e]
ing = [e for e in events if e["event"] == "stream_ingest"]
assert ing, {e["event"] for e in events}
assert ing[0]["rows"] == 5000 and ing[0]["chunk_rows"] == 600, ing[0]
print(f"streaming ingest smoke: ok (5000 rows in chunks of 600, "
      f"{ing[0]['device_cols']} device-binned cols, model byte-equal)")
EOF
# quantized-histogram leg: 5 rounds with int16 gradient quantization
# must emit the quant_hist event and stay within AUC tolerance of f32
python - <<'EOF'
import numpy as np

import lightgbm_tpu as lgb
from lightgbm_tpu.utils import log
from lightgbm_tpu.utils.log import parse_event

rng = np.random.RandomState(37)
X = rng.rand(3000, 10)
y = (X[:, 0] + 0.3 * rng.randn(3000) > 0.5).astype(float)


def auc(labels, preds):
    order = np.argsort(preds, kind="mergesort")
    ranks = np.empty(len(preds))
    ranks[order] = np.arange(1, len(preds) + 1)
    pos = labels > 0
    np_, nn = pos.sum(), (~pos).sum()
    return (ranks[pos].sum() - np_ * (np_ + 1) / 2) / (np_ * nn)


def train(quant):
    lines = []
    log.register_callback(lines.append)
    try:
        bst = lgb.train({"objective": "binary", "num_leaves": 15,
                         "verbosity": 2, "tpu_quant_hist": quant},
                        lgb.Dataset(X, label=y), num_boost_round=5)
    finally:
        log.register_callback(None)
    events = [e for e in map(parse_event, lines) if e]
    return auc(y, bst.predict(X)), events


auc_off, _ = train("off")
auc_on, events = train("on")
qh = [e for e in events if e["event"] == "quant_hist"]
assert qh, "tpu_quant_hist=on emitted no quant_hist event"
assert qh[0]["bits"] == 16 and qh[0]["dtype"] == "int16", qh[0]
assert abs(auc_on - auc_off) < 1e-3, (auc_on, auc_off)
print(f"quantized hist smoke: ok (int16 AUC {auc_on:.5f} vs "
      f"f32 {auc_off:.5f}, quant_hist event emitted)")
EOF
if [ -n "${CI_ARTIFACT_DIR:-}" ]; then
    echo "ingest artifacts kept under $ING_DIR for artifact upload"
else
    rm -rf "$(dirname "$ING_DIR")"
fi

echo "== out-of-core stream-to-shard smoke (pipelined ingest on 4 devices) =="
OOC_DIR="${CI_ARTIFACT_DIR:-$(mktemp -d)}/lgbt_ooc"
mkdir -p "$OOC_DIR"
python - <<EOF
import numpy as np
rng = np.random.RandomState(41)
X = rng.rand(6000, 12).astype(np.float32)
y = (X[:, 0] + 0.3 * rng.randn(6000) > 0.5).astype(np.float32)
np.savetxt("$OOC_DIR/train.tsv",
           np.column_stack([y, X]), delimiter="\t", fmt="%.6g")
EOF
# shared leg: f64 histogram accumulation is the byte-equal contract
OOC_ARGS="task=train data=$OOC_DIR/train.tsv objective=binary
          num_leaves=15 num_iterations=5 tpu_use_f64_hist=true"
# serial in-memory reference
# shellcheck disable=SC2086
python -m lightgbm_tpu $OOC_ARGS verbosity=-1 tree_learner=serial \
    output_model="$OOC_DIR/serial.txt" > "$OOC_DIR/serial.log" 2>&1
# streamed-sharded run: 6000 rows in chunks of 500 (12 chunks, each
# smaller than the 1500-row per-device block), parsed on the prefetch
# thread and binned/appended on the 4 owner devices — the [n, U] host
# matrix never exists; verbose so dist_stream lands in the log
# shellcheck disable=SC2086
XLA_FLAGS="--xla_force_host_platform_device_count=4" JAX_PLATFORMS=cpu \
    python -m lightgbm_tpu $OOC_ARGS verbosity=2 tree_learner=data \
    num_machines=4 tpu_stream_chunk_rows=500 \
    output_model="$OOC_DIR/shard.txt" > "$OOC_DIR/shard.log" 2>&1
if ! cmp -s "$OOC_DIR/serial.txt" "$OOC_DIR/shard.txt"; then
    echo "FAIL: streamed-sharded model is not byte-equal to the serial model" >&2
    diff "$OOC_DIR/serial.txt" "$OOC_DIR/shard.txt" | head -20 >&2
    exit 1
fi
OOC_SMOKE_DIR="$OOC_DIR" python - <<'EOF'
import os

from lightgbm_tpu.utils.log import parse_event

d = os.environ["OOC_SMOKE_DIR"]
events = [e for e in (parse_event(ln.strip())
                      for ln in open(os.path.join(d, "shard.log")))
          if e]
kinds = {e["event"] for e in events}
assert {"dist_stream", "dist_shard", "stream_ingest"} <= kinds, kinds
ev = next(e for e in events if e["event"] == "dist_stream")
assert ev["shards"] == 4 and ev["rows"] == 6000, ev
assert ev["per_shard"] == 1500, ev
# every device's shard bytes are accounted to a per-device owner
for i in range(4):
    assert f"dist/shard_bytes/d{i}" in ev["owners"], ev["owners"]
assert float(ev["overlap_eff"]) > 0, ev
print(f"out-of-core smoke: ok (4-shard streamed model byte-equal, "
      f"per_shard={ev['per_shard']}, overlap_eff={ev['overlap_eff']}, "
      f"owners on d0..d3)")
EOF
if [ -n "${CI_ARTIFACT_DIR:-}" ]; then
    echo "out-of-core artifacts kept under $OOC_DIR for artifact upload"
else
    rm -rf "$(dirname "$OOC_DIR")"
fi

echo "== timeline smoke (traced 4-shard run + export CLI + forced anomaly) =="
TL_DIR="${CI_ARTIFACT_DIR:-$(mktemp -d)}/lgbt_timeline"
mkdir -p "$TL_DIR"
python - <<EOF
import numpy as np
rng = np.random.RandomState(17)
X = rng.rand(1200, 8).astype(np.float32)
y = (X[:, 0] + 0.3 * rng.randn(1200) > 0.5).astype(np.float32)
np.savetxt("$TL_DIR/train.tsv",
           np.column_stack([y, X]), delimiter="\t", fmt="%.6g")
EOF
# clean traced 4-shard run: the CLI auto-writes timeline.json next to
# trace_summary.json
XLA_FLAGS="--xla_force_host_platform_device_count=4" JAX_PLATFORMS=cpu \
    python -m lightgbm_tpu task=train "data=$TL_DIR/train.tsv" \
    objective=binary num_leaves=15 num_iterations=6 verbosity=-1 \
    tree_learner=data num_machines=4 \
    tpu_trace=true "tpu_trace_dir=$TL_DIR/trace" \
    "output_model=$TL_DIR/model.txt" > "$TL_DIR/train.log" 2>&1
grep -q "run timeline at" "$TL_DIR/train.log" || {
    echo "FAIL: CLI did not announce the timeline artifact" >&2
    tail -5 "$TL_DIR/train.log" >&2; exit 1; }
# the export tool must re-produce it from the same artifacts: exit 0
python tools/timeline_export.py --trace-dir "$TL_DIR/trace" \
    --out "$TL_DIR/export.json" 2> "$TL_DIR/export.log"
TL_SMOKE_DIR="$TL_DIR" python - <<'EOF'
import glob
import json
import os

from lightgbm_tpu.obs import ledger as obs_ledger

d = os.environ["TL_SMOKE_DIR"]
tdir = os.path.join(d, "trace")
doc = json.load(open(os.path.join(tdir, "timeline.json")))
evs = doc["traceEvents"]
assert evs and all("ph" in e and "pid" in e for e in evs), evs[:3]
other = doc["otherData"]
assert other["schema"] == 1, other
assert other["lanes"]["train"] == 6, other["lanes"]
srcs = {e.get("args", {}).get("src") for e in evs
        if e.get("ph") in ("X", "i")}
assert {"spans", "ledger", "events"} <= srcs, srcs
# a clean run has no anomaly notes
paths = sorted(glob.glob(os.path.join(tdir, "ledger-*.jsonl")))
recs = obs_ledger.read_ledger(paths[-1])
notes = {r.get("note") for r in recs if r.get("kind") == "note"}
assert "round_anomaly" not in notes, notes
exp = json.load(open(os.path.join(d, "export.json")))
assert len(exp["traceEvents"]) == len(evs), (len(exp["traceEvents"]),
                                             len(evs))
print(f"timeline smoke: ok ({len(evs)} trace events, "
      f"{other['lanes']['train']} rounds on the train lane)")
EOF
# forced anomaly: factor 0.5 makes any round slower than half the
# rolling median "anomalous", so once the 3-round baseline exists the
# watch must fire — pure host arithmetic, deterministic on CPU
python -m lightgbm_tpu task=train "data=$TL_DIR/train.tsv" \
    objective=binary num_leaves=15 num_iterations=10 verbosity=-1 \
    tpu_anomaly_factor=0.5 tpu_anomaly_window=4 \
    tpu_trace=true "tpu_trace_dir=$TL_DIR/trace_anom" \
    "output_model=$TL_DIR/model_anom.txt" > "$TL_DIR/anom.log" 2>&1
TL_SMOKE_DIR="$TL_DIR" python - <<'EOF'
import glob
import json
import os

from lightgbm_tpu.obs import ledger as obs_ledger

d = os.environ["TL_SMOKE_DIR"]
tdir = os.path.join(d, "trace_anom")
paths = sorted(glob.glob(os.path.join(tdir, "ledger-*.jsonl")))
recs = obs_ledger.read_ledger(paths[-1])
anom = [r for r in recs if r.get("kind") == "note"
        and r.get("note") == "round_anomaly"]
assert anom, "forced anomaly watch did not fire"
a = anom[0]
assert a["ratio"] > 0 and a["median_ms"] > 0 and "round" in a, a
doc = json.load(open(os.path.join(tdir, "timeline.json")))
marks = [e for e in doc["traceEvents"] if e.get("ph") == "i"
         and e.get("name") == "round_anomaly"]
assert marks, "round_anomaly instant missing from timeline"
print(f"anomaly smoke: ok (round {a['round']} flagged at "
      f"{a['ratio']}x median {a['median_ms']}ms, instant on timeline)")
EOF
if [ -n "${CI_ARTIFACT_DIR:-}" ]; then
    echo "timeline artifacts kept under $TL_DIR for artifact upload"
else
    rm -rf "$(dirname "$TL_DIR")"
fi

echo "== graftlint (invariant gate) =="
# the real tree must be clean: exit 0, no new findings
python -m tools.lint
# the gate must actually gate: an injected violation of each rule in a
# scratch tree must exit nonzero and name its rule in the JSON report
LINT_DIR="$(mktemp -d)/glt"
mkdir -p "$LINT_DIR/lightgbm_tpu/obs"
cat > "$LINT_DIR/lightgbm_tpu/bad.py" <<'EOF'
import os
import time

import jax

from .utils import log


def g(a):
    return a + 1


def run(x):
    fn = jax.jit(g, donate_argnums=(0,))
    y = fn(x)
    jax.block_until_ready(y)
    log.event("not_a_kind", n=1)
    return x + y


def step(a):
    return a + time.time() + float(os.environ.get("K", "0"))


prog = jax.jit(step)


class Box:
    def __init__(self):
        import threading
        self._lock = threading.Lock()
        self._items = []        # guarded-by: _lock

    def put(self, v):
        self._items.append(v)
EOF
cat > "$LINT_DIR/lightgbm_tpu/obs/events.py" <<'EOF'
EVENTS = {"good_kind": "only catalogued kind"}
EOF
cat > "$LINT_DIR/lightgbm_tpu/config.py" <<'EOF'
from dataclasses import dataclass


@dataclass
class Config:
    tpu_alpha: int = 1
    tpu_orphan: int = 2      # in neither signature nor runtime set
EOF
cat > "$LINT_DIR/lightgbm_tpu/compile_cache.py" <<'EOF'
def config_signature(cfg):
    names = ["tpu_alpha"]
    return tuple((n, getattr(cfg, n)) for n in names)
EOF
mkdir -p "$LINT_DIR/lightgbm_tpu/resilience"
cat > "$LINT_DIR/lightgbm_tpu/resilience/checkpoint.py" <<'EOF'
RUNTIME_ONLY_PARAMS = frozenset()
EOF
if python -m tools.lint --root "$LINT_DIR" --paths lightgbm_tpu \
        --json > "$LINT_DIR/report.json"; then
    echo "graftlint FAILED to flag the injected violations" >&2
    exit 1
fi
LINT_REPORT="$LINT_DIR/report.json" python - <<'EOF'
import json
import os

rep = json.load(open(os.environ["LINT_REPORT"]))
hit = {f["rule"] for f in rep["new"]}
want = {"LGT001", "LGT002", "LGT003", "LGT004", "LGT005", "LGT006"}
assert want <= hit, f"injected violations missed: {sorted(want - hit)}"
print(f"graftlint gate: ok (clean tree green, injected tree flagged "
      f"{sorted(hit)})")
EOF
rm -rf "$(dirname "$LINT_DIR")"

echo "== tests ($MODE tier) =="
if [ "$MODE" = "full" ]; then
    python -m pytest tests/ -q
else
    python -m pytest tests/ -q -m "not slow"
fi
