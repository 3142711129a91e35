#!/usr/bin/env python
"""Full-scale reference head-to-head: train the ACTUAL reference binary
on chip_smoke.py's synthetic HIGGS data (10.5M x 28, seed 7) for 500
iterations / 255 leaves at max_bin 63 AND 255, score the 500K holdout,
and cache the AUCs to docs/ref_full_auc.json.

On a one-core host this takes hours, so it runs out-of-band. The data
is deterministic (seed 7), so a run of this repo on the same rows
compares like with like; the JSON records the protocol.

python tools/ref_full_headtohead.py [--bins 63,255] [--iters 500]
"""
import json
import os
import subprocess
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tests"))

import numpy as np

OUT = os.path.join(ROOT, "docs", "ref_full_auc.json")
N = 10_500_000
NH = 500_000
F = 28
LEAVES = 255


def log(msg):
    print(msg, flush=True)


def write_tsv(path, y, X):
    t0 = time.perf_counter()
    with open(path, "w") as fh:
        blk = 200_000
        for s in range(0, len(y), blk):
            e = min(s + blk, len(y))
            rows = np.concatenate([y[s:e, None], X[s:e]], axis=1)
            np.savetxt(fh, rows, fmt="%.6g", delimiter="\t")
    log(f"# tsv {path}: {time.perf_counter() - t0:.1f}s")


def main():
    bins = [int(b) for b in "63,255".split(",")]
    iters = 500
    for i, a in enumerate(sys.argv):
        if a == "--bins":
            bins = [int(b) for b in sys.argv[i + 1].split(",")]
        if a == "--iters":
            iters = int(sys.argv[i + 1])

    from test_reference_parity import _ensure_cli, CLI
    assert _ensure_cli(), "reference CLI could not be built"

    import chip_smoke
    t0 = time.perf_counter()
    Xall, yall = chip_smoke.synth_higgs(N + NH, F)
    log(f"# gen {time.perf_counter() - t0:.1f}s")
    td = tempfile.mkdtemp(prefix="ref_full_")
    train_p = os.path.join(td, "train.tsv")
    hold_p = os.path.join(td, "hold.tsv")
    write_tsv(train_p, yall[:N], Xall[:N])
    write_tsv(hold_p, yall[N:], Xall[N:])
    hy = yall[N:]
    del Xall, yall

    out = {"protocol": {
        "data": "chip_smoke.synth_higgs(11M, 28, seed 7); first 10.5M "
                "train, last 500K holdout",
        "config": f"num_leaves {LEAVES}, learning_rate 0.1, "
                  f"min_data_in_leaf 20, num_trees {iters}",
        "reference": "the CLI built from /root/reference by "
                     "tests/test_reference_parity._ensure_cli",
        "host": "1-core Xeon (wall times are NOT comparable to the "
                "16-thread baseline; quality numbers are)"}}
    if os.path.isfile(OUT):
        try:
            out.update(json.load(open(OUT)))
        except Exception:
            pass
    for mb in bins:
        conf = [
            "task = train", "objective = binary",
            f"num_leaves = {LEAVES}", f"max_bin = {mb}",
            "learning_rate = 0.1", "min_data_in_leaf = 20",
            f"num_trees = {iters}", "verbosity = 1", "metric = auc",
            f"data = {train_p}",
            f"output_model = {os.path.join(td, f'ref{mb}.txt')}",
        ]
        cpath = os.path.join(td, "t.conf")
        with open(cpath, "w") as fh:
            fh.write("\n".join(conf))
        t0 = time.perf_counter()
        subprocess.run([CLI, f"config={cpath}"], check=True,
                       timeout=6 * 3600)
        tt = time.perf_counter() - t0
        log(f"# ref train mb={mb}: {tt:.1f}s")
        pconf = [
            "task = predict", f"data = {hold_p}",
            f"input_model = {os.path.join(td, f'ref{mb}.txt')}",
            f"output_result = {os.path.join(td, 'pred.txt')}",
        ]
        with open(cpath, "w") as fh:
            fh.write("\n".join(pconf))
        subprocess.run([CLI, f"config={cpath}"], check=True, timeout=3600)
        pred = np.loadtxt(os.path.join(td, "pred.txt"))
        auc = chip_smoke.auc_of(pred, hy)
        log(f"# ref full AUC mb={mb}: {auc:.6f}")
        out[f"auc_ref_full_{mb}bin"] = round(float(auc), 6)
        out[f"ref_train_1core_s_{mb}bin"] = round(tt, 1)
        with open(OUT, "w") as fh:
            json.dump(out, fh, indent=1)
        log(f"# wrote {OUT}")


if __name__ == "__main__":
    main()
