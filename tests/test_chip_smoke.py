"""chip_smoke.py on the CPU tier: every leg at toy size with the Pallas
kernels under the interpreter (the four-chip leg on conftest's 8-device
CPU mesh), main() refusing a backend without a chip, and the one
compile-cache resolver. The chip itself is only ever reached through the
chip tool (`python chip_smoke.py`)."""
import gc
import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from lightgbm_tpu import compile_cache  # noqa: E402

# the smoke's own route on the chip is tpu_grow_mode=auto with compiled
# kernels; off the chip the same kernels run interpreted
INTERPRET = {"tpu_grow_mode": "aligned", "tpu_aligned_interpret": True}


def test_train_and_score_legs():
    res, bst, hold_X = chip_smoke.leg_train(
        1500, 8, iters=2, num_leaves=7, holdout_rows=400,
        extra_params=INTERPRET)
    assert res["path"].startswith("aligned") and res["fallbacks"] == 0
    assert res["walls"]["first_iter_s"] > 0
    assert len(res["walls"]["iter_host_s"]) == 2
    score = chip_smoke.leg_score(
        bst, hold_X, subset=200, requests=2, request_rows=8,
        predict_params={"tpu_predict_device": "on"})
    assert score["engine_calls"] > 0
    assert score["http_codes"] == [200] * 4


def test_rank_leg_spills_and_stays_fused():
    # a 0.25 MB budget forces the HBM spill ring at toy width (at the
    # real 137 x 255-bin width the default 48 MB budget spills by itself)
    res = chip_smoke.leg_rank(
        1500, 8, iters=2, num_leaves=7,
        extra_params=dict(INTERPRET, tpu_rank_fused="on",
                          tpu_hist_spill_vmem_mb=0.25))
    assert res["hist_spill"] and res["rank_fused_active"]
    assert res["rank_grad_rel_err"] < 1e-5


def test_multichip_leg_on_cpu_mesh():
    try:
        res = chip_smoke.leg_multichip(2000, 8, shards=4, iters=2,
                                       num_leaves=7, extra_params=INTERPRET)
    finally:
        # the program registry's closures keep the engine — and through
        # it the sharded Dataset and its HBM-accountant rows — alive
        compile_cache.clear_programs()
        gc.collect()
    assert res["shards"] == 4 and len(set(res["devices"])) == 4
    assert len(set(res["placed_bytes"]["bins"].values())) == 1


def test_fixtures_are_seeded_and_shaped():
    """The smoke's data comes from its own generators: one seed, one
    data set, in the shapes the legs train."""
    X, y = chip_smoke.synth_higgs(3000, 28)
    X2, y2 = chip_smoke.synth_higgs(3000, 28)
    assert X.shape == (3000, 28) and X.dtype == np.float32
    assert y.shape == (3000,) and y.dtype == np.int8
    assert set(np.unique(y)) == {0, 1}
    assert np.array_equal(X, X2) and np.array_equal(y, y2)
    assert not np.array_equal(X, chip_smoke.synth_higgs(3000, 28, seed=8)[0])

    R, g, group = chip_smoke.synth_mslr(5000, 137)
    R2, g2, group2 = chip_smoke.synth_mslr(5000, 137)
    assert R.shape == (5000, 137) and R.dtype == np.float32
    assert g.shape == (5000,) and g.dtype == np.float32
    assert set(np.unique(g)) <= {0.0, 1.0, 2.0, 3.0, 4.0} and g.max() == 4.0
    assert group.dtype == np.int32 and int(group.sum()) == 5000
    assert 80 <= group[:-1].min() and group.max() < 160
    assert np.array_equal(R, R2) and np.array_equal(g, g2)
    assert np.array_equal(group, group2)


def test_auc_of_against_the_benchmark_reference():
    """`auc_of` is the rank-sum AUC of `benchmark/reference.py` except
    that it leaves ties in `argsort`'s order where the reference gives
    them their average rank: equal on distinct scores, and within half
    a rank per tied pair of unlike labels otherwise."""
    from benchmark import reference
    rng = np.random.default_rng(2000)
    score = rng.standard_normal(2000)
    label = (score + rng.standard_normal(2000) > 0).astype(np.int8)
    assert len(np.unique(score)) == 2000
    assert chip_smoke.auc_of(score, label) == pytest.approx(
        reference.auc(score, label), abs=1e-12)
    tied = np.round(score, 1)
    pos, neg = tied[label > 0], tied[label == 0]
    vals, npos = np.unique(pos, return_counts=True)
    nneg = np.array([(neg == v).sum() for v in vals])
    unlike_tied_pairs = int((npos * nneg).sum())
    assert unlike_tied_pairs > 1000          # the ties are really there
    bound = 0.5 * unlike_tied_pairs / (len(pos) * len(neg))
    assert abs(chip_smoke.auc_of(tied, label)
               - reference.auc(tied, label)) <= bound + 1e-12
    # ties among like labels only: the order within them cannot matter
    like = np.where(label > 0, np.round(score, 1) + 100.0, score)
    assert chip_smoke.auc_of(like, label) == pytest.approx(
        reference.auc(like, label), abs=1e-12)


def test_main_refuses_a_backend_without_a_chip(capsys):
    assert chip_smoke.main() != 0
    out = capsys.readouterr()
    assert "no TPU" in out.err
    assert '"ok"' not in out.out          # no result line
    # and it bailed out before wiring the persistent cache
    assert compile_cache.persistent_cache_dir() is None


def test_report_ends_with_the_exact_verdict(capsys):
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    for ok in (True, False):
        rc = chip_smoke.report({"ok": ok, "device": device,
                                "train": {"path": "aligned"}})
        lines = capsys.readouterr().out.splitlines()
        assert (rc == 0) == ok
        # the last line holds exactly these keys and nothing else
        assert json.loads(lines[-1]) == {"ok": ok, "device": device}
        assert lines[-2].startswith("summary: ")
        assert lines[-2].endswith('"claim": null}')


def test_cache_dir_resolver(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.cache_dir() == str(tmp_path)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert compile_cache.cache_dir() == os.path.join(REPO, ".jax_cache")
