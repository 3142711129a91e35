#!/usr/bin/env python
"""What the device pack's upload and pack cost on the chip, over random
uint8 bins of the Criteo cell's shape (67 columns, 8-bit bins, chunks of
2,048 rows, the compact record).

python tools/pack_upload_time.py [rows] [cols]

Prints one JSON line: the rate at which blocks of PACK_BLOCK_BYTES cross
to the device in each upload form the pack could take (the block's bins
as one flat uint8 vector, or as the [rows, cols] matrix they are), each
timed one block at a time to the device and then all enqueued before one
wait; and the seconds of `pack_device` over every row, its first call
(the program's compile or cache load included) and a second.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np

from lightgbm_tpu.obs import trace as obs_trace
from lightgbm_tpu.ops import aligned

C = 2048


def rate(blocks, form):
    """GB/s of device_put over `blocks`: one at a time, then all at once."""
    shaped = [b.reshape(-1) if form == "flat" else b for b in blocks]
    nbytes = sum(b.nbytes for b in blocks)
    obs_trace.force_fence(jax.device_put(shaped[0]))
    t = time.perf_counter()
    for b in shaped:
        obs_trace.force_fence(jax.device_put(b))
    serial = nbytes / (time.perf_counter() - t) / 1e9
    t = time.perf_counter()
    obs_trace.force_fence([jax.device_put(b) for b in shaped])
    enqueued = nbytes / (time.perf_counter() - t) / 1e9
    return {"one_at_a_time_gbps": serial, "enqueued_gbps": enqueued}


def main(argv):
    rows = int(argv[1]) if len(argv) > 1 else 12_000_000
    cols = int(argv[2]) if len(argv) > 2 else 67
    rng = np.random.default_rng(0)
    bins = rng.integers(0, 255, (rows, cols), dtype=np.uint8)
    label = (rng.random(rows) < 0.25).astype(np.float32)
    per = aligned.pack_block_chunks(C, cols, -(-rows // C)) * C
    blocks = [bins[r:r + per] for r in range(0, rows - per + 1, per)]
    out = {"device": jax.devices()[0].device_kind, "rows": rows,
           "cols": cols, "block_bytes": per * cols,
           "blocks": len(blocks)}
    for form in ("flat", "matrix"):
        out[form] = rate(blocks, form)
    nc = -(-rows // C) + 2
    for run in ("first", "second"):
        t = time.perf_counter()
        rec, *_, info = aligned.pack_device(
            bins, label, None, C, nc, bits=8, compact=True)
        obs_trace.force_fence(rec)
        out[f"pack_{run}_s"] = time.perf_counter() - t
        del rec
    out["pack_blocks"] = info["blocks"]
    out["upload_bytes"] = info["upload_bytes"]
    print(json.dumps(out))


if __name__ == "__main__":
    main(sys.argv)
