"""Persistent device bytes of a training configuration, from its sizes.

The benchmark's own copy of the arithmetic by which the aligned engine
sizes what it keeps on the device (`ops/aligned.py`: `chunk_for`,
`lane_layout`, `pack_records`' bin width, `hist_layout`;
`level_builder.spec_slots`; `AlignedEngine.__init__`'s `NC`), copied and
not imported: it is how the rows of a configuration were chosen against
the driver's floor of a quarter of the chip's memory, and each run prints
it beside the measured peak, with what the peak holds over it
(`held_over_gib`). It gates nothing, so a later change that
shrinks the records is not stopped by the yardstick; it only makes the
printed figure stale, which the measured one beside it shows.
"""
import math

GIB = float(1 << 30)
RANK_OBJECTIVES = ("lambdarank", "rank_xendcg")


def persistent_bytes(rows: int, features: int, max_bin: int,
                     objective: str = "binary", num_leaves: int = 255,
                     level_spec: float = 4.5, spill_budget_mb: float = 48.0,
                     round_splits: int = 256) -> dict:
    """Record matrix and histogram spill store of one chip, in bytes."""
    # chunk: 1024 rows up to 40 features, else 512, doubled until the
    # chunk count fits the move pass's scalar-prefetch budget
    chunk = 1024 if features <= 40 else 512
    while rows // chunk > 40_000:
        chunk *= 2
    # bins per 32-bit word at the narrowest width the bin range allows
    bins_per_word = 8 if max_bin <= 16 else 5 if max_bin <= 64 else 4
    words = -(-features // bins_per_word)
    if objective in RANK_OBJECTIVES:
        layout, lanes = "ext", words + 4        # score grad hess rid
    elif rows <= 1 << 24:
        layout, lanes = "compact", words + 2    # score meta
    else:
        layout, lanes = "std", words + 6   # score label grad hess rid weight
    lanes_padded = -(-lanes // 8) * 8
    slots = max(math.ceil(level_spec * num_leaves), num_leaves + 1)
    chunks = -(-rows // chunk) + slots + 2
    records = chunks * lanes_padded * chunk * 4
    # one slot's histogram block; the store has one slot per split of a
    # round and one more, and lives in HBM only when it outgrows VMEM
    if max_bin > 128:
        slot = features * 16 * 128 * 4
    else:
        group = 8 if max_bin <= 64 else 4
        slot = -(-features // group) * 6 * group * max_bin * 4
    store = slot * (min(slots - 1, round_splits) + 1)
    spill = store > spill_budget_mb * (1 << 20)
    return {"layout": layout, "chunk": chunk, "lanes": lanes_padded,
            "chunks": chunks, "records_bytes": records,
            "slot_bytes": slot, "spill": spill,
            "spill_store_bytes": store if spill else 0,
            "persistent_bytes": records + (store if spill else 0)}


def held_over_gib(peak_bytes: int, size: dict) -> float:
    """What the measured peak holds that the arithmetic does not count, in
    GiB: scores, gradients and tables in row order, the programs' own
    temporaries. Measured at 1.0 GiB on the standard layout's cells
    (PERF.md section 4); the EXT layout's adds the rank kernel's tables."""
    return (peak_bytes - size["persistent_bytes"]) / GIB
