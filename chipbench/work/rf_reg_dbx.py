"""Operations and bytes that a regression-forest fit NEEDS, by
``work/rf_dbx.py``'s convention (its module text; the sketch's and
``binize``'s counts are that file's): the growth is one update and one bin
byte a weighted row, level and SAMPLED feature — ``ceil(cols / 3)`` a node
for a regressor, 1000 of 3000 — plus the routing's bin byte and the label's
statistics and weight (12 + 4 bytes) a weighted row and level. A node's
weighted rows are its served count, ``leaf_stats[..., 0]``. The algorithm's
floor, whatever implements the histogram: not the lane padding, not a
selection product, not a one-hot's zeros — so no share of it can pass 100%.
"""
import math

from chipbench.work.rf_dbx import binize_work, sketch_work


def features_per_node(cols: int) -> int:
    return max(1, min(cols, math.ceil(cols / 3.0)))


def hist_work(cols: int, model: dict) -> dict:
    """The histogram updates of the whole forest: weighted rows in the nodes
    of every level but the last, times the features a node samples."""
    count = model["leaf_stats"][:, :, 0]
    depth = int(math.log2(count.shape[1] + 1)) - 1
    row_levels = float(count[:, : (1 << depth) - 1].sum())
    updates = row_levels * features_per_node(cols)
    return {"flops": updates, "bytes": updates + 17.0 * row_levels, "row_levels": row_levels}


def fit_work(rows: int, cols: int, model: dict) -> dict:
    bins = int(model["bin_edges"].shape[1]) + 1
    parts = (sketch_work(rows, cols), binize_work(rows, cols, bins), hist_work(cols, model))
    return {"flops": sum(p["flops"] for p in parts), "bytes": sum(p["bytes"] for p in parts)}
