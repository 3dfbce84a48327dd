"""Operations and bytes that a random-forest fit NEEDS, from the shapes and
the forest it returned — whatever implements them.

* the sketch: the quantile edges come from a sample of ``min(rows, 131072)``
  rows, sorted a column: m·cols·ceil(log2 m) compares, one read of the sample;
* ``binize``: every value is compared with its column's ``bins − 1`` edges
  (rows·cols·(bins − 1) compares), X is read once (4 bytes a value) and the
  bins written once (1 byte a value);
* the growth, a tree and a level: every row that still sits in a node (the
  forest's own class counts say how many: ``leaf_stats`` summed over a level;
  Poisson(1) weights count a row as often as the tree drew it) adds its weight
  to one histogram cell for each of the node's k sampled features — one update
  and one bin byte read a (row, feature), bins read once a level at the
  sampled columns — and is then routed by one more bin (1 byte), with its
  label and weight (8 bytes) read once a level.

The published 3000 columns, 128 bins and ceil(sqrt(3000)) = 55 features a
node count, not the lane padding, the node-sorted copy or the one-hot
products the program turns an update into: the algorithm's floor, so no share
of it can pass 100%. An update and a compare count as one operation each at
the chip's peak.
"""
import math

SKETCH_ROWS = 131072


def features_per_node(cols: int) -> int:
    return max(1, min(cols, math.ceil(math.sqrt(cols))))


def sketch_work(rows: int, cols: int) -> dict:
    m = min(rows, SKETCH_ROWS)
    return {"flops": float(m) * cols * math.ceil(math.log2(max(m, 2))), "bytes": 4.0 * m * cols}


def binize_work(rows: int, cols: int, bins: int) -> dict:
    return {"flops": float(rows) * cols * (bins - 1), "bytes": 5.0 * rows * cols}


def hist_work(cols: int, model: dict) -> dict:
    """The histogram updates of the whole forest: weighted rows in the nodes
    of every level but the last, times the features a node samples."""
    leaf = model["leaf_stats"]
    trees, nodes = leaf.shape[0], leaf.shape[1]
    depth = int(math.log2(nodes + 1)) - 1
    per_node = leaf.reshape(trees, nodes, -1).sum(axis=2)
    row_levels = float(per_node[:, : (1 << depth) - 1].sum())
    updates = row_levels * features_per_node(cols)
    return {"flops": updates, "bytes": updates + 9.0 * row_levels, "row_levels": row_levels}


def fit_work(rows: int, cols: int, model: dict) -> dict:
    bins = int(model["bin_edges"].shape[1]) + 1
    parts = (sketch_work(rows, cols), binize_work(rows, cols, bins), hist_work(cols, model))
    return {"flops": sum(p["flops"] for p in parts), "bytes": sum(p["bytes"] for p in parts)}
