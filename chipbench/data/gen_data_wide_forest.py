"""``rf_reg_dbx``'s frame: the source's ``regression`` set exactly as
``gen_data.py`` makes it (``make`` hands over to it, parameter for parameter),
behind the cell's one precondition on the program.

The cell grows nodes that sample 1000 of 3000 features. A program without a
histogram that selects such a subset in the kernel
(``ops/rf_pallas.subblock_hist_sel_wide``, PR 39) still ACCEPTS the shape: it
plans every level onto a per-row gather of 1024 columns (206.7 ms a
16,384-row chunk on a v5e: 25 s a tree, 6 minutes a fit) in front of 64
unrolled kernel calls a chunk, 384 Mosaic calls to compile — an hour before
the first timed job, which no harness limit waits for. Such a program cannot
run this configuration in a time worth measuring, and is told so here, at
once and with an exit code, where the alternative is a run that is killed
(PERF.md section 6, PR 39). ``run.py`` imports this module before it makes
any data or touches the program's estimator.
"""
from chipbench.data import gen_data


def _require_wide_subset_histogram() -> None:
    from spark_rapids_ml_tpu.ops import rf_pallas

    if not hasattr(rf_pallas, "subblock_hist_sel_wide"):
        raise SystemExit(
            "chipbench: rf_reg_dbx needs a histogram that selects a 1000-feature subset in the kernel "
            "(spark_rapids_ml_tpu.ops.rf_pallas.subblock_hist_sel_wide); this program would gather 1024 columns a row, "
            "minutes a fit and an hour of compiles: not run"
        )


_require_wide_subset_histogram()


def make(seed: int, rows: int, cols: int, params: dict) -> dict:
    return gen_data.make(seed, rows, cols, params)
