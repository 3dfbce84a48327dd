"""link_reduce and the ten readers of PR 37. (a) on hand-built trace
dictionaries (``span_reduce.read``'s shape) whose answers are worked out in
the comments: a batch that puts and fetches the way LogisticRegression does, a
batch whose first program does not touch it, a staged batch whose program
takes it transposed, a batch that no operation names, a frame of one put, two
device planes; (b) on one trace recorded on the chip (``record_trace.py``: a
PCA job at 200,000 x 3000, whose frame goes up in four row blocks with a fold
a block and whose transform takes two batches), against values read by hand
from a dump of that trace."""
import copy
import importlib.util
import json
import os

import pytest

from chipbench import link_reduce as lr
from chipbench import span_reduce as sr
from chipbench import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
COLS = 3000
NEW = ("crossing_s.fit", "put_host_s.fit", "put_wait_s.fit", "put_wait_max_s.fit", "link_gb.job",
       "input_wait_s.transform", "fetch_tail_s.transform", "idle_in_solver_s.fit", "idle_unexplained_s.job", "live_share.fit")


def reader(metric):
    spec = importlib.util.spec_from_file_location("m_" + metric.replace(".", "_"), os.path.join(ROOT, "chipbench", "layer_metrics", metric + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_config(name):
    with open(os.path.join(ROOT, "chipbench", "configs", name + ".json")) as f:
        return json.load(f)


# ---- (a) a job drawn by hand; times in nanoseconds on one clock ----

FIT, CALL, INNER = "LogisticRegression.fit", "LogisticRegressionModel.transform.call", "LogisticRegressionModel.transform"
# (span_id, parent_id, name, lo, hi, attributes)
SPANS = [
    (1, None, FIT, 100, 5000, {}),
    (2, 1, "preprocess", 150, 2600, {}),
    (3, 2, "h2d.enqueue", 200, 2500, {"bytes": 12040, "host_bytes": 10040, "write_program": "_write_block", "blocks": 3}),
    (4, 3, "h2d.put", 210, 300, {"block": 0, "bytes": 4000}),
    (5, 3, "h2d.put", 310, 400, {"block": 1, "bytes": 4000}),
    (6, 3, "h2d.wait", 410, 1200, {"block": 0}),
    (7, 3, "h2d.put", 1210, 1300, {"block": 2, "bytes": 2000}),
    (8, 2, "h2d.enqueue", 2520, 2550, {"bytes": 40, "host_bytes": 40}),
    (9, 1, "fit.dispatch", 2700, 4900, {}),
    (10, 9, "solver.launch", 2710, 2800, {"program": "logreg_fit"}),
    (11, 9, "solver.fetch", 2810, 4800, {"n_evals": 3}),
    (20, None, CALL, 5200, 9800, {}),
    (21, 20, INNER, 5300, 9500, {}),
    # batch 0, 8 rows: put inside apply, fetched column by column inside transform.fetch
    (22, 21, "transform.stage", 5310, 5320, {"batch": 0, "rows": 8}),
    (23, 21, "transform.apply", 5400, 5600, {"batch": 0, "rows": 8, "bytes": 8 * COLS * 4}),
    (24, 23, "transform.h2d", 5410, 5500, {"bytes": 8 * COLS * 4, "rows": 8}),
    (25, 21, "transform.fetch", 5610, 6900, {"batch": 0, "rows": 8}),
    (26, 25, "transform.d2h", 5620, 6500, {"bytes": 32}),
    (27, 25, "transform.d2h", 6510, 6900, {"bytes": 64}),
    # batch 1, 4 rows: staged ahead; its program takes it transposed; fetched inside apply
    (28, 21, "transform.stage", 6905, 6990, {"batch": 1, "rows": 4}),
    (29, 28, "transform.h2d", 6910, 6980, {"bytes": 4 * COLS * 4, "rows": 4}),
    (30, 21, "transform.apply", 7000, 7750, {"batch": 1, "rows": 4, "bytes": 4 * COLS * 4}),
    (31, 30, "transform.d2h", 7110, 7700, {"bytes": 16}),
    # batch 2, 5 rows: no operation names it
    (32, 21, "transform.apply", 7800, 8350, {"batch": 2, "rows": 5, "bytes": 5 * COLS * 4}),
    (33, 32, "transform.h2d", 7805, 7840, {"bytes": 5 * COLS * 4, "rows": 5}),
    (34, 32, "transform.d2h", 7910, 8300, {"bytes": 20}),
    (35, 21, "transform.assemble", 8400, 8500, {}),
]
MODULES = [
    ("jit__fill(1)", 220, 240), ("jit__write_block(2)", 1000, 1100), ("jit__write_block(2)", 1900, 2000),
    ("jit__write_block(2)", 2900, 3000), ("jit_logreg_fit(3)", 3100, 4500),
    ("jit_convert_element_type(4)", 5450, 5460), ("jit_logreg_predict(5)", 6000, 6400),
    ("jit_descend(6)", 7010, 7400), ("jit_other(7)", 7850, 8000),
]
OPS = [
    ("%broadcast.1 = f32[10,3000]{1,0} broadcast(f32[] %p)", 220, 240),
    ("%dus.1 = f32[10,3000]{1,0} dynamic-update-slice(f32[10,3000]{1,0} %b, f32[4,3000]{1,0} %r, s32[] %i)", 1000, 1100),
    ("%dus.1 = f32[10,3000]{1,0} dynamic-update-slice(f32[10,3000]{1,0} %b, f32[4,3000]{1,0} %r, s32[] %i)", 1900, 2000),
    ("%dus.2 = f32[10,3000]{1,0} dynamic-update-slice(f32[10,3000]{1,0} %b, f32[2,3000]{1,0} %r, s32[] %i)", 2900, 3000),
    ("%fusion.7 = f32[3000]{0} fusion(f32[10,3000]{1,0} %x, f32[10]{0} %y), kind=kLoop", 3100, 3600),
    ("%fusion.8 = f32[3000]{0} fusion(f32[10,3000]{1,0} %x, f32[10]{0} %y), kind=kLoop", 3800, 4500),
    # batch 0's window opens with a program that does not touch the batch
    ("%convert.1 = f32[3000]{0} convert(f32[3000]{0} %p)", 5450, 5460),
    ("%fusion.1 = f32[8]{0} fusion(f32[8,3000]{0,1:T(8,128)} %p0, f32[3000]{0} %p1), kind=kLoop", 6000, 6300),
    ("%fusion.2 = f32[8,2]{1,0} fusion(f32[8]{0} %fusion.1), kind=kLoop", 6300, 6400),
    ("%custom-call.3 = u8[4,3072]{1,0} custom-call(f32[3000,4]{1,0} %p0), custom_call_target=\"tpu_custom_call\"", 7010, 7400),
    ("%iota.4 = s32[5]{0} iota(), iota_dimension=0", 7850, 8000),
]
PLANE = "/device:TPU:0"


def build(spans=SPANS, planes=None):
    trace = {
        "job": (0, 10000),
        "spans": sorted(({"name": n, "span_id": i, "parent_id": p, "lo": lo, "hi": hi} for i, p, n, lo, hi, _ in spans), key=lambda s: (s["lo"], -s["hi"])),
        "modules": {k: list(v[0]) for k, v in (planes or {PLANE: (MODULES, OPS)}).items()},
        "ops": {k: list(v[1]) for k, v in (planes or {PLANE: (MODULES, OPS)}).items()},
    }
    sink = [
        {"name": n, "ph": "X", "ts": lo * 1e-3, "dur": (hi - lo) * 1e-3, "args": dict(a, span_id=i, **({"parent_id": p} if p else {}))}
        for i, p, n, lo, hi, a in spans
    ]
    return trace, sink


@pytest.fixture
def hand(monkeypatch):
    def make(spans=SPANS, planes=None, extra_sink=()):
        trace, sink = build(spans, planes)
        monkeypatch.setattr(sr, "traced", lambda ctx: ctx["hand_trace"])
        return {"config": load_config("logreg_dbx"), "rows": 10, "spans": sink + list(extra_sink), "hand_trace": trace}
    return make


def test_the_crossing_ends_with_the_last_write_on_the_device(hand):
    ctx = hand()
    assert lr.crossing(ctx) == {"lo": 200, "hi": 3000, "seconds": pytest.approx(2800e-9), "bytes": 10000, "puts": 3}
    assert reader("crossing_s.fit")(ctx) == pytest.approx(2800e-9)
    # ... and is no more than the wait of a solver that waits for the frame
    assert reader("crossing_s.fit")(ctx) <= sr.fit_split(ctx)["input_wait"] == pytest.approx(2900e-9)


def test_the_sink_readers_count_every_fit_of_the_window(hand):
    grow = [{"name": "forest.grow_group", "ph": "X", "ts": 0, "dur": 1, "args": {"span_id": 90 + i, "live_share": v}} for i, v in enumerate((0.3, 0.4))]
    ctx = hand(extra_sink=grow)
    assert reader("put_host_s.fit")(ctx) == pytest.approx(270e-9)          # 90 + 90 + 90 ns in one fit
    assert reader("put_wait_s.fit")(ctx) == pytest.approx(790e-9)
    assert reader("put_wait_max_s.fit")(ctx) == pytest.approx(790e-9)
    # the frame and its mask, the labels, and three batches of the second crossing
    assert reader("link_gb.job")(ctx) == pytest.approx((10040 + 40 + (8 + 4 + 5) * COLS * 4) / 1e9)
    assert reader("live_share.fit")(ctx) == pytest.approx(0.35)
    # two fits in the window: every per-fit number halves, the longest wait stays
    ctx["spans"] = ctx["spans"] + [dict(s, args=dict(s["args"], span_id=s["args"]["span_id"] + 100)) for s in ctx["spans"] if s["name"] == FIT]
    assert reader("put_host_s.fit")(ctx) == pytest.approx(135e-9) and reader("put_wait_max_s.fit")(ctx) == pytest.approx(790e-9)
    assert reader("link_gb.job")(ctx) == pytest.approx((10040 + 40 + 17 * COLS * 4) / 2e9)


def test_a_batch_waits_until_an_operation_names_it(hand):
    ctx = hand()
    b0, b1, b2 = lr.batches(ctx)
    # batch 0: the convert at 5450 does not touch the batch; the fusion at 6000 takes f32[8,3000]
    assert (b0["lo"], b0["first"][PLANE], b0["last"][PLANE], b0["hi"]) == (5400, 6000, 6400, 6900)
    # batch 1, staged: its program takes it as f32[3000,4] ten nanoseconds after the opening
    assert (b1["lo"], b1["first"][PLANE], b1["last"][PLANE], b1["hi"]) == (7000, 7010, 7400, 7700)
    # batch 2: nothing names f32[5,3000]: the first program of the window
    assert (b2["lo"], b2["first"][PLANE], b2["last"][PLANE], b2["hi"]) == (7800, 7850, 8000, 8300)
    assert reader("input_wait_s.transform")(ctx) == pytest.approx((600 + 10 + 50) * 1e-9)
    assert reader("fetch_tail_s.transform")(ctx) == pytest.approx((500 + 300 + 300) * 1e-9)


def test_every_idle_nanosecond_of_the_job_has_one_owner(hand, capfd):
    ctx = hand()
    assert reader("idle_in_solver_s.fit")(ctx) == pytest.approx(200e-9)      # 3600 .. 3800 inside 3100 .. 4500
    tiling = lr.idle_tiling(ctx)
    # busy: 20 + 3 x 100 + 500 + 700 + 10 + 400 + 390 + 150 = 2470 of 10000
    assert tiling["total"] == pytest.approx(7530e-9)
    assert tiling["input_wait_fit"] == pytest.approx(2580e-9)                # 2900 less the fill and the writes
    assert tiling["in_solver"] == pytest.approx(200e-9) and tiling["fetch_tail_fit"] == pytest.approx(300e-9)
    assert tiling["input_wait_transform"] == pytest.approx(650e-9)           # 660 less the convert's 10
    assert tiling["fetch_tail_transform"] == pytest.approx(1100e-9)
    # outside: 0..200, 4800..5400, 6900..7000, 7700..7800, 8300..10000
    assert tiling["unexplained"] == pytest.approx(2700e-9)
    assert sum(v for k, v in tiling.items() if k != "total") == pytest.approx(tiling["total"])
    assert reader("idle_unexplained_s.job")(ctx) == pytest.approx(2700e-9)
    err = capfd.readouterr().err
    assert "chipbench: idle tiling: input_wait_fit" in err
    assert "the frame crossed in 3 puts, 0.000 GB in 0.0000 s: 3.57 GB/s" in err         # 10000 bytes in 2800 ns


def test_a_run_parses_its_trace_once_however_many_readers_ask(hand, monkeypatch, capfd):
    ctx, calls = hand(), []
    walk = sr.descendants
    monkeypatch.setattr(sr, "descendants", lambda trace, root: calls.append(root["name"]) or walk(trace, root))
    first = {metric: reader(metric)(ctx) for metric in NEW}
    assert calls == [FIT, CALL]                                                  # the fit's spans once, the transform's once
    assert {metric: reader(metric)(ctx) for metric in NEW} == first and calls == [FIT, CALL]
    assert capfd.readouterr().err.count("idle tiling:") == 1
    # another run's ctx is parsed afresh
    assert reader("crossing_s.fit")(hand()) == first["crossing_s.fit"] and calls == [FIT, CALL, FIT]


def test_two_device_planes_are_averaged_and_the_frame_lands_with_the_later(hand):
    modules_b = [m if m[1] != 2900 else (m[0], 2950, 3050) for m in MODULES]
    ops_b = [o if o[1] != 2900 else (o[0], 2950, 3050) for o in OPS if o[1] != 3800] + [(OPS[5][0], 3600, 4500)]  # no gap in the solver
    ops_b = [o if o[1] != 6000 else (o[0], 6100, 6300) for o in ops_b]     # the batch's program starts 100 later there
    ctx = hand(planes={PLANE: (MODULES, OPS), "/device:TPU:1": (modules_b, ops_b)})
    assert lr.crossing(ctx)["hi"] == 3050
    assert reader("idle_in_solver_s.fit")(ctx) == pytest.approx(100e-9)      # (200 + 0) / 2
    assert reader("input_wait_s.transform")(ctx) == pytest.approx((660 + 760) / 2 * 1e-9)
    tiling = lr.idle_tiling(ctx)
    assert sum(v for k, v in tiling.items() if k != "total") == pytest.approx(tiling["total"])
    assert tiling["total"] == pytest.approx((7530 + 7530 - 200 + 100) / 2 * 1e-9)


def test_a_frame_of_one_put_has_no_crossing_to_read(hand):
    one_put = [s for s in copy.deepcopy(SPANS) if s[2] not in ("h2d.put", "h2d.wait")]
    for s in one_put:
        s[5].pop("write_program", None)
    ctx = hand(spans=one_put)
    for metric in ("crossing_s.fit", "put_host_s.fit", "put_wait_s.fit", "put_wait_max_s.fit"):
        assert reader(metric)(ctx) is None, metric
    # the rest of the job reads as before
    assert reader("link_gb.job")(ctx) is not None and reader("idle_unexplained_s.job")(ctx) == pytest.approx(2700e-9)


def test_a_program_without_the_spans_gives_nothing_and_never_zero(hand):
    # the parent's program: no h2d.put / h2d.wait, no host_bytes, no transform.h2d / transform.d2h, no live_share
    parent = [(i, p, n, lo, hi, {k: v for k, v in a.items() if k not in ("host_bytes", "write_program")})
              for i, p, n, lo, hi, a in SPANS if n not in ("h2d.put", "h2d.wait", "transform.h2d", "transform.d2h")]
    grow = [{"name": "forest.grow_group", "ph": "X", "ts": 0, "dur": 1, "args": {"span_id": 90, "levels_declined": 0}}]
    ctx = hand(spans=parent, extra_sink=grow)
    for metric in NEW:
        if metric != "idle_in_solver_s.fit":                                   # reads the fit's old spans alone
            assert reader(metric)(ctx) is None, metric
    assert reader("idle_in_solver_s.fit")(ctx) == pytest.approx(200e-9)
    # no device plane (the CPU rehearsal), and no trace at all
    trace, sink = build()
    bare = {"config": load_config("logreg_dbx"), "rows": 10, "spans": sink, "hand_trace": dict(trace, modules={}, ops={})}
    none = {"config": load_config("logreg_dbx"), "rows": 10, "spans": [], "hand_trace": None}
    for metric in ("crossing_s.fit", "input_wait_s.transform", "fetch_tail_s.transform", "idle_in_solver_s.fit", "idle_unexplained_s.job"):
        assert reader(metric)(bare) is None, metric
    for metric in NEW:
        assert reader(metric)(none) is None, metric


# ---- (b) a PCA job recorded on the chip (TPU v5 lite, PR 37): 200,000 x 3000 f32 in four row blocks ----
# chiprun -- python3 chipbench/tests/record_trace.py chiprun_out/rec37b/pca_200000rows_link \
#     --workload pca_dbx.job --seed 3700000011 --seconds 1 --rows 200000
# python3 chipbench/tests/trim_trace.py chiprun_out/rec37b/pca_200000rows_link.xplane.pb chipbench/tests/data/pca_200000rows_link.xplane.pb
# ``trim_trace.py`` drops the host-plane lines of the runtime's linearizing worker threads (``futex-default-SDomainT/*``
# with ``Transpose`` events: 28,083 events on thirteen lines, no reader looks at them) to bring the file from 1.40 MB
# under 1 MB; every other line is as recorded, and the ``.spans.json`` is the recorder's, whole.

REC = os.path.join(HERE, "data", "pca_200000rows_link")


@pytest.fixture
def rec():
    with open(REC + ".spans.json") as f:
        spans = json.load(f)
    return {"config": load_config("pca_dbx"), "rows": 200000, "spans": spans, "xplane": REC + ".xplane.pb"}


def test_the_recorded_frame_goes_up_in_four_blocks(rec):
    t = sr.read(rec["xplane"])
    assert os.path.getsize(rec["xplane"]) < 1 << 20
    enqueue = next(s for s in t["spans"] if s["name"] == "h2d.enqueue")
    args = sr.attrs(rec, enqueue)
    assert (args["blocks"], args["folded_blocks"], args["write_program"]) == (4, 4, "_write_block")
    assert args["host_bytes"] == args["bytes"] == 200000 * 3000 * 4 + 200000 * 4      # nothing padded: the frame and its mask
    inside = [(s["name"], sr.attrs(rec, s).get("block")) for s in t["spans"] if s["parent_id"] == enqueue["span_id"]]
    assert inside == [("h2d.put", 0), ("h2d.fold", 0), ("h2d.put", 1), ("h2d.fold", 1), ("h2d.wait", 0), ("h2d.put", 2),
                      ("h2d.fold", 2), ("h2d.wait", 1), ("h2d.put", 3), ("h2d.fold", 3)]
    # the enqueue returns at 223956190 with two writes still to run: the tail block's ends at 307132414
    found = lr.crossing(rec)
    assert (found["lo"], found["hi"], found["puts"]) == (48827663.0, 307132414.0, 4)
    assert found["hi"] > enqueue["hi"] == 223956190.0
    assert found["bytes"] == 3 * 65536 * 3000 * 4 + 3392 * 3000 * 4 == 2_400_000_000
    assert reader("crossing_s.fit")(rec) == pytest.approx(0.258304751, abs=1e-9)
    # PCA's solver is the first fold: it starts under the crossing, one block in
    assert sr.fit_split(rec)["input_wait"] == pytest.approx(0.143501005, abs=1e-9)
    assert sr.fit_split(rec)["input_wait"] < reader("crossing_s.fit")(rec)


def test_the_recorded_window_readers(rec, capfd):
    # one fit in the window; the sink's durations are the host clock's (the trace reads 43.368 and 115.491 ms)
    assert reader("put_host_s.fit")(rec) == pytest.approx(0.043336724, abs=1e-9)
    assert reader("put_wait_s.fit")(rec) == pytest.approx(0.115475335, abs=1e-9)
    assert reader("put_wait_max_s.fit")(rec) == pytest.approx(0.107235746, abs=1e-9)
    assert "longest h2d.wait 0.1072 s (block 0" in capfd.readouterr().err
    # the frame and its mask up once for the fit, the frame again in two batches
    assert reader("link_gb.job")(rec) == pytest.approx((2_400_800_000 + 2_400_000_000) / 1e9)
    assert reader("live_share.fit")(rec) is None


def test_the_recorded_batches_wait_for_the_link_and_the_tiling_closes(rec):
    b0, b1 = lr.batches(rec)
    (plane,) = b0["first"]
    # batch 0: apply opens at 316869217; jit__project starts at 478995450 and its first operation that
    # takes f32[131072,3000] 655 ns later; the last ends at 481102375; the d2h closes at 483901614
    assert (b0["lo"], b0["first"][plane], b0["last"][plane], b0["hi"]) == (316869217.0, 478996105.0, 481102375.0, 483901614.0)
    assert (b1["lo"], b1["first"][plane], b1["last"][plane], b1["hi"]) == (484158274.0, 569841310.0, 570939467.0, 573591962.0)
    assert reader("input_wait_s.transform")(rec) == pytest.approx((162126888 + 85683036) * 1e-9, abs=1e-9)
    assert reader("fetch_tail_s.transform")(rec) == pytest.approx((2799239 + 2652495) * 1e-9, abs=1e-9)
    # the folds wait for their blocks: 118.69 ms from the first fold to the finish's end, busy 81.73
    assert reader("idle_in_solver_s.fit")(rec) == pytest.approx(0.036966994, abs=1e-9)
    tiling = lr.idle_tiling(rec)
    summary = tr.reduce(rec["xplane"], rec["config"]["annotations"])
    assert tiling["total"] == pytest.approx(summary["window_s"] - summary["busy_s"], abs=1e-9)   # device_idle_pct's idle seconds
    assert sum(v for k, v in tiling.items() if k != "total") == pytest.approx(tiling["total"], abs=1e-12)
    assert reader("idle_unexplained_s.job")(rec) == tiling["unexplained"] == pytest.approx(0.002690301, abs=1e-9)
    assert tiling["input_wait_transform"] == pytest.approx(0.247809256, abs=1e-9)   # busy for 668 ns before the batch's first reader


def test_the_older_fixture_has_none_of_the_new_spans():
    old = {"config": load_config("logreg_dbx"), "rows": 32768, "xplane": os.path.join(HERE, "data", "logreg_32768rows_spans.xplane.pb")}
    with open(os.path.join(HERE, "data", "logreg_32768rows_spans.spans.json")) as f:
        old["spans"] = json.load(f)
    for metric in NEW:
        if metric != "idle_in_solver_s.fit":
            assert reader(metric)(old) is None, metric
    assert reader("idle_in_solver_s.fit")(old) == pytest.approx(0.0, abs=1e-4)       # one while loop
