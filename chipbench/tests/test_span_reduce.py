"""span_reduce and the nine readers that use it, on a small trace recorded on
the chip (``record_trace.py``: one logreg job at 32,768 × 3000 on a TPU v5
lite, PR 27, with the window's sink spans beside it), against values read by
hand from a dump of that trace; and ``trace_reduce`` on the same trace, to
show that the ``tpuml:`` events do not leak into the old reducer."""
import importlib.util
import json
import os

import pytest

from chipbench import span_reduce as sr
from chipbench import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TRACE = os.path.join(HERE, "data", "logreg_32768rows_spans.xplane.pb")
SPANS = os.path.join(HERE, "data", "logreg_32768rows_spans.spans.json")
OLD_TRACE = os.path.join(HERE, "data", "logreg_32768rows.xplane.pb")


def reader(metric):
    spec = importlib.util.spec_from_file_location("m_" + metric.replace(".", "_"), os.path.join(ROOT, "chipbench", "layer_metrics", metric + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "chipbench", "configs", "logreg_dbx.json")) as f:
        return json.load(f)


@pytest.fixture
def ctx(config):
    with open(SPANS) as f:
        spans = json.load(f)
    return {"config": config, "rows": 32768, "spans": spans, "xplane": TRACE}


def test_spans_modules_and_ops_of_the_recorded_trace():
    t = sr.read(TRACE)
    assert t["job"] == (45274467.0, 178644616.0)
    names = [s["name"] for s in t["spans"]]
    assert names == [
        "LogisticRegression.fit", "preprocess", "h2d.enqueue", "h2d.enqueue", "fit.dispatch", "solver.launch", "solver.fetch",
        "LogisticRegressionModel.transform.call", "transform.extract", "LogisticRegressionModel.transform",
        "transform.stage", "transform.apply", "transform.fetch", "transform.assemble",
    ]
    by = {s["span_id"]: s for s in t["spans"]}
    assert [(s["span_id"], s["parent_id"]) for s in t["spans"][:7]] == [(15, None), (16, 15), (17, 16), (18, 16), (19, 15), (20, 19), (21, 19)]
    for s in t["spans"]:
        if s["parent_id"] is not None:
            assert by[s["parent_id"]]["lo"] <= s["lo"] and s["hi"] <= by[s["parent_id"]]["hi"]
    (modules,) = t["modules"].values()
    assert [m[0].split("(")[0] for m in modules] == ["jit_convert_element_type"] * 3 + ["jit_logreg_fit", "jit_logreg_predict"]
    assert modules[3][1:] == (94139516.0, 123535079.0)
    (ops,) = t["ops"].values()
    assert len(ops) > 1000 and all(" = " in name for name, _, _ in ops[:50])


def test_fit_split_tiles_enqueue_to_fetch(ctx, capfd):
    split = sr.fit_split(ctx)
    assert split["input_wait"] == pytest.approx(0.048446559, abs=1e-9)      # 94139516 - 45692957
    assert split["solver_device"] == pytest.approx(0.029395563, abs=1e-9)   # 123535079 - 94139516
    assert split["fetch_tail"] == pytest.approx(0.005231041, abs=1e-9)      # 128766120 - 123535079
    # the additive identity: nothing between the three, first enqueue to the fetch's close
    assert sum(split.values()) == pytest.approx((128766120 - 45692957) * 1e-9, abs=1e-9)
    # ... which is preprocess + the glue between + fit.dispatch, less what lies before the first put and after the last fetch
    fit = sr.traced_fit(ctx)
    by = {s["name"]: s for s in reversed(fit["spans"])}
    outer = (by["fit.dispatch"]["hi"] - by["preprocess"]["lo"]) * 1e-9
    assert 0 < outer - sum(split.values()) < 0.001
    assert reader("input_wait_s.fit")(ctx) == split["input_wait"]
    assert reader("solver_device_s.fit")(ctx) == split["solver_device"]
    assert reader("fetch_tail_s.fit")(ctx) == split["fetch_tail"]
    err = capfd.readouterr().err
    assert "chipbench: fit split: input_wait 0.048447 s + solver_device 0.029396 s + fetch_tail 0.005231 s = 0.083073 s" in err
    assert "fit.dispatch 0.081326 s" in err and "solver.fetch 0.078677 s" in err


def test_frame_reads_inside_the_loop(ctx):
    reads = sr.frame_reads(ctx)
    # n_iter 22: the forward and the backward pass of every iteration's first trial, and of one backtracking trial
    assert reads == {"reads": 46.0, "shape": "f32[32768,3000]", "ops": {
        "multiply_reduce_fusion.37": 22, "multiply_reduce_fusion.38": 22, "multiply_reduce_fusion.43": 1, "multiply_reduce_fusion.44": 1}}
    assert sr.traced_fit(ctx)["fetch_attrs"]["n_evals"] == 24 and sr.traced_fit(ctx)["fetch_attrs"]["n_iter"] == 22
    assert reader("x_reads_per_eval.fit")(ctx) == pytest.approx(46 / 24)
    assert sr.opcode_and_operands("%w = (f32[8]{0:T(128)S(1)}, s32[]) while((f32[8]{0} %a, s32[] %b)), body=%b1")[0] == "while"
    assert sr.opcode_and_operands("%f.3 = f32[8]{0} fusion(f32[8,4]{1,0:T(8,128)} %p), kind=kLoop") == ("fusion", "f32[8,4]{1,0:T(8,128)} %p), kind=kLoop")


def test_window_readers_on_the_sink_spans(ctx):
    # five fits and five transforms in the window; read from the spans file by hand
    fits = [s for s in ctx["spans"] if s["name"] == "LogisticRegression.fit"]
    assert len(fits) == 5
    assert reader("launch_s.fit")(ctx) == pytest.approx(0.0025990116, rel=1e-6)
    assert reader("evals_per_fit")(ctx) == 24.0
    assert reader("stage_s.transform")(ctx) == pytest.approx(3.55e-05, rel=1e-3)
    assert reader("fetch_s.transform")(ctx) == pytest.approx(0.0460841396, rel=1e-6)
    assert reader("assemble_s.transform")(ctx) == pytest.approx(3.73758e-05, rel=1e-4)
    total = sum(reader(m)(ctx) for m in ("stage_s.transform", "fetch_s.transform", "assemble_s.transform"))
    calls = [s for s in ctx["spans"] if s["name"] == "LogisticRegressionModel.transform.call"]
    assert total <= sum(s["dur"] for s in calls) * 1e-6 / len(calls)
    launch = [s for s in ctx["spans"] if s["name"] == "solver.launch"][0]["args"]
    assert (launch["program"], launch["loss_grad"], launch["declined"]) == ("logreg_fit", "xla_autodiff", "d%128,d<=2048")


def test_idle_goes_to_the_innermost_span(ctx):
    t = sr.read(TRACE)
    idle = dict(sr.idle_by_span(t))
    summary = tr.reduce(TRACE, ctx["config"]["annotations"])
    # every idle second of the job has one owner: the spans' own shares and what lies outside them add up
    assert sum(idle.values()) == pytest.approx(summary["window_s"] - summary["busy_s"], abs=1e-6)
    # the host is blocked in two fetches: the fit's (waiting for the frame) and the transform's
    top = sorted(idle, key=idle.get, reverse=True)[:2]
    assert top == ["solver.fetch", "transform.fetch"]
    assert idle["solver.fetch"] == pytest.approx(0.0493, abs=1e-4) and idle["transform.fetch"] == pytest.approx(0.0453, abs=1e-4)
    assert idle["fit.dispatch"] < 0.001 and idle["LogisticRegression.fit"] < 0.002


def test_old_reducer_is_blind_to_the_span_events(ctx):
    s = tr.reduce(TRACE, ctx["config"]["annotations"])
    assert s["phase_count"] == {"preprocess": 1, "dispatch": 1, "transform": 1}
    assert s["devices"] == 1 and 0 < s["busy_s"] < s["window_s"]
    assert s["window_s"] == pytest.approx((178644616 - 45274467) * 1e-9, abs=1e-9)
    # the dispatch phase is the hand-written annotation (47566196..128949490), not the span of the same name
    assert s["phase_s"]["dispatch"] == pytest.approx((128949490 - 47566196) * 1e-9, abs=1e-9)


def test_nothing_to_read_gives_nothing(config):
    # the trace of PR 26 has no span events; a rehearsal has no trace at all
    old = {"config": config, "rows": 32768, "spans": [], "xplane": OLD_TRACE}
    none = {"config": config, "rows": 8192, "spans": [], "xplane": os.path.join(HERE, "data", "no_such.xplane.pb")}
    for metric in ("input_wait_s.fit", "solver_device_s.fit", "fetch_tail_s.fit", "x_reads_per_eval.fit",
                   "launch_s.fit", "evals_per_fit", "stage_s.transform", "fetch_s.transform", "assemble_s.transform"):
        assert reader(metric)(old) is None, metric
        assert reader(metric)(none) is None, metric
    assert sr.idle_by_span(sr.read(OLD_TRACE)) == [("outside_spans", pytest.approx(0.101216930, abs=1e-6))]
