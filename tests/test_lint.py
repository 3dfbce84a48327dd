"""tpuml_lint: per-rule positive/suppressed/negative fixtures, baseline
mechanics, envspec parse semantics, and the whole-repo integration run
(the tree must lint clean with the committed — empty — baseline)."""

import ast
import json
import os
import re
import subprocess
import sys
import textwrap

import pytest

import tpuml_lint
from tpuml_lint import (
    tpu001_raw_env,
    tpu003_jit_in_loop,
    tpu004_nondeterminism,
    tpu005_static_args,
    tpu006_lane_align,
    tpu007_metric_catalog,
    tpu008_label_cardinality,
    tpu009_inline_pspec,
)
from tpuml_lint.core import (
    Finding,
    SourceFile,
    apply_baseline,
    load_baseline,
    write_baseline,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def lint_snippet(rule, code, path="pkg/mod.py"):
    """Run one per-file rule over a source snippet; suppressions applied."""
    text = textwrap.dedent(code)
    sf = SourceFile(
        path=path, abspath="/" + path, text=text,
        tree=ast.parse(text),
    )
    return [f for f in rule.check_file(sf) if not sf.suppressed(f)]


# --- TPU001: raw env reads -------------------------------------------------


def test_tpu001_flags_all_read_forms():
    findings = lint_snippet(tpu001_raw_env, """
        import os
        from os import environ, getenv

        a = os.environ.get("TPUML_RETRIES")
        b = os.getenv("TPUML_CKPT_DIR", "x")
        c = os.environ["TPUML_NUM_PROCS"]
        d = "TPUML_COORDINATOR" in os.environ
        e = environ.get("TPUML_LIB")
        f = getenv("TPUML_BLAS_LIB")
    """)
    assert len(findings) == 6
    assert all(f.rule == "TPU001" for f in findings)
    assert "envspec" in findings[0].fixit


def test_tpu001_aliased_import():
    findings = lint_snippet(tpu001_raw_env, """
        import os as _os
        v = _os.environ.get("TPUML_UMAP_OPT", "auto")
    """)
    assert len(findings) == 1


def test_tpu001_allows_writes_and_non_tpuml():
    findings = lint_snippet(tpu001_raw_env, """
        import os
        os.environ["TPUML_RETRIES"] = "3"     # write: allowed
        os.environ.pop("TPUML_RETRIES", None) # write: allowed
        del os.environ["TPUML_CKPT_DIR"]      # write: allowed
        path = os.environ.get("HOME")         # not TPUML_*
    """)
    assert findings == []


def test_tpu001_exempts_envspec_itself():
    findings = lint_snippet(
        tpu001_raw_env,
        'import os\nx = os.environ.get("TPUML_RETRIES")\n',
        path="spark_rapids_ml_tpu/runtime/envspec.py",
    )
    assert findings == []


def test_tpu001_suppression_comment():
    findings = lint_snippet(tpu001_raw_env, """
        import os
        x = os.environ.get("TPUML_NB_CPU")  # tpuml: ignore[TPU001]
        # tpuml: ignore[TPU001]
        y = os.environ.get("TPUML_NB_CPU")
        z = os.environ.get("TPUML_NB_CPU")  # tpuml: ignore[TPU003]
    """)
    assert len(findings) == 1  # wrong code doesn't suppress


# --- TPU003: jit construction hazards --------------------------------------


def test_tpu003_jit_in_loop():
    findings = lint_snippet(tpu003_jit_in_loop, """
        import jax
        def fit(chunks):
            for c in chunks:
                f = jax.jit(lambda x: x + 1)
                f(c)
    """)
    assert len(findings) == 1
    assert "loop" in findings[0].message


def test_tpu003_partial_jit_and_comprehension():
    findings = lint_snippet(tpu003_jit_in_loop, """
        import functools
        import jax
        def fit(fns):
            return [functools.partial(jax.jit, static_argnames=("n",))(f)
                    for f in fns]
    """)
    assert len(findings) == 1


def test_tpu003_construct_and_invoke_per_call():
    findings = lint_snippet(tpu003_jit_in_loop, """
        import jax
        def fetch(arr):
            return jax.jit(lambda a: a * 2)(arr)
    """)
    assert len(findings) == 1
    assert "per call" in findings[0].message


def test_tpu003_clean_patterns():
    findings = lint_snippet(tpu003_jit_in_loop, """
        import functools
        import jax

        @jax.jit
        def f(x):
            return x

        @functools.partial(jax.jit, static_argnames=("n",))
        def g(x, n):
            return x * n

        h = jax.jit(lambda x: x)  # module-level: constructed once

        def fit(chunks):
            for c in chunks:
                f(c)  # calling a cached jit in a loop is the whole point
    """)
    assert findings == []


# --- TPU004: nondeterminism ------------------------------------------------


def test_tpu004_numpy_global_rng():
    findings = lint_snippet(tpu004_nondeterminism, """
        import numpy as np
        def init(shape):
            np.random.seed(0)
            return np.random.randn(*shape)
    """)
    assert len(findings) == 2
    assert "default_rng" in findings[0].fixit


def test_tpu004_stdlib_random_module_calls():
    findings = lint_snippet(tpu004_nondeterminism, """
        import random
        def jitter():
            return random.uniform(0, 1)
    """)
    assert len(findings) == 1


def test_tpu004_allows_seeded_instances():
    findings = lint_snippet(tpu004_nondeterminism, """
        import random
        import numpy as np
        rng = random.Random(1234)
        gen = np.random.default_rng(0)
        v = rng.uniform(0, 1)
    """)
    assert findings == []


def test_tpu004_clock_in_traced_code():
    findings = lint_snippet(tpu004_nondeterminism, """
        import time
        import jax

        @jax.jit
        def step(x):
            t0 = time.time()
            return x + t0

        def host_timer():
            return time.time()  # outside trace: fine

        def add_kernel(x_ref, o_ref):
            o_ref[...] = x_ref[...] + time.time()
    """)
    assert len(findings) == 2
    assert {"step", "add_kernel"} == {
        f.message.split("'")[1] for f in findings
    }


def test_tpu004_prngkey_in_loop():
    findings = lint_snippet(tpu004_nondeterminism, """
        import jax
        def fit(n, base):
            for epoch in range(n):
                k = jax.random.PRNGKey(epoch)
            for epoch in range(n):
                k = jax.random.fold_in(base, epoch)  # the sanctioned form
    """)
    assert len(findings) == 1
    assert "fold_in" in findings[0].fixit


# --- TPU005: static arg hazards --------------------------------------------


def test_tpu005_unknown_static_argname():
    findings = lint_snippet(tpu005_static_args, """
        import functools
        import jax

        @functools.partial(jax.jit, static_argnames=("n_bins",))
        def hist(x, nbins):
            return x * nbins
    """)
    assert len(findings) == 1
    assert "n_bins" in findings[0].message


def test_tpu005_unhashable_default():
    findings = lint_snippet(tpu005_static_args, """
        import functools
        import jax

        @functools.partial(jax.jit, static_argnames=("shape",))
        def zeros(x, shape=[8, 128]):
            return x
    """)
    assert len(findings) == 1
    assert "unhashable" in findings[0].message


def test_tpu005_argnums_out_of_range():
    findings = lint_snippet(tpu005_static_args, """
        import functools
        import jax

        @functools.partial(jax.jit, static_argnums=(2,))
        def f(x, y):
            return x + y
    """)
    assert len(findings) == 1


def test_tpu005_assigned_jit_of_local_def():
    findings = lint_snippet(tpu005_static_args, """
        import jax

        def _impl(x, cfg):
            return x

        f = jax.jit(_impl, static_argnames=("config",))
    """)
    assert len(findings) == 1


def test_tpu005_clean():
    findings = lint_snippet(tpu005_static_args, """
        import functools
        import jax

        @functools.partial(jax.jit, static_argnames=("n", "shape"))
        def f(x, n, shape=(8, 128)):
            return x

        @functools.partial(jax.jit, static_argnames=("opt",))
        def g(x, **opts):
            return x  # **kwargs can absorb any static name
    """)
    assert findings == []


# --- TPU006: lane alignment ------------------------------------------------


def test_tpu006_unaligned_minor_dim():
    findings = lint_snippet(tpu006_lane_align, """
        import jax.experimental.pallas as pl
        spec = pl.BlockSpec((8, 100), lambda i: (i, 0))
    """)
    assert len(findings) == 1
    assert "128" in findings[0].message


def test_tpu006_clean_specs():
    findings = lint_snippet(tpu006_lane_align, """
        import jax.experimental.pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        a = pl.BlockSpec((8, 256), lambda i: (i, 0))     # aligned
        b = pl.BlockSpec((bn, feat_pad), lambda i: (i, 0))  # symbolic
        c = pl.BlockSpec((1, 1), memory_space=pltpu.SMEM)   # scalar
        d = pl.BlockSpec((1, 1), lambda i: (0, 0))          # (1,1) scalar
    """)
    assert findings == []


# --- TPU007: metric catalog ------------------------------------------------


def lint_project_snippet(rule, code, path="pkg/mod.py"):
    """Run one project rule over a single-file snippet; suppressions
    applied (mirrors how the runner filters project findings)."""
    text = textwrap.dedent(code)
    sf = SourceFile(
        path=path, abspath="/" + path, text=text,
        tree=ast.parse(text),
    )
    return [
        f for f in rule.check_project([sf], REPO_ROOT)
        if f.path != sf.path or not sf.suppressed(f)
    ]


def test_tpu007_flags_undeclared_names():
    findings = lint_project_snippet(tpu007_metric_catalog, """
        from spark_rapids_ml_tpu.runtime import counters, telemetry
        counters.bump("bogus_counter")
        counters.note("bogus_gauge", 3)
        telemetry.counter("bogus_tele").inc()
        telemetry.histogram("bogus_hist").observe(0.5)
    """)
    assert len(findings) == 4
    assert all(f.rule == "TPU007" for f in findings)
    assert all("not declared" in f.message for f in findings)


def test_tpu007_flags_kind_mismatch():
    # resumed_from is declared as a gauge; bump() implies a counter
    findings = lint_project_snippet(tpu007_metric_catalog, """
        from spark_rapids_ml_tpu.runtime import counters
        counters.bump("resumed_from")
    """)
    assert len(findings) == 1
    assert "declared as a gauge" in findings[0].message


def test_tpu007_allows_declared_and_dynamic_names():
    findings = lint_project_snippet(tpu007_metric_catalog, """
        from spark_rapids_ml_tpu.runtime import counters, telemetry
        counters.bump("retries")
        counters.note("resumed_from", 7)
        counters.get("retries")
        telemetry.counter("gang_dispatches").inc(2)
        telemetry.gauge("hbm_budget_bytes").set(1.0)
        name = "retr" + "ies"
        counters.bump(name)  # dynamic: out of scope
        unrelated.bump("whatever")  # not a counters/telemetry call
    """)
    assert findings == []


def test_tpu007_suppression_comment():
    findings = lint_project_snippet(tpu007_metric_catalog, """
        from spark_rapids_ml_tpu.runtime import counters
        counters.bump("bogus_one")  # tpuml: ignore[TPU007]
        counters.bump("bogus_two")
    """)
    assert len(findings) == 1
    assert "bogus_two" in findings[0].message


def test_tpu007_slo_catalog_must_reference_declared_metrics(tmp_path):
    """An SLO over a nonexistent metric would silently never measure —
    the project pass rejects it (checked against a scratch repo whose
    slo.py references a bogus metric; the real catalog is covered by
    the clean whole-repo run)."""
    rt = tmp_path / "spark_rapids_ml_tpu" / "runtime"
    rt.mkdir(parents=True)
    real = os.path.join(REPO_ROOT, "spark_rapids_ml_tpu", "runtime")
    for name in ("envspec.py", "metricspec.py"):
        with open(os.path.join(real, name)) as fh:
            (rt / name).write_text(fh.read())
    (rt / "slo.py").write_text(textwrap.dedent("""
        from dataclasses import dataclass

        @dataclass(frozen=True)
        class SLOSpec:
            name: str
            metric: str

        CATALOG = (SLOSpec("phantom", "metric_nobody_declared"),)
    """))
    findings = list(tpu007_metric_catalog.check_project([], str(tmp_path)))
    assert len(findings) == 1
    assert findings[0].rule == "TPU007"
    assert "metric_nobody_declared" in findings[0].message
    assert findings[0].context == "slo:phantom"

    # a bare scratch repo with no slo.py at all lints clean
    (rt / "slo.py").unlink()
    assert list(tpu007_metric_catalog.check_project([], str(tmp_path))) == []


# --- TPU008: metric label cardinality ---------------------------------------


def test_tpu008_flags_splat_and_undeclared_labels():
    findings = lint_project_snippet(tpu008_label_cardinality, """
        from spark_rapids_ml_tpu.runtime import telemetry
        labels = {"request_id": rid}
        telemetry.counter("retries").inc(**labels)
        telemetry.counter("retries").inc(model="x")
        telemetry.gauge("hbm_live_bytes").set(1.0, shard=3)
        telemetry.histogram("serve_p99_ms").observe(2.0, user=u)
    """)
    assert len(findings) == 4
    assert all(f.rule == "TPU008" for f in findings)
    assert "splat" in findings[0].message
    assert "undeclared label 'model'" in findings[1].message
    assert "'site'" in findings[2].message  # names the declared set
    assert "undeclared label 'user'" in findings[3].message


def test_tpu008_allows_declared_labels_and_value_params():
    findings = lint_project_snippet(tpu008_label_cardinality, """
        from spark_rapids_ml_tpu.runtime import telemetry
        telemetry.counter("retries").inc()
        telemetry.counter("retries").inc(by=3)
        telemetry.counter("xla_compiles").inc(site="serve.batch")
        telemetry.gauge("hbm_live_bytes").set(1.0, site="gang_fit")
        telemetry.gauge("resumed_from").set(value=7)
        telemetry.histogram("serve_p99_ms").observe(2.0, model="pca")
        telemetry.histogram("span_seconds").observe(value=0.1, name="x")
        telemetry.counter("undeclared_name").inc(model="x")  # TPU007's job
        name = "ret" + "ries"
        telemetry.counter(name).inc(model="x")  # dynamic: out of scope
        m = telemetry.counter("retries")
        m.inc(model="x")  # not the chained form: out of scope
    """)
    assert findings == []


def test_tpu008_suppression_comment():
    findings = lint_project_snippet(tpu008_label_cardinality, """
        from spark_rapids_ml_tpu.runtime import telemetry
        telemetry.counter("retries").inc(model="a")  # tpuml: ignore[TPU008]
        telemetry.counter("retries").inc(model="b")
    """)
    assert len(findings) == 1
    assert "model" in findings[0].message


# --- TPU009: inline PartitionSpec outside parallel/ -------------------------


def test_tpu009_flags_inline_pspec_in_kernels():
    findings = lint_snippet(tpu009_inline_pspec, """
        import jax
        from jax.sharding import PartitionSpec as P

        a = P("dp")
        b = P(None, "mp")
        c = jax.sharding.PartitionSpec("dp", "mp")
    """, path="spark_rapids_ml_tpu/ops/some_kernels.py")
    assert len(findings) == 3
    assert all(f.rule == "TPU009" for f in findings)
    assert "LAYOUT" in findings[0].fixit


def test_tpu009_allows_parallel_package_and_out_of_scope_paths():
    code = """
        from jax.sharding import PartitionSpec

        s = PartitionSpec("dp")
    """
    for path in (
        "spark_rapids_ml_tpu/parallel/layout.py",
        "spark_rapids_ml_tpu/parallel/mesh.py",
        "tests/test_mesh2d.py",
        "benchmark_runner.py",
    ):
        assert lint_snippet(tpu009_inline_pspec, code, path=path) == []


def test_tpu009_ignores_layout_calls_and_unrelated_names():
    findings = lint_snippet(tpu009_inline_pspec, """
        from spark_rapids_ml_tpu.parallel.layout import LAYOUT

        a = LAYOUT.rows()
        b = LAYOUT.cols()

        def P(x):
            return x

        c = P("not a partition spec")
    """, path="spark_rapids_ml_tpu/ops/clean.py")
    assert findings == []


def test_tpu009_suppression_comment():
    findings = lint_snippet(tpu009_inline_pspec, """
        from jax.sharding import PartitionSpec as P

        a = P("dp")  # tpuml: ignore[TPU009]
        b = P("dp")
    """, path="spark_rapids_ml_tpu/ops/some_kernels.py")
    assert len(findings) == 1


# --- baseline + suppression mechanics --------------------------------------


def _finding(path="a.py", rule="TPU001", context="x = 1"):
    return Finding(rule=rule, path=path, line=3, col=1,
                   message="m", context=context)


def test_baseline_roundtrip_and_churn_tolerance(tmp_path):
    f = _finding()
    p = str(tmp_path / "baseline.json")
    write_baseline(p, [f])
    baseline = load_baseline(p)
    # same finding on a DIFFERENT line (code above it churned): absorbed
    moved = Finding(rule=f.rule, path=f.path, line=99, col=5,
                    message=f.message, context=f.context)
    new, stale = apply_baseline([moved], baseline)
    assert new == [] and stale == []
    # different context line: new finding + stale entry
    other = _finding(context="y = 2")
    new, stale = apply_baseline([other], baseline)
    assert len(new) == 1 and len(stale) == 1


def test_committed_baseline_is_empty():
    p = os.path.join(REPO_ROOT, "tpuml_lint", "baseline.json")
    with open(p) as fh:
        assert json.load(fh)["findings"] == []


# --- envspec parse semantics ------------------------------------------------


def test_envspec_parse_errors_name_variable_and_domain():
    from spark_rapids_ml_tpu.runtime import envspec

    with pytest.raises(envspec.EnvSpecError, match="TPUML_NUM_PROCS"):
        envspec.parse("TPUML_NUM_PROCS", "zero")
    with pytest.raises(envspec.EnvSpecError, match="must be >= 1"):
        envspec.parse("TPUML_NUM_PROCS", "0")
    with pytest.raises(envspec.EnvSpecError, match="auto|sort|partial"):
        envspec.parse("TPUML_KNN_TOPK", "bogus")
    with pytest.raises(envspec.EnvSpecError, match="boolean"):
        envspec.parse("TPUML_RF_CHECK_FINITE", "maybe")
    # EnvSpecError is a ValueError for pre-registry except clauses
    assert issubclass(envspec.EnvSpecError, ValueError)


def test_envspec_defaults_and_empty_means_unset():
    from spark_rapids_ml_tpu.runtime import envspec

    assert envspec.parse("TPUML_RETRIES", None) == 0
    assert envspec.parse("TPUML_RETRIES", "") == 0
    assert envspec.parse("TPUML_CV_FAILFAST", "off") is False
    assert envspec.parse("TPUML_UMAP_OPT", " Pallas ") == "pallas"
    assert envspec.get("TPUML_CKPT_EVERY", env={}) == 1
    assert envspec.get("TPUML_CKPT_EVERY", env={"TPUML_CKPT_EVERY": "7"}) == 7


def test_envspec_is_stdlib_only():
    """The by-file-path loaders (tpuml_lint, gen_config_docs) depend on
    envspec importing nothing beyond the stdlib."""
    path = os.path.join(
        REPO_ROOT, "spark_rapids_ml_tpu", "runtime", "envspec.py"
    )
    with open(path) as fh:
        tree = ast.parse(fh.read())
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            assert node.level == 0, "no relative imports in envspec.py"
            assert node.module.split(".")[0] in ("os", "dataclasses", "typing", "__future__")
        elif isinstance(node, ast.Import):
            for a in node.names:
                assert a.name.split(".")[0] in ("os", "dataclasses", "typing")


def test_every_registered_var_is_in_docs_table():
    from spark_rapids_ml_tpu.runtime import envspec

    with open(os.path.join(REPO_ROOT, "docs", "configuration.md")) as fh:
        doc = fh.read()
    for name in envspec.registered_names():
        assert name in doc, f"{name} missing from docs/configuration.md"


# --- integration ------------------------------------------------------------


def _run_lint(*args, cwd=REPO_ROOT):
    return subprocess.run(
        [sys.executable, "-m", "tpuml_lint", *args],
        cwd=cwd, capture_output=True, text=True,
    )


def test_repo_lints_clean():
    """The acceptance gate: the tree has zero non-baselined findings."""
    r = _run_lint("spark_rapids_ml_tpu", "tests", "benchmark_runner.py")
    assert r.returncode == 0, r.stdout + r.stderr


def test_lint_fails_on_each_rule(tmp_path):
    bad = {
        "TPU001": 'import os\nx = os.environ.get("TPUML_RETRIES")\n',
        "TPU003": (
            "import jax\n"
            "def f(cs):\n"
            "    for c in cs:\n"
            "        jax.jit(lambda x: x)(c)\n"
        ),
        "TPU004": "import numpy as np\nnp.random.seed(0)\n",
        "TPU005": (
            "import functools, jax\n"
            "@functools.partial(jax.jit, static_argnames=('typo',))\n"
            "def f(x):\n"
            "    return x\n"
        ),
        "TPU006": (
            "import jax.experimental.pallas as pl\n"
            "s = pl.BlockSpec((8, 100), lambda i: (i, 0))\n"
        ),
        "TPU007": (
            "from spark_rapids_ml_tpu.runtime import counters\n"
            'counters.bump("not_in_the_catalog")\n'
        ),
        "TPU008": (
            "from spark_rapids_ml_tpu.runtime import telemetry\n"
            'telemetry.counter("retries").inc(request_id="r1")\n'
        ),
        "TPU010": (
            "from spark_rapids_ml_tpu.runtime import lockwitness\n"
            'l = lockwitness.make_lock("not.in.the.catalog")\n'
        ),
        "TPU011": (
            "import time\n"
            "from spark_rapids_ml_tpu.runtime import lockwitness\n"
            '_L = lockwitness.make_lock("faults.cache")\n'
            "def f():\n"
            "    with _L:\n"
            "        time.sleep(1)\n"
        ),
        # TPU012 is scoped to spark_rapids_ml_tpu/ paths, so a tmp-file
        # fixture cannot trip it; tests/test_concurrency.py covers it
        # through the in-process harness with a scoped path.
    }
    for code, src in bad.items():
        p = tmp_path / f"{code.lower()}_fixture.py"
        p.write_text(src)
        r = _run_lint(str(p), "--no-baseline", "--rule", code)
        assert r.returncode == 1, f"{code} not detected:\n{r.stdout}"
        assert code in r.stdout


def test_gen_config_docs_check_mode():
    r = subprocess.run(
        [sys.executable, "scripts/gen_config_docs.py", "--check"],
        cwd=REPO_ROOT, capture_output=True, text=True,
    )
    assert r.returncode == 0, r.stdout + r.stderr


# a repo-relative script path: not part of a longer word, an absolute
# path, or a shell expansion
_SCRIPT_PATH_RE = re.compile(
    r"(?<![\w/.$}-])((?:\./)?(?:[\w-]+/)*[\w-]+\.(?:py|sh))\b"
)
_LINT_TARGETS_RE = re.compile(r"python -m (?:compileall|tpuml_lint)\b(.*)")


@pytest.mark.parametrize(
    "script",
    ["ci/test.sh", "run_benchmark.sh", "run_benchmark_multihost.sh"],
)
def test_shell_entry_points_name_files_that_exist(script):
    """Every repo-relative ``*.py`` / ``*.sh`` a shell entry point runs
    (heredoc bodies included), and every path it hands to ``compileall``
    or ``tpuml_lint``, is in the tree."""
    with open(os.path.join(REPO_ROOT, script)) as f:
        text = f.read().replace("\\\n", " ")
    named = set()
    for line in text.splitlines():
        if line.lstrip().startswith("#"):
            continue
        named.update(_SCRIPT_PATH_RE.findall(line))
        targets = _LINT_TARGETS_RE.search(line)
        if targets:
            named.update(
                a for a in targets.group(1).split()
                if not a.startswith("-") and not re.fullmatch(r"TPU\d+", a)
            )
    assert named, script
    missing = sorted(
        n for n in named if not os.path.exists(os.path.join(REPO_ROOT, n))
    )
    assert not missing, f"{script} names files that do not exist: {missing}"
