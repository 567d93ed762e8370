"""Tests for the static-analysis pass (src/repro/analysis + tools/dtmlint).

Three layers, mirroring the package:

* lint rules DTM001..DTM011 — one bad fixture (fires) and one good
  fixture (clean) per rule, plus suppression-comment syntax;
* kernel contract checker — the real registry is green, and the checker
  demonstrably catches overflow / out-of-bounds / coverage / divide
  faults on deliberately-broken synthetic plans;
* trace-contract audit — golden round-trip in a temp baseline, and the
  audit demonstrably FAILS when the committed golden diverges.
"""

import json
import shutil
import subprocess
import sys
import threading
from pathlib import Path

import pytest

from repro.analysis import lint
from repro.analysis.lint import RULES, lint_paths, lint_source

REPO = Path(__file__).resolve().parent.parent

KERNEL_PATH = "src/repro/kernels/somekernel.py"
LAUNCH_PATH = "src/repro/launch/somelaunch.py"
CORE_PATH = "src/repro/core/somecore.py"


def codes(src, relpath=CORE_PATH):
    return [f.code for f in lint_source(src, relpath)]


# --------------------------------------------------------------------------- #
# rule table                                                                  #
# --------------------------------------------------------------------------- #

def test_rule_table():
    assert len(RULES) >= 8, "ISSUE floor: at least 8 active rules"
    assert len({r.code for r in RULES}) == len(RULES)
    for r in RULES:
        assert r.code.startswith("DTM") and len(r.code) == 6
        assert r.rationale and r.scope, f"{r.code} missing metadata"


def test_tree_is_clean():
    """The acceptance bar: `tools/dtmlint src/` exits 0."""
    assert lint_paths([str(REPO / "src")]) == []


# --------------------------------------------------------------------------- #
# per-rule fixtures                                                           #
# --------------------------------------------------------------------------- #

def test_dtm001_unsized_dynamic_shape():
    assert codes("idx = jnp.nonzero(x)") == ["DTM001"]
    assert codes("idx = jnp.flatnonzero(mask)") == ["DTM001"]
    assert codes("idx = jnp.argwhere(mask)") == ["DTM001"]
    assert codes("idx = jnp.where(mask)") == ["DTM001"]
    # sized / three-arg forms are the sanctioned shapes
    assert codes("idx = jnp.nonzero(x, size=4, fill_value=0)") == []
    assert codes("y = jnp.where(mask, a, b)") == []
    assert codes("idx = jnp.where(mask, size=8)") == []
    assert codes("idx = np.nonzero(x)") == []       # host numpy is fine


def test_dtm002_env_read_outside_resolver():
    bad = "import os\nv = os.environ.get('REPRO_SKIP', '1')\n"
    assert "DTM002" in codes(bad, LAUNCH_PATH)
    assert "DTM002" in codes("import os\nv = os.getenv('X')\n", CORE_PATH)
    # the two designated resolver sites are exempt
    assert codes(bad, "src/repro/kernels/ops.py") == []
    assert codes(bad, "src/repro/kernels/autotune.py") == []


def test_dtm003_hot_path_sync():
    bad = "def f(x):\n    jax.block_until_ready(x)\n"
    assert codes(bad, LAUNCH_PATH) == ["DTM003"]
    # collect() is the sanctioned sync point; other packages unscoped
    assert codes("def collect(x):\n    jax.block_until_ready(x)\n",
                 LAUNCH_PATH) == []
    assert codes(bad, CORE_PATH) == []


def test_dtm004_python_branch_on_traced():
    bad = "def f(x):\n    if jnp.any(x > 0):\n        return 1\n"
    assert codes(bad, KERNEL_PATH) == ["DTM004"]
    assert codes("def f(x):\n    while lax.lt(x, 3):\n        pass\n",
                 "src/repro/core/dtm.py") == ["DTM004"]
    assert codes("def f(x):\n    if x.any():\n        return 1\n",
                 KERNEL_PATH) == ["DTM004"]
    # host values and host numpy stay branchable; other modules unscoped
    assert codes("def f(flag):\n    if flag:\n        return 1\n",
                 KERNEL_PATH) == []
    assert codes("def f(x):\n    if np.any(x):\n        return 1\n",
                 KERNEL_PATH) == []
    assert codes(bad, CORE_PATH) == []


def test_dtm005_untyped_int_literal_array():
    assert codes("z = jnp.asarray(0)", KERNEL_PATH) == ["DTM005"]
    assert codes("z = jnp.full((4,), 1)", KERNEL_PATH) == ["DTM005"]
    assert codes("z = jnp.asarray(0, dtype=jnp.uint8)", KERNEL_PATH) == []
    assert codes("z = jnp.asarray(x)", KERNEL_PATH) == []
    assert codes("z = jnp.asarray(0.5)", KERNEL_PATH) == []
    # only the packed-layout modules are scoped
    assert codes("z = jnp.asarray(0)", "src/repro/core/feedback.py") == []


def test_dtm006_writeable_lru_cached_array():
    bad = ("@functools.lru_cache()\n"
           "def table(n):\n"
           "    return np.arange(n)\n")
    assert codes(bad) == ["DTM006"]
    good = ("@functools.lru_cache()\n"
            "def table(n):\n"
            "    out = np.arange(n)\n"
            "    out.flags.writeable = False\n"
            "    return out\n")
    assert codes(good) == []
    # uncached array builders are unaffected
    assert codes("def table(n):\n    return np.arange(n)\n") == []


def test_dtm007_mutable_default_arg():
    assert codes("def f(x, acc=[]):\n    pass\n") == ["DTM007"]
    assert codes("def f(x, m={}):\n    pass\n") == ["DTM007"]
    assert codes("def f(x, *, s=set()):\n    pass\n") == ["DTM007"]
    assert codes("def f(x, acc=None):\n    pass\n") == []
    assert codes("def f(x, t=()):\n    pass\n") == []


def test_dtm008_interpret_literal_default():
    assert codes("def k(x, interpret=True):\n    pass\n",
                 KERNEL_PATH) == ["DTM008"]
    assert codes("def k(x, *, interpret=False):\n    pass\n",
                 KERNEL_PATH) == ["DTM008"]
    assert codes("def k(x, interpret=None):\n    pass\n", KERNEL_PATH) == []
    # only kernel entry points are scoped
    assert codes("def k(x, interpret=True):\n    pass\n", CORE_PATH) == []


def test_dtm009_bare_except():
    bad = "try:\n    f()\nexcept:\n    pass\n"
    assert codes(bad) == ["DTM009"]
    assert codes("try:\n    f()\nexcept ValueError:\n    pass\n") == []


def test_dtm010_unlocked_stats_read():
    path = "src/repro/launch/scheduler.py"
    bad = ("def stats(self):\n"
           "    return {'done': self.completed}\n")
    assert codes(bad, path) == ["DTM010"]
    good = ("def stats(self):\n"
            "    with self._work:\n"
            "        return {'done': self.completed}\n")
    assert codes(good, path) == []
    # only stats() in scheduler.py is scoped
    assert codes(bad, LAUNCH_PATH) == []
    assert codes("def other(self):\n    return self.completed\n",
                 path) == []


def test_dtm011_non_atomic_file_publish():
    path = "src/repro/checkpoint/somestore.py"
    # bare open(final, "w") + json.dump: a crash mid-dump leaves a torn
    # file at the path readers trust
    bad_open = ("import json, os\n"
                "def publish(final, obj):\n"
                "    with open(final, 'w') as f:\n"
                "        json.dump(obj, f)\n")
    assert codes(bad_open, path) == ["DTM011"]
    bad_np = ("import numpy as np, os\n"
              "def publish(final, arrs):\n"
              "    np.savez(final, **arrs)\n")
    assert codes(bad_np, path) == ["DTM011"]
    # the atomic discipline: write under a *tmp* path, then os.replace
    good = ("import json, os\n"
            "def publish(final, obj):\n"
            "    tmp = final + '.tmp'\n"
            "    with open(tmp, 'w') as f:\n"
            "        json.dump(obj, f)\n"
            "    os.replace(tmp, final)\n")
    assert codes(good, path) == []
    good_np = ("import numpy as np, os\n"
               "def publish(tmp_dir, arrs):\n"
               "    np.savez(os.path.join(tmp_dir, 'shard.npz'), **arrs)\n")
    assert codes(good_np, path) == []
    # reads are fine; runtime/ is in scope, launch/ is not
    assert codes("def read(final):\n    return open(final).read()\n",
                 path) == []
    assert codes(bad_open, "src/repro/runtime/somewriter.py") == ["DTM011"]
    assert codes(bad_open, LAUNCH_PATH) == []


# --------------------------------------------------------------------------- #
# suppression + CLI                                                           #
# --------------------------------------------------------------------------- #

def test_suppression_comment():
    assert codes("idx = jnp.nonzero(x)  # dtmlint: disable=DTM001") == []
    assert codes("idx = jnp.nonzero(x)  # dtmlint: disable=all") == []
    assert codes("idx = jnp.nonzero(x)  "
                 "# dtmlint: disable=DTM002,DTM001") == []
    # the wrong code does not suppress
    assert codes("idx = jnp.nonzero(x)  "
                 "# dtmlint: disable=DTM009") == ["DTM001"]
    # suppression is per-line, not per-file
    two = ("a = jnp.nonzero(x)  # dtmlint: disable=DTM001\n"
           "b = jnp.nonzero(y)\n")
    assert codes(two) == ["DTM001"]


def test_cli_src_green_and_bad_fixture_red(tmp_path):
    tool = REPO / "tools" / "dtmlint"
    r = subprocess.run([sys.executable, str(tool), str(REPO / "src")],
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr
    bad = tmp_path / "repro" / "kernels" / "bad.py"
    bad.parent.mkdir(parents=True)
    bad.write_text("def k(x, interpret=True):\n    return x\n")
    r = subprocess.run([sys.executable, str(tool), "lint", str(bad)],
                       capture_output=True, text=True)
    assert r.returncode == 1 and "DTM008" in r.stdout


def test_ruff_baseline_if_available():
    """Generic-hygiene split: ruff must pass where it is installed (CI
    lint job); locally we only check when the binary exists."""
    ruff = shutil.which("ruff")
    if ruff is None:
        pytest.skip("ruff not installed in this environment")
    r = subprocess.run([ruff, "check", "src", "tests"],
                       capture_output=True, text=True, cwd=REPO)
    assert r.returncode == 0, r.stdout + r.stderr


# --------------------------------------------------------------------------- #
# kernel contract checker                                                     #
# --------------------------------------------------------------------------- #

def test_kernel_registry_is_green():
    from repro.analysis import kernel_check
    n, violations = kernel_check.check_all()
    assert not violations, "\n".join(v.render() for v in violations)
    # the audit space covers every autotuner-emittable stage x tile x
    # shape x batch-bucket combination — three figures of plans
    assert n >= 100


def test_kernel_checker_catches_vmem_overflow():
    from repro.analysis import kernel_check
    plan = kernel_check.plan_clause_eval(1024, 1024, 512)
    bad = kernel_check.check_plan(plan, vmem_bytes=64 * 1024)
    assert any(v.kind == "vmem" for v in bad)
    # a deliberately-overflowing synthetic plan against the REAL budget:
    # a streamed-rand TA baseline at bench batch 1024 cannot launch
    huge = kernel_check.plan_ta_update_streamed(1024, 1024, 512)
    assert any(v.kind == "vmem" for v in kernel_check.check_plan(huge))


def test_kernel_checker_catches_broken_maps():
    from repro.analysis.kernel_check import (BlockUse, KernelPlan,
                                             check_plan)
    # off-by-one base: last grid step reads past the padded bounds
    oob = KernelPlan("synthetic", "oob", (4,), (
        BlockUse("x", (32,), (8,), lambda i: (i + 1,)),))
    assert [v.kind for v in check_plan(oob)] == ["oob"]
    # constant output map: only block 0 is ever written
    cov = KernelPlan("synthetic", "cov", (4,), (
        BlockUse("y", (32,), (8,), lambda i: (0,), is_output=True),))
    assert [v.kind for v in check_plan(cov)] == ["coverage"]
    # non-dividing block shape
    div = KernelPlan("synthetic", "div", (4,), (
        BlockUse("x", (30,), (8,), lambda i: (i,)),))
    assert any(v.kind == "divide" for v in check_plan(div))
    # non-affine map is rejected rather than trusted
    nonaff = KernelPlan("synthetic", "nonaff", (4,), (
        BlockUse("x", (32,), (8,), lambda i: (i * i % 4,)),))
    assert any("non-affine" in v.detail for v in check_plan(nonaff))


def test_kernel_checker_catches_untiled_block():
    """The (8, 8) block on a [32, 56] packed-literal array that the
    seed's ``wt=8`` tiles handed out: it divides its array, fits VMEM,
    and still cannot lower — Mosaic takes (8, 128)-aligned blocks or
    whole dims only."""
    from repro.analysis import kernel_check
    plan = kernel_check.plan_packed_clause(32, 1792, 2048, wt=8)
    bad = kernel_check.check_plan(plan)
    assert {v.kind for v in bad} == {"tiling"}
    assert any("plits" in v.detail and "block 8" in v.detail
               and "dim 56" in v.detail for v in bad)
    # a word tile at least as wide as the row becomes the whole row
    assert kernel_check.check_plan(
        kernel_check.plan_packed_clause(32, 1792, 2048, wt=128)) == []


# --------------------------------------------------------------------------- #
# trace-contract audit                                                        #
# --------------------------------------------------------------------------- #

def test_committed_golden_has_all_ci_legs():
    golden = json.loads((REPO / "ANALYSIS_baseline.json").read_text())
    legs = golden["legs"]
    forces = {k.split("|")[1] for k in legs}
    assert "force=auto" in forces and "force=packed_vpu" in forces
    assert any("skip=0" in k for k in legs)
    assert any("autotune=off" in k for k in legs)
    for entry in legs.values():
        assert set(entry) == {"session_paths", "serving_paths"}


def test_trace_audit_roundtrip_and_divergence(tmp_path):
    """One real audit run; then the golden round-trip both ways."""
    from repro.analysis.trace_audit import (AuditError, compare_to_golden,
                                            run_audit)
    baseline = tmp_path / "golden.json"
    report = run_audit(update=True, baseline=baseline)
    assert report.session_paths and report.serving_paths
    assert all(v <= 1 for v in report.session_caches.values())
    assert all(v <= 1 for v in report.serving_caches.values())
    # round-trip: the entry just written matches
    compare_to_golden(report, baseline)
    # tamper one dispatch entry -> the audit must FAIL, naming the stage
    golden = json.loads(baseline.read_text())
    entry = golden["legs"][report.leg]["session_paths"]
    stage = sorted(entry)[0]
    entry[stage] = "not-a-real-path"
    baseline.write_text(json.dumps(golden))
    with pytest.raises(AuditError, match="diverged"):
        compare_to_golden(report, baseline)
    # a missing leg is an error (never silently green)
    with pytest.raises(AuditError, match="no golden entry"):
        compare_to_golden(report, tmp_path / "empty.json")


# --------------------------------------------------------------------------- #
# scheduler thread-safety (the DTM010 incident, exercised live)               #
# --------------------------------------------------------------------------- #

def test_stats_consistent_under_concurrent_driver():
    """Hammer stats() from reader threads while the driver thread runs:
    every snapshot must be internally consistent (completed+failed never
    exceeds submitted) and nothing may raise."""
    import numpy as np

    from repro import api
    from repro.launch.scheduler import SchedulerConfig
    from repro.launch.serve_tm import demo_batch, demo_specs

    specs = demo_specs(small=True)
    name, spec = sorted(specs.items())[0]
    sched = api.serve({name: spec}, batch_slot=4,
                      config=SchedulerConfig(max_wait_s=0.0))
    errors = []
    stop = threading.Event()

    def reader():
        try:
            while not stop.is_set():
                s = sched.stats()
                if s["completed"] + s["rejected"] > s["submitted"]:
                    errors.append(f"inconsistent snapshot: {s}")
                    return
        except Exception as e:          # pragma: no cover - failure path
            errors.append(repr(e))

    readers = [threading.Thread(target=reader) for _ in range(3)]
    sched.start()
    try:
        for t in readers:
            t.start()
        futs = [sched.submit(name, demo_batch(spec, 4, seed=s))
                for s in range(8)]
        for f in futs:
            assert np.asarray(f.result(timeout=120)).shape[0] == 4
    finally:
        stop.set()
        for t in readers:
            t.join(timeout=30)
        sched.stop()
    assert not errors, errors
    final = sched.stats()
    assert final["submitted"] == 8 and final["completed"] == 8


def test_lint_module_exports():
    assert lint.__all__ == ["RULES", "Finding", "lint_source",
                            "lint_paths", "main"]
