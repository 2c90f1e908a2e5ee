"""graftlint (gtopkssgd_tpu.analysis) — rule fixtures + the tree gate.

Layout per rule: a positive fixture (the rule fires), a negative one
(it stays quiet), plus suppression and baseline behavior on shared
fixtures. The final tests are the enforcement gate: the shipped tree
must lint clean against the committed repo baseline, and each rule must
return nonzero through the real CLI on its positive fixture.

No jax import anywhere in this file — the analyzer's contract is that
linting never initializes a backend, and this suite would catch an
accidental jax dependency by simply becoming slow/backend-bound.
"""

import json
import os
import textwrap

from gtopkssgd_tpu.analysis import engine
from gtopkssgd_tpu.analysis.__main__ import main as lint_main
from gtopkssgd_tpu.analysis.rules import ALL_RULES, RULES_BY_NAME

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tree(tmp_path, files: dict) -> str:
    for rel, text in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(text))
    return str(tmp_path)


def _run(root, rule, files=None, baseline=None):
    return engine.run(
        [os.path.join(root, f) for f in files] if files else [root],
        rules=ALL_RULES, rule_names={rule}, baseline=baseline, root=root)


def _rules_of(result):
    return [(f.rule, f.line) for f in result.findings]


# ------------------------------------------------------------ host-sync


HOST_SYNC_POS = """\
    import jax
    import jax.numpy as jnp

    @jax.jit
    def step(x):
        y = jnp.sum(x * x)
        return float(y)
"""

HOST_SYNC_NEG = """\
    import jax
    import jax.numpy as jnp

    @jax.jit
    def step(x, density):
        k = int(x.shape[0])        # static metadata: no sync
        d = float(density)          # parameter, not a jnp product
        return jnp.sum(x) * d, k

    def host_loop(x):
        return float(x)             # not jit-reachable at all
"""


def test_host_sync_positive(tmp_path):
    root = _tree(tmp_path, {"mod.py": HOST_SYNC_POS})
    res = _run(root, "host-sync-in-jit")
    assert [f.rule for f in res.findings] == ["host-sync-in-jit"]
    assert "float" in res.findings[0].message
    assert res.findings[0].symbol == "step"


def test_host_sync_item_and_device_get(tmp_path):
    root = _tree(tmp_path, {"mod.py": """\
        import jax

        @jax.jit
        def step(x):
            a = x.item()
            b = jax.device_get(x)
            return a, b
    """})
    res = _run(root, "host-sync-in-jit")
    msgs = sorted(f.message for f in res.findings)
    assert len(msgs) == 2
    assert any(".item()" in m for m in msgs)
    assert any("device_get" in m for m in msgs)


def test_host_sync_negative(tmp_path):
    root = _tree(tmp_path, {"mod.py": HOST_SYNC_NEG})
    res = _run(root, "host-sync-in-jit")
    assert res.findings == []


def test_host_sync_wrapper_call_site_entry(tmp_path):
    # jax.jit(f) / shard_map(step, ...) entries, not just decorators.
    root = _tree(tmp_path, {"mod.py": """\
        import jax
        import jax.numpy as jnp

        def build():
            def step(x):
                return float(jnp.sum(x))
            return jax.jit(step)
    """})
    res = _run(root, "host-sync-in-jit")
    assert [f.symbol for f in res.findings] == ["build.step"]


def test_host_sync_suppressed(tmp_path):
    root = _tree(tmp_path, {"mod.py": """\
        import jax
        import jax.numpy as jnp

        @jax.jit
        def step(x):
            y = jnp.sum(x)
            # graftlint: disable=host-sync-in-jit
            return float(y)
    """})
    res = _run(root, "host-sync-in-jit")
    assert res.findings == [] and len(res.suppressed) == 1


def test_host_sync_baselined(tmp_path):
    root = _tree(tmp_path, {"mod.py": HOST_SYNC_POS})
    raw = _run(root, "host-sync-in-jit")
    baseline = {f.baseline_key: {"reason": "fixture"}
                for f in raw.findings}
    res = _run(root, "host-sync-in-jit", baseline=baseline)
    assert res.findings == [] and len(res.baselined) == 1
    assert res.stale_baseline == []


def test_baseline_key_survives_line_drift(tmp_path):
    root = _tree(tmp_path, {"mod.py": HOST_SYNC_POS})
    key = _run(root, "host-sync-in-jit").findings[0].baseline_key
    shifted = _tree(tmp_path / "v2",
                    {"mod.py": "# a new header comment\n"
                               + textwrap.dedent(HOST_SYNC_POS)})
    res = _run(shifted, "host-sync-in-jit",
               baseline={key: {"reason": "fixture"}})
    assert res.findings == [] and len(res.baselined) == 1


# ----------------------------------------------------------- metric-kind


METRICS_FIXTURE = """\
    KINDS = frozenset({"train", "event"})
"""


def test_metric_kind_unregistered_literal(tmp_path):
    # Regression for the deleted grep test
    # (test_every_logged_kind_literal_is_registered): a typo'd literal
    # kind at a .log( call site must be caught statically.
    root = _tree(tmp_path, {
        "pkg/utils/metrics.py": METRICS_FIXTURE,
        "pkg/mod.py": """\
            def f(m):
                m.log("tpyo_kind", step=1)
        """})
    res = _run(root, "metric-kind")
    assert [f.rule for f in res.findings] == ["metric-kind"]
    assert "tpyo_kind" in res.findings[0].message


def test_metric_kind_negative_literal_and_bound_name(tmp_path):
    root = _tree(tmp_path, {
        "pkg/utils/metrics.py": METRICS_FIXTURE,
        "pkg/mod.py": """\
            KIND = "event"

            def f(m):
                m.log("train", step=1)
                m.log(KIND, step=2)
        """})
    assert _run(root, "metric-kind").findings == []


def test_metric_kind_fstring_is_a_finding(tmp_path):
    root = _tree(tmp_path, {
        "pkg/utils/metrics.py": METRICS_FIXTURE,
        "pkg/mod.py": """\
            def f(m, i):
                m.log(f"train_{i}", step=1)
        """})
    res = _run(root, "metric-kind")
    assert len(res.findings) == 1
    assert "f-string" in res.findings[0].message


def test_metric_kind_ignores_numeric_and_logger_log(tmp_path):
    root = _tree(tmp_path, {
        "pkg/utils/metrics.py": METRICS_FIXTURE,
        "pkg/mod.py": """\
            import numpy as np
            import math

            def f(logger, x):
                np.log(x)
                math.log(x)
                logger.log(30, "a stdlib-logging message")
        """})
    assert _run(root, "metric-kind").findings == []


# ------------------------------------------------------------- exit-code


EXIT_FIXTURE = """\
    EXIT_OK = 0
    EXIT_WEDGED = 7
"""


def test_exit_code_unregistered_literal(tmp_path):
    root = _tree(tmp_path, {
        "pkg/exit_codes.py": EXIT_FIXTURE,
        "pkg/mod.py": """\
            import sys

            def f():
                sys.exit(8)
        """})
    res = _run(root, "exit-code")
    assert [f.rule for f in res.findings] == ["exit-code"]
    assert "8" in res.findings[0].message


def test_exit_code_registered_literals_pass(tmp_path):
    root = _tree(tmp_path, {
        "pkg/exit_codes.py": EXIT_FIXTURE,
        "pkg/mod.py": """\
            import os
            import sys

            def f(bad):
                if bad:
                    raise SystemExit(7)
                os._exit(0)
                sys.exit("a message is rc 1, not a literal code")
        """})
    assert _run(root, "exit-code").findings == []


def test_exit_code_collision_and_minted_constant(tmp_path):
    root = _tree(tmp_path, {
        "pkg/exit_codes.py": EXIT_FIXTURE + "    EXIT_CLASH = 7\n",
        "pkg/mod.py": "WEDGE_EXIT_CODE = 9\n"})
    res = _run(root, "exit-code")
    msgs = sorted(f.message for f in res.findings)
    assert len(msgs) == 2
    assert any("collision" in m for m in msgs)
    assert any("WEDGE_EXIT_CODE" in m for m in msgs)


# ------------------------------------------------------------ codec-wire


def test_codec_wire_raw_sparse_gather(tmp_path):
    root = _tree(tmp_path, {"pkg/parallel/coll.py": """\
        from jax import lax

        def bad(vals, idx, axis_name):
            av = lax.all_gather(vals, axis_name, tiled=True)
            ai = lax.all_gather(idx, axis_name, tiled=True)
            return av, ai
    """})
    res = _run(root, "codec-wire")
    assert [f.rule for f in res.findings] == ["codec-wire"] * 2
    assert all(f.symbol == "bad" for f in res.findings)


def test_codec_wire_encoded_and_dense_pass(tmp_path):
    root = _tree(tmp_path, {"pkg/parallel/coll.py": """\
        from jax import lax

        def good(vals, idx, axis_name, codec, n):
            wire = codec.encode(vals, idx, n=n)
            pwire = tuple(lax.ppermute(w, axis_name, [(0, 1)])
                          for w in wire)
            return codec.decode(pwire, k=2, n=n)

        def dense_ok(x, axis_name):
            return lax.psum(x, axis_name)
    """})
    assert _run(root, "codec-wire").findings == []


def test_codec_wire_all_to_all_and_nonleading_payload(tmp_path):
    # The balanced-schedule extension: all_to_all is a wire collective
    # too, and a sparse payload in ANY positional slot (not just the
    # leading one) must be codec-mediated.
    root = _tree(tmp_path, {"pkg/parallel/coll.py": """\
        from jax import lax

        def bad_a2a(vals, axis_name):
            return lax.all_to_all(vals, axis_name, 0, 0)

        def bad_tail(mask, vals, axis_name):
            return lax.ppermute(mask * vals, axis_name, [(0, 1)])

        def good_a2a(vals, idx, axis_name, codec, n):
            wire = codec.encode(vals, idx, n=n)
            swire = tuple(lax.all_to_all(w, axis_name, 0, 0)
                          for w in wire)
            return codec.decode(swire, k=2, n=n)
    """})
    res = _run(root, "codec-wire")
    assert sorted(f.symbol for f in res.findings) == [
        "bad_a2a", "bad_tail"]


def test_codec_wire_scoped_to_parallel(tmp_path):
    root = _tree(tmp_path, {"pkg/other.py": """\
        from jax import lax

        def elsewhere(vals, axis_name):
            return lax.all_gather(vals, axis_name, tiled=True)
    """})
    assert _run(root, "codec-wire").findings == []


# ---------------------------------------------------------- durable-event


def test_durable_event_requires_flush(tmp_path):
    root = _tree(tmp_path, {
        "pkg/utils/metrics.py": METRICS_FIXTURE,
        "pkg/mod.py": """\
            def f(m, extra):
                m.log("event", what="anomaly")
                m.log("event", flush=extra)
        """})
    res = _run(root, "durable-event")
    assert [f.rule for f in res.findings] == ["durable-event"] * 2


def test_durable_event_flush_true_passes(tmp_path):
    root = _tree(tmp_path, {
        "pkg/utils/metrics.py": METRICS_FIXTURE,
        "pkg/mod.py": """\
            def f(m):
                m.log("event", flush=True, what="anomaly")
                m.log("train", step=1)  # non-durable: flush optional
        """})
    assert _run(root, "durable-event").findings == []


# ------------------------------------------------------------ event-rule


EVENTS_FIXTURE = """\
    RULES = frozenset({"nan_loss", "goodput_collapse"})
"""


def test_event_rule_unregistered_names(tmp_path):
    # Both static emit-site shapes: the "rule" key of a record dict and
    # the first argument of a local fire(...) helper.
    root = _tree(tmp_path, {
        "pkg/obs/events.py": EVENTS_FIXTURE,
        "pkg/mod.py": """\
            def f(fire):
                ev = {"rule": "tpyo_rule", "severity": "warn"}
                fire("also_unregistered", step=1)
                return ev
        """})
    res = _run(root, "event-rule")
    assert [f.rule for f in res.findings] == ["event-rule"] * 2
    assert "tpyo_rule" in res.findings[0].message
    assert "also_unregistered" in res.findings[1].message


def test_event_rule_registered_and_dynamic_pass(tmp_path):
    root = _tree(tmp_path, {
        "pkg/obs/events.py": EVENTS_FIXTURE,
        "pkg/mod.py": """\
            def f(fire, name):
                ev = {"rule": "goodput_collapse", "severity": "warn"}
                fire("nan_loss", step=1)
                fire(name, step=2)           # dynamic: runtime _emit's job
                other = {"rule": name}       # non-constant value: ignored
                return ev, other
        """})
    assert _run(root, "event-rule").findings == []


# ------------------------------------------------------- syntax handling


def test_unparseable_file_is_its_own_finding(tmp_path):
    root = _tree(tmp_path, {"pkg/broken.py": "def f(:\n"})
    res = engine.run([root], rules=ALL_RULES, root=root)
    assert [f.rule for f in res.findings] == ["syntax"]


# ------------------------------------------------------------- the gate


def _positive_fixture_for(rule_name):
    return {
        "host-sync-in-jit": {"mod.py": HOST_SYNC_POS},
        "metric-kind": {
            "pkg/utils/metrics.py": METRICS_FIXTURE,
            "pkg/mod.py": 'def f(m):\n    m.log("nope", step=1)\n'},
        "exit-code": {
            "pkg/exit_codes.py": EXIT_FIXTURE,
            "pkg/mod.py": "import sys\nsys.exit(8)\n"},
        "codec-wire": {
            "pkg/parallel/coll.py":
                "from jax import lax\n\n"
                "def bad(vals, axis_name):\n"
                "    return lax.all_gather(vals, axis_name)\n"},
        "durable-event": {
            "pkg/utils/metrics.py": METRICS_FIXTURE,
            "pkg/mod.py": 'def f(m):\n    m.log("event", what="x")\n'},
        "event-rule": {
            "pkg/obs/events.py": EVENTS_FIXTURE,
            "pkg/mod.py":
                'def f(fire):\n    fire("nope_rule", step=1)\n'},
    }[rule_name]


def test_cli_nonzero_on_every_rule_fixture(tmp_path):
    for i, rule in enumerate(RULES_BY_NAME):
        root = _tree(tmp_path / f"fix{i}", _positive_fixture_for(rule))
        rc = lint_main([root, "--no-baseline", "--rule", rule])
        assert rc == 1, f"rule {rule} did not fire through the CLI"


def test_cli_rejects_unknown_rule_and_path(tmp_path):
    assert lint_main([str(tmp_path), "--rule", "no-such-rule"]) == 2
    assert lint_main([str(tmp_path / "missing")]) == 2


def test_shipped_tree_lints_clean():
    """The tier-1 enforcement gate: any non-baselined finding in the
    shipped package or benchmarks fails this test. Fix the finding,
    suppress it with a justification comment, or (last resort)
    grandfather it into graftlint_baseline.json with a reason."""
    rc = lint_main([
        os.path.join(REPO, "gtopkssgd_tpu"),
        os.path.join(REPO, "benchmarks"),
        "--baseline", os.path.join(REPO, "graftlint_baseline.json")])
    assert rc == 0, (
        "graftlint found non-baselined findings — run "
        "`python -m gtopkssgd_tpu.analysis gtopkssgd_tpu/ benchmarks/` "
        "for the report")


def test_committed_baseline_entries_have_reasons():
    baseline = engine.load_baseline(
        os.path.join(REPO, "graftlint_baseline.json"))
    for key, entry in baseline.items():
        reason = entry.get("reason", "")
        assert reason and "TODO" not in reason, (
            f"baseline entry {key} lacks a real justification")


def test_analysis_package_never_imports_jax():
    """Contract: linting must work with no accelerator attached and
    must not pay backend init. Import the analyzer in a clean
    subprocess and assert jax was never pulled in."""
    import subprocess
    import sys

    code = (
        "import sys\n"
        "import gtopkssgd_tpu.analysis.rules\n"
        "import gtopkssgd_tpu.analysis.__main__\n"
        "assert 'jax' not in sys.modules, 'analysis imported jax'\n"
        "print('ok')\n")
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO,
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert "ok" in proc.stdout


def test_lint_gate_record_shape(tmp_path):
    """The gate-smoke lint record (benchmarks/obs_gate_smoke.py)
    carries the counts the committed obs gate baseline pins."""
    import importlib
    import sys

    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    try:
        smoke = importlib.import_module("obs_gate_smoke")
        rec = smoke.run_lint_smoke()
    finally:
        sys.path.pop(0)
    assert rec["non_baselined"] == 0
    assert rec["files_scanned"] > 50
    assert set(rec) == {"files_scanned", "non_baselined", "baselined",
                        "suppressed", "stale_baseline"}

    baseline = json.load(open(os.path.join(
        REPO, "benchmarks", "results", "obs_gate_baseline_cpu.json")))
    lint_checks = [c for c in baseline["checks"]
                   if c.get("kind") == "lint"]
    assert lint_checks == [{"kind": "lint", "field": "non_baselined",
                            "stat": "last", "expect": 0.0, "atol": 0.0}]
