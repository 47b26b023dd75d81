"""The unified project-invariant linter (tools/lint) — r15 correctness
tooling plane.

Two halves:
  * mutation tests — every rule is proven to CATCH a seeded violation in a
    minimal fixture tree (a rule that cannot fail is not a rule), plus a
    clean-fixture control where the subtlety warrants it;
  * the committed tree is green — `run_rules(REPO) == []` is the tier-1
    form of the static gate (tools/check.sh runs the same rules from the
    CLI for benches/CI).

The ad-hoc drift guards these rules absorbed keep their original coverage:
tests/test_autotune.py (pins stay bench artifacts) and
tests/test_telemetry.py (counter-table drift) now call the framework — the
seeded-violation proofs for those contracts live HERE.
"""

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from tools.lint import RepoContext, all_rules, get_rule, run_rules  # noqa: E402


def _write(root, rel, content):
    path = os.path.join(root, rel)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(textwrap.dedent(content))


def _rule_hits(rule_name, root):
    return [v for v in get_rule(rule_name).check(RepoContext(str(root)))
            if v.rule == rule_name]


# --------------------------------------------------------------- framework
def test_all_rules_registered_and_described():
    rules = all_rules()
    names = {r.name for r in rules}
    assert {"counter-namespace-drift", "scaling-model-isolation",
            "schema-version-stamping", "kill-switch-completeness",
            "config-field-docs", "telemetry-import-isolation"} <= names
    for r in rules:
        assert r.description, r.name


def test_committed_tree_is_green():
    """The static gate itself: every invariant holds on this checkout."""
    violations = run_rules(REPO)
    assert violations == [], "\n".join(str(v) for v in violations)


def test_cli_green_and_lists_rules():
    out = subprocess.run([sys.executable, "-m", "tools.lint"], cwd=REPO,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert "lint: OK" in out.stdout
    listed = subprocess.run([sys.executable, "-m", "tools.lint", "--list"],
                            cwd=REPO, capture_output=True, text=True,
                            timeout=120)
    assert "counter-namespace-drift" in listed.stdout


# ------------------------------------------------- counter-namespace-drift
_README_TABLE = """\
    # fixture

    ### Counter namespace

    | namespace | source | names |
    |---|---|---|
    | `foo/` | somewhere | `a`, `stale_entry` |

    ### Next section
"""

# the r22 help-registry half of the contract: fixtures carry a matching
# NAMESPACE_HELP table so the original drift cases stay isolated
_HELP_MODULE_SRC = """\
    NAMESPACE_HELP = {
        "foo": "Fixture counters.",
    }
"""


def test_counter_rule_catches_undocumented_and_stale(tmp_path):
    _write(tmp_path, "README.md", _README_TABLE)
    _write(tmp_path, "distributed_vgg_f_tpu/telemetry/metric_help.py",
           _HELP_MODULE_SRC)
    _write(tmp_path, "distributed_vgg_f_tpu/mod.py", """\
        inc("foo/a")
        inc("foo/undocumented_counter")
        inc("nowhere/b")
    """)
    hits = _rule_hits("counter-namespace-drift", tmp_path)
    messages = " | ".join(v.message for v in hits)
    assert "foo/undocumented_counter" in messages     # registered, no row
    assert "nowhere" in messages                      # namespace w/o row
    assert "foo/stale_entry" in messages              # documented, dead
    assert len(hits) == 3


def test_counter_rule_clean_fixture(tmp_path):
    _write(tmp_path, "README.md", _README_TABLE.replace(
        ", `stale_entry`", ""))
    _write(tmp_path, "distributed_vgg_f_tpu/telemetry/metric_help.py",
           _HELP_MODULE_SRC)
    _write(tmp_path, "distributed_vgg_f_tpu/mod.py", 'inc("foo/a")\n')
    assert _rule_hits("counter-namespace-drift", tmp_path) == []


def test_counter_rule_catches_help_table_drift_both_ways(tmp_path):
    """r22: the NAMESPACE_HELP registry must cover EXACTLY the README
    counter-table namespaces — a seeded gap is caught in each direction,
    plus the missing/empty-module degenerate cases."""
    readme = _README_TABLE.replace(
        "| `foo/` | somewhere | `a`, `stale_entry` |",
        "| `foo/` | somewhere | `a` |\n"
        "    | `bar/` | somewhere | `b` |")
    code = 'inc("foo/a")\ninc("bar/b")\n'
    # direction 1: README namespace with no help entry
    _write(tmp_path, "README.md", readme)
    _write(tmp_path, "distributed_vgg_f_tpu/mod.py", code)
    _write(tmp_path, "distributed_vgg_f_tpu/telemetry/metric_help.py",
           _HELP_MODULE_SRC)
    messages = " | ".join(
        v.message for v in _rule_hits("counter-namespace-drift", tmp_path))
    assert "'bar' has no NAMESPACE_HELP entry" in messages
    # direction 2: help entry for a namespace nothing documents
    _write(tmp_path, "distributed_vgg_f_tpu/telemetry/metric_help.py",
           _HELP_MODULE_SRC.replace(
               '"foo": "Fixture counters.",',
               '"foo": "Fixture counters.",\n'
               '    "bar": "Fixture counters.",\n'
               '    "ghost": "Nothing documents me.",'))
    messages = " | ".join(
        v.message for v in _rule_hits("counter-namespace-drift", tmp_path))
    assert "stale help entry" in messages and "ghost" in messages
    # degenerate: empty table, then missing module — each is one loud hit
    _write(tmp_path, "distributed_vgg_f_tpu/telemetry/metric_help.py",
           "NAMESPACE_HELP = {}\n")
    messages = " | ".join(
        v.message for v in _rule_hits("counter-namespace-drift", tmp_path))
    assert "not found/empty" in messages
    os.remove(os.path.join(
        tmp_path, "distributed_vgg_f_tpu/telemetry/metric_help.py"))
    messages = " | ".join(
        v.message for v in _rule_hits("counter-namespace-drift", tmp_path))
    assert "metric_help.py missing" in messages


# ------------------------------------------------- scaling-model-isolation
def test_scaling_isolation_catches_runtime_pin_read(tmp_path):
    _write(tmp_path, "distributed_vgg_f_tpu/data/bad.py", """\
        from distributed_vgg_f_tpu.utils.scaling_model import (
            HOST_DECODE_RATE_R9)
        RATE = HOST_DECODE_RATE_R9
    """)
    hits = _rule_hits("scaling-model-isolation", tmp_path)
    assert len(hits) == 2  # names the pin AND imports the model
    assert all(v.path.endswith("data/bad.py") for v in hits)


def test_scaling_isolation_allows_prose_citations(tmp_path):
    _write(tmp_path, "distributed_vgg_f_tpu/data/ok.py", '''\
        """Retires HOST_DECODE_RATE_R* as a runtime input; the
        scaling_model keeps them as bench artifacts."""
        X = 1
    ''')
    assert _rule_hits("scaling-model-isolation", tmp_path) == []


# ------------------------------------------------- schema-version-stamping
def test_schema_rule_catches_literal_stamp(tmp_path):
    _write(tmp_path, "distributed_vgg_f_tpu/utils/logging.py", """\
        from distributed_vgg_f_tpu.telemetry.schema import SCHEMA_VERSION
        def rec():
            return {"event": "x", "schema_version": SCHEMA_VERSION}
    """)
    _write(tmp_path, "distributed_vgg_f_tpu/telemetry/flight.py", """\
        from distributed_vgg_f_tpu.telemetry import schema
        def box():
            return {"schema_version": schema.SCHEMA_VERSION}
    """)
    _write(tmp_path, "distributed_vgg_f_tpu/telemetry/regress.py", """\
        def art():
            return {"schema_version": "9.0"}
    """)
    hits = _rule_hits("schema-version-stamping", tmp_path)
    # regress.py: literal stamp AND therefore no constant-sourced stamp
    assert any("'9.0'" in v.message for v in hits)
    assert any(v.path.endswith("regress.py")
               and "no longer stamps" in v.message for v in hits)
    assert not any(v.path.endswith("logging.py") for v in hits)
    assert not any(v.path.endswith("flight.py") for v in hits)


# ----------------------------------------------- kill-switch-completeness
_COMPLETE_SWITCH = """\
    #if !defined(DVGGF_NO_WIDGET)
    #define DVGG_WIDGET 1
    #else
    #define DVGG_WIDGET 0
    #endif
    int active_widget_kind() {
      const char* env = std::getenv("DVGGF_DECODE_WIDGET");
      return (env && env[0] == '0') ? 0 : DVGG_WIDGET;
    }
    extern "C" {
    int dvgg_x_set_widget(int enable) { return enable; }
    }
"""


def test_kill_switch_rule_accepts_complete_triple(tmp_path):
    _write(tmp_path, "native/x.cc", _COMPLETE_SWITCH)
    assert _rule_hits("kill-switch-completeness", tmp_path) == []


def test_kill_switch_rule_catches_missing_parts(tmp_path):
    # env kill with neither compile-out nor setter
    _write(tmp_path, "native/x.cc", """\
        int active_widget_kind() {
          const char* env = std::getenv("DVGGF_DECODE_WIDGET");
          return (env && env[0] == '0') ? 0 : 1;
        }
    """)
    hits = _rule_hits("kill-switch-completeness", tmp_path)
    assert any("-DDVGGF_NO_WIDGET" in v.message for v in hits)
    assert any("set_widget" in v.message for v in hits)
    # compile-out with no env kill (the vice-versa direction)
    _write(tmp_path, "native/x.cc", """\
        #if !defined(DVGGF_NO_GADGET)
        #define DVGG_GADGET 1
        #endif
        extern "C" {
        int dvgg_x_set_gadget(int enable) { return enable; }
        }
    """)
    hits = _rule_hits("kill-switch-completeness", tmp_path)
    assert any("no matching env kill-switch" in v.message for v in hits)


def test_kill_switch_rule_ignores_tuning_knobs(tmp_path):
    # DVGGF_RESTART_FANOUT-style atoi knob: an env default, not a kill
    _write(tmp_path, "native/x.cc", """\
        int active_fanout() {
          const char* env = std::getenv("DVGGF_WIDGET_FANOUT");
          return env ? std::atoi(env) : 1;
        }
    """)
    assert _rule_hits("kill-switch-completeness", tmp_path) == []

def test_kill_switch_rule_covers_config_plane_switches(tmp_path):
    """r18/r19: every declared config-plane switch
    (`rules.CONFIG_KILL_SWITCHES`: data.iterator_state.enabled,
    mesh.elastic.enabled, mesh.shard_params, serving.tiers.enabled) needs a
    boolean config field AND a tier-1 test naming the dotted switch — each
    absence is its own violation; a complete set is clean."""
    cc = _COMPLETE_SWITCH
    good_cfg = """\
        from dataclasses import dataclass

        @dataclass(frozen=True)
        class IteratorStateConfig:
            enabled: bool = True

        @dataclass(frozen=True)
        class ElasticConfig:
            enabled: bool = False

        @dataclass(frozen=True)
        class MeshConfig:
            shard_params: bool = False

        @dataclass(frozen=True)
        class ServingTiersConfig:
            enabled: bool = False
    """
    good_test = ('SWITCH = "data.iterator_state.enabled"\n'
                 'ELASTIC = "mesh.elastic.enabled"\n'
                 'ZERO3 = "mesh.shard_params"\n'
                 'TIERS = "serving.tiers.enabled"\n')
    # the fixture names every switch the rule declares: a new one fails
    # here by name, not as a violation count on a "clean" tree
    from tools.lint.rules import CONFIG_KILL_SWITCHES
    assert all(f"class {cls}" in good_cfg and dotted in good_test
               for dotted, cls, _ in CONFIG_KILL_SWITCHES)
    _write(tmp_path, "native/x.cc", cc)
    _write(tmp_path, "distributed_vgg_f_tpu/config.py", good_cfg)
    _write(tmp_path, "tests/test_x.py", good_test)
    assert _rule_hits("kill-switch-completeness", tmp_path) == []
    # missing boolean field
    _write(tmp_path, "distributed_vgg_f_tpu/config.py", """\
        from dataclasses import dataclass

        @dataclass(frozen=True)
        class IteratorStateConfig:
            other: int = 1

        @dataclass(frozen=True)
        class ElasticConfig:
            enabled: bool = False
    """)
    hits = _rule_hits("kill-switch-completeness", tmp_path)
    assert any("no boolean field IteratorStateConfig.enabled" in v.message
               for v in hits)
    # field back, but no test names the dotted switch
    _write(tmp_path, "distributed_vgg_f_tpu/config.py", good_cfg)
    _write(tmp_path, "tests/test_x.py", "pass\n")
    hits = _rule_hits("kill-switch-completeness", tmp_path)
    assert any("named by no tier-1 test" in v.message for v in hits)



# -------------------------------------------------------- config-field-docs
def test_config_docs_rule_catches_undocumented_field(tmp_path):
    _write(tmp_path, "distributed_vgg_f_tpu/config.py", """\
        from dataclasses import dataclass

        @dataclass(frozen=True)
        class FooConfig:
            documented: int = 1  # what this knob does
            undocumented_knob: int = 2
    """)
    hits = _rule_hits("config-field-docs", tmp_path)
    assert len(hits) == 1
    assert "FooConfig.undocumented_knob" in hits[0].message


def test_config_docs_rule_accepts_docstring_mention(tmp_path):
    _write(tmp_path, "distributed_vgg_f_tpu/config.py", '''\
        from dataclasses import dataclass

        @dataclass(frozen=True)
        class FooConfig:
            """The knob `threshold` gates the thing."""
            threshold: float = 0.5
    ''')
    assert _rule_hits("config-field-docs", tmp_path) == []


# ----------------------------------------------- telemetry-import-isolation
def test_telemetry_isolation_catches_module_level_heavy_import(tmp_path):
    _write(tmp_path, "distributed_vgg_f_tpu/telemetry/bad.py", """\
        import numpy as np
        try:
            from distributed_vgg_f_tpu.data import native_jpeg
        except ImportError:
            native_jpeg = None
    """)
    hits = _rule_hits("telemetry-import-isolation", tmp_path)
    assert any("numpy" in v.message for v in hits)
    assert any("native-build trigger" in v.message for v in hits)
    assert len(hits) == 2


def test_telemetry_isolation_allows_lazy_imports(tmp_path):
    _write(tmp_path, "distributed_vgg_f_tpu/telemetry/ok.py", """\
        import json

        def snapshot():
            import numpy as np  # lazy: only when a consumer calls in
            return np.zeros(1)
    """)
    assert _rule_hits("telemetry-import-isolation", tmp_path) == []


# -------------------------------------------------------------- CLI plumbing
def test_cli_reports_seeded_violation(tmp_path):
    """End-to-end: the CLI exits 1 and names the rule on a dirty tree."""
    _write(tmp_path, "distributed_vgg_f_tpu/telemetry/bad.py",
           "import numpy\n")
    out = subprocess.run(
        [sys.executable, "-m", "tools.lint", "--repo", str(tmp_path),
         "--rule", "telemetry-import-isolation"],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert out.returncode == 1
    assert "telemetry-import-isolation" in out.stderr


def test_unknown_rule_fails_loudly():
    with pytest.raises(KeyError):
        get_rule("no-such-rule")
