"""graftlint suite (tools/graftlint.py, docs/analysis.md).

Each rule fires on a synthetic module and stays quiet on the clean
variant; waivers suppress with the documented syntax; traced-scope
inference follows decorators, jit call sites, known traced hooks, the
module-local call graph, and nesting; and the repo itself lints to the
committed zero-findings baseline.
"""

import importlib.util
import json
import os
import sys

import pytest

_TOOLS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools")


def _load_graftlint():
    spec = importlib.util.spec_from_file_location(
        "graftlint", os.path.join(_TOOLS, "graftlint.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


gl = _load_graftlint()


def _lint_source(source: str, in_package: bool = True,
                 path: str = "geomx_tpu/fake_module.py"):
    linter = gl.ModuleLinter(path, source, in_package=in_package)
    return linter.run()


def _rules(findings):
    return [f.rule for f in findings]


# --------------------------------------------------------------------------
# rule firing
# --------------------------------------------------------------------------

def test_wall_clock_in_jitted_function_fires_gxl001():
    findings = _lint_source(
        "import time\n"
        "import jax\n"
        "@jax.jit\n"
        "def step(x):\n"
        "    t0 = time.time()\n"
        "    return x + t0\n")
    assert _rules(findings) == ["GXL001"]
    assert "step" in findings[0].message


def test_wall_clock_aliased_spellings_fire_gxl001():
    """`from time import time` and `import time as t` must be caught
    through the import-alias map, same as GXL002's RNG resolution."""
    from_import = _lint_source(
        "from time import time\n"
        "import jax\n"
        "@jax.jit\n"
        "def step(x):\n"
        "    return x + time()\n")
    assert _rules(from_import) == ["GXL001"]
    aliased = _lint_source(
        "import time as t\n"
        "import jax\n"
        "@jax.jit\n"
        "def step(x):\n"
        "    return x + t.perf_counter()\n")
    assert _rules(aliased) == ["GXL001"]
    # a local callable that happens to be named `time` is not the clock
    clean = _lint_source(
        "import jax\n"
        "def time():\n"
        "    return 0.0\n"
        "@jax.jit\n"
        "def step(x):\n"
        "    return x + time()\n")
    assert clean == []


def test_np_random_in_traced_scope_fires_gxl002_but_jax_random_clean():
    findings = _lint_source(
        "import jax\n"
        "import numpy as np\n"
        "from jax import random\n"
        "@jax.jit\n"
        "def step(x, key):\n"
        "    noise = np.random.randn(4)\n"         # host RNG: fires
        "    good = random.normal(key, (4,))\n"    # jax RNG: clean
        "    return x + noise + good\n")
    assert _rules(findings) == ["GXL002"]


def test_env_read_in_traced_scope_fires_gxl003_and_gxl006():
    findings = _lint_source(
        "import os\n"
        "import jax\n"
        "@jax.jit\n"
        "def step(x):\n"
        "    if os.environ.get('GEOMX_FAST'):\n"
        "        return x * 2\n"
        "    return x\n")
    assert sorted(_rules(findings)) == ["GXL003", "GXL006"]


def test_registry_mutation_in_traced_scope_fires_gxl004():
    findings = _lint_source(
        "import jax\n"
        "from geomx_tpu.telemetry import get_registry\n"
        "@jax.jit\n"
        "def step(x):\n"
        "    get_registry().counter('steps').inc()\n"
        "    return x\n")
    assert "GXL004" in _rules(findings)
    # .at[...].set(...) is jnp functional update, NOT a registry call
    clean = _lint_source(
        "import jax\n"
        "@jax.jit\n"
        "def step(x):\n"
        "    return x.at[0].set(1.0)\n")
    assert clean == []


def test_mutable_default_in_public_api_fires_gxl005():
    findings = _lint_source(
        "def make_loader(x, opts={}):\n"
        "    return x, opts\n")
    assert _rules(findings) == ["GXL005"]
    # private helpers, non-package files, and None defaults are exempt
    assert _lint_source("def _helper(x, opts={}):\n    return x\n") == []
    assert _lint_source("def make_loader(x, opts={}):\n    return x\n",
                        in_package=False, path="tools/fake.py") == []
    assert _lint_source(
        "def make_loader(x, opts=None):\n    return x\n") == []


def test_env_read_outside_config_fires_gxl006_package_only():
    src = "import os\nPORT = os.environ.get('GEOMX_PORT', '1')\n"
    assert _rules(_lint_source(src)) == ["GXL006"]
    # config.py itself is the sanctioned reader
    assert _lint_source(src, path="geomx_tpu/config.py") == []
    # outside the package the rule doesn't apply
    assert _lint_source(src, in_package=False, path="chip_smoke.py") == []


# --------------------------------------------------------------------------
# traced-scope inference
# --------------------------------------------------------------------------

def test_function_passed_to_jit_is_traced():
    findings = _lint_source(
        "import time\n"
        "import jax\n"
        "def body(x):\n"
        "    return x + time.time()\n"
        "step = jax.jit(body)\n")
    assert _rules(findings) == ["GXL001"]


def test_known_traced_method_and_self_call_graph():
    findings = _lint_source(
        "import time\n"
        "class MyCompressor:\n"
        "    def _boundary(self, g):\n"
        "        return g * time.time()\n"       # reached from compress
        "    def compress(self, g, u, v):\n"
        "        return self._boundary(g)\n")
    assert _rules(findings) == ["GXL001"]
    assert "_boundary" in findings[0].message


def test_nested_function_inherits_traced_scope():
    findings = _lint_source(
        "import time\n"
        "import jax\n"
        "@jax.jit\n"
        "def outer(x):\n"
        "    def inner(v):\n"
        "        return v * time.time()\n"
        "    return inner(x)\n")
    assert _rules(findings) == ["GXL001"]


def test_untraced_host_function_is_clean():
    findings = _lint_source(
        "import time\n"
        "def log_step(it):\n"
        "    return {'t': time.time(), 'it': it}\n")
    assert findings == []


# --------------------------------------------------------------------------
# waivers + baseline
# --------------------------------------------------------------------------

def test_waiver_suppresses_on_line_and_line_above():
    # the marker is assembled from halves so THIS file's literals don't
    # register as waivers when the repo itself is linted
    def waiver(rules):
        return "# graftlint: " + "dis" + f"able={rules}"

    base = ("import os\n"
            "A = os.environ.get('GEOMX_A')  "
            f"{waiver('GXL006')} — reason\n")
    assert _lint_source(base) == []
    above = ("import os\n"
             f"{waiver('GXL006')} — reason\n"
             "A = os.environ.get('GEOMX_A')\n")
    assert _lint_source(above) == []
    wrong_rule = ("import os\n"
                  "A = os.environ.get('GEOMX_A')  "
                  f"{waiver('GXL001')}\n")
    assert _rules(_lint_source(wrong_rule)) == ["GXL006"]
    disable_all = ("import os\n"
                   "A = os.environ.get('GEOMX_A')  "
                   f"{waiver('all')}\n")
    assert _lint_source(disable_all) == []


def test_pickle_on_service_path_fires_gx_wire_001():
    src = ("import pickle\n"
           "def encode(h):\n"
           "    return pickle.dumps(h)\n"
           "def decode(b):\n"
           "    return pickle.loads(b)\n"
           "class U(pickle.Unpickler):\n"
           "    pass\n")
    hits = _rules(_lint_source(src, path="geomx_tpu/service/fake.py"))
    assert hits == ["GX-WIRE-001"] * 3
    # the `from pickle import loads` spelling resolves through aliases
    aliased = ("from pickle import loads as _l\n"
               "def decode(b):\n"
               "    return _l(b)\n")
    assert _rules(_lint_source(
        aliased, path="geomx_tpu/service/fake.py")) == ["GX-WIRE-001"]
    # same source outside geomx_tpu/service/ is not the wire hot path
    assert _lint_source(src, path="geomx_tpu/utils/fake.py") == []
    assert _lint_source(src, path="tools/fake.py", in_package=False) == []
    # the hyphenated rule id waives with the documented syntax
    waiver = "# graftlint: " + "dis" + "able=GX-WIRE-001 — legacy codec"
    waived = ("import pickle\n"
              "def encode(h):\n"
              f"    return pickle.dumps(h)  {waiver}\n")
    assert _lint_source(waived, path="geomx_tpu/service/fake.py") == []


def test_repo_lints_clean_against_committed_baseline():
    findings, waivers = gl.lint_paths(gl.DEFAULT_ROOTS)
    assert findings == [], [f.format() for f in findings]
    with open(gl.BASELINE_PATH) as f:
        base = json.load(f)
    assert base["findings"] == 0
    assert waivers == base["waivers"], (
        f"waiver count drifted from the committed baseline "
        f"({waivers} vs {base['waivers']}): refresh via "
        "`python tools/graftlint.py --write-baseline` and justify the "
        "new waivers in review")


def test_cli_json_and_baseline_gate(tmp_path, capsys, monkeypatch):
    rc = gl.main(["--json"])
    out = capsys.readouterr().out
    rec = json.loads(out.strip().splitlines()[-1])
    assert rec["mode"] == "graftlint" and rec["findings"] == 0
    assert rc == 0 or rec["findings"] == 0
    assert gl.main(["--check-baseline"]) == 0
    capsys.readouterr()
    # a drifted baseline fails the gate loudly
    bad = tmp_path / "baseline.json"
    bad.write_text(json.dumps({"findings": 3, "waivers": 0,
                               "rules": {"GXL001": 3}}))
    monkeypatch.setattr(gl, "BASELINE_PATH", str(bad))
    assert gl.main(["--check-baseline"]) == 1
    assert "BASELINE MISMATCH" in capsys.readouterr().out


@pytest.mark.parametrize("rule", ["GXL001", "GXL002", "GXL003",
                                  "GXL004", "GXL005", "GXL006",
                                  "GX-WIRE-001"])
def test_rule_catalog_documented(rule):
    """Every rule id the linter can emit is documented in its module
    docstring AND in docs/analysis.md."""
    assert rule in (gl.__doc__ or "")
    docs = os.path.join(os.path.dirname(_TOOLS), "docs", "analysis.md")
    with open(docs) as f:
        assert rule in f.read()
