"""repro.analysis.staticcheck — the stdlib-only lint gate.

Deliberately imports NO jax (directly or transitively): the CI staticcheck
job runs this file on a bare python + pytest install.  Coverage contract:

- every registered RL### rule has at least one negative fixture below that
  makes it fire (enforced by ``test_every_rule_has_a_negative_fixture``);
- the real repo tree is clean (``lint_tree`` returns no findings) — the
  same check ``python -m repro.analysis.lint`` gates CI on;
- the two tree rules (salt uniqueness, wire-registry completeness) are
  exercised against tmp_path mini-repos with planted violations.
"""
import pathlib
import sys

import pytest

from repro.analysis.staticcheck import (
    RULES,
    Finding,
    lint_source,
    lint_tree,
)
from repro.analysis.staticcheck.contracts import (
    _ROUNDS_FILE,
    _SALTS_FILE,
    _WIRE_DOC,
    _WIRE_FILE,
)

REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]


def test_no_jax_imported():
    """The whole point of the package: importing the linter (and the CLI
    module) must not drag in jax — the CI staticcheck job has no jax
    installed.  Checked in a subprocess with the import poisoned, so it
    holds even when the surrounding pytest run has long since imported
    jax for other test files."""
    import subprocess

    code = (
        "import sys\n"
        "sys.modules['jax'] = None\n"   # any `import jax` now raises
        "import repro.analysis.lint as m\n"
        "from repro.analysis.staticcheck import lint_source\n"
        "assert callable(m.main)\n"
        "assert lint_source('x = 1\\n', 'src/repro/x.py') == []\n"
        "print('NOJAX_OK')\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True,
        env={"PYTHONPATH": str(REPO_ROOT / "src"), "PATH": "/usr/bin:/bin"},
    )
    assert out.returncode == 0, out.stderr
    assert "NOJAX_OK" in out.stdout


def rules_of(findings):
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# negative fixtures: one snippet per file-scope rule
# ---------------------------------------------------------------------------

# rule id -> (rel_path the snippet pretends to live at, source)
FILE_RULE_FIXTURES = {
    "RL001": ("src/repro/x.py", "def f(:\n    pass\n"),
    "RL002": ("src/repro/x.py", "break\n"),
    "RL003": ("src/repro/x.py", "y = undefined_name_xyz + 1\n"),
    "RL004": ("src/repro/x.py", "flag = (x is 'a')\nx = 1\n"),
    "RL005": ("src/repro/x.py", "assert (1 == 1, 'msg')\n"),
    "RL010": ("src/repro/x.py",
              "import numpy as np\nv = np.random.rand(3)\n"),
    "RL011": ("src/repro/x.py",
              "import time, jax\nk = jax.random.key(int(time.time()))\n"),
    "RL021": ("src/repro/core/x.py",
              "from jax import shard_map\n"),
}


@pytest.mark.parametrize("rule_id", sorted(FILE_RULE_FIXTURES))
def test_file_rule_fires_on_fixture(rule_id):
    rel, src = FILE_RULE_FIXTURES[rule_id]
    findings = lint_source(src, rel)
    assert rule_id in rules_of(findings), findings


def test_every_rule_has_a_negative_fixture():
    """The fixture tables must cover the whole registry — adding a rule
    without a fixture is itself a failure."""
    tree_rules = {"RL020", "RL022"}  # exercised via tmp_path repos below
    assert set(FILE_RULE_FIXTURES) | tree_rules == set(RULES)


def test_findings_format_and_order():
    f = Finding("src/a.py", 3, "RL004", "msg")
    assert str(f) == "src/a.py:3: RL004 msg"
    findings = lint_source("assert (1, 'm')\nz = (q is 'a')\nq = 1\n",
                           "src/repro/x.py")
    assert findings == sorted(findings)
    assert rules_of(findings) == {"RL004", "RL005"}


# ---------------------------------------------------------------------------
# clean cases: the rules must NOT fire on the idioms the repo relies on
# ---------------------------------------------------------------------------

def test_seeded_rng_and_bare_time_are_clean():
    src = (
        "import time\n"
        "import numpy as np\n"
        "rng = np.random.default_rng(0)\n"
        "t0 = time.time()\n"        # timing is fine — only seed sinks flag
    )
    assert lint_source(src, "src/repro/x.py") == []


def test_path_scoping_of_contract_rules():
    """src/-only rules stay quiet for tests/ (ad-hoc RNG is fine there),
    and the kernel-primitive confinement allowlist covers kernels/ and
    distributed/."""
    rng = "import numpy as np\nv = np.random.rand(3)\n"
    assert "RL010" in rules_of(lint_source(rng, "src/repro/x.py"))
    assert lint_source(rng, "tests/test_x.py") == []

    pallas = "from jax.experimental import pallas as pl\n"
    assert "RL021" in rules_of(lint_source(pallas, "src/repro/core/x.py"))
    assert lint_source(pallas, "src/repro/kernels/x.py") == []
    assert lint_source(pallas, "src/repro/distributed/x.py") == []


def test_star_import_disables_undefined_names():
    assert lint_source("from os.path import *\nq = join('a')\n",
                       "src/repro/x.py") == []


# ---------------------------------------------------------------------------
# tree rules against tmp_path mini-repos
# ---------------------------------------------------------------------------

def _mini_repo(tmp_path, salts_src, rounds_src):
    (tmp_path / _SALTS_FILE).parent.mkdir(parents=True, exist_ok=True)
    (tmp_path / _SALTS_FILE).write_text(salts_src)
    (tmp_path / _ROUNDS_FILE).parent.mkdir(parents=True, exist_ok=True)
    (tmp_path / _ROUNDS_FILE).write_text(rounds_src)
    return tmp_path


GOOD_SALTS = '_WIRE_SALTS = {"naive": 1, "dcd": 2}\n'
GOOD_ROUNDS = (
    "def _naive_round(wire, X, t):\n"
    "    return wire.encode_tree(X, t, salt=1)\n"
    "def _dcd_round(wire, X, t):\n"
    "    return wire.encode_tree(X, t, salt=2)\n"
)


def test_rl020_clean_mini_repo(tmp_path):
    root = _mini_repo(tmp_path, GOOD_SALTS, GOOD_ROUNDS)
    findings = [f for f in lint_tree(root) if f.rule == "RL020"]
    assert findings == []


def test_rl020_salt_collision_in_table(tmp_path):
    root = _mini_repo(tmp_path,
                      '_WIRE_SALTS = {"naive": 1, "dcd": 1}\n', GOOD_ROUNDS)
    msgs = [f.message for f in lint_tree(root) if f.rule == "RL020"]
    assert any("collision" in m for m in msgs), msgs


def test_rl020_runtime_mismatch(tmp_path):
    bad_rounds = GOOD_ROUNDS.replace("salt=2", "salt=9")
    root = _mini_repo(tmp_path, GOOD_SALTS, bad_rounds)
    msgs = [f.message for f in lint_tree(root) if f.rule == "RL020"]
    assert any("diverge" in m for m in msgs), msgs


def test_rl020_runtime_collision(tmp_path):
    bad_rounds = GOOD_ROUNDS.replace("salt=2", "salt=1")
    root = _mini_repo(tmp_path, GOOD_SALTS, bad_rounds)
    msgs = [f.message for f in lint_tree(root) if f.rule == "RL020"]
    assert any("collision" in m for m in msgs), msgs


def test_rl020_missing_contract_file(tmp_path):
    msgs = [f.message for f in lint_tree(tmp_path) if f.rule == "RL020"]
    assert any("missing" in m for m in msgs), msgs


WIRE_OK = (
    "class QuantWire: pass\n"
    "def register_wire_format(name, ctor, positional=()): pass\n"
    'register_wire_format("quant", QuantWire)\n'
    "def wire_spec(w):\n"
    "    if isinstance(w, QuantWire):\n"
    '        return "quant"\n'
)


def _wire_repo(tmp_path, wire_src, doc_text="the `quant:<bits>` format\n"):
    (tmp_path / _WIRE_FILE).parent.mkdir(parents=True, exist_ok=True)
    (tmp_path / _WIRE_FILE).write_text(wire_src)
    (tmp_path / _WIRE_DOC).parent.mkdir(parents=True, exist_ok=True)
    (tmp_path / _WIRE_DOC).write_text(doc_text)
    return tmp_path


def test_rl022_clean_mini_repo(tmp_path):
    root = _wire_repo(tmp_path, WIRE_OK)
    assert [f for f in lint_tree(root) if f.rule == "RL022"] == []


def test_rl022_missing_wire_spec_branch(tmp_path):
    no_branch = WIRE_OK.replace("isinstance(w, QuantWire)", "False")
    msgs = [f.message for f in lint_tree(_wire_repo(tmp_path, no_branch))
            if f.rule == "RL022"]
    assert any("round-trip" in m for m in msgs), msgs


def test_rl022_missing_doc_anchor(tmp_path):
    root = _wire_repo(tmp_path, WIRE_OK, doc_text="nothing relevant\n")
    msgs = [f.message for f in lint_tree(root) if f.rule == "RL022"]
    assert any("anchor" in m for m in msgs), msgs


# ---------------------------------------------------------------------------
# the real tree is clean — the same gate the CLI/CI enforces
# ---------------------------------------------------------------------------

def test_repo_tree_is_clean():
    findings = lint_tree(REPO_ROOT)
    assert findings == [], "\n".join(str(f) for f in findings)


def test_cli_clean_tree_exits_zero():
    """`python -m repro.analysis.lint` (no --jaxpr) is the gate CI runs on
    the no-jax job: exit 0 + a summary line on the clean tree."""
    import subprocess

    out = subprocess.run(
        [sys.executable, "-m", "repro.analysis.lint",
         "--root", str(REPO_ROOT)],
        capture_output=True, text=True,
        env={"PYTHONPATH": str(REPO_ROOT / "src"), "PATH": "/usr/bin:/bin"},
    )
    assert out.returncode == 0, out.stdout + out.stderr
    assert "staticcheck: 0 finding(s)" in out.stdout
