"""repro-lint: per-pass good/bad fixture pairs + repo self-check.

Every pass is driven through ``Project.from_sources`` — the same code
path the CLI uses on the real tree — with a minimal bad fixture that
must fire and its minimally-fixed twin that must stay silent. The final
tests pin the shipped ``LINT_baseline.json`` to an actual fresh run, so
the committed baseline can never drift from what the tool reports.
"""
import json
from pathlib import Path

from repro.analysis import BASELINE_NAME, run_all
from repro.analysis.base import Project, load_baseline
from repro.analysis.determinism import DeterminismPass
from repro.analysis.metrics_schema import MetricsSchemaPass
from repro.analysis.parity import ParityPass
from repro.analysis.refusals import RefusalsPass
from repro.analysis.soa import SoaCoherencePass
from repro.analysis.syncdonate import SyncDonationPass

REPO_ROOT = Path(__file__).resolve().parent.parent


def _rules(pass_cls, sources, data=None):
    findings = pass_cls().run(Project.from_sources(sources, data))
    return sorted(f.rule for f in findings)


# ------------------------------------------------------------ determinism

def test_determinism_flags_wallclock_rng_and_set_iter():
    bad = """
import time, random
import numpy as np

def decide(jobs):
    t = time.time()
    r = np.random.rand()
    g = np.random.default_rng()
    u = random.random()
    for j in {1, 2, 3}:
        t += j
    order = list({4, 5})
    return t, r, g, u, order
"""
    rules = _rules(DeterminismPass, {"src/repro/sched/policy.py": bad})
    assert rules.count("wallclock") == 1
    assert rules.count("unseeded-rng") == 3
    assert rules.count("set-iter") == 2


def test_determinism_good_twin_is_silent():
    good = """
import time
import numpy as np

def decide(jobs, seed):
    t = time.time()  # lint: allow-wallclock(measured harness)
    g = np.random.default_rng(seed)
    for j in sorted({1, 2, 3}):
        t += j
    order = sorted({4, 5})
    return t, g.random(), order
"""
    assert _rules(DeterminismPass, {"src/repro/sched/policy.py": good}) == []


def test_determinism_ignores_out_of_scope_files():
    bad = "import time\nT = time.time()\n"
    assert _rules(DeterminismPass, {"src/repro/models/layers.py": bad}) == []


def test_determinism_covers_the_counters_module():
    bad = "import time\nT = time.perf_counter()\n"
    assert _rules(DeterminismPass, {"src/repro/obs.py": bad}) == \
        ["wallclock"]


# -------------------------------------------------------------------- soa

SOA_BAD = """
class Refresher:
    def sneak(self, view):
        object.__setattr__(view, "free_pages", 3)

    def evict(self, rid):
        self.decode_running.pop(rid)
"""

SOA_GOOD = """
class Refresher:
    def refresh(self, view, cols):
        object.__setattr__(view, "free_pages", 3)
        cols.dirty.add(view._row)

    def plumbing(self, view):
        object.__setattr__(view, "_row", 7)   # not a mirrored field

    def _decode_discard(self, rid):
        self.decode_running.pop(rid)

    def fail(self, rid):
        self.decode_running.clear()
        self._batch_version += 1
        self._cols.dirty = True
"""


def test_soa_flags_bypass_write_and_unversioned_mutation():
    rules = _rules(SoaCoherencePass, {"src/repro/serving/engine.py": SOA_BAD})
    assert rules == ["bypass-setattr", "decode-batch-version"]


def test_soa_good_twin_is_silent():
    assert _rules(SoaCoherencePass,
                  {"src/repro/serving/engine.py": SOA_GOOD}) == []


def test_soa_mirrored_fields_derived_from_viewcolumns():
    # a project that mirrors ONLY `speed` must not flag free_pages
    sources = {
        "src/repro/core/toggle.py": """
class ViewColumns:
    def _pull(self, views):
        for i, v in enumerate(views):
            self.speed[i] = v.speed
""",
        "src/repro/serving/engine.py": """
def poke(view):
    object.__setattr__(view, "free_pages", 3)

def tweak(view):
    object.__setattr__(view, "speed", 2.0)
""",
    }
    findings = SoaCoherencePass().run(Project.from_sources(sources))
    assert [f.scope for f in findings] == ["tweak"]


# ------------------------------------------------------------------- sync

SYNC_SCAFFOLD = """
import jax
import numpy as np

class Kernels:
    def prefill_fn(self, bucket, rows):
        fn = jax.jit(step, donate_argnums=1)
        return fn

    def warmup(self, params):
        cache = init()
        _, cache = self.prefill_fn(8, 1)(params, cache)
        jax.block_until_ready(cache)

class Executor:
    def _run_plan_fast(self, plan):
        {body}
"""


def _sync_rules(body):
    src = SYNC_SCAFFOLD.replace("{body}", body)
    return _rules(SyncDonationPass, {"src/repro/serving/executor.py": src})


def test_sync_budget_flags_loop_sync_and_extra_transfer():
    body = """for part in plan:
            jax.block_until_ready(part)
            n = int(part.tokens.item())
        host = np.asarray(plan.out)"""
    rules = _sync_rules(body)
    assert rules.count("sync-budget") == 2       # block in loop, host x2


def test_sync_budget_good_twin_is_silent():
    body = """jax.block_until_ready(plan.cache)
        host = np.asarray(plan.a) if plan.one else np.asarray(plan.b)"""
    assert _sync_rules(body) == []


def test_sync_missing_fast_path_scope_is_reported():
    src = "def unrelated():\n    pass\n"
    rules = _rules(SyncDonationPass, {"src/repro/serving/executor.py": src})
    assert rules == ["missing-fast-path", "missing-fast-path"]


def test_use_after_donate_flags_read_of_dead_buffer():
    body = """toks = self.kernels.prefill_fn(8, 4)(self.params, self.cache)
        return self.cache"""
    rules = _sync_rules(body)
    assert "use-after-donate" in rules


def test_use_after_donate_rebind_idiom_is_silent():
    body = """toks, self.cache = self.kernels.prefill_fn(8, 4)(
            self.params, self.cache)
        jax.block_until_ready(self.cache)
        host = np.asarray(toks)"""
    assert _sync_rules(body) == []


# ----------------------------------------------------------------- parity

def test_parity_flags_missing_scalar_ref_and_missing_test():
    sources = {
        "src/repro/core/dispatch.py": """
def choose(xs):
    return min(xs)

def choose_vec(xs):
    return xs.min()

def orphan_vec(xs):
    return xs
""",
        "tests/test_dispatch.py": "from repro.core.dispatch import choose_vec\n",
    }
    findings = ParityPass().run(Project.from_sources(sources))
    by_rule = {f.rule: f.scope for f in findings}
    assert by_rule == {"no-scalar-ref": "orphan_vec",
                       "no-parity-test": "orphan_vec"}


def test_parity_transitive_caller_coverage_and_pragmas():
    sources = {
        "src/repro/core/dispatch.py": """
def handle(xs):
    return inner_vec(xs)

def inner_vec(xs):  # lint: parity-ref(choose)
    return xs.min()

def choose(xs):
    return min(xs)

def helper_batch(xs):  # lint: not-parity(shape utility, no scalar twin)
    return xs
""",
        "tests/test_dispatch.py": "import handle  # drives the vec path\n",
    }
    assert ParityPass().run(Project.from_sources(sources)) == []


def test_parity_ref_to_nonexistent_def_is_flagged():
    sources = {"src/repro/core/dispatch.py": """
def lost_vec(xs):  # lint: parity-ref(ghost)
    return xs
""",
               "tests/test_dispatch.py": "lost_vec\n"}
    rules = _rules(ParityPass, sources)
    assert rules == ["parity-ref-missing"]


# ---------------------------------------------------------------- metrics

CHECKER_FIXTURE = '''
EXACT_KEYS = {"schema_version", "n_requests"}
'''


def test_metrics_flags_info_key_and_unclassified_emit():
    sources = {
        "benchmarks/check_summary.py": CHECKER_FIXTURE,
        "benchmarks/run.py": """
summary = {"schema_version": 5}
summary["weird_blob"] = 17
summary["ttft_p90_s"] = 0.5
""",
    }
    data = {"BENCH_summary.json": json.dumps(
        {"schema_version": 5, "weird_blob": 17, "ttft_p90_s": 0.5})}
    findings = MetricsSchemaPass().run(Project.from_sources(sources, data))
    rules = sorted(f.rule for f in findings)
    assert rules == ["unclassified-emit", "unclassified-key"]
    assert all(f.scope == "weird_blob" for f in findings)


def test_metrics_emitted_key_missing_from_snapshot():
    sources = {
        "benchmarks/check_summary.py": CHECKER_FIXTURE,
        "benchmarks/run.py": 'summary = {}\nsummary["new_thing_s"] = 1.0\n',
    }
    data = {"BENCH_summary.json": json.dumps({"schema_version": 5})}
    rules = _rules(MetricsSchemaPass, sources, data)
    assert rules == ["emitted-not-in-snapshot"]


def test_metrics_allow_key_pragma_and_update_kwargs():
    sources = {
        "benchmarks/check_summary.py": CHECKER_FIXTURE,
        "benchmarks/run.py": """
summary = {}
summary["blob"] = 17  # lint: allow-key(blob: debug payload, not gated)
summary.update(tpot_p90_s=0.1)
""",
    }
    data = {"BENCH_summary.json": json.dumps(
        {"schema_version": 5, "blob": 17, "tpot_p90_s": 0.1})}
    assert _rules(MetricsSchemaPass, sources, data) == []


# --------------------------------------------------------------- refusals

def test_refusals_flags_short_context_and_bare_raises():
    bad = """
def admit(self, wid, rid):
    if self.full:
        raise SlotExhausted(wid)
    if rid < 0:
        raise ValueError()
"""
    rules = _rules(RefusalsPass, {"src/repro/sched/backend.py": bad})
    assert rules == ["bare-raise", "refusal-context"]


def test_refusals_good_twin_is_silent():
    good = """
def admit(self, wid, rid, limit):
    if self.full:
        raise SlotExhausted(wid, rid, limit)
    if rid < 0:
        raise ValueError(f"rid {rid} negative (wid={wid})")
    try:
        pass
    except KeyError:
        raise
"""
    assert _rules(RefusalsPass, {"src/repro/sched/backend.py": good}) == []


# ----------------------------------------------------------------- pragmas

def test_unknown_and_reasonless_pragmas_are_findings():
    src = """
X = 1  # lint: allow-wallclok(typo'd name)
Y = 2  # lint: allow-wallclock()
"""
    project = Project.from_sources({"src/repro/core/x.py": src})
    rules = sorted(f.rule for f in project.pragma_findings())
    assert rules == ["pragma-reason", "unknown-pragma"]


# --------------------------------------------------------- repo self-check

def test_repo_is_clean_and_baseline_matches_fresh_run():
    """The shipped baseline must equal a fresh run EXACTLY — and the goal
    state is an empty baseline (violations get fixed, not baselined)."""
    project = Project.from_dir(REPO_ROOT)
    findings = run_all(project)
    findings.extend(project.pragma_findings())
    fresh = sorted(f.fingerprint for f in findings)
    shipped = sorted(load_baseline(REPO_ROOT / BASELINE_NAME))
    assert fresh == shipped, (
        "LINT_baseline.json is stale vs a fresh run; regenerate with "
        "`PYTHONPATH=src python -m repro.analysis --write-baseline` "
        "(after fixing, not baselining, new findings)")
    assert shipped == [], "baseline must stay empty: fix findings instead"


def test_cli_check_exits_clean_at_head():
    from repro.analysis.__main__ import main
    assert main(["--check", "--root", str(REPO_ROOT)]) == 0


def test_cli_exit_contract_on_bad_input(tmp_path):
    from repro.analysis.__main__ import main
    assert main(["--root", str(tmp_path / "missing")]) == 2   # no such dir
    (tmp_path / "src").mkdir()
    assert main(["--root", str(tmp_path)]) == 2               # no sources
    # malformed baseline -> exit 2
    repo = tmp_path / "repo"
    (repo / "src" / "repro").mkdir(parents=True)
    (repo / "src" / "repro" / "ok.py").write_text("X = 1\n")
    (repo / BASELINE_NAME).write_text('{"wrong": true}')
    assert main(["--check", "--root", str(repo)]) == 2


def test_cli_check_fails_on_new_finding_and_stale_entry(tmp_path, capsys):
    from repro.analysis.__main__ import main
    repo = tmp_path / "repo"
    (repo / "src" / "repro" / "sched").mkdir(parents=True)
    bad = repo / "src" / "repro" / "sched" / "p.py"
    bad.write_text("import time\nT = time.time()\n")
    # no baseline: the finding is NEW -> exit 1
    assert main(["--check", "--root", str(repo)]) == 1
    assert "NEW" in capsys.readouterr().out
    # accept it, then fix it: the baseline entry goes STALE -> exit 1
    assert main(["--write-baseline", "--root", str(repo)]) == 0
    assert main(["--check", "--root", str(repo)]) == 0
    bad.write_text("T = 0\n")
    assert main(["--check", "--root", str(repo)]) == 1
    assert "STALE" in capsys.readouterr().out
