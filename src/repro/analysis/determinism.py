"""Pass 1 — determinism: the sim/decision path must stay bit-reproducible.

Tropical's headline claims (decision parity sim-vs-real, the CI perf
gate's attainment numbers) assume a deterministic simulation: the same
seed must produce the same decision stream on every machine, forever.
Three hazard classes are forbidden in the decision path (``sched/``,
``serving/``, ``core/``, ``workload/``, and ``obs.py``, whose counters
the decision path writes) and in ``benchmarks/`` /
``examples/`` (whose published numbers must replay exactly):

* ``wallclock``     — ``time.time``/``perf_counter``/``monotonic``/
  ``process_time``, ``datetime.now``/``utcnow``. Measured-clock scopes
  (the real executor, benchmark timing harnesses) carry an explicit
  ``# lint: allow-wallclock(reason)``.
* ``unseeded-rng``  — module-level ``np.random.*`` calls (global-state
  RNG), ``default_rng()`` / ``RandomState()`` with no seed, and stdlib
  ``random.*`` module calls. Seeded generators are the only sanctioned
  source of randomness.
* ``set-iter``      — iterating a set (or feeding one to an
  order-sensitive consumer: ``list``/``tuple``/``enumerate``/``sum``/
  ``iter``) leaks hash-seed ordering into results. Order-insensitive
  consumers (``sorted``, ``len``, ``min``, ``max``, ``any``, ``all``,
  membership) are fine and not flagged.
"""
from __future__ import annotations

import ast

from repro.analysis.base import Finding, Project, SourceFile, dotted_name

PASS_ID = "determinism"

SCOPE = ("src/repro/sched/", "src/repro/serving/", "src/repro/core/",
         "src/repro/workload/", "src/repro/obs.py", "benchmarks/",
         "examples/")

WALLCLOCK_CALLS = frozenset({
    "time.time", "time.time_ns", "time.monotonic", "time.monotonic_ns",
    "time.perf_counter", "time.perf_counter_ns", "time.process_time",
    "datetime.now", "datetime.utcnow", "datetime.datetime.now",
    "datetime.datetime.utcnow",
})

#: np.random attributes that are constructors/types, not global-state draws
RNG_SAFE_ATTRS = frozenset({
    "default_rng", "Generator", "SeedSequence", "PCG64", "Philox",
    "MT19937", "SFC64", "RandomState", "BitGenerator",
})

#: set-consuming callables whose result does not depend on iteration order
ORDER_FREE_CONSUMERS = frozenset({
    "sorted", "len", "min", "max", "any", "all", "set", "frozenset", "bool",
})
ORDER_SENSITIVE_CONSUMERS = frozenset({
    "list", "tuple", "enumerate", "sum", "iter", "zip", "map", "filter",
})


def _is_set_expr(node: ast.AST) -> bool:
    if isinstance(node, (ast.Set, ast.SetComp)):
        return True
    if isinstance(node, ast.Call) and isinstance(node.func, ast.Name) \
            and node.func.id in ("set", "frozenset"):
        return True
    return False


class DeterminismPass:
    pass_id = PASS_ID

    def run(self, project: Project) -> list[Finding]:
        out: list[Finding] = []
        for sf in project.iter_files(*SCOPE):
            out.extend(self._check_file(sf))
        return out

    def _check_file(self, sf: SourceFile) -> list[Finding]:
        out: list[Finding] = []
        # only treat bare `random.x` as the stdlib module when it was
        # actually imported as such (a local Generator named `random`
        # would otherwise false-positive)
        stdlib_random = any(
            isinstance(n, ast.Import) and any(a.name == "random"
                                              for a in n.names)
            for n in ast.walk(sf.tree))

        for node in ast.walk(sf.tree):
            if isinstance(node, ast.Call):
                out.extend(self._check_call(sf, node, stdlib_random))
            elif isinstance(node, (ast.For, ast.AsyncFor)):
                out.extend(self._check_set_iter(sf, node.iter, node))
            elif isinstance(node, (ast.ListComp, ast.SetComp, ast.DictComp,
                                   ast.GeneratorExp)):
                for gen in node.generators:
                    out.extend(self._check_set_iter(sf, gen.iter, node))
        return out

    # -------------------------------------------------------------- rules
    def _check_call(self, sf: SourceFile, node: ast.Call,
                    stdlib_random: bool) -> list[Finding]:
        name = dotted_name(node.func)
        out: list[Finding] = []

        if name in WALLCLOCK_CALLS:
            if not sf.has_pragma(node, "allow-wallclock"):
                out.append(Finding(
                    PASS_ID, "wallclock", sf.path, node.lineno,
                    f"wall-clock call {name}() on the deterministic path; "
                    "use the simulation clock, or annotate a measured-"
                    "clock scope with `# lint: allow-wallclock(reason)`",
                    sf.scope(node)))
            return out

        parts = name.split(".")
        # global-state numpy RNG: np.random.rand / .seed / .shuffle ...
        if len(parts) >= 3 and parts[-2] == "random" \
                and parts[0] in ("np", "numpy") \
                and parts[-1] not in RNG_SAFE_ATTRS:
            if not sf.has_pragma(node, "allow-rng"):
                out.append(Finding(
                    PASS_ID, "unseeded-rng", sf.path, node.lineno,
                    f"global-state RNG call {name}(); draw from a seeded "
                    "np.random.default_rng(seed) generator instead",
                    sf.scope(node)))
        # default_rng()/RandomState() with no seed
        elif parts and parts[-1] in ("default_rng", "RandomState") \
                and not node.args and not node.keywords:
            if not sf.has_pragma(node, "allow-rng"):
                out.append(Finding(
                    PASS_ID, "unseeded-rng", sf.path, node.lineno,
                    f"{name}() constructed without a seed: every run "
                    "draws a different stream", sf.scope(node)))
        # stdlib random module calls (random.random, random.shuffle, ...)
        elif stdlib_random and len(parts) == 2 and parts[0] == "random":
            if not sf.has_pragma(node, "allow-rng"):
                out.append(Finding(
                    PASS_ID, "unseeded-rng", sf.path, node.lineno,
                    f"stdlib global-state RNG call {name}(); use a seeded "
                    "np.random.default_rng(seed) generator",
                    sf.scope(node)))

        # order-sensitive consumption of a set expression
        if isinstance(node.func, ast.Name) \
                and node.func.id in ORDER_SENSITIVE_CONSUMERS \
                and node.args and _is_set_expr(node.args[0]):
            if not sf.has_pragma(node, "allow-set-iter"):
                out.append(Finding(
                    PASS_ID, "set-iter", sf.path, node.lineno,
                    f"{node.func.id}() over a set leaks hash ordering "
                    "into results; sort first (sorted(...)) or keep an "
                    "insertion-ordered dict", sf.scope(node)))
        return out

    def _check_set_iter(self, sf: SourceFile, iter_node: ast.AST,
                        host: ast.AST) -> list[Finding]:
        if not _is_set_expr(iter_node):
            return []
        if sf.has_pragma(host, "allow-set-iter") \
                or sf.has_pragma(iter_node, "allow-set-iter"):
            return []
        return [Finding(
            PASS_ID, "set-iter", sf.path, iter_node.lineno,
            "iterating a set: order depends on the hash seed; iterate a "
            "sorted(...) copy or an insertion-ordered dict",
            sf.scope(iter_node))]
