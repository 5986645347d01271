"""MX-R001..MX-R004 — registration completeness for the config, metric,
and fault surfaces.

The repo's operational surfaces each have a registry and a generated or
linted doc: env vars (``base.register_env`` -> docs/env_vars.md), metric
families (``metrics.counter/gauge/histogram`` ->
docs/observability.md), fault sites (``faults._SITES`` ->
docs/fault_tolerance.md).  PRs 6-10 added to all three ad hoc; the old
``ci/run.sh envdoc``/``faultdoc`` shell greps covered two of them and
nothing covered unregistered ``os.environ`` reads at all.  These rules
subsume those greps:

* MX-R001 — every literal ``MXNET_*`` env read (``os.getenv``,
  ``os.environ[...]``, ``os.environ.get``, ``base.getenv``) must name a
  var registered somewhere in the scanned tree via ``register_env``.
* MX-R002 — every metric family constructed with a literal name must
  appear in docs/observability.md.
* MX-R003 — every fault site key in ``faults._SITES`` must appear in
  docs/fault_tolerance.md (the old ``faultdoc`` gate, now static).
* MX-R004 — docs/env_vars.md must byte-match what
  :func:`render_env_doc` renders from the live registry (the old
  ``envdoc`` regen+git-diff gate, now dirty-tree-friendly).  This is
  the one rule that imports the package; fixture runs skip it.
"""
from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from .core import AnalysisContext, Finding, Source, dotted as _dotted

#: modules imported so every use-site ``register_env`` runs before the
#: registry is rendered — tools/gen_env_doc.py delegates here.
_ENV_DOC_MODULES = (
    "mxnet_tpu", "mxnet_tpu.kvstore", "mxnet_tpu.metrics",
    "mxnet_tpu.profiler", "mxnet_tpu.subgraph", "mxnet_tpu.optimizer",
    "mxnet_tpu.ops.pallas.attention", "mxnet_tpu.parallel",
    "mxnet_tpu.gluon.data.dataloader", "mxnet_tpu.serving",
    "mxnet_tpu.faults", "mxnet_tpu.retry", "mxnet_tpu.kvstore_async",
    "mxnet_tpu.kvstore_sched", "mxnet_tpu.tracing",
    "mxnet_tpu.health", "mxnet_tpu.io",
    "mxnet_tpu._native", "mxnet_tpu.recordio", "mxnet_tpu.test_utils",
)


def render_env_doc() -> str:
    """Render docs/env_vars.md from the live ``register_env`` registry.

    Single source of truth shared by ``tools/gen_env_doc.py`` (writes
    the file) and rule MX-R004 (asserts the checked-in file matches).
    """
    import importlib
    for mod in _ENV_DOC_MODULES:
        importlib.import_module(mod)
    from mxnet_tpu.base import list_env
    rows = ["# Environment variables",
            "",
            "Runtime configuration surface (reference analog: "
            "`docs/.../env_var.md`). Generated by `tools/gen_env_doc.py` "
            "from `mxnet_tpu.base.register_env` registrations — "
            "`mx.runtime.list_env()` returns the same data at runtime.",
            "",
            "| Variable | Default | Effect |",
            "|---|---|---|"]
    for name, meta in sorted(list_env().items()):
        doc = " ".join(str(meta["doc"]).split())
        rows.append(f"| `{name}` | `{meta['default']}` | {doc} |")
    return "\n".join(rows) + "\n"


def documented_metric_names(doc: str) -> Set[str]:
    """Metric family names a doc mentions, expanding the established
    ``mxnet_foo_{a,b}_total`` brace shorthand (docs/observability.md's
    convention for sibling families)."""
    import itertools
    import re
    names: Set[str] = set()
    for tok in re.findall(r"mxnet_[a-z0-9_{},]+", doc):
        tok = tok.rstrip("_,")
        while tok.count("{") != tok.count("}"):
            # label annotation cut mid-brace by the charset (=, |):
            # drop the unterminated trailing brace group
            tok = tok[: tok.rfind("{")]
        # A brace group can be name alternation
        # (mxnet_checkpoint_{saves,...}_total) or a label set
        # (mxnet_faults_injected_total{site,kind}) — syntactically
        # identical.  Generate every candidate: for each brace
        # boundary, the token truncated there with the leading groups
        # expanded.  Over-approximating "documented" is harmless
        # because constructed names are matched exactly.
        spans = [m.span() for m in re.finditer(r"\{[^{}]*\}", tok)]
        for i in range(len(spans) + 1):
            end = spans[i][0] if i < len(spans) else len(tok)
            part = tok[:end]
            groups = re.findall(r"\{([^{}]*)\}", part)
            if not groups:
                names.add(part.rstrip("_"))
                continue
            template = re.sub(r"\{[^{}]*\}", "{}", part)
            for combo in itertools.product(
                    *[g.split(",") for g in groups]):
                names.add(template.format(*combo).rstrip("_"))
    return names


def _str_const(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def _collect_registered(sources: List[Source]) -> Set[str]:
    registered: Set[str] = set()
    for src in sources:
        for node in ast.walk(src.tree):
            if not isinstance(node, ast.Call):
                continue
            d = _dotted(node.func) or ""
            if d.rsplit(".", 1)[-1] == "register_env" and node.args:
                name = _str_const(node.args[0])
                if name:
                    registered.add(name)
    return registered


def _env_reads(src: Source) -> List[Tuple[str, int]]:
    reads: List[Tuple[str, int]] = []
    for node in ast.walk(src.tree):
        if isinstance(node, ast.Call):
            d = _dotted(node.func) or ""
            leaf = d.rsplit(".", 1)[-1]
            # endswith, not equality: 'import os as _os' style aliases
            # must not evade the detector
            if (d.endswith("environ.get") or leaf == "getenv") \
                    and node.args:
                name = _str_const(node.args[0])
                if name and name.startswith("MXNET_"):
                    reads.append((name, node.lineno))
        elif isinstance(node, ast.Subscript):
            # Load ctx only: os.environ['X'] = ... (the child-env
            # write pattern) and del os.environ['X'] are not reads
            if (_dotted(node.value) or "").endswith("environ") \
                    and isinstance(node.ctx, ast.Load):
                name = _str_const(node.slice)
                if name and name.startswith("MXNET_"):
                    reads.append((name, node.lineno))
    return reads


def _metric_families(src: Source) -> List[Tuple[str, int]]:
    fams: List[Tuple[str, int]] = []
    for node in ast.walk(src.tree):
        if not isinstance(node, ast.Call):
            continue
        d = _dotted(node.func) or ""
        if d.rsplit(".", 1)[-1] in ("counter", "gauge", "histogram") \
                and node.args:
            name = _str_const(node.args[0])
            if name and name.startswith("mxnet_"):
                fams.append((name, node.lineno))
    return fams


def _fault_sites(src: Source
                 ) -> Tuple[List[Tuple[str, int]], List[int]]:
    """Fault-site names declared in this module, plus the lines of any
    ``_SITES`` mutation the parser cannot resolve to literals.  The
    retired runtime ``faultdoc`` gate saw every site however it was
    registered; to keep that strength statically, an unresolvable
    mutation (``_SITES[var] = ...``, ``_SITES.update(expr)``, a
    non-dict-literal assignment) is itself reported rather than
    silently checked as nothing."""
    sites: List[Tuple[str, int]] = []
    dynamic: List[int] = []
    for node in ast.walk(src.tree):
        if isinstance(node, ast.Assign):
            names = {t.id for t in node.targets
                     if isinstance(t, ast.Name)}
            sub_hit = any(
                isinstance(t, ast.Subscript)
                and (_dotted(t.value) or "").endswith("_SITES")
                for t in node.targets)
            if "_SITES" in names:
                val = node.value
                if isinstance(val, ast.Dict):
                    for key in val.keys:
                        name = (_str_const(key) if key is not None
                                else None)
                        if name:
                            sites.append((name, key.lineno))
                        else:
                            dynamic.append(node.lineno)
                else:
                    dynamic.append(node.lineno)
            elif sub_hit:
                # _SITES["new.site"] = "..." — literal key is a site,
                # anything else is a dynamic mutation
                for t in node.targets:
                    if (isinstance(t, ast.Subscript)
                            and (_dotted(t.value) or ""
                                 ).endswith("_SITES")):
                        name = _str_const(t.slice)
                        if name:
                            sites.append((name, node.lineno))
                        else:
                            dynamic.append(node.lineno)
        elif isinstance(node, ast.Call):
            # suffix match, like the environ detector: cross-module
            # faults._SITES.update(...) must not evade the gate
            d = _dotted(node.func) or ""
            if d.endswith("_SITES.update") \
                    or d.endswith("_SITES.setdefault"):
                arg = node.args[0] if node.args else None
                if d.endswith("update") and isinstance(arg, ast.Dict):
                    for key in arg.keys:
                        name = (_str_const(key) if key is not None
                                else None)
                        if name:
                            sites.append((name, key.lineno))
                        else:
                            dynamic.append(node.lineno)
                elif d.endswith("setdefault") and _str_const(arg):
                    sites.append((_str_const(arg), node.lineno))
                else:
                    dynamic.append(node.lineno)
    return sites, dynamic


def analyze(ctx: AnalysisContext) -> List[Finding]:
    findings: List[Finding] = []
    registered = _collect_registered(
        ctx.registration_sources or ctx.sources)

    obs_doc = ctx.doc("observability.md")
    documented = documented_metric_names(obs_doc)
    fault_doc = ctx.doc("fault_tolerance.md")
    seen_fams: Set[str] = set()
    seen_sites: Set[str] = set()

    for src in ctx.sources:
        for name, line in _env_reads(src):
            if name not in registered:
                findings.append(Finding(
                    "MX-R001", src.rel, line,
                    f"env var {name!r} read but never registered via "
                    "register_env",
                    "register_env(name, default, doc) at the use site "
                    "and run 'python tools/gen_env_doc.py' so the var "
                    "lands in docs/env_vars.md"))
        for name, line in _metric_families(src):
            if name in seen_fams:
                continue
            seen_fams.add(name)
            if name not in documented:
                findings.append(Finding(
                    "MX-R002", src.rel, line,
                    f"metric family {name!r} constructed but not "
                    "documented in docs/observability.md",
                    "add the family (name, type, labels, meaning) to "
                    "the docs/observability.md catalog"))
        sites, dynamic = _fault_sites(src)
        for name, line in sites:
            if name in seen_sites:
                continue
            seen_sites.add(name)
            if name not in fault_doc:
                findings.append(Finding(
                    "MX-R003", src.rel, line,
                    f"fault site {name!r} not documented in "
                    "docs/fault_tolerance.md",
                    "document the site (where it lives, what each kind "
                    "does there) — the site table is "
                    "faults.known_sites()"))
        for line in dynamic:
            findings.append(Finding(
                "MX-R003", src.rel, line,
                "fault-site table _SITES mutated with a non-literal "
                "key or value set — the doc lint cannot see what was "
                "registered",
                "declare every site in the _SITES dict literal (or "
                "with a literal key) so the registration can be "
                "checked against docs/fault_tolerance.md"))

    if ctx.check_env_doc:
        expect = render_env_doc()
        # soundness of the render itself: every register_env the static
        # scan sees must have actually RUN during the render — a
        # registration in a module _ENV_DOC_MODULES doesn't reach would
        # otherwise drift out of the doc with both MX-R001 and the
        # byte-compare staying green
        rendered_vars = {ln.split("`")[1] for ln in expect.splitlines()
                         if ln.startswith("| `")}
        unreached = registered - rendered_vars
        if unreached:
            findings.append(Finding(
                "MX-R004", "mxnet_tpu/analysis/registration.py", 1,
                f"register_env call(s) for {sorted(unreached)} were "
                "not executed by render_env_doc — their module is not "
                "reachable from _ENV_DOC_MODULES",
                "add the registering module to _ENV_DOC_MODULES so the "
                "var lands in docs/env_vars.md"))
        actual_path = ctx.docs_root / "env_vars.md"
        actual = actual_path.read_text() if actual_path.exists() else ""
        if actual != expect:
            exp_vars = {ln.split("`")[1] for ln in expect.splitlines()
                        if ln.startswith("| `")}
            act_vars = {ln.split("`")[1] for ln in actual.splitlines()
                        if ln.startswith("| `")}
            missing = sorted(exp_vars - act_vars)
            extra = sorted(act_vars - exp_vars)
            detail = []
            if missing:
                detail.append(f"missing: {missing}")
            if extra:
                detail.append(f"stale rows: {extra}")
            if not detail:
                detail.append("row text drifted (default or doc "
                              "changed)")
            findings.append(Finding(
                "MX-R004", "docs/env_vars.md", 1,
                "stale vs the registered env surface — "
                + "; ".join(detail),
                "run 'python tools/gen_env_doc.py' and commit the "
                "result"))
    return findings
