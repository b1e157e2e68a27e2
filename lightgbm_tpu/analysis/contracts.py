"""Project-contract rules (CFG2xx, OBS3xx).

The repo keeps three views of the parameter surface that must agree:

  * the declarative ``_PARAMS`` registry in ``lightgbm_tpu/config.py``
    (single source of truth for names/aliases/defaults/checks),
  * every ``params.get("key")`` / ``config.<attr>`` read in the code,
  * the generated table in ``docs/Parameters.md``.

PRs 2 and 3 each had to keep these in sync by hand; these rules make
the contract mechanical.  Everything is read via ``ast`` — ``_PARAMS``
is a pure literal, so :func:`load_registry` gets names, aliases,
defaults and checks with ``ast.literal_eval`` and never imports the
package (no jax import in the lint gate).

OBS301 does the same for telemetry counters: every counter name bumped
via ``count_event``/``MetricsRegistry.inc``/``GBDT._count`` must be
declared once in ``lightgbm_tpu/obs/metrics.py`` ``COUNTERS`` (and every
declared counter must be bumped somewhere).
"""

from __future__ import annotations

import ast
import os
import re
from typing import Dict, Iterable, List, Optional, Set, Tuple

from .core import (FileContext, LintRun, Rule, SEVERITY_ERROR,
                   SEVERITY_WARNING, Violation, register_rule)


# --------------------------------------------------------------- registry
class Registry:
    """The ``_PARAMS`` registry, loaded without importing config.py."""

    def __init__(self, canonical: Dict[str, Tuple[object, Tuple, Tuple]],
                 linenos: Dict[str, int],
                 objective_aliases: Dict[str, str],
                 compat_only: Dict[str, int] = None):
        self.canonical = canonical        # name -> (default, aliases, checks)
        self.linenos = linenos            # name -> line in config.py
        self.objective_aliases = objective_aliases
        #: accepted-but-inert reference-compat keys: name -> decl lineno
        self.compat_only = compat_only or {}
        self.aliases: Dict[str, str] = {}
        for name, (_, aliases, _) in canonical.items():
            self.aliases[name] = name
            for a in aliases:
                self.aliases[a] = name

    @property
    def known_keys(self) -> Set[str]:
        return set(self.aliases)


def load_registry(config_path: str) -> Registry:
    with open(config_path, encoding="utf-8") as fh:
        tree = ast.parse(fh.read(), filename=config_path)
    params_node = None
    objalias_node = None
    compat_node = None
    for node in tree.body:
        targets = []
        if isinstance(node, ast.Assign):
            targets = [t.id for t in node.targets
                       if isinstance(t, ast.Name)]
        elif isinstance(node, ast.AnnAssign) and \
                isinstance(node.target, ast.Name):
            targets = [node.target.id]
        if "_PARAMS" in targets:
            params_node = node.value
        if "_OBJECTIVE_ALIASES" in targets:
            objalias_node = node.value
        if "_COMPAT_ONLY" in targets:
            compat_node = node.value
    if params_node is None:
        raise ValueError(f"{config_path}: no _PARAMS assignment found")
    entries = ast.literal_eval(params_node)
    canonical: Dict[str, Tuple[object, Tuple, Tuple]] = {}
    linenos: Dict[str, int] = {}
    for elt, raw in zip(entries, params_node.elts):
        name, default, aliases, checks = elt
        canonical[name] = (default, tuple(aliases), tuple(checks))
        linenos[name] = raw.lineno
    objective_aliases = ast.literal_eval(objalias_node) \
        if objalias_node is not None else {}
    compat_only: Dict[str, int] = {}
    if compat_node is not None and \
            isinstance(compat_node, (ast.Tuple, ast.List, ast.Set)):
        for el in compat_node.elts:
            if isinstance(el, ast.Constant) and isinstance(el.value, str):
                compat_only[el.value] = el.lineno
    return Registry(canonical, linenos, objective_aliases, compat_only)


def render_param_rows(reg: Registry) -> Dict[str, str]:
    """The docs/Parameters.md table row each parameter must have —
    byte-identical to ``config.generate_parameter_docs``."""
    rows = {}
    for name, (default, aliases, checks) in reg.canonical.items():
        d = repr(default) if default != "" else "`\"\"`"
        a = ", ".join(aliases) if aliases else "—"
        c = ", ".join(f"{op} {val:g}" for op, val in checks) if checks \
            else "—"
        rows[name] = f"| `{name}` | {d} | {a} | {c} |"
    return rows


_DOC_ROW_RE = re.compile(r"^\| `([A-Za-z0-9_]+)` \|")


def parse_doc_rows(docs_path: str) -> Dict[str, Tuple[int, str]]:
    """Parameter-table rows of docs/Parameters.md: name -> (lineno, row).
    Stops at the objective-alias section (its rows use the same shape)."""
    rows: Dict[str, Tuple[int, str]] = {}
    if not os.path.exists(docs_path):
        return rows
    with open(docs_path, encoding="utf-8") as fh:
        for lineno, line in enumerate(fh, start=1):
            line = line.rstrip("\n")
            if line.startswith("## Objective aliases"):
                break
            m = _DOC_ROW_RE.match(line)
            if m and m.group(1) != "Parameter":
                rows[m.group(1)] = (lineno, line)
    return rows


# ------------------------------------------------------- read collection
#: Config API members that are not parameters
_CONFIG_API = {
    "set", "is_explicit", "to_dict", "check_param_conflict",
}

#: receiver names treated as a params dict
_PARAMS_RECEIVERS = {"params"}

#: receiver names treated as a Config instance
_CONFIG_RECEIVERS = {"config", "cfg"}


def _receiver_kind(node: ast.expr,
                   local_config_aliases: Set[str]) -> Optional[str]:
    """'params' / 'config' / None for the receiver of a .get()/attr."""
    if isinstance(node, ast.Name):
        if node.id in _PARAMS_RECEIVERS:
            return "params"
        if node.id in _CONFIG_RECEIVERS or node.id in local_config_aliases:
            return "config"
    elif isinstance(node, ast.Attribute):
        if node.attr in _PARAMS_RECEIVERS:
            return "params"
        if node.attr in _CONFIG_RECEIVERS:
            return "config"
    return None


def _local_config_aliases(fn: ast.AST) -> Set[str]:
    """Names assigned from a config-ish expression inside ``fn``
    (``c = self.config`` makes ``c`` a Config receiver in that scope)."""
    out: Set[str] = set()
    for node in ast.walk(fn):
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name):
            if _receiver_kind(node.value, set()) == "config":
                out.add(node.targets[0].id)
    return out


class ParamReads:
    """Per-run collection of every parameter read the code performs."""

    def __init__(self) -> None:
        # (relpath, line, col, key, kind) — kind in {'params', 'config'}
        self.reads: List[Tuple[str, int, int, str, str]] = []
        # every string constant seen anywhere (dead-key fallback: a key
        # driven through getattr()/dynamic dispatch still counts as used
        # when its name appears as a literal)
        self.string_constants: Set[str] = set()
        # every attribute name read anywhere (same fallback for
        # `dataclasses.replace(cfg, key=...)`-style indirect access)
        self.attr_names: Set[str] = set()
        # function parameter / keyword-argument names: config keys that
        # flow through the Python-API kwargs bridge (engine pulls the
        # key out of the params dict and passes it as a kwarg, e.g.
        # `predict(pred_early_stop=...)`) count as consumed
        self.signature_names: Set[str] = set()

    def collect(self, ctx: FileContext) -> None:
        in_config_py = ctx.relpath.replace("\\", "/").endswith(
            "lightgbm_tpu/config.py")
        fn_aliases: Dict[ast.AST, Set[str]] = {}
        parents: Dict[ast.AST, ast.AST] = {}
        for node in ast.walk(ctx.tree):
            for child in ast.iter_child_nodes(node):
                parents[child] = node

        def aliases_for(node: ast.AST) -> Set[str]:
            cur = parents.get(node)
            while cur is not None:
                if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    if cur not in fn_aliases:
                        fn_aliases[cur] = _local_config_aliases(cur)
                    return fn_aliases[cur]
                cur = parents.get(cur)
            return set()

        if in_config_py:
            # config.py spells every registered name as a literal, so its
            # constants must NOT feed the dead-key fallback — CFG202
            # could never fire otherwise
            return
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Constant) and \
                    isinstance(node.value, str):
                self.string_constants.add(node.value)
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                a = node.args
                for p in a.posonlyargs + a.args + a.kwonlyargs:
                    self.signature_names.add(p.arg)
            elif isinstance(node, ast.keyword) and node.arg is not None:
                self.signature_names.add(node.arg)
            if isinstance(node, ast.Attribute):
                self.attr_names.add(node.attr)
                kind = _receiver_kind(node.value, aliases_for(node))
                if kind == "config" and \
                        isinstance(node.ctx, (ast.Load, ast.Store)) and \
                        not node.attr.startswith("_") and \
                        node.attr not in _CONFIG_API:
                    self.reads.append((ctx.relpath, node.lineno,
                                       node.col_offset, node.attr, "config"))
            elif isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Attribute) and \
                    node.func.attr == "get" and node.args and \
                    isinstance(node.args[0], ast.Constant) and \
                    isinstance(node.args[0].value, str):
                if _receiver_kind(node.func.value,
                                  aliases_for(node)) == "params":
                    self.reads.append((ctx.relpath, node.lineno,
                                       node.col_offset,
                                       node.args[0].value, "params"))
            elif isinstance(node, ast.Subscript) and \
                    isinstance(node.slice, ast.Constant) and \
                    isinstance(node.slice.value, str):
                if _receiver_kind(node.value, aliases_for(node)) == "params":
                    self.reads.append((ctx.relpath, node.lineno,
                                       node.col_offset,
                                       node.slice.value, "params"))


class _ContractRule(Rule):
    """Shared registry/reads plumbing.  Paths default to the run root;
    tests inject toy registries via the constructor."""

    def __init__(self, registry_path: Optional[str] = None,
                 docs_path: Optional[str] = None):
        self._registry_path = registry_path
        self._docs_path = docs_path

    def registry_path(self, run: LintRun) -> str:
        return self._registry_path or os.path.join(
            run.root, "lightgbm_tpu", "config.py")

    def docs_path(self, run: LintRun) -> str:
        return self._docs_path or os.path.join(
            run.root, "docs", "Parameters.md")

    def reads(self, run: LintRun) -> ParamReads:
        pr = run.scratch.get("param_reads")
        if pr is None:
            pr = ParamReads()
            for ctx in run.contexts:
                pr.collect(ctx)
            run.scratch["param_reads"] = pr
        return pr

    def package_scope(self, run: LintRun) -> bool:
        """True when the run linted the whole package tree — the
        "never used anywhere" rule directions (CFG202, half of OBS301)
        are only sound then; a single-file lint must not report
        package-wide absence."""
        return run.covers(os.path.dirname(self.registry_path(run)))

    def load(self, run: LintRun) -> Optional[Registry]:
        key = ("registry", self.registry_path(run))
        if key not in run.scratch:
            try:
                run.scratch[key] = load_registry(self.registry_path(run))
            except OSError:
                # no config.py under --root (toy fixture trees): the
                # contract rules simply don't apply
                run.scratch[key] = None
            except (ValueError, SyntaxError) as e:
                # config.py exists but _PARAMS is not a pure literal any
                # more — that must FAIL the gate, not silently disable
                # every CFG rule (LNT005, reported once by CFG201)
                run.scratch[key] = None
                run.scratch[key + ("error",)] = str(e)
        return run.scratch[key]

    def load_error(self, run: LintRun) -> Optional[str]:
        return run.scratch.get(
            ("registry", self.registry_path(run), "error"))


@register_rule
class UnregisteredConfigKey(_ContractRule):
    id = "CFG201"
    name = "unregistered-config-key"
    severity = SEVERITY_ERROR
    description = ("a `params.get(\"key\")`/`config.attr` read of a key "
                   "that is not registered in config.py `_PARAMS`")

    def finalize(self, run: LintRun) -> Iterable[Violation]:
        reg = self.load(run)
        if reg is None:
            err = self.load_error(run)
            if err is not None:
                yield Violation(
                    "LNT005", SEVERITY_ERROR, "lightgbm_tpu/config.py",
                    1, 0,
                    "_PARAMS is no longer a pure literal — tpulint "
                    "cannot load the registry and the CFG contract "
                    f"rules cannot run ({err}); keep _PARAMS "
                    "ast.literal_eval-able")
            return
        known = reg.known_keys
        for relpath, line, col, key, kind in self.reads(run).reads:
            if key not in known:
                what = f'params.get("{key}")' if kind == "params" \
                    else f"config.{key}"
            else:
                continue
            yield self.violation(
                relpath, line, col,
                f"{what} reads a key that is not registered in "
                "lightgbm_tpu/config.py _PARAMS — register it (with "
                "default/aliases/checks) and regenerate "
                "docs/Parameters.md")


@register_rule
class DeadConfigKey(_ContractRule):
    id = "CFG202"
    name = "dead-config-key"
    severity = SEVERITY_ERROR
    description = ("a parameter registered in config.py `_PARAMS` that "
                   "no code ever reads")

    def finalize(self, run: LintRun) -> Iterable[Violation]:
        reg = self.load(run)
        if reg is None or not self.package_scope(run):
            # "never read anywhere" is only decidable when the run saw
            # the whole package, not a file subset
            return
        pr = self.reads(run)
        read_keys = {key for (_, _, _, key, _) in pr.reads}
        # canonical resolution: reading an alias reads its canonical key
        read_canonical = {reg.aliases.get(k, k) for k in read_keys}

        def consumed(name: str) -> bool:
            if name in read_canonical:
                return True
            # indirect reads (getattr string, kwargs-bridge parameter,
            # dataclasses.replace(cfg, key=...)): the key's literal or
            # signature name shows up somewhere in the package
            return (name in pr.string_constants or name in pr.attr_names
                    or name in pr.signature_names)

        config_rel = "lightgbm_tpu/config.py"
        for name in reg.canonical:
            if name in reg.compat_only or consumed(name):
                continue
            yield self.violation(
                config_rel, reg.linenos.get(name, 1), 0,
                f"registered parameter `{name}` is never read anywhere "
                "in the package — wire it to its consumer, remove it "
                "from _PARAMS (and regenerate docs/Parameters.md), or "
                "declare it accepted-but-inert in _COMPAT_ONLY")
        # the compat list cannot rot: an entry that IS consumed (or no
        # longer registered) must leave _COMPAT_ONLY
        for name, lineno in reg.compat_only.items():
            if name not in reg.canonical:
                yield self.violation(
                    config_rel, lineno, 0,
                    f"_COMPAT_ONLY entry `{name}` is not registered in "
                    "_PARAMS — drop the stale compat marker")
            elif consumed(name):
                yield self.violation(
                    config_rel, lineno, 0,
                    f"_COMPAT_ONLY entry `{name}` IS read by the package "
                    "— it is no longer inert; remove it from "
                    "_COMPAT_ONLY")


@register_rule
class DocsRegistrySync(_ContractRule):
    id = "CFG203"
    name = "docs-registry-sync"
    severity = SEVERITY_ERROR
    description = ("docs/Parameters.md is out of sync with the "
                   "config.py `_PARAMS` registry")

    def finalize(self, run: LintRun) -> Iterable[Violation]:
        reg = self.load(run)
        if reg is None:
            return
        docs_path = self.docs_path(run)
        docs_rel = os.path.relpath(docs_path, run.root)
        expected = render_param_rows(reg)
        actual = parse_doc_rows(docs_path)
        if not actual:
            yield self.violation(
                docs_rel, 1, 0,
                "docs/Parameters.md missing or holds no parameter table; "
                "regenerate with `python -m lightgbm_tpu.config`")
            return
        for name, row in expected.items():
            if name not in actual:
                yield self.violation(
                    docs_rel, 1, 0,
                    f"registered parameter `{name}` has no row in "
                    "docs/Parameters.md; regenerate with `python -m "
                    "lightgbm_tpu.config`")
            elif actual[name][1] != row:
                yield self.violation(
                    docs_rel, actual[name][0], 0,
                    f"docs row for `{name}` is stale (defaults/aliases/"
                    "checks changed); regenerate with `python -m "
                    "lightgbm_tpu.config`")
        for name, (lineno, _) in actual.items():
            if name not in expected:
                yield self.violation(
                    docs_rel, lineno, 0,
                    f"documented parameter `{name}` is not registered in "
                    "config.py _PARAMS; regenerate the docs (or register "
                    "the key)")


# ------------------------------------------------------------- telemetry
def load_declared_counters(metrics_path: str) -> Dict[str, int]:
    """``COUNTERS`` declaration in obs/metrics.py: name -> lineno."""
    with open(metrics_path, encoding="utf-8") as fh:
        tree = ast.parse(fh.read(), filename=metrics_path)
    for node in tree.body:
        target = None
        if isinstance(node, ast.Assign) and len(node.targets) == 1 and \
                isinstance(node.targets[0], ast.Name):
            target = node.targets[0].id
        elif isinstance(node, ast.AnnAssign) and \
                isinstance(node.target, ast.Name):
            target = node.target.id
        if target == "COUNTERS" and isinstance(node.value, ast.Dict):
            out = {}
            for k in node.value.keys:
                if isinstance(k, ast.Constant) and isinstance(k.value, str):
                    out[k.value] = k.lineno
            return out
    return {}


@register_rule
class UndeclaredCounter(Rule):
    id = "OBS301"
    name = "undeclared-telemetry-counter"
    severity = SEVERITY_ERROR
    description = ("a telemetry counter bumped/read under a name not "
                   "declared in obs/metrics.py `COUNTERS` (or declared "
                   "but never used)")

    def __init__(self, metrics_path: Optional[str] = None):
        self._metrics_path = metrics_path

    @staticmethod
    def _collect_uses(run: LintRun) -> List[Tuple[str, int, int, str]]:
        """(relpath, line, col, name) per counter bump/read — gathered
        per run (never on the rule instance, so a reused LintRunner
        cannot leak one run's uses into the next)."""
        uses: List[Tuple[str, int, int, str]] = []
        for ctx in run.contexts:
            rel = ctx.relpath.replace("\\", "/")
            if rel.endswith("obs/metrics.py"):
                continue
            for node in ast.walk(ctx.tree):
                if not isinstance(node, ast.Call) or not node.args:
                    continue
                if isinstance(node.func, ast.Name) and \
                        node.func.id == "phase":
                    # a span that adds its seconds to a counter on exit
                    # (utils/timer.py ``phase(..., seconds=<counter>)``)
                    uses.extend(
                        (ctx.relpath, node.lineno, node.col_offset,
                         kw.value.value) for kw in node.keywords
                        if kw.arg == "seconds"
                        and isinstance(kw.value, ast.Constant)
                        and isinstance(kw.value.value, str))
                    continue
                first = node.args[0]
                if not (isinstance(first, ast.Constant)
                        and isinstance(first.value, str)):
                    continue
                name: Optional[str] = None
                if isinstance(node.func, ast.Name) and \
                        node.func.id == "count_event":
                    name = first.value
                elif isinstance(node.func, ast.Attribute):
                    attr = node.func.attr
                    if attr in ("inc", "counter", "_count"):
                        name = first.value
                    elif attr == "get" and \
                            isinstance(node.func.value, ast.Name) and \
                            node.func.value.id == "counters":
                        name = first.value
                if name is not None:
                    uses.append((ctx.relpath, node.lineno,
                                 node.col_offset, name))
        return uses

    def finalize(self, run: LintRun) -> Iterable[Violation]:
        path = self._metrics_path or os.path.join(
            run.root, "lightgbm_tpu", "obs", "metrics.py")
        try:
            declared = load_declared_counters(path)
        except (OSError, SyntaxError):
            return
        metrics_rel = os.path.relpath(path, run.root)
        if not declared:
            yield self.violation(
                metrics_rel, 1, 0,
                "no COUNTERS declaration found in obs/metrics.py — every "
                "telemetry counter name must be declared there once")
            return
        used_names = set()
        for relpath, line, col, name in self._collect_uses(run):
            used_names.add(name)
            if name not in declared:
                yield self.violation(
                    relpath, line, col,
                    f"telemetry counter `{name}` is not declared in "
                    "obs/metrics.py COUNTERS — declare it (name + one-"
                    "line meaning) so dashboards/docs track it")
        # the reverse direction ("never bumped anywhere") is only
        # decidable when the run covered the whole package
        if not run.covers(os.path.dirname(os.path.dirname(path))):
            return
        for name, lineno in declared.items():
            if name not in used_names:
                yield self.violation(
                    metrics_rel, lineno, 0,
                    f"declared telemetry counter `{name}` is never "
                    "bumped or read anywhere — remove it or wire the "
                    "instrumentation point")


# --------------------------------------------------------- event journal
def load_declared_events(events_path: str) -> Dict[str, int]:
    """``EVENTS`` declaration in obs/events.py: name -> lineno."""
    with open(events_path, encoding="utf-8") as fh:
        tree = ast.parse(fh.read(), filename=events_path)
    for node in tree.body:
        target = None
        if isinstance(node, ast.Assign) and len(node.targets) == 1 and \
                isinstance(node.targets[0], ast.Name):
            target = node.targets[0].id
        elif isinstance(node, ast.AnnAssign) and \
                isinstance(node.target, ast.Name):
            target = node.target.id
        if target == "EVENTS" and isinstance(node.value, ast.Dict):
            out = {}
            for k in node.value.keys:
                if isinstance(k, ast.Constant) and isinstance(k.value, str):
                    out[k.value] = k.lineno
            return out
    return {}


@register_rule
class UndeclaredEvent(Rule):
    id = "OBS302"
    name = "undeclared-journal-event"
    severity = SEVERITY_ERROR
    description = ("an event journaled via `emit_event` under a name not "
                   "declared in obs/events.py `EVENTS` (or declared but "
                   "never emitted)")

    def __init__(self, events_path: Optional[str] = None):
        self._events_path = events_path

    @staticmethod
    def _collect_uses(run: LintRun) -> List[Tuple[str, int, int, str]]:
        """(relpath, line, col, name) per emit_event call — gathered per
        run, same runner-reuse discipline as OBS301."""
        uses: List[Tuple[str, int, int, str]] = []
        for ctx in run.contexts:
            rel = ctx.relpath.replace("\\", "/")
            if rel.endswith("obs/events.py"):
                continue
            for node in ast.walk(ctx.tree):
                if not isinstance(node, ast.Call) or not node.args:
                    continue
                first = node.args[0]
                if not (isinstance(first, ast.Constant)
                        and isinstance(first.value, str)):
                    continue
                is_emit = (isinstance(node.func, ast.Name)
                           and node.func.id == "emit_event") or \
                          (isinstance(node.func, ast.Attribute)
                           and node.func.attr == "emit_event")
                if is_emit:
                    uses.append((ctx.relpath, node.lineno,
                                 node.col_offset, first.value))
        return uses

    def finalize(self, run: LintRun) -> Iterable[Violation]:
        path = self._events_path or os.path.join(
            run.root, "lightgbm_tpu", "obs", "events.py")
        try:
            declared = load_declared_events(path)
        except (OSError, SyntaxError):
            return
        events_rel = os.path.relpath(path, run.root)
        if not declared:
            yield self.violation(
                events_rel, 1, 0,
                "no EVENTS declaration found in obs/events.py — every "
                "journal event name must be declared there once")
            return
        used_names = set()
        for relpath, line, col, name in self._collect_uses(run):
            used_names.add(name)
            if name not in declared:
                yield self.violation(
                    relpath, line, col,
                    f"journal event `{name}` is not declared in "
                    "obs/events.py EVENTS — declare it (name + severity "
                    "+ one-line meaning) so readers of the journal can "
                    "rely on the schema")
        # "never emitted anywhere" is only decidable on a whole-package
        # run, mirroring OBS301's reverse direction
        if not run.covers(os.path.dirname(os.path.dirname(path))):
            return
        for name, lineno in declared.items():
            if name not in used_names:
                yield self.violation(
                    events_rel, lineno, 0,
                    f"declared journal event `{name}` is never emitted "
                    "anywhere — remove it or wire the emission site")


# ------------------------------------------------------------------ SLOs
def load_declared_slos(slo_path: str) -> Dict[str, int]:
    """``SLOS`` declaration in obs/slo.py: name -> lineno (same pure-
    literal AST contract as COUNTERS/EVENTS)."""
    with open(slo_path, encoding="utf-8") as fh:
        tree = ast.parse(fh.read(), filename=slo_path)
    for node in tree.body:
        target = None
        if isinstance(node, ast.Assign) and len(node.targets) == 1 and \
                isinstance(node.targets[0], ast.Name):
            target = node.targets[0].id
        elif isinstance(node, ast.AnnAssign) and \
                isinstance(node.target, ast.Name):
            target = node.target.id
        if target == "SLOS" and isinstance(node.value, ast.Dict):
            out = {}
            for k in node.value.keys:
                if isinstance(k, ast.Constant) and isinstance(k.value, str):
                    out[k.value] = k.lineno
            return out
    return {}


@register_rule
class UndeclaredSlo(Rule):
    id = "OBS303"
    name = "undeclared-slo"
    severity = SEVERITY_ERROR
    description = ("an SLO watched via `watch_slo` under a name not "
                   "declared in obs/slo.py `SLOS` (or declared but never "
                   "watched)")

    def __init__(self, slo_path: Optional[str] = None):
        self._slo_path = slo_path

    @staticmethod
    def _collect_uses(run: LintRun) -> List[Tuple[str, int, int, str]]:
        """(relpath, line, col, name) per watch_slo call — gathered per
        run, same runner-reuse discipline as OBS301/OBS302."""
        uses: List[Tuple[str, int, int, str]] = []
        for ctx in run.contexts:
            rel = ctx.relpath.replace("\\", "/")
            if rel.endswith("obs/slo.py"):
                continue
            for node in ast.walk(ctx.tree):
                if not isinstance(node, ast.Call) or not node.args:
                    continue
                first = node.args[0]
                if not (isinstance(first, ast.Constant)
                        and isinstance(first.value, str)):
                    continue
                is_watch = (isinstance(node.func, ast.Name)
                            and node.func.id == "watch_slo") or \
                           (isinstance(node.func, ast.Attribute)
                            and node.func.attr == "watch_slo")
                if is_watch:
                    uses.append((ctx.relpath, node.lineno,
                                 node.col_offset, first.value))
        return uses

    def finalize(self, run: LintRun) -> Iterable[Violation]:
        path = self._slo_path or os.path.join(
            run.root, "lightgbm_tpu", "obs", "slo.py")
        try:
            declared = load_declared_slos(path)
        except (OSError, SyntaxError):
            return
        slo_rel = os.path.relpath(path, run.root)
        if not declared:
            yield self.violation(
                slo_rel, 1, 0,
                "no SLOS declaration found in obs/slo.py — every SLO "
                "name must be declared there once")
            return
        used_names = set()
        for relpath, line, col, name in self._collect_uses(run):
            used_names.add(name)
            if name not in declared:
                yield self.violation(
                    relpath, line, col,
                    f"SLO `{name}` is not declared in obs/slo.py SLOS — "
                    "declare it (domain + direction + default budget + "
                    "one-line meaning) so operators can rely on the "
                    "alert vocabulary")
        # the reverse direction ("declared but never watched") is only
        # decidable on a whole-package run, like OBS301/OBS302
        if not run.covers(os.path.dirname(os.path.dirname(path))):
            return
        for name, lineno in declared.items():
            if name not in used_names:
                yield self.violation(
                    slo_rel, lineno, 0,
                    f"declared SLO `{name}` is never watched anywhere — "
                    "remove it or wire a watch_slo site that can feed it")


# ----------------------------------------------------------------- spans
def load_declared_spans(spans_path: str) -> Dict[str, int]:
    """``SPANS`` declaration in obs/reqtrace.py: name -> lineno (same
    pure-literal AST contract as COUNTERS/EVENTS/SLOS)."""
    with open(spans_path, encoding="utf-8") as fh:
        tree = ast.parse(fh.read(), filename=spans_path)
    for node in tree.body:
        target = None
        if isinstance(node, ast.Assign) and len(node.targets) == 1 and \
                isinstance(node.targets[0], ast.Name):
            target = node.targets[0].id
        elif isinstance(node, ast.AnnAssign) and \
                isinstance(node.target, ast.Name):
            target = node.target.id
        if target == "SPANS" and isinstance(node.value, ast.Dict):
            out = {}
            for k in node.value.keys:
                if isinstance(k, ast.Constant) and isinstance(k.value, str):
                    out[k.value] = k.lineno
            return out
    return {}


@register_rule
class UndeclaredSpan(Rule):
    id = "OBS304"
    name = "undeclared-span"
    severity = SEVERITY_ERROR
    description = ("a request-trace span recorded via `record_span` under "
                   "a name not declared in obs/reqtrace.py `SPANS` (or "
                   "declared but never recorded)")

    def __init__(self, spans_path: Optional[str] = None):
        self._spans_path = spans_path

    @staticmethod
    def _collect_uses(run: LintRun) -> List[Tuple[str, int, int, str]]:
        """(relpath, line, col, name) per record_span call — gathered
        per run, same runner-reuse discipline as OBS301/OBS302/OBS303."""
        uses: List[Tuple[str, int, int, str]] = []
        for ctx in run.contexts:
            rel = ctx.relpath.replace("\\", "/")
            if rel.endswith("obs/reqtrace.py"):
                continue
            for node in ast.walk(ctx.tree):
                if not isinstance(node, ast.Call) or not node.args:
                    continue
                first = node.args[0]
                if not (isinstance(first, ast.Constant)
                        and isinstance(first.value, str)):
                    continue
                is_rec = (isinstance(node.func, ast.Name)
                          and node.func.id == "record_span") or \
                         (isinstance(node.func, ast.Attribute)
                          and node.func.attr == "record_span")
                if is_rec:
                    uses.append((ctx.relpath, node.lineno,
                                 node.col_offset, first.value))
        return uses

    def finalize(self, run: LintRun) -> Iterable[Violation]:
        path = self._spans_path or os.path.join(
            run.root, "lightgbm_tpu", "obs", "reqtrace.py")
        try:
            declared = load_declared_spans(path)
        except (OSError, SyntaxError):
            return
        spans_rel = os.path.relpath(path, run.root)
        if not declared:
            yield self.violation(
                spans_rel, 1, 0,
                "no SPANS declaration found in obs/reqtrace.py — every "
                "request-trace span name must be declared there once")
            return
        used_names = set()
        for relpath, line, col, name in self._collect_uses(run):
            used_names.add(name)
            if name not in declared:
                yield self.violation(
                    relpath, line, col,
                    f"trace span `{name}` is not declared in "
                    "obs/reqtrace.py SPANS — declare it (name + one-line "
                    "meaning) so trace consumers can rely on the span "
                    "vocabulary")
        # the reverse direction ("declared but never recorded") is only
        # decidable on a whole-package run, like OBS301/OBS302/OBS303
        if not run.covers(os.path.dirname(os.path.dirname(path))):
            return
        for name, lineno in declared.items():
            if name not in used_names:
                yield self.violation(
                    spans_rel, lineno, 0,
                    f"declared trace span `{name}` is never recorded "
                    "anywhere — remove it or wire the record_span site")
