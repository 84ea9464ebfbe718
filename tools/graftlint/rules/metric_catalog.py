"""metric-name-catalog: docs/observability.md and the code agree, both
directions — the env-var-catalog rule's twin for the telemetry registry.

Every counter/gauge/histogram/span/stage name LITERAL recorded through
``telemetry.{inc,gauge,observe,span,record_interval,add_stage}`` (and
``record_retrace(site)``, counted as ``retrace.<site>``) in the metric
scopes (``mxtpu/``) must have a table row in the observability catalog
(first cell, backticked), and every cataloged row must have a surviving
record site — a stale row is flagged at its doc line. Without this rule a
new metric ships invisible to anyone reading the catalog, and a renamed
one leaves dashboards silently flat; the runtime can never notice either.

Dynamic names are handled structurally, not ignored: a ``"%s.wait" %
site`` / ``"retrace." + site`` / f-string name becomes a PATTERN, so doc
rows it can produce (``data.wait``, ``retrace.fused_optimizer``) are not
stale, and doc rows with ``<i>``-style placeholders are probed against
the code side with the placeholder instantiated. A ``span(..., d2h=True)``
literal additionally declares its ``<name>.d2h`` attribution counter.

Doc-row grammar (the catalog's own idiom): backticked names in the first
table cell; ``{a,b,c}`` comma groups expand to alternatives,
``{reason}``-style single-word groups are tag annotations and drop,
``<i>`` placeholders match any suffix."""
from __future__ import annotations

import ast
import re

from ..core import Rule

# writer -> index of the name argument
_WRITERS = {"inc": 0, "gauge": 0, "observe": 0, "span": 0,
            "record_interval": 0, "add_stage": 1}
# declared metric-writing WRAPPERS (any receiver): the name literal lives
# at the given positional index of the wrapper call, not in a direct
# telemetry.* call — MicroBatcher._share_stage fans one stage duration
# out to every cohort member's breakdown
_WRAPPER_WRITERS = {"_share_stage": 1}
_RETRACE = "record_retrace"
_TELEMETRY_NAMES = ("telemetry", "_telemetry")
_FMT_RE = re.compile(r"%[sdrxif]")
_NAME_RE = re.compile(r"^[a-z0-9_.]+$")
_TOKEN_RE = re.compile(r"`([^`]+)`")


def call_keywords(node):
    return node.keywords or ()


def _resolve_name(node):
    """(kind, value) where kind is 'lit' (exact string), 'pat' (regex
    source), or None (statically unresolvable, skipped)."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return "lit", node.value
    if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Mod):
        left = node.left
        if isinstance(left, ast.Constant) and isinstance(left.value, str):
            # escape the literal text, then turn %s/%d placeholders
            # into wildcards
            pat = re.escape(_FMT_RE.sub("\0", left.value)).replace(
                re.escape("\0"), ".*")
            return "pat", pat
        return None, None
    if isinstance(node, ast.JoinedStr):
        parts = []
        for v in node.values:
            if isinstance(v, ast.Constant) and isinstance(v.value, str):
                parts.append(re.escape(v.value))
            else:
                parts.append(".*")
        pat = "".join(parts)
        return ("pat", pat) if pat.strip(".*") else (None, None)
    if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Add):
        lk, lv = _resolve_name(node.left)
        rk, rv = _resolve_name(node.right)
        lpat = re.escape(lv) if lk == "lit" else (lv if lk == "pat"
                                                  else ".*")
        rpat = re.escape(rv) if rk == "lit" else (rv if rk == "pat"
                                                  else ".*")
        if lk is None and rk is None:
            return None, None
        return "pat", lpat + rpat
    return None, None


def parse_doc_rows(text):
    """{literal_name: line} + [(pattern, line)] from the first cells of
    the catalog's markdown table rows."""
    names, patterns = {}, []
    for i, line in enumerate(text.splitlines(), 1):
        stripped = line.lstrip()
        if not stripped.startswith("|"):
            continue
        cells = stripped.split("|")
        if len(cells) < 3:
            continue
        for token in _TOKEN_RE.findall(cells[1]):
            for name in _expand_token(token):
                if "\0" in name:
                    patterns.append((re.escape(name).replace(
                        re.escape("\0"), ".*"), i))
                elif _NAME_RE.match(name):
                    names.setdefault(name, i)
    return names, patterns


def _expand_token(token):
    """Expand one backticked doc token into candidate metric names;
    non-metric tokens (env vars, code fragments) expand to nothing."""
    token = token.strip()
    if not token or " " in token or "=" in token:
        return []
    # placeholders like <i> become wildcard marks before brace handling
    token = re.sub(r"<[^>]*>", "\0", token)
    out = [""]
    pos = 0
    for m in re.finditer(r"\{([^{}]*)\}", token):
        chunk = token[pos:m.start()]
        body = m.group(1)
        if "," in body:
            alts = [a.strip() for a in body.split(",") if a.strip()]
            out = [o + chunk + a for o in out for a in alts]
        else:
            # single-word group = tag annotation ({reason}, {r<i>}): the
            # base name is the metric; the tag dimension is not a name
            out = [o + chunk for o in out]
        pos = m.end()
    out = [o + token[pos:] for o in out]
    return [o for o in out
            if o and _NAME_RE.match(o.replace("\0", "x"))]


class MetricNameCatalog(Rule):
    id = "metric-name-catalog"

    def __init__(self, config):
        super().__init__(config)
        self._lits = {}      # name -> (ctx, line) of first record site
        self._pats = []      # (regex-source, ctx, line)

    # ------------------------------------------------------------ collection
    def _in_scope(self, rel):
        for s in getattr(self.config, "metric_scopes", ("mxtpu",)):
            if s in ("", "."):
                return True
            if rel == s or rel.startswith(s.rstrip("/") + "/"):
                return True
        return False

    def visit(self, ctx, project):
        if not self._in_scope(ctx.rel):
            return
        telemetry_module = ctx.rel.endswith("telemetry.py")
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            if isinstance(fn, ast.Attribute) and \
                    isinstance(fn.value, ast.Name) and \
                    fn.value.id in _TELEMETRY_NAMES:
                attr = fn.attr
            elif telemetry_module and isinstance(fn, ast.Name):
                # inside mxtpu/telemetry.py the writers are module-local
                # (inc("transfer.d2h"), span(...) the class)
                attr = fn.id
            elif isinstance(fn, (ast.Name, ast.Attribute)) and \
                    (fn.id if isinstance(fn, ast.Name)
                     else fn.attr) == "with_retries":
                # resilience.with_retries(metric="retry.<site>") is a
                # declared counter writer — the literal lives in the
                # kwarg, not in a telemetry.inc call
                for kw in call_keywords(node):
                    if kw.arg == "metric":
                        k, v = _resolve_name(kw.value)
                        if k == "lit":
                            self._lits.setdefault(v, (ctx, node.lineno))
                        elif k == "pat":
                            self._pats.append((v, ctx, node.lineno))
                continue
            elif isinstance(fn, ast.Attribute) and \
                    fn.attr in _WRAPPER_WRITERS:
                self._take(node, _WRAPPER_WRITERS[fn.attr], ctx)
                continue
            else:
                continue
            if attr == _RETRACE:
                self._take(node, 0, ctx, prefix="retrace.")
                continue
            if attr not in _WRITERS:
                continue
            self._take(node, _WRITERS[attr], ctx,
                       d2h_twin=(attr == "span"))

    def _take(self, call, argpos, ctx, prefix="", d2h_twin=False):
        if len(call.args) <= argpos:
            return
        kind, v = _resolve_name(call.args[argpos])
        line = call.lineno
        if kind == "lit":
            self._lits.setdefault(prefix + v, (ctx, line))
            if d2h_twin and any(
                    kw.arg == "d2h" and
                    isinstance(kw.value, ast.Constant) and
                    kw.value.value is True for kw in call.keywords):
                self._lits.setdefault(v + ".d2h", (ctx, line))
        elif kind == "pat":
            self._pats.append((re.escape(prefix) + v, ctx, line))

    # ------------------------------------------------------------- verdicts
    def finalize(self, project):
        if not self._lits and not self._pats:
            return  # nothing scanned (rule scoped out) — no doc verdicts
        doc_rel = getattr(self.config, "metric_doc",
                          "docs/observability.md")
        doc_path = self.config.root / doc_rel
        try:
            doc_text = doc_path.read_text(encoding="utf-8")
        except OSError:
            self.report(None, doc_rel, 1,
                        "metric catalog %s is missing — every telemetry "
                        "metric/span name needs a documented row" % doc_rel)
            return
        doc_names, doc_pats = parse_doc_rows(doc_text)
        doc_regexes = [re.compile(p + "$") for p, _ in doc_pats]

        for name in sorted(self._lits):
            if name in doc_names or \
                    any(rx.match(name) for rx in doc_regexes):
                continue
            ctx, line = self._lits[name]
            self.report(
                ctx, ctx.rel, line,
                "metric/span name '%s' is recorded here but has no row in "
                "%s — add one (meaning + source) to the metric catalog"
                % (name, doc_rel))

        code_regexes = [re.compile(p + "$") for p, _, _ in self._pats]

        def covered(probe):
            return probe in self._lits or \
                any(rx.match(probe) for rx in code_regexes)

        for name in sorted(doc_names):
            if not covered(name):
                self.report(
                    None, doc_rel, doc_names[name],
                    "metric '%s' is cataloged here but no record site "
                    "survives in the scanned tree — stale row; delete it "
                    "or restore the metric" % name)
        for pat, line in doc_pats:
            # instantiate the placeholder with a probe value: the row is
            # alive iff SOME code site can produce a matching name
            probe = pat.replace("\\", "")
            probe = probe.replace(".*", "0")
            if not covered(probe):
                self.report(
                    None, doc_rel, line,
                    "metric family row (pattern %r) has no surviving "
                    "record site — stale row" % pat)
