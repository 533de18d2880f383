"""doc-sync: the registries and the docs that claim to mirror them.

Five sub-areas, each cross-referencing a source-of-truth registry against
the documentation (and secondary consumers) that enumerate it. Drift here
is invisible to every runtime test — the code works, the docs lie:

- **faults** — ``utils/faults.py`` ``KNOWN_POINTS`` vs the fault-point
  table in ``docs/robustness.md`` (both directions).
- **config** — ``MarlinConfig`` dataclass fields vs the knob table in
  ``docs/configuration.md``: undocumented fields, documented ghosts,
  *default-value drift* (the table's Default column is parsed, GiB/MiB and
  2^n notations included, and compared to the dataclass default), knobs no
  code ever reads (dead knob; a DEPRECATED comment on the field exempts
  it), and attribute reads off ``get_config()`` that name no field.
- **metrics** — every family registered in the package
  (``reg.counter/gauge/histogram("marlin_*", ...)``) vs the metric table in
  ``docs/observability.md`` (both directions).
- **memory** — ``obs/memledger.py`` ``KNOWN_COMPONENTS`` (the HBM
  ledger's attribution vocabulary) vs the component table inside
  ``docs/observability.md``'s "Memory attribution" section (both
  directions): an undocumented component is a ledger slice no operator
  can interpret, a ghost row promises attribution nothing records.
- **events** — EventLog ``kind=`` literals and serving ``ev=``
  discriminators actually emitted vs the post-mortem vocabulary
  ``obs/report.py`` declares (``KNOWN_KINDS`` / ``KNOWN_SERVE_EVS``): a
  record kind the analyzer has never heard of is a black-box stream.

Each sub-area silently skips when its source files are absent, so the
check runs unchanged over the seeded fixture trees.
"""

from __future__ import annotations

import ast
import re

from ..core import Finding, Repo, dotted, str_const
from .testcov import known_points

NAME = "doc-sync"
SCOPE = "repo"

CONFIG_REL = "marlin_tpu/config.py"
REPORT_REL = "marlin_tpu/obs/report.py"
MEMLEDGER_REL = "marlin_tpu/obs/memledger.py"
DOC_ROBUST = "docs/robustness.md"
DOC_CONFIG = "docs/configuration.md"
DOC_OBS = "docs/observability.md"

_ROW_RE = re.compile(r"^\|\s*`")


def _doc_rows(text: str) -> dict[str, tuple[int, list[str]]]:
    """Backticked key(s) in the first column -> (lineno, remaining cells)
    for every markdown table row. A cell documenting several keys at once
    (``| `ckpt.write` / `ckpt.manifest` | ...``) yields every key."""
    rows: dict[str, tuple[int, list[str]]] = {}
    for i, line in enumerate(text.splitlines(), start=1):
        if not _ROW_RE.match(line):
            continue
        cells = [c.strip() for c in line.strip().strip("|").split("|")]
        for m in re.finditer(r"`([^`]+)`", cells[0]):
            rows.setdefault(m.group(1), (i, cells[1:]))
    return rows


# ------------------------------------------------------------------ faults

def _check_faults(repo: Repo, findings: list[Finding]) -> None:
    points, lineno = known_points(repo)
    doc = repo.text(DOC_ROBUST)
    if not points or doc is None:
        return
    rows = {k: v for k, v in _doc_rows(doc).items()
            if re.fullmatch(r"[a-z_]+\.[a-z_]+", k)}
    from .testcov import FAULTS_REL
    for pt in points:
        if pt not in rows:
            findings.append(Finding(
                check=NAME, path=FAULTS_REL, line=lineno,
                message=(f"fault point {pt!r} is in KNOWN_POINTS but has "
                         f"no row in {DOC_ROBUST}'s fault-point table"),
                hint=f"add a `{pt}` row (fires-from + blast radius)",
                key=f"{NAME}:faults:{pt}@undocumented"))
    for key, (line, _) in sorted(rows.items()):
        if key not in points:
            findings.append(Finding(
                check=NAME, path=DOC_ROBUST, line=line,
                message=(f"{DOC_ROBUST} documents fault point {key!r} "
                         f"which KNOWN_POINTS does not register"),
                hint="drop the row or register the point in utils/faults.py",
                key=f"{NAME}:faults:{key}@ghost"))


# ------------------------------------------------------------------ config

_UNIT_RE = re.compile(r"^(\d+(?:\.\d+)?)\s*([KMG])iB$")
_SUPERSCRIPTS = str.maketrans("⁰¹²³⁴⁵⁶⁷⁸⁹", "0123456789")


def _eval_const(node: ast.AST):
    """Constant value of a default expression; handles the repo's shift /
    power idioms (``1 << 30``, ``256 << 20``). None when not constant."""
    try:
        return ast.literal_eval(node)
    except (ValueError, TypeError, SyntaxError, MemoryError):
        pass
    if isinstance(node, ast.BinOp):
        left, right = _eval_const(node.left), _eval_const(node.right)
        if isinstance(left, (int, float)) and isinstance(right, (int, float)):
            if isinstance(node.op, ast.LShift):
                return int(left) << int(right)
            if isinstance(node.op, ast.Pow):
                return left ** right
            if isinstance(node.op, ast.Mult):
                return left * right
    return None


def _parse_doc_default(s: str):
    """The Default cell: numbers, GiB/MiB units, 2^n superscripts, quoted
    strings, tuples, None. Falls back to the raw string."""
    s = s.strip().strip("`")
    m = _UNIT_RE.match(s)
    if m:
        return float(m.group(1)) * (
            1 << {"K": 10, "M": 20, "G": 30}[m.group(2)])
    if any(c in "⁰¹²³⁴⁵⁶⁷⁸⁹" for c in s):
        base = s.rstrip("⁰¹²³⁴⁵⁶⁷⁸⁹")
        exp = s[len(base):].translate(_SUPERSCRIPTS)
        if base.isdigit() and exp.isdigit():
            return int(base) ** int(exp)
    try:
        return ast.literal_eval(s)
    except (ValueError, TypeError, SyntaxError, MemoryError):
        return s


def _norm_str(v) -> str:
    return re.sub(r"[\s'\"]", "", str(v)).lower()


def _defaults_match(code_val, code_src: str, doc_val) -> bool:
    if code_val is not None and not isinstance(code_val, str):
        if isinstance(code_val, (int, float)) and isinstance(
                doc_val, (int, float)) and not isinstance(
                code_val, bool) and not isinstance(doc_val, bool):
            return float(code_val) == float(doc_val)
        if isinstance(doc_val, str):
            return _norm_str(code_val) == _norm_str(doc_val)
        return code_val == doc_val
    if code_val is None and isinstance(doc_val, str) \
            and doc_val.strip() in {"None", "none"}:
        # unevaluable code default documented as None: can't compare
        return True
    a, b = _norm_str(code_val if code_val is not None else code_src), \
        _norm_str(doc_val)
    # "jnp.float32" documents as "float32"
    return a == b or a.endswith("." + b) or b.endswith("." + a)


def _config_fields(repo: Repo):
    """field -> (lineno, default AST|None, deprecated?) from the first
    dataclass in config.py, plus the SourceFile."""
    sf = repo.file(CONFIG_REL)
    if sf is None or sf.tree is None:
        return {}, None
    cls = next((n for n in ast.walk(sf.tree)
                if isinstance(n, ast.ClassDef) and "Config" in n.name), None)
    if cls is None:
        return {}, sf
    fields = {}
    for node in cls.body:
        if isinstance(node, ast.AnnAssign) and isinstance(node.target,
                                                          ast.Name):
            dep = False
            i = node.lineno - 1
            while i >= 1 and sf.lines[i - 1].strip().startswith("#"):
                if "DEPRECATED" in sf.lines[i - 1]:
                    dep = True
                i -= 1
            fields[node.target.id] = (node.lineno, node.value, dep)
    return fields, sf


def _check_config(repo: Repo, findings: list[Finding]) -> None:
    fields, sf = _config_fields(repo)
    if not fields or sf is None:
        return
    doc = repo.text(DOC_CONFIG)
    rows = _doc_rows(doc) if doc is not None else None

    if rows is not None:
        for name, (line, default, _) in sorted(fields.items()):
            if name not in rows:
                findings.append(Finding(
                    check=NAME, path=CONFIG_REL, line=line,
                    message=(f"config knob {name!r} has no row in "
                             f"{DOC_CONFIG}'s knob table"),
                    hint="document the knob (default + effect)",
                    key=f"{NAME}:config:{name}@undocumented"))
                continue
            doc_line, cells = rows[name]
            if default is None or not cells:
                continue
            doc_val = _parse_doc_default(cells[0])
            code_val = _eval_const(default)
            code_src = ast.unparse(default)
            if not _defaults_match(code_val, code_src, doc_val):
                findings.append(Finding(
                    check=NAME, path=DOC_CONFIG, line=doc_line,
                    message=(f"documented default for {name!r} "
                             f"({cells[0]!r}) != code default "
                             f"({code_src})"),
                    hint=f"sync the Default cell with {CONFIG_REL}",
                    key=f"{NAME}:config:{name}@default-drift"))
        for key, (line, _) in sorted(rows.items()):
            if re.fullmatch(r"[a-z][a-z0-9_]*", key) and key not in fields:
                findings.append(Finding(
                    check=NAME, path=DOC_CONFIG, line=line,
                    message=(f"{DOC_CONFIG} documents knob {key!r} which "
                             f"MarlinConfig does not define"),
                    hint="drop the row or add the field",
                    key=f"{NAME}:config:{key}@ghost"))

    # dead knob: a field no attribute read in the package ever names
    reads: set[str] = set()
    for src in repo.py_files():
        if src.rel == CONFIG_REL or src.tree is None:
            continue
        for node in ast.walk(src.tree):
            if isinstance(node, ast.Attribute) and isinstance(node.ctx,
                                                              ast.Load):
                reads.add(node.attr)
    for name, (line, _, deprecated) in sorted(fields.items()):
        if name in reads or deprecated:
            continue
        if sf.ignored(line, NAME):
            continue
        findings.append(Finding(
            check=NAME, path=CONFIG_REL, line=line,
            message=(f"config knob {name!r} is never read anywhere in the "
                     f"package — setting it changes nothing"),
            hint=("wire the knob up, or mark its comment DEPRECATED "
                  "(keeping parse-compat) and say what replaced it"),
            key=f"{NAME}:config:{name}@dead-knob"))

    # reads off get_config() that name no field
    for src in repo.py_files():
        if src.tree is None:
            continue
        for fn in ast.walk(src.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            cfg_names: set[str] = set()
            for node in ast.walk(fn):
                if isinstance(node, ast.Assign) \
                        and isinstance(node.value, ast.Call) \
                        and (dotted(node.value.func) or "").split(".")[-1] \
                        == "get_config":
                    for tgt in node.targets:
                        if isinstance(tgt, ast.Name):
                            cfg_names.add(tgt.id)
            for node in ast.walk(fn):
                if not (isinstance(node, ast.Attribute)
                        and isinstance(node.ctx, ast.Load)):
                    continue
                base = node.value
                is_cfg = (isinstance(base, ast.Call)
                          and (dotted(base.func) or "").split(".")[-1]
                          == "get_config") \
                    or (isinstance(base, ast.Name) and base.id in cfg_names)
                if is_cfg and node.attr not in fields \
                        and not node.attr.startswith("__") \
                        and not src.ignored(node.lineno, NAME):
                    findings.append(Finding(
                        check=NAME, path=src.rel, line=node.lineno,
                        message=(f"read of config attribute {node.attr!r} "
                                 f"which MarlinConfig does not define"),
                        hint="typo'd knob? set_config would reject it, but "
                             "a read raises only when reached",
                        key=(f"{NAME}:config:{node.attr}@unknown-read:"
                             f"{src.rel}:{fn.name}")))


# ----------------------------------------------------------------- metrics

def _registered_metrics(repo: Repo) -> dict[str, tuple[str, int]]:
    """name -> (rel, lineno) for every reg.counter/gauge/histogram family."""
    out: dict[str, tuple[str, int]] = {}
    for sf in repo.py_files():
        if sf.tree is None:
            continue
        for node in ast.walk(sf.tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in {"counter", "gauge", "histogram"}
                    and node.args):
                continue
            name = str_const(node.args[0])
            if name and name.startswith("marlin_") and name not in out:
                out[name] = (sf.rel, node.lineno)
    return out


def _check_metrics(repo: Repo, findings: list[Finding]) -> None:
    registered = _registered_metrics(repo)
    if not registered:
        return
    doc = repo.text(DOC_OBS)
    if doc is not None:
        rows = {k: v for k, v in _doc_rows(doc).items()
                if k.startswith("marlin_")}
        for name, (rel, line) in sorted(registered.items()):
            if name not in rows:
                findings.append(Finding(
                    check=NAME, path=rel, line=line,
                    message=(f"metric family {name!r} is registered but "
                             f"has no row in {DOC_OBS}'s metric table"),
                    hint="add the row (type, labels, source)",
                    key=f"{NAME}:metrics:{name}@undocumented"))
        for name, (line, _) in sorted(rows.items()):
            if name not in registered:
                findings.append(Finding(
                    check=NAME, path=DOC_OBS, line=line,
                    message=(f"{DOC_OBS} documents metric {name!r} which "
                             f"nothing registers"),
                    hint="drop the row or restore the family",
                    key=f"{NAME}:metrics:{name}@ghost"))


# ----------------------------------------------------------------- memory

_MEM_SECTION = "Memory attribution"


def _md_section(text: str, title: str) -> tuple[str | None, int]:
    """(section body, 0-based line offset) of the first markdown section
    whose heading contains ``title`` (case-insensitive), running to the
    next heading of the same or higher level; (None, 0) when absent."""
    lines = text.splitlines()
    start = level = None
    for i, ln in enumerate(lines):
        m = re.match(r"^(#+)\s+(.*)", ln)
        if not m:
            continue
        if start is None:
            if title.lower() in m.group(2).lower():
                start, level = i, len(m.group(1))
        elif len(m.group(1)) <= level:
            return "\n".join(lines[start:i]), start
    if start is not None:
        return "\n".join(lines[start:]), start
    return None, 0


def _known_components(repo: Repo) -> tuple[set | None, int]:
    """(KNOWN_COMPONENTS, lineno) parsed from obs/memledger.py."""
    sf = repo.file(MEMLEDGER_REL)
    if sf is None or sf.tree is None:
        return None, 0
    for node in ast.walk(sf.tree):
        if not isinstance(node, ast.Assign):
            continue
        for tgt in node.targets:
            if isinstance(tgt, ast.Name) \
                    and tgt.id == "KNOWN_COMPONENTS" \
                    and isinstance(node.value,
                                   (ast.Tuple, ast.List, ast.Set)):
                return ({el.value for el in node.value.elts
                         if isinstance(el, ast.Constant)
                         and isinstance(el.value, str)}, node.lineno)
    return None, 0


def _check_memory(repo: Repo, findings: list[Finding]) -> None:
    comps, lineno = _known_components(repo)
    if comps is None:
        return
    doc = repo.text(DOC_OBS)
    if doc is None:
        return
    sec, off = _md_section(doc, _MEM_SECTION)
    if sec is None:
        findings.append(Finding(
            check=NAME, path=DOC_OBS, line=1,
            message=(f"{DOC_OBS} has no {_MEM_SECTION!r} section but "
                     f"{MEMLEDGER_REL} defines KNOWN_COMPONENTS — the "
                     f"ledger's attribution vocabulary is undocumented"),
            hint="add the section with one row per ledger component",
            key=f"{NAME}:memory:section@missing"))
        return
    # component rows only: single lowercase slugs in the section's tables
    # (metric rows — marlin_mem_* — live in the metric table and are
    # cross-checked by the metrics sub-area)
    rows = {k: (line + off, cells)
            for k, (line, cells) in _doc_rows(sec).items()
            if re.fullmatch(r"[a-z][a-z0-9_]*", k)
            and not k.startswith("marlin_")}
    for comp in sorted(comps):
        if comp not in rows:
            findings.append(Finding(
                check=NAME, path=MEMLEDGER_REL, line=lineno,
                message=(f"ledger component {comp!r} is in "
                         f"KNOWN_COMPONENTS but has no row in {DOC_OBS}'s "
                         f"memory-attribution table"),
                hint=f"add a `{comp}` row (what registers it, lifetime)",
                key=f"{NAME}:memory:{comp}@undocumented"))
    for key, (line, _) in sorted(rows.items()):
        if key not in comps:
            findings.append(Finding(
                check=NAME, path=DOC_OBS, line=line,
                message=(f"{DOC_OBS} documents ledger component {key!r} "
                         f"which KNOWN_COMPONENTS does not define"),
                hint=(f"drop the row or add the component to "
                      f"KNOWN_COMPONENTS in {MEMLEDGER_REL}"),
                key=f"{NAME}:memory:{key}@ghost"))


# ------------------------------------------------------------------ events

def _known_sets(repo: Repo) -> tuple[set | None, set | None, int]:
    """(KNOWN_KINDS, KNOWN_SERVE_EVS, lineno) parsed from obs/report.py."""
    sf = repo.file(REPORT_REL)
    if sf is None or sf.tree is None:
        return None, None, 0
    kinds = evs = None
    line = 1
    for node in ast.walk(sf.tree):
        if not isinstance(node, ast.Assign):
            continue
        for tgt in node.targets:
            if not isinstance(tgt, ast.Name):
                continue
            val = node.value
            if isinstance(val, ast.Call) and val.args:
                val = val.args[0]
            if not isinstance(val, (ast.Set, ast.Tuple, ast.List)):
                continue
            items = {el.value for el in val.elts
                     if isinstance(el, ast.Constant)
                     and isinstance(el.value, str)}
            if tgt.id == "KNOWN_KINDS":
                kinds, line = items, node.lineno
            elif tgt.id == "KNOWN_SERVE_EVS":
                evs = items
    return kinds, evs, line


def _emitted_events(repo: Repo):
    """(kind -> first (rel, line), serve ev -> first (rel, line)) collected
    from the emission sites (AST literals only — docstrings don't count)."""
    kinds: dict[str, tuple[str, int]] = {}
    evs: dict[str, tuple[str, int]] = {}

    def note(d, name, sf, line):
        if name and name not in d:
            d[name] = (sf.rel, line)

    for sf in repo.py_files():
        if sf.tree is None or sf.rel == REPORT_REL:
            continue
        in_serving = "/serving/" in f"/{sf.rel}"
        for node in ast.walk(sf.tree):
            if isinstance(node, ast.Call):
                fn = dotted(node.func) or ""
                leaf = fn.split(".")[-1]
                recv = fn.rsplit(".", 1)[0].lower() if "." in fn else ""
                first = str_const(node.args[0]) if node.args else None
                if leaf in {"event", "timed"} and "log" in recv:
                    note(kinds, first, sf, node.lineno)
                    if first == "serve":
                        for kw in node.keywords:
                            if kw.arg == "ev":
                                note(evs, str_const(kw.value), sf,
                                     node.lineno)
                elif leaf in {"_log", "_log_event"}:
                    note(kinds, first, sf, node.lineno)
                elif leaf == "emit":
                    for kw in node.keywords:
                        if kw.arg == "kind":
                            note(kinds, str_const(kw.value), sf,
                                 node.lineno)
                elif leaf == "_emit" and in_serving:
                    for kw in node.keywords:
                        if kw.arg == "ev":
                            note(evs, str_const(kw.value), sf, node.lineno)
            elif isinstance(node, ast.Dict):
                keys = {str_const(k): v for k, v in zip(node.keys,
                                                        node.values)
                        if k is not None}
                if "kind" in keys and "t" in keys:
                    note(kinds, str_const(keys["kind"]), sf, node.lineno)
                if "ev" in keys and in_serving:
                    note(evs, str_const(keys["ev"]), sf, node.lineno)
    return kinds, evs


def _check_events(repo: Repo, findings: list[Finding]) -> None:
    known_kinds, known_evs, decl_line = _known_sets(repo)
    if known_kinds is None and known_evs is None:
        return
    kinds, evs = _emitted_events(repo)
    if known_kinds is not None:
        for kind, (rel, line) in sorted(kinds.items()):
            if kind not in known_kinds:
                findings.append(Finding(
                    check=NAME, path=rel, line=line,
                    message=(f"EventLog kind {kind!r} is emitted but "
                             f"missing from KNOWN_KINDS in {REPORT_REL} — "
                             f"obs.report has never heard of it"),
                    hint="add the kind to KNOWN_KINDS (and a report "
                         "section if generic per-kind latency isn't "
                         "enough)",
                    key=f"{NAME}:events:kind:{kind}@unknown"))
    if known_evs is not None:
        for ev, (rel, line) in sorted(evs.items()):
            if ev not in known_evs:
                findings.append(Finding(
                    check=NAME, path=rel, line=line,
                    message=(f"serve ev {ev!r} is emitted but missing "
                             f"from KNOWN_SERVE_EVS in {REPORT_REL}"),
                    hint="add it to KNOWN_SERVE_EVS (and teach the "
                         "serving section if it matters)",
                    key=f"{NAME}:events:ev:{ev}@unknown"))
        for ev in sorted(known_evs - set(evs)):
            findings.append(Finding(
                check=NAME, path=REPORT_REL, line=decl_line,
                message=(f"KNOWN_SERVE_EVS declares ev {ev!r} which no "
                         f"serving code emits"),
                hint="prune the stale entry or restore the emitter",
                key=f"{NAME}:events:ev:{ev}@stale"))


def run(repo: Repo) -> list[Finding]:
    findings: list[Finding] = []
    _check_faults(repo, findings)
    _check_config(repo, findings)
    _check_metrics(repo, findings)
    _check_memory(repo, findings)
    _check_events(repo, findings)
    return findings
