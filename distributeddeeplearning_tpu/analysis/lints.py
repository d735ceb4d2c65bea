"""Repo-invariant AST lints — conventions the repo already bled for.

Each rule here encodes a convention an earlier PR introduced for a
concrete failure mode, now checked mechanically so the next subsystem
cannot quietly regress it:

- ``sidecar-direct-write``: every ``.cache/*.json`` run sidecar goes
  through ``observability/sidecars.py`` (atomic rename, envelope with
  ``schema``/``written_at``, never-raise). A direct ``open``/``json.dump``
  is a torn-read and stale-data hazard the sidecar API exists to close.
- ``fsync-before-fire``: a function that kills its own process
  (``os.kill(os.getpid(), ...)`` — the faults.py chaos emitters) must
  have put a flight record / flush on disk first, or the post-mortem
  loses the one event that explains the death.
- ``unpaired-telemetry-span``: ``telemetry.span(...)`` returns a context
  manager; a call whose result is discarded times nothing and silently
  drops the phase from every trace.
- ``perf-record-provenance``: every serialized perf record (a dict with
  a ``"metric"`` key) carries a ``perf_report.annotate`` provenance stamp
  — PR 6's rule that perf claims are dated, attributed, and
  staleness-graded or they don't exist.
- ``page-table-log-before-dispatch``: a serve-engine function that
  stores into a KV ``page_table`` subscript and then launches a
  prefill/decode program must put a flight ``record(...)`` between the
  mutation and the dispatch — the page table is the map to pool state
  a crashed replica cannot otherwise reconstruct.
- ``cow-before-write``: a function that dispatches a KV page copy
  (a call whose name mentions ``page_copy``/``copy_page`` — the
  copy-on-write clone of a shared prefix page) must have flight-logged
  a ``record(...)`` first. The clone changes which physical page a
  slot's writes land in; a replica killed mid-copy with no record of
  it leaves a page table a post-mortem cannot trust.
- ``serve-span-registered``: every telemetry emission whose literal
  name starts with ``serve:`` (span / instant / record_span / flow /
  async begin+end) must use a name registered in
  ``serve/tracing.REGISTERED_PHASES``. The serve trace schema
  (docs/serve_tracing.md) is what tools/trace_report.py and the
  attribution tests key on — an unregistered name is a span the whole
  reporting stack silently ignores.
- ``master-weight-cast``: optimizer / master-weight state must stay
  float32 (ISSUE 20's silent-precision-loss bug class: a bf16 master
  drops every update below ~2^-8 of the weight magnitude and training
  quietly plateaus). Any cast of a value whose name mentions
  ``opt_state`` / ``master`` to a sub-fp32 dtype (``astype``, or a
  ``dtype=``-carrying array constructor) outside the sanctioned
  gather-path helpers in ``parallel/zero.py`` is flagged.
- ``axis-name-consistency``: string axis names at ``psum`` /
  ``psum_scatter`` / ``all_gather`` / ``pmean`` / ... call sites must be
  declared in ``parallel/mesh.py``'s ``MESH_AXES`` — a typo'd axis name
  is an obscure trace error at best and a wrong-group collective at
  worst. Module-level tuple constants (``DATA_AXES``-style) are resolved;
  dynamic values are out of static reach and skipped.

All rules are AST-only (no imports of the linted code, no jax) and are
tuned to zero false positives on this repo — the gate fails tier-1, and
a noisy gate gets baselined into uselessness.
"""
from __future__ import annotations

import ast
import os
from typing import Optional, Sequence

from distributeddeeplearning_tpu.analysis import (finding, iter_py_files,
                                                  repo_root)

# Files exempt from sidecar-direct-write: the sidecar implementation
# itself, and the doctor (read-only display of raw paths).
_SIDECAR_EXEMPT = ("observability/sidecars.py",)

_COLLECTIVE_CALLS = {"psum", "psum_scatter", "all_gather", "pmean",
                     "pmax", "pmin", "all_to_all", "ppermute",
                     "reduce_scatter"}


def _terminal_name(func: ast.expr) -> Optional[str]:
    if isinstance(func, ast.Attribute):
        return func.attr
    if isinstance(func, ast.Name):
        return func.id
    return None


def _const_str(node: ast.expr) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def _shallow_walk(fn: ast.AST):
    """Walk a function body WITHOUT descending into nested function
    definitions — those are visited as their own scope by the outer
    ``ast.walk`` over the module, and double-visiting them both
    duplicates findings and mixes scopes."""
    stack = list(ast.iter_child_nodes(fn))
    while stack:
        node = stack.pop()
        yield node
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda)):
            stack.extend(ast.iter_child_nodes(node))


# ---------------------------------------------------------------------------
# sidecar-direct-write
# ---------------------------------------------------------------------------

def check_sidecar_writes(tree: ast.Module, path: str) -> list[dict]:
    rel = os.path.relpath(os.path.abspath(path), repo_root())
    if rel.replace(os.sep, "/").endswith(_SIDECAR_EXEMPT):
        return []
    findings = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        name = _terminal_name(node.func)
        consts = [c for c in (_const_str(a) for a in node.args)
                  if c is not None]
        hit = None
        if name == "join" and ".cache" in consts and any(
                c.endswith(".json") for c in consts):
            hit = next(c for c in consts if c.endswith(".json"))
        elif name == "open" and node.args:
            c = _const_str(node.args[0])
            if c and ".cache/" in c.replace(os.sep, "/") \
                    and c.endswith(".json"):
                hit = c
        if hit:
            findings.append(finding(
                "lints", "sidecar-direct-write",
                f"direct .cache sidecar path {hit!r} — route through "
                f"observability/sidecars.py (path_for/write/read) for "
                f"atomic rename + schema/written_at envelope",
                file=path, line=node.lineno))
    return findings


# ---------------------------------------------------------------------------
# fsync-before-fire
# ---------------------------------------------------------------------------

def _is_self_kill(call: ast.Call) -> bool:
    if _terminal_name(call.func) != "kill" or not call.args:
        return False
    first = call.args[0]
    return (isinstance(first, ast.Call)
            and _terminal_name(first.func) == "getpid")


def check_fsync_before_fire(tree: ast.Module, path: str) -> list[dict]:
    findings = []
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        durable_line = None  # earliest record/fsync/flush
        kill_lines: list[int] = []
        for sub in _shallow_walk(node):
            if not isinstance(sub, ast.Call):
                continue
            name = _terminal_name(sub.func)
            if name in ("record", "fsync", "flush"):
                durable_line = (sub.lineno if durable_line is None
                                else min(durable_line, sub.lineno))
            elif _is_self_kill(sub):
                kill_lines.append(sub.lineno)
        for kill_line in sorted(kill_lines):
            if durable_line is None or durable_line > kill_line:
                findings.append(finding(
                    "lints", "fsync-before-fire",
                    f"{node.name}() kills its own process with no "
                    f"flight record / fsync / flush before the kill "
                    f"— the event that explains the death dies "
                    f"with the process",
                    file=path, line=kill_line))
    return findings


# ---------------------------------------------------------------------------
# unpaired-telemetry-span
# ---------------------------------------------------------------------------

def check_unpaired_spans(tree: ast.Module, path: str) -> list[dict]:
    findings = []
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Expr)
                and isinstance(node.value, ast.Call)
                and _terminal_name(node.value.func) == "span"):
            continue
        findings.append(finding(
            "lints", "unpaired-telemetry-span",
            "span(...) result discarded — it is a context manager; a "
            "span never entered times nothing and the phase vanishes "
            "from traces (use `with tele.span(...):`)",
            file=path, line=node.lineno))
    return findings


# ---------------------------------------------------------------------------
# perf-record-provenance
# ---------------------------------------------------------------------------

def _is_metric_dict(node: ast.expr) -> bool:
    return (isinstance(node, ast.Dict)
            and any(_const_str(k) == "metric"
                    for k in node.keys if k is not None))


def check_perf_record_provenance(tree: ast.Module, path: str) -> list[dict]:
    """``json.dump(s)`` of a perf record (dict with a ``"metric"`` key,
    literal or via a local name) must be stamped: either the dumps arg is
    an ``annotate(...)`` call, or ``annotate(<name>, ...)`` ran lexically
    earlier in the same function."""
    findings = []
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        metric_names: dict[str, int] = {}   # name -> assign line
        annotated: dict[str, int] = {}      # name -> annotate line
        dumps: list[ast.Call] = []
        for sub in _shallow_walk(node):
            if isinstance(sub, ast.Assign) and _is_metric_dict(sub.value):
                for t in sub.targets:
                    if isinstance(t, ast.Name):
                        metric_names[t.id] = sub.lineno
            elif isinstance(sub, ast.Call):
                name = _terminal_name(sub.func)
                if name == "annotate" and sub.args:
                    a = sub.args[0]
                    if isinstance(a, ast.Name):
                        annotated[a.id] = min(
                            annotated.get(a.id, sub.lineno), sub.lineno)
                elif name in ("dumps", "dump") and sub.args:
                    dumps.append(sub)
        for call in dumps:
            arg = call.args[0]
            if isinstance(arg, ast.Call) \
                    and _terminal_name(arg.func) == "annotate":
                continue
            bad = None
            if _is_metric_dict(arg):
                bad = "a literal perf record"
            elif isinstance(arg, ast.Name) and arg.id in metric_names:
                if arg.id in annotated \
                        and annotated[arg.id] < call.lineno:
                    continue
                bad = f"perf record {arg.id!r}"
            if bad:
                findings.append(finding(
                    "lints", "perf-record-provenance",
                    f"{bad} serialized without a perf_report.annotate "
                    f"provenance stamp — perf claims must carry "
                    f"fresh/stale grading, git rev, and attempt "
                    f"history (PR 6 rule)",
                    file=path, line=call.lineno))
    return findings


# ---------------------------------------------------------------------------
# page-table-log-before-dispatch
# ---------------------------------------------------------------------------

_PAGE_TABLE_NAMES = ("_page_table", "page_table")


def check_page_table_log_before_dispatch(tree: ast.Module,
                                         path: str) -> list[dict]:
    """A serve-engine page-table mutation must hit the flight record
    before the step that consumes it dispatches.

    The page table is the one piece of engine state a post-mortem cannot
    reconstruct after a crash (pool contents die with the process, the
    table is the map to them). The serve-chaos PR's convention: any
    function that stores into a ``page_table``/``_page_table`` subscript
    and then launches a prefill/decode program must ``record(...)``
    between the mutation and the dispatch — otherwise a replica killed
    inside that program leaves a flight record that never mentions the
    mutation the dying step was built on."""
    findings = []
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        stores: list[int] = []
        records: list[int] = []
        dispatches: list[int] = []
        for sub in _shallow_walk(node):
            if isinstance(sub, (ast.Assign, ast.AugAssign)):
                targets = (sub.targets if isinstance(sub, ast.Assign)
                           else [sub.target])
                for t in targets:
                    if isinstance(t, ast.Subscript) \
                            and _terminal_name(t.value) \
                            in _PAGE_TABLE_NAMES:
                        stores.append(sub.lineno)
            elif isinstance(sub, ast.Call):
                name = _terminal_name(sub.func)
                if name == "record":
                    records.append(sub.lineno)
                elif name is not None and ("prefill" in name.lower()
                                           or "decode" in name.lower()):
                    dispatches.append(sub.lineno)
        for d in sorted(dispatches):
            prior = [s for s in stores if s < d]
            if not prior:
                continue
            if not any(min(prior) <= r < d for r in records):
                findings.append(finding(
                    "lints", "page-table-log-before-dispatch",
                    f"{node.name}() mutates the KV page table (line "
                    f"{max(prior)}) and dispatches a prefill/decode "
                    f"program (line {d}) with no flight record in "
                    f"between — a replica killed inside that program "
                    f"leaves no durable trace of the mapping the dying "
                    f"step was built on",
                    file=path, line=d))
                break  # one finding per function tells the story
    return findings


# ---------------------------------------------------------------------------
# cow-before-write
# ---------------------------------------------------------------------------

def check_cow_before_write(tree: ast.Module, path: str) -> list[dict]:
    """A copy-on-write page clone must be flight-logged before it
    dispatches — same record-then-dispatch discipline as
    ``page-table-log-before-dispatch``, applied to the COW copy.

    The clone rewires a slot's page mapping (its writes start landing in
    the private copy instead of the shared prefix page); a replica
    SIGKILLed inside the copy with no record of it leaves a flight log
    that still describes the OLD mapping. Any call whose terminal name
    mentions ``page_copy``/``copy_page`` counts as the dispatch; a
    ``record(...)`` lexically earlier in the same function satisfies the
    rule."""
    findings = []
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        record_line = None
        copies: list[int] = []
        for sub in _shallow_walk(node):
            if not isinstance(sub, ast.Call):
                continue
            name = _terminal_name(sub.func)
            if name == "record":
                record_line = (sub.lineno if record_line is None
                               else min(record_line, sub.lineno))
            elif name is not None and ("page_copy" in name.lower()
                                       or "copy_page" in name.lower()):
                copies.append(sub.lineno)
        for c in sorted(copies):
            if record_line is None or record_line > c:
                findings.append(finding(
                    "lints", "cow-before-write",
                    f"{node.name}() dispatches a KV page copy (line {c}) "
                    f"with no flight record before it — a replica killed "
                    f"mid-copy leaves a log that still describes the old "
                    f"page mapping (copy-on-write must be logged before "
                    f"it rewires the table)",
                    file=path, line=c))
                break  # one finding per function tells the story
    return findings


# ---------------------------------------------------------------------------
# axis-name-consistency
# ---------------------------------------------------------------------------

def declared_mesh_axes(mesh_path: Optional[str] = None) -> Optional[set]:
    """``MESH_AXES`` from parallel/mesh.py, by AST (no import)."""
    mesh_path = mesh_path or os.path.join(
        repo_root(), "distributeddeeplearning_tpu", "parallel", "mesh.py")
    try:
        tree = ast.parse(open(mesh_path, encoding="utf-8").read())
    except (OSError, SyntaxError):
        return None
    for node in ast.walk(tree):
        targets = []
        if isinstance(node, ast.Assign):
            targets, value = node.targets, node.value
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            targets, value = [node.target], node.value
        else:
            continue
        if not any(isinstance(t, ast.Name) and t.id == "MESH_AXES"
                   for t in targets):
            continue
        if isinstance(value, (ast.Tuple, ast.List)):
            axes = {_const_str(e) for e in value.elts}
            if None not in axes:
                return axes
    return None


def _module_tuple_consts(tree: ast.Module) -> dict[str, tuple[str, ...]]:
    """Module-level ``NAME = ("a", "b")`` string-tuple constants —
    resolvable axis aliases like steps.py's ``DATA_AXES``."""
    out: dict[str, tuple[str, ...]] = {}
    for node in tree.body:
        if isinstance(node, ast.Assign) \
                and isinstance(node.value, (ast.Tuple, ast.List)):
            vals = tuple(_const_str(e) for e in node.value.elts)
            if vals and None not in vals:
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        out[t.id] = vals
    return out


def check_axis_names(tree: ast.Module, path: str,
                     mesh_axes: Optional[set] = None) -> list[dict]:
    if mesh_axes is None:
        mesh_axes = declared_mesh_axes()
    if not mesh_axes:
        return []  # mesh.py unreadable: tolerate, never guess
    aliases = _module_tuple_consts(tree)
    findings = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        if _terminal_name(node.func) not in _COLLECTIVE_CALLS:
            continue
        axis_arg = None
        if len(node.args) >= 2:
            axis_arg = node.args[1]
        for kw in node.keywords:
            if kw.arg in ("axis_name", "axes", "axis_names"):
                axis_arg = kw.value
        if axis_arg is None:
            continue
        names: list[str] = []
        if _const_str(axis_arg) is not None:
            names = [_const_str(axis_arg)]
        elif isinstance(axis_arg, (ast.Tuple, ast.List)):
            vals = [_const_str(e) for e in axis_arg.elts]
            if None in vals:
                continue  # dynamic element: out of static reach
            names = vals
        elif isinstance(axis_arg, ast.Name) and axis_arg.id in aliases:
            names = list(aliases[axis_arg.id])
        for name in names:
            if name not in mesh_axes:
                findings.append(finding(
                    "lints", "axis-name-consistency",
                    f"axis {name!r} at this "
                    f"{_terminal_name(node.func)}() call is not "
                    f"declared in parallel/mesh.py MESH_AXES "
                    f"{sorted(mesh_axes)} — a typo'd axis is a "
                    f"wrong-group collective",
                    file=path, line=node.lineno))
    return findings


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

# ---------------------------------------------------------------------------
# serve-span-registered
# ---------------------------------------------------------------------------

_SERVE_EMITTERS = {"span", "instant", "record_span", "flow",
                   "async_begin", "async_end"}


def check_serve_span_registry(tree: ast.Module, path: str) -> list[dict]:
    """Every literal ``serve:*`` name at a telemetry emission site must
    be registered in ``serve/tracing.REGISTERED_PHASES`` — the schema
    the serve trace tooling (trace_report, attribution tests, docs) keys
    on. tracing.py is pure stdlib, so importing the registry here keeps
    the lint and the runtime schema one source of truth."""
    from distributeddeeplearning_tpu.serve.tracing import REGISTERED_PHASES

    findings = []
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and _terminal_name(node.func) in _SERVE_EMITTERS
                and node.args):
            continue
        name = _const_str(node.args[0])
        if name is None or not name.startswith("serve:"):
            continue
        if name not in REGISTERED_PHASES:
            findings.append(finding(
                "lints", "serve-span-registered",
                f"serve trace name {name!r} is not in "
                f"serve/tracing.REGISTERED_PHASES — register it (and "
                f"document it in docs/serve_tracing.md) or the serve "
                f"reporting stack silently ignores this event",
                file=path, line=node.lineno))
    return findings


# ---------------------------------------------------------------------------
# master-weight-cast
# ---------------------------------------------------------------------------

# Identifier fragments that mark a value as optimizer / master-weight
# state. Deliberately narrow (no "mu"/"nu"): the gate fails tier-1 and a
# noisy rule gets baselined into uselessness.
_MASTER_STATE_MARKERS = ("opt_state", "master")
# Sub-fp32 dtypes a master must never land in. fp32 and wider are fine;
# integer casts are shape bookkeeping, not precision loss.
_SUB_FP32_DTYPES = {"bfloat16", "float16", "bf16", "f16", "half"}
# The sanctioned policy helpers: parallel/zero.py's gather path casts
# *gathered params* to the policy's compute dtype on the wire (the
# sharded fp32 masters themselves are never rewritten — _scatter_members
# restores plan dtypes). A new helper that legitimately moves values out
# of fp32 is added here in the same diff that introduces it.
_MASTER_CAST_SANCTIONED = {"_gather_members", "all_gather_chunks",
                           "gather_params_overlapped"}
# Array constructors whose dtype= keyword retypes their first argument.
_DTYPE_KW_CONSTRUCTORS = {"asarray", "array", "full_like", "zeros_like",
                          "ones_like", "empty_like"}


def _dtype_token(node: ast.expr) -> Optional[str]:
    """The dtype a cast targets, as a lowercase token: 'bfloat16' from
    ``jnp.bfloat16`` / ``"bfloat16"`` / ``np.float16``; None when the
    dtype is not a statically readable literal."""
    s = _const_str(node)
    if s is not None:
        return s.lower()
    if isinstance(node, (ast.Attribute, ast.Name)):
        name = _terminal_name(node)
        return name.lower() if name else None
    return None


def _mentions_master_state(node: ast.expr) -> bool:
    for sub in ast.walk(node):
        ident = None
        if isinstance(sub, ast.Name):
            ident = sub.id
        elif isinstance(sub, ast.Attribute):
            ident = sub.attr
        if ident and any(m in ident.lower()
                         for m in _MASTER_STATE_MARKERS):
            return True
    return False


def _master_casts_in_scope(scope: ast.AST, path: str) -> list[dict]:
    out = []
    for node in _shallow_walk(scope):
        if not isinstance(node, ast.Call):
            continue
        name = _terminal_name(node.func)
        tok = target = None
        if (name == "astype" and isinstance(node.func, ast.Attribute)
                and node.args):
            tok = _dtype_token(node.args[0])
            target = node.func.value
        elif name in _DTYPE_KW_CONSTRUCTORS and node.args:
            kw = next((k for k in node.keywords if k.arg == "dtype"), None)
            if kw is not None:
                tok = _dtype_token(kw.value)
                target = node.args[0]
        if (tok in _SUB_FP32_DTYPES and target is not None
                and _mentions_master_state(target)):
            out.append(finding(
                "lints", "master-weight-cast",
                f"optimizer/master state cast to {tok} — master weights "
                f"and optimizer state stay float32 (a bf16 master drops "
                f"every update below ~2^-8 of the weight magnitude; "
                f"docs/mixed_precision.md). Wire-compression belongs in "
                f"the sanctioned parallel/zero.py gather helpers",
                file=path, line=node.lineno))
    return out


def check_master_weight_cast(tree: ast.Module, path: str) -> list[dict]:
    """Flag sub-fp32 casts of optimizer / master-weight state outside the
    sanctioned policy helpers. Scope-aware: each function body is
    scanned once (via ``_shallow_walk``), and bodies of helpers in
    :data:`_MASTER_CAST_SANCTIONED` are skipped entirely."""
    findings = _master_casts_in_scope(tree, path)
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if node.name in _MASTER_CAST_SANCTIONED:
            continue
        findings.extend(_master_casts_in_scope(node, path))
    return findings


_CHECKS = (check_sidecar_writes, check_fsync_before_fire,
           check_unpaired_spans, check_perf_record_provenance,
           check_page_table_log_before_dispatch, check_cow_before_write,
           check_serve_span_registry, check_master_weight_cast)


def analyze_source(src: str, path: str = "<memory>", *,
                   mesh_axes: Optional[set] = None) -> list[dict]:
    try:
        tree = ast.parse(src)
    except SyntaxError as exc:
        return [finding("lints", "unparseable", f"cannot parse: {exc}",
                        file=path, line=exc.lineno)]
    findings: list[dict] = []
    for check in _CHECKS:
        findings.extend(check(tree, path))
    findings.extend(check_axis_names(tree, path, mesh_axes))
    return findings


def analyze_file(path: str, *, mesh_axes: Optional[set] = None
                 ) -> list[dict]:
    try:
        with open(path, encoding="utf-8") as fh:
            src = fh.read()
    except OSError as exc:
        return [finding("lints", "unparseable", f"cannot read: {exc}",
                        file=path)]
    return analyze_source(src, path, mesh_axes=mesh_axes)


def analyze_paths(roots: Sequence[str]) -> list[dict]:
    mesh_axes = declared_mesh_axes()
    findings: list[dict] = []
    for path in iter_py_files(roots):
        findings.extend(analyze_file(path, mesh_axes=mesh_axes))
    return findings
