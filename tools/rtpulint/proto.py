"""rtpuproto: whole-program distributed-protocol contract analyzer.

rtpulint's per-function rules (RTPU001-007) catch concurrency mistakes a
single frame can prove. This pass gives the analyzer whole-program eyes:
it parses the entire ``ray_tpu`` package ONCE (plus ``tests/`` and
``benchmarks/`` as auxiliary evidence), extracts the distributed-protocol
facts that today live only in hand-maintained strings and dicts, and
cross-checks them:

- the RPC surface: every handler-table registration (``{"method":
  self.handler}`` dicts bound to a ``*handler*`` context) against every
  call site (``client.call/call_async/notify/notify_async/notify_nowait``
  and string-carrying wrappers like ``_notify_worker``);
- the failure-semantics registry (``IDEMPOTENT_METHODS`` /
  ``UNBOUNDED_METHODS`` / ``NON_IDEMPOTENT_METHODS`` in runtime/rpc.py);
- the fault-plane grammar: ``SYNCPOINTS`` vs planted
  ``faults.syncpoint(...)`` sites (both AST-parsed from the package, so
  a new plane's syncpoint — e.g. PR 13's ``serve.admission``, PR 15's
  ``controller.persist`` planted mid journal-append in
  runtime/storage.py — must land in runtime/faults.py's tuple AND as a
  planted call in the same commit, or RTPU104 flags the half that is
  missing), and every fault-rule string (``RTPU_FAULTS`` specs in
  source, tests and benchmarks) vs the methods and syncpoints that
  actually exist;
- ``RuntimeConfig`` fields vs ``get_config().X`` reads;
- ``rtpu_*`` metric declarations (name/type/label-set consistency).

Rules (same pragma/severity/JSON machinery as RTPU001-007):

RTPU101  an RPC call site names a method no server registers (a typo is
         a silent 60s timeout under the default deadlines) — and,
         inversely, a registered handler nothing ever calls.
RTPU102  a call site passes a kwarg no handler of that method accepts
         (the server answers with a TypeError-shaped RemoteHandlerError
         at runtime; the analyzer answers at review time).
RTPU103  an RPC method in no deliberate failure class: every method must
         be in exactly one of IDEMPOTENT_METHODS / UNBOUNDED_METHODS /
         NON_IDEMPOTENT_METHODS, so adding an RPC forces the
         retry-semantics decision that PR 10's ``actor_died``
         double-restart was paid to teach. Stale entries (classifying a
         method that no longer exists) are flagged too.
RTPU104  a fault rule or kill_at syncpoint referencing a method or
         syncpoint that doesn't exist — a chaos drill that can never
         fire is a drill that silently stopped drilling. Also: a
         documented SYNCPOINTS entry nothing plants, and a planted
         syncpoint the documented set omits.
RTPU105  ``get_config().X`` where ``X`` is not a RuntimeConfig field
         (an AttributeError at runtime, on whatever rare path reads it),
         and dead knobs no package code reads.
RTPU106  ``rtpu_*`` metric hygiene: counters must end ``_total``,
         non-counters must not, and one name must keep one (type,
         label-set) across every declaration site.

This module is IMPORT-FREE with respect to ray_tpu: it never imports the
package it analyzes (pure ``ast`` + ``re``), so the tier-1 gate can run
it in a subprocess that forbids ray_tpu imports and collection stays
hermetic. The fault-rule grammar is therefore mirrored here (see
``_parse_fault_spec``) rather than imported from runtime/faults.py — the
fixture tests pin both sides so they cannot drift silently.
"""

from __future__ import annotations

import ast
import os
import re
from typing import Dict, List, Optional, Set, Tuple

from .analyzer import (Finding, _parse_pragmas, iter_python_files)

# attribute names that ARE the RPC send surface: first positional arg is
# the method name, keywords are the handler kwargs
_DIRECT_CALL_ATTRS = {"call", "call_async", "notify", "notify_async",
                      "notify_nowait", "request"}
# kwargs consumed by the transport itself, never forwarded to handlers
_TRANSPORT_KWARGS = {"_timeout", "_retry"}
# wrapper-call exclusions: loop APIs and stdlib that happen to contain
# "call" but never carry an RPC method name
_WRAPPER_BLACKLIST = {
    "call_soon", "call_soon_threadsafe", "call_later", "call_at",
    "call_exception_handler", "run_coroutine_threadsafe", "callable",
    "check_call", "__call__",
}
_METHOD_NAME_RE = re.compile(r"^[a-z_][a-z0-9_]*$")
_CLASS_SET_NAMES = ("IDEMPOTENT_METHODS", "UNBOUNDED_METHODS",
                    "NON_IDEMPOTENT_METHODS")
_FAULT_HEAD_RE = re.compile(
    r"(?:^|;)\s*(?:[\w.-]+\s*:)?\s*(drop|delay|error|partition|kill_at)\(")
_SYNCPOINT_STR_RE = re.compile(r"syncpoint\(\s*['\"]([\w.*-]+)['\"]")
_METRIC_CTORS = {"Counter": "counter", "Gauge": "gauge",
                 "Histogram": "histogram"}


def _unparse(node: ast.AST) -> str:
    try:
        return ast.unparse(node)
    except Exception:  # pragma: no cover - cosmetic
        return "<expr>"


# --------------------------------------------------------------- fault spec
class _FaultRuleRef:
    __slots__ = ("kind", "method", "syncpoint")

    def __init__(self, kind: str, method: str = "", syncpoint: str = ""):
        self.kind = kind
        self.method = method
        self.syncpoint = syncpoint


def _parse_fault_spec(spec: str) -> Optional[List[_FaultRuleRef]]:
    """Parse a ';'-separated fault spec under a mirror of the
    runtime/faults.py grammar. Returns None unless EVERY segment parses —
    a string that fails the real parser is not a fault spec (or is a
    deliberately-invalid grammar-test string) and must not be validated.
    '*' stands in for f-string placeholders and matches anything."""
    rules: List[_FaultRuleRef] = []
    for part in (spec or "").split(";"):
        part = part.strip()
        if not part:
            continue
        head, sep, rest = part.partition("(")
        if not sep:
            return None
        if ":" in head:
            _, _, head = head.rpartition(":")
        kind = head.strip()
        if kind not in ("drop", "delay", "error", "partition", "kill_at"):
            return None
        body, sep, tail = rest.rpartition(")")
        if not sep:
            return None
        tail = tail.strip()
        if tail and not tail.startswith("@"):
            return None
        subject = ""
        kw: Dict[str, str] = {}
        for i, seg in enumerate(s.strip() for s in body.split(",")
                                if s.strip()):
            if "=" in seg:
                k, _, v = seg.partition("=")
                kw[k.strip()] = v.strip()
            elif i == 0:
                subject = seg
            else:
                return None
        for numeric in ("nth", "times"):
            v = kw.get(numeric)
            if v is not None and v != "*" and not _is_int(v):
                return None
        for numeric in ("p", "ms"):
            v = kw.get(numeric)
            if v is not None and v != "*" and not _is_float(v):
                return None
        if kind == "partition":
            src, sep, dst = subject.partition("->")
            if not sep or not src.strip() or not dst.strip():
                return None
            rules.append(_FaultRuleRef(kind))
            continue
        if kind == "kill_at":
            if not subject or kw.get("action", "exit") not in ("exit",
                                                              "raise"):
                return None
            rules.append(_FaultRuleRef(kind, syncpoint=subject))
            continue
        if not subject:
            return None
        if kind == "delay" and kw.get("ms") is None:
            return None
        rules.append(_FaultRuleRef(kind, method=subject))
    return rules or None


def _is_int(v: str) -> bool:
    try:
        int(v)
        return True
    except ValueError:
        return False


def _is_float(v: str) -> bool:
    try:
        float(v)
        return True
    except ValueError:
        return False


# ------------------------------------------------------------- file facts
class _HandlerReg:
    __slots__ = ("method", "path", "line", "params", "has_var_kw",
                 "resolved")

    def __init__(self, method, path, line, params=None, has_var_kw=False,
                 resolved=False):
        self.method = method
        self.path = path
        self.line = line
        self.params: Set[str] = params or set()
        self.has_var_kw = has_var_kw
        self.resolved = resolved


class _CallRef:
    __slots__ = ("method", "path", "line", "kwargs", "checkable")

    def __init__(self, method, path, line, kwargs=None, checkable=False):
        self.method = method
        self.path = path
        self.line = line
        self.kwargs: Optional[Set[str]] = kwargs
        self.checkable = checkable  # direct site with a closed kwarg set


class _FileFacts:
    def __init__(self, path: str, in_package: bool):
        self.path = path
        self.in_package = in_package
        self.handlers: List[_HandlerReg] = []
        self.calls: List[_CallRef] = []
        # method-name-shaped strings OUTSIDE registration/classification
        # positions: weak liveness evidence for the dead-handler check
        # (`meth = "drain_exit" if drain else "kill_self"` is a real
        # caller even though no Call node carries the literal)
        self.string_mentions: Set[str] = set()
        # name -> (entries [(value, line)], assign line)
        self.class_sets: Dict[str, Tuple[List[Tuple[str, int]], int]] = {}
        self.syncpoints_decl: List[Tuple[str, int]] = []
        self.syncpoint_plants: List[Tuple[str, int]] = []
        self.fault_specs: List[Tuple[List[_FaultRuleRef], int]] = []
        self.config_fields: List[Tuple[str, int]] = []
        self.config_reads: List[Tuple[str, int, bool]] = []  # strict?
        self.metric_decls: List[Tuple[str, str, Optional[Tuple], int]] = []
        self.pragmas: Dict[int, Tuple[Set[str], str]] = {}


def _callable_ish(node: ast.AST) -> bool:
    return isinstance(node, (ast.Name, ast.Attribute, ast.Lambda))


def _dict_is_handler_shaped(node: ast.Dict) -> bool:
    if not node.keys:
        return False
    return all(isinstance(k, ast.Constant) and isinstance(k.value, str)
               for k in node.keys) and \
        all(_callable_ish(v) for v in node.values)


class _FileScanner:
    """One pass over one module: extraction only, no cross-file checks."""

    def __init__(self, path: str, source: str, tree: ast.Module,
                 in_package: bool):
        self.path = path
        self.source = source
        self.tree = tree
        self.facts = _FileFacts(path, in_package)
        # every node once, in `ast.walk`'s order: the passes below and the
        # scans read this list instead of walking the tree again (the walk
        # is most of the pass's time over 230 files)
        self.nodes: List[ast.AST] = list(ast.walk(tree))
        self.parents: Dict[ast.AST, ast.AST] = {}
        for node in self.nodes:
            for child in ast.iter_child_nodes(node):
                self.parents[child] = node
        # every def in the file by name (for handler-signature and
        # handler-dict-argument resolution)
        self.func_defs: Dict[str, List[ast.AST]] = {}
        self.docstring_nodes: Set[ast.AST] = set()
        for node in self.nodes:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.func_defs.setdefault(node.name, []).append(node)
            if isinstance(node, (ast.Module, ast.ClassDef, ast.FunctionDef,
                                 ast.AsyncFunctionDef)):
                body = getattr(node, "body", [])
                if body and isinstance(body[0], ast.Expr) and \
                        isinstance(body[0].value, ast.Constant) and \
                        isinstance(body[0].value.value, str):
                    self.docstring_nodes.add(body[0].value)
        # `get_config` is only the RUNTIME config accessor when this file
        # defines it or imports it from a *config module — serve/llm code
        # imports an unrelated model-config get_config from models.llama
        self.runtime_config_file = "get_config" in self.func_defs
        for node in self.nodes:
            if isinstance(node, ast.ImportFrom) and node.module and \
                    node.module.split(".")[-1] == "config" and \
                    any(a.name == "get_config" for a in node.names):
                self.runtime_config_file = True
        # local zero-arg helpers that just return the config singleton
        # (`def _cfg(): return get_config()`) count as config calls too
        self.config_helpers: Set[str] = set()
        if self.runtime_config_file:
            for name, defs in self.func_defs.items():
                for fn in defs:
                    for stmt in fn.body:
                        if isinstance(stmt, ast.Return) and \
                                isinstance(stmt.value, ast.Call) and \
                                _unparse(stmt.value.func).endswith(
                                    "get_config"):
                            self.config_helpers.add(name)

    # ----------------------------------------------------------- helpers
    def _enclosing_func(self, node: ast.AST):
        cur = self.parents.get(node)
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return cur
            cur = self.parents.get(cur)
        return None

    def _handler_context(self, d: ast.Dict) -> bool:
        """Is this string->callable dict bound to a handler table?"""
        parent = self.parents.get(d)
        # returned (possibly via a temp) from a *handler*-named function
        if isinstance(parent, ast.Return):
            fn = self._enclosing_func(d)
            if fn is not None and "handler" in fn.name.lower():
                return True
        if isinstance(parent, (ast.Assign, ast.AnnAssign)):
            targets = parent.targets if isinstance(parent, ast.Assign) \
                else [parent.target]
            for t in targets:
                if "handler" in _unparse(t).lower():
                    return True
            # `handlers = {...}` later returned from a *handler* func
            fn = self._enclosing_func(d)
            if fn is not None and "handler" in fn.name.lower():
                return True
        if isinstance(parent, ast.keyword) and parent.arg and \
                "handler" in parent.arg.lower():
            return True
        if isinstance(parent, ast.Call):
            fname = _unparse(parent.func)
            if fname.endswith("RpcServer"):
                return True
            if isinstance(parent.func, ast.Attribute) and \
                    parent.func.attr == "update" and \
                    "handler" in _unparse(parent.func.value).lower():
                return True
            # positional arg of a locally-defined function whose
            # matching parameter is named *handler* (test harnesses:
            # `_socket_pair(tmp_path, {...})`)
            callee = parent.func.id if isinstance(parent.func, ast.Name) \
                else None
            if callee and callee in self.func_defs and d in parent.args:
                idx = parent.args.index(d)
                for fn in self.func_defs[callee]:
                    params = [a.arg for a in fn.args.args]
                    if idx < len(params) and \
                            "handler" in params[idx].lower():
                        return True
        return False

    def _resolve_handler_value(self, value: ast.AST):
        """(params, has_var_kw, resolved) for a handler dict value."""
        target_name = None
        if isinstance(value, ast.Lambda):
            return self._sig_of_args(value.args, method_like=False) + (True,)
        if isinstance(value, ast.Attribute):
            target_name = value.attr
        elif isinstance(value, ast.Name):
            target_name = value.id
        if target_name:
            for fn in self.func_defs.get(target_name, ()):
                params, var_kw = self._sig_of_args(
                    fn.args,
                    method_like=isinstance(self.parents.get(fn),
                                           ast.ClassDef))
                return params, var_kw, True
        return set(), False, False

    @staticmethod
    def _sig_of_args(args: ast.arguments, method_like: bool):
        names = [a.arg for a in (args.posonlyargs + args.args)]
        if method_like and names and names[0] in ("self", "cls"):
            names = names[1:]
        names += [a.arg for a in args.kwonlyargs]
        params = {n for n in names if n != "_conn"}
        return params, args.kwarg is not None

    # -------------------------------------------------------------- scan
    def scan(self) -> _FileFacts:
        self.facts.pragmas = _parse_pragmas(self.source, self.path, [])
        for node in self.nodes:
            if isinstance(node, ast.Dict):
                self._scan_dict(node)
            elif isinstance(node, ast.Call):
                self._scan_call(node)
            elif isinstance(node, ast.Assign):
                self._scan_assign(node)
            elif isinstance(node, ast.ClassDef) and \
                    node.name == "RuntimeConfig":
                self._scan_config_class(node)
            elif isinstance(node, ast.Attribute):
                self._scan_attribute_read(node)
            elif isinstance(node, ast.Constant) and \
                    isinstance(node.value, str):
                if isinstance(self.parents.get(node),
                              (ast.JoinedStr, ast.FormattedValue)):
                    continue  # scanned once, as the flattened f-string
                self._note_string_mention(node)
                self._scan_string(node, node.value)
            elif isinstance(node, ast.JoinedStr):
                self._scan_string(node, self._flatten_fstring(node))
        self._scan_subscript_regs()
        self._scan_config_aliases()
        return self.facts

    @staticmethod
    def _flatten_fstring(node: ast.JoinedStr) -> str:
        parts = []
        for v in node.values:
            if isinstance(v, ast.Constant) and isinstance(v.value, str):
                parts.append(v.value)
            else:
                parts.append("*")
        return "".join(parts)

    def _scan_dict(self, node: ast.Dict):
        if not _dict_is_handler_shaped(node):
            return
        if not self._handler_context(node):
            return
        for k, v in zip(node.keys, node.values):
            params, var_kw, resolved = self._resolve_handler_value(v)
            self.facts.handlers.append(_HandlerReg(
                k.value, self.path, k.lineno, params, var_kw, resolved))

    def _scan_subscript_regs(self):
        # handlers["method"] = fn
        for node in self.nodes:
            if not isinstance(node, ast.Assign) or len(node.targets) != 1:
                continue
            t = node.targets[0]
            if isinstance(t, ast.Subscript) and \
                    "handler" in _unparse(t.value).lower() and \
                    isinstance(t.slice, ast.Constant) and \
                    isinstance(t.slice.value, str) and \
                    _callable_ish(node.value):
                params, var_kw, resolved = \
                    self._resolve_handler_value(node.value)
                self.facts.handlers.append(_HandlerReg(
                    t.slice.value, self.path, t.lineno, params, var_kw,
                    resolved))

    def _scan_call(self, node: ast.Call):
        func = node.func
        base = func.attr if isinstance(func, ast.Attribute) else (
            func.id if isinstance(func, ast.Name) else "")
        if not base:
            return
        # syncpoint plants
        if base == "syncpoint" and node.args and \
                isinstance(node.args[0], ast.Constant) and \
                isinstance(node.args[0].value, str):
            self.facts.syncpoint_plants.append(
                (node.args[0].value, node.lineno))
            return
        # metric declarations
        mtype = _METRIC_CTORS.get(base)
        if mtype and node.args and isinstance(node.args[0], ast.Constant) \
                and isinstance(node.args[0].value, str) \
                and node.args[0].value.startswith("rtpu_"):
            self.facts.metric_decls.append(
                (node.args[0].value, mtype, self._metric_tags(node),
                 node.lineno))
            return
        # RPC send surface
        if isinstance(func, ast.Attribute) and base in _DIRECT_CALL_ATTRS:
            recv = _unparse(func.value)
            if recv.split(".")[0] in ("subprocess", "os"):
                return
            if node.args and isinstance(node.args[0], ast.Constant) and \
                    isinstance(node.args[0].value, str) and \
                    _METHOD_NAME_RE.match(node.args[0].value):
                kwargs = {kw.arg for kw in node.keywords if kw.arg}
                closed = not any(kw.arg is None for kw in node.keywords)
                self.facts.calls.append(_CallRef(
                    node.args[0].value, self.path, node.lineno,
                    kwargs - _TRANSPORT_KWARGS, checkable=closed))
            return
        # wrapper surface: a *call*/*notify*-named METHOD carrying the
        # RPC name as an early string arg (`self._notify_worker(ws,
        # "execute_task", ...)`, client.py's `self._call("c_export")`);
        # liveness/typo evidence only — the wrapper owns the kwarg
        # plumbing, so no RTPU102 here. Attribute receivers only: bare
        # module-level helpers named *call* (util/collective.py's
        # `_call(group, "barrier", ...)` actor bridge) are not RPC
        if isinstance(func, ast.Attribute) and \
                ("call" in base or "notify" in base) and \
                base not in _DIRECT_CALL_ATTRS and \
                base not in _WRAPPER_BLACKLIST:
            for arg in node.args[:3]:
                if isinstance(arg, ast.Constant) and \
                        isinstance(arg.value, str) and \
                        _METHOD_NAME_RE.match(arg.value):
                    self.facts.calls.append(_CallRef(
                        arg.value, self.path, node.lineno))
                    break

    def _metric_tags(self, node: ast.Call) -> Optional[Tuple]:
        tags_node = None
        for kw in node.keywords:
            if kw.arg == "tag_keys":
                tags_node = kw.value
        if tags_node is None and len(node.args) >= 3:
            tags_node = node.args[2]
        if tags_node is None:
            return ()
        if isinstance(tags_node, (ast.Tuple, ast.List)) and all(
                isinstance(e, ast.Constant) for e in tags_node.elts):
            return tuple(e.value for e in tags_node.elts)
        return None  # dynamic: exempt from the conflict check

    def _scan_assign(self, node: ast.Assign):
        if len(node.targets) != 1 or not isinstance(node.targets[0],
                                                    ast.Name):
            return
        name = node.targets[0].id
        if name in _CLASS_SET_NAMES:
            entries = self._string_elements(node.value)
            if entries is not None:
                self.facts.class_sets[name] = (entries, node.lineno)
        elif name == "SYNCPOINTS":
            entries = self._string_elements(node.value)
            if entries is not None:
                self.facts.syncpoints_decl.extend(entries)

    @staticmethod
    def _string_elements(value: ast.AST):
        if isinstance(value, ast.Call) and \
                _unparse(value.func) in ("frozenset", "set") and \
                len(value.args) == 1:
            value = value.args[0]
        if isinstance(value, (ast.Tuple, ast.List, ast.Set)):
            out = []
            for e in value.elts:
                if not (isinstance(e, ast.Constant) and
                        isinstance(e.value, str)):
                    return None
                out.append((e.value, e.lineno))
            return out
        return None

    def _scan_config_class(self, node: ast.ClassDef):
        for stmt in node.body:
            if isinstance(stmt, ast.AnnAssign) and \
                    isinstance(stmt.target, ast.Name):
                self.facts.config_fields.append(
                    (stmt.target.id, stmt.lineno))

    # config reads ---------------------------------------------------
    def _is_config_call(self, node: ast.AST) -> bool:
        if not isinstance(node, ast.Call):
            return False
        fname = _unparse(node.func)
        if fname.endswith("RuntimeConfig"):
            return True
        if not self.runtime_config_file:
            return False
        return fname.endswith("get_config") or \
            fname in self.config_helpers

    def _scan_attribute_read(self, node: ast.Attribute):
        # get_config().X / RuntimeConfig().X — provably a config read
        if self._is_config_call(node.value) and \
                isinstance(node.ctx, ast.Load):
            self.facts.config_reads.append((node.attr, node.lineno, True))

    def _scan_config_aliases(self):
        """`cfg = get_config()` provenance, scoped per function frame —
        nested frames inherit the enclosing aliases (closures read them:
        compiled_dag binds `cfg` once and edge factories capture it).
        Attribute-target aliases (`self._cfg = get_config()`) apply
        file-wide since the attribute outlives the assigning method."""
        attr_aliases: Set[str] = set()
        for node in self.nodes:
            if isinstance(node, ast.Assign) and \
                    self._is_config_call(node.value):
                for t in node.targets:
                    if isinstance(t, ast.Attribute):
                        attr_aliases.add(_unparse(t))

        def visit_frame(frame: ast.AST, inherited: Set[str]):
            names = set(inherited)
            subs = list(self._frame_walk(frame))     # walked once a frame
            for sub in subs:
                if isinstance(sub, ast.Assign) and \
                        self._is_config_call(sub.value):
                    for t in sub.targets:
                        if isinstance(t, ast.Name):
                            names.add(t.id)
            for sub in subs:
                # getattr(cfg, "field"[, default])
                if isinstance(sub, ast.Call) and \
                        isinstance(sub.func, ast.Name) and \
                        sub.func.id == "getattr" and len(sub.args) >= 2 and \
                        isinstance(sub.args[1], ast.Constant):
                    recv = _unparse(sub.args[0])
                    if recv in names or recv in attr_aliases or \
                            self._is_config_call(sub.args[0]):
                        # a 3-arg getattr is the tolerant compat form:
                        # counts as a read, never flags unknown
                        self.facts.config_reads.append(
                            (sub.args[1].value, sub.lineno,
                             len(sub.args) < 3))
                elif isinstance(sub, ast.Attribute) and \
                        isinstance(sub.ctx, ast.Load):
                    recv = _unparse(sub.value)
                    if recv in names or recv in attr_aliases:
                        self.facts.config_reads.append(
                            (sub.attr, sub.lineno, True))
            for sub in subs:
                if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef,
                                    ast.Lambda)):
                    visit_frame(sub, names)

        visit_frame(self.tree, set())

    def _frame_walk(self, frame: ast.AST):
        """Children of `frame` without descending into nested defs
        (nested frames are visited as their own entry in `frames`)."""
        if isinstance(frame, ast.Lambda):
            yield from ast.walk(frame.body)
            return
        stack = list(ast.iter_child_nodes(frame))
        while stack:
            sub = stack.pop()
            yield sub
            if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.Lambda)):
                continue
            stack.extend(ast.iter_child_nodes(sub))

    def _note_string_mention(self, node: ast.Constant):
        """Weak liveness evidence: a method-name-shaped string anywhere
        EXCEPT a registration key, a classification/SYNCPOINTS element,
        or a docstring. Feeds only the dead-handler check."""
        text = node.value
        if len(text) > 64 or not _METHOD_NAME_RE.match(text):
            return
        if node in self.docstring_nodes:
            return
        parent = self.parents.get(node)
        if isinstance(parent, ast.Dict) and node in parent.keys:
            return
        cur = parent
        for _ in range(4):
            if cur is None:
                break
            if isinstance(cur, ast.Assign) and any(
                    isinstance(t, ast.Name) and
                    t.id in _CLASS_SET_NAMES + ("SYNCPOINTS",)
                    for t in cur.targets):
                return
            cur = self.parents.get(cur)
        self.facts.string_mentions.add(text)

    def _scan_string(self, node: ast.AST, text: str):
        if node in self.docstring_nodes:
            return  # grammar EXAMPLES live in docstrings
        for m in _SYNCPOINT_STR_RE.finditer(text):
            # syncpoint plants inside program strings (subprocess -c
            # drills) still count as plants
            self.facts.syncpoint_plants.append((m.group(1), node.lineno))
        if not _FAULT_HEAD_RE.search(text):
            return
        rules = _parse_fault_spec(text)
        if rules:
            self.facts.fault_specs.append((rules, node.lineno))


# ----------------------------------------------------------------- model
class ProtoModel:
    """Merged whole-program facts + the cross-checks (RTPU101-106)."""

    def __init__(self, files: List[_FileFacts]):
        self.files = files
        self.findings: List[Finding] = []
        # merged views
        self.registered_pkg: Dict[str, List[_HandlerReg]] = {}
        self.registered_all: Set[str] = set()
        self.called: Dict[str, List[_CallRef]] = {}
        self.class_sets: Dict[str, Tuple[List[Tuple[str, int]], int, str]] = {}
        self.syncpoints_decl: List[Tuple[str, int, str]] = []
        self.plants_pkg: Dict[str, List[Tuple[str, int]]] = {}
        self.plants_all: Set[str] = set()
        self.config_fields: List[Tuple[str, int, str]] = []
        self.config_reads_pkg: Set[str] = set()
        self.mentions: Set[str] = set()
        for ff in files:
            self.mentions |= ff.string_mentions
            for reg in ff.handlers:
                self.registered_all.add(reg.method)
                if ff.in_package:
                    self.registered_pkg.setdefault(reg.method,
                                                   []).append(reg)
            for call in ff.calls:
                self.called.setdefault(call.method, []).append(call)
            for name, (entries, line) in ff.class_sets.items():
                if ff.in_package and name not in self.class_sets:
                    self.class_sets[name] = (entries, line, ff.path)
            for sp, line in ff.syncpoints_decl:
                if ff.in_package:
                    self.syncpoints_decl.append((sp, line, ff.path))
            for sp, line in ff.syncpoint_plants:
                self.plants_all.add(sp)
                if ff.in_package:
                    self.plants_pkg.setdefault(sp, []).append(
                        (ff.path, line))
            if ff.in_package:
                for fname, line in ff.config_fields:
                    self.config_fields.append((fname, line, ff.path))
                for fname, _line, _strict in ff.config_reads:
                    self.config_reads_pkg.add(fname)

    def _emit(self, path: str, line: int, rule: str, message: str):
        self.findings.append(Finding(path, line, 0, rule, message))

    # ------------------------------------------------------------ checks
    def check(self) -> List[Finding]:
        self._check_rpc_graph()      # RTPU101 + RTPU102
        self._check_classification()  # RTPU103
        self._check_fault_plane()    # RTPU104
        self._check_config()         # RTPU105
        self._check_metrics()        # RTPU106
        return self.findings

    def _check_rpc_graph(self):
        known = set(self.registered_pkg)
        for ff in self.files:
            if not ff.in_package:
                continue
            for call in ff.calls:
                if call.method not in known:
                    self._emit(
                        ff.path, call.line, "RTPU101",
                        f"RPC call names method {call.method!r} that no "
                        "server registers — under default deadlines this "
                        "is a silent 60s timeout, not an error")
                    continue
                if call.checkable and call.kwargs:
                    self._check_call_kwargs(ff.path, call)
        for method, regs in sorted(self.registered_pkg.items()):
            if method not in self.called and method not in self.mentions:
                reg = regs[0]
                self._emit(
                    reg.path, reg.line, "RTPU101",
                    f"handler {method!r} is registered but no call site "
                    "in the package, tests or benchmarks ever names it — "
                    "dead protocol surface (delete it or add the "
                    "missing caller)")

    def _check_call_kwargs(self, path: str, call: _CallRef):
        regs = [r for r in self.registered_pkg[call.method] if r.resolved]
        if not regs:
            return  # nothing provable
        rejected = set(call.kwargs)
        for reg in regs:
            if reg.has_var_kw:
                return
            rejected &= (call.kwargs - reg.params)
            if not rejected:
                return
        self._emit(
            path, call.line, "RTPU102",
            f"call passes kwarg(s) {sorted(rejected)} that no handler "
            f"of {call.method!r} accepts (handler signature: "
            f"{sorted(regs[0].params)}) — the server answers with a "
            "TypeError-shaped RemoteHandlerError at runtime")

    def _check_classification(self):
        if not self.class_sets:
            return  # no registry in scope (non-package fixture runs)
        members: Dict[str, List[str]] = {}
        for set_name, (entries, _line, path) in self.class_sets.items():
            for method, line in entries:
                members.setdefault(method, []).append(set_name)
                if method not in self.registered_pkg:
                    self._emit(
                        path, line, "RTPU103",
                        f"{set_name} classifies {method!r} but no server "
                        "registers that method — stale entry (drop it, "
                        "or restore the handler it described)")
        anchor = self.class_sets.get("NON_IDEMPOTENT_METHODS") or \
            next(iter(self.class_sets.values()))
        for method, regs in sorted(self.registered_pkg.items()):
            in_sets = members.get(method, [])
            if len(in_sets) > 1:
                self._emit(
                    anchor[2], anchor[1], "RTPU103",
                    f"RPC method {method!r} is classified in "
                    f"{sorted(in_sets)} — retry semantics must be "
                    "exactly one deliberate choice")
            elif not in_sets:
                reg = regs[0]
                self._emit(
                    reg.path, reg.line, "RTPU103",
                    f"RPC method {method!r} is in no failure class: add "
                    "it to exactly one of IDEMPOTENT_METHODS / "
                    "UNBOUNDED_METHODS / NON_IDEMPOTENT_METHODS "
                    "(runtime/rpc.py) — unclassified methods are how "
                    "the actor_died double-restart happened")

    def _check_fault_plane(self):
        declared = {sp for sp, _l, _p in self.syncpoints_decl}
        known_sps = declared | self.plants_all
        methods_ok = self.registered_all | {"*"}
        for sp, line, path in self.syncpoints_decl:
            if sp not in self.plants_pkg:
                self._emit(
                    path, line, "RTPU104",
                    f"SYNCPOINTS documents {sp!r} but nothing in the "
                    "package plants it (faults.syncpoint call) — a "
                    "kill_at drill against it can never fire")
        for sp, sites in sorted(self.plants_pkg.items()):
            if sp not in declared:
                path, line = sites[0]
                self._emit(
                    path, line, "RTPU104",
                    f"syncpoint {sp!r} is planted but missing from "
                    "faults.SYNCPOINTS — drills can only target what "
                    "the documented set advertises")
        for ff in self.files:
            for rules, line in ff.fault_specs:
                for rule in rules:
                    if rule.kind == "kill_at":
                        if "*" not in rule.syncpoint and \
                                rule.syncpoint not in known_sps:
                            self._emit(
                                ff.path, line, "RTPU104",
                                f"fault rule kill_at({rule.syncpoint}) "
                                "names a syncpoint that is neither "
                                "documented nor planted anywhere — this "
                                "drill silently never fires")
                    elif rule.method and "*" not in rule.method and \
                            rule.method not in methods_ok:
                        self._emit(
                            ff.path, line, "RTPU104",
                            f"fault rule {rule.kind}({rule.method}) "
                            "names an RPC method no server registers — "
                            "this drill silently never fires")

    def _check_config(self):
        fields = {f for f, _l, _p in self.config_fields}
        if not fields:
            return
        exempt = {"from_env", "to_dict", "from_dict"}
        for ff in self.files:
            if not ff.in_package:
                continue
            for fname, line, strict in ff.config_reads:
                if strict and fname not in fields and \
                        fname not in exempt and not fname.startswith("__"):
                    self._emit(
                        ff.path, line, "RTPU105",
                        f"get_config().{fname}: RuntimeConfig has no "
                        f"field {fname!r} — AttributeError on whatever "
                        "path reads this")
        for fname, line, path in self.config_fields:
            if fname not in self.config_reads_pkg:
                self._emit(
                    path, line, "RTPU105",
                    f"RuntimeConfig.{fname} is a dead knob: no package "
                    "code reads it — wire it into the behavior it "
                    "promises, or delete it")

    def _check_metrics(self):
        seen: Dict[str, Tuple[str, Optional[Tuple], str, int]] = {}
        for ff in self.files:
            if not ff.in_package:
                continue
            for name, mtype, tags, line in ff.metric_decls:
                if mtype == "counter" and not name.endswith("_total"):
                    self._emit(
                        ff.path, line, "RTPU106",
                        f"counter {name!r} must end '_total' "
                        "(Prometheus counter naming; dashboards and "
                        "rate() queries key on it)")
                if mtype != "counter" and name.endswith("_total"):
                    self._emit(
                        ff.path, line, "RTPU106",
                        f"{mtype} {name!r} ends '_total', which "
                        "promises a counter — readers will rate() a "
                        "non-monotonic series")
                prev = seen.get(name)
                if prev is None:
                    seen[name] = (mtype, tags, ff.path, line)
                    continue
                p_type, p_tags, p_path, p_line = prev
                if p_type != mtype or (tags is not None and
                                       p_tags is not None and
                                       set(tags) != set(p_tags)):
                    self._emit(
                        ff.path, line, "RTPU106",
                        f"metric {name!r} redeclared as {mtype} with "
                        f"labels {sorted(tags or ())} — first declared "
                        f"as {p_type} with labels {sorted(p_tags or ())} "
                        f"at {p_path}:{p_line}; one name, one (type, "
                        "label-set)")


# ------------------------------------------------------------------- api
def _scan_files(paths: List[str], package_paths: List[str]
                ) -> List[_FileFacts]:
    pkg_abs = [os.path.abspath(p) for p in package_paths]
    explicit = {os.path.abspath(p) for p in paths if os.path.isfile(p)}

    def in_pkg(fp: str) -> bool:
        afp = os.path.abspath(fp)
        return any(afp == p or afp.startswith(p + os.sep) for p in pkg_abs)

    facts = []
    for fp in iter_python_files(paths):
        if os.sep + "lint_fixtures" + os.sep in fp and \
                os.path.abspath(fp) not in explicit:
            # fixtures deliberately violate the rules; they only count
            # when named directly (their own self-tests)
            continue
        try:
            with open(fp, "r", encoding="utf-8") as fh:
                source = fh.read()
            tree = ast.parse(source)
        except (OSError, SyntaxError):
            continue  # per-file rules already report syntax errors
        facts.append(_FileScanner(fp, source, tree, in_pkg(fp)).scan())
    return facts


def run_proto(package_paths: List[str],
              aux_paths: Optional[List[str]] = None
              ) -> Tuple[List[Finding], int]:
    """Analyze the whole program. `package_paths` hold the protocol
    DEFINITIONS (handlers, sets, knobs, metrics — declaration-side
    checks anchor there); `aux_paths` (tests/benchmarks) contribute
    call-liveness evidence, extra handler tables (test harness servers),
    syncpoint plants, and fault-spec strings to validate (RTPU104
    findings do fire in aux files). Returns (findings, files_scanned)."""
    aux_paths = [p for p in (aux_paths or []) if os.path.exists(p)]
    facts = _scan_files(list(package_paths) + aux_paths, package_paths)
    findings = ProtoModel(facts).check()
    # dedup (two call sites on one line produce one actionable finding)
    uniq: Dict[Tuple, Finding] = {}
    for f in findings:
        uniq.setdefault((f.path, f.line, f.rule, f.message), f)
    findings = list(uniq.values())
    # pragma suppression: same grammar, same line / line-above scope
    pragmas_by_path = {ff.path: ff.pragmas for ff in facts}
    for f in findings:
        pragmas = pragmas_by_path.get(f.path, {})
        for lineno in (f.line, f.line - 1):
            entry = pragmas.get(lineno)
            if entry and f.rule in entry[0]:
                f.suppressed = True
                f.reason = entry[1]
                break
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return findings, len(facts)


def default_aux_paths(package_path: str) -> List[str]:
    """tests/ and benchmarks/ siblings of the package checkout."""
    repo = os.path.dirname(os.path.abspath(package_path.rstrip(os.sep)))
    return [os.path.join(repo, "tests"), os.path.join(repo, "benchmarks")]
