"""The concurrency model racelint's checkers run against.

One pass over each module builds, per class (and per module, for
module-global state):

- **sync primitives**: attributes/globals assigned ``threading.Lock /
  RLock / Condition / Event`` (through any import alias). Locks get a
  stable identity (``path:Class.self._lock``) used by the guarded-by
  inference and the global lock-acquisition graph.
- **function units**: every method and nested function, with the
  *execution contexts* it can run under:

  - ``thread`` — a ``threading.Thread`` target, ``executor.submit`` /
    ``asyncio.to_thread`` / ``run_in_executor`` callee, ``Timer``
    callback, or ``run()`` of a ``threading.Thread`` subclass;
  - ``loop``   — an ``async def``, or a callback handed to
    ``call_soon_threadsafe`` / ``call_soon`` / ``call_later`` /
    ``create_task`` / ``run_coroutine_threadsafe``;
  - ``caller`` — a public method (no leading underscore): callable from
    whatever thread the transport happens to be on;
  - ``init``   — ``__init__`` and everything reachable only from it
    (single-threaded by construction).

  Contexts propagate through the intra-class call graph to a fixpoint.
  Leading-underscore methods are treated as internal: they run in their
  callers' contexts. That convention is what makes guarded-by inference
  work — a ``_locked`` helper called only under ``with self._lock`` is
  guarded, even though the lock is lexically elsewhere.
- **accesses**: every ``self.X`` read / write / read-modify-write with
  the set of locks *definitely held* at the access — the lexical
  ``with``-stack plus the function's inferred entry locks (the
  intersection of locks held at every internal call site; externally
  enterable functions get the empty set, because outside callers hold
  nothing).
- **lock-order edges**: lock A held while lock B is acquired (lexically,
  or through an internal call whose transitive acquires include B).
- **hazard sites**: ``await`` while a *threading* lock is held, and
  timeout-less sync waits (``.wait()`` / ``.join()`` / ``.result()``).

The model deliberately ignores foreign-object state (``adm.shed_total``
read by the metrics registry): cross-object disciplines belong to the
owning class, and chasing them would drown the signal. ``lambda``s are
not tracked as separate units (they inherit the enclosing function).
"""

from __future__ import annotations

import ast
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Set, Tuple

from tools.graftlint.core import Module, Project, dotted

# the packages whose concurrency this layer guards (ISSUE 6 scope: the
# serving runtime and everything the multi-host/control-plane roadmap
# items will thread through)
CONCURRENT_DIRS = ("runtime", "transport", "servers", "controlplane", "metrics")

LOCK_CTORS = {"Lock": "lock", "RLock": "rlock", "Condition": "condition"}
EVENT_CTORS = {"Event"}

# read-modify-write mutators: calling these on a shared binding mutates
# the object behind it — for discipline purposes that is a write
MUTATOR_METHODS = {
    "append", "appendleft", "extend", "extendleft", "insert", "pop",
    "popleft", "remove", "clear", "add", "discard", "update", "setdefault",
    "sort", "reverse", "set", "rotate",
}

SHUTDOWN_FN_RE_SRC = (
    r"(close|stop|shutdown|halt|terminate|finalize|cleanup|teardown"
    r"|__exit__|__del__|atexit|quit)"
)

CTX_THREAD = "thread"
CTX_LOOP = "loop"
CTX_CALLER = "caller"
CTX_INIT = "init"


@dataclass
class LockInfo:
    lock_id: str      # stable: "relpath:Class.self._lock" / "relpath:<module>._lock"
    kind: str         # lock | rlock | condition
    short: str        # "self._lock" / "_lock" — for messages


@dataclass
class Access:
    attr: str
    kind: str         # read | write | rmw
    line: int
    func: "FuncUnit"
    lexical_locks: frozenset

    def held(self) -> frozenset:
        return self.lexical_locks | self.func.entry_locks


@dataclass
class CallSite:
    callee: str       # bare function/method name
    line: int
    lexical_locks: frozenset
    func: "FuncUnit"  # caller


@dataclass
class WaitSite:
    receiver: str     # dotted receiver ("self._halt", "t")
    method: str       # wait | join | result
    line: int
    func: "FuncUnit"


@dataclass
class AwaitSite:
    line: int
    locks: frozenset
    func: "FuncUnit"


@dataclass
class LockEdge:
    held: str         # lock_id already held
    acquired: str     # lock_id acquired under it
    line: int
    module: Module
    func: "FuncUnit"
    via_call: str = ""  # callee name when the edge crosses a call


@dataclass
class FuncUnit:
    qualname: str     # dotted through class + enclosing defs
    name: str
    node: ast.AST
    owner: Optional["ClassModel"]
    is_async: bool
    direct_ctxs: Set[str] = field(default_factory=set)
    ctxs: Set[str] = field(default_factory=set)
    external: bool = False      # enterable from outside the class
    entry_locks: frozenset = frozenset()
    accesses: List[Access] = field(default_factory=list)
    calls: List[CallSite] = field(default_factory=list)
    lexical_acquires: Set[str] = field(default_factory=set)
    trans_acquires: Set[str] = field(default_factory=set)
    waits: List[WaitSite] = field(default_factory=list)
    awaits: List[AwaitSite] = field(default_factory=list)


@dataclass
class ClassModel:
    qualname: str
    module: Module
    node: ast.ClassDef
    locks: Dict[str, LockInfo] = field(default_factory=dict)   # attr -> info
    events: Set[str] = field(default_factory=set)
    funcs: Dict[str, FuncUnit] = field(default_factory=dict)   # bare name -> unit
    spawns: bool = False          # creates threads/tasks/executors
    thread_subclass: bool = False

    @property
    def active(self) -> bool:
        """Concurrency-active: this class's state can be reached by more
        than one thread/task at once, so lock discipline applies."""
        return bool(self.locks) or self.spawns or self.thread_subclass


@dataclass
class ModuleModel:
    """Module-global shared state (e.g. the gRPC channel cache): analyzed
    exactly like a class, but only when a module-level lock exists —
    without one there is no declared discipline to check against."""
    module: Module
    locks: Dict[str, LockInfo] = field(default_factory=dict)   # global name -> info
    globals_assigned: Set[str] = field(default_factory=set)
    funcs: Dict[str, FuncUnit] = field(default_factory=dict)
    classes: List[ClassModel] = field(default_factory=list)
    thread_aliases: Set[str] = field(default_factory=set)      # {"threading", "_threading"}
    from_imports: Dict[str, str] = field(default_factory=dict)  # local -> "threading.Lock"


def in_scope(module: Module) -> bool:
    return any(p in CONCURRENT_DIRS for p in module.parts[:-1])


# ---------------------------------------------------------------------------
# module scanning
# ---------------------------------------------------------------------------


def _collect_imports(tree: ast.Module) -> Tuple[Set[str], Dict[str, str]]:
    aliases: Set[str] = set()
    from_imports: Dict[str, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name == "threading":
                    aliases.add(a.asname or a.name)
        elif isinstance(node, ast.ImportFrom) and node.module == "threading":
            for a in node.names:
                from_imports[a.asname or a.name] = f"threading.{a.name}"
    return aliases, from_imports


def _sync_ctor(value: ast.AST, mm: ModuleModel) -> Optional[str]:
    """'lock'/'rlock'/'condition'/'event' when ``value`` constructs a
    threading primitive (through any alias), else None."""
    if not isinstance(value, ast.Call):
        return None
    f = value.func
    if isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name) \
            and f.value.id in mm.thread_aliases:
        name = f.attr
    elif isinstance(f, ast.Name):
        resolved = mm.from_imports.get(f.id, "")
        name = resolved.split(".")[-1] if resolved.startswith("threading.") else ""
    else:
        return None
    if name in LOCK_CTORS:
        return LOCK_CTORS[name]
    if name in EVENT_CTORS:
        return "event"
    return None


def _is_thread_base(base: ast.AST, mm: ModuleModel) -> bool:
    d = dotted(base) or ""
    if d.endswith(".Thread"):
        root = d.split(".", 1)[0]
        return root in mm.thread_aliases
    return mm.from_imports.get(d, "") == "threading.Thread"


def build_module_model(module: Module) -> ModuleModel:
    mm = ModuleModel(module=module)
    mm.thread_aliases, mm.from_imports = _collect_imports(module.tree)

    # module-level locks and assigned globals
    for stmt in module.tree.body:
        if isinstance(stmt, ast.Assign):
            kind = _sync_ctor(stmt.value, mm)
            for t in stmt.targets:
                if isinstance(t, ast.Name):
                    if kind in ("lock", "rlock", "condition"):
                        mm.locks[t.id] = LockInfo(
                            f"{module.relpath}:<module>.{t.id}", kind, t.id)
                    elif kind is None:
                        mm.globals_assigned.add(t.id)

    # classes
    def scan_body(body, prefix):
        for node in body:
            if isinstance(node, ast.ClassDef):
                q = f"{prefix}.{node.name}" if prefix else node.name
                cm = ClassModel(qualname=q, module=module, node=node)
                cm.thread_subclass = any(
                    _is_thread_base(b, mm) for b in node.bases)
                mm.classes.append(cm)
                _scan_class(cm, mm)
                scan_body(node.body, q)  # nested classes
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and prefix == "":
                unit = FuncUnit(
                    qualname=node.name, name=node.name, node=node, owner=None,
                    is_async=isinstance(node, ast.AsyncFunctionDef))
                unit.external = True
                unit.direct_ctxs.add(
                    CTX_LOOP if unit.is_async else CTX_CALLER)
                mm.funcs[node.name] = unit
                # nested defs (the ipc drain pattern: a closure handed to
                # threading.Thread inside a module function) are their own
                # units so spawn registrations can reach them
                for sub in ast.walk(node):
                    if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                            and sub is not node:
                        nested = FuncUnit(
                            qualname=f"{node.name}.{sub.name}", name=sub.name,
                            node=sub, owner=None,
                            is_async=isinstance(sub, ast.AsyncFunctionDef))
                        mm.funcs.setdefault(sub.name, nested)

    scan_body(module.tree.body, "")

    # module-level function bodies (walked with the module lock table)
    for unit in mm.funcs.values():
        _walk_function(unit, mm, None)
    for unit in mm.funcs.values():
        unit.ctxs = set(unit.direct_ctxs) or {CTX_CALLER}
        unit.entry_locks = frozenset()

    for cm in mm.classes:
        _finalize_class(cm)
    return mm


# ---------------------------------------------------------------------------
# class scanning
# ---------------------------------------------------------------------------


def _scan_class(cm: ClassModel, mm: ModuleModel) -> None:
    # pass 1: sync-primitive attributes (wherever assigned: __init__ or not)
    for node in ast.walk(cm.node):
        if isinstance(node, ast.Assign):
            kind = _sync_ctor(node.value, mm)
            if kind is None:
                continue
            for t in node.targets:
                d = dotted(t)
                if d and d.startswith("self."):
                    attr = d[len("self."):]
                    if kind in ("lock", "rlock", "condition"):
                        cm.locks[attr] = LockInfo(
                            f"{cm.module.relpath}:{cm.qualname}.self.{attr}",
                            kind, f"self.{attr}")
                    else:
                        cm.events.add(attr)

    # pass 2: function units (methods + their nested defs)
    def add_unit(fn, qual):
        unit = FuncUnit(
            qualname=qual, name=fn.name, node=fn, owner=cm,
            is_async=isinstance(fn, ast.AsyncFunctionDef))
        cm.funcs[fn.name] = unit
        return unit

    for item in cm.node.body:
        if isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef)):
            unit = add_unit(item, f"{cm.qualname}.{item.name}")
            # nested defs become their own units (they may be handed to
            # another thread/loop as callbacks)
            for sub in ast.walk(item):
                if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                        and sub is not item:
                    nested = FuncUnit(
                        qualname=f"{unit.qualname}.{sub.name}", name=sub.name,
                        node=sub, owner=cm,
                        is_async=isinstance(sub, ast.AsyncFunctionDef))
                    cm.funcs.setdefault(sub.name, nested)

    # direct contexts from names/shape
    for name, unit in cm.funcs.items():
        if name == "__init__":
            unit.direct_ctxs.add(CTX_INIT)
            unit.external = True
        elif unit.is_async:
            unit.direct_ctxs.add(CTX_LOOP)
            unit.external = True
        elif cm.thread_subclass and name == "run":
            unit.direct_ctxs.add(CTX_THREAD)
            unit.external = True
        elif not name.startswith("_") or (
                name.startswith("__") and name.endswith("__")):
            unit.direct_ctxs.add(CTX_CALLER)
            unit.external = True
        # bare leading-underscore methods: internal; contexts and entry
        # locks come from their call sites

    # pass 3: walk bodies
    for unit in list(cm.funcs.values()):
        _walk_function(unit, mm, cm)


def _finalize_class(cm: ClassModel) -> None:
    _propagate_ctxs(cm)
    _infer_entry_locks(cm)
    _close_acquires(cm)


# ---------------------------------------------------------------------------
# the statement walk (shared by class methods and module functions)
# ---------------------------------------------------------------------------


def _lock_of(expr: ast.AST, mm: ModuleModel, cm: Optional[ClassModel]) -> Optional[LockInfo]:
    d = dotted(expr)
    if d is None:
        return None
    if cm is not None and d.startswith("self."):
        return cm.locks.get(d[len("self."):])
    return mm.locks.get(d)


def _spawn_targets(call: ast.Call, mm: ModuleModel):
    """Yield (callee_expr, ctx) for concurrency registrations in ``call``."""
    f = call.func
    d = dotted(f) or ""
    term = f.attr if isinstance(f, ast.Attribute) else (
        f.id if isinstance(f, ast.Name) else "")
    root = d.split(".", 1)[0] if d else ""

    def kw(name):
        for k in call.keywords:
            if k.arg == name:
                return k.value
        return None

    if term == "Thread" and (root in mm.thread_aliases
                             or mm.from_imports.get(d) == "threading.Thread"):
        t = kw("target")
        if t is not None:
            yield t, CTX_THREAD
    elif term == "Timer" and (root in mm.thread_aliases
                              or mm.from_imports.get(d) == "threading.Timer"):
        if len(call.args) >= 2:
            yield call.args[1], CTX_THREAD
    elif term == "submit" and isinstance(f, ast.Attribute) and call.args:
        yield call.args[0], CTX_THREAD
    elif (d == "asyncio.to_thread" or term == "_to_thread") and call.args:
        # asyncio's, or a class's own wrapper of it under its name
        # (runtime/batcher.py ContinuousBatcher._to_thread stamps the hand-off)
        yield call.args[0], CTX_THREAD
    elif term == "run_in_executor" and len(call.args) >= 2:
        yield call.args[1], CTX_THREAD
    elif term in ("call_soon_threadsafe", "call_soon") and call.args:
        yield call.args[0], CTX_LOOP
    elif term == "call_later" and len(call.args) >= 2:
        yield call.args[1], CTX_LOOP
    elif term in ("create_task", "ensure_future") and call.args:
        yield call.args[0], CTX_LOOP
    elif term == "run_coroutine_threadsafe" and call.args:
        yield call.args[0], CTX_LOOP


def _callee_name(expr: ast.AST) -> Optional[str]:
    """Bare name of a self-method / local function reference (or the
    function CALLED, for coroutine arguments like ``self.m(...)``)."""
    if isinstance(expr, ast.Call):
        return _callee_name(expr.func)
    d = dotted(expr)
    if d is None:
        return None
    if d.startswith("self."):
        rest = d[len("self."):]
        return rest if "." not in rest else None
    return d if "." not in d else None


def _is_spawn_call(call: ast.Call, mm: ModuleModel) -> bool:
    f = call.func
    d = dotted(f) or ""
    term = f.attr if isinstance(f, ast.Attribute) else (
        f.id if isinstance(f, ast.Name) else "")
    root = d.split(".", 1)[0] if d else ""
    if term in ("Thread", "Timer", "ThreadPoolExecutor"):
        return root in mm.thread_aliases or "futures" in d \
            or mm.from_imports.get(d, "").startswith("threading.") \
            or d in ("futures.ThreadPoolExecutor",
                     "concurrent.futures.ThreadPoolExecutor")
    return d in ("asyncio.to_thread", "asyncio.run_coroutine_threadsafe") \
        or term in ("run_in_executor", "submit", "_to_thread")


class _FunctionWalker:
    def __init__(self, unit: FuncUnit, mm: ModuleModel, cm: Optional[ClassModel]):
        self.unit = unit
        self.mm = mm
        self.cm = cm
        self.held: List[str] = []          # lock-id stack
        self.awaited_calls: Set[int] = set()
        # rmw detection needs the attrs read on the value side of the
        # statement currently being processed
        self._stmt_reads: Set[str] = set()

    # -- helpers --------------------------------------------------------
    def _heldset(self) -> frozenset:
        return frozenset(self.held)

    def _self_attr(self, node: ast.AST) -> Optional[str]:
        if isinstance(node, ast.Attribute) and isinstance(node.value, ast.Name) \
                and node.value.id == "self" and self.cm is not None:
            return node.attr
        return None

    def _global_name(self, node: ast.AST) -> Optional[str]:
        if self.cm is None and isinstance(node, ast.Name) \
                and (node.id in self.mm.globals_assigned
                     or node.id in self.mm.locks):
            return node.id
        return None

    def _is_primitive(self, attr: str) -> bool:
        if self.cm is not None:
            return attr in self.cm.locks or attr in self.cm.events
        return attr in self.mm.locks

    def _record(self, attr: str, kind: str, node: ast.AST):
        if self._is_primitive(attr):
            return
        self.unit.accesses.append(Access(
            attr, kind, getattr(node, "lineno", 0) or 0, self.unit,
            self._heldset()))

    # -- expression-level reads ----------------------------------------
    def _scan_expr(self, node: ast.AST):
        """Record attribute/global reads, mutator calls, spawn
        registrations, self-calls, wait hazards inside one expression."""
        for sub in ast.walk(node):
            if isinstance(sub, ast.Attribute) and isinstance(sub.ctx, ast.Load):
                attr = self._self_attr(sub)
                if attr is not None:
                    self._stmt_reads.add(attr)
                    self._record(attr, "read", sub)
            elif isinstance(sub, ast.Name) and isinstance(sub.ctx, ast.Load):
                g = self._global_name(sub)
                if g is not None and g not in self.mm.locks:
                    self._stmt_reads.add(g)
                    self._record(g, "read", sub)
            elif isinstance(sub, ast.Call):
                self._scan_call(sub)

    def _scan_call(self, call: ast.Call):
        f = call.func
        # spawn registrations: the referenced callee gains a context
        for target, ctx in _spawn_targets(call, self.mm):
            name = _callee_name(target)
            if name:
                owner_funcs = (self.cm.funcs if self.cm is not None
                               else self.mm.funcs)
                unit = owner_funcs.get(name)
                if unit is not None:
                    unit.direct_ctxs.add(ctx)
                    unit.external = True
        if self.cm is not None and _is_spawn_call(call, self.mm):
            self.cm.spawns = True

        if isinstance(f, ast.Attribute):
            recv = dotted(f.value)
            # mutator method on a shared binding = write
            if f.attr in MUTATOR_METHODS:
                attr = self._self_attr(f.value)
                if attr is not None:
                    self._record(attr, "write", call)
                g = self._global_name(f.value) if recv else None
                if g is not None and g not in self.mm.locks:
                    self._record(g, "write", call)
            # manual acquire/release on a known lock
            lock = _lock_of(f.value, self.mm, self.cm)
            if lock is not None:
                if f.attr == "acquire":
                    self._acquire(lock, call)
                elif f.attr == "release" and lock.lock_id in self.held:
                    self.held.remove(lock.lock_id)
            # timeout-less sync waits (await-wrapped calls are the async
            # world — deadline-governed, not racelint's)
            if f.attr in ("wait", "join", "result") and id(call) not in self.awaited_calls \
                    and not call.args \
                    and not any(k.arg == "timeout" for k in call.keywords):
                self.unit.waits.append(WaitSite(
                    recv or "", f.attr, call.lineno, self.unit))
        # intra-class / intra-module call
        name = _callee_name(f)
        if name is not None:
            self.unit.calls.append(CallSite(
                name, call.lineno, self._heldset(), self.unit))

    def _acquire(self, lock: LockInfo, node: ast.AST):
        for held_id in self.held:
            if held_id == lock.lock_id and lock.kind in ("rlock", "condition"):
                # reentrant self-acquire is fine (Condition's default
                # internal lock is an RLock)
                continue
            # a self-edge on a non-reentrant lock IS the deadlock;
            # distinct locks form the ordering graph
            self._edge(held_id, lock.lock_id, node)
        self.held.append(lock.lock_id)

    def _edge(self, held_id: str, acquired_id: str, node: ast.AST, via: str = ""):
        owner = self.cm.module if self.cm is not None else self.mm.module
        edges = _module_edges.setdefault(id(owner), [])
        edges.append(LockEdge(held_id, acquired_id,
                              getattr(node, "lineno", 0) or 0,
                              owner, self.unit, via))

    # -- statements -----------------------------------------------------
    def walk(self, body: Sequence[ast.stmt]):
        # pre-pass: awaited call ids (so x.wait() under `await` is skipped)
        for stmt in body:
            for sub in ast.walk(stmt):
                if isinstance(sub, ast.Await) and isinstance(sub.value, ast.Call):
                    self.awaited_calls.add(id(sub.value))
        self._walk_block(body)

    def _walk_block(self, body: Sequence[ast.stmt]):
        for stmt in body:
            self._stmt(stmt)

    def _stmt(self, stmt: ast.stmt):
        self._stmt_reads = set()
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
            return  # nested defs are separate units
        if isinstance(stmt, (ast.With, ast.AsyncWith)):
            pushed = 0
            for item in stmt.items:
                self._scan_expr(item.context_expr)
                self._note_awaits(item.context_expr)
                lock = None
                if isinstance(stmt, ast.With):
                    lock = _lock_of(item.context_expr, self.mm, self.cm)
                if lock is not None:
                    self._acquire(lock, item.context_expr)
                    pushed += 1
            self._walk_block(stmt.body)
            for _ in range(pushed):
                self.held.pop()
            return
        if isinstance(stmt, ast.Assign):
            self._scan_expr(stmt.value)
            self._note_awaits(stmt.value)
            self._assign_targets(stmt.targets, stmt)
            return
        if isinstance(stmt, ast.AugAssign):
            self._scan_expr(stmt.value)
            self._note_awaits(stmt.value)
            self._aug_target(stmt.target, stmt)
            return
        if isinstance(stmt, ast.AnnAssign):
            if stmt.value is not None:
                self._scan_expr(stmt.value)
                self._note_awaits(stmt.value)
                self._assign_targets([stmt.target], stmt)
            return
        if isinstance(stmt, ast.Delete):
            for t in stmt.targets:
                if isinstance(t, ast.Subscript):
                    attr = self._self_attr(t.value)
                    if attr is not None:
                        self._record(attr, "write", stmt)
                    g = self._global_name(t.value)
                    if g is not None:
                        self._record(g, "write", stmt)
            return
        if isinstance(stmt, (ast.For, ast.AsyncFor)):
            self._scan_expr(stmt.iter)
            self._note_awaits(stmt.iter)
            self._walk_block(stmt.body)
            self._walk_block(stmt.orelse)
            return
        if isinstance(stmt, (ast.If, ast.While)):
            self._scan_expr(stmt.test)
            self._note_awaits(stmt.test)
            self._walk_block(stmt.body)
            self._walk_block(stmt.orelse)
            return
        if isinstance(stmt, ast.Try):
            self._walk_block(stmt.body)
            for h in stmt.handlers:
                self._walk_block(h.body)
            self._walk_block(stmt.orelse)
            self._walk_block(stmt.finalbody)
            return
        if isinstance(stmt, (ast.Return, ast.Expr, ast.Raise, ast.Assert)):
            for v in (getattr(stmt, "value", None), getattr(stmt, "exc", None),
                      getattr(stmt, "test", None)):
                if v is not None:
                    self._scan_expr(v)
                    self._note_awaits(v)
            return
        # anything else: scan its expressions generically
        self._scan_expr(stmt)
        self._note_awaits(stmt)

    def _note_awaits(self, node: ast.AST):
        if not self.held:
            return
        for sub in ast.walk(node):
            if isinstance(sub, ast.Await):
                self.unit.awaits.append(AwaitSite(
                    sub.value.lineno if hasattr(sub.value, "lineno")
                    else getattr(sub, "lineno", 0),
                    self._heldset(), self.unit))

    def _assign_targets(self, targets, stmt):
        for t in targets:
            self._one_target(t, stmt)

    def _one_target(self, t: ast.AST, stmt: ast.stmt):
        if isinstance(t, (ast.Tuple, ast.List)):
            for elt in t.elts:
                self._one_target(elt, stmt)
            return
        if isinstance(t, ast.Starred):
            self._one_target(t.value, stmt)
            return
        if isinstance(t, ast.Subscript):
            attr = self._self_attr(t.value)
            if attr is not None:
                kind = "rmw" if attr in self._stmt_reads else "write"
                self._record(attr, kind, t)
            g = self._global_name(t.value)
            if g is not None and g not in self.mm.locks:
                kind = "rmw" if g in self._stmt_reads else "write"
                self._record(g, kind, t)
            self._scan_expr(t.slice)
            return
        attr = self._self_attr(t)
        if attr is not None:
            kind = "rmw" if attr in self._stmt_reads else "write"
            self._record(attr, kind, t)
            return
        if isinstance(t, ast.Name) and self.cm is None \
                and t.id in self.mm.globals_assigned:
            kind = "rmw" if t.id in self._stmt_reads else "write"
            self._record(t.id, kind, t)

    def _aug_target(self, t: ast.AST, stmt: ast.stmt):
        if isinstance(t, ast.Subscript):
            attr = self._self_attr(t.value)
            if attr is not None:
                self._record(attr, "rmw", t)
            g = self._global_name(t.value)
            if g is not None:
                self._record(g, "rmw", t)
            self._scan_expr(t.slice)
            return
        attr = self._self_attr(t)
        if attr is not None:
            self._record(attr, "rmw", t)
            return
        if isinstance(t, ast.Name) and self.cm is None \
                and t.id in self.mm.globals_assigned:
            self._record(t.id, "rmw", t)


# edges are collected per-module during walking, then read by the checker
_module_edges: Dict[int, List[LockEdge]] = {}


def _own_statements(body: Sequence[ast.stmt]):
    """Every AST node of this function EXCLUDING nested function bodies
    (those are separate units with their own acquire sets)."""
    stack: List[ast.AST] = list(body)
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        yield node
        stack.extend(ast.iter_child_nodes(node))


def _walk_function(unit: FuncUnit, mm: ModuleModel, cm: Optional[ClassModel]):
    w = _FunctionWalker(unit, mm, cm)
    w.walk(unit.node.body)
    # every lock this function acquires lexically (edges only record
    # acquisitions made while something else was already held)
    for node in _own_statements(unit.node.body):
        if isinstance(node, ast.With):
            for item in node.items:
                lock = _lock_of(item.context_expr, mm, cm)
                if lock is not None:
                    unit.lexical_acquires.add(lock.lock_id)
        elif isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute) \
                and node.func.attr == "acquire":
            lock = _lock_of(node.func.value, mm, cm)
            if lock is not None:
                unit.lexical_acquires.add(lock.lock_id)


# ---------------------------------------------------------------------------
# fixpoints
# ---------------------------------------------------------------------------


def _propagate_ctxs(cm: ClassModel) -> None:
    for unit in cm.funcs.values():
        unit.ctxs = set(unit.direct_ctxs)
    changed = True
    while changed:
        changed = False
        for unit in cm.funcs.values():
            for site in unit.calls:
                callee = cm.funcs.get(site.callee)
                if callee is None:
                    continue
                add = unit.ctxs - callee.ctxs
                if add:
                    callee.ctxs |= add
                    changed = True
    # a unit nothing reaches and nothing registered: treat as caller-
    # entered (we cannot prove it is internal-only dead code)
    for unit in cm.funcs.values():
        if not unit.ctxs:
            unit.ctxs = {CTX_CALLER}
            unit.external = True


def _infer_entry_locks(cm: ClassModel) -> None:
    universe = frozenset(info.lock_id for info in cm.locks.values())
    for unit in cm.funcs.values():
        unit.entry_locks = frozenset() if unit.external else universe
    changed = True
    while changed:
        changed = False
        for unit in cm.funcs.values():
            if unit.external:
                continue
            sites = [s for caller in cm.funcs.values() for s in caller.calls
                     if s.callee == unit.name]
            if not sites:
                new = frozenset()
            else:
                new = universe
                for s in sites:
                    new &= (s.lexical_locks | s.func.entry_locks)
            if new != unit.entry_locks:
                unit.entry_locks = new
                changed = True


def _close_acquires(cm: ClassModel) -> None:
    for unit in cm.funcs.values():
        unit.trans_acquires = set(unit.lexical_acquires)
    changed = True
    while changed:
        changed = False
        for unit in cm.funcs.values():
            for site in unit.calls:
                callee = cm.funcs.get(site.callee)
                if callee is None:
                    continue
                add = callee.trans_acquires - unit.trans_acquires
                if add:
                    unit.trans_acquires |= add
                    changed = True


def interprocedural_edges(cm: ClassModel) -> List[LockEdge]:
    """Edges crossing a call: lock(s) held at a call site x every lock the
    callee transitively acquires."""
    out: List[LockEdge] = []
    lock_kinds = {info.lock_id: info.kind for info in cm.locks.values()}
    for unit in cm.funcs.values():
        for site in unit.calls:
            callee = cm.funcs.get(site.callee)
            if callee is None:
                continue
            held = site.lexical_locks | unit.entry_locks
            for h in held:
                for a in callee.trans_acquires:
                    if h == a and lock_kinds.get(a) in ("rlock", "condition"):
                        continue  # reentrant self-acquire is fine
                    out.append(LockEdge(h, a, site.line, cm.module, unit,
                                        via_call=site.callee))
    return out


def lexical_edges(module: Module) -> List[LockEdge]:
    return list(_module_edges.get(id(module), []))


def build_models(project: Project) -> List[ModuleModel]:
    _module_edges.clear()
    models = []
    for module in project.modules:
        if not in_scope(module):
            continue
        models.append(build_module_model(module))
    return models
