"""graftlint rule engine: pure-AST jit-hygiene analysis.

The linter answers one question per rule: *could this line knock a hot
path out of XLA?* — a host sync mid-step, a Python side effect baked
into a trace, a silent recompile per iteration. The hard part is
scoping: ``print`` in the trainer's host loop is fine, ``print`` in the
jitted step body is a trace-time landmine. So the engine first infers
which functions are **jit-scoped** (traced by jax), then applies the
line rules only inside those.

Jit-scope inference (two passes over the whole linted file set):

1. per-file: parse, track import aliases, index every function (incl.
   nested and methods), and mark *roots* — functions decorated with or
   passed to ``jax.jit`` / ``shard_map`` / ``pmap`` / ``vmap`` /
   ``grad`` / ``checkpoint`` / ``lax.scan``-family wrappers (the
   control-flow primitives trace their bodies from ANY caller, jitted
   or not — a ``lax.scan`` body in a host function is still traced).
   A wrapper whose argument is a *call* of a local function (the
   factory idiom this codebase uses everywhere:
   ``jax.jit(self._make_decode_horizon())``,
   ``jax.shard_map(_train_body(...))``) marks the factory's *nested*
   functions as traced — the factory body itself runs at build time —
   and a body reaching the wrapper through a local variable
   (``body = make_body(...); lax.scan(body, ...)``) resolves through
   the assignment.
2. global: propagate scope through the call graph — a traced function's
   callees are traced too, resolved through module-level names and
   intra-package ``from``-imports (``serving.engine`` calling
   ``inference.generate._block_decode_slots`` is resolved across
   files).

This is deliberately static and approximate: no jax import, no
execution, milliseconds over the whole package. Known limits are
documented per rule; escape hatches are per-line suppressions and the
committed baseline (see :mod:`.lint`).
"""

from __future__ import annotations

import ast
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Set, Tuple

RULES: Dict[str, str] = {
    "GL000": "file does not parse (syntax error)",
    "GL101": "host sync inside jit-traced code (.item(), float()/int() on "
             "a traced value, np.asarray/np.array, jax.device_get, "
             "block_until_ready)",
    "GL102": "print/logging side effect inside jit-traced code (runs at "
             "trace time only, or crashes on tracers — use "
             "jax.debug.print)",
    "GL103": "wall clock or host RNG inside jit-traced code (time.*, "
             "stdlib random.*, np.random.* — baked in at trace time; use "
             "jax.random)",
    "GL104": "mutation of enclosing-scope state inside jit-traced code "
             "(global/nonlocal or captured-container mutation — silent "
             "under jit: runs once at trace time)",
    "GL105": "jax.jit constructed inside a loop body (a fresh jit wrapper "
             "per iteration retraces/recompiles every time — hoist it)",
    "GL106": "Python branch on a traced argument of a jitted function "
             "(TracerBoolConversionError, or a recompile per value if "
             "made static — use lax.cond/lax.select or static_argnames)",
    "GL107": "mutable (unhashable) default on a static jit argument "
             "(TypeError at call time, or identity-keyed retraces)",
    "GL108": "train-step-shaped jit (state in, updated state out) without "
             "donate_argnums — the old state stays resident, doubling "
             "state HBM",
    "GL109": "PartitionSpec axis name not declared by any mesh in the "
             "linted files (typo'd axis names fail far from here, at "
             "sharding time)",
    "GL110": "device scalar built from a Python value inside a "
             "lax.scan/cond/while body (jnp.int32(i), jnp.asarray(c) — "
             "the body retraces per host call and each constant is an "
             "implicit H2D the transfer sentinel only catches at "
             "runtime; stage it outside, or thread it through the "
             "carry)",
    "GL111": "broad except (bare, Exception, BaseException) that "
             "swallows the error — no re-raise, the bound exception "
             "unused, nothing logged: a fault domain that eats its "
             "faults cannot be recovered OR debugged (record the "
             "error, re-raise, or narrow the except)",
    "GL112": "graftscope emission or datetime wall-clock read inside "
             "jit-traced code — the timestamp is a trace-time "
             "constant and the event records ONCE, at trace time: a "
             "silent lie on the timeline (emit at host boundaries — "
             "drain, admission, metric fetch; bare time.* reads are "
             "GL103's)",
    "GL113": "profiler misuse: jax.profiler.start_trace with no "
             "reachable stop_trace (an unstopped trace buffers "
             "forever and the .xplane.pb never flushes — the run "
             "ends with NO artifact), or profiler trace "
             "control (utils.profiler.trace / jax.profiler.start_"
             "trace) inside jit-traced code (runs once at trace "
             "time; the profiled region is a lie)",
    "GL114": "signal.signal installing a fresh handler without "
             "capturing the previous one (no signal.getsignal in "
             "scope) — the displaced handler is DISCARDED: a second "
             "registrant (preemption checkpointing, drain, an "
             "external supervisor's hook) silently stops firing; "
             "capture with getsignal and CHAIN it, as the trainer's "
             "_install_preemption_handler does",
    "GL115": "wall-clock timing around a dispatch-only jitted call "
             "with no block_until_ready/device sync between the "
             "start and the closing clock read — jax dispatch is "
             "async, so the stopwatch measures ENQUEUE latency, not "
             "execution: the reported number is a lie that gets "
             "faster the less the host waits (sync the result — "
             "block_until_ready / device_get / profiler.sync — "
             "inside the timed region, the bench.py readback "
             "discipline)",
    "GL116": "Python control flow coercing a traced array to bool "
             "inside jit-traced code (`if accepted:`, `while mask:`, "
             "`bool(tracer)` on a jnp/jax-produced value — the "
             "accept-mask bug class: TracerBoolConversionError at "
             "trace time, which only explodes when the branch is "
             "finally traced; keep acceptance/freeze logic as array "
             "masking — jnp.where/lax.select/lax.cond)",
    "GL117": "blocking socket op with no timeout/deadline in scope "
             "(.recv/.recv_into/.recvfrom/.accept/.makefile, a "
             "sock.connect, or socket.create_connection without a "
             "timeout, in a scope — function, class, or module top "
             "level — with no settimeout/setdefaulttimeout/"
             "create_connection(timeout=)/run_with_timeout/"
             "*ensure_timeout establishing a bound): the "
             "distributed-hang class — a silent peer parks the "
             "process forever, with no named error and no timeline "
             "(graftwire's sockets are all deadline-bounded; keep it "
             "that way)",
    "GL118": "child-process spawn with no reaping evidence in scope "
             "(subprocess.Popen or multiprocessing.Process in a "
             "scope — function, class, or module top level — with no "
             ".wait/.join/.kill/.terminate/.communicate anywhere in "
             "that scope chain): the orphan-child class — a spawned "
             "replica/worker that nothing ever reaps leaks a zombie "
             "on every crash path and outlives the run holding "
             "ports, devices and file locks (graftscale's "
             "ProcessReplicaSpawner discipline: every Popen has a "
             "wait-then-kill release in the same class; "
             "subprocess.run/check_call/check_output self-reap)",
    "GL119": "lock-order cycle across the package lock graph (lock B "
             "acquired while holding A at one site, A while holding B "
             "at another — directly or through the resolved call "
             "graph; re-acquiring a non-reentrant threading.Lock "
             "already held reports as a one-lock cycle): two threads "
             "entering in opposite order deadlock permanently with no "
             "named error — pick ONE global acquisition order "
             "(graftrace reports the full cycle with every "
             "acquisition site)",
    "GL120": "blocking operation under a held lock (socket recv/"
             "accept/connect/sendall, time.sleep, subprocess run/"
             "wait/communicate, os.fsync, Thread.join, wire RPC "
             ".call — direct, through resolved callees, or through a "
             "function passed as an argument inside the lock scope): "
             "every thread contending that lock parks behind one "
             "slow peer/disk/child for the full wait — the exact "
             "class PR 15's review fixed by hand in WireServer "
             "(kill_connections queued behind a drain handler "
             "holding the verb lock); move the slow work outside "
             "the lock or give it its own lock",
    "GL121": "thread-shared mutable attribute with no common lock in "
             "evidence (attribute written outside __init__ from a "
             "Thread(target=...) entry point's reachable body and "
             "accessed from methods outside that closure, with no "
             "single lock held at every involved site): the lost-"
             "update / torn-read class that only surfaces under "
             "load — guard every access with ONE shared lock, or "
             "confine the attribute to a single thread",
    "GL122": "copy-on-send in a wire path (``.tobytes()``, "
             "``b''.join(...)``, or ``bytes(buf)`` inside a scope "
             "that also calls ``.sendall``/``.sendmsg``): the frame "
             "was about to be handed to the kernel, and this call "
             "duplicated the payload in Python first — at KV-block "
             "size that is a second multi-MB copy per RPC on the "
             "PageTransfer hot path (graftlink's discipline: the "
             "header prefix plus raw numpy memoryview segments ride "
             "a scatter-gather sendmsg; nothing is assembled)",
    "GL123": "resource acquired with an escaping path that skips its "
             "release (pool grant / socket / thread / file / "
             "PageTransfer still owned at an early return, an "
             "unwinding raise, a risky call with no try/finally, or "
             "a loop iteration end): the leaked grant is capacity "
             "another request never gets back — release it, move "
             "ownership explicitly (return / store-into-owner / "
             "consuming call), or guard the gap",
    "GL124": "double-release: a release of a resource that EVERY "
             "path already released (straight-line repeat, a finally "
             "duplicating the body's release, a release after both "
             "branches released): the pool free list corrupts (or "
             "another holder's live grant is freed under it) with no "
             "named error at the true culprit — release exactly "
             "once, on exactly one path",
    "GL125": "ownership ambiguity: a pooled resource (slot/page/"
             "buffer) stored into the same self.<attr> from two or "
             "more call paths while NO method of the class releases "
             "through that attribute — every path assumes another "
             "is the owner and nobody frees; give the attribute one "
             "releasing owner or release before storing",
}

# wrappers that COMPILE (jit family) — GL105/106/107/108 anchor on these
_JIT_DOTTED = {
    "jax.jit", "jax.pjit", "jax.experimental.pjit.pjit",
}
# wrappers that TRACE their function argument(s)
_TRACE_DOTTED = _JIT_DOTTED | {
    "jax.shard_map",
    "jax.pmap", "jax.vmap", "jax.grad", "jax.value_and_grad",
    "jax.checkpoint", "jax.remat",
    "jax.lax.scan", "jax.lax.cond", "jax.lax.while_loop",
    "jax.lax.fori_loop", "jax.lax.map", "jax.lax.switch",
}
_TIME_ATTRS = {"time", "perf_counter", "monotonic", "process_time",
               "sleep", "time_ns", "perf_counter_ns", "monotonic_ns"}
# graftscope emission helpers (GL112): timestamps read at trace time
# record one constant event — never inside traced scope
_SCOPE_EMITTERS = {"emit", "emit_span", "span", "flight_dump"}
_DATETIME_CLOCKS = {"now", "utcnow", "today"}
_LOG_ATTRS = {"debug", "info", "warning", "warn", "error", "critical",
              "exception", "log"}
_LOG_BASES = {"logger", "log", "LOG", "logging"}
_MUTATORS = {"append", "extend", "insert", "add", "update", "pop",
             "setdefault", "remove", "discard", "clear", "popitem"}
# Pallas kernel refs: subscript-STORES to `*_ref` names are the Pallas
# memory model (o_ref[...] = acc), not a Python side effect
_REF_NAME = re.compile(r"(^|_)refs?$")
_STATIC_ATTRS = {"shape", "ndim", "size", "dtype", "sharding"}
_AXIS_KWARGS = {"axis_name", "seq_axis", "pipe_axis", "bn_axis"}
_STATE_PARAMS = {"state", "train_state"}


@dataclass(frozen=True)
class Finding:
    path: str
    line: int
    col: int
    rule: str
    message: str

    def render(self) -> str:
        return f"{self.path}:{self.line}:{self.col}: {self.rule} {self.message}"


@dataclass
class _Func:
    node: ast.AST  # FunctionDef | AsyncFunctionDef
    file: "_File"
    qual: str
    parent: Optional["_Func"]
    params: List[str] = field(default_factory=list)
    pos_params: List[str] = field(default_factory=list)
    calls: Set[str] = field(default_factory=set)
    nested: Dict[str, "_Func"] = field(default_factory=dict)
    jit_scoped: bool = False
    # body of a control-flow primitive (lax.scan/cond/while/fori/
    # switch/map) — traced from ANY caller, jitted or not (GL110)
    ctrl_body: bool = False
    # direct jit root: (statics, donate_seen, site_line) — only set when
    # the function NAME is wrapped/decorated by jax.jit itself, so its
    # static_argnames/argnums are knowable (GL106/107/108 need this)
    root_statics: Optional[Set[str]] = None
    root_donate: bool = False
    root_line: int = 0

    @property
    def name(self) -> str:
        return self.node.name


class _File:
    def __init__(self, path: str, modkey: Tuple[str, ...], tree: ast.AST,
                 lines: List[str]):
        self.path = path
        self.modkey = modkey
        self.tree = tree
        self.lines = lines
        self.origins: Dict[str, str] = {}  # local name -> dotted origin
        # local name -> (modkey, original name) for intra-package imports
        self.pkg_imports: Dict[str, Tuple[Tuple[str, ...], str]] = {}
        self.funcs: List[_Func] = []
        self.by_name: Dict[str, _Func] = {}  # module+method level defs
        self.owner: Dict[int, Optional[_Func]] = {}  # id(node) -> func


def _dotted(expr: ast.AST, file: _File) -> Optional[str]:
    """Resolve an expression to a dotted origin path: ``np.asarray`` ->
    ``numpy.asarray`` (through import aliases), bare names through
    ``from x import y`` origins. None when not a name/attribute chain."""
    parts: List[str] = []
    node = expr
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if not isinstance(node, ast.Name):
        return None
    base = file.origins.get(node.id, node.id)
    parts.append(base)
    return ".".join(reversed(parts))


def _iter_own(func_node: ast.AST):
    """Yield every node lexically in ``func_node``'s body but not inside
    a nested def/class (those have their own _Func entries)."""
    stack = list(ast.iter_child_nodes(func_node))
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            continue
        yield node
        stack.extend(ast.iter_child_nodes(node))


def _const_str_seq(node: ast.AST) -> Optional[List[str]]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return [node.value]
    if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
        out = []
        for el in node.elts:
            if isinstance(el, ast.Constant) and isinstance(el.value, str):
                out.append(el.value)
            else:
                return None
        return out
    return None


def _const_int_seq(node: ast.AST) -> Optional[List[int]]:
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return [node.value]
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for el in node.elts:
            if isinstance(el, ast.Constant) and isinstance(el.value, int):
                out.append(el.value)
            else:
                return None
        return out
    return None


def _modkey_for(path: str, root_parent: Optional[str]) -> Tuple[str, ...]:
    import os

    if root_parent:
        rel = os.path.relpath(os.path.abspath(path),
                              os.path.abspath(root_parent))
    else:
        rel = os.path.basename(path)
    parts = rel.replace("\\", "/").split("/")
    if parts and parts[-1].endswith(".py"):
        parts[-1] = parts[-1][:-3]
    if parts and parts[-1] == "__init__":
        parts = parts[:-1]
    return tuple(p for p in parts if p and p != ".")


# --------------------------------------------------------------- pass 1

def _collect_file(path: str, src: str, modkey: Tuple[str, ...]) -> _File:
    tree = ast.parse(src, filename=path)
    f = _File(path, modkey, tree, src.splitlines())

    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                f.origins[alias.asname or alias.name.split(".")[0]] = (
                    alias.name if alias.asname else alias.name.split(".")[0])
        elif isinstance(node, ast.ImportFrom):
            if node.level:
                base = modkey[:-node.level] if node.level <= len(modkey) \
                    else ()
                mod = base + tuple((node.module or "").split(".")
                                   if node.module else ())
                mod = tuple(p for p in mod if p)
                for alias in node.names:
                    f.pkg_imports[alias.asname or alias.name] = (
                        mod, alias.name)
                    f.origins[alias.asname or alias.name] = ".".join(
                        mod + (alias.name,))
            else:
                mod = node.module or ""
                for alias in node.names:
                    f.origins[alias.asname or alias.name] = (
                        f"{mod}.{alias.name}" if mod else alias.name)
                    if mod:
                        f.pkg_imports[alias.asname or alias.name] = (
                            tuple(mod.split(".")), alias.name)

    # function index with lexical parents
    def index(node: ast.AST, parent: Optional[_Func], prefix: str):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                qual = f"{prefix}{child.name}"
                fn = _Func(child, f, qual, parent)
                a = child.args
                fn.pos_params = [x.arg for x in a.posonlyargs + a.args]
                fn.params = list(fn.pos_params) + \
                    [x.arg for x in a.kwonlyargs]
                f.funcs.append(fn)
                if parent is None:
                    f.by_name.setdefault(child.name, fn)
                else:
                    parent.nested[child.name] = fn
                index(child, fn, qual + ".")
            elif isinstance(child, ast.ClassDef):
                # methods register at module visibility by simple name
                # (resolves the ``jax.jit(self._insert_fn)`` idiom)
                index(child, parent, f"{prefix}{child.name}.")
            else:
                index(child, parent, prefix)

    index(tree, None, "")
    # methods (parent None but nested in classes) land in by_name via
    # the parent-None branch above; also make every top-level-class
    # method resolvable
    for fn in f.funcs:
        if fn.parent is None:
            f.by_name.setdefault(fn.name, fn)

    # per-func call sets (own body only)
    for fn in f.funcs:
        for node in _iter_own(fn.node):
            if isinstance(node, ast.Call):
                if isinstance(node.func, ast.Name):
                    fn.calls.add(node.func.id)
                elif (isinstance(node.func, ast.Attribute)
                      and isinstance(node.func.value, ast.Name)
                      and node.func.value.id in ("self", "cls")):
                    fn.calls.add(node.func.attr)
    return f


# ------------------------------------------------------- root detection

def _is_trace_wrapper(dotted: Optional[str]) -> bool:
    if not dotted:
        return False
    return dotted in _TRACE_DOTTED


def _is_jit(dotted: Optional[str]) -> bool:
    return dotted in _JIT_DOTTED


def _resolve_local(file: _File, name: str,
                   scope: Optional[_Func]) -> Optional[_Func]:
    fn = scope
    while fn is not None:
        if name in fn.nested:
            return fn.nested[name]
        fn = fn.parent
    return file.by_name.get(name)


def _descendants(fn: _Func) -> List[_Func]:
    out = []
    stack = list(fn.nested.values())
    while stack:
        x = stack.pop()
        out.append(x)
        stack.extend(x.nested.values())
    return out


def _jit_statics(call_kwargs, target: Optional[_Func]) -> Set[str]:
    statics: Set[str] = set()
    for kw in call_kwargs:
        if kw.arg == "static_argnames":
            names = _const_str_seq(kw.value)
            if names:
                statics.update(names)
        elif kw.arg == "static_argnums" and target is not None:
            nums = _const_int_seq(kw.value)
            if nums:
                for i in nums:
                    if 0 <= i < len(target.pos_params):
                        statics.add(target.pos_params[i])
    return statics


def _donate_seen(call_kwargs) -> bool:
    return any(kw.arg in ("donate_argnums", "donate_argnames")
               for kw in call_kwargs)


def _mark_root(target: _Func, statics: Set[str], donate: bool, line: int):
    target.jit_scoped = True
    if target.root_statics is None:
        target.root_statics = statics
        target.root_donate = donate
        target.root_line = line


def _scan_roots(files: Sequence[_File], index) -> List[_Func]:
    """Find every jit/trace root; returns the seed list for the global
    closure. ``index[(modkey, name)]`` resolves cross-file targets."""
    seeds: List[_Func] = []

    def resolve_arg(file: _File, scope: Optional[_Func], arg: ast.AST,
                    *, factories: bool = True,
                    seen: Optional[Set[str]] = None) -> List[_Func]:
        """Functions a wrapper argument refers to. A direct Name/self
        attr resolves to its def; a Call of a local def is the factory
        idiom — the factory's nested defs are the traced ones; a Name
        bound by a local assignment (``body = make_body(...)`` before
        ``lax.scan(body, ...)``) resolves through the assignment's
        value (``seen`` breaks self-referential chains)."""
        if isinstance(arg, ast.Name):
            t = _resolve_local(file, arg.id, scope)
            if t is None and arg.id in file.pkg_imports:
                t = index.get(file.pkg_imports[arg.id])
            if t is not None:
                return [t]
            if not factories or (seen and arg.id in seen):
                return []
            # control-flow-primitive bodies often reach the wrapper
            # through a local variable; chase the assignment(s)
            seen = (seen or set()) | {arg.id}
            space = (_iter_own(scope.node) if scope is not None
                     else ast.iter_child_nodes(file.tree))
            out: List[_Func] = []
            for node in space:
                if isinstance(node, ast.Assign) and any(
                        isinstance(t_, ast.Name) and t_.id == arg.id
                        for t_ in node.targets):
                    out.extend(resolve_arg(file, scope, node.value,
                                           seen=seen))
            return out
        if (isinstance(arg, ast.Attribute)
                and isinstance(arg.value, ast.Name)
                and arg.value.id in ("self", "cls")):
            t = file.by_name.get(arg.attr)
            return [t] if t is not None else []
        if factories and isinstance(arg, ast.Call):
            inner = resolve_arg(file, scope, arg.func, factories=False,
                                seen=seen)
            out = []
            for fac in inner:
                out.extend(_descendants(fac))
            return out
        return []

    for file in files:
        # decorators
        for fn in file.funcs:
            for dec in fn.node.decorator_list:
                d = _dotted(dec, file)
                if _is_trace_wrapper(d):
                    if _is_jit(d):
                        _mark_root(fn, set(), False, fn.node.lineno)
                    fn.jit_scoped = True
                    seeds.append(fn)
                elif isinstance(dec, ast.Call):
                    dc = _dotted(dec.func, file)
                    if _is_jit(dc):
                        _mark_root(fn, _jit_statics(dec.keywords, fn),
                                   _donate_seen(dec.keywords),
                                   fn.node.lineno)
                        seeds.append(fn)
                    elif (dc == "functools.partial" and dec.args
                          and _is_jit(_dotted(dec.args[0], file))):
                        _mark_root(fn, _jit_statics(dec.keywords, fn),
                                   _donate_seen(dec.keywords),
                                   fn.node.lineno)
                        seeds.append(fn)
                    elif _is_trace_wrapper(dc):
                        fn.jit_scoped = True
                        seeds.append(fn)
        # wrapper call sites
        for node in ast.walk(file.tree):
            if not isinstance(node, ast.Call):
                continue
            d = _dotted(node.func, file)
            if not _is_trace_wrapper(d):
                continue
            scope = file.owner.get(id(node))
            func_args = node.args
            is_ctrl = bool(d) and d.endswith(
                ("scan", "while_loop", "fori_loop", "cond", "switch",
                 "map"))
            if is_ctrl:
                candidates = func_args  # body position varies — take all
            else:
                candidates = func_args[:1]
            for arg in candidates:
                for target in resolve_arg(file, scope, arg):
                    if (_is_jit(d) and isinstance(
                            arg, (ast.Name, ast.Attribute))):
                        _mark_root(target,
                                   _jit_statics(node.keywords, target),
                                   _donate_seen(node.keywords),
                                   node.lineno)
                    target.jit_scoped = True
                    if is_ctrl:
                        target.ctrl_body = True
                    seeds.append(target)
    return seeds


def _fill_owners(file: _File):
    def walk(node: ast.AST, owner: Optional[_Func]):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                fn = next((x for x in file.funcs if x.node is child), None)
                file.owner[id(child)] = owner
                walk(child, fn)
            else:
                file.owner[id(child)] = owner
                walk(child, owner)

    walk(file.tree, None)


def _propagate(files: Sequence[_File], index, seeds: List[_Func]):
    """Call-graph closure: a traced function's callees are traced."""
    work = list(seeds)
    while work:
        fn = work.pop()
        for name in fn.calls:
            t = _resolve_local(fn.file, name, fn)
            if t is None and name in fn.file.pkg_imports:
                t = index.get(fn.file.pkg_imports[name])
            if t is not None and not t.jit_scoped:
                t.jit_scoped = True
                work.append(t)


# --------------------------------------------------------------- rules

def _is_shape_static(expr: ast.AST) -> bool:
    """True when the expression is trace-time static by construction:
    a constant, a len() call, or anything reading .shape/.ndim/etc."""
    if isinstance(expr, ast.Constant):
        return True
    for node in ast.walk(expr):
        if isinstance(node, ast.Attribute) and node.attr in _STATIC_ATTRS:
            return True
        if (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
                and node.func.id == "len"):
            return True
    return not any(isinstance(n, (ast.Name, ast.Subscript, ast.Call))
                   for n in ast.walk(expr))


def _local_names(fn: _Func) -> Set[str]:
    names = set(fn.params)
    a = fn.node.args
    if a.vararg:
        names.add(a.vararg.arg)
    if a.kwarg:
        names.add(a.kwarg.arg)
    for node in _iter_own(fn.node):
        if isinstance(node, ast.Name) and isinstance(node.ctx, ast.Store):
            names.add(node.id)
        elif isinstance(node, (ast.For, ast.AsyncFor)):
            for t in ast.walk(node.target):
                if isinstance(t, ast.Name):
                    names.add(t.id)
        elif isinstance(node, ast.withitem) and node.optional_vars:
            for t in ast.walk(node.optional_vars):
                if isinstance(t, ast.Name):
                    names.add(t.id)
        elif isinstance(node, ast.NamedExpr) and isinstance(
                node.target, ast.Name):
            names.add(node.target.id)
    names.update(fn.nested)
    return names


def _traced_names_in_test(test: ast.AST, traced: Set[str]) -> List[str]:
    """Names from ``traced`` whose VALUE the test depends on — skipping
    is/is-not None checks, .shape/.ndim/.dtype reads, isinstance, len."""
    hits: List[str] = []

    def visit(node: ast.AST):
        if isinstance(node, ast.Compare) and all(
                isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops):
            return
        if isinstance(node, ast.Attribute):
            if node.attr in _STATIC_ATTRS:
                return
            visit(node.value)
            return
        if isinstance(node, ast.Call):
            d = node.func
            if isinstance(d, ast.Name) and d.id in ("isinstance", "len",
                                                    "getattr", "hasattr"):
                return
            for a in node.args:
                visit(a)
            return
        if isinstance(node, ast.Name):
            if node.id in traced:
                hits.append(node.id)
            return
        for child in ast.iter_child_nodes(node):
            visit(child)

    visit(test)
    return hits


def _check_jit_scoped_body(fn: _Func, out: List[Finding]):
    file = fn.file
    path = file.path

    def add(node, rule, msg):
        out.append(Finding(path, node.lineno, node.col_offset, rule, msg))

    locals_ = None  # computed lazily for GL104
    for node in _iter_own(fn.node):
        if isinstance(node, (ast.Global, ast.Nonlocal)):
            kind = "global" if isinstance(node, ast.Global) else "nonlocal"
            add(node, "GL104",
                f"{kind} statement in jit-traced `{fn.qual}` — the "
                "rebinding happens once at trace time, not per step")
            continue
        if isinstance(node, ast.Call):
            d = _dotted(node.func, file)
            # ---- GL101: host syncs
            if isinstance(node.func, ast.Attribute):
                if node.func.attr == "item" and not node.args:
                    add(node, "GL101",
                        f".item() in jit-traced `{fn.qual}` forces a "
                        "device->host sync (trace error under jit)")
                    continue
                if node.func.attr == "block_until_ready":
                    add(node, "GL101",
                        f".block_until_ready() in jit-traced `{fn.qual}`"
                        " — a host sync; jit output is already async")
                    continue
            if d in ("jax.device_get", "jax.block_until_ready"):
                add(node, "GL101",
                    f"{d} in jit-traced `{fn.qual}` forces a device->"
                    "host sync")
                continue
            if d in ("numpy.asarray", "numpy.array"):
                add(node, "GL101",
                    f"{d.replace('numpy', 'np')} in jit-traced "
                    f"`{fn.qual}` materializes on host (use jnp, or "
                    "hoist to the caller)")
                continue
            if (isinstance(node.func, ast.Name)
                    and node.func.id in ("float", "int", "bool")
                    and len(node.args) == 1 and not node.keywords
                    and not _is_shape_static(node.args[0])):
                arg = node.args[0]
                # a bare Name is only knowably traced when it is a
                # non-static param of a DIRECT jit root; in closure-
                # propagated functions plain names are usually Python
                # config captured at build time (e.g. int(block_k))
                name_traced = (
                    isinstance(arg, ast.Name)
                    and fn.root_statics is not None
                    and arg.id in set(fn.params) - fn.root_statics)
                if name_traced or not isinstance(arg, ast.Name):
                    add(node, "GL101",
                        f"{node.func.id}() on a traced value in "
                        f"`{fn.qual}` is a host sync "
                        "(ConcretizationTypeError under jit)")
                    continue
            # ---- GL102: print / logging
            if isinstance(node.func, ast.Name) and node.func.id == "print":
                add(node, "GL102",
                    f"print() in jit-traced `{fn.qual}` fires at trace "
                    "time only — use jax.debug.print")
                continue
            if (isinstance(node.func, ast.Attribute)
                    and node.func.attr in _LOG_ATTRS
                    and isinstance(node.func.value, ast.Name)
                    and (node.func.value.id in _LOG_BASES
                         or (file.origins.get(node.func.value.id, "")
                             .split(".")[0] == "logging"))):
                add(node, "GL102",
                    f"logging call in jit-traced `{fn.qual}` fires at "
                    "trace time only — use jax.debug.print")
                continue
            # ---- GL103: wall clock / host RNG
            if d:
                root = d.split(".")[0]
                if root == "time" and d.split(".")[-1] in _TIME_ATTRS:
                    add(node, "GL103",
                        f"{d} in jit-traced `{fn.qual}` is baked in as "
                        "a constant at trace time")
                    continue
                if root == "random" and any(
                        v == "random" or v.startswith("random.")
                        for v in file.origins.values()):
                    # d is already alias-resolved ("import random as
                    # rnd" and "from random import randint" both land
                    # here); the origins scan rules out a mere local
                    # variable that happens to be NAMED random
                    add(node, "GL103",
                        f"stdlib {d} in jit-traced `{fn.qual}` draws "
                        "once at trace time — use jax.random")
                    continue
                if d.startswith("numpy.random."):
                    add(node, "GL103",
                        f"np.random in jit-traced `{fn.qual}` draws "
                        "once at trace time — use jax.random")
                    continue
                # ---- GL112: graftscope emission / datetime clocks —
                # the silent-lie class GL103's time.* check cannot
                # see (the clock read hides inside the emit helper,
                # or behind the datetime module)
                parts = d.split(".")
                if (len(parts) >= 2 and parts[-2] == "scope"
                        and parts[-1] in _SCOPE_EMITTERS):
                    add(node, "GL112",
                        f"graftscope {parts[-1]}() in jit-traced "
                        f"`{fn.qual}` stamps a trace-time constant "
                        "and records ONE event, at trace time — a "
                        "silent lie on the timeline; emit at a host "
                        "boundary instead")
                    continue
                if (root == "datetime"
                        and parts[-1] in _DATETIME_CLOCKS):
                    add(node, "GL112",
                        f"{d} in jit-traced `{fn.qual}` is baked in "
                        "as a trace-time constant (the datetime "
                        "spelling of GL103's wall-clock rule)")
                    continue
                # ---- GL113: profiler control from inside the trace —
                # start/stop_trace and the utils.profiler.trace ctx
                # manager run ONCE at trace time, so the "profiled"
                # region covers tracing, not execution
                if (d in ("jax.profiler.start_trace",
                          "jax.profiler.stop_trace")
                        or (len(parts) >= 2 and parts[-2] == "profiler"
                            and parts[-1] == "trace")):
                    add(node, "GL113",
                        f"profiler trace control ({parts[-1]}) in "
                        f"jit-traced `{fn.qual}` runs once at trace "
                        "time — profile around the jitted call, not "
                        "inside it")
                    continue
            continue
        # ---- GL104: captured-container mutation. Only BARE statement
        # calls (result discarded) — a used return value means a
        # functional API like optimizer.update(grads, ...), not a
        # container mutation (dict.update/list.append return None).
        if (isinstance(node, ast.Expr)
                and isinstance(node.value, ast.Call)
                and isinstance(node.value.func, ast.Attribute)
                and node.value.func.attr in _MUTATORS
                and isinstance(node.value.func.value, ast.Name)
                and node.value.func.value.id not in ("self", "cls")):
            call = node.value
            if locals_ is None:
                locals_ = _local_names(fn)
            if call.func.value.id not in locals_:
                add(call, "GL104",
                    f"`{call.func.value.id}.{call.func.attr}(...)` "
                    f"in jit-traced `{fn.qual}` mutates enclosing-"
                    "scope state once at trace time, not per step")
            continue
        if isinstance(node, (ast.Assign, ast.AugAssign)):
            targets = (node.targets if isinstance(node, ast.Assign)
                       else [node.target])
            for t in targets:
                if (isinstance(t, ast.Subscript)
                        and isinstance(t.value, ast.Name)
                        and not _REF_NAME.search(t.value.id)):
                    if locals_ is None:
                        locals_ = _local_names(fn)
                    if t.value.id not in locals_ | {"self", "cls"}:
                        add(node, "GL104",
                            f"subscript-assign to captured "
                            f"`{t.value.id}` in jit-traced `{fn.qual}` "
                            "mutates enclosing-scope state at trace "
                            "time")


def _check_traced_branches(fn: _Func, out: List[Finding]):
    """GL106 — only on DIRECT jit roots, whose static_argnames/argnums
    are parseable (closure-propagated functions receive values whose
    staticness is unknowable statically: skipping them keeps the rule
    high-precision)."""
    if fn.root_statics is None:
        return
    traced = set(fn.params) - fn.root_statics - {"self", "cls"}
    if not traced:
        return
    for node in _iter_own(fn.node):
        if isinstance(node, (ast.If, ast.While, ast.IfExp, ast.Assert)):
            test = node.test
            hits = _traced_names_in_test(test, traced)
            if hits:
                out.append(Finding(
                    fn.file.path, node.lineno, node.col_offset, "GL106",
                    f"branch on traced argument(s) {sorted(set(hits))} "
                    f"of jitted `{fn.qual}` — TracerBoolConversionError "
                    "at trace time (use lax.cond/lax.select, or declare "
                    "the arg in static_argnames)"))


# GL116: jax/jnp calls whose RESULT is host metadata, not a traced
# array — branching on these is ordinary Python (keep the rule
# high-precision; anything else under the jax/jnp namespaces is
# assumed array-valued)
_GL116_STATIC_TAILS = {
    "ShapeDtypeStruct", "dtype", "device_count", "local_device_count",
    "default_backend", "devices", "process_index", "process_count",
    "tree_structure", "eval_shape", "named_scope",
}


def _gl116_array_call(node: ast.AST, file: _File) -> bool:
    """Is ``node`` a call into the jax/jnp namespaces that returns a
    traced array (by the static-tail allowlist)?"""
    if not isinstance(node, ast.Call):
        return False
    d = _dotted(node.func, file)
    if not d:
        return False
    parts = d.split(".")
    if parts[0] != "jax":  # jnp resolves to jax.numpy via origins
        return False
    return parts[-1] not in _GL116_STATIC_TAILS


def _check_traced_bool_coercion(fn: _Func, out: List[Finding]):
    """GL116 — Python `if`/`while`/`bool()` on a LOCAL value produced
    by a jnp/jax call inside jit-traced code. Complements GL106 (which
    covers branches on traced PARAMS of direct jit roots): the
    accept-mask bug class builds the mask locally (`accepted =
    jnp.logical_and(...)`) and branches on it — invisible to GL106,
    and it only explodes at trace time. High-precision by
    construction: only bare names assigned from jax/jnp array calls
    (or direct jnp calls in the test) are flagged."""
    file = fn.file
    traced_locals: Set[str] = set()
    for node in _iter_own(fn.node):
        if isinstance(node, ast.Assign) and len(node.targets) == 1:
            t = node.targets[0]
            if (isinstance(t, ast.Name)
                    and _gl116_array_call(node.value, file)):
                traced_locals.add(t.id)

    def name_hits(test) -> List[str]:
        if isinstance(test, ast.Name):
            return [test.id] if test.id in traced_locals else []
        if (isinstance(test, ast.UnaryOp)
                and isinstance(test.op, ast.Not)):
            return name_hits(test.operand)
        if isinstance(test, ast.BoolOp):
            hits: List[str] = []
            for v in test.values:
                hits.extend(name_hits(v))
            return hits
        return []

    def add(node, what):
        out.append(Finding(
            fn.file.path, node.lineno, node.col_offset, "GL116",
            f"{what} in jit-traced `{fn.qual}` coerces a traced "
            "array to a Python bool — TracerBoolConversionError at "
            "trace time (the accept-mask bug class); keep it as "
            "array masking (jnp.where/lax.select) or lax.cond"))

    for node in _iter_own(fn.node):
        if isinstance(node, (ast.If, ast.While, ast.IfExp)):
            kind = ("while" if isinstance(node, ast.While) else "if")
            hits = name_hits(node.test)
            if hits:
                add(node, f"`{kind} {'/'.join(sorted(set(hits)))}:` "
                          "branch on a jnp-produced value")
                continue
            if _gl116_array_call(node.test, file):
                add(node, f"`{kind}` on a jnp/jax call result")
        elif (isinstance(node, ast.Call)
              and isinstance(node.func, ast.Name)
              and node.func.id == "bool" and len(node.args) == 1
              and not node.keywords
              and isinstance(node.args[0], ast.Name)
              and node.args[0].id in traced_locals):
            add(node, f"bool({node.args[0].id}) on a jnp-produced "
                      "value")


def _check_static_defaults(fn: _Func, out: List[Finding]):
    """GL107: a static jit arg whose default is a mutable literal."""
    if fn.root_statics is None or not fn.root_statics:
        return
    a = fn.node.args
    pos = a.posonlyargs + a.args
    defaults: Dict[str, ast.AST] = {}
    for arg, dflt in zip(pos[len(pos) - len(a.defaults):], a.defaults):
        defaults[arg.arg] = dflt
    for arg, dflt in zip(a.kwonlyargs, a.kw_defaults):
        if dflt is not None:
            defaults[arg.arg] = dflt
    for name in sorted(fn.root_statics):
        dflt = defaults.get(name)
        if isinstance(dflt, (ast.List, ast.Dict, ast.Set)) or (
                isinstance(dflt, ast.Call)
                and isinstance(dflt.func, ast.Name)
                and dflt.func.id in ("list", "dict", "set")):
            out.append(Finding(
                fn.file.path, fn.node.lineno, fn.node.col_offset, "GL107",
                f"static jit argument `{name}` of `{fn.qual}` has a "
                "mutable (unhashable) default — jit statics must hash "
                "(use a tuple / frozenset / None)"))


def _check_missing_donate(fn: _Func, out: List[Finding]):
    """GL108: jitted state-in/state-out function without donation."""
    if fn.root_statics is None or fn.root_donate:
        return
    params = [p for p in fn.params if p not in ("self", "cls")]
    if not params or params[0] not in _STATE_PARAMS:
        return
    state = params[0]
    replaces = any(
        isinstance(node, ast.Call)
        and isinstance(node.func, ast.Attribute)
        and node.func.attr == "replace"
        and isinstance(node.func.value, ast.Name)
        and node.func.value.id == state
        for node in _iter_own(fn.node))
    if replaces:
        out.append(Finding(
            fn.file.path, fn.root_line, 0, "GL108",
            f"jit of `{fn.qual}` takes `{state}` and returns an updated "
            "copy but declares no donate_argnums — the old state stays "
            "resident, doubling state HBM (donate_argnums=(0,))"))


_JNP_SCALAR_CTORS = {
    "asarray", "array", "int8", "int16", "int32", "int64", "uint8",
    "uint16", "uint32", "uint64", "float16", "bfloat16", "float32",
    "float64",
}


def _module_numeric_const(file: _File, name: str) -> bool:
    """True when ``name`` is assigned a numeric literal at MODULE
    level (``EPS = 1e-6``) — the module-scope half of GL110's
    'Python scalar captured from a host scope'."""
    for node in ast.iter_child_nodes(file.tree):
        if isinstance(node, ast.Assign) and any(
                isinstance(t, ast.Name) and t.id == name
                for t in node.targets):
            return (isinstance(node.value, ast.Constant)
                    and isinstance(node.value.value, (int, float, bool)))
    return False


def _check_ctrl_body_scalars(fn: _Func, out: List[Finding]):
    """GL110 — only in control-flow bodies (``lax.scan``/``cond``/
    ``while``/``fori``/``switch``), which jax re-traces on EVERY host
    call when the wrapper runs outside jit: a ``jnp.int32(chunk)`` /
    ``jnp.asarray(0.5)`` built from a Python value there materializes
    a fresh device constant per call — the implicit H2D class the
    runtime sentinel (``guard_transfers``) catches only when traffic
    actually hits it. Flags numeric literals and names captured from
    HOST scopes; operands that are body parameters/locals, captured
    from an enclosing TRACED function (tracers), or shape-derived are
    exempt — and so is the WHOLE body when any lexical ancestor is
    itself jit-traced (the wrapper then runs under jit: the body
    traces once per compile and its constants bake into the
    executable — no per-call H2D)."""
    if not fn.ctrl_body:
        return
    ancestor = fn.parent
    while ancestor is not None:
        if ancestor.jit_scoped:
            return
        ancestor = ancestor.parent
    file = fn.file
    locals_ = _local_names(fn)
    for node in _iter_own(fn.node):
        if not isinstance(node, ast.Call) or not node.args:
            continue
        d = _dotted(node.func, file)
        if (not d or not d.startswith("jax.numpy.")
                or d.split(".")[-1] not in _JNP_SCALAR_CTORS):
            continue
        arg = node.args[0]
        flagged = False
        if isinstance(arg, ast.Constant) and isinstance(
                arg.value, (int, float, bool)):
            flagged = True
        elif (isinstance(arg, ast.Name) and arg.id not in locals_
                and not _is_shape_static(arg)):
            parent = fn.parent
            while parent is not None:
                if (arg.id in _local_names(parent)
                        or arg.id in parent.nested):
                    # bound by an enclosing fn: a tracer when that fn
                    # is itself traced, a Python scalar when it is a
                    # host factory/driver
                    flagged = not parent.jit_scoped
                    break
                parent = parent.parent
            else:
                # no enclosing fn binds it: a module-level NUMERIC
                # constant (EPS = 1e-6) is a host scalar too — same
                # fresh-device-constant-per-trace hazard; anything
                # else at module scope (arrays, config objects) is
                # not knowably a Python scalar, so it stays exempt
                flagged = _module_numeric_const(file, arg.id)
        if flagged:
            out.append(Finding(
                file.path, node.lineno, node.col_offset, "GL110",
                f"`{ast.unparse(node) if hasattr(ast, 'unparse') else d}"
                f"` builds a device scalar from a Python value inside "
                f"control-flow body `{fn.qual}` — re-traced per host "
                "call, an implicit H2D each time (stage it outside the "
                "body or thread it through the carry)"))


_BROAD_EXC = {"Exception", "BaseException"}


def _is_broad_handler(handler: ast.ExceptHandler, file: _File) -> bool:
    """Bare ``except:``, ``except Exception``, ``except BaseException``
    (alone or anywhere in a tuple)."""
    t = handler.type
    if t is None:
        return True
    elts = t.elts if isinstance(t, ast.Tuple) else [t]
    for el in elts:
        d = _dotted(el, file)
        if d and d.split(".")[-1] in _BROAD_EXC:
            return True
    return False


def _handler_records(handler: ast.ExceptHandler, file: _File) -> bool:
    """Does the handler re-raise, use the bound exception (format it,
    store it, wrap it), or at least emit through a logging-ish call?
    Any of these makes the swallow deliberate and observable."""
    bound = handler.name
    for stmt in handler.body:
        for node in ast.walk(stmt):
            if isinstance(node, ast.Raise):
                return True
            if (bound and isinstance(node, ast.Name)
                    and node.id == bound):
                return True
            if isinstance(node, ast.Call):
                d = _dotted(node.func, file)
                last = d.split(".")[-1] if d else ""
                if (last in _LOG_ATTRS or last in ("print", "warn")
                        or d == "warnings.warn"):
                    return True
    return False


def _check_swallowed_except(file: _File, out: List[Finding]):
    """GL111 — a broad except whose handler swallows the error: no
    re-raise, the bound exception never read, nothing logged. Silent
    fault-swallowing is the anti-pattern the graftfault layer exists
    to kill: a retry path can only recover what it can SEE, and a
    fleet can only page on what is recorded. The optional-dependency
    probe idiom (a ``try`` whose body is imports only) is exempt —
    there the absence of the module IS the information."""
    for node in ast.walk(file.tree):
        if not isinstance(node, ast.Try):
            continue
        import_probe = bool(node.body) and all(
            isinstance(s, (ast.Import, ast.ImportFrom))
            for s in node.body)
        if import_probe:
            continue
        for handler in node.handlers:
            if not _is_broad_handler(handler, file):
                continue
            if _handler_records(handler, file):
                continue
            shown = ("except:" if handler.type is None else
                     f"except {ast.unparse(handler.type)}:"
                     if hasattr(ast, "unparse") else "except ...:")
            out.append(Finding(
                file.path, handler.lineno, handler.col_offset, "GL111",
                f"`{shown}` swallows the error — no re-raise, the "
                "exception unused, nothing logged; record it, re-raise "
                "it, or narrow the except (silent fault-swallowing "
                "hides exactly the failures graftfault injects)"))


def _check_unpaired_trace(file: _File, out: List[Finding]):
    """GL113 (host half) — ``jax.profiler.start_trace`` in a file with
    NO reachable ``stop_trace``. Reachability is approximated at file
    granularity (a paired stop in the same function, a finally block,
    or a sibling wrapper method all count): the bug class this catches
    is the stop being FORGOTTEN entirely, which leaves the trace
    buffering until process exit and never flushes an .xplane.pb —
    a whole run's profiling silently lost. Starts inside
    jit-traced scope are the trace-time-misuse half's (skipped here
    so one line never double-reports)."""
    stop_seen = False
    starts = []
    for node in ast.walk(file.tree):
        if not isinstance(node, ast.Call):
            continue
        d = _dotted(node.func, file)
        if d == "jax.profiler.stop_trace":
            stop_seen = True
        elif d == "jax.profiler.start_trace":
            owner = file.owner.get(id(node))
            if owner is None or not owner.jit_scoped:
                starts.append(node)
    if stop_seen:
        return
    for node in starts:
        out.append(Finding(
            file.path, node.lineno, node.col_offset, "GL113",
            "jax.profiler.start_trace with no reachable stop_trace in "
            "this file — an unstopped trace buffers until process "
            "exit and never flushes its .xplane.pb (use "
            "utils.profiler.trace, a try/finally, or call stop_trace)"))


# GL115: host clocks that start/stop a stopwatch, and the calls that
# actually force device completion inside a timed region
_GL115_CLOCKS = {"time.perf_counter", "time.monotonic", "time.time"}
_GL115_SYNC_ATTRS = {"block_until_ready", "item"}
_GL115_SYNC_DOTTED = {"jax.block_until_ready", "jax.device_get",
                      "jax.effects_barrier", "numpy.asarray",
                      "numpy.array"}


def _is_gl115_sync(node: ast.Call, file: _File) -> bool:
    if (isinstance(node.func, ast.Attribute)
            and node.func.attr in _GL115_SYNC_ATTRS):
        return True
    d = _dotted(node.func, file)
    if not d:
        return False
    # utils.profiler.sync (the framework's one D2H-forcing readback —
    # what bench.py's window discipline uses) counts however imported
    return d in _GL115_SYNC_DOTTED or d.endswith("profiler.sync")


def _check_unsynced_timing(file: _File, out: List[Finding]):
    """GL115 (host half) — per HOST function scope (and module scope),
    the stopwatch idiom ``t0 = clock(); ... jitted(...) ...;
    dt = clock() - t0`` with NO device sync between the start and the
    closing read. jax dispatch is asynchronous: the jitted call
    returns the moment the work is enqueued, so the measured interval
    is dispatch overhead, not execution — serving_bench's round-1
    class of lie. Deliberately precise over complete: only bare-name
    clock starts (``t0 = time.perf_counter()``), only closes that
    subtract a tracked start (a fresh clock read, or another tracked
    clock name, minus it), and only dispatch calls the file can prove
    are jitted (a direct jit root, or a name assigned from
    ``jax.jit(...)``). A sync anywhere in [start, close] — including
    the trainer's ``device_get`` windowed fetch and bench.py's
    ``profiler.sync`` readback — silences the finding."""
    module_jit_names = {
        t.id for node in ast.iter_child_nodes(file.tree)
        if isinstance(node, ast.Assign)
        and isinstance(node.value, ast.Call)
        and _is_jit(_dotted(node.value.func, file))
        for t in node.targets if isinstance(t, ast.Name)}

    def is_jit_dispatch(node: ast.Call, scope: Optional[_Func],
                        jit_names: Set[str]) -> bool:
        f = node.func
        if isinstance(f, ast.Call):  # jax.jit(f)(x) inline
            return _is_jit(_dotted(f.func, file))
        if not isinstance(f, ast.Name):
            return False
        if f.id in jit_names or f.id in module_jit_names:
            return True
        target = _resolve_local(file, f.id, scope)
        return target is not None and target.root_statics is not None

    scopes: List[Optional[_Func]] = [None] + [
        fn for fn in file.funcs if not fn.jit_scoped]
    for scope in scopes:
        nodes = list(_iter_own(scope.node) if scope is not None
                     else _iter_own(file.tree))
        # local names bound from jax.jit(...) in this scope
        jit_names = {
            t.id for node in nodes
            if isinstance(node, ast.Assign)
            and isinstance(node.value, ast.Call)
            and _is_jit(_dotted(node.value.func, file))
            for t in node.targets if isinstance(t, ast.Name)}
        # clock-start bindings: name -> lines it was bound at
        starts: Dict[str, List[int]] = {}
        sync_lines: List[int] = []
        dispatch_lines: List[int] = []
        closes: List[Tuple[ast.AST, str]] = []  # (sub node, start name)
        for node in nodes:
            if isinstance(node, ast.Assign) and isinstance(
                    node.value, ast.Call) and _dotted(
                    node.value.func, file) in _GL115_CLOCKS:
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        starts.setdefault(t.id, []).append(node.lineno)
            if isinstance(node, ast.Call):
                if _is_gl115_sync(node, file):
                    sync_lines.append(node.lineno)
                elif is_jit_dispatch(node, scope, jit_names):
                    dispatch_lines.append(node.lineno)
            if (isinstance(node, ast.BinOp)
                    and isinstance(node.op, ast.Sub)
                    and isinstance(node.right, ast.Name)):
                # candidate close; judged after the loop, once every
                # start binding is known (_iter_own's visit order is
                # not source order)
                closes.append((node, node.right.id))
        for node, start_name in closes:
            if start_name not in starts:
                continue
            left = node.left
            left_is_clock = (
                (isinstance(left, ast.Call)
                 and _dotted(left.func, file) in _GL115_CLOCKS)
                or (isinstance(left, ast.Name) and left.id in starts
                    and left.id != start_name))
            if not left_is_clock:
                continue
            close_line = node.lineno
            bound = [ln for ln in starts.get(start_name, [])
                     if ln < close_line]
            if not bound:
                continue
            start_line = max(bound)
            timed_dispatch = any(start_line < ln <= close_line
                                 for ln in dispatch_lines)
            synced = any(start_line <= ln <= close_line
                         for ln in sync_lines)
            if timed_dispatch and not synced:
                out.append(Finding(
                    file.path, close_line, node.col_offset, "GL115",
                    f"wall-clock close over `{start_name}` times a "
                    "dispatch-only jitted call with no "
                    "block_until_ready/device sync inside the timed "
                    "region — async dispatch makes this latency a "
                    "lie (sync the result before stopping the "
                    "clock, as bench.py's readback does)"))


def _check_signal_discard(file: _File, out: List[Finding]):
    """GL114 — ``signal.signal(sig, handler)`` installing a FRESH
    handler (a lambda, or a name resolving to a def in this file)
    from a scope with no ``signal.getsignal`` call: the previous
    handler is discarded, so whoever registered it (the trainer's
    preemption checkpointing, the serving drain hook, an external
    supervisor) silently stops seeing the signal. The clean shape —
    capture with ``getsignal``, chain in the new handler, restore on
    teardown — is what ``trainer._install_preemption_handler`` and
    ``heal.install_drain_handler`` do. Restores are exempt: passing a
    non-def value (a saved previous handler, ``signal.SIG_DFL``, a
    conditional of the two) is putting a handler BACK, not displacing
    one."""
    def scope_nodes(owner):
        if owner is not None:
            return _iter_own(owner.node)
        # module scope: top-level statements, minus def/class bodies
        return _iter_own(file.tree)

    for node in ast.walk(file.tree):
        if not isinstance(node, ast.Call):
            continue
        if _dotted(node.func, file) != "signal.signal":
            continue
        if len(node.args) < 2:
            continue
        handler = node.args[1]
        owner = file.owner.get(id(node))
        fresh = isinstance(handler, ast.Lambda)
        if isinstance(handler, ast.Name):
            fresh = _resolve_local(file, handler.id, owner) is not None
        if not fresh:
            continue  # restore / passthrough of a saved handler
        captured = any(
            isinstance(n, ast.Call)
            and _dotted(n.func, file) == "signal.getsignal"
            for n in scope_nodes(owner))
        if captured:
            continue
        out.append(Finding(
            file.path, node.lineno, node.col_offset, "GL114",
            "signal.signal installs a fresh handler but the previous "
            "one is never captured (no signal.getsignal in this "
            "scope) — it is DISCARDED, and whoever registered it "
            "(preemption checkpoint, drain hook, supervisor) silently "
            "stops firing; capture it and chain (see "
            "trainer._install_preemption_handler)"))


_BLOCKING_SOCKET_ATTRS = {"recv", "recv_into", "recvfrom", "accept",
                          "makefile"}
_TIMEOUT_SETTERS = {"settimeout", "setdefaulttimeout"}


def _check_blocking_socket(file: _File, out: List[Finding]):
    """GL117 — blocking socket operations with no timeout/deadline
    IN SCOPE: the distributed-hang class graftwire must never
    reintroduce. A ``.recv``/``.recv_into``/``.recvfrom``/
    ``.accept``/``.makefile`` call (any receiver — pipes and socket
    wrappers block the same way), a ``*sock*.connect(...)``, or a
    ``socket.create_connection`` WITHOUT a timeout argument is flagged
    unless deadline evidence exists in the call's scope chain:

    - the enclosing function (any enclosing def) contains a
      ``settimeout``/``setdefaulttimeout`` call, a
      ``create_connection(..., timeout)`` or a ``run_with_timeout``/
      ``*ensure_timeout`` call (the repo's canonical guard helper);
    - or the enclosing CLASS does, anywhere in its body — the
      configure-in-``__init__``, read-in-a-method shape;
    - or the module's top level does.

    Evidence in an UNRELATED sibling function does not count: a
    timeout someone set on a different socket in a different scope is
    exactly the false comfort that leaves the accept loop unbounded.
    """
    evidence_fns: Set[int] = set()
    evidence_cls: Set[int] = set()
    module_evidence = [False]
    # (call node, enclosing-fn id chain, enclosing-class id, label)
    blocking: List[Tuple[ast.Call, Tuple[int, ...], Optional[int],
                         str]] = []

    def _has_timeout_arg(call: ast.Call) -> bool:
        # timeout=None is an EXPLICIT request for an unbounded
        # blocking connect — the exact hang this rule targets — so
        # only a non-None timeout counts as a deadline
        for kw in call.keywords:
            if kw.arg == "timeout":
                return not (isinstance(kw.value, ast.Constant)
                            and kw.value.value is None)
        if len(call.args) >= 2:  # create_connection(addr, timeout)
            arg = call.args[1]
            return not (isinstance(arg, ast.Constant)
                        and arg.value is None)
        return False

    def _recv_name(expr: ast.AST) -> str:
        if isinstance(expr, ast.Name):
            return expr.id
        if isinstance(expr, ast.Attribute):
            return expr.attr
        return ""

    def _classify(call: ast.Call, fns: Tuple[int, ...],
                  cls: Optional[int]) -> None:
        func = call.func
        attr = func.attr if isinstance(func, ast.Attribute) else None
        d = _dotted(func, file) or ""
        last = d.split(".")[-1] if d else (
            func.id if isinstance(func, ast.Name) else (attr or ""))
        evidence = (attr in _TIMEOUT_SETTERS
                    or last in _TIMEOUT_SETTERS
                    or last == "run_with_timeout"
                    or last.endswith("ensure_timeout"))
        if last == "create_connection":
            if _has_timeout_arg(call):
                evidence = True
            else:
                blocking.append((call, fns, cls,
                                 "socket.create_connection without a "
                                 "timeout argument"))
        if evidence:
            evidence_fns.update(fns)
            if cls is not None:
                evidence_cls.add(cls)
            if not fns and cls is None:
                module_evidence[0] = True
            return
        if attr in _BLOCKING_SOCKET_ATTRS:
            blocking.append((call, fns, cls, f".{attr}()"))
        elif (attr == "connect"
              and "sock" in _recv_name(func.value).lower()):
            blocking.append((call, fns, cls, ".connect() on a socket"))

    def _visit(node: ast.AST, fns: Tuple[int, ...],
               cls: Optional[int]) -> None:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            fns = fns + (id(node),)
        elif isinstance(node, ast.ClassDef):
            cls = id(node)
        if isinstance(node, ast.Call):
            _classify(node, fns, cls)
        for child in ast.iter_child_nodes(node):
            _visit(child, fns, cls)

    _visit(file.tree, (), None)
    for call, fns, cls, label in blocking:
        if any(f in evidence_fns for f in fns):
            continue
        if cls is not None and cls in evidence_cls:
            continue
        if module_evidence[0]:
            continue
        out.append(Finding(
            file.path, call.lineno, call.col_offset, "GL117",
            f"blocking socket op ({label}) with no timeout/deadline "
            "in scope — a silent peer hangs this call forever with "
            "no named error; settimeout/create_connection(timeout=)/"
            "run_with_timeout bound it (the graftwire discipline: "
            "every socket op has a deadline)"))


_REAP_ATTRS = {"wait", "join", "kill", "terminate", "communicate"}


def _check_spawn_reap(file: _File, out: List[Finding]):
    """GL118 — child-process spawn with no reaping evidence IN SCOPE:
    the orphan-child class graftscale must never reintroduce. A
    ``subprocess.Popen(...)`` or ``multiprocessing.Process(...)``
    call is flagged unless reaping evidence exists in the call's
    scope chain:

    - the enclosing function (any enclosing def) contains a
      ``.wait``/``.join``/``.kill``/``.terminate``/``.communicate``
      attribute call;
    - or the enclosing CLASS does, anywhere in its body — the
      spawn-in-``spawn``, reap-in-``release`` shape
      (ProcessReplicaSpawner's discipline);
    - or, for a spawn at MODULE scope only, the module's top level
      does (a script's spawn-then-join main block).

    ``subprocess.run``/``check_call``/``check_output`` self-reap and
    are never flagged. Evidence in an UNRELATED sibling function does
    not count, and module-level evidence never excuses a spawn inside
    a function or class: a ``wait`` on a different child in a
    different scope is exactly the false comfort that leaks the
    zombie.
    """
    evidence_fns: Set[int] = set()
    evidence_cls: Set[int] = set()
    module_evidence = [False]
    spawns: List[Tuple[ast.Call, Tuple[int, ...], Optional[int],
                       str]] = []

    def _classify(call: ast.Call, fns: Tuple[int, ...],
                  cls: Optional[int]) -> None:
        func = call.func
        attr = func.attr if isinstance(func, ast.Attribute) else None
        if attr in _REAP_ATTRS:
            evidence_fns.update(fns)
            if cls is not None:
                evidence_cls.add(cls)
            if not fns and cls is None:
                module_evidence[0] = True
            return
        d = _dotted(func, file) or ""
        if d == "subprocess.Popen" or d.endswith(".subprocess.Popen"):
            spawns.append((call, fns, cls, "subprocess.Popen"))
        elif d in ("multiprocessing.Process",
                   "torch.multiprocessing.Process") \
                or d.endswith(".multiprocessing.Process"):
            spawns.append((call, fns, cls, "multiprocessing.Process"))

    def _visit(node: ast.AST, fns: Tuple[int, ...],
               cls: Optional[int]) -> None:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            fns = fns + (id(node),)
        elif isinstance(node, ast.ClassDef):
            cls = id(node)
        if isinstance(node, ast.Call):
            _classify(node, fns, cls)
        for child in ast.iter_child_nodes(node):
            _visit(child, fns, cls)

    _visit(file.tree, (), None)
    for call, fns, cls, label in spawns:
        if any(f in evidence_fns for f in fns):
            continue
        if cls is not None and cls in evidence_cls:
            continue
        # module-level evidence only excuses module-scope spawns: a
        # top-level join() must not grant file-wide amnesty to spawns
        # buried in unrelated functions
        if module_evidence[0] and not fns and cls is None:
            continue
        out.append(Finding(
            file.path, call.lineno, call.col_offset, "GL118",
            f"child-process spawn ({label}) with no reaping evidence "
            "in scope — nothing here ever wait/join/kill/terminates "
            "the child: every crash path leaks a zombie that "
            "outlives the run holding ports and file locks; reap it "
            "in the same scope (the graftscale spawner discipline: "
            "wait with a deadline, then kill LOUDLY), or use "
            "subprocess.run, which self-reaps"))


_SEND_ATTRS = {"sendall", "sendmsg"}


def _check_copy_on_send(file: _File, out: List[Finding]):
    """GL122 — copy-on-send in wire paths: the throughput class
    graftlink exists to kill. Inside any scope (function chain or
    module top level) that also calls ``.sendall``/``.sendmsg``, an
    assembly copy of the outgoing payload is flagged:

    - ``arr.tobytes()`` — a full copy of an array that could ride as
      a zero-copy ``memoryview`` segment of a scatter-gather send;
    - ``b"".join(...)`` (any bytes-literal ``.join``) — frame
      assembly by concatenation;
    - ``bytes(buf)`` with a non-constant argument — materializing a
      buffer that ``sendmsg`` would take as-is.

    A scope with no send call is never flagged: builders like
    ``pack_frame`` legitimately assemble (tests, faults, fallbacks
    consume the assembled representation); the copy only costs when
    it sits on the send path itself.
    """
    send_fns: Set[int] = set()
    module_send = [False]
    copies: List[Tuple[ast.Call, Tuple[int, ...], str]] = []

    def _classify(call: ast.Call, fns: Tuple[int, ...]) -> None:
        func = call.func
        if isinstance(func, ast.Attribute):
            if func.attr in _SEND_ATTRS:
                send_fns.update(fns)
                if not fns:
                    module_send[0] = True
                return
            if func.attr == "tobytes" and not call.args:
                copies.append((call, fns,
                               ".tobytes() copies the whole array"))
                return
            if (func.attr == "join"
                    and isinstance(func.value, ast.Constant)
                    and isinstance(func.value.value,
                                   (bytes, bytearray))):
                copies.append((call, fns,
                               "b''.join assembles the frame by "
                               "concatenation"))
                return
        elif (isinstance(func, ast.Name) and func.id == "bytes"
                and len(call.args) == 1 and not call.keywords
                and not isinstance(call.args[0], ast.Constant)):
            copies.append((call, fns,
                           "bytes(...) materializes the buffer"))

    def _visit(node: ast.AST, fns: Tuple[int, ...]) -> None:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            fns = fns + (id(node),)
        if isinstance(node, ast.Call):
            _classify(node, fns)
        for child in ast.iter_child_nodes(node):
            _visit(child, fns)

    _visit(file.tree, ())
    for call, fns, label in copies:
        on_send_path = (any(f in send_fns for f in fns)
                        or (not fns and module_send[0]))
        if not on_send_path:
            continue
        out.append(Finding(
            file.path, call.lineno, call.col_offset, "GL122",
            f"copy-on-send in a wire path ({label}) in a scope that "
            "also sends — the payload is duplicated in Python right "
            "before the kernel takes it, a second multi-MB copy per "
            "RPC at KV-block size; hand the header prefix plus raw "
            "memoryview segments to a scatter-gather sendmsg "
            "instead (the graftlink discipline: nothing on the send "
            "path is assembled)"))


def _check_jit_in_loop(file: _File, out: List[Finding]):
    """GL105: jax.jit(...) lexically inside a for/while body."""
    loops: List[ast.AST] = [n for n in ast.walk(file.tree)
                            if isinstance(n, (ast.For, ast.AsyncFor,
                                              ast.While))]
    for loop in loops:
        stack = [n for part in ("body", "orelse")
                 for n in getattr(loop, part, [])]
        while stack:
            node = stack.pop()
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                continue  # a def in a loop body runs on call, not per iter
            if isinstance(node, ast.Call):
                d = _dotted(node.func, file)
                if _is_jit(d) or (
                        d == "functools.partial" and node.args
                        and _is_jit(_dotted(node.args[0], file))):
                    out.append(Finding(
                        file.path, node.lineno, node.col_offset, "GL105",
                        "jax.jit constructed inside a loop body — each "
                        "iteration builds a fresh wrapper with an empty "
                        "trace cache (recompiles every pass); hoist the "
                        "jit out of the loop"))
            stack.extend(ast.iter_child_nodes(node))


def _collect_axes(files: Sequence[_File]) -> Set[str]:
    axes: Set[str] = set()
    for file in files:
        for node in ast.walk(file.tree):
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    if (isinstance(t, ast.Name)
                            and t.id.upper().endswith("_AXIS")
                            and isinstance(node.value, ast.Constant)
                            and isinstance(node.value.value, str)):
                        axes.add(node.value.value)
            elif isinstance(node, ast.Call):
                d = _dotted(node.func, file)
                if d and d.split(".")[-1] == "Mesh" and len(node.args) >= 2:
                    names = _const_str_seq(node.args[1])
                    if names:
                        axes.update(names)
                for kw in node.keywords:
                    if kw.arg == "axis_names":
                        names = _const_str_seq(kw.value)
                        if names:
                            axes.update(names)
                    elif (kw.arg in _AXIS_KWARGS
                          and isinstance(kw.value, ast.Constant)
                          and isinstance(kw.value.value, str)):
                        axes.add(kw.value.value)
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                a = node.args
                pos = a.posonlyargs + a.args
                for arg, dflt in zip(pos[len(pos) - len(a.defaults):],
                                     a.defaults):
                    if (arg.arg in _AXIS_KWARGS
                            and isinstance(dflt, ast.Constant)
                            and isinstance(dflt.value, str)):
                        axes.add(dflt.value)
                for arg, dflt in zip(a.kwonlyargs, a.kw_defaults):
                    if (dflt is not None and arg.arg in _AXIS_KWARGS
                            and isinstance(dflt, ast.Constant)
                            and isinstance(dflt.value, str)):
                        axes.add(dflt.value)
    return axes


def _check_pspec_axes(file: _File, axes: Set[str], out: List[Finding]):
    """GL109: string axis in a PartitionSpec literal must be declared."""
    if not axes:
        return
    for node in ast.walk(file.tree):
        if not isinstance(node, ast.Call):
            continue
        d = _dotted(node.func, file)
        if not d or d.split(".")[-1] != "PartitionSpec":
            continue
        for arg in node.args:
            for el in ([arg] if not isinstance(arg, (ast.Tuple, ast.List))
                       else arg.elts):
                if (isinstance(el, ast.Constant)
                        and isinstance(el.value, str)
                        and el.value not in axes):
                    out.append(Finding(
                        file.path, node.lineno, node.col_offset, "GL109",
                        f"PartitionSpec axis {el.value!r} is not an axis "
                        f"of any mesh declared in the linted files "
                        f"(known: {sorted(axes)}) — typo'd axes fail "
                        "far away, at sharding time"))


# ------------------------------------------------------------ top level

def analyze_files(paths: Sequence[str],
                  package_parent: Optional[str] = None) -> List[Finding]:
    """Lint ``paths`` (Python files) as one closed world: jit scopes
    propagate across files through intra-package imports resolved
    relative to ``package_parent`` (the directory CONTAINING the
    package). Returns findings sorted by (path, line)."""
    files: List[_File] = []
    findings: List[Finding] = []
    for path in paths:
        try:
            with open(path, "r", encoding="utf-8") as fh:
                src = fh.read()
            f = _collect_file(path, src, _modkey_for(path, package_parent))
        except SyntaxError as e:
            findings.append(Finding(path, e.lineno or 0, 0, "GL000",
                                    f"does not parse: {e.msg}"))
            continue
        _fill_owners(f)
        files.append(f)

    index: Dict[Tuple[Tuple[str, ...], str], _Func] = {}
    for f in files:
        for name, fn in f.by_name.items():
            index.setdefault((f.modkey, name), fn)

    seeds = _scan_roots(files, index)
    _propagate(files, index, seeds)

    axes = _collect_axes(files)
    for f in files:
        _check_jit_in_loop(f, findings)
        _check_pspec_axes(f, axes, findings)
        _check_swallowed_except(f, findings)
        _check_unpaired_trace(f, findings)
        _check_signal_discard(f, findings)
        _check_blocking_socket(f, findings)
        _check_spawn_reap(f, findings)
        _check_copy_on_send(f, findings)
        _check_unsynced_timing(f, findings)
        for fn in f.funcs:
            if fn.jit_scoped:
                _check_jit_scoped_body(fn, findings)
                _check_traced_branches(fn, findings)
                _check_traced_bool_coercion(fn, findings)
                _check_static_defaults(fn, findings)
                _check_missing_donate(fn, findings)
                _check_ctrl_body_scalars(fn, findings)
    # graftrace: the GL119/GL120/GL121 concurrency pass shares this
    # file set and index (imported here to avoid a module cycle)
    from .concurrency import check_concurrency
    check_concurrency(files, index, findings)
    # graftlife: the GL123/GL124/GL125 resource-lifecycle pass —
    # same file set and index, same late import
    from .lifecycle import check_lifecycle
    check_lifecycle(files, index, findings)

    findings.sort(key=lambda x: (x.path, x.line, x.rule))
    return findings
