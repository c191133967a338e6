"""AST invariant checkers (photon-lint).

Each rule encodes a performance/correctness contract an earlier round
established by hand and a later tier could silently regress:

- ``jit-in-function``: no ``jax.jit`` / ``partial(jax.jit, ...)``
  constructed inside function bodies or loops.  A per-call jit wrapper
  owns a fresh executable cache, so every call re-traces and recompiles
  the identical program -- the exact recompile hazard PR 2 removed from
  the lambda-grid loop by hoisting per-chunk programs to module level.
  Jits must be module-level or memoized (an ``functools.lru_cache`` /
  ``functools.cache`` enclosing function is exempt).
- ``tracer-hygiene``: no ``np.*`` calls, ``float()``/``int()``/
  ``bool()``/``.item()`` casts, or ``if``-branching applied to values
  that flow from a jitted/vmapped function's array parameters
  (``static_argnums`` excluded).  Any of these forces a trace-time
  concretization error at best, a silent host round-trip at worst.
- ``unlocked-shared-write``: classes that spawn ``threading.Thread`` /
  ``ThreadPoolExecutor`` (or that own a lock) must mutate shared
  attributes under their lock or communicate via ``queue.Queue`` /
  ``threading.Event``.  Flags writes reachable from both the worker
  and the caller that are not lexically under a ``with self.<lock>:``.
- ``accumulator-dtype``: streaming metric/loss accumulators (classes
  with the ``update``/``result`` protocol) must fold on host in
  float64 -- accumulation expressions must not run through ``jnp``
  (device f32 folds) or explicit float32 casts.
- ``env-read``: no raw ``os.environ`` / ``os.getenv`` reads outside
  ``config.py``'s sanctioned registry (``config.read_env``) -- scatter
  env fallbacks are invisible configuration.
- ``swallowed-exception``: an ``except`` whose body only
  passes/continues/breaks/bare-returns — the failure is silently
  discarded
  (ISSUE 9: fault tolerance is only honest when every absorbed failure
  is reported, handled with a real fallback, or waived with a reason).
- ``eternal-wait``: in a thread-spawning class, a blocking wait with
  no timeout — ``queue.get()``, ``Event.wait()``, ``Thread.join()``,
  ``socket.recv()`` — can pin a thread forever when its peer dies
  (ISSUE 13: the serving tier's wedged-handler class of outage).
  Every cross-thread wait must be bounded, or waived with the reason
  the block is provably terminated (e.g. a close() sentinel).
- ``while-loop-carry-dtype``: a ``lax.while_loop`` body whose carry
  leaf changes dtype fails at trace time with an opaque
  body-function-output-mismatch error (ISSUE 17: an f64 cast — or a
  float literal folded into an int/bool carry — silently rewrites the
  leaf's dtype).  Flags mismatched-literal arithmetic on carry names
  inside while-body functions whose init dtype is statically inferable.
- ``slow-unmarked``: tests whose recorded tier-1 duration exceeds the
  threshold must carry ``@pytest.mark.slow`` so the tier-1 wall clock
  stops creeping (durations recorded once in
  ``tests/tier1_durations.json``; see PERF.md).

Waivers: a violation line may carry an inline waiver comment

    # photon-lint: disable=<rule>[,<rule>] (<reason>)

The reason is mandatory -- a waiver without one is ignored (and
reported), so every suppression documents why the contract does not
apply at that site.
"""

from __future__ import annotations

import ast
import dataclasses
import io
import json
import os
import re
import tokenize

# Test duration above which a test must be @pytest.mark.slow (seconds).
# Pinned at 10 s at introduction: the 5-10 s band holds ~30 more cases
# whose removal would take tier-1 below its seed pass-count floor;
# ratchet the threshold down as the fast tier grows (ISSUE 6 audit —
# the 15 functions over 10 s were marked, cutting ~266 s of tier-1
# wall clock; measurements in tests/tier1_durations.json).
SLOW_THRESHOLD_S = 10.0

# Recorded tier-1 durations (max over parametrizations, seconds),
# measured once per re-baseline -- see tests/tier1_durations.json.
DURATIONS_FILE = os.path.join("tests", "tier1_durations.json")

RULES = {
    "jit-in-function": (
        "jax.jit constructed inside a function body or loop "
        "(per-call recompile hazard; hoist to module level or memoize)"
    ),
    "tracer-hygiene": (
        "host-side numpy/cast/branch applied to a traced array value "
        "inside a jitted/vmapped function"
    ),
    "unlocked-shared-write": (
        "shared mutable attribute written without the owning lock in a "
        "thread-spawning class"
    ),
    "accumulator-dtype": (
        "streaming accumulator folds through jnp/float32 instead of "
        "host float64"
    ),
    "env-read": (
        "raw os.environ read outside config.py's sanctioned registry "
        "(use photon_ml_tpu.config.read_env)"
    ),
    "naked-clock": (
        "time.time() used in duration arithmetic; wall clock steps "
        "under NTP/suspend — use time.monotonic()/time.perf_counter()"
    ),
    "metric-name": (
        "telemetry counter/gauge/histogram registered under a name "
        "that is not a dotted lowercase identifier (namespace.metric)"
    ),
    "swallowed-exception": (
        "except handler silently discards the failure (pass/continue/"
        "break/bare return) without re-raising or logging — waiver "
        "with reason for deliberate best-effort sites"
    ),
    "eternal-wait": (
        "unbounded blocking wait (queue.get()/Event.wait()/"
        "Thread.join()/socket.recv() with no timeout) in a "
        "thread-spawning class — a dead peer pins the thread forever; "
        "bound it or waive with the termination argument"
    ),
    "collective-in-host-branch": (
        "psum/all_gather/... lexically inside a branch conditioned on "
        "the process identity (process_index()/host_id) — hosts that "
        "skip the branch never reach the collective and the fleet "
        "deadlocks at the barrier"
    ),
    "while-loop-carry-dtype": (
        "arithmetic on a lax.while_loop carry name whose literal "
        "operand changes the carry leaf's dtype (f64 cast, or a float "
        "literal on an int/bool carry) — the body/carry dtype mismatch "
        "fails at trace time with an opaque error"
    ),
    "slow-unmarked": (
        "test measured slower than the threshold lacks "
        "@pytest.mark.slow"
    ),
    "bad-waiver": (
        "photon-lint waiver without a (reason) — every suppression "
        "must say why the contract does not apply"
    ),
    "syntax-error": "file failed to parse",
}

_WAIVER_RE = re.compile(
    r"#\s*photon-lint:\s*disable=([\w,-]+)\s*(?:\((.*?)\))?")


def _comments(source: str):
    """(lineno, text, comment_only) for every real COMMENT token.

    ``comment_only`` is True when nothing but whitespace precedes the
    comment on its line.  Tokenization errors (the caller has already
    ast-parsed the file, so these are near-impossible) degrade to the
    comments seen so far."""
    out = []
    try:
        for tok in tokenize.generate_tokens(io.StringIO(source).readline):
            if tok.type == tokenize.COMMENT:
                out.append((tok.start[0], tok.string,
                            tok.line[: tok.start[1]].strip() == ""))
    except (tokenize.TokenError, IndentationError):  # photon-lint: disable=swallowed-exception (degrade to the comments seen so far, documented above)
        pass
    return out


@dataclasses.dataclass(frozen=True)
class Violation:
    path: str
    line: int
    rule: str
    message: str

    def __str__(self) -> str:
        return f"{self.path}:{self.line} {self.rule} {self.message}"

    def github(self) -> str:
        return (f"::error file={self.path},line={self.line},"
                f"title={self.rule}::{self.message}")


# ---------------------------------------------------------------------------
# Shared AST plumbing
# ---------------------------------------------------------------------------


def _parents(tree: ast.AST) -> dict:
    par: dict = {}
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            par[child] = node
    return par


def _ancestors(node: ast.AST, par: dict):
    n = par.get(node)
    while n is not None:
        yield n
        n = par.get(n)


def _dotted(node: ast.AST) -> str | None:
    """``a.b.c`` attribute/name chain as a string, else None."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _names_in(node: ast.AST) -> set[str]:
    return {n.id for n in ast.walk(node)
            if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load)}


def _is_jit_call(call: ast.Call) -> bool:
    """``jax.jit(...)`` or ``[functools.]partial(jax.jit, ...)``."""
    tgt = _dotted(call.func)
    if tgt in ("jax.jit", "jax.pmap"):
        return True
    if tgt in ("partial", "functools.partial") and call.args:
        return _dotted(call.args[0]) in ("jax.jit", "jax.pmap")
    return False


def _static_argnums(call: ast.Call) -> tuple[set[int], set[str]]:
    """Literal static_argnums / static_argnames from a jit call."""
    nums: set[int] = set()
    names: set[str] = set()
    for kw in call.keywords:
        if kw.arg == "static_argnums":
            v = kw.value
            elts = v.elts if isinstance(v, (ast.Tuple, ast.List)) else [v]
            for e in elts:
                if isinstance(e, ast.Constant) and isinstance(e.value, int):
                    nums.add(e.value)
        elif kw.arg == "static_argnames":
            v = kw.value
            elts = v.elts if isinstance(v, (ast.Tuple, ast.List)) else [v]
            for e in elts:
                if isinstance(e, ast.Constant) and isinstance(e.value, str):
                    names.add(e.value)
    return nums, names


class _FileContext:
    """One parsed source file + its waiver table."""

    def __init__(self, path: str, source: str):
        self.path = path
        self.source = source
        self.tree = ast.parse(source, filename=path)
        self.parents = _parents(self.tree)
        self.waivers: dict[int, set[str]] = {}
        self.bad_waivers: list[int] = []
        lines = source.splitlines()
        # Real COMMENT tokens only (tokenize): a waiver example quoted
        # inside a docstring/string literal must neither suppress the
        # next code line nor be reported as a bad waiver.
        for lineno, text, comment_only in _comments(source):
            m = _WAIVER_RE.search(text)
            if not m:
                continue
            rules = {r.strip() for r in m.group(1).split(",") if r.strip()}
            reason = (m.group(2) or "").strip()
            if not reason:
                self.bad_waivers.append(lineno)
                continue
            self.waivers.setdefault(lineno, set()).update(rules)
            # A waiver on a comment-only line covers the next code
            # line (the inline form rarely fits the line limit).
            if comment_only:
                nxt = lineno + 1
                while nxt <= len(lines) and (
                        not lines[nxt - 1].strip()
                        or lines[nxt - 1].strip().startswith("#")):
                    nxt += 1
                if nxt <= len(lines):
                    self.waivers.setdefault(nxt, set()).update(rules)

    def waived(self, line: int, rule: str) -> bool:
        return rule in self.waivers.get(line, ())


# ---------------------------------------------------------------------------
# Rule: jit-in-function
# ---------------------------------------------------------------------------


_MEMO_DECORATORS = ("functools.lru_cache", "lru_cache", "functools.cache",
                    "cache")


def _is_memoized(fn: ast.AST) -> bool:
    if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
        return False
    for dec in fn.decorator_list:
        d = dec.func if isinstance(dec, ast.Call) else dec
        if _dotted(d) in _MEMO_DECORATORS:
            return True
    return False


def check_jit_in_function(ctx: _FileContext):
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Call) and _is_jit_call(node):
            enclosing = None
            in_loop = False
            for anc in _ancestors(node, ctx.parents):
                if isinstance(anc, (ast.For, ast.While)):
                    in_loop = True
                if isinstance(anc, (ast.FunctionDef,
                                    ast.AsyncFunctionDef, ast.Lambda)):
                    enclosing = anc
                    break
            if enclosing is None and not in_loop:
                continue
            if enclosing is not None and _is_memoized(enclosing):
                continue
            # A decorator expression evaluates at def time, which for a
            # module-level def is module scope -- exempt.
            parent = ctx.parents.get(node)
            if (isinstance(parent, (ast.FunctionDef, ast.AsyncFunctionDef))
                    and node in parent.decorator_list
                    and parent is enclosing):
                continue
            where = ("a loop" if enclosing is None
                     else f"'{getattr(enclosing, 'name', '<lambda>')}'")
            yield Violation(
                ctx.path, node.lineno, "jit-in-function",
                f"jax.jit constructed inside {where}: every call "
                "re-traces and recompiles; hoist to module level or "
                "memoize (functools.lru_cache)")
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            # @jax.jit on a def nested inside another function: the
            # wrapper (and its compile cache) is rebuilt per outer call.
            for anc in _ancestors(node, ctx.parents):
                if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef,
                                    ast.Lambda)):
                    if _is_memoized(anc):
                        break
                    for dec in node.decorator_list:
                        # Bare @jax.jit (an Attribute — _dotted returns
                        # None for Call nodes) or @partial(jax.jit, …).
                        if _dotted(dec) in ("jax.jit", "jax.pmap") or (
                                isinstance(dec, ast.Call)
                                and _is_jit_call(dec)):
                            yield Violation(
                                ctx.path, node.lineno, "jit-in-function",
                                f"@jax.jit on '{node.name}' nested "
                                f"inside '{anc.name}': the wrapper is "
                                "rebuilt (and recompiled) per outer "
                                "call")
                            break
                    break


# ---------------------------------------------------------------------------
# Rule: tracer-hygiene
# ---------------------------------------------------------------------------

_NP_ALIASES = ("np", "numpy")
_TRANSFORM_CALLS = ("jax.jit", "jax.vmap", "jax.pmap")


def _jit_targets(ctx: _FileContext):
    """(function node, static positions, static names) for every
    function this file jits/vmaps: decorated defs, and module-level
    ``name = jax.jit(fn_or_lambda, ...)`` assignments."""
    defs: dict[str, ast.AST] = {}
    lambdas: dict[str, ast.Lambda] = {}
    for node in ctx.tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            defs[node.name] = node
        elif isinstance(node, ast.Assign) and len(node.targets) == 1:
            t = node.targets[0]
            if isinstance(t, ast.Name) and isinstance(node.value,
                                                      ast.Lambda):
                lambdas[t.id] = node.value

    seen: set[int] = set()
    for node in ast.walk(ctx.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                tgt = _dotted(dec if not isinstance(dec, ast.Call)
                              else dec.func)
                if tgt in _TRANSFORM_CALLS:
                    if id(node) not in seen:
                        seen.add(id(node))
                        yield node, set(), set()
                elif isinstance(dec, ast.Call) and _is_jit_call(dec):
                    nums, names = _static_argnums(dec)
                    if id(node) not in seen:
                        seen.add(id(node))
                        yield node, nums, names
        elif isinstance(node, ast.Call) and (
                _dotted(node.func) in _TRANSFORM_CALLS) and node.args:
            fn = node.args[0]
            nums, names = _static_argnums(node)
            target = None
            if isinstance(fn, ast.Lambda):
                target = fn
            elif isinstance(fn, ast.Name):
                target = defs.get(fn.id) or lambdas.get(fn.id)
            if target is not None and id(target) not in seen:
                seen.add(id(target))
                yield target, nums, names


def _tainted_params(fn, static_nums: set[int],
                    static_names: set[str]) -> set[str]:
    a = fn.args
    ordered = list(a.posonlyargs) + list(a.args)
    tainted = set()
    for i, p in enumerate(ordered):
        if i in static_nums or p.arg in static_names or p.arg == "self":
            continue
        tainted.add(p.arg)
    for p in a.kwonlyargs:
        if p.arg not in static_names:
            tainted.add(p.arg)
    if a.vararg:
        tainted.add(a.vararg.arg)
    if a.kwarg:
        tainted.add(a.kwarg.arg)
    return tainted


def _propagate_taint(fn, tainted: set[str]) -> set[str]:
    """Forward-propagate taint through simple assignments (two passes
    cover loop-carried names)."""
    body = fn.body if not isinstance(fn, ast.Lambda) else []
    for _ in range(2):
        for node in ast.walk(ast.Module(body=list(body),
                                        type_ignores=[])):
            targets = None
            value = None
            if isinstance(node, ast.Assign):
                targets, value = node.targets, node.value
            elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                targets = [node.target]
                value = node.value
            elif isinstance(node, ast.For):
                targets, value = [node.target], node.iter
            elif isinstance(node, (ast.comprehension,)):
                targets, value = [node.target], node.iter
            if targets is None or value is None:
                continue
            if _names_in(value) & tainted:
                for t in targets:
                    for n in ast.walk(t):
                        if isinstance(n, ast.Name):
                            tainted.add(n.id)
    return tainted


def _analyze_jit_body(ctx: _FileContext, fn, tainted: set[str]):
    nodes = fn.body if not isinstance(fn, ast.Lambda) else [fn.body]
    wrapper = ast.Module(body=[], type_ignores=[])
    for stmt in nodes:
        wrapper.body.append(stmt)
    fname = getattr(fn, "name", "<lambda>")
    for node in ast.walk(wrapper):
        if isinstance(node, ast.Call):
            tgt = _dotted(node.func)
            arg_names = set()
            for a in list(node.args) + [k.value for k in node.keywords]:
                arg_names |= _names_in(a)
            if (tgt and tgt.split(".")[0] in _NP_ALIASES
                    and arg_names & tainted):
                yield Violation(
                    ctx.path, node.lineno, "tracer-hygiene",
                    f"{tgt}() applied to traced value in jitted "
                    f"'{fname}': numpy concretizes tracers (host "
                    "round-trip or ConcretizationTypeError); use jnp")
            elif (tgt in ("float", "int", "bool")
                  and arg_names & tainted):
                yield Violation(
                    ctx.path, node.lineno, "tracer-hygiene",
                    f"{tgt}() cast of traced value in jitted "
                    f"'{fname}' forces concretization")
            elif (isinstance(node.func, ast.Attribute)
                  and node.func.attr == "item"
                  and _names_in(node.func.value) & tainted):
                yield Violation(
                    ctx.path, node.lineno, "tracer-hygiene",
                    f".item() on traced value in jitted '{fname}' "
                    "forces a device sync")
        elif isinstance(node, (ast.If, ast.While)):
            test = node.test
            # Identity tests (x is None) never read the traced value.
            if (isinstance(test, ast.Compare)
                    and all(isinstance(op, (ast.Is, ast.IsNot))
                            for op in test.ops)):
                continue
            if _names_in(test) & tainted:
                kind = "if" if isinstance(node, ast.If) else "while"
                yield Violation(
                    ctx.path, test.lineno, "tracer-hygiene",
                    f"python `{kind}` on traced value in jitted "
                    f"'{fname}': branch is resolved at trace time "
                    "(use jnp.where / lax.cond)")


def check_tracer_hygiene(ctx: _FileContext):
    for fn, nums, names in _jit_targets(ctx):
        tainted = _tainted_params(fn, nums, names)
        if not tainted:
            continue
        tainted = _propagate_taint(fn, set(tainted))
        yield from _analyze_jit_body(ctx, fn, tainted)


# ---------------------------------------------------------------------------
# Rule: unlocked-shared-write
# ---------------------------------------------------------------------------

_MUTATORS = ("append", "extend", "insert", "add", "update", "clear",
             "pop", "popitem", "remove", "discard", "setdefault",
             "move_to_end", "sort")
_LOCK_CTORS = ("threading.Lock", "threading.RLock", "threading.Condition",
               "Lock", "RLock", "Condition")
_SYNC_CTORS = _LOCK_CTORS + ("queue.Queue", "Queue", "threading.Event",
                             "Event", "queue.LifoQueue",
                             "queue.PriorityQueue")
_THREAD_CTORS = ("threading.Thread", "Thread")
_POOL_CTORS = ("ThreadPoolExecutor",
               "concurrent.futures.ThreadPoolExecutor")


def _self_attr(node: ast.AST) -> str | None:
    if (isinstance(node, ast.Attribute)
            and isinstance(node.value, ast.Name)
            and node.value.id == "self"):
        return node.attr
    return None


class _MethodInfo:
    def __init__(self, node):
        self.node = node
        self.write_nodes: list[tuple[str, ast.AST]] = []  # attr, ast node
        # (attr, line, locked, kind) — kind "rmw" | "rebind"
        self.writes: list[tuple[str, int, bool, str]] = []
        self.reads: set[str] = set()
        self.calls: set[str] = set()    # self.X() method calls


def _scan_class(cls: ast.ClassDef, par: dict):
    methods: dict[str, _MethodInfo] = {}
    workers: set[str] = set()
    lock_attrs: set[str] = set()
    sync_attrs: set[str] = set()

    for item in cls.body:
        if not isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        mi = _MethodInfo(item)
        methods[item.name] = mi
        for node in ast.walk(item):
            if isinstance(node, ast.Call):
                tgt = _dotted(node.func)
                if tgt in _THREAD_CTORS:
                    for kw in node.keywords:
                        if kw.arg == "target":
                            attr = _self_attr(kw.value)
                            if attr:
                                workers.add(attr)
                elif (isinstance(node.func, ast.Attribute)
                      and node.func.attr == "submit" and node.args):
                    attr = _self_attr(node.args[0])
                    if attr:
                        workers.add(attr)
                if isinstance(node.func, ast.Attribute):
                    if _self_attr(node.func) is not None:
                        # self.method(...)
                        mi.calls.add(node.func.attr)
                    else:
                        m_attr = _self_attr(node.func.value)
                        if m_attr is not None and \
                                node.func.attr in _MUTATORS:
                            # self.attr.append(...) etc.
                            mi.write_nodes.append((m_attr, node))
                if item.name == "__init__" and tgt in _SYNC_CTORS:
                    assign = par.get(node)
                    if isinstance(assign, ast.Assign):
                        for t in assign.targets:
                            attr = _self_attr(t)
                            if attr:
                                sync_attrs.add(attr)
                                if tgt in _LOCK_CTORS:
                                    lock_attrs.add(attr)
            elif isinstance(node, (ast.Assign, ast.AugAssign)):
                targets = (node.targets if isinstance(node, ast.Assign)
                           else [node.target])
                for t in targets:
                    elts = (t.elts if isinstance(t, (ast.Tuple, ast.List))
                            else [t])
                    for e in elts:
                        attr = _self_attr(e)
                        if attr:
                            mi.write_nodes.append((attr, node))
            elif isinstance(node, ast.Attribute) and isinstance(
                    node.ctx, ast.Load):
                attr = _self_attr(node)
                if attr:
                    mi.reads.add(attr)
    # Lock coverage is resolved after the whole class is scanned, so a
    # lock attribute declared below its first use still counts.  Kind
    # "rmw" = read-modify-write (AugAssign / container mutator) — the
    # lost-update shape; "rebind" = plain assignment.
    for mi in methods.values():
        mi.writes = [(attr, node.lineno,
                      _under_lock(node, par, lock_attrs),
                      "rebind" if isinstance(node, ast.Assign) else "rmw")
                     for attr, node in mi.write_nodes]
    return methods, workers, lock_attrs, sync_attrs


def _under_lock(node: ast.AST, par: dict, lock_attrs: set[str]) -> bool:
    for anc in _ancestors(node, par):
        if isinstance(anc, ast.With):
            for item in anc.items:
                attr = _self_attr(item.context_expr)
                if attr and (attr in lock_attrs
                             or "lock" in attr.lower()):
                    return True
    return False


def check_thread_discipline(ctx: _FileContext):
    for cls in ast.walk(ctx.tree):
        if not isinstance(cls, ast.ClassDef):
            continue
        methods, workers, lock_attrs, sync_attrs = _scan_class(
            cls, ctx.parents)
        if not workers and not lock_attrs:
            continue

        # Worker-reachable closure over self.X() calls.
        reach = set(workers)
        frontier = list(workers)
        while frontier:
            m = frontier.pop()
            if m not in methods:
                continue
            for callee in methods[m].calls:
                if callee in methods and callee not in reach:
                    reach.add(callee)
                    frontier.append(callee)

        worker_writes: dict[str, list] = {}
        worker_reads: set[str] = set()
        caller_access: set[str] = set()
        caller_writes: dict[str, list] = {}
        for name, mi in methods.items():
            if name == "__init__":
                continue
            if name in reach:
                for a, ln, locked, _kind in mi.writes:
                    worker_writes.setdefault(a, []).append((ln, locked,
                                                            name))
                worker_reads |= mi.reads
            else:
                for a, ln, locked, _kind in mi.writes:
                    caller_writes.setdefault(a, []).append((ln, locked,
                                                            name))
                caller_access |= mi.reads
                caller_access |= {a for a, _, _, _ in mi.writes}

        flagged: set[tuple[int, str]] = set()

        def flag(attr, ln, method, side):
            if (ln, attr) in flagged:
                return None
            flagged.add((ln, attr))
            return Violation(
                ctx.path, ln, "unlocked-shared-write",
                f"'{cls.name}.{attr}' written in {method}() without "
                f"the lock but shared with the {side} thread; guard "
                "with the class lock or route through queue.Queue/"
                "Event")

        if workers:
            for attr, writes in worker_writes.items():
                if attr in sync_attrs or attr not in caller_access:
                    continue
                for ln, locked, m in writes:
                    if not locked:
                        v = flag(attr, ln, m, "caller")
                        if v:
                            yield v
            for attr, writes in caller_writes.items():
                if attr in sync_attrs:
                    continue
                if attr not in worker_reads and attr not in worker_writes:
                    continue
                for ln, locked, m in writes:
                    if not locked:
                        v = flag(attr, ln, m, "worker")
                        if v:
                            yield v
        if lock_attrs:
            # Lock-owning class: every non-init READ-MODIFY-WRITE
            # (+=, container mutators — the lost-update shape) must
            # hold the lock.  The ChunkStore discipline: `get`/`put`
            # run on the prefetch thread and the main thread alike, so
            # there is no single-threaded method to exempt.  Plain
            # rebinds (e.g. a thread handle) are only flagged when the
            # worker/caller sharing analysis above proves them shared.
            for name, mi in methods.items():
                if name == "__init__":
                    continue
                for attr, ln, locked, kind in mi.writes:
                    if attr in sync_attrs or locked or kind != "rmw":
                        continue
                    if (ln, attr) in flagged:
                        continue
                    flagged.add((ln, attr))
                    yield Violation(
                        ctx.path, ln, "unlocked-shared-write",
                        f"'{cls.name}.{attr}' mutated in {name}() "
                        f"outside the class lock ({sorted(lock_attrs)})"
                        "; lock-owning classes mutate shared state "
                        "under it")


# ---------------------------------------------------------------------------
# Rule: accumulator-dtype
# ---------------------------------------------------------------------------


def _mentions_f32_or_device(node: ast.AST) -> str | None:
    for n in ast.walk(node):
        d = _dotted(n) if isinstance(n, (ast.Attribute, ast.Name)) else None
        if d and d.split(".")[0] == "jnp":
            return "jnp (device fold)"
        if d and d.endswith("float32"):
            return "float32 cast"
        if isinstance(n, ast.Constant) and n.value == "float32":
            return "float32 cast"
    return None


def check_accumulator_dtype(ctx: _FileContext):
    for cls in ast.walk(ctx.tree):
        if not isinstance(cls, ast.ClassDef):
            continue
        names = {m.name for m in cls.body
                 if isinstance(m, ast.FunctionDef)}
        if not {"update", "result"} <= names:
            continue
        for method in cls.body:
            if not isinstance(method, ast.FunctionDef):
                continue
            for node in ast.walk(method):
                if isinstance(node, ast.AugAssign):
                    attr = _self_attr(node.target)
                    if attr is None:
                        continue
                    why = _mentions_f32_or_device(node.value)
                    if why:
                        yield Violation(
                            ctx.path, node.lineno, "accumulator-dtype",
                            f"accumulator '{cls.name}.{attr}' folds "
                            f"through {why}; streaming metrics "
                            "accumulate on host in float64")


# ---------------------------------------------------------------------------
# Rule: env-read
# ---------------------------------------------------------------------------

_ENV_SANCTIONED_FILES = ("config.py",)


def check_env_read(ctx: _FileContext):
    if os.path.basename(ctx.path) in _ENV_SANCTIONED_FILES:
        return
    for node in ast.walk(ctx.tree):
        bad = None
        if isinstance(node, ast.Attribute) and _dotted(node) in (
                "os.environ",):
            bad = "os.environ"
        elif isinstance(node, ast.Call) and _dotted(node.func) in (
                "os.getenv", "getenv"):
            bad = "os.getenv"
        elif (isinstance(node, ast.Name) and node.id == "environ"
              and isinstance(node.ctx, ast.Load)):
            bad = "environ"
        if bad:
            yield Violation(
                ctx.path, node.lineno, "env-read",
                f"raw {bad} read; route through "
                "photon_ml_tpu.config.read_env (the sanctioned "
                "registry) so every env knob is discoverable")


# ---------------------------------------------------------------------------
# Rule: naked-clock
# ---------------------------------------------------------------------------

_WALL_CLOCKS = ("time.time",)


def _calls_wall_clock(node: ast.AST) -> bool:
    return any(isinstance(n, ast.Call)
               and _dotted(n.func) in _WALL_CLOCKS
               for n in ast.walk(node))


def check_naked_clock(ctx: _FileContext):
    """Durations must come from a monotonic clock.

    ``time.time()`` is the wall clock: it steps under NTP adjustment
    and suspend/resume, so ``time.time() - t0`` can go negative or jump
    by seconds — every phase timer, bench number, and telemetry span in
    the repo uses ``monotonic``/``perf_counter`` instead (the ISSUE-7
    telemetry tier made timing a first-class output, so a wall-clock
    duration is now a data-corruption bug, not just jitter).  Flags
    subtractions where either operand is a direct ``time.time()`` call
    or a name assigned from one; epoch TIMESTAMPS (no subtraction) stay
    legal, and deliberate wall-clock math can carry a waiver."""
    def _scope(node: ast.AST):
        """Nearest enclosing function (None = module scope) — plain
        names are tainted PER FUNCTION, so `t0 = time.time()` in one
        function cannot flag another function's perf_counter `t0`
        subtraction (reuse of conventional names is the norm)."""
        for anc in _ancestors(node, ctx.parents):
            if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.Lambda)):
                return anc
        return None

    clock_names: dict = {}         # scope id -> set of tainted names
    attr_names: set[str] = set()   # self.<attr> taint is class/file-wide
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Assign) and isinstance(node.value,
                                                       ast.Call):
            if _dotted(node.value.func) in _WALL_CLOCKS:
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        clock_names.setdefault(
                            id(_scope(node)), set()).add(t.id)
                    else:
                        attr = _self_attr(t)
                        if attr:
                            attr_names.add(attr)

    def tainted(side: ast.AST, scoped: set[str]) -> bool:
        if _calls_wall_clock(side):
            return True
        for n in ast.walk(side):
            if (isinstance(n, ast.Name) and n.id in scoped
                    and isinstance(n.ctx, ast.Load)):
                return True
            attr = _self_attr(n)
            if attr is not None and attr in attr_names:
                return True
        return False

    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Sub):
            scoped = clock_names.get(id(_scope(node)), set())
            if tainted(node.left, scoped) or tainted(node.right, scoped):
                yield Violation(
                    ctx.path, node.lineno, "naked-clock",
                    "duration arithmetic on time.time(): the wall "
                    "clock steps under NTP/suspend; use "
                    "time.monotonic() or time.perf_counter()")


# ---------------------------------------------------------------------------
# Rule: metric-name
# ---------------------------------------------------------------------------

# Dotted lowercase identifier with at least two segments
# ("namespace.metric"): the report CLIs address metrics by dotted
# path, so a flat or mixed-case name silently falls out of every
# dashboard slice.
_METRIC_NAME_RE = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z][a-z0-9_]*)+$")
_METRIC_FNS = ("count", "gauge", "observe")
# Receivers that identify the metrics registry at a call site: the
# module-level helpers, the conventional session handles, and the
# session's own methods.  Keyed narrowly so ``line.count(",")`` (str)
# or a container's ``.count`` can never false-positive.
_METRIC_RECEIVERS = ("telemetry", "t", "tel", "self", "self._t")


def check_metric_name(ctx: _FileContext):
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call) or not node.args:
            continue
        func = node.func
        if not (isinstance(func, ast.Attribute)
                and func.attr in _METRIC_FNS):
            continue
        recv = _dotted(func.value)
        if recv not in _METRIC_RECEIVERS:
            continue
        arg = node.args[0]
        if not (isinstance(arg, ast.Constant)
                and isinstance(arg.value, str)):
            continue            # dynamic names: the caller's contract
        if not _METRIC_NAME_RE.match(arg.value):
            yield Violation(
                ctx.path, node.lineno, "metric-name",
                f"metric name {arg.value!r} is not a dotted lowercase "
                "identifier (want namespace.metric, e.g. "
                "'solver.sweeps'); flat or mixed-case names fall out "
                "of the report's metric paths")


# ---------------------------------------------------------------------------
# Rule: swallowed-exception
# ---------------------------------------------------------------------------

# A call through any of these shapes counts as REPORTING the failure:
#   * attribute calls whose method name is a logging/telemetry verb
#     (logger.warning, log.event, telemetry.thread_exception, ...);
#   * calls rooted at the logging/warnings modules (logging.warning,
#     warnings.warn).
_REPORTING_ATTRS = frozenset({
    "debug", "info", "warning", "warn", "error", "exception",
    "critical", "log", "event", "heartbeat", "thread_exception",
})
_REPORTING_ROOTS = ("logging", "warnings")


def _handler_reports(handler: ast.ExceptHandler) -> bool:
    for node in ast.walk(handler):
        if isinstance(node, ast.Raise):
            return True
        if isinstance(node, ast.Call):
            func = node.func
            if (isinstance(func, ast.Attribute)
                    and func.attr in _REPORTING_ATTRS):
                return True
            d = _dotted(func)
            if d and d.split(".")[0] in _REPORTING_ROOTS:
                return True
    return False


def _handler_discards(handler: ast.ExceptHandler) -> bool:
    """True when the handler body does NOTHING with the failure:
    only ``pass``/``continue``/``break``, bare or constant ``return``,
    and constant expressions (docstrings).  A handler that computes a
    fallback, retries with new state, or mutates anything is HANDLING
    the error — different contract, not this rule's."""
    for stmt in handler.body:
        if isinstance(stmt, (ast.Pass, ast.Continue, ast.Break)):
            continue
        if isinstance(stmt, ast.Return):
            if stmt.value is None or isinstance(stmt.value,
                                                ast.Constant):
                continue
            return False
        if isinstance(stmt, ast.Expr) and isinstance(stmt.value,
                                                     ast.Constant):
            continue
        return False
    return True


def check_swallowed_exception(ctx: _FileContext):
    """An ``except`` that silently discards the failure hides it: the
    run proceeds on wrong/partial state and the forensic trail has
    nothing (ISSUE 9 — fault tolerance is only honest when every
    absorbed failure is reported, handled with a real fallback, or
    explicitly waived as best-effort).  The waiver's mandatory reason
    IS the documentation of why silence is correct at that site."""
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.ExceptHandler):
            continue
        if not _handler_discards(node) or _handler_reports(node):
            continue
        what = (_dotted(node.type) if node.type is not None
                else "BaseException")
        yield Violation(
            ctx.path, node.lineno, "swallowed-exception",
            f"except {what or '...'} handler silently discards the "
            "failure: report it (logger/telemetry), handle it with a "
            "real fallback, or waive with a reason documenting why "
            "best-effort silence is correct here")


# ---------------------------------------------------------------------------
# Rule: eternal-wait
# ---------------------------------------------------------------------------

# Zero-argument blocking calls that wait forever without a timeout.
# The zero-arg requirement keeps dict.get(key) / str.join(seq) /
# path.join(a, b) out by construction: the flagged shapes are
# queue.Queue.get(), threading.Event.wait() / Condition.wait(), and
# Thread.join().
_ETERNAL_ZERO_ARG = ("get", "wait", "join")


def _has_timeout_kw(call: ast.Call) -> bool:
    return any(kw.arg in ("timeout", "timeout_s") and not (
        isinstance(kw.value, ast.Constant) and kw.value.value is None)
        for kw in call.keywords)


def check_eternal_wait(ctx: _FileContext):
    """A thread-spawning class owns at least one cross-thread wait; a
    wait with NO timeout turns a dead peer into a silently pinned
    thread (the wedged-replica outage class, ISSUE 13).  Flags
    ``.get()`` / ``.wait()`` / ``.join()`` calls with neither a
    positional argument nor a timeout keyword, and ``.recv(...)``
    (socket reads — the timeout lives in ``settimeout``, which static
    analysis cannot prove was called) inside classes that construct
    ``threading.Thread`` / ``ThreadPoolExecutor``.  Deliberately
    unbounded waits (a ``close()`` that provably enqueues a sentinel,
    a main thread parked on a stop event) carry a waiver naming the
    termination argument."""
    for cls in ast.walk(ctx.tree):
        if not isinstance(cls, ast.ClassDef):
            continue
        spawns = any(
            isinstance(n, ast.Call)
            and _dotted(n.func) in _THREAD_CTORS + _POOL_CTORS
            for n in ast.walk(cls))
        if not spawns:
            continue
        for node in ast.walk(cls):
            if not isinstance(node, ast.Call) or not isinstance(
                    node.func, ast.Attribute):
                continue
            name = node.func.attr
            if name in _ETERNAL_ZERO_ARG:
                if node.args or _has_timeout_kw(node):
                    continue
                recv = _dotted(node.func.value) or "<expr>"
                yield Violation(
                    ctx.path, node.lineno, "eternal-wait",
                    f"{recv}.{name}() blocks with no timeout in "
                    f"thread-spawning class '{cls.name}': a dead peer "
                    "pins this thread forever — pass a timeout (poll) "
                    "or waive with the termination argument")
            elif name == "recv" and not _has_timeout_kw(node):
                recv = _dotted(node.func.value) or "<expr>"
                yield Violation(
                    ctx.path, node.lineno, "eternal-wait",
                    f"{recv}.recv() in thread-spawning class "
                    f"'{cls.name}': socket reads block forever unless "
                    "settimeout() was called — set one (or waive "
                    "naming where the timeout is applied)")


# ---------------------------------------------------------------------------
# Rule: collective-in-host-branch
# ---------------------------------------------------------------------------

# Cross-device/cross-host collectives: every participant must reach the
# call or the fleet deadlocks at the barrier.
_COLLECTIVE_FNS = ("psum", "psum_scatter", "pmean", "pmax", "pmin",
                   "all_gather", "all_to_all", "ppermute", "pshuffle")


def _divergent_host_test(test: ast.AST) -> bool:
    """Does a branch condition read the PROCESS IDENTITY — a value that
    differs per host, so the branch arms diverge across the fleet?
    ``process_index()`` calls and ``host_id`` reads (the FleetContext
    field) qualify; ``process_count()`` does not — it is uniform."""
    for n in ast.walk(test):
        if isinstance(n, ast.Call):
            d = _dotted(n.func)
            if d and d.split(".")[-1] == "process_index":
                return True
        elif isinstance(n, ast.Attribute) and n.attr == "host_id":
            return True
        elif (isinstance(n, ast.Name) and n.id == "host_id"
              and isinstance(n.ctx, ast.Load)):
            return True
    return False


def check_collective_in_host_branch(ctx: _FileContext):
    """A collective (psum/all_gather/...) lexically inside a branch
    conditioned on the process identity (``jax.process_index()`` /
    ``host_id``) is a fleet deadlock: only SOME hosts reach the
    barrier, the rest wait forever (ISSUE 16 — the sharded streaming
    tier pads ragged shards with empty-chunk sentinels precisely so
    every host runs the same collective count).  Hoist the collective
    out of the branch, make the condition uniform across hosts, or
    waive with the reason every host still participates."""
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        d = _dotted(node.func)
        if not d or d.split(".")[-1] not in _COLLECTIVE_FNS:
            continue
        for anc in _ancestors(node, ctx.parents):
            # A def boundary ends the lexical branch: a helper merely
            # DEFINED under a host-conditional may be called by every
            # host (lambdas stay transparent — jax collectives live in
            # lambdas invoked in place).
            if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef)):
                break
            if (isinstance(anc, (ast.If, ast.While, ast.IfExp))
                    and _divergent_host_test(anc.test)):
                yield Violation(
                    ctx.path, node.lineno, "collective-in-host-branch",
                    f"{d.split('.')[-1]} inside a branch on the process "
                    "identity (process_index()/host_id, line "
                    f"{anc.lineno}): hosts that skip the branch never "
                    "reach the collective and the fleet deadlocks — "
                    "hoist it out or make the condition uniform")
                break


# ---------------------------------------------------------------------------
# Rule: while-loop-carry-dtype
# ---------------------------------------------------------------------------


def _literal_class(node: ast.AST) -> str | None:
    """Best-effort dtype CLASS ('bool'/'int'/'float') of a carry-init
    expression, from literal structure only.  None = not inferable
    (Name, general Call, ...) — such positions are never flagged."""
    if isinstance(node, ast.Constant):
        if isinstance(node.value, bool):
            return "bool"
        if isinstance(node.value, int):
            return "int"
        if isinstance(node.value, float):
            return "float"
        return None
    if isinstance(node, ast.Compare):
        return "bool"
    if isinstance(node, ast.UnaryOp):
        return _literal_class(node.operand)
    if isinstance(node, ast.Call):
        d = _dotted(node.func) or ""
        tail = d.split(".")[-1]

        def cls_of(name: str) -> str | None:
            if "bool" in name:
                return "bool"
            if "int" in name:
                return "int"
            if "float" in name or name == "double":
                return "float"
            return None

        # An explicit dtype argument wins (jnp.asarray(0, jnp.int32),
        # jnp.zeros(n, dtype=jnp.float32), ...).
        for arg in list(node.args) + [kw.value for kw in node.keywords]:
            dt = _dotted(arg)
            if dt is not None:
                c = cls_of(dt.split(".")[-1])
                if c:
                    return c
        if cls_of(tail):                       # jnp.int32(...), float(...)
            return cls_of(tail)
        if tail in ("asarray", "array") and node.args:
            return _literal_class(node.args[0])
        if tail in ("logical_and", "logical_or", "logical_not"):
            return "bool"
        if tail in ("zeros", "ones", "full", "zeros_like", "ones_like"):
            return "float"                     # jnp default dtype
    return None


def _is_float_literal(node: ast.AST) -> bool:
    if isinstance(node, ast.UnaryOp):
        node = node.operand
    return (isinstance(node, ast.Constant)
            and isinstance(node.value, float))


def _is_number_literal(node: ast.AST) -> bool:
    if isinstance(node, ast.UnaryOp):
        node = node.operand
    return (isinstance(node, ast.Constant)
            and not isinstance(node.value, bool)
            and isinstance(node.value, (int, float)))


def _is_f64_cast(node: ast.AST) -> bool:
    """``np.float64(...)`` / ``jnp.float64(...)`` / ``np.double(...)``
    — a concrete f64 value (not a weak Python literal) whose fold
    promotes an f32 carry under x64."""
    if not isinstance(node, ast.Call):
        return False
    d = _dotted(node.func) or ""
    return d.split(".")[-1] in ("float64", "double")


def _carry_classes(body, init) -> dict:
    """{carry_name: dtype_class | None} for a while-body function.

    Names come from the body's single carry parameter: the parameter
    itself (single-leaf carry), or the targets of a top-level
    ``a, b, c = <param>`` unpack matched positionally against a literal
    init tuple at the call site.  Dataclass carries and cross-function
    inits resolve to no names — never flagged (the rule only fires
    where the init dtype is statically known)."""
    args = body.args.args
    if len(args) != 1:
        return {}
    param = args[0].arg
    if not isinstance(init, (ast.Tuple, ast.List)):
        return {param: _literal_class(init)}
    if not isinstance(body, (ast.FunctionDef, ast.AsyncFunctionDef)):
        return {}                      # lambda cannot tuple-unpack
    for st in body.body:
        if (isinstance(st, ast.Assign) and len(st.targets) == 1
                and isinstance(st.targets[0], (ast.Tuple, ast.List))
                and isinstance(st.value, ast.Name)
                and st.value.id == param):
            targets = st.targets[0].elts
            if len(targets) != len(init.elts):
                return {}
            return {t.id: _literal_class(e)
                    for t, e in zip(targets, init.elts)
                    if isinstance(t, ast.Name)}
    return {}


def check_while_carry_dtype(ctx: _FileContext):
    """A ``lax.while_loop`` body must return every carry leaf with the
    init's dtype — JAX rejects the mismatch at trace time with an
    opaque "body function output ... differs from the carry" error far
    from the offending expression.  The classic folds: a float literal
    into an int/bool carry (``it + 1.0`` on an int32 counter turns the
    leaf weak-f32), and an explicit f64 cast into an f32 carry.  Only
    carry names whose init dtype is statically inferable are checked;
    waive with the reason the fold provably preserves the dtype."""
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        d = _dotted(node.func) or ""
        if d.split(".")[-1] != "while_loop" or len(node.args) < 3:
            continue
        body_arg, init = node.args[1], node.args[2]
        body = None
        if isinstance(body_arg, ast.Lambda):
            body = body_arg
        elif isinstance(body_arg, ast.Name):
            # Nearest enclosing scope's def of that name (while bodies
            # are conventionally local helpers).
            for anc in (*_ancestors(node, ctx.parents), ctx.tree):
                if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef,
                                    ast.Module)):
                    for n in ast.walk(anc):
                        if (isinstance(n, ast.FunctionDef)
                                and n.name == body_arg.id):
                            body = n
                            break
                if body is not None:
                    break
        if body is None:
            continue
        classes = _carry_classes(body, init)
        if not any(classes.values()):
            continue
        for sub in ast.walk(body):
            if isinstance(sub, ast.BinOp):
                pairs = ((sub.left, sub.right), (sub.right, sub.left))
            elif isinstance(sub, ast.AugAssign):
                pairs = ((sub.target, sub.value),)
            else:
                continue
            for carry_side, other in pairs:
                if not (isinstance(carry_side, ast.Name)
                        and carry_side.id in classes):
                    continue
                cls = classes[carry_side.id]
                if cls == "int" and _is_float_literal(other):
                    yield Violation(
                        ctx.path, sub.lineno, "while-loop-carry-dtype",
                        f"float literal folded into int carry "
                        f"'{carry_side.id}' (init at line "
                        f"{init.lineno}): the leaf turns weak-f32 and "
                        "the while_loop carry dtype check fails at "
                        "trace time — use an int literal or cast "
                        "explicitly outside the carry")
                    break
                if cls == "bool" and _is_number_literal(other):
                    yield Violation(
                        ctx.path, sub.lineno, "while-loop-carry-dtype",
                        f"numeric literal folded into bool carry "
                        f"'{carry_side.id}' (init at line "
                        f"{init.lineno}): the leaf leaves bool and the "
                        "while_loop carry dtype check fails at trace "
                        "time — use jnp.logical_* on bool carries")
                    break
                if cls is not None and _is_f64_cast(other):
                    yield Violation(
                        ctx.path, sub.lineno, "while-loop-carry-dtype",
                        f"float64 cast folded into carry "
                        f"'{carry_side.id}' (init at line "
                        f"{init.lineno}): under x64 the promoted leaf "
                        "no longer matches the f32 init — keep carry "
                        "arithmetic in the carry's own dtype")
                    break


# ---------------------------------------------------------------------------
# Rule: slow-unmarked (repo-level: needs the recorded durations)
# ---------------------------------------------------------------------------


def _is_slow_mark(node: ast.AST) -> bool:
    """Exactly ``[pytest.]mark.slow`` (optionally called) — a substring
    test would false-match e.g. a skipif reason mentioning "slow"."""
    if isinstance(node, ast.Call):
        node = node.func
    return (_dotted(node) or "").endswith("mark.slow")


def _test_has_slow(tree: ast.AST, func: str) -> bool:
    for node in tree.body:
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name) and t.id == "pytestmark":
                    v = node.value
                    marks = (v.elts if isinstance(v, (ast.List, ast.Tuple))
                             else [v])
                    if any(_is_slow_mark(m) for m in marks):
                        return True
    for node in ast.walk(tree):
        if isinstance(node, ast.FunctionDef) and node.name == func:
            if any(_is_slow_mark(d) for d in node.decorator_list):
                return True
    return False


def check_slow_unmarked(root: str):
    dur_path = os.path.join(root, DURATIONS_FILE)
    if not os.path.exists(dur_path):
        return
    with open(dur_path) as f:
        recorded = json.load(f)
    durations = recorded.get("durations", recorded)
    by_func: dict[tuple[str, str], float] = {}
    for nodeid, secs in durations.items():
        if "::" not in nodeid:
            continue
        file_part, test_part = nodeid.split("::", 1)
        # Last :: segment = the function/method name (class-based tests
        # produce file.py::TestCls::test_x; ast.walk in _test_has_slow
        # visits methods, so the unqualified name is what matches).
        func = test_part.split("[", 1)[0].split("::")[-1]
        key = (file_part, func)
        by_func[key] = max(by_func.get(key, 0.0), float(secs))
    trees: dict[str, tuple] = {}
    for (file_part, func), secs in sorted(by_func.items()):
        if secs <= SLOW_THRESHOLD_S:
            continue
        path = os.path.join(root, file_part)
        if not os.path.exists(path):
            continue
        if path not in trees:
            with open(path) as f:
                src = f.read()
            ctx = _FileContext(path, src)   # parses once; .tree reused
            trees[path] = (ctx.tree, ctx)
        tree, ctx = trees[path]
        if _test_has_slow(tree, func):
            continue
        line = 1
        for node in ast.walk(tree):
            if isinstance(node, ast.FunctionDef) and node.name == func:
                line = node.lineno
                break
        v = Violation(
            path, line, "slow-unmarked",
            f"'{func}' measured {secs:.1f}s (> {SLOW_THRESHOLD_S:.0f}s "
            "threshold) in the recorded tier-1 run but lacks "
            "@pytest.mark.slow")
        if not ctx.waived(line, "slow-unmarked"):
            yield v


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------

_FILE_CHECKERS = (
    check_jit_in_function,
    check_tracer_hygiene,
    check_thread_discipline,
    check_accumulator_dtype,
    check_env_read,
    check_naked_clock,
    check_metric_name,
    check_swallowed_exception,
    check_eternal_wait,
    check_collective_in_host_branch,
    check_while_carry_dtype,
)


def check_source(source: str, path: str = "<fixture>",
                 rules=None) -> list[Violation]:
    """Run the per-file checkers over one source string (the unit-test
    surface for the fixture corpus)."""
    ctx = _FileContext(path, source)
    out: list[Violation] = []
    for checker in _FILE_CHECKERS:
        for v in checker(ctx):
            if rules is not None and v.rule not in rules:
                continue
            if not ctx.waived(v.line, v.rule):
                out.append(v)
    if rules is None or "bad-waiver" in rules:
        for line in ctx.bad_waivers:
            out.append(Violation(
                path, line, "bad-waiver",
                "photon-lint waiver without a (reason); every "
                "suppression must say why the contract does not apply"))
    return sorted(out, key=lambda v: (v.path, v.line, v.rule))


def _package_files(root: str) -> list[str]:
    pkg = os.path.join(root, "photon_ml_tpu")
    out = []
    for dirpath, _dirnames, filenames in os.walk(pkg):
        if "__pycache__" in dirpath:
            continue
        for fn in sorted(filenames):
            if fn.endswith(".py"):
                out.append(os.path.join(dirpath, fn))
    return sorted(out)


def run_checks(root: str, rules=None, files=None):
    """All violations for the repo at ``root`` (package files + the
    recorded-duration test audit).  Returns (violations, files_checked).
    """
    targets = files if files is not None else _package_files(root)
    violations: list[Violation] = []
    for path in targets:
        with open(path) as f:
            source = f.read()
        try:
            violations.extend(check_source(source, path, rules=rules))
        except SyntaxError as e:
            if rules is None or "syntax-error" in rules:
                violations.append(Violation(
                    path, e.lineno or 1, "syntax-error", str(e)))
    if rules is None or "slow-unmarked" in rules:
        audited = list(check_slow_unmarked(root))
        if files is not None:
            # Explicit file list: the audit still runs (the JSON must
            # not claim a requested rule ran when it did not), scoped
            # to those files.
            wanted = {os.path.abspath(p) for p in targets}
            audited = [v for v in audited
                       if os.path.abspath(v.path) in wanted]
        violations.extend(audited)
    violations.sort(key=lambda v: (v.path, v.line, v.rule))
    return violations, len(targets)
