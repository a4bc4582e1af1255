"""The fhh-lint rule set, tuned to this codebase's invariants.

Thirteen rules over twelve concerns (the broad-except/bare-print concern
ships as two rules so suppressions and severities stay per-rule; the
two interprocedural fhh-race rules live in :mod:`.concurrency` and are
registered here):

- ``host-sync-in-hot-loop`` — device->host synchronization primitives
  (``.item()``, ``np.asarray``, ``jax.device_get``,
  ``.block_until_ready()``; plus ``bool``/``int``/``float`` casts under
  jit, which force a tracer sync or fail outright) reachable from the
  per-level crawl path: lexically inside a loop in a hot module, inside
  a configured hot-root function or its in-module transitive callees, or
  inside any jit-decorated function.  The sanctioned fetch path
  (``protocol.rpc._fetch``: off-event-loop, obs-counted) never trips
  this rule — raw syncs in the crawl are exactly the smell.
- ``secret-to-sink`` — identifiers matching the secret lexicon (seeds,
  correction-word planes, GC labels, Δ, MAC keys) flowing into log/emit
  calls, ``print``, or exception messages.  A crawl that logs a seed has
  leaked a client's key share to whoever reads the log.
- ``recompile-churn`` — ``jax.jit``/``jax.pmap`` wrappers created inside
  function bodies (a fresh wrapper per call = a fresh compile cache per
  call), ``pallas_call`` constructed inside a lexical loop, and static
  arguments of module-local jit functions fed unhashable literals or
  loop variables (one XLA compile per iteration).
- ``unguarded-shared-state`` — module-level mutables in the configured
  shared-state modules written outside every registered lock
  (module-level ``threading.Lock/RLock``/``asyncio.Lock``).  The obs
  registries are read by the heartbeat thread concurrently with the
  event loop; an unlocked write there is a data race by construction.
- ``broad-except`` — bare ``except:`` or ``except Exception`` handlers
  that neither re-raise nor call pytest's raising helpers.  Catch-all
  boundaries that are deliberate (an RPC verb handler surfacing errors
  to its caller) carry an inline suppression with a justification.
- ``bare-print`` — ``print()`` in crawl-path package modules (the
  ``test_obs`` stdout-hygiene guard, generalized): telemetry goes
  through ``obs.emit``; stdout stays a clean program-output channel.
- ``chunked-device-readback`` — device->host readbacks (``_fetch``,
  ``np.asarray``, ``jax.device_get``, ``.copy_to_host_async()``) inside
  loops in the secure-kernel hot roots (``readback_modules``).  A loop
  of per-chunk fetches serializes the crawl on one device round trip
  per chunk — the exact pattern the whole-level kernel restructure
  removed; the rule pins it at zero.  Deliberately overlaps host-sync
  on ``np.asarray`` (both fire) and deliberately covers ``_fetch``,
  which host-sync sanctions: counted and off-loop does not make a loop
  of fetches cheap.
- ``unbounded-await`` — ``await`` on network reads (``readexactly``,
  ``read``, ...), ``asyncio.wait``, event waits, or dials carrying no
  timeout/deadline, in the configured transport modules
  (``await_modules``: protocol + resilience).  A black-holed peer (no
  FIN, no RST, frames silently dropped) hangs such an await forever;
  the resilience layer's whole premise is that every wait is bounded —
  by a kwarg timeout, ``asyncio.wait_for``, or a ``Deadline`` — and the
  deliberately-unbounded sites (serve loops waiting for the next
  command) carry inline suppressions with justifications.
- ``unbounded-queue`` — ``asyncio.Queue()``/``queue.Queue()`` without a
  positive ``maxsize`` and ``collections.deque()`` without a ``maxlen``
  in the configured ingest/transport modules (``queue_modules``:
  protocol + resilience).  An unbounded buffer between a fast producer
  (a flooding client) and a slow consumer (the crawl) converts overload
  into OOM — the exact failure class the admission-controlled front
  door exists to prevent; every buffer is bounded or carries an inline
  suppression proving it is bounded by construction.
- ``span-discipline`` — obs spans (``reg.span(...)``) not used as
  context managers (a never-entered span records nothing and reads as
  if it instruments the code; an abandoned one dangles in the
  heartbeat and the merged trace) and ``emit()``/``observe()``
  telemetry inside jit-decorated bodies (runs at trace time: records
  once per compile, never per execution).  Scope ``span_modules``:
  protocol/, obs/, parallel/.
- ``metric-naming`` — exported series names must be valid Prometheus
  identifiers: literal metric names fed to the registry methods
  (``count``/``gauge``/``observe``/``timer_add``) must be lowercase
  ``[a-z][a-z0-9_]*`` chunks (optionally ``:sub``, folded into a
  ``key`` label by obs/exporter.py), and a hand-rolled ``fhh_...``
  series literal must end with a unit suffix (``_seconds``, ``_bytes``,
  ``_total``, ...) — the live /metrics plane's naming contract,
  enforced where the names are born instead of on the wire.
- ``guarded-state-unlocked`` / ``stale-read-across-await`` — the
  fhh-race pair (:mod:`.concurrency`): interprocedural asyncio
  lock-discipline over the declared guard map
  (``[tool.fhh-lint.guards]`` + inline ``# fhh-guard:``), and the
  snapshot-await-use atomicity break that every review round since the
  pipelined crawl has hand-caught.  Validated dynamically by the
  ``FHH_DEBUG_GUARDS=1`` runtime sanitizer
  (:mod:`fuzzyheavyhitters_tpu.utils.guards`).
"""

from __future__ import annotations

import ast
import re

from .concurrency import RACE_RULES
from .taint import TAINT_RULES
from .engine import Rule, SourceModule, dotted_name, last_segment

# ---------------------------------------------------------------------------
# shared helpers
# ---------------------------------------------------------------------------

_JIT_NAMES = ("jit", "pmap")


def _mentions_jit(node: ast.AST) -> bool:
    for n in ast.walk(node):
        if isinstance(n, ast.Name) and n.id in _JIT_NAMES:
            return True
        if isinstance(n, ast.Attribute) and n.attr in _JIT_NAMES:
            return True
    return False


def _is_jit_decorated(fn) -> bool:
    return any(_mentions_jit(dec) for dec in fn.decorator_list)


def _module_functions(mod: SourceModule) -> dict:
    """bare name -> list of (Async)FunctionDef anywhere in the module."""
    defs: dict[str, list] = {}
    for node in ast.walk(mod.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            defs.setdefault(node.name, []).append(node)
    return defs


def _callee_names(fn) -> set[str]:
    """Bare names this function calls (``f(...)``, ``obj.f(...)``)."""
    out = set()
    for node in ast.walk(fn):
        if isinstance(node, ast.Call):
            seg = last_segment(dotted_name(node.func))
            if seg:
                out.add(seg)
    return out


def _hot_functions(mod: SourceModule, cfg) -> set[str]:
    """Configured hot roots plus their in-module transitive callees."""
    defs = _module_functions(mod)
    hot = {name for name in cfg.hot_roots if name in defs}
    work = list(hot)
    while work:
        name = work.pop()
        for fn in defs[name]:
            for callee in _callee_names(fn):
                if callee in defs and callee not in hot:
                    hot.add(callee)
                    work.append(callee)
    return hot


def _under_prefix(relpath: str, prefixes) -> bool:
    return any(
        relpath == p or relpath.startswith(p.rstrip("/") + "/")
        for p in prefixes
    )


def _loop_targets(mod: SourceModule, node: ast.AST) -> set[str]:
    """Names bound as ``for`` targets in loops enclosing ``node`` (within
    the nearest function boundary)."""
    out: set[str] = set()
    for a in mod.ancestors(node):
        if isinstance(a, (ast.For, ast.AsyncFor)):
            for t in ast.walk(a.target):
                if isinstance(t, ast.Name):
                    out.add(t.id)
        if isinstance(a, (ast.FunctionDef, ast.AsyncFunctionDef)):
            break
    return out


def _span(node: ast.AST):
    return node.lineno, getattr(node, "end_lineno", node.lineno)


# ---------------------------------------------------------------------------
# 1. host-sync-in-hot-loop
# ---------------------------------------------------------------------------

_HOST_SYNC_DOTTED = {
    "np.asarray": "np.asarray",
    "numpy.asarray": "np.asarray",
    "jax.device_get": "jax.device_get",
    "device_get": "jax.device_get",
}
_HOST_SYNC_METHODS = {"item", "block_until_ready"}
_TRACER_CASTS = {"bool", "int", "float"}


class HostSyncInHotLoop(Rule):
    name = "host-sync-in-hot-loop"
    default_severity = "warning"

    def check(self, mod: SourceModule, cfg):
        in_hot_module = _under_prefix(mod.relpath, cfg.hot_modules)
        hot_fns = _hot_functions(mod, cfg) if in_hot_module else set()
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            sync = self._sync_kind(node)
            cast = self._cast_kind(node)
            if sync is None and cast is None:
                continue
            chain = mod.enclosing_functions(node)
            jit_fn = next((f for f in chain if _is_jit_decorated(f)), None)
            if jit_fn is not None:
                what = sync or f"{cast}() cast"
                yield (
                    *_span(node),
                    f"{what} inside jit-compiled function "
                    f"'{jit_fn.name}' forces a host sync on every call",
                )
                continue
            if sync is None or not in_hot_module:
                continue  # bare casts only matter under jit
            hot_fn = next((f.name for f in chain if f.name in hot_fns), None)
            if mod.in_loop_within_function(node):
                yield (
                    *_span(node),
                    f"{sync} inside a loop in hot module "
                    f"{mod.relpath} blocks on a device round trip per "
                    "iteration (batch or hoist it, or route it through "
                    "the counted _fetch helper)",
                )
            elif hot_fn is not None:
                yield (
                    *_span(node),
                    f"{sync} on the per-level crawl path "
                    f"(reachable from hot root via '{hot_fn}') costs a "
                    "device round trip per level (batch or hoist it, or "
                    "route it through the counted _fetch helper)",
                )

    @staticmethod
    def _sync_kind(call: ast.Call) -> str | None:
        dn = dotted_name(call.func)
        if dn in _HOST_SYNC_DOTTED:
            return _HOST_SYNC_DOTTED[dn]
        if (
            isinstance(call.func, ast.Attribute)
            and call.func.attr in _HOST_SYNC_METHODS
            and not call.args
            and not call.keywords
        ):
            return f".{call.func.attr}()"
        return None

    @staticmethod
    def _cast_kind(call: ast.Call) -> str | None:
        # only casts of computed expressions (a call result, an element, an
        # attribute) — bool(p) of a plain local is almost always a static
        # Python value, not a tracer
        if (
            isinstance(call.func, ast.Name)
            and call.func.id in _TRACER_CASTS
            and len(call.args) == 1
            and not call.keywords
            and isinstance(call.args[0], (ast.Call, ast.Subscript, ast.Attribute))
        ):
            return call.func.id
        return None


# ---------------------------------------------------------------------------
# 2. secret-to-sink
# ---------------------------------------------------------------------------


def _secret_match(identifier: str, lexicon) -> bool:
    segments = [s for s in identifier.lower().split("_") if s]
    return any(s in lexicon for s in segments)


def _secret_idents(node: ast.AST, lexicon) -> list[str]:
    """Secret-matching identifiers appearing anywhere in an expression
    (f-string holes, call args, attribute chains included)."""
    out = []
    for n in ast.walk(node):
        ident = None
        if isinstance(n, ast.Name):
            ident = n.id
        elif isinstance(n, ast.Attribute):
            ident = n.attr
        elif isinstance(n, ast.arg):
            ident = n.arg
        if ident and _secret_match(ident, lexicon):
            out.append(ident)
    return out


class SecretToSink(Rule):
    name = "secret-to-sink"
    default_severity = "error"

    def check(self, mod: SourceModule, cfg):
        lexicon = set(cfg.secret_lexicon)
        sinks = set(cfg.sink_calls)
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Call):
                seg = last_segment(dotted_name(node.func))
                if seg not in sinks:
                    continue
                leaked = []
                for arg in node.args:
                    leaked += _secret_idents(arg, lexicon)
                for kw in node.keywords:
                    leaked += _secret_idents(kw.value, lexicon)
                    if (
                        kw.arg
                        and _secret_match(kw.arg, lexicon)
                        and not isinstance(kw.value, ast.Constant)
                    ):
                        leaked.append(kw.arg)
                if leaked:
                    yield (
                        *_span(node),
                        f"secret-lexicon identifier(s) "
                        f"{sorted(set(leaked))} flow into sink "
                        f"'{seg}' — key material must never reach "
                        "logs, metrics, or stdout",
                    )
            elif isinstance(node, ast.Raise) and node.exc is not None:
                leaked = _secret_idents(node.exc, lexicon)
                if leaked:
                    yield (
                        *_span(node),
                        f"secret-lexicon identifier(s) "
                        f"{sorted(set(leaked))} flow into an exception "
                        "message — tracebacks cross trust boundaries "
                        "(RPC error responses, logs)",
                    )


# ---------------------------------------------------------------------------
# 3. recompile-churn
# ---------------------------------------------------------------------------

_UNHASHABLE_LITERALS = (ast.List, ast.Dict, ast.Set, ast.ListComp, ast.DictComp, ast.SetComp)


def _jit_static_params(fn) -> set[str] | None:
    """For a jit-decorated function, the parameter names declared static
    (via static_argnames or static_argnums); None when not jit-decorated."""
    if not _is_jit_decorated(fn):
        return None
    statics: set[str] = set()
    arg_names = [a.arg for a in fn.args.posonlyargs + fn.args.args]
    for dec in fn.decorator_list:
        for n in ast.walk(dec):
            if not isinstance(n, ast.Call):
                continue
            for kw in n.keywords:
                if kw.arg == "static_argnames":
                    for c in ast.walk(kw.value):
                        if isinstance(c, ast.Constant) and isinstance(c.value, str):
                            statics.add(c.value)
                elif kw.arg == "static_argnums":
                    for c in ast.walk(kw.value):
                        if isinstance(c, ast.Constant) and isinstance(c.value, int):
                            if 0 <= c.value < len(arg_names):
                                statics.add(arg_names[c.value])
    return statics


class RecompileChurn(Rule):
    name = "recompile-churn"
    default_severity = "warning"

    def check(self, mod: SourceModule, cfg):
        # module-local jit functions and their static params
        jit_statics: dict[str, tuple[set[str], list[str]]] = {}
        for name, fns in _module_functions(mod).items():
            for fn in fns:
                statics = _jit_static_params(fn)
                if statics:
                    arg_names = [
                        a.arg for a in fn.args.posonlyargs + fn.args.args
                    ]
                    jit_statics[name] = (statics, arg_names)
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            if self._in_decorator(mod, node):
                continue
            dn = dotted_name(node.func)
            seg = last_segment(dn)
            chain = mod.enclosing_functions(node)
            if seg in _JIT_NAMES and chain:
                # jax.jit(f) inside a function body: a fresh wrapper (and
                # compile cache) per call.  Trace-time creation inside an
                # already-jit-decorated function is fine.
                if not any(_is_jit_decorated(f) for f in chain):
                    yield (
                        *_span(node),
                        f"'{dn}' wrapper created inside function "
                        f"'{chain[0].name}' — hoist to module scope or "
                        "the compile cache is rebuilt on every call",
                    )
                continue
            if seg == "pallas_call" and mod.in_loop_within_function(node):
                yield (
                    *_span(node),
                    "pallas_call constructed inside a loop — hoist the "
                    "kernel wrapper out of the iteration",
                )
                continue
            if seg in jit_statics:
                statics, arg_names = jit_statics[seg]
                loop_vars = _loop_targets(mod, node)
                bindings = []
                for i, arg in enumerate(node.args):
                    if i < len(arg_names) and arg_names[i] in statics:
                        bindings.append((arg_names[i], arg))
                for kw in node.keywords:
                    if kw.arg in statics:
                        bindings.append((kw.arg, kw.value))
                for pname, val in bindings:
                    if isinstance(val, _UNHASHABLE_LITERALS):
                        yield (
                            *_span(node),
                            f"unhashable literal passed for static arg "
                            f"'{pname}' of jit function '{seg}' — jit "
                            "static args must be hashable (use a tuple)",
                        )
                    elif isinstance(val, ast.Name) and val.id in loop_vars:
                        yield (
                            *_span(node),
                            f"loop variable '{val.id}' passed for static "
                            f"arg '{pname}' of jit function '{seg}' — "
                            "one fresh XLA compile per iteration",
                        )

    @staticmethod
    def _in_decorator(mod: SourceModule, node: ast.AST) -> bool:
        child = node
        for a in mod.ancestors(node):
            if isinstance(a, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
                if child in a.decorator_list or any(
                    child is d or child in ast.walk(d) for d in a.decorator_list
                ):
                    return True
            child = a
        return False


# ---------------------------------------------------------------------------
# 4. unguarded-shared-state
# ---------------------------------------------------------------------------

_LOCK_CTORS = {"Lock", "RLock", "Condition", "Semaphore", "BoundedSemaphore"}
_MUTABLE_CTORS = {
    "dict", "list", "set", "deque", "defaultdict", "OrderedDict", "WeakSet",
    "WeakValueDictionary", "WeakKeyDictionary", "Counter",
}
_MUTATING_METHODS = {
    "append", "extend", "insert", "add", "update", "setdefault", "pop",
    "popitem", "remove", "discard", "clear", "appendleft", "popleft",
}


class UnguardedSharedState(Rule):
    name = "unguarded-shared-state"
    default_severity = "error"

    def check(self, mod: SourceModule, cfg):
        if not _under_prefix(mod.relpath, cfg.shared_state_modules):
            return
        mutables, locks = self._module_state(mod)
        # names rebound via `global` anywhere count as shared scalars
        global_names: set[str] = set()
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Global):
                global_names.update(node.names)
        shared = mutables | global_names
        if not shared:
            return
        for fn in [
            n
            for n in ast.walk(mod.tree)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
        ]:
            fn_globals = set()
            for node in ast.walk(fn):
                if isinstance(node, ast.Global):
                    fn_globals.update(node.names)
            for node in ast.walk(fn):
                hit = self._write_target(node, shared, mutables, fn_globals)
                if hit is None:
                    continue
                name, verb = hit
                if self._under_lock(mod, node, locks):
                    continue
                lock_hint = (
                    f"hold one of {sorted(locks)}"
                    if locks
                    else "register a module lock and hold it"
                )
                yield (
                    *_span(node),
                    f"module-level shared state '{name}' {verb} in "
                    f"'{fn.name}' outside any registered lock — "
                    f"{lock_hint} around the write",
                )

    @staticmethod
    def _module_state(mod: SourceModule):
        """(mutable names, lock names) assigned at module top level."""
        mutables: set[str] = set()
        locks: set[str] = set()
        for stmt in mod.tree.body:
            targets = []
            value = None
            if isinstance(stmt, ast.Assign):
                targets, value = stmt.targets, stmt.value
            elif isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
                targets, value = [stmt.target], stmt.value
            for t in targets:
                if not isinstance(t, ast.Name):
                    continue
                if isinstance(value, (ast.Dict, ast.List, ast.Set)):
                    mutables.add(t.id)
                elif isinstance(value, ast.Call):
                    seg = last_segment(dotted_name(value.func))
                    if seg in _LOCK_CTORS:
                        locks.add(t.id)
                    elif seg in _MUTABLE_CTORS:
                        mutables.add(t.id)
        return mutables, locks

    @staticmethod
    def _write_target(node, shared, mutables, fn_globals):
        """(name, verb) when ``node`` writes a shared module name."""
        if isinstance(node, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
            targets = (
                node.targets if isinstance(node, ast.Assign) else [node.target]
            )
            for t in targets:
                # rebinding a global-declared name
                if isinstance(t, ast.Name) and t.id in fn_globals and t.id in shared:
                    return t.id, "rebound"
                # container element store: m[...] = / m.attr =
                base = t
                while isinstance(base, (ast.Subscript, ast.Attribute)):
                    base = base.value
                if (
                    isinstance(base, ast.Name)
                    and base.id in mutables
                    and base is not t
                ):
                    return base.id, "mutated (element store)"
        elif isinstance(node, ast.Delete):
            for t in node.targets:
                base = t
                while isinstance(base, (ast.Subscript, ast.Attribute)):
                    base = base.value
                if isinstance(base, ast.Name) and base.id in mutables and base is not t:
                    return base.id, "mutated (del)"
        elif isinstance(node, ast.Call):
            f = node.func
            if (
                isinstance(f, ast.Attribute)
                and f.attr in _MUTATING_METHODS
                and isinstance(f.value, ast.Name)
                and f.value.id in mutables
            ):
                return f.value.id, f"mutated (.{f.attr})"
        return None

    @staticmethod
    def _under_lock(mod: SourceModule, node, locks) -> bool:
        if not locks:
            return False
        for a in mod.ancestors(node):
            if isinstance(a, (ast.With, ast.AsyncWith)):
                for item in a.items:
                    for n in ast.walk(item.context_expr):
                        if isinstance(n, ast.Name) and n.id in locks:
                            return True
                        if isinstance(n, ast.Attribute) and n.attr in locks:
                            return True
            if isinstance(a, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return False
        return False


# ---------------------------------------------------------------------------
# 5. broad-except  +  6. bare-print
# ---------------------------------------------------------------------------

_BROAD_TYPES = {"Exception", "BaseException"}
_RERAISE_EQUIVALENTS = {"skip", "xfail", "fail", "exit"}  # pytest helpers raise


class BroadExcept(Rule):
    name = "broad-except"
    default_severity = "error"

    def check(self, mod: SourceModule, cfg):
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.ExceptHandler):
                continue
            broad = node.type is None or self._is_broad(node.type)
            if not broad:
                continue
            if self._reraises(node):
                continue
            what = (
                "bare 'except:'"
                if node.type is None
                else f"'except {last_segment(dotted_name(node.type)) or 'Exception'}'"
            )
            yield (
                node.lineno,
                node.lineno,
                f"{what} swallows every failure mode — narrow the "
                "exception types, or re-raise after telemetry",
            )

    @staticmethod
    def _is_broad(type_node) -> bool:
        nodes = (
            type_node.elts if isinstance(type_node, ast.Tuple) else [type_node]
        )
        return any(
            last_segment(dotted_name(n)) in _BROAD_TYPES for n in nodes
        )

    @staticmethod
    def _reraises(handler: ast.ExceptHandler) -> bool:
        for n in ast.walk(handler):
            if isinstance(n, ast.Raise):
                return True
            if isinstance(n, ast.Call):
                seg = last_segment(dotted_name(n.func))
                if seg in _RERAISE_EQUIVALENTS:
                    return True
        return False


class BarePrint(Rule):
    name = "bare-print"
    default_severity = "error"

    def check(self, mod: SourceModule, cfg):
        if not _under_prefix(mod.relpath, cfg.print_scope):
            return
        if mod.relpath in cfg.print_allowed:
            return
        for node in ast.walk(mod.tree):
            if (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Name)
                and node.func.id == "print"
            ):
                yield (
                    *_span(node),
                    "bare print() telemetry in a crawl-path module — "
                    "use fuzzyheavyhitters_tpu.obs.emit (stdout is a "
                    "program-output channel)",
                )


# ---------------------------------------------------------------------------
# 7. chunked-device-readback
# ---------------------------------------------------------------------------

# device->host readback entry points: the sanctioned counted fetch
# (protocol.rpc._fetch), the raw bulk fetches, and the async-DMA kickoff
_READBACK_DOTTED = {
    "np.asarray": "np.asarray",
    "numpy.asarray": "np.asarray",
    "jax.device_get": "jax.device_get",
    "device_get": "jax.device_get",
}


class ChunkedDeviceReadback(Rule):
    """Device readbacks inside per-chunk loops in the secure-kernel hot
    roots (``readback_modules``): a loop that fetches (or starts the DMA
    for) one chunk per iteration serializes the crawl on one device
    round trip PER CHUNK, each with a fixed cost regardless of size.
    The whole-level restructure exists
    to batch these into ONE fetch per level; this rule keeps the pattern
    from growing back.  Note the sanctioned ``_fetch`` helper is flagged
    here too — being counted and off-loop does not make a per-chunk loop
    of fetches cheap — which is exactly the gap the host-sync rule (which
    deliberately never flags ``_fetch``) leaves open."""

    name = "chunked-device-readback"
    default_severity = "warning"

    _LOOPY = (
        ast.For, ast.AsyncFor, ast.While,
        # a comprehension of fetches is the same per-chunk pathology
        ast.ListComp, ast.SetComp, ast.DictComp, ast.GeneratorExp,
    )

    def check(self, mod: SourceModule, cfg):
        if not _under_prefix(mod.relpath, cfg.readback_modules):
            return
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            kind = self._readback_kind(node)
            if kind is None:
                continue
            if not self._in_chunk_loop(mod, node):
                continue
            yield (
                *_span(node),
                f"{kind} inside a per-chunk loop costs one device round "
                "trip per iteration — batch the chunks into one "
                "whole-level readback (stack on device, fetch once after "
                "the loop)",
            )

    @classmethod
    def _in_chunk_loop(cls, mod: SourceModule, node: ast.AST) -> bool:
        for a in mod.ancestors(node):
            if isinstance(a, cls._LOOPY):
                return True
            if isinstance(a, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return False
        return False

    @staticmethod
    def _readback_kind(call: ast.Call) -> str | None:
        dn = dotted_name(call.func)
        if dn in _READBACK_DOTTED:
            return _READBACK_DOTTED[dn]
        if last_segment(dn) == "_fetch":
            return "_fetch"  # self._fetch / rpc._fetch forms
        if (
            isinstance(call.func, ast.Attribute)
            and call.func.attr == "copy_to_host_async"
        ):
            return ".copy_to_host_async()"
        return None


# ---------------------------------------------------------------------------
# 8. unbounded-await
# ---------------------------------------------------------------------------

# attribute calls whose await can hang forever on a wedged/black-holed
# peer: stream reads (protocol/wire.py's FrameReader.readinto, which
# fills a frame's out-of-band buffer, and wire.read_body, a frame's whole
# body, among them), event/condition waits (incl. asyncio.wait itself)
_AWAIT_NET_METHODS = {
    "readexactly", "readinto", "read_body", "readuntil", "readline", "read",
    "wait",
}


class UnboundedAwait(Rule):
    name = "unbounded-await"
    default_severity = "warning"

    def check(self, mod: SourceModule, cfg):
        if not _under_prefix(mod.relpath, cfg.await_modules):
            return
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Await) or not isinstance(
                node.value, ast.Call
            ):
                continue
            call = node.value
            dn = dotted_name(call.func)
            seg = last_segment(dn)
            if seg == "wait_for":
                # the bounded wrapper — unless its timeout is literally
                # None, which is an unbounded await in disguise
                if self._timeout_is_none(call):
                    yield (
                        *_span(node),
                        "wait_for with timeout=None is an unbounded "
                        "await in disguise — pass a finite deadline",
                    )
                continue
            if seg == "open_connection":
                yield (
                    *_span(node),
                    "await on a bare dial: the OS SYN timeout is minutes "
                    "— wrap in asyncio.wait_for with a dial timeout "
                    "(resilience.policy.DIAL_TIMEOUT_S)",
                )
                continue
            if (
                seg in _AWAIT_NET_METHODS
                and isinstance(call.func, ast.Attribute)
                and not self._has_finite_timeout(call)
            ):
                yield (
                    *_span(node),
                    f"await on '{dn or seg}(...)' carries no timeout or "
                    "deadline — a black-holed peer hangs this task "
                    "forever (pass timeout=, bound with asyncio.wait_for/"
                    "Deadline, or suppress with a justification)",
                )

    @staticmethod
    def _timeout_kwarg(call: ast.Call):
        for kw in call.keywords:
            if kw.arg == "timeout":
                return kw.value
        return None

    @classmethod
    def _timeout_is_none(cls, call: ast.Call) -> bool:
        t = call.args[1] if len(call.args) >= 2 else cls._timeout_kwarg(call)
        return isinstance(t, ast.Constant) and t.value is None

    @classmethod
    def _has_finite_timeout(cls, call: ast.Call) -> bool:
        t = cls._timeout_kwarg(call)
        if t is None:
            return False
        return not (isinstance(t, ast.Constant) and t.value is None)


# ---------------------------------------------------------------------------
# 9. span-discipline
# ---------------------------------------------------------------------------


class SpanDiscipline(Rule):
    """Telemetry-correctness pair for the obs layer (``span_modules``:
    protocol/, obs/, parallel/):

    1. ``reg.span(...)`` objects not used as ``with`` context managers —
       a span context that is never entered/exited records NOTHING (no
       timer, no trace event) while reading as if it instruments the
       code around it; a span entered but abandoned dangles forever in
       the heartbeat and the merged trace.  The one legitimate
       split-enter/exit site (WindowedIngest's per-window ingest span)
       carries an inline suppression with its justification.
    2. ``emit()``/``observe()`` calls inside jit-decorated functions —
       they run at TRACE time, once per compile, not once per
       execution: the metric silently records compile counts, not run
       counts (hoist the telemetry to the host-side caller)."""

    name = "span-discipline"
    default_severity = "error"

    def check(self, mod: SourceModule, cfg):
        if not _under_prefix(mod.relpath, cfg.span_modules):
            return
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            if (
                isinstance(node.func, ast.Attribute)
                and node.func.attr == "span"
            ):
                parent = mod.parent(node)
                if not (
                    isinstance(parent, ast.withitem)
                    and parent.context_expr is node
                ):
                    yield (
                        *_span(node),
                        "span(...) created outside a with statement — a "
                        "span context that never enters/exits records no "
                        "timer and dangles in the heartbeat/trace (use "
                        "`with reg.span(...):`, or suppress with a "
                        "justification where enter/exit are explicitly "
                        "managed)",
                    )
                continue
            seg = last_segment(dotted_name(node.func))
            if seg in ("emit", "observe"):
                chain = mod.enclosing_functions(node)
                jit_fn = next(
                    (f for f in chain if _is_jit_decorated(f)), None
                )
                if jit_fn is not None:
                    yield (
                        *_span(node),
                        f"telemetry call '{seg}(...)' inside jit-compiled "
                        f"function '{jit_fn.name}' runs at trace time — "
                        "it records once per COMPILE, never per "
                        "execution (hoist it to the host-side caller)",
                    )


# ---------------------------------------------------------------------------
# 10. unbounded-queue
# ---------------------------------------------------------------------------

# buffer constructors and the kwarg that bounds each.  SimpleQueue has no
# bound AT ALL, so its mere construction is the finding.
_QUEUE_CTORS = {
    "Queue": "maxsize",
    "LifoQueue": "maxsize",
    "PriorityQueue": "maxsize",
    "deque": "maxlen",
}


class UnboundedQueue(Rule):
    """Unbounded producer/consumer buffers in the ingest/transport
    modules (``queue_modules``).  ``asyncio.Queue()`` with no (or zero)
    ``maxsize`` and ``deque()`` with no ``maxlen`` grow without limit
    when the producer outruns the consumer — under a client flood that
    is an OOM, not backpressure.  The front door's contract is bounded
    pools + explicit shed/reject verdicts; a buffer that is provably
    bounded by construction carries an inline suppression saying why."""

    name = "unbounded-queue"
    default_severity = "error"

    def check(self, mod: SourceModule, cfg):
        if not _under_prefix(mod.relpath, cfg.queue_modules):
            return
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            seg = last_segment(dotted_name(node.func))
            if seg == "SimpleQueue":
                yield (
                    *_span(node),
                    "SimpleQueue has no maxsize at all — use Queue with "
                    "a positive maxsize (bounded buffers or explicit "
                    "shed, never silent growth)",
                )
                continue
            bound_kw = _QUEUE_CTORS.get(seg)
            if bound_kw is None:
                continue
            bound = self._bound_arg(node, seg, bound_kw)
            if bound is None:
                yield (
                    *_span(node),
                    f"{seg}() constructed without a {bound_kw} bound — "
                    "an overloaded producer grows it without limit "
                    f"(pass a positive {bound_kw}, or suppress with a "
                    "justification if it is bounded by construction)",
                )
            elif isinstance(bound, ast.Constant) and bound.value in (0, None):
                yield (
                    *_span(node),
                    f"{seg}({bound_kw}={bound.value!r}) is unbounded in "
                    f"disguise — pass a positive {bound_kw}",
                )

    @staticmethod
    def _bound_arg(call: ast.Call, seg: str, bound_kw: str):
        """The expression bounding this constructor, or None.  Queue's
        maxsize is its first positional; deque's maxlen is its second."""
        for kw in call.keywords:
            if kw.arg == bound_kw:
                return kw.value
        pos = 0 if bound_kw == "maxsize" else 1
        if len(call.args) > pos:
            return call.args[pos]
        return None


# ---------------------------------------------------------------------------
# 11. metric-naming
# ---------------------------------------------------------------------------

# a registry metric name: lowercase identifier chunk, optional ":sub"
# parts (the exporter folds a colon into a `key` label — obs/exporter.py)
_METRIC_NAME_RE = re.compile(r"[a-z][a-z0-9_]*(?::[a-z0-9_]+)*")
# identifier-LIKE: a literal that was plausibly meant as a metric name
# but is invalid (camelCase, dashes, dots).  Literals outside this shape
# (spaces, arbitrary punctuation) are substring-search arguments to
# str.count()-style calls, never metric names — skipping them keeps the
# rule zero-noise over the shared `count` method name.
_METRIC_LIKE_RE = re.compile(r"[A-Za-z0-9_.:\-]+")
# a hand-rolled exported-series literal (scrape parsers, exposition
# producers); the exporter's own f-string assembly is out of scope
# (JoinedStr fragments are never whole names)
_EXPORTED_RE = re.compile(r"fhh_[a-z0-9_]+")


class MetricNaming(Rule):
    """Exported series names must be valid Prometheus identifiers.

    Two checks over ``metric_modules``: (1) literal first arguments of
    the registry metric methods (``metric_calls``) must match
    ``[a-z][a-z0-9_]*`` with optional ``:sub`` parts — the exporter
    prefixes ``fhh_`` and appends ``_total``/``_seconds`` itself, so a
    conforming internal name IS a conforming series name; (2) a full
    ``fhh_...`` string literal (a hand-rolled exposition line or scrape
    key) must end with a recognized unit suffix
    (``metric_unit_suffixes``), because Prometheus consumers key on the
    unit token and a bare name reads as unitless."""

    name = "metric-naming"
    default_severity = "error"

    def check(self, mod: SourceModule, cfg):
        if not _under_prefix(mod.relpath, cfg.metric_modules):
            return
        # constants living inside f-strings are fragments, not names
        joined: set[int] = set()
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.JoinedStr):
                joined.update(id(v) for v in node.values)
        suffixes = tuple(cfg.metric_unit_suffixes)
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Call):
                yield from self._check_call(node, cfg)
            elif (
                isinstance(node, ast.Constant)
                and isinstance(node.value, str)
                and id(node) not in joined
                and _EXPORTED_RE.fullmatch(node.value)
                and not node.value.endswith(suffixes)
            ):
                yield (
                    *_span(node),
                    f"exported series literal {node.value!r} carries no "
                    "unit suffix "
                    f"({', '.join(suffixes[:4])}, ...) — Prometheus "
                    "consumers key on the unit token; rename it, or "
                    "suppress with a justification if it is not a "
                    "series name",
                )

    def _check_call(self, node: ast.Call, cfg):
        if not isinstance(node.func, ast.Attribute):
            return
        if node.func.attr not in cfg.metric_calls:
            return
        if not node.args:
            return
        arg = node.args[0]
        if not (isinstance(arg, ast.Constant) and isinstance(arg.value, str)):
            return
        s = arg.value
        if _METRIC_NAME_RE.fullmatch(s):
            return
        # not even identifier-like (spaces, lone punctuation, no letter):
        # a substring-search argument, not a metric name attempt
        if not _METRIC_LIKE_RE.fullmatch(s):
            return
        if len(s) < 2 or not any(c.isalpha() for c in s):
            return
        yield (
            *_span(node),
            f"metric name {s!r} is not a valid Prometheus identifier "
            "chunk — use [a-z][a-z0-9_]* (optionally :sub, which the "
            "exporter folds into a key label); uppercase, dashes, and "
            "dots break the fhh_* exposition contract",
        )


ALL_RULES: tuple[Rule, ...] = (
    HostSyncInHotLoop(),
    SecretToSink(),
    RecompileChurn(),
    UnguardedSharedState(),
    BroadExcept(),
    BarePrint(),
    ChunkedDeviceReadback(),
    UnboundedAwait(),
    UnboundedQueue(),
    SpanDiscipline(),
    MetricNaming(),
    # the interprocedural fhh-race pair (analysis/concurrency.py)
    *RACE_RULES,
    # the interprocedural fhh-taint triple (analysis/taint.py) — in
    # taint_modules these supersede lexical secret-to-sink, which stays
    # on everywhere as the fast pre-filter (subset-tested)
    *TAINT_RULES,
)

RULES_BY_NAME = {r.name: r for r in ALL_RULES}
