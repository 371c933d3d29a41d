"""osselint — the project's AST invariant linter.

Every rule here encodes a bug class this repo has actually shipped (or a
reference-engine discipline that keeps it from shipping one):

* ``ttlcache-offplane`` — PR 4 unified caching onto the cache plane
  (generation invalidation + single-flight); a raw ``TtlCache(`` off the
  plane silently serves stale entries across index generations.
* ``urllib-in-parallel`` — all cross-shard HTTP rides the pooled
  ``parallel/transport.py`` (hedging, tracing, connection reuse); a bare
  ``urlopen`` bypasses every one of those.
* ``bare-stats-timed`` — the query path must use ``trace.timed_span``
  (which also feeds g_stats) so cross-shard waterfalls stay complete; a
  bare ``g_stats.timed`` records a duration no trace can attribute.
* ``id-key`` — PR 4 shipped an ``id(conf)`` cache key: CPython reuses
  addresses after GC, so a dead object's id aliases a live one and the
  cache returns wrong-config results. ``id()`` never belongs in a key.
* ``blocking-under-lock`` — sleeping or doing socket/subprocess I/O
  inside a ``with <lock>:`` body stalls every thread behind the lock.
* ``silent-except`` — ``except: pass`` ate real corruption reports more
  than once; failures must at least count or log.
* ``mutable-default`` — the classic shared-default-argument aliasing.
* ``thread-spawn`` — threads come from ``utils.threads`` so every one is
  a *named daemon*: names make lockcheck/profiler output readable and
  daemonization keeps test runs from hanging on shutdown.
* ``locked-global`` — module-level mutable state in ``serve/`` and
  ``parallel/`` is shared across request threads; mutations outside a
  ``with <lock>:`` are data races.
* ``device-sync`` — ``jax.device_get``/``block_until_ready`` force a
  host sync; outside the two blessed device-boundary modules they
  silently serialize the TPU pipeline.
* ``proc-spawn`` — child processes and signals are the fleet plane's
  job: ``subprocess.Popen`` / ``os.kill`` / ``os.fork`` outside
  ``parallel/fleet.py`` and ``utils/chaos.py`` spawn or kill processes
  no supervisor tracks and no teardown reaps — exactly the orphan
  leaks the FleetManager process groups exist to prevent.
* ``residency-bypass`` — HBM-resident state is the tenancy plane's
  job: a ``DeviceIndex(`` / ``ResidentLoop(`` constructed outside
  ``serve/tenancy.py`` and the ``query/engine.py`` factories creates
  device buffers the ResidencyManager never sees — the LRU can't
  evict them, the membudget 'device' label never bills them, and
  delColl can't unserve them.

The ``jit-*`` family covers JAX trace discipline — the failure modes
are invisible until they show up as a latency cliff (the Gigablast
analog: Msg39 latency spikes when a query shape misses every warm
plan):

* ``jit-unstable-static`` — a float / container / array /
  unbucketed ``len()``-derived value passed to a ``static_argnames``
  parameter: every distinct value is a fresh XLA compile (retrace
  cliff + unbounded jit cache).
* ``jit-in-body`` — ``jax.jit(...)`` wrapped inside a function body:
  each call mints a fresh wrapper with an empty compile cache, so
  nothing is ever warm (memoized factories via ``lru_cache`` are the
  sanctioned escape).
* ``jit-mutable-closure`` — a jitted function reading module-level
  mutable state: the value is frozen into the traced program at
  compile time and silently goes stale when the dict/list mutates.
* ``jit-donated-reuse`` — an argument donated via ``donate_argnums``
  read after the donating call: donation deallocates the buffer; the
  read returns garbage (or crashes) on real backends.
* ``jit-implicit-transfer`` — ``float()`` / ``.item()`` /
  ``np.asarray()`` / ``.tolist()`` on a device value outside the
  device-boundary modules (devindex, scorer, sharded): an implicit
  device→host sync on the request path, exactly the hidden
  serialization the resident loop exists to avoid.
* ``bare-deadline`` — raw ``time.monotonic() + timeout`` /
  ``x - time.monotonic()`` deadline math on the query/parallel/serve
  paths: a hand-rolled deadline never stamps ``X-OSSE-Deadline`` onto
  scatter legs and never feeds the ``deadline.abandoned`` counters —
  use ``utils.deadline.Deadline`` (``.after``/``.remaining``/
  ``.clamp``). ``now - t0`` duration measurement stays legal.
* ``adhoc-timing`` — ``time.perf_counter() - t0`` /
  ``time.time() - t0`` latency measurement on the query/parallel/serve
  paths: the measured duration reaches neither the /admin/perf
  histograms nor the trace waterfall (two-timing-planes drift) — use
  ``trace.timed_span`` or ``trace.record``, which feed both.
  ``time.monotonic() - t0`` budget arithmetic stays legal.

Waive a finding with a trailing comment on its line::

    risky_call()  # osselint: ignore[rule-name] — why it is safe here

``python -m tools.osselint`` scans the package + tools + tests;
``--changed`` scans only files touched vs. git HEAD; ``--format=json``
emits machine-readable findings. Exit status 1 when anything unwaived
is found.
"""

from __future__ import annotations

import ast
import json
import re
import sys
from dataclasses import dataclass
from pathlib import Path

PKG = "open_source_search_engine_tpu"

#: dirs never scanned (fixtures are deliberate violations)
EXCLUDE_PARTS = {"__pycache__", "lint_fixtures", ".git"}

_WAIVER_RE = re.compile(r"osselint:\s*ignore\[([A-Za-z0-9_\-,\s]+)\]")

#: a ``# osselint: path=<relpath>`` comment in the first lines of a
#: file re-scopes it to that virtual path (fixtures exercise
#: parallel/-only rules from tests/lint_fixtures/)
_PATH_PRAGMA_RE = re.compile(r"osselint:\s*path=(\S+)")

#: ``with`` context expressions whose final identifier matches this are
#: treated as lock acquisitions by blocking-under-lock / locked-global
_LOCKISH_RE = re.compile(r"lock|mutex|cond|(^|_)cv$", re.IGNORECASE)

#: dotted-call prefixes that block the calling thread
_BLOCKING_PREFIXES = ("socket.", "urllib.", "subprocess.")
_BLOCKING_EXACT = {"time.sleep", "sleep"}

#: mutating container methods for locked-global
_MUTATORS = {"append", "add", "update", "pop", "popitem", "clear",
             "extend", "remove", "discard", "setdefault", "insert"}

#: cache-ish methods whose key args must not contain id()
_CACHE_METHODS = {"get", "put", "setdefault", "get_or_compute"}


@dataclass
class Finding:
    path: str
    line: int
    rule: str
    msg: str

    def as_dict(self) -> dict:
        return {"path": self.path, "line": self.line,
                "rule": self.rule, "msg": self.msg}


class Ctx:
    """One parsed file: tree + parent links + per-line waivers."""

    def __init__(self, src: str, rel: str):
        self.rel = rel.replace("\\", "/")
        self.tree = ast.parse(src)
        self.parents: dict[ast.AST, ast.AST] = {}
        #: every node, in ``ast.walk`` order, walked once: each of
        #: the whole-tree rules iterates this list (the walk itself
        #: was most of the linter's CPU time)
        self.nodes: list[ast.AST] = list(ast.walk(self.tree))
        for node in self.nodes:
            for child in ast.iter_child_nodes(node):
                self.parents[child] = node
        self.waivers: dict[int, set[str]] = {}
        for i, line in enumerate(src.splitlines(), start=1):
            m = _WAIVER_RE.search(line)
            if m:
                self.waivers[i] = {r.strip() for r in
                                   m.group(1).split(",") if r.strip()}

    def ancestors(self, node: ast.AST):
        """(child, parent) pairs walking from ``node`` to the root."""
        cur = node
        while True:
            parent = self.parents.get(cur)
            if parent is None:
                return
            yield cur, parent
            cur = parent


def dotted(node: ast.AST) -> str | None:
    """``a.b.c`` for Name/Attribute chains, else None."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _final_ident(node: ast.AST) -> str | None:
    """Last identifier of an expression (``self._lock`` → ``_lock``)."""
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Call):
        return _final_ident(node.func)
    return None


def _is_lockish(expr: ast.AST) -> bool:
    ident = _final_ident(expr)
    return ident is not None and bool(_LOCKISH_RE.search(ident))


def _under_lock(ctx: Ctx, node: ast.AST) -> bool:
    """Is ``node`` lexically inside a ``with <lock>:`` body?"""
    for _child, parent in ctx.ancestors(node):
        if isinstance(parent, (ast.With, ast.AsyncWith)):
            if any(_is_lockish(item.context_expr)
                   for item in parent.items):
                return True
    return False


def _body_calls(body: list[ast.stmt]):
    """Every Call lexically in ``body``, NOT descending into nested
    function/lambda definitions (closures run later, not here)."""
    stack: list[ast.AST] = list(body)
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            continue
        if isinstance(node, ast.Call):
            yield node
        stack.extend(ast.iter_child_nodes(node))


# ---------------------------------------------------------------------------
# rules: each is (name, path-predicate, checker(ctx) -> [Finding])
# ---------------------------------------------------------------------------

def _in_pkg(rel: str) -> bool:
    return rel.startswith(PKG + "/")


def _scope_pkg_tools(rel: str) -> bool:
    return _in_pkg(rel) or rel.startswith("tools/")


def rule_ttlcache_offplane(ctx: Ctx) -> list[Finding]:
    out = []
    for node in ctx.nodes:
        if isinstance(node, ast.Call):
            name = dotted(node.func)
            if name and name.split(".")[-1] == "TtlCache":
                out.append(Finding(
                    ctx.rel, node.lineno, "ttlcache-offplane",
                    "raw TtlCache() off the cache plane — use "
                    "cache.plane (generation invalidation, "
                    "single-flight)"))
    return out


def _ttl_scope(rel: str) -> bool:
    return _in_pkg(rel) and rel not in (
        f"{PKG}/cache/plane.py", f"{PKG}/utils/ttlcache.py")


def rule_urllib_in_parallel(ctx: Ctx) -> list[Finding]:
    out = []
    for node in ctx.nodes:
        bad = None
        if isinstance(node, ast.Import):
            if any(a.name.split(".")[0] == "urllib" for a in node.names):
                bad = "import urllib"
        elif isinstance(node, ast.ImportFrom):
            if node.module and node.module.split(".")[0] == "urllib":
                bad = f"from {node.module} import ..."
        elif isinstance(node, ast.Call):
            name = dotted(node.func)
            if name and name.split(".")[-1] == "urlopen":
                bad = "urlopen()"
        if bad:
            out.append(Finding(
                ctx.rel, node.lineno, "urllib-in-parallel",
                f"{bad} in parallel/ — all cross-shard HTTP goes "
                "through transport.py (pooling, hedging, tracing)"))
    return out


def _urllib_scope(rel: str) -> bool:
    return (rel.startswith(f"{PKG}/parallel/")
            and not rel.endswith("/transport.py"))


def rule_bare_stats_timed(ctx: Ctx) -> list[Finding]:
    out = []
    for node in ctx.nodes:
        if isinstance(node, ast.Call) \
                and dotted(node.func) == "g_stats.timed":
            out.append(Finding(
                ctx.rel, node.lineno, "bare-stats-timed",
                "bare g_stats.timed() on the query path — use "
                "trace.timed_span (feeds stats AND the waterfall)"))
    return out


def _timed_scope(rel: str) -> bool:
    return any(rel.startswith(f"{PKG}/{d}/")
               for d in ("query", "parallel", "serve"))


#: stats/trace entry points whose first positional argument is a
#: metric name — a dynamically built name there mints a new time
#: series per distinct value (the devindex.wave_f1+f2_n5 class:
#: one gauge per observed wave count, unbounded dashboards).
_STATS_NAME_FUNCS = {
    "g_stats.count", "g_stats.gauge", "g_stats.record_ms",
    "g_stats.timed", "trace.record", "trace.timed_span",
    "trace_mod.record", "trace_mod.timed_span",
}


def rule_stats_cardinality(ctx: Ctx) -> list[Finding]:
    out = []
    for node in ctx.nodes:
        if not (isinstance(node, ast.Call)
                and dotted(node.func) in _STATS_NAME_FUNCS
                and node.args):
            continue
        arg = node.args[0]
        dyn = None
        if isinstance(arg, ast.JoinedStr) and any(
                isinstance(v, ast.FormattedValue) for v in arg.values):
            dyn = "an f-string"
        elif isinstance(arg, ast.Call) \
                and isinstance(arg.func, ast.Attribute) \
                and arg.func.attr == "format":
            dyn = ".format()"
        elif isinstance(arg, ast.BinOp) \
                and isinstance(arg.op, ast.Mod):
            dyn = "%-formatting"
        elif isinstance(arg, ast.BinOp) \
                and isinstance(arg.op, ast.Add):
            dyn = "concatenation"
        if dyn:
            out.append(Finding(
                ctx.rel, node.lineno, "stats-cardinality",
                f"stat name built with {dyn} — every distinct value "
                "mints a new time series (unbounded cardinality); "
                "bucket the variable and look the name up from a "
                "module-level literal table"))
    return out


def _stats_name_scope(rel: str) -> bool:
    return rel.startswith(f"{PKG}/query/")


def rule_id_key(ctx: Ctx) -> list[Finding]:
    out = []
    for node in ctx.nodes:
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Name)
                and node.func.id == "id"):
            continue
        keyish = False
        for child, parent in ctx.ancestors(node):
            if isinstance(parent, ast.Tuple):
                keyish = True
            elif isinstance(parent, ast.Dict) and child in parent.keys:
                keyish = True
            elif isinstance(parent, ast.Subscript) \
                    and child is parent.slice:
                keyish = True
            elif isinstance(parent, ast.Call) and child is not parent.func:
                ident = _final_ident(parent.func)
                if ident in _CACHE_METHODS:
                    keyish = True
            if keyish:
                break
        if keyish:
            out.append(Finding(
                ctx.rel, node.lineno, "id-key",
                "id() in a cache/dict key — CPython reuses addresses "
                "after GC, so dead objects alias live ones (the PR 4 "
                "id(conf) bug); key on identity-stable values"))
    return out


def rule_blocking_under_lock(ctx: Ctx) -> list[Finding]:
    out = []
    for node in ctx.nodes:
        if not isinstance(node, (ast.With, ast.AsyncWith)):
            continue
        if not any(_is_lockish(item.context_expr)
                   for item in node.items):
            continue
        for call in _body_calls(node.body):
            name = dotted(call.func)
            if name is None:
                continue
            if name in _BLOCKING_EXACT \
                    or name.startswith(_BLOCKING_PREFIXES):
                out.append(Finding(
                    ctx.rel, call.lineno, "blocking-under-lock",
                    f"{name}() inside a `with lock:` body — every "
                    "thread behind the lock stalls for the call"))
    return out


def rule_silent_except(ctx: Ctx) -> list[Finding]:
    out = []

    def broad(t: ast.AST | None) -> bool:
        if t is None:
            return True
        if isinstance(t, ast.Name):
            return t.id in ("Exception", "BaseException")
        if isinstance(t, ast.Tuple):
            return any(broad(e) for e in t.elts)
        return False

    for node in ctx.nodes:
        if not isinstance(node, ast.ExceptHandler):
            continue
        if node.type is None:
            out.append(Finding(
                ctx.rel, node.lineno, "silent-except",
                "bare `except:` — catches KeyboardInterrupt/SystemExit "
                "too; name the exception"))
        elif broad(node.type) and len(node.body) == 1 \
                and isinstance(node.body[0], ast.Pass):
            out.append(Finding(
                ctx.rel, node.lineno, "silent-except",
                "`except Exception: pass` — failures must at least "
                "count (g_stats) or log"))
    return out


def rule_mutable_default(ctx: Ctx) -> list[Finding]:
    out = []
    for node in ctx.nodes:
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        defaults = list(node.args.defaults) + [
            d for d in node.args.kw_defaults if d is not None]
        for d in defaults:
            mutable = isinstance(d, (ast.List, ast.Dict, ast.Set)) or (
                isinstance(d, ast.Call)
                and isinstance(d.func, ast.Name)
                and d.func.id in ("list", "dict", "set"))
            if mutable:
                out.append(Finding(
                    ctx.rel, d.lineno, "mutable-default",
                    "mutable default argument — shared across every "
                    "call; default to None and create inside"))
    return out


def rule_thread_spawn(ctx: Ctx) -> list[Finding]:
    out = []
    for node in ctx.nodes:
        if isinstance(node, ast.Call):
            name = dotted(node.func)
            if name and (name == "Thread"
                         or name.endswith(".Thread")):
                out.append(Finding(
                    ctx.rel, node.lineno, "thread-spawn",
                    "raw threading.Thread — use utils.threads.spawn/"
                    "make_thread (named daemon threads; lockcheck and "
                    "the profiler need the names)"))
    return out


def _thread_scope(rel: str) -> bool:
    return _in_pkg(rel) and rel != f"{PKG}/utils/threads.py"


#: signal/fork primitives that create or destroy processes behind the
#: fleet plane's back (``proc.kill()``/``send_signal()`` methods on a
#: Popen handle stay legal — they act on a handle someone owns)
_PROC_CALLS = {"os.kill", "os.killpg", "os.fork", "os.forkpty"}


def rule_proc_spawn(ctx: Ctx) -> list[Finding]:
    out = []
    for node in ctx.nodes:
        if not isinstance(node, ast.Call):
            continue
        name = dotted(node.func)
        if not name:
            continue
        if name == "Popen" or name.endswith(".Popen"):
            what = "subprocess.Popen"
        elif name in _PROC_CALLS:
            what = name
        else:
            continue
        out.append(Finding(
            ctx.rel, node.lineno, "proc-spawn",
            f"{what} outside the fleet plane — child processes and "
            "signals belong to parallel/fleet.py (supervised, "
            "process-grouped, reaped at teardown) or utils/chaos.py "
            "(aimed faults); a stray spawn/kill leaks orphans no "
            "teardown reaps"))
    return out


def _proc_scope(rel: str) -> bool:
    """Package + tests, minus the two modules whose job this is.
    tools/ is out of scope by construction — build/ops scripts run
    outside the serving tree."""
    if rel in (f"{PKG}/parallel/fleet.py", f"{PKG}/utils/chaos.py"):
        return False
    return rel.startswith((f"{PKG}/", "tests/"))


#: the classes whose construction mints HBM-resident state
_RESIDENCY_CLASSES = {"DeviceIndex", "ResidentLoop"}


def rule_residency_bypass(ctx: Ctx) -> list[Finding]:
    """DeviceIndex/ResidentLoop constructed outside the residency
    plane — device buffers the ResidencyManager never tracks: the
    tenant LRU can't evict them under membudget pressure, the
    'device' label never bills them, and delColl can't unserve
    them. Go through query/engine's factories
    (``build_device_index`` / ``spawn_resident_loop`` /
    ``get_resident_loop``), which serve/tenancy.py owns."""
    out = []
    for node in ctx.nodes:
        if not isinstance(node, ast.Call):
            continue
        name = dotted(node.func)
        tail = name.split(".")[-1] if name else ""
        if tail in _RESIDENCY_CLASSES:
            out.append(Finding(
                ctx.rel, node.lineno, "residency-bypass",
                f"{tail}() outside the residency plane — buffers the "
                "ResidencyManager can't evict, bill, or unserve; use "
                "query/engine's build_device_index / "
                "spawn_resident_loop / get_resident_loop (owned by "
                "serve/tenancy.py)"))
    return out


def _residency_scope(rel: str) -> bool:
    """Package only, minus the residency plane and the engine
    factories. Tests stay out of scope — they construct ResidentLoop
    directly against fakes."""
    return _in_pkg(rel) and rel not in (
        f"{PKG}/serve/tenancy.py", f"{PKG}/query/engine.py")


def _module_mutables(tree: ast.Module) -> set[str]:
    """Module-level names bound to mutable containers."""
    mutables: set[str] = set()
    for stmt in tree.body:
        targets: list[ast.expr] = []
        if isinstance(stmt, ast.Assign):
            targets, value = stmt.targets, stmt.value
        elif isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
            targets, value = [stmt.target], stmt.value
        else:
            continue
        is_mut = isinstance(value, (ast.Dict, ast.List, ast.Set,
                                    ast.ListComp, ast.DictComp,
                                    ast.SetComp)) or (
            isinstance(value, ast.Call)
            and _final_ident(value.func) in ("dict", "list", "set",
                                             "defaultdict",
                                             "OrderedDict", "deque",
                                             "Counter"))
        if not is_mut:
            continue
        for t in targets:
            if isinstance(t, ast.Name):
                mutables.add(t.id)
    return mutables


def rule_locked_global(ctx: Ctx) -> list[Finding]:
    mutables = _module_mutables(ctx.tree)
    if not mutables:
        return []

    out = []

    def in_function(node: ast.AST) -> bool:
        return any(isinstance(p, (ast.FunctionDef,
                                  ast.AsyncFunctionDef))
                   for _c, p in ctx.ancestors(node))

    def flag(node: ast.AST, name: str) -> None:
        if in_function(node) and not _under_lock(ctx, node):
            out.append(Finding(
                ctx.rel, node.lineno, "locked-global",
                f"module-level mutable `{name}` mutated outside a "
                "`with lock:` — request threads share it"))

    for node in ctx.nodes:
        if isinstance(node, (ast.Assign, ast.AugAssign, ast.Delete)):
            targets = node.targets if isinstance(node, (ast.Assign,
                                                        ast.Delete)) \
                else [node.target]
            for t in targets:
                if isinstance(t, ast.Subscript) \
                        and isinstance(t.value, ast.Name) \
                        and t.value.id in mutables:
                    flag(node, t.value.id)
        elif isinstance(node, ast.Call) \
                and isinstance(node.func, ast.Attribute) \
                and isinstance(node.func.value, ast.Name) \
                and node.func.value.id in mutables \
                and node.func.attr in _MUTATORS:
            flag(node, node.func.value.id)
    return out


def _locked_global_scope(rel: str) -> bool:
    return rel.startswith((f"{PKG}/serve/", f"{PKG}/parallel/"))


def rule_device_sync(ctx: Ctx) -> list[Finding]:
    # the resident serving loop is the one file where even ASYNC
    # host→device traffic is banned: submit() runs on request threads
    # and the loop's contract is "enqueue only" — staging transfers
    # belong in devindex.py's issue path
    resident = ctx.rel == f"{PKG}/query/resident.py"
    out = []
    for node in ctx.nodes:
        if not isinstance(node, ast.Call):
            continue
        name = dotted(node.func)
        tail = name.split(".")[-1] if name else ""
        hit = None
        if tail == "device_get":
            hit = "device_get"
        elif isinstance(node.func, ast.Attribute) \
                and node.func.attr == "block_until_ready":
            hit = "block_until_ready"
        elif resident and tail in ("device_put", "asarray"):
            out.append(Finding(
                ctx.rel, node.lineno, "device-sync",
                f"{tail} in the resident loop — the enqueue path must "
                "not stage device buffers; issue_batch in "
                "query/devindex.py owns host→device transfers"))
            continue
        if hit:
            out.append(Finding(
                ctx.rel, node.lineno, "device-sync",
                f"{hit} outside the device boundary — host syncs "
                "serialize the TPU pipeline; keep them in "
                "query/devindex.py or query/scorer.py"))
    return out


def _device_scope(rel: str) -> bool:
    return _in_pkg(rel) and rel not in (
        f"{PKG}/query/devindex.py", f"{PKG}/query/scorer.py",
        f"{PKG}/build/devbuild.py")


def _devbuild_scope(rel: str) -> bool:
    return rel == f"{PKG}/build/devbuild.py"


#: the numpy orderings whose presence means a posting stage fell back
#: to the host (each has a jnp twin the ingest plane must use instead)
_HOST_SORTS = {"sort", "unique", "argsort", "lexsort"}


def rule_host_sort(ctx: Ctx) -> list[Finding]:
    """``build/devbuild.py`` is the device ingest plane: the posting
    sort/dedup/pack pipeline stays on-chip by contract (mirroring the
    device-sync fence on ``query/resident.py``). A ``np.sort`` /
    ``np.unique`` / ``np.argsort`` / ``sorted`` call there means a
    stage quietly fell back to host ordering — exactly the O(corpus)
    CPU work the plane exists to remove. Host ordering belongs to the
    oracle pipeline in ``query/devindex.py``."""
    out = []
    for node in ctx.nodes:
        if not isinstance(node, ast.Call):
            continue
        name = dotted(node.func)
        if not name:
            continue
        parts = name.split(".")
        if parts[0] in ("np", "numpy") and parts[-1] in _HOST_SORTS:
            hit = name
        elif name == "sorted":
            hit = "sorted"
        else:
            continue
        out.append(Finding(
            ctx.rel, node.lineno, "host-sort",
            f"{hit} in the device ingest plane — posting "
            "sort/dedup/pack must stay on-chip (jnp.lexsort / "
            "segmented scans); host ordering belongs to the oracle "
            "pipeline in query/devindex.py"))
    return out


#: cross-chip collectives — the ICI traffic primitives. One module owns
#: them so the mesh topology (axis names, gather layout, replica
#: folding) has a single home; a collective elsewhere silently couples
#: that file to the serving mesh shape
_MESH_COLLECTIVES = {"all_gather", "psum", "pmean"}


def rule_mesh_collective(ctx: Ctx) -> list[Finding]:
    """``jax.lax.all_gather``/``psum``/``pmean`` outside
    parallel/sharded.py: cross-shard collectives belong to the mesh
    plane (the Msg3a merge program), not to per-shard kernels — scorer
    and devindex code must stay mesh-agnostic so the flat single-chip
    path runs it unchanged."""
    out = []
    for node in ctx.nodes:
        if not isinstance(node, ast.Call):
            continue
        name = dotted(node.func)
        tail = name.split(".")[-1] if name else ""
        if tail in _MESH_COLLECTIVES:
            out.append(Finding(
                ctx.rel, node.lineno, "mesh-collective",
                f"{tail} outside parallel/sharded.py — cross-shard "
                "collectives live in the mesh plane; keep per-shard "
                "kernels mesh-agnostic and merge in the shard_map "
                "program"))
    return out


def _mesh_collective_scope(rel: str) -> bool:
    return _in_pkg(rel) and rel != f"{PKG}/parallel/sharded.py"


# ---------------------------------------------------------------------------
# jit trace-discipline family
# ---------------------------------------------------------------------------

#: modules that OWN device↔host traffic: devindex's collect path and
#: scorer's packed fetch (the device-sync boundary) plus the mesh
#: path's replicated-output materialization in sharded.py
_JIT_TRANSFER_BOUNDARY = (
    f"{PKG}/query/devindex.py", f"{PKG}/query/scorer.py",
    f"{PKG}/parallel/sharded.py", f"{PKG}/build/devbuild.py")

_ARRAYISH_CALLS = {"np.array", "np.asarray", "numpy.array",
                   "numpy.asarray", "jnp.array", "jnp.asarray",
                   "jax.numpy.array", "jax.numpy.asarray"}

#: decorators that make a jit-wrapping factory safe (one wrapper per
#: distinct key, not one per call)
_CACHED_DECOS = {"lru_cache", "cache", "cached_property"}

_MATERIALIZERS = {"float", "int", "bool"}
_HOST_ARRAY_CALLS = {"np.asarray", "np.array", "numpy.asarray",
                     "numpy.array"}
_MATERIALIZE_METHODS = {"item", "tolist", "__array__"}


def _is_jax_jit(node: ast.AST) -> bool:
    return dotted(node) == "jax.jit"


def _jit_wrap_call(node: ast.Call) -> bool:
    """``jax.jit(...)`` or ``[functools.]partial(jax.jit, ...)``."""
    if _is_jax_jit(node.func):
        return True
    fn = dotted(node.func)
    return fn in ("partial", "functools.partial") \
        and bool(node.args) and _is_jax_jit(node.args[0])


def _jit_kwargs(call: ast.Call) -> tuple[set[str], set[int]]:
    """(static_argnames, donate_argnums) literals of a jit wrap."""
    statics: set[str] = set()
    donate: set[int] = set()
    for kw in call.keywords:
        vals = kw.value.elts if isinstance(kw.value, ast.Tuple) \
            else [kw.value]
        if kw.arg == "static_argnames":
            statics |= {v.value for v in vals
                        if isinstance(v, ast.Constant)
                        and isinstance(v.value, str)}
        elif kw.arg == "donate_argnums":
            donate |= {v.value for v in vals
                       if isinstance(v, ast.Constant)
                       and isinstance(v.value, int)}
    return statics, donate


@dataclass
class _JitSite:
    name: str
    statics: set
    donate: set
    def_node: ast.FunctionDef | None


def _jit_registry(ctx: Ctx) -> dict[str, _JitSite]:
    """Per-file map of names bound to jit-wrapped callables: decorated
    defs (``@jax.jit`` / ``@partial(jax.jit, ...)``) plus module-level
    ``name = jax.jit(fn, ...)`` rebinds."""
    reg = getattr(ctx, "_jit_reg", None)
    if reg is not None:
        return reg
    reg = {}
    defs = {n.name: n for n in ctx.nodes
            if isinstance(n, ast.FunctionDef)}
    for node in ctx.nodes:
        if isinstance(node, ast.FunctionDef):
            for deco in node.decorator_list:
                if _is_jax_jit(deco):
                    statics, donate = set(), set()
                elif isinstance(deco, ast.Call) and _jit_wrap_call(deco):
                    statics, donate = _jit_kwargs(deco)
                else:
                    continue
                reg[node.name] = _JitSite(node.name, statics, donate,
                                          node)
        elif isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name) \
                and isinstance(node.value, ast.Call) \
                and _is_jax_jit(node.value.func):
            statics, donate = _jit_kwargs(node.value)
            inner = node.value.args[0] if node.value.args else None
            def_node = defs.get(inner.id) \
                if isinstance(inner, ast.Name) else None
            reg[node.targets[0].id] = _JitSite(
                node.targets[0].id, statics, donate, def_node)
    ctx._jit_reg = reg
    return reg


def _enclosing_function(ctx: Ctx, node: ast.AST):
    for _c, p in ctx.ancestors(node):
        if isinstance(p, (ast.FunctionDef, ast.AsyncFunctionDef)):
            return p
    return None


def _local_exprs(fn: ast.AST | None) -> dict[str, list[ast.AST]]:
    """name → RHS expressions assigned to it inside ``fn`` — the few
    hops of local dataflow static-arg provenance needs."""
    out: dict[str, list[ast.AST]] = {}
    if fn is None:
        return out
    for node in ast.walk(fn):
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name):
            out.setdefault(node.targets[0].id, []).append(node.value)
        elif isinstance(node, ast.AugAssign) \
                and isinstance(node.target, ast.Name):
            out.setdefault(node.target.id, []).append(node.value)
    return out


def _value_nodes(expr: ast.AST):
    """Like ast.walk, but skips ``IfExp`` tests: a conditional
    quantizes a value into its branch set (``A if n <= A else B`` is
    two-valued however ``n`` was derived), so sizes read only in the
    test don't make the value unstable."""
    stack = [expr]
    while stack:
        node = stack.pop()
        yield node
        if isinstance(node, ast.IfExp):
            stack.extend((node.body, node.orelse))
        else:
            stack.extend(ast.iter_child_nodes(node))


def _expr_matches(expr, amap, pred, depth=4, seen=None) -> bool:
    """Does ``pred`` hit any node of ``expr``, chasing local Name
    assignments up to ``depth`` hops?"""
    if seen is None:
        seen = set()
    for node in _value_nodes(expr):
        if pred(node):
            return True
        if depth > 0 and isinstance(node, ast.Name) \
                and isinstance(node.ctx, ast.Load) \
                and node.id not in seen and node.id in amap:
            seen.add(node.id)
            for rhs in amap[node.id]:
                if _expr_matches(rhs, amap, pred, depth - 1, seen):
                    return True
    return False


def _is_len_or_shape(node: ast.AST) -> bool:
    if isinstance(node, ast.Call) and isinstance(node.func, ast.Name) \
            and node.func.id == "len":
        return True
    # x.shape[i] — a runtime size is just as unstable as len()
    return isinstance(node, ast.Subscript) \
        and isinstance(node.value, ast.Attribute) \
        and node.value.attr == "shape"


def _is_bucketish(node: ast.AST) -> bool:
    if isinstance(node, ast.Call):
        ident = _final_ident(node.func)
        return ident is not None and "bucket" in ident.lower()
    return False


def rule_jit_unstable_static(ctx: Ctx) -> list[Finding]:
    """Unstable value passed to a static_argnames parameter — every
    distinct value is a fresh XLA compile (retrace cliff + unbounded
    jit cache)."""
    reg = _jit_registry(ctx)
    out: list[Finding] = []
    if not reg:
        return out
    for node in ctx.nodes:
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Name)
                and node.func.id in reg):
            continue
        site = reg[node.func.id]
        if not site.statics:
            continue
        amap = _local_exprs(_enclosing_function(ctx, node))
        for kw in node.keywords:
            if kw.arg not in site.statics:
                continue
            frag = None
            for n in ast.walk(kw.value):
                if isinstance(n, ast.Constant) \
                        and isinstance(n.value, float):
                    frag = "a float"
                elif isinstance(n, ast.Call) \
                        and isinstance(n.func, ast.Name) \
                        and n.func.id == "float":
                    frag = "a float()"
                elif isinstance(n, (ast.Dict, ast.List, ast.Set,
                                    ast.DictComp, ast.ListComp,
                                    ast.SetComp)):
                    frag = "an unhashable container"
                elif isinstance(n, ast.Call) \
                        and dotted(n.func) in _ARRAYISH_CALLS:
                    frag = "an array value"
                if frag:
                    break
            if frag is None \
                    and _expr_matches(kw.value, amap, _is_len_or_shape) \
                    and not _expr_matches(kw.value, amap, _is_bucketish):
                frag = "a len()/shape-derived value with no bucket " \
                       "rounding"
            if frag:
                out.append(Finding(
                    ctx.rel, kw.value.lineno, "jit-unstable-static",
                    f"{frag} passed to static arg '{kw.arg}' of "
                    f"{node.func.id}() — every distinct value is a "
                    "fresh XLA compile; statics must be bucketed "
                    "stable ints/bools (query/packer._bucket)"))
    return out


def rule_jit_in_body(ctx: Ctx) -> list[Finding]:
    """jax.jit wrapped inside a function body — a fresh wrapper (and
    empty compile cache) per call, so nothing is ever warm."""
    out: list[Finding] = []
    for node in ctx.nodes:
        if not (isinstance(node, ast.Call) and _jit_wrap_call(node)):
            continue
        encl = None
        for child, parent in ctx.ancestors(node):
            if isinstance(parent, (ast.FunctionDef,
                                   ast.AsyncFunctionDef)):
                if child in parent.decorator_list:
                    continue  # decorator position == module-level wrap
                encl = parent
                break
        if encl is None:
            continue
        if any(_final_ident(d) in _CACHED_DECOS
               for d in encl.decorator_list):
            continue  # memoized factory: one wrapper per key
        out.append(Finding(
            ctx.rel, node.lineno, "jit-in-body",
            f"jax.jit inside {encl.name}() — a fresh wrapper (and "
            "compile cache) per call; hoist to module level or "
            "memoize the factory with lru_cache"))
    return out


def _jit_body_scope(rel: str) -> bool:
    return any(rel.startswith(f"{PKG}/{d}/")
               for d in ("query", "parallel", "serve"))


def rule_jit_mutable_closure(ctx: Ctx) -> list[Finding]:
    """A jitted function reading module-level mutable state — the
    value is frozen into the traced program and silently goes stale
    when the container mutates."""
    reg = _jit_registry(ctx)
    muts = _module_mutables(ctx.tree)
    out: list[Finding] = []
    if not (reg and muts):
        return out
    for site in reg.values():
        fn = site.def_node
        if fn is None:
            continue
        a = fn.args
        local = {p.arg for p in
                 a.args + a.kwonlyargs + a.posonlyargs}
        for va in (a.vararg, a.kwarg):
            if va is not None:
                local.add(va.arg)
        for node in ast.walk(fn):
            if isinstance(node, ast.Name) \
                    and isinstance(node.ctx, ast.Store):
                local.add(node.id)
        for node in ast.walk(fn):
            if isinstance(node, ast.Name) \
                    and isinstance(node.ctx, ast.Load) \
                    and node.id in muts and node.id not in local:
                out.append(Finding(
                    ctx.rel, node.lineno, "jit-mutable-closure",
                    f"jitted {fn.name}() reads module-level mutable "
                    f"'{node.id}' at trace time — the traced value is "
                    "frozen into the compiled program and goes stale "
                    "when the container mutates; pass it as an "
                    "argument"))
    return out


def rule_jit_donated_reuse(ctx: Ctx) -> list[Finding]:
    """An argument donated via donate_argnums read after the donating
    call — donation deallocates the buffer; the read returns garbage
    (or crashes) on real backends."""
    reg = _jit_registry(ctx)
    donators = {n: s for n, s in reg.items() if s.donate}
    out: list[Finding] = []
    if not donators:
        return out
    for node in ctx.nodes:
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Name)
                and node.func.id in donators):
            continue
        site = donators[node.func.id]
        encl = _enclosing_function(ctx, node)
        if encl is None:
            continue
        targets: set[str] = set()
        parent = ctx.parents.get(node)
        if isinstance(parent, ast.Assign) and parent.value is node:
            targets = {dotted(t) for t in parent.targets} - {None}
        end = getattr(node, "end_lineno", node.lineno)
        for pos in site.donate:
            if pos >= len(node.args):
                continue
            dn = dotted(node.args[pos])
            if dn is None or dn in targets:
                continue  # rebind of the donated name: the safe idiom
            for later in ast.walk(encl):
                if isinstance(later, (ast.Name, ast.Attribute)) \
                        and later.lineno > end \
                        and isinstance(getattr(later, "ctx", None),
                                       ast.Load) \
                        and dotted(later) == dn:
                    out.append(Finding(
                        ctx.rel, later.lineno, "jit-donated-reuse",
                        f"'{dn}' donated to {node.func.id}() on line "
                        f"{node.lineno} is read afterwards — donation "
                        "deallocates the buffer; rebind the result to "
                        f"'{dn}' or drop donate_argnums"))
                    break
    return out


def _device_producer(call: ast.Call, reg) -> bool:
    name = dotted(call.func)
    if name is None:
        return False
    if isinstance(call.func, ast.Name) and name in reg:
        return True
    return name.startswith(("jnp.", "jax.numpy.")) \
        or name == "jax.device_put"


def rule_jit_implicit_transfer(ctx: Ctx) -> list[Finding]:
    """float()/.item()/np.asarray()/.tolist() on a device value
    outside the device-boundary modules — an implicit device→host
    sync on the request path."""
    reg = _jit_registry(ctx)
    # device-valued local names: single-name targets assigned from a
    # jit-wrapped or jnp-producing call, keyed by enclosing function
    dev_by_fn: dict[int, set[str]] = {}
    for node in ctx.nodes:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name) \
                and isinstance(node.value, ast.Call) \
                and _device_producer(node.value, reg):
            fnkey = id(_enclosing_function(ctx, node) or ctx.tree)
            dev_by_fn.setdefault(fnkey, set()).add(node.targets[0].id)

    def is_dev(expr: ast.AST, fnkey: int) -> bool:
        if isinstance(expr, ast.Name) \
                and expr.id in dev_by_fn.get(fnkey, ()):
            return True
        return isinstance(expr, ast.Call) \
            and _device_producer(expr, reg)

    out: list[Finding] = []
    for node in ctx.nodes:
        if not isinstance(node, ast.Call):
            continue
        fnkey = id(_enclosing_function(ctx, node) or ctx.tree)
        name = dotted(node.func)
        hit = None
        if isinstance(node.func, ast.Name) \
                and name in _MATERIALIZERS \
                and node.args and is_dev(node.args[0], fnkey):
            hit = f"{name}()"
        elif name in _HOST_ARRAY_CALLS and node.args \
                and is_dev(node.args[0], fnkey):
            hit = f"{name}()"
        elif isinstance(node.func, ast.Attribute) \
                and node.func.attr in _MATERIALIZE_METHODS \
                and is_dev(node.func.value, fnkey):
            hit = f".{node.func.attr}()"
        if hit:
            out.append(Finding(
                ctx.rel, node.lineno, "jit-implicit-transfer",
                f"{hit} on a device value outside the device boundary "
                "— an implicit host sync serializes the pipeline; "
                "fetch at the boundary (devindex collect / scorer / "
                "sharded) or keep the value on device"))
    return out


def _jit_transfer_scope(rel: str) -> bool:
    return _in_pkg(rel) and rel not in _JIT_TRANSFER_BOUNDARY


def rule_bare_deadline(ctx: Ctx) -> list[Finding]:
    """Hand-rolled deadline arithmetic on the budgeted paths.

    ``time.monotonic() + timeout`` mints a deadline no header stamps
    and no abandon checkpoint sees; ``x - time.monotonic()`` is its
    remaining-time read. Both must come through
    ``utils.deadline.Deadline``. Duration measurement
    (``time.monotonic() - t0``: the time call on the LEFT of the
    subtraction) is not a deadline and stays legal."""
    def is_now(expr: ast.AST) -> bool:
        return (isinstance(expr, ast.Call)
                and dotted(expr.func) in ("time.time",
                                          "time.monotonic"))

    out = []
    for node in ctx.nodes:
        if not isinstance(node, ast.BinOp):
            continue
        if isinstance(node.op, ast.Add) \
                and (is_now(node.left) or is_now(node.right)):
            what = "now + budget mints a deadline"
        elif isinstance(node.op, ast.Sub) and is_now(node.right):
            what = "x - now reads a hand-rolled deadline"
        else:
            continue
        out.append(Finding(
            ctx.rel, node.lineno, "bare-deadline",
            f"{what} outside the Deadline helper — use "
            "utils.deadline.Deadline (.after/.remaining/.clamp) so "
            "the budget rides X-OSSE-Deadline and the "
            "deadline.abandoned counters can't be bypassed"))
    return out


def rule_adhoc_timing(ctx: Ctx) -> list[Finding]:
    """Ad-hoc latency measurement on the timed paths.

    ``time.perf_counter() - t0`` (or ``time.time() - t0``) computes a
    duration the aggregate plane and the trace plane never see — the
    two-timing-planes-drift bug class: a latency that shows up in a
    log line but not on /admin/perf, or vice versa. Measured intervals
    come through ``trace.timed_span`` (measures for you) or
    ``trace.record`` (attributes an interval you timed yourself) —
    both feed g_stats AND the waterfall. ``time.monotonic() - t0``
    stays legal: that is elapsed-budget arithmetic (deadlines,
    backoff), not a latency measurement."""
    def is_clock(expr: ast.AST) -> bool:
        return (isinstance(expr, ast.Call)
                and dotted(expr.func) in ("time.perf_counter",
                                          "time.time"))

    out = []
    for node in ctx.nodes:
        if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Sub) \
                and is_clock(node.left):
            out.append(Finding(
                ctx.rel, node.lineno, "adhoc-timing",
                "ad-hoc clock delta measures a latency neither "
                "/admin/perf nor the trace waterfall will see — use "
                "trace.timed_span (or trace.record for an interval "
                "you timed yourself); both feed g_stats AND the "
                "trace plane"))
    return out


def _admission_scope(rel: str) -> bool:
    """serve/ routes only — admission.py IS the gate, and the other
    planes (query/, parallel/) sit below it by design."""
    return rel.startswith(f"{PKG}/serve/") \
        and not rel.endswith("/admission.py")


#: serve/ functions allowed to touch the dispatch planes directly:
#: the one call site that runs AFTER AdmissionGate.admit()
_ADMISSION_SANCTIONED = {"_render_search"}


def rule_admission_bypass(ctx: Ctx) -> list[Finding]:
    """Dispatch-plane calls from serve/ that skip the admission gate.

    ``_batcher.search(...)`` / ``get_resident_loop(...).submit(...)``
    from a serve route hands work to the device planes without
    admission control — under overload that path grows an unbounded
    queue and bypasses the tier/shed accounting the load gates assert
    on. Route through ``AdmissionGate.admit()`` first (the sanctioned
    call site is ``_render_search``, which runs under the admitted
    token)."""
    #: names bound from get_resident_loop(...) anywhere in the file —
    #: one hop of dataflow catches `loop = get_resident_loop(c)`
    tainted: set[str] = set()
    for node in ctx.nodes:
        if isinstance(node, ast.Assign) \
                and isinstance(node.value, ast.Call) \
                and _final_ident(node.value.func) \
                == "get_resident_loop":
            for t in node.targets:
                if isinstance(t, ast.Name):
                    tainted.add(t.id)

    def bypasses(node: ast.Call) -> str | None:
        if not isinstance(node.func, ast.Attribute):
            return None
        val = node.func.value
        chain = dotted(val) or ""
        if node.func.attr == "search" \
                and chain.endswith("_batcher"):
            return f"{chain}.search()"
        if node.func.attr == "submit" and (
                "resident" in chain
                or (isinstance(val, ast.Call)
                    and _final_ident(val.func) == "get_resident_loop")
                or (isinstance(val, ast.Name)
                    and val.id in tainted)):
            return "resident submit()"
        return None

    out = []
    for node in ctx.nodes:
        if not isinstance(node, ast.Call):
            continue
        hit = bypasses(node)
        if hit is None:
            continue
        fn = _enclosing_function(ctx, node)
        if fn is not None and fn.name in _ADMISSION_SANCTIONED:
            continue
        out.append(Finding(
            ctx.rel, node.lineno, "admission-bypass",
            f"{hit} from a serve route skips the admission gate — "
            "unbounded queueing and untiered overload; go through "
            "AdmissionGate.admit() (only _render_search may touch "
            "the dispatch planes directly)"))
    return out


def _conc_scope(rel: str) -> bool:
    """The planes whose objects real threads share — the schedcheck
    scenario surface: query/, serve/, parallel/, cache/."""
    return any(rel.startswith(f"{PKG}/{d}/")
               for d in ("query", "serve", "parallel", "cache"))


#: constructor-shaped methods whose writes happen before the object is
#: published to other threads (dataclasses run __post_init__ inside
#: generated __init__)
_PREPUB = ("__init__", "__post_init__")


def _locked_method(fn: ast.AST) -> bool:
    """The repo's caller-holds-the-lock conventions: ``*_locked``
    method names (admission.py) and locked-ish decorators (rdblite's
    ``@_locked``) mean the lock is held on entry — writes inside are
    protected even without a lexical ``with``."""
    if fn.name.endswith("_locked"):
        return True
    return any((_final_ident(d) or "").endswith("locked")
               for d in fn.decorator_list)


def _self_attr(node: ast.AST) -> str | None:
    """``x`` for a ``self.x`` expression, else None."""
    if isinstance(node, ast.Attribute) \
            and isinstance(node.value, ast.Name) \
            and node.value.id == "self":
        return node.attr
    return None


def _body_stmts(body: list[ast.stmt]):
    """Every node lexically in ``body``, NOT descending into nested
    function/lambda definitions (closures run later, elsewhere)."""
    stack: list[ast.AST] = list(body)
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            continue
        yield node
        stack.extend(ast.iter_child_nodes(node))


def rule_shared_state_unlocked(ctx: Ctx) -> list[Finding]:
    """Per class: a ``self.``-attribute written under a lock in one
    method (lexical ``with <lockish>:``, a ``*_locked`` name, or a
    locked decorator) but without one in another. That split is the
    lost-update shape schedcheck's explorer demonstrates dynamically —
    two writers interleaving between read and write. ``__init__``
    writes are pre-publication and exempt both ways."""
    out = []
    for cls in ctx.nodes:
        if not isinstance(cls, ast.ClassDef):
            continue
        methods = [n for n in cls.body if isinstance(
            n, (ast.FunctionDef, ast.AsyncFunctionDef))]
        #: (attr, write node, method name, lock held)
        writes: list[tuple[str, ast.AST, str, bool]] = []
        for m in methods:
            if m.name in _PREPUB:
                continue
            held = _locked_method(m)
            for node in ast.walk(m):
                if not isinstance(node, (ast.Assign, ast.AugAssign)):
                    continue
                targets = node.targets if isinstance(node, ast.Assign) \
                    else [node.target]
                for t in targets:
                    attr = _self_attr(t)
                    if attr is not None:
                        writes.append((attr, node, m.name,
                                       held or _under_lock(ctx, node)))
        locked_in: dict[str, set[str]] = {}
        for attr, _node, mname, prot in writes:
            if prot:
                locked_in.setdefault(attr, set()).add(mname)
        seen: set[tuple[str, int]] = set()
        for attr, node, mname, prot in writes:
            if prot:
                continue
            others = locked_in.get(attr, set()) - {mname}
            if not others or (attr, node.lineno) in seen:
                continue
            seen.add((attr, node.lineno))
            out.append(Finding(
                ctx.rel, node.lineno, "shared-state-unlocked",
                f"self.{attr} written without a lock here but under "
                f"one in {sorted(others)[0]}() — a thread can "
                "interleave between the two writers (the lost-update "
                "shape schedcheck explores); take the same lock"))
    return out


def rule_check_then_act(ctx: Ctx) -> list[Finding]:
    """``if k in self.d:`` / ``if self.x is None:`` followed by a
    mutation of the SAME shared container/attribute, outside any lock
    body: the classic TOCTOU — another thread can act between the
    check and the act. Lock-holding conventions (``with <lockish>:``,
    ``*_locked`` names, locked decorators) exempt the site."""
    out = []
    for node in ctx.nodes:
        if not isinstance(node, ast.If):
            continue
        fn = _enclosing_function(ctx, node)
        if fn is None or fn.name in _PREPUB or _locked_method(fn):
            continue
        if _under_lock(ctx, node):
            continue
        test, attr, shape = node.test, None, None
        if isinstance(test, ast.Compare) and len(test.ops) == 1:
            op = test.ops[0]
            if isinstance(op, (ast.In, ast.NotIn)):
                attr = _self_attr(test.comparators[0])
                shape = "membership"
            elif isinstance(op, (ast.Is, ast.IsNot)) \
                    and isinstance(test.comparators[0], ast.Constant) \
                    and test.comparators[0].value is None:
                attr = _self_attr(test.left)
                shape = "none"
        if attr is None:
            continue
        for sub in _body_stmts(node.body):
            hit = False
            if isinstance(sub, (ast.Assign, ast.AugAssign)):
                targets = sub.targets if isinstance(sub, ast.Assign) \
                    else [sub.target]
                for t in targets:
                    if isinstance(t, ast.Subscript) \
                            and _self_attr(t.value) == attr:
                        hit = True
                    elif shape == "none" and _self_attr(t) == attr:
                        hit = True
            elif isinstance(sub, ast.Delete):
                hit = any(isinstance(t, ast.Subscript)
                          and _self_attr(t.value) == attr
                          for t in sub.targets)
            elif isinstance(sub, ast.Call) \
                    and isinstance(sub.func, ast.Attribute) \
                    and sub.func.attr in _MUTATORS \
                    and _self_attr(sub.func.value) == attr:
                hit = True
            if hit:
                out.append(Finding(
                    ctx.rel, sub.lineno, "check-then-act",
                    f"self.{attr} checked then mutated without a lock "
                    "— another thread can act between the check and "
                    "this write (TOCTOU); hold the owning lock across "
                    "both"))
                break
    return out


def rule_cond_wait_no_loop(ctx: Ctx) -> list[Finding]:
    """``Condition.wait`` not inside a ``while`` predicate loop.
    Spurious wakeups and notify_all herds make a bare ``wait()`` (or
    an ``if``-guarded one) return with the predicate false; every wait
    must re-check in a loop — the shape schedcheck's notify scheduling
    exercises directly."""
    out = []
    for node in ctx.nodes:
        if not isinstance(node, ast.Call) \
                or not isinstance(node.func, ast.Attribute) \
                or node.func.attr != "wait" \
                or not _is_lockish(node.func.value):
            continue
        in_while = False
        for _child, parent in ctx.ancestors(node):
            if isinstance(parent, ast.While):
                in_while = True
                break
            if isinstance(parent, (ast.FunctionDef,
                                   ast.AsyncFunctionDef, ast.Lambda)):
                break
        if not in_while:
            out.append(Finding(
                ctx.rel, node.lineno, "cond-wait-no-loop",
                "Condition.wait outside a while predicate loop — "
                "spurious wakeups / notify_all herds return with the "
                "predicate false; wrap in `while not <predicate>:`"))
    return out


#: (rule-name, path predicate, checker)
RULES = [
    ("ttlcache-offplane", _ttl_scope, rule_ttlcache_offplane),
    ("urllib-in-parallel", _urllib_scope, rule_urllib_in_parallel),
    ("bare-stats-timed", _timed_scope, rule_bare_stats_timed),
    ("stats-cardinality", _stats_name_scope, rule_stats_cardinality),
    ("id-key", _in_pkg, rule_id_key),
    ("blocking-under-lock", _in_pkg, rule_blocking_under_lock),
    ("silent-except", _scope_pkg_tools, rule_silent_except),
    ("mutable-default", _scope_pkg_tools, rule_mutable_default),
    ("thread-spawn", _thread_scope, rule_thread_spawn),
    ("locked-global", _locked_global_scope, rule_locked_global),
    ("device-sync", _device_scope, rule_device_sync),
    ("host-sort", _devbuild_scope, rule_host_sort),
    ("mesh-collective", _mesh_collective_scope, rule_mesh_collective),
    ("jit-unstable-static", _in_pkg, rule_jit_unstable_static),
    ("jit-in-body", _jit_body_scope, rule_jit_in_body),
    ("jit-mutable-closure", _in_pkg, rule_jit_mutable_closure),
    ("jit-donated-reuse", _in_pkg, rule_jit_donated_reuse),
    ("jit-implicit-transfer", _jit_transfer_scope,
     rule_jit_implicit_transfer),
    ("bare-deadline", _timed_scope, rule_bare_deadline),
    ("adhoc-timing", _timed_scope, rule_adhoc_timing),
    ("admission-bypass", _admission_scope, rule_admission_bypass),
    ("proc-spawn", _proc_scope, rule_proc_spawn),
    ("residency-bypass", _residency_scope, rule_residency_bypass),
    ("shared-state-unlocked", _conc_scope, rule_shared_state_unlocked),
    ("check-then-act", _conc_scope, rule_check_then_act),
    ("cond-wait-no-loop", _in_pkg, rule_cond_wait_no_loop),
]

RULE_NAMES = {name for name, _p, _c in RULES}


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def check_source(src: str, rel: str) -> list[Finding]:
    """Lint one source text as if it lived at ``rel`` (posix relative
    path — rule scoping keys off it). The fixture/test entry point."""
    rel = rel.replace("\\", "/")
    for line in src.splitlines()[:5]:
        m = _PATH_PRAGMA_RE.search(line)
        if m:
            rel = m.group(1)
            break
    try:
        ctx = Ctx(src, rel)
    except SyntaxError as exc:
        return [Finding(rel, exc.lineno or 1, "syntax-error", str(exc))]
    findings: list[Finding] = []
    for name, pred, checker in RULES:
        if not pred(rel):
            continue
        for f in checker(ctx):
            if name in ctx.waivers.get(f.line, ()):
                continue
            findings.append(f)
    findings.sort(key=lambda f: (f.line, f.rule))
    return findings


def default_paths(root: Path) -> list[Path]:
    return [root / PKG, root / "tools", root / "tests"]


def iter_py_files(paths: list[Path], root: Path) -> list[Path]:
    out: list[Path] = []
    for p in paths:
        if p.is_file() and p.suffix == ".py":
            out.append(p)
        elif p.is_dir():
            for f in sorted(p.rglob("*.py")):
                if not EXCLUDE_PARTS & set(f.relative_to(root).parts):
                    out.append(f)
    return out


def changed_files(root: Path) -> list[Path]:
    """Files touched vs. HEAD: unstaged + staged + untracked.

    Parsed from NUL-separated ``--name-status`` records so rename and
    delete entries are handled explicitly: a rename (``R``/``C``, two
    path fields) contributes its NEW path, a deletion contributes
    nothing (the old path no longer exists to lint), and ``-z``
    sidesteps git's path quoting for unusual filenames."""
    import subprocess
    names: set[str] = set()
    for args in (["git", "diff", "--name-status", "-z", "-M", "HEAD"],
                 ["git", "diff", "--name-status", "-z", "-M",
                  "--cached"]):
        proc = subprocess.run(args, cwd=root, capture_output=True,
                              text=True, check=False)
        fields = proc.stdout.split("\0")
        i = 0
        while i < len(fields):
            status = fields[i].strip()
            if not status:
                i += 1
                continue
            if status[0] in "RC":  # rename/copy: status, old, new
                if i + 2 < len(fields) and fields[i + 2]:
                    names.add(fields[i + 2])
                i += 3
            elif status[0] == "D":  # deletion: nothing left to lint
                i += 2
            else:
                if i + 1 < len(fields) and fields[i + 1]:
                    names.add(fields[i + 1])
                i += 2
    proc = subprocess.run(
        ["git", "ls-files", "--others", "--exclude-standard", "-z"],
        cwd=root, capture_output=True, text=True, check=False)
    names.update(n for n in proc.stdout.split("\0") if n)
    out = []
    for n in sorted(names):
        p = root / n
        if p.suffix == ".py" and p.exists() \
                and not (EXCLUDE_PARTS & set(Path(n).parts)):
            out.append(p)
    return out


def lint_files(files: list[Path], root: Path) -> list[Finding]:
    findings: list[Finding] = []
    for f in files:
        rel = f.relative_to(root).as_posix()
        try:
            src = f.read_text(encoding="utf-8")
        except OSError as exc:
            findings.append(Finding(rel, 1, "unreadable", str(exc)))
            continue
        findings.extend(check_source(src, rel))
    return findings


def main(argv: list[str] | None = None) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        prog="osselint", description=__doc__.splitlines()[0])
    ap.add_argument("paths", nargs="*",
                    help="files/dirs to lint (default: package + "
                         "tools + tests)")
    ap.add_argument("--format", choices=("text", "json"),
                    default="text")
    ap.add_argument("--changed", action="store_true",
                    help="lint only files changed vs. git HEAD")
    ap.add_argument("--root", default=None,
                    help="repo root (default: this file's repo)")
    ap.add_argument("--list-rules", action="store_true")
    args = ap.parse_args(argv)

    if args.list_rules:
        for name, _pred, checker in RULES:
            doc = (checker.__doc__ or "").strip().splitlines()
            print(f"{name}: {doc[0] if doc else ''}")
        return 0

    root = Path(args.root).resolve() if args.root \
        else Path(__file__).resolve().parent.parent
    if args.changed:
        files = changed_files(root)
    elif args.paths:
        files = iter_py_files([Path(p).resolve() for p in args.paths],
                              root)
    else:
        files = iter_py_files(default_paths(root), root)

    findings = lint_files(files, root)
    if args.format == "json":
        print(json.dumps({"files": len(files),
                          "findings": [f.as_dict() for f in findings]},
                         indent=2))
    else:
        for f in findings:
            print(f"{f.path}:{f.line}: {f.rule}: {f.msg}")
        print(f"osselint: {len(files)} files, "
              f"{len(findings)} finding(s)")
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
