"""The h2o3-lint rules: this repo's invariants, machine-checked.

Each rule's docstring is its catalog entry (``tools/h2o3_lint.py
--rules`` prints them) and records the tightening decisions made when a
finding turned out to be a false positive — per the repo policy, FPs
tighten the rule instead of growing the baseline.
"""
from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from h2o3_tpu.analysis.core import (Finding, ModuleInfo, Rule, SEV_ERROR,
                                    SEV_WARNING, ancestors, attach_parents,
                                    dotted_name)

# ======================================================================
# transfer-seam
# ======================================================================

# Modules allowed to touch the raw JAX transfer API: they ARE the seam.
_BLESSED_TRANSFER_MODULES = (
    # the one policy point for H2D: fault seam + retry + sharding
    "h2o3_tpu/resilience.py",
    # the counted D2H choke point (telemetry.device_get) + byte counters
    "h2o3_tpu/telemetry/collectors.py",
    # partitioner internals — called FROM resilience.resilient_shard_rows,
    # it owns device placement for sharded arrays
    "h2o3_tpu/parallel/mesh.py",
    # the frame-layer choke point: spill/unspill/to_numpy count their
    # own bytes inline (record_h2d/record_d2h with fallback="frame")
    # and the unspill must run under the memman lock — it IS a seam
    "h2o3_tpu/frame/vec.py",
)


class TransferSeamRule(Rule):
    """Raw ``jax.device_put`` / ``jax.device_get`` /
    ``(jax|x).block_until_ready`` outside the blessed seam modules.

    Every H2D must flow through ``resilience.resilient_device_put`` /
    ``resilient_shard_rows`` (fault-injectable, retried, counted) and
    every ad-hoc D2H through ``telemetry.device_get`` (byte-counted), or
    the transfer-budget guards (``train.streamed_h2d_guard``,
    ``h2o3_{h2d,d2h}_pipeline_bytes_total``) silently under-report.
    Deliberate pipeline barriers (the ingest double-buffer bound, the
    train-loop timing fences) carry inline allows with a reason.

    Scope decision: "np.asarray on a device value" is also a raw D2H,
    but whether an ``np.asarray`` argument is device-resident is not
    decidable syntactically — that spelling is only covered inside hot
    zones (host-sync-hot-loop), where data is device-resident by
    construction.
    """

    name = "transfer-seam"
    severity = SEV_ERROR

    _RAW = {"jax.device_put", "jax.device_get"}

    def check_module(self, mod: ModuleInfo) -> List[Finding]:
        if mod.relpath.endswith(_BLESSED_TRANSFER_MODULES):
            return []
        out: List[Finding] = []
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            name = dotted_name(node.func)
            if name in self._RAW:
                seam = ("resilience.resilient_device_put"
                        if name.endswith("device_put")
                        else "telemetry.device_get")
                out.append(self.finding(
                    mod, node,
                    f"raw {name} outside the blessed seam modules — "
                    f"route through {seam} so the transfer is counted "
                    f"and fault-injectable"))
            elif (isinstance(node.func, ast.Attribute)
                  and node.func.attr == "block_until_ready"):
                out.append(self.finding(
                    mod, node,
                    "block_until_ready outside the blessed seam modules "
                    "— a hidden host sync; if it is a deliberate "
                    "pipeline barrier, add an inline allow with the "
                    "reason"))
        return out


# ======================================================================
# recompile-hazard
# ======================================================================

def _jit_static_names(deco: ast.AST, args: ast.arguments) -> Optional[Set[str]]:
    """If ``deco`` spells jax.jit (bare, or partial(jax.jit, ...) /
    jax.jit(...) with static_argnums/static_argnames), return the set of
    STATIC parameter names; None when deco is not a jit spelling."""
    posnames = [a.arg for a in args.posonlyargs + args.args]

    def _resolve(call: ast.Call) -> Set[str]:
        static: Set[str] = set()
        for kw in call.keywords:
            if kw.arg == "static_argnames":
                for n in ast.walk(kw.value):
                    if isinstance(n, ast.Constant) and isinstance(n.value, str):
                        static.add(n.value)
            elif kw.arg == "static_argnums":
                for n in ast.walk(kw.value):
                    if isinstance(n, ast.Constant) and isinstance(n.value, int):
                        if 0 <= n.value < len(posnames):
                            static.add(posnames[n.value])
        return static

    d = dotted_name(deco)
    if d in ("jax.jit", "jit"):
        return set()
    if isinstance(deco, ast.Call):
        head = dotted_name(deco.func)
        if head in ("jax.jit", "jit"):
            return _resolve(deco)
        if head in ("partial", "functools.partial") and deco.args:
            if dotted_name(deco.args[0]) in ("jax.jit", "jit"):
                return _resolve(deco)
    return None


def _is_static_test_ref(name_node: ast.Name) -> bool:
    """A traced-param reference that is actually trace-time static:
    ``x is None`` / ``x is not None``, ``isinstance(x, ...)``,
    ``x.shape/...``, ``len(x)`` — these resolve during tracing and
    neither fail nor force a recompile per value."""
    parent = getattr(name_node, "_h2o3_parent", None)
    if isinstance(parent, ast.Attribute) and parent.attr in (
            "shape", "ndim", "dtype", "size", "sharding", "weak_type"):
        return True
    if isinstance(parent, ast.Call):
        head = dotted_name(parent.func)
        if head in ("isinstance", "len", "callable", "type"):
            return True
    if isinstance(parent, ast.Compare):
        ops = parent.ops
        comps = [parent.left] + list(parent.comparators)
        if all(isinstance(op, (ast.Is, ast.IsNot)) for op in ops) and any(
                isinstance(c, ast.Constant) and c.value is None
                for c in comps):
            return True
    return False


class RecompileHazardRule(Rule):
    """``@jax.jit``-reachable code that hides a recompile hazard or a
    trace-time failure (the zero-recompile contract from PRs 2/3/7).

    Sub-checks:

    - **param-branch**: ``if``/``while``/ternary tests referencing a
      non-static parameter of a jitted function. On a traced value this
      raises at trace time; on a Python scalar it silently specializes
      the executable per VALUE — the exact warm-retrain recompile class
      PR 2's traced-rates work eliminated. Tests on ``x is None``,
      ``isinstance``, ``len(x)`` and ``.shape/.ndim/.dtype`` are exempt
      (static under tracing).
    - **loop-var-closure**: a jitted function DEFINED inside a loop that
      closes over the loop variable — a fresh closure constant (and a
      fresh compile) every iteration.
    - **np-on-param**: ``np.*`` called on a non-static parameter inside
      a jitted function — a host op on a tracer fails at trace time (or
      constant-folds the argument, hiding a per-call recompile).

    Tightening decisions: bucketed static specialization (the
    chunk-length-bucket pattern) passes params via static_argnums/names,
    which this rule honors; branches on them are exempt.
    """

    name = "recompile-hazard"
    severity = SEV_WARNING

    def check_module(self, mod: ModuleInfo) -> List[Finding]:
        attach_parents(mod.tree)
        out: List[Finding] = []
        # fn name -> static names, for `f = jax.jit(f, static_...)` rebinds
        rebound: Dict[str, Set[str]] = {}
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Call):
                head = dotted_name(node.func)
                if head in ("jax.jit", "jit") and node.args and \
                        isinstance(node.args[0], ast.Name):
                    static: Set[str] = set()
                    for kw in node.keywords:
                        if kw.arg == "static_argnames":
                            for n in ast.walk(kw.value):
                                if isinstance(n, ast.Constant) and \
                                        isinstance(n.value, str):
                                    static.add(n.value)
                    rebound[node.args[0].id] = static
        for node in ast.walk(mod.tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            static: Optional[Set[str]] = None
            for deco in node.decorator_list:
                s = _jit_static_names(deco, node.args)
                if s is not None:
                    static = s
                    break
            if static is None and node.name in rebound:
                static = rebound[node.name]
            if static is None:
                continue
            out.extend(self._check_jitted(mod, node, static))
        return out

    def _check_jitted(self, mod: ModuleInfo, fn: ast.FunctionDef,
                      static: Set[str]) -> Iterable[Finding]:
        params = {a.arg for a in (fn.args.posonlyargs + fn.args.args
                                  + fn.args.kwonlyargs)} - static - {"self"}
        out: List[Finding] = []
        flagged_tests: Set[int] = set()
        for node in ast.walk(fn):
            tests: List[ast.AST] = []
            if isinstance(node, (ast.If, ast.While)):
                tests = [node.test]
            elif isinstance(node, ast.IfExp):
                tests = [node.test]
            for test in tests:
                if id(test) in flagged_tests:
                    continue
                for ref in ast.walk(test):
                    if isinstance(ref, ast.Name) and ref.id in params \
                            and not _is_static_test_ref(ref):
                        out.append(self.finding(
                            mod, node,
                            f"branch on non-static parameter '{ref.id}' "
                            f"inside jitted '{fn.name}' — a tracer here "
                            f"fails at trace time, a Python scalar "
                            f"recompiles per value; use jnp.where/"
                            f"lax.cond or declare it static"))
                        flagged_tests.add(id(test))
                        break
            if isinstance(node, ast.Call):
                head = dotted_name(node.func) or ""
                if head.startswith("np.") or head.startswith("numpy."):
                    for arg in node.args:
                        if isinstance(arg, ast.Name) and arg.id in params:
                            out.append(self.finding(
                                mod, node,
                                f"{head} on parameter '{arg.id}' inside "
                                f"jitted '{fn.name}' — host op on a "
                                f"tracer (use jnp, or hoist to the "
                                f"caller)"))
                            break
        # loop-var closure: this fn nested under a For whose target it reads
        loop_vars: Set[str] = set()
        for anc in ancestors(fn):
            if isinstance(anc, ast.For) and isinstance(anc.target, ast.Name):
                loop_vars.add(anc.target.id)
        if loop_vars:
            bound = params | static | {"self"}
            defaults = {a.arg for a in fn.args.args}  # params already in
            for ref in ast.walk(fn):
                if isinstance(ref, ast.Name) and isinstance(
                        ref.ctx, ast.Load) and ref.id in loop_vars \
                        and ref.id not in bound and ref.id not in defaults:
                    out.append(self.finding(
                        mod, fn,
                        f"jitted '{fn.name}' closes over loop variable "
                        f"'{ref.id}' — a fresh compile every iteration; "
                        f"pass it as a (traced or static) argument"))
                    break
        return out


# ======================================================================
# host-sync-hot-loop
# ======================================================================

# (module-relpath suffix) -> function names whose LOOP BODIES must not
# host-sync. These are the three hot loops the bench trajectory rests
# on: the GBM/DRF tree loop, the serve batcher's encode/dispatch stage
# (the COLLECTOR thread is the designated sync point and is not listed),
# and the streamed-chunk pipelines (their double-buffer bounds carry
# inline allows).
DEFAULT_HOT_ZONES: Dict[str, Tuple[str, ...]] = {
    "h2o3_tpu/models/gbm.py": ("_train_dense", "_train_streaming"),
    "h2o3_tpu/models/drf.py": ("_train_impl",),
    "h2o3_tpu/models/streaming.py": ("level_pass", "begin_tree"),
    "h2o3_tpu/serve/batcher.py": ("_batch_loop", "_take_batch", "submit"),
    "h2o3_tpu/ingest/stream.py": ("add",),
}


class HostSyncHotLoopRule(Rule):
    """Host synchronization inside a hot loop: ``.item()``, any
    ``device_get`` spelling (the counted seam is still a sync) and
    ``block_until_ready`` inside ``for``/``while`` bodies of the
    designated hot functions (tree loop, serve batcher dispatch stage,
    streamed-chunk pipeline).

    One sync per iteration serializes the pipelined dispatch the PR-2/3
    speculative-chunk work bought. Deliberate per-iteration barriers
    (the double-buffer depth bound in ingest/stream.add) carry inline
    allows naming the reason.

    Tightening decisions: ``float(x)``/``int(x)`` on arbitrary locals
    are NOT flagged (too many trace-time Python scalars). Bare
    ``np.asarray``/``np.array`` are NOT flagged either — the canonical
    FP was ingest/stream.add converting freshly TOKENIZED host columns
    (``np.asarray(c.data)``), which never touches the device; an
    np.asarray that wraps a device value always wraps a flagged
    ``device_get`` (or is itself the sync, which block_until_ready/
    device_get spellings catch at the call that produced the value).
    The serve collector thread is the designated sync point, so
    ``_collect_loop`` is not a hot zone.
    """

    name = "host-sync-hot-loop"
    severity = SEV_ERROR

    def __init__(self, zones: Optional[Dict[str, Tuple[str, ...]]] = None):
        self.zones = DEFAULT_HOT_ZONES if zones is None else zones

    _SYNC_DOTTED = {"jax.device_get", "telemetry.device_get"}

    def _zone_functions(self, mod: ModuleInfo) -> Tuple[str, ...]:
        for suffix, fns in self.zones.items():
            if mod.relpath.endswith(suffix):
                return fns
        return ()

    def check_module(self, mod: ModuleInfo) -> List[Finding]:
        fns = self._zone_functions(mod)
        if not fns:
            return []
        out: List[Finding] = []
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.FunctionDef) and (
                    node.name in fns or "*" in fns):
                for loop in ast.walk(node):
                    if isinstance(loop, (ast.For, ast.While)):
                        out.extend(self._check_loop_body(mod, node, loop))
        # dedupe (nested loops walk the same calls twice)
        seen: Set[Tuple[int, int, str]] = set()
        uniq = []
        for f in out:
            k = (f.line, f.col, f.message)
            if k not in seen:
                seen.add(k)
                uniq.append(f)
        return uniq

    def _check_loop_body(self, mod: ModuleInfo, fn: ast.FunctionDef,
                         loop: ast.AST) -> Iterable[Finding]:
        body = getattr(loop, "body", []) + getattr(loop, "orelse", [])
        for stmt in body:
            for node in ast.walk(stmt):
                if not isinstance(node, ast.Call):
                    continue
                name = dotted_name(node.func) or ""
                attr = node.func.attr if isinstance(
                    node.func, ast.Attribute) else ""
                if name in self._SYNC_DOTTED or attr == "device_get":
                    yield self.finding(
                        mod, node,
                        f"host sync '{name or attr}' inside the "
                        f"'{fn.name}' hot loop — one D2H per iteration "
                        f"serializes the pipelined dispatch; batch the "
                        f"fetch outside the loop or pipeline it")
                elif attr == "block_until_ready":
                    yield self.finding(
                        mod, node,
                        f"block_until_ready inside the '{fn.name}' hot "
                        f"loop — per-iteration barrier; if this is a "
                        f"deliberate depth bound, add an inline allow")
                elif attr == "item" and not node.args:
                    yield self.finding(
                        mod, node,
                        f".item() inside the '{fn.name}' hot loop — "
                        f"scalar D2H per iteration; keep it a device "
                        f"scalar or fetch once after the loop")


# ======================================================================
# lock-discipline
# ======================================================================

_LOCK_NAME_HINTS = ("lock", "mutex")
_LOCK_EXACT = {"_mu", "_cv", "_mutex", "_lock", "_LOCK", "_STATE_LOCK"}


def _is_lock_expr(expr: ast.AST) -> bool:
    """with-item expressions that acquire a lock: the terminal name
    contains lock/mutex or is one of the repo's conventional spellings
    (_mu, _cv). ``lock.acquire()``-style calls are not with-items."""
    name = dotted_name(expr)
    if name is None:
        return False
    terminal = name.rsplit(".", 1)[-1]
    low = terminal.lower()
    return terminal in _LOCK_EXACT or any(h in low for h in _LOCK_NAME_HINTS)


# Calls that must never run while a registry/jobs/batcher lock is held:
# device work and sleeps serialize every other thread on the lock for
# device-latency timescales; network I/O for unbounded ones.
_BLOCKING_UNDER_LOCK = {
    "time.sleep": "sleeps while holding it",
    "jax.device_put": "does device transfer while holding it",
    "jax.device_get": "does device transfer while holding it",
    "jax.block_until_ready": "blocks on device work while holding it",
    "telemetry.device_get": "does device transfer while holding it",
    "resilient_device_put": "does device transfer while holding it",
    "resilience.resilient_device_put": "does device transfer while "
                                       "holding it",
    "resilient_shard_rows": "does device transfer while holding it",
    "urllib.request.urlopen": "does network I/O while holding it",
    "urlopen": "does network I/O while holding it",
    "socket.create_connection": "does network I/O while holding it",
    "subprocess.run": "spawns a process while holding it",
    "subprocess.check_output": "spawns a process while holding it",
}


class LockDisciplineRule(Rule):
    """Threading hygiene for the registry/jobs/batcher planes.

    Sub-checks:

    - **blocking-under-lock**: ``time.sleep``, device dispatch/transfer
      or network I/O inside a ``with <lock>:`` block. A device fetch
      under the jobs or batcher lock serializes every REST poller on
      device latency — the class of bug fixed by hand in PRs 3/8.
    - **unlocked-guarded-write**: an attribute written both under a
      lock somewhere and with no lock elsewhere in the same module
      (``__init__``/module scope exempt — construction happens-before
      publication). Mixed discipline means one of the sites is wrong:
      either the lock is unnecessary or the bare write races.

    Tightening decisions: ``Condition.wait`` RELEASES the lock and is
    not a blocking call here. ``.join``/``queue.get`` are excluded
    (str.join/dict.get false positives). jax.jit/jnp.* CONSTRUCTION
    under a lock is allowed — only transfers/syncs are flagged.
    Event.set() after a bare write is a legitimate happens-before for
    the waiter, but not for concurrent third threads — writes claimed
    by a lock elsewhere must take it everywhere.
    """

    name = "lock-discipline"
    severity = SEV_ERROR

    def check_module(self, mod: ModuleInfo) -> List[Finding]:
        attach_parents(mod.tree)
        out: List[Finding] = []
        out.extend(self._blocking_under_lock(mod))
        out.extend(self._unlocked_guarded_writes(mod))
        return out

    # -- sub-check (a) --------------------------------------------------

    def _under_lock(self, node: ast.AST) -> bool:
        for anc in ancestors(node):
            if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return False      # a nested def runs later, not under it
            if isinstance(anc, ast.With):
                for item in anc.items:
                    if _is_lock_expr(item.context_expr):
                        return True
        return False

    def _blocking_under_lock(self, mod: ModuleInfo) -> Iterable[Finding]:
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            name = dotted_name(node.func) or ""
            why = _BLOCKING_UNDER_LOCK.get(name)
            if why is None and isinstance(node.func, ast.Attribute) and \
                    node.func.attr == "block_until_ready":
                why = "blocks on device work while holding it"
            if why is None:
                continue
            if self._under_lock(node):
                yield self.finding(
                    mod, node,
                    f"'{name or node.func.attr}' under a held lock — "
                    f"{why}; move the call outside the critical "
                    f"section")

    # -- sub-check (b) --------------------------------------------------

    def _unlocked_guarded_writes(self, mod: ModuleInfo) -> Iterable[Finding]:
        # attr name -> [(node, under_lock, in_init)]
        writes: Dict[str, List[Tuple[ast.AST, bool, bool]]] = {}
        for node in ast.walk(mod.tree):
            targets: List[ast.AST] = []
            if isinstance(node, ast.Assign):
                targets = node.targets
            elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                targets = [node.target]
            else:
                continue
            for t in targets:
                if not isinstance(t, ast.Attribute):
                    continue
                in_init = False
                in_func = False
                for anc in ancestors(node):
                    if isinstance(anc, ast.FunctionDef):
                        in_func = True
                        if anc.name == "__init__":
                            in_init = True
                        break
                if not in_func:
                    continue            # module-level constant setup
                writes.setdefault(t.attr, []).append(
                    (node, self._under_lock(node), in_init))
        for attr, sites in writes.items():
            locked = [s for s in sites if s[1]]
            bare = [s for s in sites if not s[1] and not s[2]]
            if not locked or not bare:
                continue
            for node, _, _ in bare:
                yield self.finding(
                    mod, node,
                    f"attribute '{attr}' is written under a lock "
                    f"elsewhere in this module but bare here — a "
                    f"concurrent reader under the lock can see a torn "
                    f"protocol; take the owning lock (or drop it "
                    f"everywhere and document why)")


# ======================================================================
# fault-seam
# ======================================================================

class FaultSeamRule(Rule):
    """Package-scope consistency of the fault-injection seams.

    Sub-checks:

    - **site-registry**: every literal site passed to ``faults.check``
      must be in ``faults.KNOWN_SITES``, and every registered site must
      be checked somewhere — a typo'd site silently never fires (chaos
      coverage holes), an unreferenced registered site is a dead seam
      that chaos specs target for nothing.
    - **ungated-check**: ``faults.check(...)`` not enclosed in an
      ``if faults.ACTIVE:`` branch — the checked-no-op contract (one
      attribute load + branch when unset, asserted by
      tests/test_resilience.py's ns-budget guard) only holds when call
      sites pre-gate.

    faults.py itself and test files are exempt from the gating check
    (tests drive check() directly on purpose).
    """

    name = "fault-seam"
    severity = SEV_ERROR
    scope = "package"

    def check_package(self, mods: Sequence[ModuleInfo]) -> List[Finding]:
        out: List[Finding] = []
        faults_mod = None
        for m in mods:
            if m.relpath.endswith("h2o3_tpu/faults.py"):
                faults_mod = m
                break
        registered: Set[str] = set()
        if faults_mod is not None:
            for node in ast.walk(faults_mod.tree):
                if isinstance(node, ast.Assign) and any(
                        isinstance(t, ast.Name) and t.id == "KNOWN_SITES"
                        for t in node.targets):
                    for c in ast.walk(node.value):
                        if isinstance(c, ast.Constant) and isinstance(
                                c.value, str):
                            registered.add(c.value)
        used: Dict[str, List[Tuple[ModuleInfo, ast.Call]]] = {}
        for m in mods:
            if m is faults_mod:
                continue
            attach_parents(m.tree)
            for node in ast.walk(m.tree):
                if not isinstance(node, ast.Call):
                    continue
                name = dotted_name(node.func) or ""
                if not (name == "faults.check"
                        or name.endswith(".faults.check")):
                    continue
                site = None
                if node.args and isinstance(node.args[0], ast.Constant) \
                        and isinstance(node.args[0].value, str):
                    site = node.args[0].value
                    used.setdefault(site, []).append((m, node))
                if registered and site is not None \
                        and site not in registered:
                    out.append(self.finding(
                        m, node,
                        f"fault site '{site}' is not in "
                        f"faults.KNOWN_SITES — register it (an "
                        f"unregistered site works but is invisible to "
                        f"the chaos tooling's coverage accounting)"))
                if not self._gated(node):
                    out.append(self.finding(
                        m, node,
                        "faults.check() without an enclosing "
                        "'if faults.ACTIVE:' gate — breaks the "
                        "checked-no-op contract on the unset path"))
        if faults_mod is not None and registered:
            for site in sorted(registered - set(used)):
                out.append(Finding(
                    rule=self.name, path=faults_mod.relpath, line=1,
                    col=1, severity=self.severity,
                    message=f"registered fault site '{site}' is never "
                            f"checked anywhere in the package — a dead "
                            f"seam; wire a faults.check('{site}') at "
                            f"the matching dispatch point or drop it "
                            f"from KNOWN_SITES",
                    code=f"KNOWN_SITES:{site}"))
        return out

    @staticmethod
    def _gated(node: ast.Call) -> bool:
        for anc in ancestors(node):
            if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return False
            if isinstance(anc, ast.If):
                for ref in ast.walk(anc.test):
                    if isinstance(ref, ast.Attribute) and \
                            ref.attr == "ACTIVE":
                        return True
                    if isinstance(ref, ast.Name) and ref.id == "ACTIVE":
                        return True
        return False


# ======================================================================
# monotonic-durations
# ======================================================================

class MonotonicDurationsRule(Rule):
    """``time.time()`` used in duration/deadline arithmetic.

    Wall clock steps under NTP slew (and leaps at DST on some hosts):
    ``max_runtime_secs`` enforcement, retry backoff and watchdog stall
    detection built on ``time.time()`` subtraction silently mis-measure.
    Duration math must use ``time.monotonic()`` (or ``perf_counter``);
    ``time.time()`` stays ONLY where an epoch timestamp is reported
    (span wall anchors, manifest times, cross-process gossip ages —
    those carry inline allows naming why wall time is required).

    Detection: any ``+``/``-`` expression with a ``time.time()`` call
    (or a local/module name assigned directly from one) in either
    operand. Multiplication (``time.time() * 1000`` epoch-ms
    reporting) is exempt by construction.
    """

    name = "monotonic-durations"
    severity = SEV_WARNING

    @staticmethod
    def _is_walltime_call(node: ast.AST) -> bool:
        return (isinstance(node, ast.Call)
                and dotted_name(node.func) == "time.time")

    def check_module(self, mod: ModuleInfo) -> List[Finding]:
        wall_names: Set[str] = set()
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Assign) and self._is_walltime_call(
                    node.value):
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        wall_names.add(t.id)

        def _has_wall(expr: ast.AST) -> bool:
            for n in ast.walk(expr):
                if self._is_walltime_call(n):
                    return True
                if isinstance(n, ast.Name) and n.id in wall_names and \
                        isinstance(n.ctx, ast.Load):
                    return True
            return False

        out: List[Finding] = []
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.BinOp) and isinstance(
                    node.op, (ast.Add, ast.Sub)):
                if _has_wall(node.left) or _has_wall(node.right):
                    out.append(self.finding(
                        mod, node,
                        "duration/deadline arithmetic on time.time() — "
                        "wall clock steps under NTP slew; use "
                        "time.monotonic() for intervals (keep "
                        "time.time() only for reported epoch "
                        "timestamps, with an inline allow saying why)"))
        return out


# ======================================================================
# pallas-grid-spec
# ======================================================================

class PallasGridSpecRule(Rule):
    """``pl.pallas_call`` without an explicit ``grid=`` or without
    explicit ``in_specs=``/``out_specs=`` BlockSpecs, and a hardcoded
    ``interpret=True`` outside tests.

    Pre-landed guardrail for the compiled-TPU histogram kernel (ROADMAP
    "raw speed" item): a pallas_call that leans on the implicit
    whole-array default grid compiles, runs — and silently serializes
    the kernel into one grid step with every operand in VMEM at once,
    which is exactly the shape that falls over (or quietly crawls) the
    first time a real block size matters. Every kernel states its grid
    and block mapping explicitly so the tiling is a reviewed decision,
    not a default. A ``grid_spec=`` kwarg carries both and satisfies
    the rule; ``**kwargs`` forwarding is assumed to carry them (call
    wrappers must not be flagged for forwarding). ``interpret=True`` as
    a LITERAL pins the interpreter
    into production code — the repo's convention is an ``interpret=``
    parameter threaded from ``pallas_interpret()`` (env-gated) so TPU
    runs compile; tests/ may pin it (CPU CI has no Mosaic).
    """

    name = "pallas-grid-spec"
    severity = SEV_ERROR

    _CALL_NAMES = ("pl.pallas_call", "pallas_call",
                   "pallas.pallas_call",
                   "jax.experimental.pallas.pallas_call")

    def check_module(self, mod: ModuleInfo) -> List[Finding]:
        in_tests = mod.relpath.startswith("tests/") or \
            "/tests/" in mod.relpath
        out: List[Finding] = []
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            if dotted_name(node.func) not in self._CALL_NAMES:
                continue
            kwargs = {kw.arg for kw in node.keywords if kw.arg}
            forwards = any(kw.arg is None for kw in node.keywords)
            has_grid = "grid" in kwargs or "grid_spec" in kwargs
            has_specs = ("grid_spec" in kwargs
                         or ("in_specs" in kwargs
                             and "out_specs" in kwargs))
            if not has_grid and not forwards:
                out.append(self.finding(
                    mod, node,
                    "pallas_call without an explicit grid= — the "
                    "implicit whole-array grid serializes the kernel "
                    "into one step with every operand in VMEM; state "
                    "the tiling"))
            if not has_specs and not forwards:
                out.append(self.finding(
                    mod, node,
                    "pallas_call without explicit in_specs/out_specs "
                    "BlockSpecs — block mapping must be a reviewed "
                    "decision, not the whole-array default"))
            if not in_tests:
                for kw in node.keywords:
                    if kw.arg == "interpret" and isinstance(
                            kw.value, ast.Constant) and \
                            kw.value.value is True:
                        out.append(self.finding(
                            mod, node,
                            "interpret=True hardcoded outside tests — "
                            "thread an interpret= parameter from "
                            "pallas_interpret() (env-gated) so TPU "
                            "runs compile the kernel"))
        return out


# ======================================================================
# fleet-peer-discipline
# ======================================================================

# Modules allowed to read the peer/seed env vars: they ARE the
# member-table seam (telemetry's env fallback + the fleet seed read).
_BLESSED_PEER_MODULES = (
    "h2o3_tpu/telemetry/snapshot.py",
    "h2o3_tpu/fleet/membership.py",
)

_PEER_ENV_VARS = ("H2O3_TELEMETRY_PEERS", "H2O3_FLEET_SEEDS")


class FleetPeerDisciplineRule(Rule):
    """Router/membership hygiene for the serving fleet (ISSUE 13 —
    pre-landed with the router per the ROADMAP).

    Sub-checks:

    - **static-peer-env**: reading ``H2O3_TELEMETRY_PEERS`` /
      ``H2O3_FLEET_SEEDS`` (``os.environ.get``/``os.getenv``/
      ``environ[...]``) outside the blessed member-table seam modules.
      A static peer list read anywhere else is exactly the
      operator-edits-an-env-var failure mode dynamic membership
      retires: peer sets must come from the member table
      (``fleet.router().table`` / ``telemetry.snapshot.peer_view``),
      which a dead replica LEAVES. Writes (launchers exporting the env
      to children) are not flagged — only reads create a second
      source of membership truth.
    - **unretried-peer-http**: a ``urlopen`` call inside
      ``h2o3_tpu/fleet/`` that (a) is not enclosed in a function or
      lambda passed to ``resilience.retry_transient`` or (b) carries
      no explicit ``timeout=``. Cross-replica calls ride the one
      shared retry/backoff policy with a bounded deadline, or a sick
      peer pins the caller (the telemetry scrape's own single-try
      fetch has its module-level deadline loop and is out of scope).
    - **epoch-blind-routing**: a routing decision point (a function
      whose name contains ``route`` or ``failover`` in
      ``fleet/router.py``) that never references a membership
      ``epoch``. Decisions made without pinning the view they were
      made under can act on (and retry into) a dead epoch — the
      resurrection class the member table's fencing exists to stop.

    Tightening decisions: a route/failover-named helper that never
    touches membership state (no ``table``/``live_members``/
    ``members`` reference — e.g. a failure-mode classifier like
    ``_safe_to_failover``) makes no routing decision and is exempt
    from the epoch check.
    """

    name = "fleet-peer-discipline"
    severity = SEV_ERROR

    def check_module(self, mod: ModuleInfo) -> List[Finding]:
        in_tests = mod.relpath.startswith("tests/") or \
            "/tests/" in mod.relpath
        if in_tests:
            return []
        out: List[Finding] = []
        if not mod.relpath.endswith(_BLESSED_PEER_MODULES):
            out.extend(self._static_peer_env(mod))
        if "h2o3_tpu/fleet/" in mod.relpath or \
                mod.relpath.startswith("fleet/"):
            out.extend(self._unretried_peer_http(mod))
        if mod.relpath.endswith("fleet/router.py"):
            out.extend(self._epoch_blind_routing(mod))
        return out

    # -- sub-check (a): static peer env reads ---------------------------

    def _static_peer_env(self, mod: ModuleInfo) -> Iterable[Finding]:
        attach_parents(mod.tree)
        for node in ast.walk(mod.tree):
            if not (isinstance(node, ast.Constant)
                    and node.value in _PEER_ENV_VARS):
                continue
            parent = getattr(node, "_h2o3_parent", None)
            is_read = False
            if isinstance(parent, ast.Call):
                head = dotted_name(parent.func) or ""
                if head.endswith(("environ.get", "getenv")) \
                        and parent.args and parent.args[0] is node:
                    is_read = True
            elif isinstance(parent, ast.Subscript) and isinstance(
                    getattr(parent, "ctx", None), ast.Load):
                base = dotted_name(parent.value) or ""
                if base.endswith("environ"):
                    is_read = True
            if is_read:
                yield self.finding(
                    mod, node,
                    f"static peer list read ({node.value}) outside the "
                    f"member-table seam — peer sets must come from the "
                    f"membership layer (fleet.router().table / "
                    f"telemetry.snapshot.peer_view), which a dead "
                    f"replica actually leaves")

    # -- sub-check (b): unretried / deadline-less peer HTTP -------------

    @staticmethod
    def _retried_scopes(mod: ModuleInfo) -> Set[int]:
        """ids of FunctionDef/Lambda nodes whose body runs under
        retry_transient: lambdas passed directly, plus defs whose NAME
        is passed (the nested-closure spelling)."""
        retried_names: Set[str] = set()
        retried_nodes: Set[int] = set()
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            head = dotted_name(node.func) or ""
            if not head.endswith("retry_transient"):
                continue
            if node.args:
                arg0 = node.args[0]
                if isinstance(arg0, ast.Lambda):
                    retried_nodes.add(id(arg0))
                elif isinstance(arg0, ast.Name):
                    retried_names.add(arg0.id)
        for node in ast.walk(mod.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and node.name in retried_names:
                retried_nodes.add(id(node))
        return retried_nodes

    def _unretried_peer_http(self, mod: ModuleInfo) -> Iterable[Finding]:
        attach_parents(mod.tree)
        retried = self._retried_scopes(mod)
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            head = dotted_name(node.func) or ""
            if not (head == "urlopen" or head.endswith(".urlopen")):
                continue
            if "timeout" not in {kw.arg for kw in node.keywords}:
                yield self.finding(
                    mod, node,
                    "cross-replica urlopen without an explicit "
                    "timeout= — a sick peer pins this caller; bound "
                    "every fleet HTTP call by the request deadline")
            under_retry = False
            for anc in ancestors(node):
                if isinstance(anc, (ast.FunctionDef,
                                    ast.AsyncFunctionDef, ast.Lambda)):
                    if id(anc) in retried:
                        under_retry = True
                    break
            if not under_retry:
                yield self.finding(
                    mod, node,
                    "cross-replica urlopen outside "
                    "resilience.retry_transient — fleet HTTP rides the "
                    "one shared transient-retry policy (wrap the "
                    "calling closure in retry_transient; attempts=1 "
                    "where failover is the retry)")

    # -- sub-check (c): epoch-blind routing decisions -------------------

    def _epoch_blind_routing(self, mod: ModuleInfo) -> Iterable[Finding]:
        for node in ast.walk(mod.tree):
            if not isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                continue
            low = node.name.lower()
            if "route" not in low and "failover" not in low:
                continue
            has_epoch = False
            touches_membership = False
            for ref in ast.walk(node):
                if isinstance(ref, ast.Attribute):
                    if "epoch" in ref.attr.lower():
                        has_epoch = True
                        break
                    if ref.attr in ("table", "live_members", "members"):
                        touches_membership = True
                elif isinstance(ref, ast.Name):
                    if "epoch" in ref.id.lower():
                        has_epoch = True
                        break
                    if ref.id in ("table", "live_members", "members"):
                        touches_membership = True
            if not touches_membership:
                continue        # a classifier/helper, not a decision
            if not has_epoch:
                yield self.finding(
                    mod, node,
                    f"routing decision point '{node.name}' never "
                    f"references a membership epoch — decisions must "
                    f"pin the view they were made under (and failover "
                    f"must re-read it) so a dead epoch is never "
                    f"routed into")


# ======================================================================
# sched-discipline
# ======================================================================

# the training-dispatch layer: work here enters the device through the
# scheduler (ModelBuilder.train -> sched.submit) or runs inline under
# an already-admitted parent. Since ISSUE 18 the fleet package is in
# scope too: its placement/migration paths are scheduler extensions,
# and its async work rides one bounded ThreadPoolExecutor.
_SCHED_SCOPE_PREFIXES = ("h2o3_tpu/models/", "h2o3_tpu/fleet/")
_SCHED_SCOPE_FILES = ("h2o3_tpu/automl.py",)

# fleet-side placement decisions: function-name markers and the
# membership references that make a function a *decision* (vs a helper)
_PLACEMENT_MARKERS = ("place", "rebalance", "resubmit")
_MEMBERSHIP_WORDS = ("table", "members", "live_members", "view",
                     "current_view", "eligible", "candidates")


class SchedDisciplineRule(Rule):
    """Scheduler-bypass hazards in the training-dispatch layer
    (``h2o3_tpu/models/``, ``automl.py``) and the fleet package
    (``h2o3_tpu/fleet/``): raw ``threading.Thread`` spawns, and fleet
    placement decisions that never pin a membership epoch.

    Since ISSUE 15, every train enters the device through the cluster
    scheduler: ``ModelBuilder.train`` enqueues (priority class +
    device-memory admission + checkpoint preemption), and nested builds
    run inline under the admitted parent's grant. A bare daemon thread
    in this layer escapes all three — no admission (it can OOM a peer
    the scheduler promised memory to), no Job supervision, no
    preemption point. Route new fan-out through ``sched.submit_context``
    + ``train(background=True)``, or an inline ThreadPoolExecutor when
    the work rides an admitted parent (the CV-fold pattern —
    executors ARE allowed; they stay inside the parent's run).

    Since ISSUE 18 the fleet scheduler places trains across replicas,
    so ``h2o3_tpu/fleet/`` is in scope: its proxy/rebalance fan-out
    must ride the bounded executor (same no-raw-Thread contract — the
    heartbeat loop carries a reasoned allow comment), and every fleet
    PLACEMENT decision (a function named ``*place*``/``*rebalance*``/
    ``*resubmit*`` that reads membership state) must pin the membership
    epoch it decided under, the same fence fleet-peer-discipline
    enforces for routing — a placement computed against a dead view
    would hand a train to an evicted replica.

    Scope decision: jobs.py (the run machinery), sched/ (the
    dispatcher) and the non-training layers (serve/ingest) spawn
    threads legitimately and are outside this rule's scope.
    """

    name = "sched-discipline"
    severity = SEV_ERROR

    def check_module(self, mod: ModuleInfo) -> List[Finding]:
        rel = mod.relpath
        if not (rel.startswith(_SCHED_SCOPE_PREFIXES)
                or rel in _SCHED_SCOPE_FILES):
            return []
        # bare `Thread(...)` only counts when imported from threading
        bare_thread = any(
            isinstance(n, ast.ImportFrom) and n.module == "threading"
            and any(a.name == "Thread" for a in n.names)
            for n in ast.walk(mod.tree))
        out: List[Finding] = []
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            name = dotted_name(node.func)
            if name == "threading.Thread" or (bare_thread
                                              and name == "Thread"):
                out.append(self.finding(
                    mod, node,
                    "raw threading.Thread in the training-dispatch "
                    "layer bypasses the scheduler — no admission, no "
                    "Job supervision, no preemption point; submit via "
                    "ModelBuilder.train(background=True) under a "
                    "sched.submit_context, or use an inline "
                    "ThreadPoolExecutor when the work rides an "
                    "admitted parent build"))
        if rel.startswith("h2o3_tpu/fleet/"):
            out.extend(self._epoch_blind_placement(mod))
        return out

    def _epoch_blind_placement(self, mod: ModuleInfo
                               ) -> Iterable[Finding]:
        """Fleet placement decisions must pin a membership epoch —
        structurally the same fence fleet-peer-discipline applies to
        routing/failover, extended to the functions that decide WHERE
        a train runs."""
        for node in ast.walk(mod.tree):
            if not isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                continue
            low = node.name.lower()
            if not any(m in low for m in _PLACEMENT_MARKERS):
                continue
            has_epoch = False
            touches_membership = False
            for ref in ast.walk(node):
                if isinstance(ref, ast.Attribute):
                    if "epoch" in ref.attr.lower():
                        has_epoch = True
                        break
                    if ref.attr in _MEMBERSHIP_WORDS:
                        touches_membership = True
                elif isinstance(ref, ast.Name):
                    if "epoch" in ref.id.lower():
                        has_epoch = True
                        break
                    if ref.id in _MEMBERSHIP_WORDS:
                        touches_membership = True
            if not touches_membership:
                continue        # a payload helper, not a decision
            if not has_epoch:
                yield self.finding(
                    mod, node,
                    f"fleet placement decision '{node.name}' never "
                    f"references a membership epoch — a train placed "
                    f"against a dead view lands on an evicted replica; "
                    f"pin the epoch the decision was made under "
                    f"(the admission headroom it read belongs to that "
                    f"view)")


# ======================================================================
# blackbox-discipline
# ======================================================================

# the control-plane packages whose decision points must leave a
# flight-recorder record (ISSUE 19)
_BB_SCOPE_PREFIXES = ("h2o3_tpu/fleet/", "h2o3_tpu/sched/")

# function names that ARE the recording/counting plumbing, not
# decision points
_BB_EXEMPT_FUNCS = {"_count", "_bb", "counters", "reset"}


class BlackboxDisciplineRule(Rule):
    """Control-plane decision points in the fleet/scheduler packages
    that mutate placement/membership state without leaving a flight-
    recorder record (ISSUE 19).

    A function in ``h2o3_tpu/fleet/`` or ``h2o3_tpu/sched/`` counts as
    a decision point when it (a) bumps a fleet decision counter
    (``_count(...)``), (b) increments a scheduler metric counter
    (``_m_*.inc(...)``), or (c) advances a membership epoch (an
    augmented assignment to ``*_epoch``, or a plain non-constant
    assignment to a ``*_epoch`` attribute — the gossip-absorb /
    ring-publish seams align the fence instead of bumping it). Each
    of those is a state
    mutation a post-mortem needs to see: a SIGKILLed replica whose
    placement/eviction/preemption decisions only lived in in-memory
    counters tells no story. The fix is one advisory
    ``blackbox.record(...)`` (or the module's ``_bb(...)`` helper)
    next to the mutation.

    Scope decisions: the counting/recording plumbing itself
    (``_count``, ``_bb``, ``counters``, ``reset``) is exempt; tests
    are out of scope. Nested closures are checked as part of their
    enclosing function — the record may legitimately sit in the outer
    body around the closure's mutation.
    """

    name = "blackbox-discipline"
    severity = SEV_ERROR

    @staticmethod
    def _mutates(ref: ast.AST) -> bool:
        if isinstance(ref, ast.Call):
            head = dotted_name(ref.func) or ""
            parts = head.split(".")
            if parts[-1] == "_count":
                return True
            if parts[-1] == "inc" and len(parts) >= 2 \
                    and parts[-2].startswith("_m_"):
                return True
        elif isinstance(ref, ast.AugAssign):
            t = ref.target
            tname = t.attr if isinstance(t, ast.Attribute) else (
                t.id if isinstance(t, ast.Name) else "")
            if tname.endswith("_epoch"):
                return True
        elif isinstance(ref, ast.Assign) and len(ref.targets) == 1:
            # a PLAIN epoch assignment to an attribute (gossip absorb
            # aligning to a peer's epoch, a published-ring stamp) moves
            # the same causal fence as an AugAssign bump. Constant
            # right-hand sides (the ``= 0`` / ``= -1`` initializers in
            # __init__/reset) are not decisions; locals ending _epoch
            # are reads of the fence, not moves of it
            t = ref.targets[0]
            v = ref.value
            if isinstance(v, ast.UnaryOp):   # ``= -1`` sentinel
                v = v.operand
            if isinstance(t, ast.Attribute) \
                    and t.attr.endswith("_epoch") \
                    and not isinstance(v, ast.Constant):
                return True
        return False

    @staticmethod
    def _records(ref: ast.AST) -> bool:
        if not isinstance(ref, ast.Call):
            return False
        head = dotted_name(ref.func) or ""
        parts = head.split(".")
        if parts[-1] == "_bb":
            return True
        return (parts[-1] == "record" and len(parts) >= 2
                and "blackbox" in parts[-2])

    def check_module(self, mod: ModuleInfo) -> List[Finding]:
        if not mod.relpath.startswith(_BB_SCOPE_PREFIXES):
            return []
        out: List[Finding] = []
        for node in ast.walk(mod.tree):
            if not isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                continue
            if node.name in _BB_EXEMPT_FUNCS:
                continue
            mutates = records = False
            for ref in ast.walk(node):
                mutates = mutates or self._mutates(ref)
                records = records or self._records(ref)
                if mutates and records:
                    break
            if mutates and not records:
                out.append(self.finding(
                    mod, node,
                    f"control-plane decision point '{node.name}' "
                    f"mutates placement/membership state (decision "
                    f"counter / metric inc / epoch bump) without a "
                    f"flight-recorder record — add an advisory "
                    f"blackbox.record()/_bb() next to the mutation so "
                    f"a post-mortem can see the decision"))
        return out


# ======================================================================
# registry
# ======================================================================

def all_rules(hot_zones: Optional[Dict[str, Tuple[str, ...]]] = None
              ) -> List[Rule]:
    return [
        TransferSeamRule(),
        RecompileHazardRule(),
        HostSyncHotLoopRule(zones=hot_zones),
        LockDisciplineRule(),
        FaultSeamRule(),
        MonotonicDurationsRule(),
        PallasGridSpecRule(),
        FleetPeerDisciplineRule(),
        SchedDisciplineRule(),
        BlackboxDisciplineRule(),
    ]


def rule_names() -> List[str]:
    return [r.name for r in all_rules()]
