"""The TPU-hazard rule set.

Every rule encodes an invariant this repo already paid to learn (the PR
that paid is named in each docstring); ``docs/lint.md`` carries the full
catalog with the historical incident behind each one.  Rules are pure
AST passes — conservative by construction: an expression a rule cannot
resolve is dropped, never guessed, so a finding is worth reading.
"""
from __future__ import annotations

import ast
import re
import time
from typing import Dict, Iterable, List, Optional, Set, Tuple

from .engine import Finding, Module

#: scalar hyperparameters that must enter compiled steps TRACED.  Exact
#: identifier / attribute matches only ("grad_accum_steps" is a program
#: *shape* and belongs in static keys; "lr" never does).
HYPERPARAM_NAMES = {
    "lr", "learning_rate", "beta1", "beta2", "betas", "eps",
    "weight_decay", "wd", "momentum", "step", "loss_scale",
}

#: mapped-axis collectives (jax.lax) that must not sit inside an
#: accumulation scan body
COLLECTIVES = {"psum", "pmean", "pmax", "pmin", "all_gather",
               "all_to_all", "ppermute", "psum_scatter"}

#: metadata attributes that are static under tracing — reading them off a
#: traced value is NOT a host sync / traced branch
_STATIC_ATTRS = {"shape", "dtype", "ndim", "size", "sharding"}

_STATIC_CALLS = {"len", "isinstance", "getattr", "hasattr", "type",
                 "callable", "format", "repr", "str"}


def _terminal(func: ast.AST) -> Optional[str]:
    if isinstance(func, ast.Attribute):
        return func.attr
    if isinstance(func, ast.Name):
        return func.id
    return None


def _dotted(node: ast.AST) -> Optional[str]:
    """'jax.lax.psum' for the matching Attribute chain, else None."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _const_strs(node: ast.AST) -> List[str]:
    """String constants in a static_argnames value (str or tuple/list)."""
    out = []
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        out.append(node.value)
    elif isinstance(node, (ast.Tuple, ast.List, ast.Set)):
        for el in node.elts:
            if isinstance(el, ast.Constant) and isinstance(el.value, str):
                out.append(el.value)
    return out


def _static_key_exprs(call: ast.Call) -> List[ast.AST]:
    """Expressions landing in hashable program-key positions: the
    ``static_key`` of ``step_cache.program``, the ``static_cfg`` /
    ``scaler_cfg`` of the optimizer-step dispatchers, and any keyword
    spelled like one of those anywhere."""
    name = _terminal(call.func)
    out = []
    if name == "program" and len(call.args) >= 2:
        out.append(call.args[1])
    elif name in ("optimizer_step", "optimizer_step_with_scaler"):
        if len(call.args) >= 2:
            out.append(call.args[1])
        if name == "optimizer_step_with_scaler" and len(call.args) >= 5:
            out.append(call.args[4])
    for kw in call.keywords:
        if kw.arg in ("static_key", "static_cfg", "scaler_cfg"):
            out.append(kw.value)
    return out


def _walk_own(root):
    """Walk a function body without descending into nested defs (each
    reachable nested def is visited as its own function)."""
    stack = list(ast.iter_child_nodes(root))
    while stack:
        node = stack.pop()
        yield node
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda)):
            stack.extend(ast.iter_child_nodes(node))


class LintContext:
    """Shared analysis context handed to every rule.

    ``dataflow`` is built lazily on first access (rules that never
    consult it keep single-fixture runs AST-only); the engine may pass
    a zero-arg factory so the built interpreter is shared through its
    analysis cache.  ``dataflow_ms`` records build time actually spent
    in THIS run (0 when the cache served it)."""

    def __init__(self, modules: List[Module], callgraph,
                 dataflow=None):
        self.modules = modules
        self.callgraph = callgraph
        self._dataflow = dataflow       # instance, factory, or None
        self.dataflow_ms = 0.0

    @property
    def dataflow(self):
        if self._dataflow is None or callable(self._dataflow):
            from . import dataflow as _df
            t0 = time.perf_counter()
            built = self._dataflow() if callable(self._dataflow) \
                else _df.build(self.modules, self.callgraph)
            self.dataflow_ms = (time.perf_counter() - t0) * 1000.0
            self._dataflow = built
        return self._dataflow


class Rule:
    """Base: subclasses set ``id``/``summary``/``hint`` and implement
    :meth:`check` yielding :class:`Finding`."""
    id: str = ""
    summary: str = ""
    hint: str = ""
    #: True for rules that only judge code inside traced-REACHABLE
    #: functions: whether they examined a given line depends on which
    #: entries the scanned scope contains, so the stale-suppression
    #: audit must not call their directives dead outside that span
    reachability_scoped: bool = False

    def check(self, module: Module, ctx: LintContext):
        raise NotImplementedError

    def finding(self, module: Module, node: ast.AST, message: str,
                hint: Optional[str] = None) -> Finding:
        return Finding(self.id, module.relpath,
                       getattr(node, "lineno", 1),
                       getattr(node, "col_offset", 0), message,
                       self.hint if hint is None else hint)


REGISTRY: Dict[str, Rule] = {}


def register(cls):
    REGISTRY[cls.id] = cls()
    return cls


def rule_ids() -> List[str]:
    return sorted(REGISTRY)


def resolve(select=None, ignore=None) -> List[Rule]:
    ids = list(select) if select else rule_ids()
    unknown = [i for i in list(ids) + list(ignore or [])
               if i not in REGISTRY]
    if unknown:
        raise KeyError(f"unknown rule id(s): {unknown}; "
                       f"known: {rule_ids()}")
    ignore = set(ignore or ())
    return [REGISTRY[i] for i in ids if i not in ignore]


# ---------------------------------------------------------------------------
# RETRACE-STATIC
# ---------------------------------------------------------------------------


@register
class RetraceStatic(Rule):
    """Traced hyperparameters in static jit keys — PR 1's ~200x bug.

    A value in ``static_argnames`` (or any hashable program-cache key)
    becomes part of the executable's identity: an lr *schedule* then
    compiles a fresh XLA program every step.  PR 1 measured ~200x step
    overhead from exactly this in the fused optimizers.  Hyperparameters
    must enter as traced device scalars.
    """
    id = "RETRACE-STATIC"
    summary = ("hyperparameter in a static jit key (retraces every "
               "schedule tick)")
    hint = ("pass lr/betas/eps/weight_decay/step as traced device "
            "scalars (jnp.asarray) — see runtime/step_cache.py's hyper "
            "tree; static keys are for program *shape* only")

    def _jit_static_calls(self, call: ast.Call) -> Set[str]:
        """static_argnames a jit/pjit/partial(jit) call declares."""
        from .callgraph import _static_argnames_of
        tn = _terminal(call.func)
        if tn in ("jit", "pjit"):
            return _static_argnames_of(call)
        if tn == "partial" and call.args and \
                _terminal(call.args[0]) in ("jit", "pjit"):
            return _static_argnames_of(call)
        return set()

    def _dataflow_pass(self, module, ctx):
        """The interprocedural half: a TRACED value bound to a declared
        static_argname of a locally-jitted function — invisible to the
        name heuristic when the value is not spelled like a
        hyperparameter (it arrived through helper frames)."""
        jit_static: Dict[str, Set[str]] = {}
        for node in ast.walk(module.tree):
            if isinstance(node, ast.Assign) and \
                    isinstance(node.value, ast.Call):
                names = self._jit_static_calls(node.value)
                if names:
                    for tgt in node.targets:
                        if isinstance(tgt, ast.Name):
                            jit_static.setdefault(tgt.id,
                                                  set()).update(names)
        if not jit_static:
            return
        df = ctx.dataflow
        for info in df.functions_in(module.path):
            for node in _walk_own(info.node):
                if not isinstance(node, ast.Call) or \
                        not isinstance(node.func, ast.Name) or \
                        node.func.id not in jit_static:
                    continue
                for kw in node.keywords:
                    if kw.arg not in jit_static[node.func.id]:
                        continue
                    if df.eval_in(info, kw.value).is_traced:
                        yield self.finding(
                            module, kw.value,
                            f"traced value bound to static_argname "
                            f"'{kw.arg}' of '{node.func.id}' — every "
                            f"distinct value retraces (and a live "
                            f"tracer here is a ConcretizationError)")

    def check(self, module, ctx):
        yield from self._dataflow_pass(module, ctx)
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            tn = _terminal(node.func)
            # jax.jit(f, static_argnames=...) and partial(jax.jit, ...)
            calls = []
            if tn in ("jit", "pjit"):
                calls.append(node)
            elif tn == "partial" and node.args and \
                    _terminal(node.args[0]) in ("jit", "pjit"):
                calls.append(node)
            for c in calls:
                for kw in c.keywords:
                    if kw.arg != "static_argnames":
                        continue
                    bad = [s for s in _const_strs(kw.value)
                           if s in HYPERPARAM_NAMES]
                    if bad:
                        yield self.finding(
                            module, kw.value,
                            f"hyperparameter(s) {bad} in static_argnames "
                            f"— every schedule change recompiles")
            # hashable step-cache key positions
            for expr in _static_key_exprs(node):
                for sub in ast.walk(expr):
                    name = None
                    if isinstance(sub, ast.Name) and \
                            sub.id in HYPERPARAM_NAMES:
                        name = sub.id
                    elif isinstance(sub, ast.Attribute) and \
                            sub.attr in HYPERPARAM_NAMES:
                        name = _dotted(sub) or sub.attr
                    if name:
                        yield self.finding(
                            module, sub,
                            f"hyperparameter '{name}' embedded in a "
                            f"static program key — one executable per "
                            f"value (schedules recompile every step)")


# ---------------------------------------------------------------------------
# HOST-SYNC
# ---------------------------------------------------------------------------


@register
class HostSync(Rule):
    """Host synchronization inside traced code.

    ``.item()`` / ``jax.device_get`` / ``np.asarray`` / Python ``float()``
    or ``if`` on a traced value blocks dispatch on a device round-trip —
    per call, per step.  Scoped by the intra-package call graph to
    functions reachable from jit entry points, so eager logging loops
    never flag.
    """
    id = "HOST-SYNC"
    reachability_scoped = True
    summary = "host round-trip inside a jit-reachable function"
    hint = ("keep the value on device (jnp ops, lax.cond on traced "
            "flags); fetch for logging OUTSIDE the compiled step — see "
            "the on-device overflow flag in amp/scaler.py for the "
            "pattern")

    def _traced_refs(self, node, is_traced, out):
        """Name nodes referring to traced values, skipping contexts that
        are static under tracing (.shape/.dtype, len(), `is None`).
        ``is_traced(name)`` decides tracedness — the syntactic
        traced-params set widened by the dataflow environment, so a
        value that arrived through helper frames still counts."""
        if isinstance(node, ast.Attribute) and node.attr in _STATIC_ATTRS:
            return
        if isinstance(node, ast.Call) and \
                _terminal(node.func) in _STATIC_CALLS:
            return
        if isinstance(node, ast.Compare) and \
                all(isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops):
            return
        if isinstance(node, ast.Name):
            if isinstance(node.ctx, ast.Load) and is_traced(node.id):
                out.append(node)
            return
        for child in ast.iter_child_nodes(node):
            self._traced_refs(child, is_traced, out)

    def _walk_own(self, root):
        return _walk_own(root)

    @staticmethod
    def _traced_pred(ctx, info, params):
        """Name -> provably traced, via the syntactic entry-param set or
        the interprocedural dataflow environment."""
        facts = None

        def is_traced(name):
            nonlocal facts
            if name in params:
                return True
            if facts is None:
                facts = ctx.dataflow.facts_for(
                    info.module_path, info.qualname) or ()
            if not facts:
                return False
            v = facts.env.get(name)
            return v is not None and v.is_traced
        return is_traced

    def check(self, module, ctx):
        table = ctx.callgraph.imports.get(module.path)
        np_aliases = {a for a, d in table.ext_alias.items()
                      if d == "numpy"} if table else {"np"}
        for info in ctx.callgraph.reachable_functions(module.path):
            # value-sensitive checks key on provably-traced values: an
            # entry's own non-static params, widened by dataflow facts;
            # .item()/device_get flag in every reachable function UNLESS
            # dataflow proves the operand lives on host
            params = ctx.callgraph.traced_params(info)
            is_traced = self._traced_pred(ctx, info, params)
            for node in self._walk_own(info.node):
                if isinstance(node, ast.Call):
                    yield from self._check_call(module, node, is_traced,
                                                np_aliases, ctx, info)
                elif isinstance(node, (ast.If, ast.While)):
                    refs = []
                    self._traced_refs(node.test, is_traced, refs)
                    if refs:
                        yield self.finding(
                            module, node.test,
                            f"Python `{type(node).__name__.lower()}` on "
                            f"traced value '{refs[0].id}' — the branch "
                            f"forces a device fetch at trace boundaries "
                            f"(use jnp.where / lax.cond)")

    def _check_call(self, module, node, is_traced, np_aliases, ctx, info):
        tn = _terminal(node.func)
        if tn == "item" and isinstance(node.func, ast.Attribute) and \
                not node.args:
            # dataflow re-grounding: an .item() on a value PROVABLY on
            # host (a numpy scalar, a config constant) costs nothing
            if ctx.dataflow.eval_in(info, node.func.value).is_host:
                return
            yield self.finding(
                module, node,
                ".item() inside traced code — blocks on a device "
                "round-trip every step")
            return
        if tn == "device_get":
            if node.args and \
                    ctx.dataflow.eval_in(info, node.args[0]).is_host:
                return
            yield self.finding(
                module, node,
                "jax.device_get inside traced code — host transfer on "
                "the step's critical path")
            return
        if tn in ("asarray", "array") and \
                isinstance(node.func, ast.Attribute) and \
                isinstance(node.func.value, ast.Name) and \
                node.func.value.id in np_aliases and node.args:
            refs = []
            self._traced_refs(node.args[0], is_traced, refs)
            if refs:
                yield self.finding(
                    module, node,
                    f"np.{tn} of traced value '{refs[0].id}' — "
                    f"materializes on host (use jnp.{tn})")
            return
        if tn in ("float", "int", "bool") and \
                isinstance(node.func, ast.Name) and len(node.args) == 1 \
                and not isinstance(node.args[0], ast.Constant):
            refs = []
            self._traced_refs(node.args[0], is_traced, refs)
            if refs:
                yield self.finding(
                    module, node,
                    f"{tn}() of traced value '{refs[0].id}' — host sync "
                    f"(keep it a device scalar)")


# ---------------------------------------------------------------------------
# SCAN-COLLECTIVE
# ---------------------------------------------------------------------------


@register
class ScanCollective(Rule):
    """Collectives inside a ``lax.scan`` body — PR 3's boundary-only
    invariant.

    ``make_train_step(accum_steps=K)`` exists so a K-microbatch window
    costs ONE gradient exchange at the boundary; a ``psum`` inside the
    scan body pays K exchanges.  Syntactic: flags collectives written
    directly in the scanned function (scan bodies that legitimately hop
    per tick — ring attention, pipeline stages — suppress with the
    algorithmic reason).
    """
    id = "SCAN-COLLECTIVE"
    summary = "collective inside a lax.scan body (per-microbatch exchange)"
    hint = ("hoist the collective to the scan boundary (accumulate in "
            "fp32 in the carry, exchange once) — see "
            "training/step.py's accumulation window; if the algorithm "
            "truly hops per step, suppress with the reason")

    def _body_ast(self, module, call: ast.Call):
        body = call.args[0] if call.args else None
        if isinstance(body, ast.Lambda):
            return body
        if isinstance(body, ast.Name):
            # nearest definition ABOVE the scan call (same-name bodies in
            # sibling scopes — e.g. two schedules each with a `tick`)
            best = None
            for node in ast.walk(module.tree):
                if isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)) and \
                        node.name == body.id and \
                        node.lineno <= call.lineno:
                    if best is None or node.lineno > best.lineno:
                        best = node
            return best
        return None

    def _rotation_only(self, body, sub):
        """A ppermute whose result is bound and never additively
        accumulated is a pure rotation — the loop-carried neighbor hop
        of pipeline/ring schedules.  One hop per tick IS the algorithm
        (nothing to hoist: the exchanged value differs every step), so
        dataflow proves the site clean without a suppression."""
        targets = None
        for st in ast.walk(body):
            if isinstance(st, ast.Assign) and st.value is sub:
                targets = {n.id for t in st.targets
                           for n in ast.walk(t) if isinstance(n, ast.Name)}
                break
        if not targets:
            return False
        for n in ast.walk(body):
            if isinstance(n, ast.BinOp) and isinstance(n.op, ast.Add):
                for side in (n.left, n.right):
                    for m in ast.walk(side):
                        if isinstance(m, ast.Name) and m.id in targets:
                            return False
            elif isinstance(n, ast.AugAssign) and \
                    isinstance(n.op, ast.Add):
                for m in ast.walk(n):
                    if isinstance(m, ast.Name) and m.id in targets:
                        return False
        return True

    def check(self, module, ctx):
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call) or \
                    _terminal(node.func) != "scan":
                continue
            body = self._body_ast(module, node)
            if body is None:
                continue
            for sub in ast.walk(body):
                if not isinstance(sub, ast.Call):
                    continue
                tn = _terminal(sub.func)
                if tn not in COLLECTIVES:
                    continue
                # lax.psum(1, axis) is the axis-size idiom: constant-
                # folded to the mesh size, no collective is emitted
                if tn in ("psum", "pmean", "pmax", "pmin") and sub.args \
                        and isinstance(sub.args[0], ast.Constant):
                    continue
                if tn == "ppermute" and self._rotation_only(body, sub):
                    continue
                yield self.finding(
                    module, sub,
                    f"lax.{tn} inside the lax.scan body at line "
                    f"{node.lineno} — one collective PER scan step, "
                    f"not per window")


# ---------------------------------------------------------------------------
# DONATED-REUSE
# ---------------------------------------------------------------------------


@register
class DonatedReuse(Rule):
    """Reading an argument after donating it.

    ``donate_argnums`` lets XLA write outputs into the input buffers;
    the step-cache donates params/moments/scaler state every step.  Any
    later read of the donated reference sees freed (or overwritten)
    memory.  Tracks, per function, names passed at donated positions of
    a jit-with-donation call site and flags later loads.
    """
    id = "DONATED-REUSE"
    summary = "argument read after being donated to a jit call"
    hint = ("rebind every output of a donating call and drop the input "
            "reference (state = fn(state, ...)); copy first "
            "(jnp.copy) if the pre-step value is really needed")

    def _donated_positions(self, call: ast.Call):
        for kw in call.keywords:
            if kw.arg != "donate_argnums":
                continue
            v = kw.value
            if isinstance(v, ast.Constant) and isinstance(v.value, int):
                return (v.value,)
            if isinstance(v, (ast.Tuple, ast.List)):
                out = tuple(e.value for e in v.elts
                            if isinstance(e, ast.Constant)
                            and isinstance(e.value, int))
                if out:
                    return out
            # conditional spellings ((0,) if donate else ()) are dynamic
            # — resolved conservatively as "maybe donates nothing"
            return ()
        return None

    def check(self, module, ctx):
        for fn in ast.walk(module.tree):
            if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                yield from self._check_function(module, fn)

    def _check_function(self, module, fn):
        jitted: Dict[str, Tuple[int, ...]] = {}
        consumed: List[Tuple[str, int, str]] = []  # (name, line, via)
        stores: List[Tuple[str, int]] = []
        loads: List[ast.Name] = []

        def record_call(call, positions):
            for p in positions:
                if p < len(call.args) and \
                        isinstance(call.args[p], ast.Name):
                    consumed.append((call.args[p].id, call.lineno,
                                     _terminal(call.func) or "<fn>"))

        for node in ast.walk(fn):
            if isinstance(node, ast.Assign) and \
                    isinstance(node.value, ast.Call):
                tn = _terminal(node.value.func)
                if tn in ("jit", "pjit"):
                    pos = self._donated_positions(node.value)
                    if pos:
                        for tgt in node.targets:
                            if isinstance(tgt, ast.Name):
                                jitted[tgt.id] = pos
            if isinstance(node, ast.Call):
                if isinstance(node.func, ast.Name) and \
                        node.func.id in jitted:
                    record_call(node, jitted[node.func.id])
                elif isinstance(node.func, ast.Call) and \
                        _terminal(node.func.func) in ("jit", "pjit"):
                    pos = self._donated_positions(node.func)
                    if pos:
                        record_call(node, pos)
            if isinstance(node, ast.Name):
                if isinstance(node.ctx, ast.Store):
                    stores.append((node.id, node.lineno))
                elif isinstance(node.ctx, ast.Load):
                    loads.append(node)

        # ast.walk is breadth-first; reads must be considered in source
        # order or a late rebind would mask an earlier stale read
        loads.sort(key=lambda n: (n.lineno, n.col_offset))
        for name, cline, via in consumed:
            for load in loads:
                if load.id != name or load.lineno <= cline:
                    continue
                # a store on the consuming line itself (`x = fn(x)`) is
                # the sanctioned rebind pattern
                if any(s == name and cline <= sl <= load.lineno
                       for s, sl in stores):
                    break       # rebound; later loads see the new value
                yield self.finding(
                    module, load,
                    f"'{name}' read after being donated to '{via}' at "
                    f"line {cline} — the buffer was invalidated by the "
                    f"call")
                break           # one finding per consumed name


# ---------------------------------------------------------------------------
# COMPAT-SHIM
# ---------------------------------------------------------------------------


@register
class CompatShim(Rule):
    """Direct ``jax.shard_map`` / ``lax.axis_size`` in package code.

    Package code reaches both through ``apex_tpu.compat`` so the
    spelling lives in one module (under the installed jax it is a
    pass-through to the native names); user code may use the jax names
    directly — so this rule only applies inside the apex_tpu package.
    """
    id = "COMPAT-SHIM"
    summary = "direct jax.shard_map / lax.axis_size (bypasses compat)"
    hint = ("use apex_tpu.compat.shard_map / compat.axis_size — the one "
            "module that names the jax entry points")

    def check(self, module, ctx):
        if not module.in_apex_package or \
                module.path.endswith("compat.py"):
            return
        for node in ast.walk(module.tree):
            if isinstance(node, ast.Attribute):
                d = _dotted(node)
                if d == "jax.shard_map":
                    yield self.finding(
                        module, node,
                        "direct jax.shard_map — package code goes "
                        "through compat.shard_map")
                elif d in ("jax.lax.axis_size", "lax.axis_size"):
                    yield self.finding(
                        module, node,
                        "direct lax.axis_size — package code goes "
                        "through compat.axis_size")
                elif d and d.startswith("jax.experimental.shard_map"):
                    yield self.finding(
                        module, node,
                        "jax.experimental.shard_map referenced directly "
                        "— deprecated in favour of jax.shard_map; route "
                        "through apex_tpu.compat")
            elif isinstance(node, ast.ImportFrom) and \
                    (node.module or "").startswith(
                        "jax.experimental.shard_map"):
                yield self.finding(
                    module, node,
                    "import from jax.experimental.shard_map — "
                    "deprecated in favour of jax.shard_map; route "
                    "through apex_tpu.compat")


# ---------------------------------------------------------------------------
# UNBOUNDED-COLLECTIVE
# ---------------------------------------------------------------------------


@register
class UnboundedCollective(Rule):
    """Process-wide collectives outside the bounded wrapper — PR 2.

    ``multihost_utils`` calls block until EVERY process arrives; one
    preempted host hangs the job forever with no diagnosis.  PR 2's
    ``timed_flat_dist_call`` (parallel/distributed.py) wraps them with a
    deadline and names the missing ranks on timeout — everything
    process-wide goes through it.
    """
    id = "UNBOUNDED-COLLECTIVE"
    summary = "raw multihost collective (no deadline, no missing-rank "\
              "diagnosis)"
    hint = ("route through apex_tpu.parallel.timed_flat_dist_call "
            "(deadline + CollectiveTimeoutError naming absent ranks) — "
            "see runtime/resilience.py's bounded init")

    def check(self, module, ctx):
        if module.path.replace("\\", "/").endswith(
                "apex_tpu/parallel/distributed.py"):
            return      # the sanctioned wrapper home
        locals_from_mhu: Set[str] = set()
        for node in ast.walk(module.tree):
            if isinstance(node, ast.ImportFrom):
                m = node.module or ""
                if "multihost_utils" in m:
                    yield self.finding(
                        module, node,
                        f"import from {m} — unbounded process-wide "
                        f"collective surface")
                    locals_from_mhu |= {al.asname or al.name
                                        for al in node.names}
                elif m == "jax.experimental":
                    for al in node.names:
                        if al.name == "multihost_utils":
                            yield self.finding(
                                module, node,
                                "import of jax.experimental."
                                "multihost_utils — unbounded collective "
                                "surface")
            elif isinstance(node, ast.Import):
                for al in node.names:
                    if "multihost_utils" in al.name:
                        yield self.finding(
                            module, node,
                            f"import {al.name} — unbounded collective "
                            f"surface")
            elif isinstance(node, ast.Call):
                d = _dotted(node.func) or ""
                tn = _terminal(node.func)
                if "multihost_utils" in d:
                    yield self.finding(
                        module, node,
                        f"{d} call — blocks until every process "
                        f"arrives, with no deadline")
                elif tn in locals_from_mhu and \
                        isinstance(node.func, ast.Name):
                    yield self.finding(
                        module, node,
                        f"{tn}() (from multihost_utils) — blocks until "
                        f"every process arrives, with no deadline")


# ---------------------------------------------------------------------------
# IMPURE-STATIC-KEY
# ---------------------------------------------------------------------------

_IMPURE_OWNERS = {"random", "secrets"}
_IMPURE_CALLS = {
    ("time", "time"), ("time", "time_ns"), ("time", "monotonic"),
    ("time", "monotonic_ns"), ("time", "perf_counter"),
    ("time", "perf_counter_ns"), ("os", "urandom"),
    ("uuid", "uuid1"), ("uuid", "uuid4"),
    ("datetime", "now"), ("datetime", "utcnow"),
}


@register
class ImpureStaticKey(Rule):
    """Wall-clock / RNG values feeding program-cache keys.

    A static key exists to make "same program" hashable; ``time.time()``
    or ``random.*`` in that position makes every call a distinct program
    — silent unbounded recompilation (and cache-stats that lie).  Also
    flags ``id(...)``: stable within a process but not across restarts,
    so resumed runs silently recompile everything.
    """
    id = "IMPURE-STATIC-KEY"
    summary = "impure value (time/random/id) in a static program key"
    hint = ("key on stable program *shape* (config tuples, treedefs, "
            "shapes/dtypes, monotonic builder tokens) — see "
            "training/step.py's _STEP_TOKENS for the per-builder "
            "pattern")

    def _impure_calls(self, expr, module) -> Iterable[ast.Call]:
        for sub in ast.walk(expr):
            if not isinstance(sub, ast.Call):
                continue
            tn = _terminal(sub.func)
            if isinstance(sub.func, ast.Name) and tn == "id":
                yield sub
                continue
            if isinstance(sub.func, ast.Attribute):
                d = _dotted(sub.func) or ""
                parts = d.split(".")
                if len(parts) >= 2:
                    owner, leaf = parts[-2], parts[-1]
                    if (owner, leaf) in _IMPURE_CALLS or \
                            owner in _IMPURE_OWNERS or \
                            (owner == "random" or
                             ".random." in f".{d}"):
                        yield sub

    def check(self, module, ctx):
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            for expr in _static_key_exprs(node):
                for bad in self._impure_calls(expr, module):
                    d = _dotted(bad.func) or _terminal(bad.func)
                    yield self.finding(
                        module, bad,
                        f"{d}(...) inside a static program key — every "
                        f"call keys a new executable (unbounded "
                        f"recompilation)")


# ---------------------------------------------------------------------------
# CKPT-ATOMIC
# ---------------------------------------------------------------------------

_CKPT_PATH_RE_SRC = r"(ckpt|checkpoint|\.pkl)"


@register
class CkptAtomic(Rule):
    """Checkpoint bytes written outside the atomic path — PR 8 (elastic).

    ``runtime/resilience.py``'s ``write_checkpoint_file`` is THE
    checkpoint write path: tmp file + fsync + one ``os.rename`` (+
    directory fsync), a manifest with per-component CRC32, and — since
    schema 2 — the sharding layout the elastic restore reshards by.  A
    raw ``pickle.dump`` / ``open(..., "wb")`` checkpoint write has none
    of that: a preemption mid-write corrupts the only copy at its final
    path, and the file can be neither validated nor resharded after a
    topology change.  The elastic recovery cycle (re-plan + reshard)
    only works when every checkpoint carries the schema-2 metadata, so
    every write must go through the one path."""

    id = "CKPT-ATOMIC"
    summary = "checkpoint written outside the atomic tmp+fsync+rename path"
    hint = ("route through runtime/resilience.py: write_checkpoint_file / "
            "CheckpointManager.save (atomic rename, CRC32 manifest, "
            "schema-2 sharding layout for elastic restore)")

    def check(self, module: Module, ctx) -> Iterable[Finding]:
        if module.path.replace("\\", "/").endswith(
                "apex_tpu/runtime/resilience.py"):
            return      # the sanctioned write path itself
        import re as _re
        ckpt_re = _re.compile(_CKPT_PATH_RE_SRC, _re.IGNORECASE)
        dump_aliases: Set[str] = set()
        for node in ast.walk(module.tree):
            if isinstance(node, ast.ImportFrom) and \
                    node.module in ("pickle", "cPickle", "dill"):
                dump_aliases |= {al.asname or al.name
                                 for al in node.names if al.name == "dump"}
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            d = _dotted(node.func) or ""
            tn = _terminal(node.func)
            if d.endswith("pickle.dump") or d == "dill.dump" or \
                    (isinstance(node.func, ast.Name)
                     and tn in dump_aliases):
                yield self.finding(
                    module, node,
                    f"{d or tn}(...) writes a pickle stream straight to "
                    f"a file — no atomic rename, no manifest, no "
                    f"checksum, no sharding layout")
            elif isinstance(node.func, ast.Name) and tn == "open" \
                    and self._binary_write_mode(node) \
                    and self._names_checkpoint(node, ckpt_re):
                yield self.finding(
                    module, node,
                    "binary-mode open() of a checkpoint path — a "
                    "preemption mid-write leaves a partial file at the "
                    "final path")

    @staticmethod
    def _binary_write_mode(call: ast.Call) -> bool:
        mode = call.args[1] if len(call.args) >= 2 else None
        for kw in call.keywords:
            if kw.arg == "mode":
                mode = kw.value
        if isinstance(mode, ast.Constant) and isinstance(mode.value, str):
            m = mode.value
            return "b" in m and any(c in m for c in "wax+")
        return False

    @staticmethod
    def _names_checkpoint(call: ast.Call, ckpt_re) -> bool:
        # conservative: only const path expressions (f-strings included)
        # can be matched; a variable path is dropped, never guessed
        if not call.args:
            return False
        for sub in ast.walk(call.args[0]):
            if isinstance(sub, ast.Constant) and \
                    isinstance(sub.value, str) and \
                    ckpt_re.search(sub.value):
                return True
        return False


# ---------------------------------------------------------------------------
# OBS-IN-JIT
# ---------------------------------------------------------------------------

#: observe names that are jit-safe BY DESIGN: the pure on-device telemetry
#: constructors the fused step folds into its donated carry.
_OBS_JIT_SAFE = {"accumulate", "init_telemetry", "StepTelemetry"}

#: the host-side observe submodules (telemetry — the on-device surface —
#: is deliberately absent)
_OBS_SUBMODULES = {"registry", "spans", "watchdog"}


@register
class ObsInJit(Rule):
    """Host-side observe calls inside traced code — the observe PR.

    Every ``apex_tpu.observe`` surface except the telemetry carry is
    host machinery: registry counters take locks and append to deques,
    spans read wall clocks and write JSONL sinks, the watchdog heartbeat
    touches thread state.  Traced, such a call runs ONCE at trace time
    and never again — silently dead telemetry (the counter sticks at its
    trace-time value, the span measures tracing, not execution) — and
    draining the telemetry carry inside jit would force the host sync
    the carry exists to avoid.  On-device accumulation belongs in
    ``observe.telemetry`` (jit-safe by construction); spans, counters,
    events, heartbeats and drains belong in the eager driver.
    """
    id = "OBS-IN-JIT"
    reachability_scoped = True
    summary = "host-side observe call inside a jit-reachable function"
    hint = ("accumulate on device via observe.telemetry (the fused "
            "step's telem carry) and log OUTSIDE the compiled step — "
            "spans/counters/events/drains belong in the eager driver; "
            "see TrainStep.drain_telemetry for the boundary")

    def _observe_bindings(self, module, ctx):
        """Local names bound to the host-side observe surface:
        ``mods`` (alias -> observe submodule) and ``funcs`` (alias ->
        imported observe callable).  Resolved through the analyzed set
        when the package is in it, through external dotted names when
        the engine is pointed at a file outside it."""
        mods: Dict[str, str] = {}
        funcs: Dict[str, str] = {}
        table = ctx.callgraph.imports.get(module.path)
        if table is None:
            return mods, funcs

        def _host_observe_path(p):
            p = p.replace("\\", "/")
            if p.endswith("/observe/telemetry.py"):
                return None         # the jit-safe on-device surface
            return p if "/observe/" in p else None

        for local, path in table.mod_alias.items():
            if _host_observe_path(path):
                mods[local] = path
        for local, (path, fn) in table.func_alias.items():
            if path.replace("\\", "/").endswith("/observe/__init__.py") \
                    and fn not in _OBS_JIT_SAFE and fn != "telemetry":
                funcs[local] = fn
        for local, dotted in table.ext_alias.items():
            if dotted.endswith(".observe") or dotted == "observe":
                mods[local] = dotted
            elif ".observe." in f".{dotted}":
                tail = dotted.rsplit(".", 1)[1]
                if tail in _OBS_SUBMODULES:
                    mods[local] = dotted
                elif tail not in _OBS_JIT_SAFE and tail != "telemetry":
                    funcs[local] = tail
        return mods, funcs

    def _walk_own(self, root):
        """Function body sans nested defs (each reachable nested def is
        visited as its own function)."""
        stack = list(ast.iter_child_nodes(root))
        while stack:
            node = stack.pop()
            yield node
            if not isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef, ast.Lambda)):
                stack.extend(ast.iter_child_nodes(node))

    def check(self, module, ctx):
        mods, funcs = self._observe_bindings(module, ctx)
        for info in ctx.callgraph.reachable_functions(module.path):
            for node in self._walk_own(info.node):
                if not isinstance(node, ast.Call):
                    continue
                f = self.flag_for(node, mods, funcs)
                if f is not None:
                    yield self.finding(module, node, f)

    def flag_for(self, node: ast.Call, mods, funcs) -> Optional[str]:
        tn = _terminal(node.func)
        if tn == "drain_telemetry":
            # any spelling, including self.drain_telemetry(): the drain
            # fetches the carry to host BY DESIGN — only legal outside
            return ("drain_telemetry() inside traced code — the drain "
                    "is the host fetch the telemetry carry defers; it "
                    "belongs outside the compiled step")
        if isinstance(node.func, ast.Name) and node.func.id in funcs:
            return (f"observe.{funcs[node.func.id]}(...) inside traced "
                    f"code — runs once at trace time, never per step "
                    f"(dead telemetry)")
        if isinstance(node.func, ast.Attribute):
            owner = node.func.value
            if isinstance(owner, ast.Name) and owner.id in mods and \
                    tn not in _OBS_JIT_SAFE:
                return (f"{owner.id}.{tn}(...) resolves into "
                        f"apex_tpu.observe's host surface inside traced "
                        f"code — runs once at trace time, never per "
                        f"step (dead telemetry)")
            d = _dotted(node.func) or ""
            if ".observe." in f".{d}" and ".telemetry." not in d and \
                    tn not in _OBS_JIT_SAFE:
                return (f"{d}(...) inside traced code — the observe "
                        f"host surface runs once at trace time, never "
                        f"per step (dead telemetry)")
        return None


# ---------------------------------------------------------------------------
# EXEC-BYPASS
# ---------------------------------------------------------------------------

#: function names that are, by this repo's convention, whole train/opt
#: step programs.  Exact matches plus the ``*_step_fn`` suffix — the
#: conservative set: inference ``run`` closures and generic helpers never
#: match.
_STEP_FN_NAMES = {"step_fn", "jit_step", "train_step", "zero_train_step"}

#: executor modules that legitimately compile/count dispatches: the
#: executor itself and the cache whose counters it bumps
_EXEC_HOMES = ("apex_tpu/runtime/executor.py",
               "apex_tpu/runtime/step_cache.py")


@register
class ExecBypass(Rule):
    """Step programs compiled or dispatched outside the one-runtime
    executor — the one-runtime PR.

    Before the executor, the eager optimizer surface and the fused train
    step each had their own route into the step-program cache; donation
    policy, dispatch counters and span/heartbeat plumbing drifted apart
    (the eager path had no heartbeats at all, so the stall watchdog was
    blind to half the library).  ``runtime/executor.py`` is now the one
    place ``jax.jit`` is called on a step program and the one place
    dispatches are counted.  Flags, outside the executor: direct
    ``step_cache.program(...)`` compile-or-hit calls, manual
    ``_bump("dispatches", ...)`` counter writes, and ``jax.jit`` of a
    function named like a train step.  Wrappers describe a
    ``Program`` and ``executor.submit`` it instead.
    """
    id = "EXEC-BYPASS"
    summary = ("step program compiled/dispatched outside "
               "runtime/executor.py")
    hint = ("describe the step as a runtime.executor.Program (static_key, "
            "donate_argnums, optional wrap/shardings) and dispatch via "
            "executor.submit — compiles, counters, dispatch spans and "
            "watchdog heartbeats then come uniformly; see "
            "docs/executor.md's migration table")

    @staticmethod
    def _is_step_name(name: Optional[str]) -> bool:
        return bool(name) and (name in _STEP_FN_NAMES
                               or name.endswith("_step_fn"))

    def check(self, module, ctx):
        path = module.path.replace("\\", "/")
        if path.endswith(_EXEC_HOMES):
            return
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            tn = _terminal(node.func)
            d = _dotted(node.func) or ""
            if tn == "program" and "step_cache" in d.split("."):
                yield self.finding(
                    module, node,
                    f"{d}(...) — direct step-cache compile-or-hit "
                    f"outside the executor (no dispatch count, no "
                    f"span, no heartbeat)")
            elif tn == "_bump" and node.args and \
                    isinstance(node.args[0], ast.Constant) and \
                    node.args[0].value == "dispatches":
                yield self.finding(
                    module, node,
                    "manual _bump('dispatches', ...) — dispatch "
                    "counting belongs to executor.submit")
            elif tn in ("jit", "pjit") and node.args:
                target = node.args[0]
                name = None
                if isinstance(target, ast.Name):
                    name = target.id
                elif isinstance(target, ast.Attribute):
                    name = target.attr
                if self._is_step_name(name):
                    yield self.finding(
                        module, node,
                        f"jax.jit of step function '{name}' outside the "
                        f"executor — the program bypasses the cache "
                        f"stats, donation policy and observability")


# ---------------------------------------------------------------------------
# SERVE-SHAPE
# ---------------------------------------------------------------------------

#: the serving program kinds (runtime/executor.py SERVE_KINDS) — string
#: literals only; a kind the rule cannot resolve is not guessed
_SERVE_PROGRAM_KINDS = {"prefill_step", "decode_step",
                        "draft_prefill_step", "spec_verify_step"}

#: attribute reads that surface a request-dependent extent
_SHAPE_ATTRS = {"shape", "size", "ndim"}

#: identifiers carrying a speculative tick's ragged acceptance count —
#: 1..k+1 per sequence per tick, the most request-dependent extent in
#: the serve path.  Matched by name because the value is a plain host
#: int by the time it could steer a program (``n_acc``, ``accepted_len``
#: and the like); routing through ``bucket*`` launders it exactly like
#: any other extent.
_ACCEPT_NAME_RE = re.compile(r"accept|(^|_)n_acc(_|$)")


def _serve_kind_of(call: ast.Call) -> Optional[str]:
    """The serving kind a ``Program(...)`` construction names, when the
    kind (first positional or ``kind=``) is a serve-kind string literal;
    else None."""
    if _terminal(call.func) != "Program":
        return None
    kind = call.args[0] if call.args else None
    for kw in call.keywords:
        if kw.arg == "kind":
            kind = kw.value
    if isinstance(kind, ast.Constant) and kind.value in _SERVE_PROGRAM_KINDS:
        return kind.value
    return None


def _serve_static_key(call: ast.Call) -> Optional[ast.AST]:
    if len(call.args) >= 2:
        return call.args[1]
    for kw in call.keywords:
        if kw.arg == "static_key":
            return kw.value
    return None


@register
class ServeShape(Rule):
    """Request-dependent shapes reaching serving programs — PR 12.

    A serving engine sees arbitrary prompt lengths, batch occupancies
    and block-table lengths; the step cache keys programs by (kind,
    static_key, operand signature).  Let a raw per-request extent —
    ``len(prompt)``, ``tokens.shape``, ``len(table)`` — reach a
    serve-kind static key (``prefill_step`` / ``decode_step`` /
    ``draft_prefill_step`` / ``spec_verify_step``) or steer which
    program gets built, and every distinct request length compiles a
    fresh executable: recompilation scales with TRAFFIC, not with
    config, and tail latency spikes exactly when load does.  The serve
    engine's discipline is a bucket table: every dynamic extent is
    rounded up through ``serve.scheduler.bucket`` (powers of two capped
    at the config maximum) before it touches program identity, so the
    shape set is ``O(log·log)`` and decode is recompile-free after
    warmup.  Speculative decoding adds the worst extent of all: the
    per-tick ragged acceptance count (``n_acc``/``accepted_len``, 1..k+1
    PER SEQUENCE PER TICK) — key or steer a program on it raw and the
    engine recompiles mid-stream on the first tick whose acceptance
    pattern is new (the PR 16 incident; docs/lint.md).  Flags, on
    serve-kind ``Program(...)`` constructions: ``len(...)`` /
    ``.shape`` / ``.size`` / ``.ndim`` / acceptance-count identifiers
    inside the static key unless routed through a ``bucket*`` call,
    and ``if``/``while`` tests on those extents inside the functions
    that build the programs (per-request program selection is the same
    recompile surface by another route).
    """
    id = "SERVE-SHAPE"
    summary = ("request-dependent shape in a serving program key / "
               "build path (recompiles per request, not per bucket)")
    hint = ("round every request-dependent extent — lengths, shapes, "
            "and speculative acceptance counts alike — through the "
            "bucket table (serve.scheduler.bucket: next power of two, "
            "capped at the config maximum) before it reaches a Program "
            "static key or build-time branch — operand signatures then "
            "complete the cache key and decode re-hits after warmup; "
            "ragged acceptance belongs in operand VALUES (the host "
            "commit loop), never in program identity; see "
            "docs/serving.md's keying discipline")

    def _dynamic_exprs(self, expr):
        """``len()`` calls, ``.shape``/``.size``/``.ndim`` reads, and
        acceptance-count identifiers (``n_acc``/``accepted_len``/...)
        in ``expr`` that are NOT routed through a ``bucket*`` call —
        descent stops at any call whose name contains ``bucket``: its
        result is by construction one of O(log) values."""
        stack = [expr]
        while stack:
            node = stack.pop()
            if isinstance(node, ast.Call):
                tn = _terminal(node.func) or ""
                if "bucket" in tn:
                    continue
                if isinstance(node.func, ast.Name) and tn == "len":
                    yield node, "len(...)"
                    continue
            if isinstance(node, ast.Attribute) and \
                    node.attr in _SHAPE_ATTRS:
                yield node, f".{node.attr}"
                continue
            if isinstance(node, ast.Name) and \
                    _ACCEPT_NAME_RE.search(node.id):
                yield node, f"raw acceptance count '{node.id}'"
                continue
            stack.extend(ast.iter_child_nodes(node))

    def check(self, module, ctx):
        serve_calls = []
        for node in ast.walk(module.tree):
            if isinstance(node, ast.Call) and _serve_kind_of(node):
                serve_calls.append(node)
        if not serve_calls:
            return
        for call in serve_calls:
            kind = _serve_kind_of(call)
            key = _serve_static_key(call)
            if key is None:
                continue
            for bad, what in self._dynamic_exprs(key):
                yield self.finding(
                    module, bad,
                    f"{what} in the '{kind}' program's static key — "
                    f"the key tracks a per-request extent, so every "
                    f"new request length compiles a fresh executable")
        # build-time branches on raw extents, in the functions that
        # lexically construct the serving programs
        ids = {id(c) for c in serve_calls}
        for fn in ast.walk(module.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if not any(id(n) in ids for n in ast.walk(fn)):
                continue
            for node in ast.walk(fn):
                if not isinstance(node, (ast.If, ast.While)):
                    continue
                for bad, what in self._dynamic_exprs(node.test):
                    yield self.finding(
                        module, bad,
                        f"{what} steering a "
                        f"{'while' if isinstance(node, ast.While) else 'if'}"
                        f" in serving-program build code — per-request "
                        f"program selection recompiles per request "
                        f"length, not per bucket")


# ---------------------------------------------------------------------------
# KERNEL-FALLBACK
# ---------------------------------------------------------------------------


def _in_kernels_package(module: Module) -> bool:
    """True for files living in ``apex_tpu/kernels/`` — the one place a
    raw ``pallas_call`` may appear."""
    dotted = module.dotted or ""
    if dotted == "apex_tpu.kernels" or \
            dotted.startswith("apex_tpu.kernels."):
        return True
    rel = "/" + module.relpath.replace("\\", "/")
    return "/apex_tpu/kernels/" in rel


@register
class KernelFallback(Rule):
    """Hand-written kernels without a declared escape hatch — PR 13.

    Rounds 4-5 measured most of this repo's hand-written Pallas kernels
    LOSING to XLA's own lowering on real shapes (norms 0.93-1.03x,
    fused LM-head chain 0.69x at GPT-2 shapes; flash attention only
    wins >= 512 keys).  A ``pallas_call`` wired straight into a model
    path locks those losses in: there is no seam to route the losing
    shapes back to XLA, and no probe record to ever find out.  The
    discipline is the ``apex_tpu.kernels`` tier: every kernel lives in
    that package, registers through ``register_kernel`` with a declared
    ``xla_fallback`` (the dotted path of the XLA tier), and holds the
    rule that picks the tier from the mode and the operands' shapes,
    with its receipt beside it.  Flags: any ``pallas_call`` call or
    import outside ``apex_tpu/kernels/``, and a ``register_kernel(...)``
    missing a usable ``xla_fallback``.
    """
    id = "KERNEL-FALLBACK"
    summary = ("pallas_call outside the kernels tier, or a kernel "
               "registered without a declared XLA fallback")
    hint = ("move the kernel into apex_tpu/kernels/ and register it: "
            "register_kernel(name, xla_fallback='<dotted path of the "
            "XLA implementation>'); the rule that sends a shape to the "
            "XLA tier lives in the kernel's own module; see "
            "docs/kernels.md")

    def _missing(self, call: ast.Call) -> List[str]:
        """Registration keywords absent or constant-empty."""
        kws = {kw.arg: kw.value for kw in call.keywords if kw.arg}
        val = kws.get("xla_fallback")
        if val is None:
            return ["xla_fallback"]
        if isinstance(val, ast.Constant) and not val.value:
            return ["xla_fallback (empty)"]
        return []

    def check(self, module, ctx):
        in_kernels = _in_kernels_package(module)
        for node in ast.walk(module.tree):
            if isinstance(node, ast.ImportFrom) and not in_kernels:
                for alias in node.names:
                    if alias.name == "pallas_call":
                        yield self.finding(
                            module, node,
                            "pallas_call imported outside "
                            "apex_tpu/kernels/ — hand-written kernels "
                            "belong in the measured-dispatch tier, not "
                            "wired raw into model code")
            if not isinstance(node, ast.Call):
                continue
            name = _terminal(node.func)
            if name == "pallas_call" and not in_kernels:
                yield self.finding(
                    module, node,
                    "raw pallas_call outside apex_tpu/kernels/ — no "
                    "XLA fallback seam: losing shapes "
                    "(round-5: norms 0.93-1.03x, lm_head chain 0.69x) "
                    "can never route back to XLA")
            elif name == "register_kernel":
                missing = self._missing(node)
                if missing:
                    yield self.finding(
                        module, node,
                        "kernel registered without " + " / ".join(missing)
                        + " — there is no XLA tier to send a losing "
                        "shape to")


# ---------------------------------------------------------------------------
# PRECISION-SINK / TRACER-LEAK / SHAPE-BRANCH — the dataflow-native rules
# ---------------------------------------------------------------------------

#: reductions/contractions where a half-precision input silently becomes
#: a half-precision ACCUMULATOR unless told otherwise
_REDUCTION_CALLS = {"sum", "mean", "prod", "cumsum", "cumprod", "dot",
                    "matmul", "tensordot", "vdot", "einsum"}

#: container mutators that smuggle a value past the end of the trace
_LEAK_MUTATORS = {"append", "add", "extend", "insert", "setdefault",
                  "update"}


@register
class PrecisionSink(Rule):
    """Half-precision values reaching a reduction without an fp32
    accumulator — the amp-O2 master-weight invariant as a rule.

    PR 4's loss-scaling work exists because fp16 overflows at 65504 and
    bf16 drops mantissa bits; both are fine for *storage* and matmul
    inputs but fatal for *accumulation*.  ``jnp.sum`` of an fp16 array
    accumulates IN fp16 unless ``preferred_element_type``/``dtype`` says
    otherwise.  The dtype lattice proves where a half value flows into a
    reduction with no fp32 upcast on any path — a proof, not a guess:
    an operand the dataflow cannot type never flags.
    """
    id = "PRECISION-SINK"
    reachability_scoped = True
    summary = "fp16/bf16 value reduced/accumulated without fp32 upcast"
    hint = ("accumulate in fp32: pass preferred_element_type="
            "jnp.float32 (dot/matmul/einsum), dtype=jnp.float32 "
            "(sum/mean/prod), or upcast with .astype(jnp.float32) "
            "first — see the master-weight chain in amp/amp.py")

    def _module_aliases(self, module, ctx):
        table = ctx.callgraph.imports.get(module.path)
        names = {"jnp", "np", "jax", "lax", "math"}
        if table:
            names |= set(table.ext_alias) | set(table.mod_alias)
        return names

    def _folded_dtype(self, df, info, call, mod_names):
        """Promoted dtype of the array operands (args + non-module
        receiver), skipping einsum subscript strings."""
        from . import dataflow as _df
        operands = [a for a in call.args
                    if not (isinstance(a, ast.Constant)
                            and isinstance(a.value, str))]
        if isinstance(call.func, ast.Attribute):
            recv = call.func.value
            if not (isinstance(recv, ast.Name) and recv.id in mod_names):
                operands.append(recv)
        if not operands or any(isinstance(a, ast.Starred)
                               for a in operands):
            return _df.DT_UNKNOWN
        dt = _df.DT_WEAK
        for a in operands:
            dt = _df.promote_dtype(dt, df.eval_in(info, a).dtype)
        return dt

    def _exempt(self, call):
        from . import dataflow as _df
        for kw in call.keywords:
            if kw.arg == "preferred_element_type":
                return True
            if kw.arg in ("dtype", "accumulator_dtype") and \
                    _df.dtype_const(kw.value) not in _df.HALF_DTYPES:
                return True
        return False

    def check(self, module, ctx):
        from . import dataflow as _df
        mod_names = None
        for info in ctx.callgraph.reachable_functions(module.path):
            df = ctx.dataflow
            if mod_names is None:
                mod_names = self._module_aliases(module, ctx)
            for node in _walk_own(info.node):
                if isinstance(node, ast.Call):
                    tn = _terminal(node.func)
                    if tn not in _REDUCTION_CALLS or self._exempt(node):
                        continue
                    dt = self._folded_dtype(df, info, node, mod_names)
                    if dt in _df.HALF_DTYPES:
                        yield self.finding(
                            module, node,
                            f"half-precision operand reaches {tn}() — "
                            f"the accumulator inherits the half dtype "
                            f"(fp16 saturates at 65504)")
                elif isinstance(node, ast.BinOp) and \
                        isinstance(node.op, ast.MatMult):
                    if df.eval_in(info, node).is_half:
                        yield self.finding(
                            module, node,
                            "half @ half matmul accumulates in half "
                            "precision — pass preferred_element_type="
                            "jnp.float32 via jnp.matmul, or upcast")
                elif isinstance(node, (ast.For, ast.While)):
                    yield from self._loop_accum(module, df, info, node)

    def _loop_accum(self, module, df, info, loop):
        """`acc += h` / `acc = acc + h` in a python loop: each iteration
        adds in half precision."""
        for st in ast.walk(loop):
            if isinstance(st, ast.AugAssign) and \
                    isinstance(st.op, ast.Add):
                if df.eval_in(info, st.value).is_half:
                    yield self.finding(
                        module, st,
                        "loop accumulation of a half-precision value — "
                        "running sum saturates/rounds in fp16/bf16")
            elif isinstance(st, ast.Assign) and \
                    isinstance(st.value, ast.BinOp) and \
                    isinstance(st.value.op, ast.Add) and \
                    len(st.targets) == 1 and \
                    isinstance(st.targets[0], ast.Name):
                tgt = st.targets[0].id
                sides = (st.value.left, st.value.right)
                if any(isinstance(s, ast.Name) and s.id == tgt
                       for s in sides) and \
                        df.eval_in(info, st.value).is_half:
                    yield self.finding(
                        module, st,
                        "loop accumulation of a half-precision value — "
                        "running sum saturates/rounds in fp16/bf16")


def _leftmost_name(node):
    while isinstance(node, (ast.Attribute, ast.Subscript)):
        node = node.value
    return node.id if isinstance(node, ast.Name) else None


@register
class TracerLeak(Rule):
    """Traced value stored into state that outlives the trace.

    A tracer written to a module global, an instance attribute, or a
    long-lived container during tracing becomes a corpse the moment the
    trace ends: touching it later raises
    ``UnexpectedTracerError`` (best case) or silently bakes one
    example's abstract value into every future step (worst case — jax
    calls this the leaked-tracer bug).  Dataflow knows which values are
    tracers and which names are module-level, so the rule fires only on
    proven leaks.
    """
    id = "TRACER-LEAK"
    reachability_scoped = True
    summary = "traced value escapes into state that outlives the trace"
    hint = ("return the value from the traced function instead (carry "
            "it through the step's outputs); host-side stores belong "
            "outside the jit boundary — see how observe/ keeps "
            "telemetry in the carry")

    def _store_desc(self, target, gdecls, mg, local):
        if isinstance(target, ast.Name):
            if target.id in gdecls:
                return f"module global '{target.id}'"
            return None
        base = _leftmost_name(target)
        if base in ("self", "cls"):
            return f"instance state '{base}.…'"
        if base and base in mg and base not in local:
            kind = ("module-level container"
                    if isinstance(target, ast.Subscript)
                    else "module-global attribute")
            return f"{kind} '{base}'"
        return None

    def check(self, module, ctx):
        for info in ctx.callgraph.reachable_functions(module.path):
            df = ctx.dataflow
            mg = df.module_globals(module.path)
            facts = df.facts_for(info.module_path, info.qualname)
            local = set(facts.env) if facts is not None else set()
            gdecls = set()
            for n in _walk_own(info.node):
                if isinstance(n, ast.Global):
                    gdecls.update(n.names)
            for n in _walk_own(info.node):
                if isinstance(n, (ast.Assign, ast.AugAssign,
                                  ast.AnnAssign)):
                    value = n.value
                    if value is None or \
                            not df.eval_in(info, value).is_traced:
                        continue
                    targets = n.targets if isinstance(n, ast.Assign) \
                        else [n.target]
                    for t in targets:
                        desc = self._store_desc(t, gdecls, mg, local)
                        if desc:
                            yield self.finding(
                                module, n,
                                f"traced value stored into {desc} — "
                                f"outlives the trace (leaked tracer)")
                elif isinstance(n, ast.Call) and \
                        isinstance(n.func, ast.Attribute) and \
                        n.func.attr in _LEAK_MUTATORS and n.args:
                    desc = self._store_desc(n.func, gdecls, mg, local)
                    if desc and any(df.eval_in(info, a).is_traced
                                    for a in n.args):
                        yield self.finding(
                            module, n,
                            f"traced value .{n.func.attr}()-ed into "
                            f"{desc} — outlives the trace (leaked "
                            f"tracer)")


@register
class ShapeBranch(Rule):
    """Python control flow on a traced value's shape — SERVE-SHAPE's
    program-identity hazard, generalized beyond serve.

    Shapes ARE concrete at trace time, so ``if x.shape[0] > n:`` runs —
    but each distinct shape now takes its own branch and keys its own
    executable, which is exactly how the serve path melted before
    bucketing (PR 9): continuous batching feeds every length that
    arrives.  The dataflow ``shape_derived`` flag follows shape reads
    through arithmetic and helpers; routing through any ``bucket*``
    quantizer clears it (the sanctioned O(log) program count).
    Raise/assert-only guards are validation, not program forks, and
    stay exempt.
    """
    id = "SHAPE-BRANCH"
    reachability_scoped = True
    summary = "python branch/loop on a traced value's shape"
    hint = ("quantize first (bucket_len / next_bucket-style helper) so "
            "the program count stays O(log max) — or move the decision "
            "on-device with jnp.where / lax.cond; see "
            "docs/serving.md on shape buckets")

    #: name fragments of sanctioned shape-quantizer helpers: branches
    #: INSIDE them are how the O(log) program count gets computed
    _QUANTIZER_NAMES = ("bucket", "block", "round_up", "chunk")

    def _is_pad_guard(self, node):
        """``if padded != raw: x = jnp.pad(...)`` — pad-to-multiple.
        Both paths converge on the quantized extent, so the branch does
        not fork program identity."""
        if not isinstance(node, ast.If) or node.orelse:
            return False
        for s in node.body:
            if not (isinstance(s, ast.Assign)
                    and isinstance(s.value, ast.Call)
                    and _terminal(s.value.func) == "pad"):
                return False
        return bool(node.body)

    def check(self, module, ctx):
        for info in ctx.callgraph.reachable_functions(module.path):
            name = info.name.lower()
            if any(q in name for q in self._QUANTIZER_NAMES):
                continue
            for node in _walk_own(info.node):
                if not isinstance(node, (ast.If, ast.While)):
                    continue
                if isinstance(node, ast.If) and not node.orelse and \
                        all(isinstance(s, (ast.Raise, ast.Assert))
                            for s in node.body):
                    continue   # shape-validation guard, not a fork
                if self._is_pad_guard(node):
                    continue
                val = ctx.dataflow.eval_in(info, node.test)
                if val.shape_derived:
                    kw = type(node).__name__.lower()
                    yield self.finding(
                        module, node.test,
                        f"`{kw}` on a shape-derived value — every "
                        f"distinct input shape takes its own branch "
                        f"and compiles its own program")


# ---------------------------------------------------------------------------
# STALE-SUPPRESSION — engine-driven: the directive audit
# ---------------------------------------------------------------------------


@register
class StaleSuppression(Rule):
    """``# tpu-lint: disable=RULE`` comments whose rule no longer fires
    on that line.

    Suppressions are debt with a reason attached; when the analyzer
    gets precise enough to prove the site clean (as dataflow did for
    the pipeline ppermute hops), the directive outlives its finding and
    silently masks FUTURE regressions on the same line.  The engine
    tracks which directives matched a finding during the run and
    reports the unmatched remainder here — a rule id, so it selects,
    suppresses and baselines like any other.
    """
    id = "STALE-SUPPRESSION"
    summary = "suppression directive whose rule no longer fires here"
    hint = ("delete the directive — the analyzer proves the line "
            "clean; if the hazard is real but currently unprovable, "
            "keep it and note why in the reason")

    #: the engine emits these findings after the rule loop (it owns the
    #: directive-usage bookkeeping); check() itself is empty
    engine_driven = True

    def check(self, module, ctx):
        return iter(())


# ---------------------------------------------------------------------------
# CLUSTER-ASSUME
# ---------------------------------------------------------------------------

#: env vars that hardcode process topology — reading them outside the
#: launcher/cluster seam bakes "the fleet I started with" into code
#: that must survive membership changes
_TOPOLOGY_ENV = {"APEX_TPU_NUM_PROCESSES", "APEX_TPU_PROCESS_ID"}


@register
class ClusterAssume(Rule):
    """Raw process-topology assumptions outside the cluster layer — PR 15.

    ``jax.process_index()`` / ``jax.process_count()`` answer "who am I
    in the fleet the job STARTED with".  Under the elastic cluster
    runtime that fleet is a moving target: a membership epoch can
    retire rank 3 while code still branches on ``process_index() != 0``
    — the incident was exactly that, a rank-0 gate in the amp logging
    path that picked a NEW rank 0 after a shrink and silently swapped
    which host wrote logs mid-run.  Topology questions go through the
    sanctioned seam (``parallel.distributed.rank/num_processes/
    init_distributed``) or key off an ``apex_tpu.cluster``
    MembershipView epoch, which is immutable per epoch by construction.
    """
    id = "CLUSTER-ASSUME"
    summary = "raw process-topology query outside the cluster layer"
    hint = ("route through apex_tpu.parallel.distributed (rank(), "
            "num_processes(), init_distributed()) or key off an "
            "apex_tpu.cluster MembershipView epoch — raw process ids "
            "go stale the moment cluster membership changes")

    _CALLS = {"jax.process_index": "jax.process_index() — raw rank "
                                   "query; stale after a membership "
                                   "change",
              "jax.process_count": "jax.process_count() — raw fleet "
                                   "size; stale after a membership "
                                   "change",
              "jax.distributed.initialize": "bare jax.distributed."
                                            "initialize — blocks "
                                            "forever with no retry; "
                                            "use init_distributed()"}

    def check(self, module, ctx):
        path = module.path.replace("\\", "/")
        if "apex_tpu/cluster/" in path or path.endswith(
                "apex_tpu/parallel/distributed.py"):
            return      # the sanctioned topology homes
        for node in ast.walk(module.tree):
            if isinstance(node, ast.Call):
                d = _dotted(node.func) or ""
                if d in self._CALLS:
                    yield self.finding(module, node, self._CALLS[d])
                elif d == "os.environ.get" and node.args and \
                        isinstance(node.args[0], ast.Constant) and \
                        node.args[0].value in _TOPOLOGY_ENV:
                    yield self.finding(
                        module, node,
                        f"os.environ.get({node.args[0].value!r}) — "
                        f"hardcoded process-count arithmetic outside "
                        f"the launcher seam")
            elif isinstance(node, ast.Subscript):
                if (_dotted(node.value) or "") == "os.environ" and \
                        isinstance(node.slice, ast.Constant) and \
                        node.slice.value in _TOPOLOGY_ENV:
                    yield self.finding(
                        module, node,
                        f"os.environ[{node.slice.value!r}] — hardcoded "
                        f"process-count arithmetic outside the "
                        f"launcher seam")


# ---------------------------------------------------------------------------
# WEIGHT-PUBLISH
# ---------------------------------------------------------------------------

#: identifier fragments that name model/optimizer state pytrees — the
#: things whose placement must stay measured (raw movement of a batch
#: named `images` or a telemetry leaf is fine)
_WEIGHTY = ("param", "master", "weight", "state")


@register
class WeightPublish(Rule):
    """Raw device placement of model-parameter pytrees — PR 18.

    ``jax.device_put`` / ``jax.device_get`` of weights outside the
    sanctioned seams is weight movement the runtime cannot see: it
    skips ``reshard_state``'s layout-identical zero-copy fast path, its
    dtype/shape validation, and the per-leaf hit stats every measured
    sync reports — the incident (docs/lint.md) was a rollout publish
    hand-rolled with ``device_get``+``device_put`` that silently
    gathered 100% of the masters to host every epoch and re-placed
    them, turning a zero-copy swap into the slowest stage of the loop.
    Weight movement goes through ``runtime/resilience.py`` (reshard /
    checkpoint), the ``parallel/`` placement layer, or the rollout
    publish path (``apex_tpu/rollout/publish.py``).
    """
    id = "WEIGHT-PUBLISH"
    summary = "raw device_put/device_get of a parameter pytree"
    hint = ("move weights through the measured surfaces — "
            "runtime.resilience.reshard_state (validated, zero-copy "
            "where layouts match, per-leaf stats) or "
            "rollout.WeightPublisher (cast-once, versioned, telemetered)"
            " — raw placement is invisible to the sync accounting")

    _CALLS = {"jax.device_put", "jax.device_get"}

    @staticmethod
    def _weighty_arg(arg: ast.AST) -> Optional[str]:
        """The first weight-ish identifier fragment in the arg subtree
        ('master_params', 'step.state', ...), else None."""
        for sub in ast.walk(arg):
            name = None
            if isinstance(sub, ast.Name):
                name = sub.id
            elif isinstance(sub, ast.Attribute):
                name = sub.attr
            if name is None:
                continue
            low = name.lower()
            if any(t in low for t in _WEIGHTY):
                return name
        return None

    def check(self, module, ctx):
        path = module.path.replace("\\", "/")
        if path.endswith("apex_tpu/runtime/resilience.py") \
                or "apex_tpu/parallel/" in path \
                or path.endswith("apex_tpu/rollout/publish.py"):
            return      # the sanctioned weight-movement homes
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            d = _dotted(node.func) or ""
            if d not in self._CALLS or not node.args:
                continue
            name = self._weighty_arg(node.args[0])
            if name is not None:
                yield self.finding(
                    module, node,
                    f"{d}({name}, ...) — raw placement of what looks "
                    f"like model/optimizer state; unmeasured weight "
                    f"movement bypasses the reshard surface")


# ---------------------------------------------------------------------------
# POOL-ALIAS
# ---------------------------------------------------------------------------

#: BlockPool bookkeeping attributes no code outside serve/pool.py may
#: touch — mutating them directly desynchronizes refcounts from block
#: tables, which the pool can only report as a leak or a double free
_POOL_PRIVATE_ATTRS = {"_free", "_refs", "_cached", "_hash_index",
                       "_block_hash"}

#: jnp ``.at[...]`` scatter methods that WRITE (``.get`` reads)
_AT_WRITE_METHODS = {"set", "add", "subtract", "multiply", "divide",
                     "min", "max", "apply", "power"}


def _names_a_pool(node: ast.AST) -> bool:
    """True when a dotted expression's name says it is a KV pool
    (``pool``, ``self.pool``, ``engine.dpool``, ``block_pool.q`` ...).
    Name-based on purpose: pool buffers are plain jnp arrays by the
    time they are scattered into, so there is no type to resolve —
    and the repo's naming convention is exactly what the rule audits."""
    d = _dotted(node)
    if d is None:
        return False
    return any("pool" in part.lower() for part in d.split("."))


@register
class PoolAlias(Rule):
    """Pool-block writes outside the refcount API — PR 20.

    The prefix cache made pool blocks SHARED: ``acquire_prefix`` hands
    N sessions the same physical block, ``commit`` publishes it in the
    hash index, and the only safe mutations are the pool's own
    refcounted verbs (``alloc`` / ``free`` / ``commit`` /
    ``acquire_prefix`` / ``flush_cache``).  Two aliasing hazards exist
    and both are silent at the write site.  (1) An in-place scatter
    (``pool.at[..., blk].set(...)``) into a shared block rewrites KV
    that OTHER sessions' attention is reading — cross-session
    corruption with no crash, just wrong tokens for whoever shares the
    prefix; every legitimate scatter lives in the serve kernel bodies
    (serve/kernels.py, including the copy-on-write fork) or the
    handoff restore (runtime/resilience.py), where the scheduler has
    proven the target block exclusive.  (2) Reaching into the pool's
    private bookkeeping (``pool._free`` / ``pool._refs`` / the hash
    index) instead of calling ``free`` bypasses refcounting entirely:
    a block two tables still reference returns to the free list, the
    allocator re-grants it, and two sessions now scatter into each
    other.  Flags both patterns on any pool-named base outside the
    sanctioned homes; docs/lint.md carries the incident.
    """
    id = "POOL-ALIAS"
    summary = ("direct free/scatter-write of KV pool blocks outside "
               "serve/pool.py's refcount API (shared-block corruption)")
    hint = ("go through the BlockPool verbs — alloc/free/commit/"
            "acquire_prefix keep refcounts and block tables in sync; "
            "a write into a shared block belongs behind a copy-on-write "
            "fork (scheduler cow_pending + the block_copy program), "
            "never an ad-hoc scatter; see docs/serving.md's Prefix "
            "caching section")

    def check(self, module, ctx):
        path = module.path.replace("\\", "/")
        if path.endswith("apex_tpu/serve/pool.py"):
            return      # the refcount API's own implementation
        kernel_home = path.endswith("apex_tpu/serve/kernels.py") \
            or path.endswith("apex_tpu/runtime/resilience.py")
        for node in ast.walk(module.tree):
            if isinstance(node, ast.Attribute) and \
                    node.attr in _POOL_PRIVATE_ATTRS and \
                    _names_a_pool(node.value):
                yield self.finding(
                    module, node,
                    f"direct access to pool bookkeeping "
                    f"'.{node.attr}' — mutating it desynchronizes "
                    f"refcounts from live block tables (double grants "
                    f"of shared blocks)")
                continue
            if kernel_home or not isinstance(node, ast.Call):
                continue
            fn = node.func
            if not (isinstance(fn, ast.Attribute)
                    and fn.attr in _AT_WRITE_METHODS
                    and isinstance(fn.value, ast.Subscript)):
                continue
            at = fn.value.value
            if isinstance(at, ast.Attribute) and at.attr == "at" and \
                    _names_a_pool(at.value):
                yield self.finding(
                    module, node,
                    f"in-place .at[...].{fn.attr}() scatter into a KV "
                    f"pool buffer — if the target block is shared "
                    f"(prefix cache), this rewrites KV other sessions "
                    f"are reading")
