"""The codebase-specific lint rules.

Each rule guards an invariant a prior PR introduced (see
``docs/static_analysis.md`` for the rule table and rationale):

* ``host-sync-in-jit`` — the fused loop window is only a win
  if nothing inside the traced region forces a host round-trip.
* ``collective-axis-consistency`` — CheckFree+ recovery *is* ``psum`` /
  ``ppermute`` collectives (PR 5); a typo'd axis name silently corrupts the
  neighbor-averaging result.
* ``prng-key-reuse`` — reusing a PRNG key correlates draws that the paper's
  init/merge math assumes independent.
* ``tracer-branch`` — Python ``if``/``while`` on array values inside traced
  code either crashes (ConcretizationTypeError) or silently bakes in one
  branch.
* ``donation-after-dispatch`` — params/opt_state are donated to the fused
  step (PR 4); touching them after dispatch reads freed buffers on donating
  backends.
* ``pallas-contract`` — BlockSpec rank / index_map arity / grid must agree,
  and the interpret flag must be chosen at call time (it follows the
  platform the call runs on), not baked in at import.
"""
from __future__ import annotations

import ast
import os
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from repro.analysis.engine import (Finding, ModuleIndex, ProjectContext,
                                   Rule, register_rule)

# ---------------------------------------------------------------------------
# host-sync-in-jit
# ---------------------------------------------------------------------------

HOST_SYNC_CALLS = {
    "jax.device_get": "forces a device->host transfer",
    "jax.block_until_ready": "blocks on device results",
    "numpy.asarray": "materializes the traced value on host",
    "numpy.array": "materializes the traced value on host",
    "numpy.copy": "materializes the traced value on host",
}
CAST_BUILTINS = {"float", "int", "bool", "complex"}


@register_rule
class HostSyncInJit(Rule):
    id = "host-sync-in-jit"
    doc = ("host synchronization (float()/.item()/np.asarray/jax.device_get)"
           " reachable from jitted/scanned/shard_mapped code")

    def check(self, index: ModuleIndex,
              project: ProjectContext) -> Iterable[Finding]:
        res = index.resolver
        for fn in index.traced:
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                canon = res.canonical(node.func)
                if canon in HOST_SYNC_CALLS:
                    yield self.finding(
                        index, node,
                        f"`{canon}` inside traced code "
                        f"({HOST_SYNC_CALLS[canon]}); hoist it out of the "
                        f"jitted region or defer to the window drain")
                elif (isinstance(node.func, ast.Attribute)
                      and node.func.attr == "item" and not node.args):
                    yield self.finding(
                        index, node,
                        "`.item()` inside traced code forces a host sync; "
                        "keep the value on device")
                elif (isinstance(node.func, ast.Name)
                      and node.func.id in CAST_BUILTINS
                      and node.args
                      and not isinstance(node.args[0], ast.Constant)):
                    yield self.finding(
                        index, node,
                        f"`{node.func.id}(...)` on a non-constant inside "
                        f"traced code concretizes the tracer (host sync); "
                        f"use jnp casts or move it to the host side")


# ---------------------------------------------------------------------------
# collective-axis-consistency
# ---------------------------------------------------------------------------

# canonical collective -> index of the axis-name positional arg
COLLECTIVES = {
    "jax.lax.psum": 1, "jax.lax.pmean": 1, "jax.lax.pmax": 1,
    "jax.lax.pmin": 1, "jax.lax.ppermute": 1, "jax.lax.pshuffle": 1,
    "jax.lax.all_gather": 1, "jax.lax.all_to_all": 1,
    "jax.lax.psum_scatter": 1, "jax.lax.axis_index": 0,
    "jax.lax.axis_size": 0,
}
SPEC_CTORS = {"jax.sharding.PartitionSpec", "jax.P",
              "jax.sharding.PartitionSpec.P"}


def _axis_strings(node: ast.AST) -> List[Tuple[ast.AST, str]]:
    """(node, name) for every constant string inside an axis argument."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return [(node, node.value)]
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for el in node.elts:
            out.extend(_axis_strings(el))
        return out
    return []


@register_rule
class CollectiveAxisConsistency(Rule):
    id = "collective-axis-consistency"
    doc = ("psum/ppermute/pmean/axis_index axis names must match a mesh "
           "axis declared by a shard_map/Mesh in the analyzed project")

    def check(self, index: ModuleIndex,
              project: ProjectContext) -> Iterable[Finding]:
        if not project.axis_names:
            return  # no Mesh declarations anywhere: nothing to check against
        res = index.resolver
        declared = sorted(project.axis_names)
        for node in ast.walk(index.tree):
            if not isinstance(node, ast.Call):
                continue
            canon = res.canonical(node.func)
            if canon in COLLECTIVES:
                pos = COLLECTIVES[canon]
                cands: List[ast.AST] = []
                if len(node.args) > pos:
                    cands.append(node.args[pos])
                cands += [kw.value for kw in node.keywords
                          if kw.arg == "axis_name"]
                for c in cands:
                    for sub, name in _axis_strings(c):
                        if name not in project.axis_names:
                            yield self.finding(
                                index, sub,
                                f"collective `{canon.split('.')[-1]}` names "
                                f"axis {name!r}, which no Mesh declares "
                                f"(declared: {declared}); a wrong axis name "
                                f"silently mis-routes the collective")
            elif canon in SPEC_CTORS or (
                    canon is not None
                    and canon.split(".")[-1] == "PartitionSpec"):
                for arg in node.args:
                    for sub, name in _axis_strings(arg):
                        if name not in project.axis_names:
                            yield self.finding(
                                index, sub,
                                f"PartitionSpec names axis {name!r}, which "
                                f"no Mesh declares (declared: {declared})")


# ---------------------------------------------------------------------------
# prng-key-reuse
# ---------------------------------------------------------------------------

KEY_PRODUCERS = {"jax.random.PRNGKey", "jax.random.key",
                 "jax.random.split", "jax.random.fold_in",
                 "jax.random.clone"}
# fold_in derives a fresh key *without* consuming its parent — deriving many
# children from one key (`fold_in(key, i)` per step) is the blessed idiom
NON_CONSUMING = {"jax.random.PRNGKey", "jax.random.key",
                 "jax.random.key_data", "jax.random.wrap_key_data",
                 "jax.random.key_impl", "jax.random.clone",
                 "jax.random.fold_in"}
KEY_PARAM_HINTS = ("key", "rng")


def _target_names(target: ast.AST) -> List[str]:
    if isinstance(target, ast.Name):
        return [target.id]
    if isinstance(target, (ast.Tuple, ast.List)):
        out: List[str] = []
        for el in target.elts:
            out.extend(_target_names(el))
        return out
    return []


class _KeyState:
    """var -> times consumed since last (re)binding; None count = not a key."""

    def __init__(self):
        self.counts: Dict[str, int] = {}

    def copy(self) -> "_KeyState":
        s = _KeyState()
        s.counts = dict(self.counts)
        return s

    def merge(self, other: "_KeyState") -> None:
        for k, v in other.counts.items():
            self.counts[k] = max(self.counts.get(k, 0), v)


@register_rule
class PrngKeyReuse(Rule):
    id = "prng-key-reuse"
    doc = ("a PRNG key consumed by more than one jax.random call without an "
           "intervening split/fold_in")

    def check(self, index: ModuleIndex,
              project: ProjectContext) -> Iterable[Finding]:
        for name, fn in list(index.functions.items()):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            # only analyze top-most functions: nested defs are walked as
            # part of their parent's body in source order
            if isinstance(index.enclosing_function(fn),
                          (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
                continue
            yield from self._check_fn(index, fn)

    @staticmethod
    def _uses_jax_random(index: ModuleIndex, fn) -> bool:
        for node in ast.walk(fn):
            if isinstance(node, ast.Call):
                canon = index.resolver.canonical(node.func)
                if canon is not None and canon.startswith("jax.random."):
                    return True
        return False

    def _seed_params(self, index: ModuleIndex, fn, state: "_KeyState") -> None:
        # a param named `key`/`rng` is only treated as a PRNG key when the
        # function actually touches jax.random — dict-style `key` params in
        # e.g. the statestore must not be tracked
        if not self._uses_jax_random(index, fn):
            return
        for arg in (list(fn.args.posonlyargs) + list(fn.args.args)
                    + list(fn.args.kwonlyargs)):
            if any(p in arg.arg.lower() for p in KEY_PARAM_HINTS):
                state.counts[arg.arg] = 0

    def _check_fn(self, index: ModuleIndex, fn) -> Iterable[Finding]:
        state = _KeyState()
        self._seed_params(index, fn, state)
        findings: List[Finding] = []
        self._walk_body(index, fn.body, state, findings)
        return findings

    # -- abstract interpretation over statements -------------------------
    def _walk_body(self, index: ModuleIndex, body: Sequence[ast.stmt],
                   state: _KeyState, findings: List[Finding]) -> None:
        for stmt in body:
            self._walk_stmt(index, stmt, state, findings)

    def _walk_stmt(self, index: ModuleIndex, stmt: ast.stmt,
                   state: _KeyState, findings: List[Finding]) -> None:
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
            # nested function: fresh scope seeded with key-ish params
            inner = _KeyState()
            self._seed_params(index, stmt, inner)
            self._walk_body(index, stmt.body, inner, findings)
            return
        if isinstance(stmt, ast.If):
            self._consume_in_expr(index, stmt.test, state, findings)
            b1, b2 = state.copy(), state.copy()
            self._walk_body(index, stmt.body, b1, findings)
            self._walk_body(index, stmt.orelse, b2, findings)
            state.counts = {}
            b1.merge(b2)
            state.counts = b1.counts
            return
        if isinstance(stmt, (ast.For, ast.AsyncFor, ast.While)):
            if isinstance(stmt, ast.While):
                self._consume_in_expr(index, stmt.test, state, findings)
            else:
                self._consume_in_expr(index, stmt.iter, state, findings)
            # run the body twice: a key consumed each iteration without a
            # rebinding shows up as reuse on the second pass (the engine
            # dedupes repeated findings on the same line)
            self._walk_body(index, stmt.body, state, findings)
            self._walk_body(index, stmt.body, state, findings)
            self._walk_body(index, stmt.orelse, state, findings)
            return
        if isinstance(stmt, (ast.Try,)):
            self._walk_body(index, stmt.body, state, findings)
            for h in stmt.handlers:
                self._walk_body(index, h.body, state.copy(), findings)
            self._walk_body(index, stmt.orelse, state, findings)
            self._walk_body(index, stmt.finalbody, state, findings)
            return
        if isinstance(stmt, ast.With):
            for item in stmt.items:
                self._consume_in_expr(index, item.context_expr, state,
                                      findings)
            self._walk_body(index, stmt.body, state, findings)
            return
        if isinstance(stmt, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
            value = stmt.value
            if value is not None:
                self._consume_in_expr(index, value, state, findings)
            targets = (stmt.targets if isinstance(stmt, ast.Assign)
                       else [stmt.target])
            produces = value is not None and self._produces_keys(
                index, value, state)
            for t in targets:
                for nm in _target_names(t):
                    # rebinding a key array invalidates its tracked slots
                    for slot in [s for s in state.counts
                                 if s.startswith(nm + "[")]:
                        del state.counts[slot]
                    if produces:
                        state.counts[nm] = 0       # fresh key(s)
                    elif nm in state.counts:
                        del state.counts[nm]       # rebound to a non-key
            return
        # everything else: just scan expressions for consumptions
        for node in ast.walk(stmt):
            if isinstance(node, ast.Call):
                self._consume_call(index, node, state, findings)

    def _produces_keys(self, index: ModuleIndex, value: ast.AST,
                       state: _KeyState) -> bool:
        if isinstance(value, ast.Call):
            return index.resolver.canonical(value.func) in KEY_PRODUCERS
        if isinstance(value, ast.Subscript):
            # `key = ks[3]` where ks is a tracked key array
            if isinstance(value.value, ast.Name) and \
                    value.value.id in state.counts:
                return True
            return self._produces_keys(index, value.value, state)
        if isinstance(value, ast.Name):
            return value.id in state.counts
        return False

    def _consume_in_expr(self, index: ModuleIndex, expr: ast.AST,
                         state: _KeyState, findings: List[Finding]) -> None:
        for node in ast.walk(expr):
            if isinstance(node, ast.Call):
                self._consume_call(index, node, state, findings)

    def _consume_call(self, index: ModuleIndex, call: ast.Call,
                      state: _KeyState, findings: List[Finding]) -> None:
        canon = index.resolver.canonical(call.func)
        is_random = canon is not None and canon.startswith("jax.random.")
        if is_random and canon in NON_CONSUMING:
            return
        if is_random:
            cands = call.args[:1] + [kw.value for kw in call.keywords
                                     if kw.arg == "key"]
        else:
            # handing a tracked key to ANY callable (an init helper, a
            # FailureContext, ...) transfers ownership — passing the same
            # key twice correlates whatever randomness both sides draw
            cands = list(call.args) + [kw.value for kw in call.keywords]
        for c in cands:
            name = self._key_var(c)
            if name is None:
                continue
            if name not in state.counts:
                # lazily track `ks[0]` slots of a tracked key array
                base = name.split("[")[0]
                if "[" in name and base in state.counts:
                    state.counts[name] = 0
                else:
                    continue
            state.counts[name] += 1
            if state.counts[name] > 1:
                findings.append(self.finding(
                    index, call,
                    f"PRNG key `{name}` consumed again without "
                    f"`jax.random.split`/`fold_in` — reused keys produce "
                    f"correlated draws"))

    @staticmethod
    def _key_var(node: ast.AST) -> Optional[str]:
        if isinstance(node, ast.Name):
            return node.id
        if isinstance(node, ast.Subscript) and isinstance(node.value,
                                                          ast.Name):
            sl = node.slice
            if isinstance(sl, ast.Constant):
                return f"{node.value.id}[{sl.value!r}]"
        return None


# ---------------------------------------------------------------------------
# tracer-branch
# ---------------------------------------------------------------------------

ARRAY_ROOTS = ("jax.numpy.", "jax.lax.", "jax.nn.", "jax.random.",
               "jax.scipy.")


@register_rule
class TracerBranch(Rule):
    id = "tracer-branch"
    doc = ("Python `if`/`while` on an array value inside traced code "
           "(concretization error, or one branch silently baked in)")

    def check(self, index: ModuleIndex,
              project: ProjectContext) -> Iterable[Finding]:
        res = index.resolver
        for fn in index.traced:
            arrayish: Set[str] = set()
            # forward pass in source order: collect array-valued locals
            for node in ast.walk(fn):
                if isinstance(node, ast.Assign) and self._is_arrayish(
                        res, node.value, arrayish):
                    for t in node.targets:
                        for nm in _target_names(t):
                            arrayish.add(nm)
            for node in ast.walk(fn):
                if isinstance(node, (ast.If, ast.While)):
                    hit = self._test_hits(res, node.test, arrayish)
                    if hit:
                        kind = "if" if isinstance(node, ast.If) else "while"
                        yield self.finding(
                            index, node,
                            f"`{kind}` on array value `{hit}` inside traced "
                            f"code; use jnp.where/lax.cond/lax.while_loop")

    def _is_arrayish(self, res, value: ast.AST, arrayish: Set[str]) -> bool:
        if isinstance(value, ast.Call):
            canon = res.canonical(value.func)
            return canon is not None and (
                canon.startswith(ARRAY_ROOTS) or canon == "jax.device_put")
        if isinstance(value, ast.BinOp):
            return (self._is_arrayish(res, value.left, arrayish)
                    or self._is_arrayish(res, value.right, arrayish))
        if isinstance(value, (ast.Subscript, ast.UnaryOp)):
            inner = (value.value if isinstance(value, ast.Subscript)
                     else value.operand)
            return self._is_arrayish(res, inner, arrayish)
        if isinstance(value, ast.Name):
            return value.id in arrayish
        if isinstance(value, ast.Compare):
            return self._is_arrayish(res, value.left, arrayish) or any(
                self._is_arrayish(res, c, arrayish)
                for c in value.comparators)
        return False

    def _test_hits(self, res, test: ast.AST,
                   arrayish: Set[str]) -> Optional[str]:
        skip: Set[ast.AST] = set()
        for node in ast.walk(test):
            if node in skip:
                skip.update(ast.walk(node))
                continue
            # `x is None` / `x is not None` inspect identity, not the
            # array's value — the optional-argument idiom is fine
            if isinstance(node, ast.Compare) and all(
                    isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops):
                for sub in ast.walk(node):
                    skip.add(sub)
                continue
            if isinstance(node, ast.Name) and node.id in arrayish:
                return node.id
            if isinstance(node, ast.Call):
                canon = res.canonical(node.func)
                if canon is not None and canon.startswith(ARRAY_ROOTS):
                    return canon
        return None


# ---------------------------------------------------------------------------
# donation-after-dispatch
# ---------------------------------------------------------------------------

# factories whose *result* is a callable donating (params, opt_state)
DONATING_FACTORIES = {
    "repro.core.trainer._jit_donated": (0, 1),
    "_jit_donated": (0, 1),
    "repro.core.trainer.make_train_step": (0, 1),
    "repro.core.trainer.make_fused_train_step": (0, 1),
    "repro.pipeline.spmd.make_spmd_fused_train_step": (0, 1),
    "make_train_step": (0, 1),
    "make_fused_train_step": (0, 1),
    "make_spmd_fused_train_step": (0, 1),
}


def _donate_argnums_of(call: ast.Call, res) -> Optional[Tuple[int, ...]]:
    """If ``call`` produces a donating callable, its donated argnums."""
    canon = res.canonical(call.func)
    if canon in DONATING_FACTORIES:
        return DONATING_FACTORIES[canon]
    if canon == "jax.jit":
        for kw in call.keywords:
            if kw.arg == "donate_argnums":
                v = kw.value
                if isinstance(v, ast.Constant) and isinstance(v.value, int):
                    return (v.value,)
                if isinstance(v, (ast.Tuple, ast.List)):
                    nums = tuple(el.value for el in v.elts
                                 if isinstance(el, ast.Constant)
                                 and isinstance(el.value, int))
                    return nums or None
    return None


@register_rule
class DonationAfterDispatch(Rule):
    id = "donation-after-dispatch"
    doc = ("a buffer passed in a donated slot is read again after the "
           "donating call (freed on donating backends)")

    def check(self, index: ModuleIndex,
              project: ProjectContext) -> Iterable[Finding]:
        res = index.resolver
        # donating callees visible in this module: local names bound to a
        # donating factory's result, attrs assigned likewise, decorated defs
        donating: Dict[str, Tuple[int, ...]] = {}
        for node in ast.walk(index.tree):
            if isinstance(node, ast.Assign) and isinstance(node.value,
                                                           ast.Call):
                nums = _donate_argnums_of(node.value, res)
                if nums:
                    for t in node.targets:
                        name = res.dotted(t)
                        if name:
                            donating[name.split(".")[-1]] = nums
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    canon = (res.canonical(dec.func)
                             if isinstance(dec, ast.Call)
                             else res.canonical(dec))
                    if canon in DONATING_FACTORIES:
                        donating[node.name] = DONATING_FACTORIES[canon]
                    elif isinstance(dec, ast.Call):
                        nums = _donate_argnums_of(dec, res)
                        if nums:
                            donating[node.name] = nums
        # the Trainer wires fused/train steps onto self.<attr>
        donating.setdefault("fused_step", (0, 1))
        for fname, fn in index.functions.items():
            if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                yield from self._check_fn(index, fn, donating)

    def _check_fn(self, index: ModuleIndex, fn,
                  donating: Dict[str, Tuple[int, ...]]) -> Iterable[Finding]:
        res = index.resolver
        findings: List[Finding] = []
        # live: donated dotted-name -> lineno of the donating call
        live: Dict[str, int] = {}

        def kill(target_name: Optional[str]) -> None:
            if not target_name:
                return
            for nm in list(live):
                if nm == target_name or nm.startswith(target_name + ".") \
                        or target_name.startswith(nm + "."):
                    del live[nm]

        def scan_reads(node: ast.AST, skip: Set[ast.AST]) -> None:
            for sub in ast.walk(node):
                if sub in skip:
                    continue
                if isinstance(sub, (ast.Name, ast.Attribute)) and \
                        isinstance(getattr(sub, "ctx", None), ast.Load):
                    nm = res.dotted(sub)
                    if nm is None:
                        continue
                    for donated, ln in live.items():
                        if nm == donated or nm.startswith(donated + "."):
                            findings.append(self.finding(
                                index, sub,
                                f"`{nm}` was donated at line {ln} and is "
                                f"read afterwards; donated buffers are "
                                f"freed on donating backends — thread the "
                                f"returned value instead"))
                            break

        def handle_stmt(stmt: ast.stmt) -> None:
            # donated reads anywhere in the statement (incl. its own call
            # args — reading an already-donated buffer to re-dispatch is
            # itself a violation)
            skip: Set[ast.AST] = set()
            calls = [n for n in ast.walk(stmt) if isinstance(n, ast.Call)]
            scan_reads(stmt, skip)
            for call in calls:
                callee = res.dotted(call.func)
                if callee is None:
                    continue
                leaf = callee.split(".")[-1]
                if leaf not in donating:
                    continue
                for i in donating[leaf]:
                    if i < len(call.args):
                        nm = res.dotted(call.args[i])
                        if nm:
                            live[nm] = call.lineno
            if isinstance(stmt, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
                targets = (stmt.targets if isinstance(stmt, ast.Assign)
                           else [stmt.target])
                for t in targets:
                    if isinstance(t, (ast.Tuple, ast.List)):
                        for el in t.elts:
                            kill(res.dotted(el))
                    else:
                        kill(res.dotted(t))

        def walk(body: Sequence[ast.stmt]) -> None:
            for stmt in body:
                if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    continue  # visited via index.functions
                if isinstance(stmt, ast.If):
                    handle_stmt_test(stmt.test)
                    saved = dict(live)
                    walk(stmt.body)
                    after_body = dict(live)
                    live.clear(); live.update(saved)
                    walk(stmt.orelse)
                    live.update(after_body)
                elif isinstance(stmt, (ast.For, ast.AsyncFor, ast.While)):
                    walk(stmt.body)
                    walk(stmt.body)   # second pass: catches next-iteration
                    walk(stmt.orelse)  # reads of a buffer donated in-loop
                elif isinstance(stmt, ast.Try):
                    walk(stmt.body)
                    for h in stmt.handlers:
                        walk(h.body)
                    walk(stmt.orelse)
                    walk(stmt.finalbody)
                elif isinstance(stmt, ast.With):
                    handle_stmt(stmt)
                    walk(stmt.body)
                else:
                    handle_stmt(stmt)

        def handle_stmt_test(test: ast.AST) -> None:
            scan_reads(test, set())

        walk(fn.body)
        seen: Set[int] = set()
        for f in findings:
            if f.line not in seen:
                seen.add(f.line)
                yield f


# ---------------------------------------------------------------------------
# pallas-contract
# ---------------------------------------------------------------------------

PALLAS_CALLS = {"jax.experimental.pallas.pallas_call"}
BLOCKSPEC = {"jax.experimental.pallas.BlockSpec"}
INTERPRET_ENV = "PALLAS_INTERPRET"


def _const_tuple_len(node: Optional[ast.AST],
                     local_consts: Dict[str, ast.AST]) -> Optional[int]:
    if isinstance(node, ast.Name) and node.id in local_consts:
        node = local_consts[node.id]
    if isinstance(node, (ast.Tuple, ast.List)):
        return len(node.elts)
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return 1  # grid=N is rank-1
    return None


@register_rule
class PallasContract(Rule):
    id = "pallas-contract"
    doc = ("BlockSpec rank vs index_map arity vs grid rank must agree; the "
           "interpret flag must not be read at import time")

    def check(self, index: ModuleIndex,
              project: ProjectContext) -> Iterable[Finding]:
        res = index.resolver
        # simple constant propagation: name -> last literal assigned in fn
        for node in ast.walk(index.tree):
            if isinstance(node, ast.Call) and \
                    res.canonical(node.func) in PALLAS_CALLS:
                yield from self._check_pallas_call(index, node)
        yield from self._check_import_time_interpret(index)

    def _local_consts(self, index: ModuleIndex,
                      call: ast.Call) -> Dict[str, ast.AST]:
        fn = index.enclosing_function(call)
        consts: Dict[str, ast.AST] = {}
        scope = fn if fn is not None else index.tree
        for node in ast.walk(scope):
            if isinstance(node, ast.Assign) and isinstance(
                    node.value, (ast.Tuple, ast.List, ast.Constant)):
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        consts[t.id] = node.value
        return consts

    def _check_pallas_call(self, index: ModuleIndex,
                           call: ast.Call) -> Iterable[Finding]:
        res = index.resolver
        consts = self._local_consts(index, call)
        kw = {k.arg: k.value for k in call.keywords}
        grid_rank = _const_tuple_len(kw.get("grid"), consts)
        specs: List[ast.Call] = []
        for key in ("in_specs", "out_specs"):
            v = kw.get(key)
            nodes = (v.elts if isinstance(v, (ast.Tuple, ast.List))
                     else [v] if v is not None else [])
            for n in nodes:
                if isinstance(n, ast.Call) and (
                        res.canonical(n.func) in BLOCKSPEC or
                        (res.canonical(n.func) or "").endswith(".BlockSpec")):
                    specs.append(n)
        for spec in specs:
            skw = {k.arg: k.value for k in spec.keywords}
            shape = skw.get("block_shape",
                            spec.args[0] if spec.args else None)
            imap = skw.get("index_map",
                           spec.args[1] if len(spec.args) > 1 else None)
            shape_rank = _const_tuple_len(shape, consts)
            if isinstance(imap, ast.Lambda):
                arity = len(imap.args.args)
                if grid_rank is not None and arity != grid_rank:
                    yield self.finding(
                        index, imap,
                        f"BlockSpec index_map takes {arity} args but the "
                        f"grid has rank {grid_rank}; each grid axis maps to "
                        f"one index_map argument")
                ret_len = (len(imap.body.elts)
                           if isinstance(imap.body, ast.Tuple) else 1)
                if shape_rank is not None and ret_len != shape_rank:
                    yield self.finding(
                        index, imap,
                        f"BlockSpec index_map returns {ret_len} indices but "
                        f"block_shape has rank {shape_rank}")
        interp = kw.get("interpret")
        if isinstance(interp, ast.Name) and \
                index.enclosing_function(call) is None:
            yield self.finding(
                index, interp,
                "pallas_call at module scope freezes `interpret` at import "
                "time; choose it at call time (kernels/ops.py pattern)")

    def _check_import_time_interpret(self, index: ModuleIndex,
                                     ) -> Iterable[Finding]:
        res = index.resolver
        for stmt in index.tree.body:          # module scope only
            for node in ast.walk(stmt):
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                     ast.Lambda, ast.ClassDef)):
                    break  # function/class bodies are call-time, not import
                if isinstance(node, ast.Call):
                    canon = res.canonical(node.func) or ""
                    if canon.split(".")[-1] == "interpret_default":
                        yield self.finding(
                            index, node,
                            "interpret flag read at import time; call "
                            "`interpret_default()` at dispatch so the "
                            "choice follows the platform the call runs on")
                    elif canon.startswith("os.environ") or canon in (
                            "os.getenv",):
                        if any(isinstance(a, ast.Constant)
                               and isinstance(a.value, str)
                               and INTERPRET_ENV in a.value
                               for a in node.args):
                            yield self.finding(
                                index, node,
                                "an interpret switch read from the "
                                "environment at import time; choose "
                                "interpret mode at call time instead")
                elif isinstance(node, ast.Subscript):
                    base = res.canonical(node.value) or ""
                    if base == "os.environ" and isinstance(
                            node.slice, ast.Constant) and isinstance(
                            node.slice.value, str) and \
                            INTERPRET_ENV in node.slice.value:
                        yield self.finding(
                            index, node,
                            "an interpret switch read from the "
                            "environment at import time; choose "
                            "interpret mode at call time instead")


# ---------------------------------------------------------------------------
# no-bare-print
# ---------------------------------------------------------------------------

@register_rule
class NoBarePrint(Rule):
    id = "no-bare-print"
    doc = ("bare print() in src/repro library code; route output through "
           "repro.telemetry.log (CLI output lines may suppress)")

    def check(self, index: ModuleIndex,
              project: ProjectContext) -> Iterable[Finding]:
        # library code only: the rule applies to files under a src/repro
        # directory pair (relative or absolute paths both resolve), which
        # leaves tests, benchmarks, examples, and fixtures free to print
        parts = os.path.normpath(os.path.abspath(index.path)).split(os.sep)
        if not any(a == "src" and b == "repro"
                   for a, b in zip(parts, parts[1:])):
            return
        for node in ast.walk(index.tree):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Name)
                    and node.func.id == "print"):
                yield self.finding(
                    index, node,
                    "bare print() in library code; use "
                    "repro.telemetry.log(...) (verbosity knob + mirrored "
                    "into the event stream), or mark deliberate CLI "
                    "output with `# repro: allow[no-bare-print]`")
