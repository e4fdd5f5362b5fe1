"""LINT-TPU-003 / LINT-TPU-005 — device-plane invariants under ops/ and tbls/.

LINT-TPU-003 (DeviceDtypeRule) — big ints must be encoded before
reaching the device. The crypto planes are int32 limb arrays; field
elements are 381-bit Python ints. Passing one (or a module constant like
`P_INT`) straight into `jnp.asarray`/`jnp.array` silently truncates or
raises at trace time — only `fq_from_int`/`limbs_from_int`/
`fq2_from_ints` make that safe. The rule flags int literals and
module-level int constants ≥ 2**31 entering a jax.numpy array
constructor outside one of the safe encoders. Module constants are
const-evaluated (including `<<`/`*`/`%`/`**` of other constants), so
derived values like `R_MONT = 1 << 384` are caught too. (The old second
invariant — host syncs inside `@jax.jit` bodies — moved to the
interprocedural LINT-TPU-017 TraceHazardRule in rules/jit.py, which
also sees through helper calls out of the decorated body.)

LINT-TPU-005 (PlaneStoreRoutingRule) — pubkey bytes route through the
PlaneStore. Compressed public-key sets are static per cluster; decoding
them per call (`g1_plane_from_compressed` / `_parse_compressed` straight
from a `pks`-like argument) re-pays the sqrt-scan decompress and subgroup
sweep that `ops.plane_store.STORE` exists to amortize. The rule flags
plane-builder calls whose first argument mentions a pubkey-hinted name,
except inside the store itself, inside the decode layer the store calls
(`g1_plane_from_compressed` and its device half), or inside a callback
handed to `STORE.host_entry`/`STORE.sharded_entry` (those ARE the
sanctioned routing).

LINT-TPU-007 (PipelineLockSyncRule) — no device sync while holding
`SigAggPipeline._lock`. The pipeline lock covers ONLY the host
pack+dispatch; a `jax.device_get(...)` or `jax.block_until_ready(...)`
(or method-form `.block_until_ready()`) lexically inside a
`with ..._lock:` body of a SigAggPipeline class would serialize every
concurrent submitter's pack behind one slot's device wait — exactly the
stall the three-stage pipeline exists to remove. Code inside nested
function definitions/lambdas is exempt (it runs later, off the lock —
the stage-3 executor scheduling shape).

LINT-TPU-008 (MeshTopologyRule) — device topology comes from the
`ops/mesh.py` seam. A bare `jax.devices()` / `jax.local_devices()` /
`jax.device_count()` / `jax.local_device_count()` anywhere else in
charon_tpu bypasses the `CHARON_TPU_SIGAGG_DEVICES` clamp and the cached
Mesh object (the sharded executable cache keys on mesh identity), so the
probing module and the sigagg plane can disagree about the machine. Scope
is the WHOLE package — not just ops/tbls — because batching knobs
(core/coalesce) and app assembly scale off the width too; `ops/mesh.py`
itself is the sanctioned probe and is exempt.

LINT-TPU-012 (NativePairingRoutingRule) — ctypes pairing/h2c stays behind
the guard seam. Slot verification runs on the device (plane_agg.
_pairing_finish → one batched h2c + multi-Miller-loop + final-exp
dispatch); the native `ct_pairing_check` / `ct_hash_to_g2` entry points
exist only as the guard's fallback rung and the h2c cache's miss path. A
new call site anywhere else in ops/ silently regresses verification to
serial host work — the exact ceiling the device path removed — and
bypasses the breaker accounting and the `ops_pairing_total{path}` split
that make such a regression visible. Sanctioned enclosing defs:
`guard.native_pairing_check` and `plane_agg._hash_to_g2_native` (the one
extracted miss path both cache accessors share). Other `ct_*` natives
(decompress bulk, g1 checks) are out of scope.
"""

from __future__ import annotations

import ast
from typing import Iterable

from ..engine import Finding, SourceFile

_SCOPE = ("ops", "tbls")
_INT32_MAX = 2 ** 31
_SAFE_ENCODERS = ("fq_from_int", "limbs_from_int", "fq2_from_ints",
                  "to_mont_int", "int_from_limbs",
                  # host transforms: the int is turned into a string/digit
                  # sequence on the host, it never reaches the array as a
                  # single numeric value
                  "bin", "hex", "oct", "str", "format", "len")
_ARRAY_CTORS = ("asarray", "array", "full")
_MAX_POW = 4096  # bound const-eval exponents; crypto consts stay below this


def _callee_name(func: ast.expr) -> str | None:
    if isinstance(func, ast.Attribute):
        return func.attr
    if isinstance(func, ast.Name):
        return func.id
    return None


def _aliases(tree: ast.Module) -> tuple[set[str], set[str], set[str]]:
    """(numpy aliases, jax.numpy aliases, jax aliases) in this module."""
    np_al: set[str] = set()
    jnp_al: set[str] = set()
    jax_al: set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                tgt = a.asname or a.name.split(".")[0]
                if a.name == "numpy":
                    np_al.add(tgt)
                elif a.name == "jax.numpy":
                    jnp_al.add(a.asname or "jax")
                elif a.name == "jax":
                    jax_al.add(tgt)
        elif isinstance(node, ast.ImportFrom):
            if node.module == "jax":
                for a in node.names:
                    if a.name == "numpy":
                        jnp_al.add(a.asname or "numpy")
    return np_al, jnp_al, jax_al


def _const_int(node: ast.expr, env: dict[str, int]) -> int | None:
    """Best-effort compile-time int evaluation over module constants."""
    if isinstance(node, ast.Constant):
        return node.value if isinstance(node.value, int) else None
    if isinstance(node, ast.Name):
        return env.get(node.id)
    if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
        v = _const_int(node.operand, env)
        return None if v is None else -v
    if isinstance(node, ast.BinOp):
        lhs = _const_int(node.left, env)
        rhs = _const_int(node.right, env)
        if lhs is None or rhs is None:
            return None
        try:
            if isinstance(node.op, ast.Add):
                return lhs + rhs
            if isinstance(node.op, ast.Sub):
                return lhs - rhs
            if isinstance(node.op, ast.Mult):
                return lhs * rhs
            if isinstance(node.op, ast.FloorDiv):
                return lhs // rhs
            if isinstance(node.op, ast.Mod):
                return lhs % rhs
            if isinstance(node.op, ast.LShift) and rhs <= _MAX_POW:
                return lhs << rhs
            if isinstance(node.op, ast.RShift):
                return lhs >> rhs
            if isinstance(node.op, ast.Pow) and rhs <= _MAX_POW:
                return lhs ** rhs
        except (ZeroDivisionError, ValueError, OverflowError):
            return None
    return None


def _module_consts(tree: ast.Module) -> dict[str, int]:
    env: dict[str, int] = {}
    for node in tree.body:
        if (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)):
            val = _const_int(node.value, env)
            if val is not None:
                env[node.targets[0].id] = val
    return env


def _is_jit_decorator(dec: ast.expr, jax_al: set[str]) -> bool:
    def is_jit_ref(e: ast.expr) -> bool:
        if isinstance(e, ast.Attribute):
            return e.attr == "jit" and isinstance(e.value, ast.Name) \
                and e.value.id in jax_al
        return isinstance(e, ast.Name) and e.id == "jit"

    if is_jit_ref(dec):
        return True
    if isinstance(dec, ast.Call):
        if is_jit_ref(dec.func):  # @jax.jit(static_argnums=...)
            return True
        if _callee_name(dec.func) == "partial" and dec.args \
                and is_jit_ref(dec.args[0]):
            return True
    return False


class DeviceDtypeRule:
    id = "LINT-TPU-003"
    description = ("big Python ints must pass through fq_from_int/"
                   "limbs_from_int before jnp arrays")

    def check(self, src: SourceFile) -> Iterable[Finding]:
        if not src.in_dir(*_SCOPE):
            return
        _np_al, jnp_al, _jax_al = _aliases(src.tree)
        env = _module_consts(src.tree)
        yield from self._check_big_ints(src, jnp_al, env)

    # -- invariant 1: big ints entering device arrays -----------------------

    def _check_big_ints(self, src: SourceFile, jnp_al: set[str],
                        env: dict[str, int]) -> Iterable[Finding]:
        for node in ast.walk(src.tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in _ARRAY_CTORS
                    and isinstance(node.func.value, ast.Name)
                    and node.func.value.id in jnp_al
                    and node.args):
                continue
            for offender in self._big_int_refs(node.args[0], env):
                label = (offender.id if isinstance(offender, ast.Name)
                         else "int literal")
                yield Finding(
                    src.rel, node.lineno, self.id,
                    f"`{label}` (≥ 2**31) flows into a jax.numpy array; "
                    "int32 limb planes overflow — encode via fq_from_int/"
                    "limbs_from_int first")

    def _big_int_refs(self, node: ast.expr,
                      env: dict[str, int]) -> Iterable[ast.expr]:
        """Int literals / const names ≥ 2**31 in `node`, skipping subtrees
        already wrapped in a safe encoder call."""
        if isinstance(node, ast.Call) \
                and _callee_name(node.func) in _SAFE_ENCODERS:
            return
        if isinstance(node, ast.Constant) and isinstance(node.value, int) \
                and abs(node.value) >= _INT32_MAX:
            yield node
        if isinstance(node, ast.Name) \
                and abs(env.get(node.id, 0)) >= _INT32_MAX:
            yield node
        for child in ast.iter_child_nodes(node):
            yield from self._big_int_refs(child, env)

    # The old invariant 2 (host syncs inside @jax.jit bodies) moved to the
    # interprocedural LINT-TPU-017 TraceHazardRule (rules/jit.py), which
    # also sees through helper calls out of the decorated body.


_PLANE_BUILDERS = ("g1_plane_from_compressed", "_parse_compressed")
_PK_HINTS = ("pk", "pubkey", "public_key")
# the decode layer the PlaneStore itself dispatches through — a pk-named
# argument HERE is the implementation of the sanctioned path, not a bypass
_SANCTIONED_DEFS = ("g1_plane_from_compressed", "_g1_plane_device")


class PlaneStoreRoutingRule:
    id = "LINT-TPU-005"
    description = ("compressed pubkey bytes must reach plane construction "
                   "through ops.plane_store.STORE (full_plane/chunk_planes/"
                   "host_entry/sharded_entry), not ad-hoc decompress calls")

    def check(self, src: SourceFile) -> Iterable[Finding]:
        if not src.in_dir(*_SCOPE):
            return
        if src.rel.split("/")[-1] == "plane_store.py":
            return  # the store IS the sanctioned decode entry
        cb_names = self._host_entry_callbacks(src.tree)
        for node in ast.walk(src.tree):
            if not (isinstance(node, ast.Call)
                    and _callee_name(node.func) in _PLANE_BUILDERS
                    and node.args):
                continue
            hint = self._pk_hint(node.args[0])
            if hint is None:
                continue
            encl = self._enclosing_defs(src, node)
            if any(n in _SANCTIONED_DEFS or n in cb_names for n in encl):
                continue
            yield Finding(
                src.rel, node.lineno, self.id,
                f"`{hint}` (compressed pubkey bytes) fed straight into "
                f"`{_callee_name(node.func)}` re-decodes a static set every "
                "call; route through plane_store.STORE (full_plane/"
                "chunk_planes/host_entry) so steady-state slots hit the "
                "device-resident cache")

    @staticmethod
    def _host_entry_callbacks(tree: ast.Module) -> set[str]:
        """Names of functions passed as arguments to `...host_entry(...)`
        or `...sharded_entry(...)` — those run exactly once per
        (digest, key) under the store's lock."""
        names: set[str] = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) \
                    and _callee_name(node.func) in ("host_entry",
                                                    "sharded_entry"):
                names.update(a.id for a in node.args
                             if isinstance(a, ast.Name))
        return names

    @staticmethod
    def _enclosing_defs(src: SourceFile, node: ast.AST) -> list[str]:
        out: list[str] = []
        cur = src.parent(node)
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                out.append(cur.name)
            cur = src.parent(cur)
        return out

    @staticmethod
    def _pk_hint(node: ast.expr) -> str | None:
        for sub in ast.walk(node):
            name = None
            if isinstance(sub, ast.Name):
                name = sub.id
            elif isinstance(sub, ast.Attribute):
                name = sub.attr
            if name and any(h in name.lower() for h in _PK_HINTS):
                return name
        return None


_PIPELINE_CLASS = "SigAggPipeline"
_DEVICE_SYNCS = ("device_get", "block_until_ready")


def _walk_same_frame(node: ast.AST) -> Iterable[ast.AST]:
    """ast.walk that does NOT descend into nested function definitions or
    lambdas — their bodies run later, off the current lock."""
    for child in ast.iter_child_nodes(node):
        if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda)):
            continue
        yield child
        yield from _walk_same_frame(child)


class PipelineLockSyncRule:
    id = "LINT-TPU-007"
    description = ("no jax.device_get/jax.block_until_ready while holding "
                   "SigAggPipeline._lock — the lock covers host "
                   "pack+dispatch only; device waits run outside it")

    def check(self, src: SourceFile) -> Iterable[Finding]:
        if not src.in_dir(*_SCOPE):
            return
        _np_al, _jnp_al, jax_al = _aliases(src.tree)
        for cls in ast.walk(src.tree):
            if not (isinstance(cls, ast.ClassDef)
                    and cls.name == _PIPELINE_CLASS):
                continue
            for w in ast.walk(cls):
                if not isinstance(w, ast.With):
                    continue
                if not any(self._is_lock_expr(i.context_expr)
                           for i in w.items):
                    continue
                yield from self._sync_calls(src, w, jax_al)

    @staticmethod
    def _is_lock_expr(expr: ast.expr) -> bool:
        for sub in ast.walk(expr):
            name = None
            if isinstance(sub, ast.Attribute):
                name = sub.attr
            elif isinstance(sub, ast.Name):
                name = sub.id
            if name is not None and name.endswith("_lock"):
                return True
        return False

    def _sync_calls(self, src: SourceFile, with_node: ast.With,
                    jax_al: set[str]) -> Iterable[Finding]:
        for stmt in with_node.body:
            for sub in [stmt, *_walk_same_frame(stmt)]:
                if not (isinstance(sub, ast.Call)
                        and isinstance(sub.func, ast.Attribute)):
                    continue
                attr = sub.func.attr
                if attr not in _DEVICE_SYNCS:
                    continue
                is_jax_mod = (isinstance(sub.func.value, ast.Name)
                              and sub.func.value.id in jax_al)
                # jax.device_get/jax.block_until_ready, or the method form
                # x.block_until_ready() on any array handle
                if not is_jax_mod and attr != "block_until_ready":
                    continue
                callee = (f"jax.{attr}" if is_jax_mod else f".{attr}")
                yield Finding(
                    src.rel, sub.lineno, self.id,
                    f"`{callee}(...)` while holding {_PIPELINE_CLASS}._lock "
                    "serializes every concurrent submit's pack behind this "
                    "slot's device wait; fence/readback must run after the "
                    "lock is released (the stage-2→3 seam)")


_TOPOLOGY_PROBES = ("devices", "local_devices", "device_count",
                    "local_device_count", "process_index", "process_count")
# jax.distributed.<attr> calls that establish or probe the multi-process
# runtime — sanctioned only inside ops/mesh.py (the one init/epoch owner)
_DISTRIBUTED_CALLS = ("initialize", "shutdown")


class MeshTopologyRule:
    id = "LINT-TPU-008"
    description = ("device/process topology must come from ops.mesh "
                   "(sigagg_mesh/device_count/host_count) — bare "
                   "jax.devices()/jax.process_index()/"
                   "jax.distributed.initialize() bypasses the "
                   "CHARON_TPU_SIGAGG_DEVICES clamp, the cached mesh, and "
                   "the multi-host membership epoch")

    def check(self, src: SourceFile) -> Iterable[Finding]:
        # whole-package scope; ops/mesh.py IS the sanctioned probe
        if src.rel.split("/")[-1] == "mesh.py" and src.in_dir("ops"):
            return
        _np_al, _jnp_al, jax_al = _aliases(src.tree)
        if not jax_al:
            return
        for node in ast.walk(src.tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)):
                continue
            fn = node.func
            if (fn.attr in _TOPOLOGY_PROBES
                    and isinstance(fn.value, ast.Name)
                    and fn.value.id in jax_al):
                yield Finding(
                    src.rel, node.lineno, self.id,
                    f"`jax.{fn.attr}()` probes device/process topology "
                    "directly; route through ops.mesh (sigagg_mesh/"
                    "device_count/host_count) so the "
                    "CHARON_TPU_SIGAGG_DEVICES clamp applies and every slot "
                    "shares the one cached Mesh")
                continue
            # jax.distributed.initialize()/shutdown(): only ops/mesh.py may
            # manage the multi-process runtime — a second initialize site
            # races the coordinator handshake and skips the membership epoch
            if (fn.attr in _DISTRIBUTED_CALLS
                    and isinstance(fn.value, ast.Attribute)
                    and fn.value.attr == "distributed"
                    and isinstance(fn.value.value, ast.Name)
                    and fn.value.value.id in jax_al):
                yield Finding(
                    src.rel, node.lineno, self.id,
                    f"`jax.distributed.{fn.attr}()` manages the "
                    "multi-process runtime outside ops/mesh.py; route "
                    "through ops.mesh (configure_distributed/invalidate) so "
                    "initialization is idempotent and membership epochs "
                    "stay coherent")


_NATIVE_PAIRING_CALLS = ("ct_pairing_check", "ct_hash_to_g2")
# the ONLY defs allowed to touch the native pairing/h2c entry points: the
# guard ladder's native rung and the h2c cache's shared miss path
_PAIRING_SANCTIONED_DEFS = ("native_pairing_check", "_hash_to_g2_native")


class NativePairingRoutingRule:
    id = "LINT-TPU-012"
    description = ("ctypes ct_pairing_check/ct_hash_to_g2 in ops/ are only "
                   "sanctioned inside guard.native_pairing_check and the "
                   "h2c cache miss path (_hash_to_g2_native) — anywhere "
                   "else silently regresses verification to serial host")

    def check(self, src: SourceFile) -> Iterable[Finding]:
        if not src.in_dir("ops"):
            return
        for node in ast.walk(src.tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in _NATIVE_PAIRING_CALLS):
                continue
            encl = PlaneStoreRoutingRule._enclosing_defs(src, node)
            if any(n in _PAIRING_SANCTIONED_DEFS for n in encl):
                continue
            yield Finding(
                src.rel, node.lineno, self.id,
                f"`{node.func.attr}` outside the sanctioned native rung "
                "(guard.native_pairing_check / plane_agg._hash_to_g2_native)"
                " silently bypasses the device verify path; route through "
                "plane_agg._pairing_finish so the guard ladder and the "
                "ops_pairing_total path split see the work")


# the Pallas field entry points: any new Mosaic field kernel wrapper that
# replaces an XLA-scan field op belongs in this tuple
_FIELD_PLANE_CALLS = ("mont_mul_rows",)
# the ONLY def allowed to call them: the curve._mont_mul routing seam, which
# reads CHARON_TPU_FIELD_PLANE and keeps the XLA/Pallas planes bit-identical
_FIELD_PLANE_SANCTIONED_DEFS = ("_mont_mul",)


class FieldPlaneRoutingRule:
    id = "LINT-TPU-016"
    description = ("Pallas field entry points (pallas_plane.mont_mul_rows) "
                   "in ops/ are only sanctioned inside the curve._mont_mul "
                   "seam — a fresh call site forks the field plane past the "
                   "CHARON_TPU_FIELD_PLANE switch and the bit-identity "
                   "oracle")

    def check(self, src: SourceFile) -> Iterable[Finding]:
        # pallas_plane.py itself defines the entry points (and their own
        # internal helpers); the seam contract binds its CONSUMERS
        if not src.in_dir("ops") or src.rel.endswith("pallas_plane.py"):
            return
        for node in ast.walk(src.tree):
            if not isinstance(node, ast.Call):
                continue
            if isinstance(node.func, ast.Attribute):
                callee = node.func.attr
            elif isinstance(node.func, ast.Name):
                callee = node.func.id
            else:
                continue
            if callee not in _FIELD_PLANE_CALLS:
                continue
            encl = PlaneStoreRoutingRule._enclosing_defs(src, node)
            if any(n in _FIELD_PLANE_SANCTIONED_DEFS for n in encl):
                continue
            yield Finding(
                src.rel, node.lineno, self.id,
                f"`{callee}` outside the curve._mont_mul seam forks the "
                "field plane: the call ignores CHARON_TPU_FIELD_PLANE, "
                "escapes the XLA-vs-Pallas bit-identity oracle, and can't "
                "be A/B'd by flipping the plane; route the product "
                "through ops.curve._mont_mul (or _fq_mul_many)")


# The slot-shaping knob env vars — mirrors the ENV_* block in
# ops/policy.py (the knob list is the contract between the two files).
_KNOB_ENV_NAMES = frozenset({
    "CHARON_TPU_PIPELINE_DEPTH",
    "CHARON_TPU_FINISH_WORKERS",
    "CHARON_TPU_SIGAGG_DEVICES",
    "CHARON_TPU_DEVICE_VERIFY",
    "CHARON_TPU_FIELD_PLANE",
    "CHARON_TPU_H2C_CACHE_CAP",
    "CHARON_TPU_BREAKER_THRESHOLD",
    "CHARON_TPU_BREAKER_COOLDOWN_S",
    "CHARON_TPU_SLOT_DEADLINE_S",
})
# Exported constant names that carry a knob env name across modules
# (policy's canonical ENV_* plus the compatibility re-exports in
# ops/mesh and ops/guard) — `os.environ.get(guard.SLOT_DEADLINE_ENV)`
# is the same bypass as spelling the string out.
_KNOB_ENV_CONSTS = {
    "ENV_PIPELINE_DEPTH": "CHARON_TPU_PIPELINE_DEPTH",
    "ENV_FINISH_WORKERS": "CHARON_TPU_FINISH_WORKERS",
    "ENV_SIGAGG_DEVICES": "CHARON_TPU_SIGAGG_DEVICES",
    "ENV_DEVICE_VERIFY": "CHARON_TPU_DEVICE_VERIFY",
    "ENV_FIELD_PLANE": "CHARON_TPU_FIELD_PLANE",
    "ENV_H2C_CACHE_CAP": "CHARON_TPU_H2C_CACHE_CAP",
    "ENV_BREAKER_THRESHOLD": "CHARON_TPU_BREAKER_THRESHOLD",
    "ENV_BREAKER_COOLDOWN": "CHARON_TPU_BREAKER_COOLDOWN_S",
    "ENV_SLOT_DEADLINE": "CHARON_TPU_SLOT_DEADLINE_S",
    "DEVICES_ENV": "CHARON_TPU_SIGAGG_DEVICES",
    "BREAKER_THRESHOLD_ENV": "CHARON_TPU_BREAKER_THRESHOLD",
    "BREAKER_COOLDOWN_ENV": "CHARON_TPU_BREAKER_COOLDOWN_S",
    "SLOT_DEADLINE_ENV": "CHARON_TPU_SLOT_DEADLINE_S",
}


class KnobEnvReadRule:
    id = "LINT-TPU-023"
    description = ("slot-shaping knob env vars are read ONLY by the policy "
                   "seam (ops/policy.py accessors, app/config.py parsing) — "
                   "an os.environ read elsewhere sees the process-start "
                   "value and silently ignores the installed SlotPolicy "
                   "snapshot the autotuner is steering")

    @staticmethod
    def _sanctioned(src: SourceFile) -> bool:
        base = src.rel.split("/")[-1]
        return ((base == "policy.py" and src.in_dir("ops"))
                or (base == "config.py" and src.in_dir("app")))

    @staticmethod
    def _module_consts(tree: ast.Module) -> dict[str, str]:
        """Module-level `NAME = <knob env>` string constants, resolving one
        level of indirection through literals, knob-carrying attribute
        re-exports, and already-resolved local names."""
        env: dict[str, str] = {}
        for stmt in tree.body:
            if not (isinstance(stmt, ast.Assign)
                    and all(isinstance(t, ast.Name) for t in stmt.targets)):
                continue
            val = stmt.value
            name: str | None = None
            if (isinstance(val, ast.Constant) and isinstance(val.value, str)
                    and val.value in _KNOB_ENV_NAMES):
                name = val.value
            elif (isinstance(val, ast.Attribute)
                    and val.attr in _KNOB_ENV_CONSTS):
                name = _KNOB_ENV_CONSTS[val.attr]
            elif isinstance(val, ast.Name) and val.id in env:
                name = env[val.id]
            if name is not None:
                for tgt in stmt.targets:
                    env[tgt.id] = name  # type: ignore[union-attr]
        return env

    @staticmethod
    def _knob_name(node: ast.expr, consts: dict[str, str]) -> str | None:
        """The knob env name `node` denotes, or None."""
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            return node.value if node.value in _KNOB_ENV_NAMES else None
        if isinstance(node, ast.Name):
            return consts.get(node.id) or _KNOB_ENV_CONSTS.get(node.id)
        if isinstance(node, ast.Attribute):
            return _KNOB_ENV_CONSTS.get(node.attr)
        return None

    @staticmethod
    def _is_environ(node: ast.expr) -> bool:
        return ((isinstance(node, ast.Name) and node.id == "environ")
                or (isinstance(node, ast.Attribute)
                    and node.attr == "environ"))

    def check(self, src: SourceFile) -> Iterable[Finding]:
        # whole-package scope; the seam and the config parser are the two
        # sanctioned readers. Env WRITES (mesh.set_override, guard.
        # configure) stay legal everywhere — they feed the initial-value
        # layer the accessors then resolve.
        if self._sanctioned(src):
            return
        consts = self._module_consts(src.tree)
        for node in ast.walk(src.tree):
            knob: str | None = None
            if isinstance(node, ast.Call):
                func = node.func
                is_get = (isinstance(func, ast.Attribute)
                          and func.attr == "get"
                          and self._is_environ(func.value))
                is_getenv = ((isinstance(func, ast.Attribute)
                              and func.attr == "getenv")
                             or (isinstance(func, ast.Name)
                                 and func.id == "getenv"))
                if (is_get or is_getenv) and node.args:
                    knob = self._knob_name(node.args[0], consts)
            elif (isinstance(node, ast.Subscript)
                    and isinstance(node.ctx, ast.Load)
                    and self._is_environ(node.value)):
                knob = self._knob_name(node.slice, consts)
            if knob is None:
                continue
            yield Finding(
                src.rel, node.lineno, self.id,
                f"env read of slot-shaping knob `{knob}` bypasses the "
                "SlotPolicy seam; call the matching ops.policy accessor "
                "(policy resolves installed snapshot -> env -> default, so "
                "tuner moves and test monkeypatching both keep working)")
