"""Engine 2: repo-specific AST lint rules over the package source.

Not a general Python linter — every rule encodes a JAX hot-path or
deployment invariant this codebase has already paid for once:

- GC101  ``jax.jit`` in ``train/``/``models/`` without ``donate_argnums``
         or ``out_shardings``: an undonated jit of params-sized state
         doubles its HBM footprint, and missing out_shardings lets GSPMD
         choose layouts the budgets never audited.
- GC102  host-sync calls (``.item()``, ``float()``, ``np.asarray``,
         ``jax.device_get``) inside the timed ``for step`` loop in
         ``train/loop.py``: each one fences the device per step and
         corrupts the published step timing (the loop's whole design is
         sync-window batching — see its timing-discipline note).
- GC103  ``with_sharding_constraint`` specs naming mesh axes that no mesh
         in the package defines: GSPMD treats an unknown axis name as
         simply unconstrained, so the typo'd constraint silently no-ops.
- GC104  ``time.time()`` in jit-adjacent modules (``train/``, ``models/``,
         ``ops/``, ``parallel/``): under trace it constant-folds to the
         trace-time clock; host-side timing uses ``time.perf_counter``.
- GC105  telemetry/file-IO/print calls inside the timed ``for step`` loop
         of ``train/loop.py`` that are not fenced at a ``sync_window``
         boundary: the flight recorder (telemetry/) writes JSONL and
         heartbeats, and the ONLY sanctioned cadence is the sync-window
         boundary — unfenced host IO mid-window lands inside the very
         step times the loop publishes.
- GC106  signal-handler installation or blocking file IO (fsync-class)
         inside the timed ``for step`` loop of ``train/loop.py``: the
         SIGTERM preemption handler must be installed OUTSIDE the loop
         (a handler interrupting arbitrary bytecode mid-commit is how
         torn state happens), and fsync/fdatasync block the host thread
         for device-unrelated milliseconds inside published step times.
- GC107  dtype-less ``jnp.asarray``/``jnp.array``/constant constructors
         (``jnp.ones``/``jnp.zeros``/``jnp.empty``/``jnp.full``) inside
         jitted model code (``models/``, ``train/step.py``): the default
         dtype is float32, and one f32 constant silently promotes the
         surrounding bf16 arithmetic — exactly the bf16->f32 convert
         chains the HLO auditor budgets (``bf16_to_f32_converts``).
- GC108  collective/axis-query calls (``psum``/``ppermute``/
         ``all_gather``/...) inside a ``shard_map`` body naming a literal
         axis outside the site's fully-literal ``axis_names`` set: the
         bad axis only raises at trace time, deep inside a jit. Sites
         whose axis set is not fully static are skipped, never guessed.
- GC111  blocking file IO (``open``/``.read()``/``.seek()``-class),
         host-iterator ``next()`` pulls, or ``time.sleep`` inside a
         timed ``for step`` loop in ``data/`` or ``train/`` with no
         sync_window fence earlier in the block and outside the
         prefetch fence: the streaming data path's ONE sanctioned
         blocking pull is the prefetcher's ``get()`` (receiver named
         ``*prefetch*``) — any other host read inside the loop
         serializes input IO into the very step times the loop
         publishes (the regression ``data_stall_frac`` exists to
         measure, not to hide).
- GC109  ``with_sharding_constraint``/``device_put``/host-sync calls
         inside a per-microbatch Python loop (``for _ in range(...)``)
         in ``parallel/``: the pipeline tick loops unroll at trace time,
         so one such call becomes M per-microbatch reshards (or M device
         fences) in the compiled step — the per-microbatch reshard
         hazard the schedule auditor's growth laws exist to catch.
- GC201  entrypoint<->harness flag-surface drift (PR 1's detector, now a
         registry rule): every ``train/harness.py`` flag must be reachable
         from the container env in ``docker/entrypoint.sh`` and vice versa.

Suppression: append ``# graftcheck: disable=GC101`` (comma-separated ids,
or ``all``) on the offending line or the line above it.
"""

from __future__ import annotations

import ast
import dataclasses
import os
import re
from typing import Callable, Dict, Iterator, List, Optional, Tuple

from .hlo_audit import REPO_ROOT

PACKAGE = "distributed_llm_training_benchmark_framework_tpu"

#: Harness flags deliberately NOT reachable from the container env, with the
#: reason each is exempt from GC201 (moved here from the PR 1 ad-hoc test so
#: there is exactly one registry):
#:   --deepspeed-config  alias of --strategy-config, which the entrypoint
#:   --fsdp-config       already sets for the ZeRO arms
ENTRYPOINT_EXEMPT_FLAGS = frozenset({"--deepspeed-config", "--fsdp-config"})

#: Flags the entrypoint passes to scripts/with_retries.sh (the retry
#: wrapper it execs in retry mode) — wrapper surface, not harness surface,
#: so they are neither "stale" nor expected in build_parser().
ENTRYPOINT_WRAPPER_FLAGS = frozenset(
    {"--drop-on-retry", "--resume-flag"}
)


@dataclasses.dataclass(frozen=True)
class Rule:
    id: str
    name: str
    description: str
    fix_hint: str


@dataclasses.dataclass(frozen=True)
class Violation:
    rule_id: str
    path: str  # repo-relative
    line: int
    message: str
    fix_hint: str

    def __str__(self) -> str:
        return (
            f"{self.path}:{self.line}: {self.rule_id} {self.message}\n"
            f"    fix: {self.fix_hint}"
        )


RULES: Dict[str, Rule] = {}
_CHECKS: List[Tuple[Rule, Callable]] = []


def _rule(id: str, name: str, description: str, fix_hint: str):
    def register(fn):
        rule = Rule(id=id, name=name, description=description, fix_hint=fix_hint)
        RULES[id] = rule
        _CHECKS.append((rule, fn))
        return fn

    return register


# ---------------------------------------------------------------------------
# Shared source helpers
# ---------------------------------------------------------------------------


class _Tree:
    def __init__(self, path: str, rel: str):
        with open(path) as f:
            self.source = f.read()
        self.rel = rel
        self.lines = self.source.splitlines()
        self.ast = ast.parse(self.source, filename=rel)


def _package_files(root: str, subdirs: Tuple[str, ...]) -> Iterator[_Tree]:
    for sub in subdirs:
        base = os.path.join(root, PACKAGE, sub)
        if not os.path.isdir(base):
            continue
        for dirpath, _dirnames, filenames in os.walk(base):
            for fn in sorted(filenames):
                if not fn.endswith(".py"):
                    continue
                path = os.path.join(dirpath, fn)
                yield _Tree(path, os.path.relpath(path, root))


def _dotted(node: ast.AST) -> Optional[str]:
    """'jax.jit' for Attribute/Name chains, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


_SUPPRESS = re.compile(r"#\s*graftcheck:\s*disable=([A-Za-z0-9_,\s]+)")


def _timed_loops(tree_ast: ast.AST) -> Iterator[ast.For]:
    """Every `for step in ...` loop — the timed-loop shape GC102/105/106
    police in train/loop.py."""
    for n in ast.walk(tree_ast):
        if (
            isinstance(n, ast.For)
            and isinstance(n.target, ast.Name)
            and n.target.id == "step"
        ):
            yield n


def _contains_sync(node: ast.AST) -> bool:
    for n in ast.walk(node):
        if isinstance(n, ast.Call) and _dotted(n.func) in (
            "sync_window", "self.sync_window"
        ):
            return True
    return False


def _stmt_calls(stmt: ast.AST) -> Iterator[ast.Call]:
    """Calls directly in ``stmt``, excluding nested function defs
    (sync_window-style boundary helpers are the sanctioned fenced
    context themselves)."""
    stack = [stmt]
    while stack:
        n = stack.pop()
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if isinstance(n, ast.Call):
            yield n
        stack.extend(ast.iter_child_nodes(n))


def _iter_timed_loop_calls(tree: "_Tree") -> Iterator[Tuple[ast.Call, bool]]:
    """(call, fenced) for every call inside the file's timed loops.

    The ONE fence walk GC105 and GC106 share (a fix to its semantics must
    never be applied twice): statement-ordered traversal where a
    statement whose subtree calls ``sync_window`` fences everything AFTER
    it in the same block (and in blocks nested under those later
    statements); compound statements pass the current flag down to their
    bodies, and their test/iter/with-item expressions are scanned
    directly (``with open(...)`` is IO too). Conservative in the right
    direction: a fence from a previous loop iteration never carries over.
    Rules decide what the flag means — GC105 ignores fenced calls
    entirely, GC106 flags signal installs through fences.
    """

    def walk_block(stmts, fenced: bool):
        for stmt in stmts:
            if isinstance(stmt, (ast.If, ast.With, ast.Try, ast.For,
                                 ast.While)):
                for field in ("body", "orelse", "finalbody"):
                    sub = getattr(stmt, field, None)
                    if sub:
                        yield from walk_block(sub, fenced)
                for handler in getattr(stmt, "handlers", []):
                    yield from walk_block(handler.body, fenced)
                scan_nodes = [getattr(stmt, "test", None),
                              getattr(stmt, "iter", None)]
                scan_nodes += [
                    item.context_expr for item in getattr(stmt, "items", [])
                ]
                calls = [
                    c for n in scan_nodes if n is not None
                    for c in _stmt_calls(n)
                ]
            else:
                calls = list(_stmt_calls(stmt))
            for call in calls:
                yield call, fenced
            if _contains_sync(stmt):
                fenced = True

    for loop in _timed_loops(tree.ast):
        yield from walk_block(loop.body, False)


def _suppressed(tree: _Tree, line: int, rule_id: str) -> bool:
    for ln in (line, line - 1):
        if 1 <= ln <= len(tree.lines):
            m = _SUPPRESS.search(tree.lines[ln - 1])
            if m:
                ids = {t.strip() for t in m.group(1).split(",")}
                if rule_id in ids or "all" in ids:
                    return True
    return False


# ---------------------------------------------------------------------------
# GC101: jit donation / out_shardings discipline
# ---------------------------------------------------------------------------


@_rule(
    "GC101",
    "jit-missing-donation-or-out-shardings",
    "jax.jit in train/ or models/ without donate_argnums/donate_argnames "
    "or out_shardings",
    "pass donate_argnums= (state the jit updates in place) or out_shardings= "
    "(pin the layout the budgets audit); suppress deliberate diagnostics "
    "with '# graftcheck: disable=GC101'",
)
def _check_jit_discipline(root: str) -> Iterator[Violation]:
    ok_kwargs = {"donate_argnums", "donate_argnames", "out_shardings"}
    for tree in _package_files(root, ("train", "models")):
        for node in ast.walk(tree.ast):
            if not (
                isinstance(node, ast.Call)
                and _dotted(node.func) in ("jax.jit", "jit")
            ):
                continue
            if any(kw.arg in ok_kwargs for kw in node.keywords):
                continue
            if _suppressed(tree, node.lineno, "GC101"):
                continue
            yield Violation(
                "GC101", tree.rel, node.lineno,
                "jax.jit call carries neither donate_argnums/donate_argnames "
                "nor out_shardings",
                RULES["GC101"].fix_hint,
            )


# ---------------------------------------------------------------------------
# GC102: host syncs inside the timed loop
# ---------------------------------------------------------------------------

@_rule(
    "GC102",
    "host-sync-in-timed-loop",
    "host-synchronizing call inside the timed `for step` loop of "
    "train/loop.py",
    "move the sync to a sync_window boundary (the loop already batches "
    "syncs every --sync-every steps); never fetch per-step values mid-window",
)
def _check_timed_loop_syncs(root: str) -> Iterator[Violation]:
    path = os.path.join(root, PACKAGE, "train", "loop.py")
    if not os.path.exists(path):
        return
    tree = _Tree(path, os.path.relpath(path, root))

    def body_calls(for_node):
        # Lexical scope only (no fence concept: a host sync is hostile at
        # ANY cadence inside the loop body — fenced syncs live INSIDE the
        # sync_window helper, which _stmt_calls excludes as a nested def).
        for stmt in for_node.body:
            yield from _stmt_calls(stmt)

    for loop in _timed_loops(tree.ast):
        for call in body_calls(loop):
            name = _dotted(call.func)
            kind = None
            if name in ("float", "int") and call.args:
                kind = ".item()-class host sync"
            elif name in ("np.asarray", "numpy.asarray", "np.array",
                          "jax.device_get"):
                kind = "device->host transfer"
            elif (
                isinstance(call.func, ast.Attribute)
                and call.func.attr == "item"
            ):
                kind = ".item() host sync"
            if kind and not _suppressed(tree, call.lineno, "GC102"):
                yield Violation(
                    "GC102", tree.rel, call.lineno,
                    f"{name or call.func.attr}(...) is a {kind} inside the "
                    "timed step loop",
                    RULES["GC102"].fix_hint,
                )


# ---------------------------------------------------------------------------
# GC105: unfenced telemetry / file IO / prints in the timed loop
# ---------------------------------------------------------------------------


def _is_telemetry_io_call(call: ast.Call) -> Optional[str]:
    """Classify a call as loop-hostile IO, or None.

    Targets: ``print``/``open``/``os.write``/``json.dump``, any
    ``*.write()``/``.writelines()``/``.flush()`` method, and any call on a
    receiver whose name mentions ``recorder``/``telemetry`` (the flight
    recorder's surface). Device work and pure bookkeeping stay out of
    scope — the rule polices host IO cadence, not computation.
    """
    name = _dotted(call.func)
    if name in ("print", "open", "os.write", "json.dump", "json.dumps"):
        # json.dumps is not IO itself, but in the timed loop it only ever
        # exists to feed a write — flag the serialization too.
        return f"{name}() host IO"
    if isinstance(call.func, ast.Attribute):
        if call.func.attr in ("write", "writelines", "flush"):
            return f".{call.func.attr}() file IO"
        recv = _dotted(call.func.value) or ""
        if "recorder" in recv.lower() or "telemetry" in recv.lower():
            return f"telemetry call {recv}.{call.func.attr}()"
    return None


@_rule(
    "GC105",
    "unfenced-telemetry-io-in-timed-loop",
    "telemetry/file-IO/print call inside the timed `for step` loop of "
    "train/loop.py with no sync_window fence earlier in its block — host "
    "IO mid-window skews the very step times the loop publishes",
    "emit telemetry from inside sync_window (the sanctioned boundary), or "
    "place the call after a sync_window(...) fence in the same block; "
    "suppress deliberate exceptions with '# graftcheck: disable=GC105'",
)
def _check_timed_loop_telemetry_io(root: str) -> Iterator[Violation]:
    path = os.path.join(root, PACKAGE, "train", "loop.py")
    if not os.path.exists(path):
        return
    tree = _Tree(path, os.path.relpath(path, root))
    for call, fenced in _iter_timed_loop_calls(tree):
        if fenced:
            continue
        kind = _is_telemetry_io_call(call)
        if kind and not _suppressed(tree, call.lineno, "GC105"):
            yield Violation(
                "GC105", tree.rel, call.lineno,
                f"{kind} inside the timed step loop with no "
                "sync_window fence earlier in its block",
                RULES["GC105"].fix_hint,
            )


# ---------------------------------------------------------------------------
# GC106: signal handlers / blocking file IO in the timed loop
# ---------------------------------------------------------------------------

#: Handler-installation calls: flagged ANYWHERE inside the timed loop,
#: fenced or not — a handler swap has no business at any step cadence
#: (install once, outside; faults/preemption.py is the sanctioned home).
_SIGNAL_CALLS = frozenset({
    "signal.signal", "signal.setitimer", "signal.siginterrupt",
    "signal.pthread_sigmask", "signal.sigwait", "signal.sigtimedwait",
})
#: Blocking file IO: flagged unless fenced by a sync_window earlier in
#: the block (same fence rule as GC105's telemetry IO).
_BLOCKING_IO_CALLS = frozenset({
    "os.fsync", "os.fdatasync", "os.sync",
    "shutil.copy", "shutil.copy2", "shutil.copytree", "shutil.move",
})


@_rule(
    "GC106",
    "signal-handler-or-blocking-io-in-timed-loop",
    "signal-handler installation (anywhere) or unfenced blocking file IO "
    "(fsync-class) inside the timed `for step` loop of train/loop.py — "
    "the SIGTERM handler must live outside the loop (faults.PreemptionGuard "
    "installs it before the first dispatch), and fsync blocks the host "
    "thread inside published step times",
    "install signal handlers once, before the loop (faults/preemption.py); "
    "move fsync-class IO behind a sync_window fence (runtime/checkpoint.py "
    "owns durable writes at checkpoint boundaries); suppress deliberate "
    "exceptions with '# graftcheck: disable=GC106'",
)
def _check_timed_loop_signal_and_blocking_io(root: str) -> Iterator[Violation]:
    path = os.path.join(root, PACKAGE, "train", "loop.py")
    if not os.path.exists(path):
        return
    tree = _Tree(path, os.path.relpath(path, root))
    # Same fence walk as GC105 (shared _iter_timed_loop_calls); the rules
    # differ only in classification — signal installs ignore the fence.
    for call, fenced in _iter_timed_loop_calls(tree):
        name = _dotted(call.func)
        if name in _SIGNAL_CALLS:
            if not _suppressed(tree, call.lineno, "GC106"):
                yield Violation(
                    "GC106", tree.rel, call.lineno,
                    f"{name}(...) installs/changes a signal handler "
                    "inside the timed step loop",
                    RULES["GC106"].fix_hint,
                )
        elif (
            name in _BLOCKING_IO_CALLS and not fenced
            and not _suppressed(tree, call.lineno, "GC106")
        ):
            yield Violation(
                "GC106", tree.rel, call.lineno,
                f"{name}(...) is blocking file IO inside the timed "
                "step loop with no sync_window fence earlier in its "
                "block",
                RULES["GC106"].fix_hint,
            )


# ---------------------------------------------------------------------------
# GC111: blocking input IO / host-iterator pulls in the timed loop
# ---------------------------------------------------------------------------

#: Dotted-name calls GC111 classifies as blocking input IO. ``next`` is
#: the host-iterator pull (a DataLoader-style ``next(it)`` inside the
#: loop is exactly the serialization the prefetcher exists to remove);
#: ``time.sleep`` is an explicit stall.
_GC111_IO_NAMES = frozenset({
    "open", "io.open", "os.read", "os.pread", "time.sleep",
})
#: Attribute calls (``f.read()``/``f.seek()``-class) GC111 flags unless
#: the receiver is the sanctioned prefetch surface.
_GC111_ATTR_IO = frozenset({
    "read", "readline", "readlines", "readinto", "seek",
})


def _is_blocking_data_io(call: ast.Call) -> Optional[str]:
    """Classify a call as loop-hostile input IO, or None.

    The prefetch fence: any call whose receiver name mentions
    ``prefetch`` is the sanctioned blocking pull (data/prefetch.py
    ``HostPrefetcher.get`` — it measures its own wait into
    ``data_stall_frac``) and is never flagged.
    """
    name = _dotted(call.func)
    if name in _GC111_IO_NAMES:
        return f"{name}() blocking host IO"
    if name == "next" and call.args:
        return "next() host-iterator pull"
    if isinstance(call.func, ast.Attribute):
        recv = _dotted(call.func.value) or ""
        if "prefetch" in recv.lower():
            return None  # the sanctioned fence itself
        if call.func.attr in _GC111_ATTR_IO:
            return f".{call.func.attr}() blocking file IO"
    return None


@_rule(
    "GC111",
    "blocking-input-io-in-timed-loop",
    "blocking file IO / host-iterator next() / time.sleep inside a timed "
    "`for step` loop in data/ or train/ with no sync_window fence earlier "
    "in its block and outside the prefetch fence — input IO serialized "
    "into the timed loop lands inside the very step times the loop "
    "publishes (the starvation data_stall_frac exists to MEASURE)",
    "pull batches through the host prefetcher (data/prefetch.py "
    "HostPrefetcher.get — the sanctioned, wait-measured fence), or move "
    "the IO behind a sync_window fence; suppress deliberate exceptions "
    "with '# graftcheck: disable=GC111'",
)
def _check_timed_loop_blocking_input_io(root: str) -> Iterator[Violation]:
    for tree in _package_files(root, ("data", "train")):
        # Same fence walk as GC105/GC106 (shared _iter_timed_loop_calls):
        # a sync_window earlier in the block fences what follows; files
        # without a sync_window helper simply never fence.
        for call, fenced in _iter_timed_loop_calls(tree):
            if fenced:
                continue
            kind = _is_blocking_data_io(call)
            if kind and not _suppressed(tree, call.lineno, "GC111"):
                yield Violation(
                    "GC111", tree.rel, call.lineno,
                    f"{kind} inside the timed step loop with no "
                    "sync_window fence earlier in its block (and outside "
                    "the prefetch fence)",
                    RULES["GC111"].fix_hint,
                )


# ---------------------------------------------------------------------------
# GC103: unknown mesh axes in sharding-constraint specs
# ---------------------------------------------------------------------------


def known_mesh_axes(root: str) -> frozenset:
    """Axis names any mesh in the package can define: the ``MeshAxes``
    canon in parallel/mesh.py plus every literal axis-name tuple passed to
    ``make_mesh``/``Mesh`` anywhere in the package (which is how 'expert'
    enters — the loop builds a 5-axis mesh)."""
    axes = set()
    mesh_py = os.path.join(root, PACKAGE, "parallel", "mesh.py")
    if os.path.exists(mesh_py):
        tree = _Tree(mesh_py, "parallel/mesh.py")
        for node in ast.walk(tree.ast):
            if isinstance(node, ast.ClassDef) and node.name == "MeshAxes":
                for stmt in node.body:
                    if (
                        isinstance(stmt, ast.AnnAssign)
                        and isinstance(stmt.value, ast.Constant)
                        and isinstance(stmt.value.value, str)
                    ):
                        axes.add(stmt.value.value)
    for tree in _package_files(root, ("",)):
        for node in ast.walk(tree.ast):
            if not (
                isinstance(node, ast.Call)
                and _dotted(node.func) in ("make_mesh", "Mesh", "jax.sharding.Mesh")
            ):
                continue
            candidates = list(node.args[1:2]) + [
                kw.value for kw in node.keywords
                if kw.arg in ("axis_names", "axis_name")
            ]
            for cand in candidates:
                if isinstance(cand, (ast.Tuple, ast.List)):
                    for el in cand.elts:
                        if isinstance(el, ast.Constant) and isinstance(el.value, str):
                            axes.add(el.value)
                elif isinstance(cand, ast.Constant) and isinstance(cand.value, str):
                    axes.add(cand.value)
    return frozenset(axes)


@_rule(
    "GC103",
    "unknown-mesh-axis-in-sharding-constraint",
    "with_sharding_constraint PartitionSpec naming an axis no package mesh "
    "defines (GSPMD silently ignores unknown axes — the constraint no-ops)",
    "use an axis from parallel/mesh.py (MeshAxes / the loop's 5-axis mesh), "
    "or add the new axis to the mesh construction first",
)
def _check_sharding_constraint_axes(root: str) -> Iterator[Violation]:
    known = known_mesh_axes(root)
    if not known:
        return
    for tree in _package_files(root, ("",)):
        for node in ast.walk(tree.ast):
            if not (
                isinstance(node, ast.Call)
                and _dotted(node.func) in (
                    "with_sharding_constraint",
                    "lax.with_sharding_constraint",
                    "jax.lax.with_sharding_constraint",
                )
            ):
                continue
            # Only literal axis names inside P(...)/PartitionSpec(...) are
            # statically checkable; computed spec trees audit elsewhere.
            for sub in ast.walk(node):
                if not (
                    isinstance(sub, ast.Call)
                    and _dotted(sub.func) in ("P", "PartitionSpec",
                                              "jax.sharding.PartitionSpec")
                ):
                    continue
                for arg in sub.args:
                    elts = arg.elts if isinstance(arg, (ast.Tuple, ast.List)) else [arg]
                    for el in elts:
                        if (
                            isinstance(el, ast.Constant)
                            and isinstance(el.value, str)
                            and el.value not in known
                            and not _suppressed(tree, el.lineno, "GC103")
                        ):
                            yield Violation(
                                "GC103", tree.rel, el.lineno,
                                f"PartitionSpec names axis {el.value!r}; "
                                f"known mesh axes are {sorted(known)}",
                                RULES["GC103"].fix_hint,
                            )


# ---------------------------------------------------------------------------
# GC104: wall-clock reads in jit-adjacent modules
# ---------------------------------------------------------------------------


@_rule(
    "GC104",
    "time-time-in-jit-scope",
    "time.time() in a jit-adjacent module (train/, models/, ops/, "
    "parallel/) — under trace it constant-folds to the trace-time clock",
    "host-side timing uses time.perf_counter() outside jit; device timing "
    "belongs to the profiler (--profile-dir)",
)
def _check_time_time(root: str) -> Iterator[Violation]:
    for tree in _package_files(root, ("train", "models", "ops", "parallel")):
        for node in ast.walk(tree.ast):
            if (
                isinstance(node, ast.Call)
                and _dotted(node.func) == "time.time"
                and not _suppressed(tree, node.lineno, "GC104")
            ):
                yield Violation(
                    "GC104", tree.rel, node.lineno,
                    "time.time() call in jit-adjacent code",
                    RULES["GC104"].fix_hint,
                )


# ---------------------------------------------------------------------------
# GC107: implicit f32 constant promotion in jitted model code
# ---------------------------------------------------------------------------

#: Constructor -> index of the positional argument that IS the dtype (a
#: call with that many positionals has pinned it positionally, like
#: ``jnp.zeros(shape, c.param_dtype)``). ``asarray``/``array`` take dtype
#: second; ``full`` takes (shape, fill_value, dtype).
_GC107_CONSTRUCTORS = {
    "jnp.asarray": 1, "jnp.array": 1,
    "jnp.ones": 1, "jnp.zeros": 1, "jnp.empty": 1,
    "jnp.full": 2,
}


@_rule(
    "GC107",
    "implicit-f32-constant-in-model-code",
    "dtype-less jnp.asarray/jnp.array/ones/zeros/empty/full inside jitted "
    "model code (models/, train/step.py) — the float32 default silently "
    "promotes bf16 arithmetic around it, minting the bf16->f32 convert "
    "chains the collective budgets pin",
    "pass dtype= (the config's compute/param dtype, or the operand's "
    "x.dtype) so the constant joins the surrounding precision; python "
    "scalars in arithmetic stay weakly typed and need no wrapper — often "
    "the fix is deleting the jnp.asarray() entirely; suppress deliberate "
    "f32 islands (loss accumulators) with '# graftcheck: disable=GC107'",
)
def _check_implicit_f32_constants(root: str) -> Iterator[Violation]:
    targets = list(_package_files(root, ("models",)))
    step_py = os.path.join(root, PACKAGE, "train", "step.py")
    if os.path.exists(step_py):
        targets.append(_Tree(step_py, os.path.relpath(step_py, root)))
    for tree in targets:
        for node in ast.walk(tree.ast):
            if not isinstance(node, ast.Call):
                continue
            name = _dotted(node.func)
            dtype_pos = _GC107_CONSTRUCTORS.get(name or "")
            if dtype_pos is None:
                continue
            if len(node.args) > dtype_pos:  # dtype pinned positionally
                continue
            if any(kw.arg == "dtype" for kw in node.keywords):
                continue
            if _suppressed(tree, node.lineno, "GC107"):
                continue
            yield Violation(
                "GC107", tree.rel, node.lineno,
                f"{name}(...) without a dtype defaults to float32 inside "
                "jitted model code",
                RULES["GC107"].fix_hint,
            )


# ---------------------------------------------------------------------------
# GC108: collective axis names vs the enclosing shard_map's axis set
# ---------------------------------------------------------------------------

#: Collective / axis-query callables whose axis argument GC108 checks,
#: mapped to the positional index of that argument (kwarg ``axis_name=``
#: is always honored too).
_GC108_COLLECTIVES = {
    "lax.psum": 1, "psum": 1,
    "lax.pmean": 1, "pmean": 1,
    "lax.pmax": 1, "pmax": 1,
    "lax.pmin": 1, "pmin": 1,
    "lax.ppermute": 1, "ppermute": 1,
    "lax.all_gather": 1, "all_gather": 1,
    "lax.all_to_all": 1, "all_to_all": 1,
    "lax.psum_scatter": 1, "psum_scatter": 1,
    "lax.axis_index": 0, "axis_index": 0,
    "lax.axis_size": 0, "axis_size": 0,
    "jax.lax.psum": 1, "jax.lax.pmean": 1, "jax.lax.ppermute": 1,
    "jax.lax.all_gather": 1, "jax.lax.all_to_all": 1,
}

_SHARD_MAP_NAMES = (
    "shard_map", "jax.shard_map", "jax.experimental.shard_map.shard_map",
)


def _literal_axis_names(node: ast.AST) -> List[Tuple[str, int]]:
    """(axis, lineno) for every string literal in an axis-bearing arg —
    a bare 'data', ('pipe', 'seq') tuples, lists."""
    out: List[Tuple[str, int]] = []
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        out.append((node.value, node.lineno))
    elif isinstance(node, (ast.Tuple, ast.List)):
        for el in node.elts:
            if isinstance(el, ast.Constant) and isinstance(el.value, str):
                out.append((el.value, el.lineno))
    return out


_P_NAMES = ("P", "PartitionSpec", "jax.sharding.PartitionSpec")


def _shard_map_axis_set(call: ast.Call) -> Optional[frozenset]:
    """The axis names one shard_map call site pins statically, or None.

    The set only CLOSES when the site passes a fully-literal
    ``axis_names=`` — that kwarg is shard_map's own declaration of the
    manual axes, so it is the one thing that bounds what a collective
    may legally name. Spec ``P(...)`` literals join the set as extras
    (defensive; they must be a subset of axis_names anyway), but
    without an explicit literal axis_names the set is OPEN and the site
    is skipped: axis_names defaults to ALL mesh axes, and the mesh is a
    runtime value, so spec literals alone under-approximate the legal
    set (a psum over an unnamed mesh axis would be a false positive).
    Any non-literal component — a partially-literal tuple
    (("data", extra_axis)), a spec variable, a helper call — also opens
    the set (models/moe.py's dp-conditional batch spec is the live
    example; such sites audit through the HLO engine instead).
    """
    axes: set = set()
    closed = False
    for kw in call.keywords:
        if kw.arg == "axis_names":
            found = _literal_axis_names(kw.value)
            axes.update(a for a, _ in found)
            # Closed ONLY when every element is literal: one runtime
            # element (("data", extra_axis)) means unknown axes exist.
            n_elts = (
                len(kw.value.elts)
                if isinstance(kw.value, (ast.Tuple, ast.List))
                else 1
            )
            closed = bool(found) and len(found) == n_elts
        elif kw.arg in ("in_specs", "out_specs") and kw.value is not None:
            stack = [kw.value]
            while stack:
                n = stack.pop()
                if isinstance(n, (ast.Tuple, ast.List)):
                    stack.extend(n.elts)
                elif isinstance(n, ast.Call) and _dotted(n.func) in _P_NAMES:
                    for arg in n.args:
                        elts = (
                            arg.elts
                            if isinstance(arg, (ast.Tuple, ast.List))
                            else [arg]
                        )
                        for el in elts:
                            if (
                                isinstance(el, ast.Constant)
                                and isinstance(el.value, str)
                            ):
                                axes.add(el.value)
    if not closed or not axes:
        return None
    return frozenset(axes)


def _mapped_function_body(call: ast.Call, tree_ast: ast.AST) -> Optional[ast.AST]:
    """The AST region shard_map maps over: a Lambda argument directly, or
    the nearest same-module ``def`` a Name argument refers to."""
    if not call.args:
        return None
    target = call.args[0]
    if isinstance(target, ast.Lambda):
        return target
    if isinstance(target, ast.Name):
        best: Optional[ast.FunctionDef] = None
        for node in ast.walk(tree_ast):
            if (
                isinstance(node, ast.FunctionDef)
                and node.name == target.id
                and node.lineno <= call.lineno
            ):
                if best is None or node.lineno > best.lineno:
                    best = node
        return best
    return None


@_rule(
    "GC108",
    "collective-axis-outside-shard-map-axes",
    "psum/ppermute/all_gather/... inside a shard_map body naming a literal "
    "axis the enclosing shard_map does not define — the collective raises "
    "(or silently binds a different mesh's axis) only at trace time, deep "
    "inside a jit",
    "use an axis from the shard_map's axis_names/in_specs set, or thread "
    "the axis name in as a parameter like ops/ring_attention.py does; "
    "suppress deliberate cross-mesh collectives with "
    "'# graftcheck: disable=GC108'",
)
def _check_shard_map_collective_axes(root: str) -> Iterator[Violation]:
    for tree in _package_files(root, ("",)):
        for call in ast.walk(tree.ast):
            if not (
                isinstance(call, ast.Call)
                and _dotted(call.func) in _SHARD_MAP_NAMES
            ):
                continue
            axes = _shard_map_axis_set(call)
            if not axes:
                continue  # nothing statically known to check against
            body = _mapped_function_body(call, tree.ast)
            if body is None:
                continue
            # Walk the mapped region but never descend into a NESTED
            # shard_map call — the inner map owns its own axis scope and
            # is checked at its own call site against its own set.
            stack = list(ast.iter_child_nodes(body))
            region: List[ast.AST] = []
            while stack:
                n = stack.pop()
                if (
                    isinstance(n, ast.Call)
                    and _dotted(n.func) in _SHARD_MAP_NAMES
                ):
                    continue
                region.append(n)
                stack.extend(ast.iter_child_nodes(n))
            for sub in region:
                if not isinstance(sub, ast.Call):
                    continue
                pos = _GC108_COLLECTIVES.get(_dotted(sub.func) or "")
                if pos is None:
                    continue
                axis_nodes = [
                    kw.value for kw in sub.keywords if kw.arg == "axis_name"
                ]
                if not axis_nodes and len(sub.args) > pos:
                    axis_nodes = [sub.args[pos]]
                for node in axis_nodes:
                    for axis, line in _literal_axis_names(node):
                        if axis in axes:
                            continue
                        if _suppressed(tree, line, "GC108"):
                            continue
                        yield Violation(
                            "GC108", tree.rel, line,
                            f"{_dotted(sub.func)}(..., {axis!r}) names an "
                            f"axis outside the enclosing shard_map's set "
                            f"{sorted(axes)}",
                            RULES["GC108"].fix_hint,
                        )


# ---------------------------------------------------------------------------
# GC109: per-microbatch reshard hazard in parallel/ schedule loops
# ---------------------------------------------------------------------------

#: Calls that re-place or re-lay-out device values: one of these inside a
#: trace-time-unrolled schedule loop becomes M copies in the compiled step.
_GC109_RESHARD_CALLS = frozenset({
    "with_sharding_constraint", "lax.with_sharding_constraint",
    "jax.lax.with_sharding_constraint",
    "device_put", "jax.device_put",
})
#: Host-synchronizing calls (the GC102 classes, scoped to parallel/):
#: inside a schedule loop each unrolled copy fences the device.
_GC109_HOST_SYNC_CALLS = frozenset({
    "np.asarray", "numpy.asarray", "np.array", "jax.device_get",
})


def _gc109_classify(call: ast.Call, traced_loop: bool) -> Optional[str]:
    name = _dotted(call.func)
    if name in _GC109_RESHARD_CALLS:
        return f"{name}(...) re-places/re-lays-out a value"
    if not traced_loop:
        # Host-sync classes only matter in loops that touch jax at all:
        # the schedule BUILDERS (build_schedule's numpy/heapq passes) are
        # pure host code where int()/np.asarray are innocent — flagging
        # them would force disable= pragmas onto correct code.
        return None
    if name in _GC109_HOST_SYNC_CALLS:
        return f"{name}(...) is a device->host transfer"
    if name in ("float", "int") and call.args:
        return f"{name}(...) is a .item()-class host sync"
    if isinstance(call.func, ast.Attribute) and call.func.attr in (
        "item", "block_until_ready"
    ):
        return f".{call.func.attr}() is a host sync"
    return None


def _loop_touches_jax(loop: ast.For) -> bool:
    """True when the loop subtree references jax/jnp/lax names — the
    trace-time-unrolled shape GC109's host-sync classes police."""
    for n in ast.walk(loop):
        name = _dotted(n) if isinstance(n, (ast.Attribute, ast.Name)) else None
        if name and name.split(".", 1)[0] in ("jax", "jnp", "lax"):
            return True
    return False


@_rule(
    "GC109",
    "per-microbatch-reshard-hazard-in-schedule-loop",
    "with_sharding_constraint/device_put/host-sync call inside a "
    "`for _ in range(...)` loop body in parallel/ — schedule loops unroll "
    "at trace time, so the call becomes one reshard/fence PER MICROBATCH "
    "in the compiled step (the growth the schedule auditor's affine law "
    "flags as pipeline reshard suspects)",
    "hoist the placement to the shard_map boundary (in_specs/out_specs or "
    "a single constraint outside the loop); derive per-tick values from "
    "sharded operands instead of host syncs; suppress deliberate "
    "exceptions with '# graftcheck: disable=GC109'",
)
def _check_schedule_loop_reshards(root: str) -> Iterator[Violation]:
    for tree in _package_files(root, ("parallel",)):
        seen = set()  # nested range loops would double-report inner calls
        for node in ast.walk(tree.ast):
            if not (
                isinstance(node, ast.For)
                and isinstance(node.iter, ast.Call)
                and _dotted(node.iter.func) == "range"
            ):
                continue
            traced = _loop_touches_jax(node)
            # Full subtree walk, INCLUDING nested function defs (unlike
            # _stmt_calls): the real tick loops put per-tick work in
            # closures invoked via lax.cond/switch each unrolled tick, so
            # a hazard inside one is still one copy per microbatch.
            for stmt in node.body + node.orelse:
                for call in ast.walk(stmt):
                    if not isinstance(call, ast.Call):
                        continue
                    kind = _gc109_classify(call, traced)
                    if (
                        kind
                        and (call.lineno, call.col_offset) not in seen
                        and not _suppressed(tree, call.lineno, "GC109")
                    ):
                        seen.add((call.lineno, call.col_offset))
                        yield Violation(
                            "GC109", tree.rel, call.lineno,
                            f"{kind} inside a range() schedule loop "
                            "(unrolls per microbatch at trace time)",
                            RULES["GC109"].fix_hint,
                        )


# ---------------------------------------------------------------------------
# GC112: hard-coded exit-code literals outside the central EXIT_* registry
# ---------------------------------------------------------------------------

#: Receiver names that mark a comparison as exit-code-shaped: `rc == 75`,
#: `proc.returncode in (75, 76)`, `exit_code != 77`. Deliberately narrow —
#: a bare 75 elsewhere (a percentile, a size) is not this rule's business.
_GC112_RECEIVER = re.compile(
    r"(^|_)(rc|returncode|exit_?code|exit_?status)(_|\d*$)", re.IGNORECASE
)
_GC112_EXIT_NAME = re.compile(r"^EXIT_[A-Z0-9_]+$")
#: Call targets whose integer argument IS a process exit code.
_GC112_EXIT_CALLS = frozenset({"sys.exit", "os._exit", "exit", "SystemExit"})


def _gc112_registry(root: str):
    """Harvest the central registry: every module-level ``EXIT_NAME = int``
    assignment in the package -> {value: name}, plus the defining
    (file, line) pairs (exempt by construction — the registry itself is
    the one place the literals belong)."""
    values: Dict[int, str] = {}
    defining = set()
    for tree in _package_files(root, ("",)):
        for node in tree.ast.body:
            if not isinstance(node, ast.Assign) or len(node.targets) != 1:
                continue
            target = node.targets[0]
            if not (isinstance(target, ast.Name)
                    and _GC112_EXIT_NAME.match(target.id)):
                continue
            if isinstance(node.value, ast.Constant) and isinstance(
                node.value.value, int
            ):
                values[node.value.value] = target.id
                defining.add((tree.rel, node.lineno))
    return values, defining


def _gc112_compare_is_exitish(node: ast.Compare) -> bool:
    for side in [node.left, *node.comparators]:
        ident = None
        if isinstance(side, ast.Attribute):
            ident = side.attr
        elif isinstance(side, ast.Name):
            ident = side.id
        if ident and _GC112_RECEIVER.search(ident):
            return True
    return False


def _gc112_literals(node: ast.AST) -> Iterator[ast.Constant]:
    """Int literals inside one expression (tuples/lists/sets unpacked —
    the ``rc in (75, 76)`` shape)."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Constant) and type(sub.value) is int:
            yield sub


@_rule(
    "GC112",
    "hard-coded-exit-code-literal",
    "a registry exit-code value (EXIT_PREEMPTED 75 / EXIT_HUNG 76 / "
    "EXIT_NOTHING_TO_RESUME 77 / EXIT_DATA_STALL 78 — harvested, not "
    "hard-coded here either) as a bare integer literal in an exit call "
    "or an exit-code comparison, outside the defining EXIT_* assignment",
    "import the named constant from the faults package (e.g. "
    "`from ..faults import EXIT_PREEMPTED`) instead of its integer value — "
    "the renumbering that moved EXIT_NOTHING_TO_RESUME 76 -> 77 is exactly "
    "the drift this rule exists to catch",
)
def _check_exit_code_literals(root: str) -> Iterator[Violation]:
    values, defining = _gc112_registry(root)
    if not values:
        return
    for tree in _package_files(root, ("",)):
        for node in ast.walk(tree.ast):
            hits: List[ast.Constant] = []
            if isinstance(node, ast.Call):
                name = _dotted(node.func)
                if name in _GC112_EXIT_CALLS:
                    hits = [
                        c for arg in node.args for c in _gc112_literals(arg)
                    ]
            elif isinstance(node, ast.Compare):
                if _gc112_compare_is_exitish(node):
                    hits = [
                        c for side in [node.left, *node.comparators]
                        for c in _gc112_literals(side)
                    ]
            for lit in hits:
                if lit.value not in values:
                    continue
                if (tree.rel, lit.lineno) in defining:
                    continue
                if _suppressed(tree, lit.lineno, "GC112"):
                    continue
                yield Violation(
                    "GC112", tree.rel, lit.lineno,
                    f"hard-coded exit code {lit.value} "
                    f"({values[lit.value]}) outside the central EXIT_* "
                    "registry",
                    RULES["GC112"].fix_hint,
                )


# ---------------------------------------------------------------------------
# GC201: entrypoint <-> harness flag-surface drift
# ---------------------------------------------------------------------------

_FLAG_TOKEN = re.compile(r"--[a-z][a-z0-9-]+")


@_rule(
    "GC201",
    "entrypoint-flag-drift",
    "docker/entrypoint.sh env contract out of sync with "
    "train/harness.py::build_parser() — in either direction",
    "plumb the new flag through an env var in docker/entrypoint.sh (or add "
    "it to lint.ENTRYPOINT_EXEMPT_FLAGS with a reason); delete stale flags "
    "the harness no longer defines",
)
def _check_entrypoint_drift(root: str) -> Iterator[Violation]:
    entrypoint = os.path.join(root, "docker", "entrypoint.sh")
    if not os.path.exists(entrypoint):
        return
    from ...train.harness import build_parser

    parser_flags = set()
    for action in build_parser()._actions:
        parser_flags.update(
            o for o in action.option_strings if o.startswith("--")
        )
    parser_flags.discard("--help")

    text = open(entrypoint).read()
    entry_flags = set(_FLAG_TOKEN.findall(text))

    stale = entry_flags - parser_flags - ENTRYPOINT_WRAPPER_FLAGS
    if stale:
        yield Violation(
            "GC201", "docker/entrypoint.sh", 1,
            f"passes flags the harness does not define: {sorted(stale)}",
            RULES["GC201"].fix_hint,
        )
    missing = parser_flags - entry_flags - ENTRYPOINT_EXEMPT_FLAGS
    if missing:
        yield Violation(
            "GC201", "docker/entrypoint.sh", 1,
            f"harness flags with no container-env plumbing: {sorted(missing)}",
            RULES["GC201"].fix_hint,
        )


# ---------------------------------------------------------------------------
# Runner
# ---------------------------------------------------------------------------


def run_lint(
    root: str = REPO_ROOT,
    rules: Optional[Tuple[str, ...]] = None,
    files: Optional[Tuple[str, ...]] = None,
) -> List[Violation]:
    """Run every registered rule (or the named subset) over ``root``.

    ``files`` (repo-relative paths) scopes the REPORT to those files —
    the `--changed` pre-commit path. Rules still scan the whole package
    for their knowledge bases (GC103's mesh-axis harvest, GC201's flag
    surfaces), so a changed file is judged against unchanged context; a
    violation is only emitted when it sits in a changed file.
    """
    out: List[Violation] = []
    for rule, check in _CHECKS:
        if rules is not None and rule.id not in rules:
            continue
        out.extend(v for v in check(root) if v is not None)
    if files is not None:
        wanted = {f.replace(os.sep, "/") for f in files}
        out = [v for v in out if v.path.replace(os.sep, "/") in wanted]
    return sorted(out, key=lambda v: (v.path, v.line, v.rule_id))
