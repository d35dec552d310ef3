"""Deterministic fault injection for the chaos harness.

Every fault fires at an exact, *reproducible* point in the run:

- the stepped kinds (``sigkill@N``, ``sigterm@N``, ``hang@N``,
  ``stall-rank@N:R``) fire at the first sync-window boundary whose last
  completed step is >= N — the loop is already fenced there, so the
  abort step in the telemetry trail is the same on every run of the same
  spec;
- ``nan-loss@N`` corrupts exactly step N's loss at dispatch (the NaN
  surfaces at that step's sync window and trips the recorder's anomaly
  screen);
- ``bitflip@N`` / ``grad-explode@N`` poison the parameter tree exactly
  before step N dispatches (one huge element / one scaled leaf) — the
  numerics-sentinel proof faults (``faults/sentinel.py``): the run must
  detect, roll back to the last validated checkpoint and replay;
- ``torn-checkpoint`` fires after the first checkpoint save that leaves
  a *previous* committed step behind it: it tears the newest step's
  payload (truncates one file) and SIGKILLs, so resume must quarantine
  the torn step and fall back;
- ``enospc-on-save`` raises ``OSError(ENOSPC)`` from every checkpoint
  save — the run must degrade (warn + telemetry event) and still finish.

The injector is inert (``armed`` False) when constructed without a spec,
so the hot loop pays one attribute check per boundary and nothing else.
Faults announce themselves with a ``fault_injected`` telemetry event
*before* firing — the JSONL stream is line-buffered, so even the SIGKILL
trail records what killed it.
"""

from __future__ import annotations

import dataclasses
import errno
import os
import signal
import time
from typing import Optional

#: kind -> one-line contract, the registry --inject-fault validates against.
FAULT_KINDS = {
    "sigkill": "SIGKILL self at the first sync boundary with step >= N "
               "(the honest crash: no handlers, no flushes)",
    "sigterm": "SIGTERM self at the first sync boundary with step >= N "
               "(exercises the preemption handler end to end)",
    "sigterm-rank": "sigterm-rank@N:R — SIGTERM self at the first sync "
                    "boundary with step >= N, but ONLY on rank R "
                    "(exercises the cross-host preempt-soon broadcast: "
                    "every OTHER rank must learn of the preemption via "
                    "the coordination-service flag, not a signal)",
    "nan-loss": "corrupt step N's loss to NaN (trips the recorder's "
                "anomaly screen; validate_results must reject the row)",
    "hang": "sleep at the first sync boundary with step >= N "
            "(hang@N:SECS overrides the default stall; exercises the "
            "in-process hang watchdog / the liveness probe)",
    "stall-rank": "stall-rank@N:R[:SECS] — sleep at the first sync "
                  "boundary with step >= N, but ONLY on rank R "
                  "(exercises the cross-host hang broadcast: every OTHER "
                  "rank must learn of the stall from the "
                  "coordination-service hang flag and join the coherent "
                  "EXIT_HUNG abort)",
    "bitflip": "bitflip@N — corrupt one element of one parameter leaf "
               "before step N dispatches (silent-data-corruption "
               "analogue; the numerics sentinel's checksum/grad guards "
               "must trip and roll back)",
    "grad-explode": "grad-explode@N — scale one parameter leaf by a large "
                    "factor before step N dispatches, so the step's "
                    "global grad-norm explodes (the sentinel's grad-norm "
                    "guard must trip and roll back)",
    "opt-moments": "opt-moments@N — collapse the optimizer's second-"
                   "moment (Adam nu) accumulators toward zero before "
                   "step N dispatches: step N's update explodes (m/"
                   "(sqrt(nu)+eps) with a vanishing denominator) while "
                   "step N's own loss/grads stay healthy, so step N+1's "
                   "global grad-norm spikes FIRST — the sentinel's "
                   "grad-norm guard must trip before the loss/checksum "
                   "guards and roll back (the ROADMAP carry-forward "
                   "fault class no other spec exercises)",
    "torn-checkpoint": "tear the newest checkpoint after a save that has "
                       "a previous committed step, then SIGKILL (restore "
                       "must quarantine and fall back)",
    "enospc-on-save": "every checkpoint save raises OSError(ENOSPC); the "
                      "run must degrade and still finish",
    "data-stall": "data-stall@N[:SECS] — the streaming input source goes "
                  "silent before the batch for step N (default stall "
                  "3600 s): the prefetch producer sleeps, the timed loop "
                  "starves, and the run must classify reason=data_stall "
                  "(exit 78, retryable-with-resume) — NOT the watchdog's "
                  "hang. Requires --data-path",
    "data-corrupt-record": "data-corrupt-record@N — flip one byte of "
                           "global record N's payload as it is read "
                           "(emulated disk bit-rot; the files are never "
                           "mutated): the CRC check must catch it, the "
                           "slot heals by substitution, and the "
                           "records_skipped ledger + data_corrupt_record "
                           "telemetry event record the quarantine. "
                           "Requires --data-path",
    "data-slow-reader": "data-slow-reader@N:MS — every record read from "
                        "global record N onward takes MS extra "
                        "milliseconds (a degraded mount): the run must "
                        "COMPLETE with an honest, elevated "
                        "data_stall_frac — degrade, never die. Requires "
                        "--data-path",
    "data-missing-shard": "data-missing-shard@K — shard K is withheld "
                          "from discovery (a hole in the corpus): the "
                          "stream must REFUSE loudly naming the shard "
                          "before any device work — training on a "
                          "silently truncated corpus is the failure this "
                          "proves impossible. Requires --data-path",
}

#: Kinds that take a mandatory ``@N`` step (for the data kinds, N is a
#: global record index / shard index rather than an optimizer step — the
#: same "a fault without a firing point is not reproducible" rule).
STEPPED_KINDS = frozenset(
    {"sigkill", "sigterm", "sigterm-rank", "nan-loss", "hang",
     "stall-rank", "bitflip", "grad-explode", "opt-moments",
     "data-stall", "data-corrupt-record", "data-slow-reader",
     "data-missing-shard"}
)

#: Kinds whose ``@N:R`` suffix names a target rank.
RANKED_KINDS = frozenset({"sigterm-rank", "stall-rank"})

#: Data-path kinds (fire inside data/stream.py + data/prefetch.py via the
#: injector's data_* hooks; require --data-path to have any consumer).
DATA_KINDS = frozenset(
    {"data-stall", "data-corrupt-record", "data-slow-reader",
     "data-missing-shard"}
)

#: The bitflip magnitude: large enough that a squared-norm reduction in
#: f32 overflows to inf (1e30^2 > f32 max), so the sentinel's checksum /
#: grad-norm guards trip deterministically on the very next boundary.
BITFLIP_VALUE = 1e30
#: grad-explode scales one leaf by this factor — logits saturate, the
#: loss and the global grad-norm jump orders of magnitude, but nothing
#: goes non-finite (the *envelope* guards must catch it, not a NaN
#: screen).
GRAD_EXPLODE_SCALE = 1e3
#: opt-moments: the exponent-burst scales for the Adam moment buffers.
#: The second moments (nu) collapse toward zero and the paired first
#: moments (mu) flip UP — one SDC burst across the adjacent moment
#: state. Both halves are needed for a physical reason worth recording:
#: a pure nu collapse CANNOT spike the next step's gradients, because
#: optax updates the moments BEFORE computing the step — the
#: ``(1 - b2) * g^2`` refill rebuilds the denominator within the very
#: corrupted step, bounding the update inflation at ``1/sqrt(1-b2)``
#: (~31x, and only ~3x at early step counts under bias correction):
#: a 31x-effective-lr drift, not an explosion. The corrupted mu has the
#: opposite refill asymmetry — ``b1 * mu`` RETAINS the corruption — so
#: the update explodes ~1e3x through the numerator while the step's own
#: loss/grads stay healthy: the first observable symptom is the NEXT
#: step's global grad-norm, which is exactly the guard this spec exists
#: to prove fires before the loss/checksum guards. The burst is sized so
#: that the next step's loss stays FINITE: at 1e4 every parameter moved by
#: thousands of its own scale, the bf16 forward sat at the edge of the
#: float range, and whether that loss came out NaN (the loss guard's trip,
#: not this one's) depended on the dropout draw. At 1e3 it is finite in
#: every draw tried (ddp and zero2, rbg and threefry, three seeds) and the
#: grad-norm still jumps 59x or more against a spike factor of 10.
MOMENT_COLLAPSE_SCALE = 1e-8
MOMENT_BURST_SCALE = 1e3

#: Default stall for ``hang`` when the spec carries no ``:SECS``. Long
#: enough that any sane per-run timeout (or the k8s liveness probe) fires
#: first; the chaos suite passes a short override.
HANG_DEFAULT_SEC = 3600.0


@dataclasses.dataclass(frozen=True)
class FaultSpec:
    """One parsed ``--inject-fault`` value."""

    kind: str
    step: Optional[int] = None
    hang_sec: Optional[float] = None
    # sigterm-rank@N:R — the one rank that receives the signal. Every rank
    # parses the same spec (the suite passes one value to every worker);
    # the injector compares against its own rank at fire time.
    rank: Optional[int] = None
    # data-slow-reader@N:MS — per-record extra read latency in
    # milliseconds (its own field so the spec string round-trips in the
    # unit the operator wrote; hang_sec stays seconds).
    delay_ms: Optional[float] = None

    def __str__(self) -> str:
        s = self.kind
        if self.step is not None:
            s += f"@{self.step}"
        if self.rank is not None:
            # Ranked grammar: KIND@N:R[:SECS] — the rank rides first.
            s += f":{self.rank}"
        if self.hang_sec is not None:
            s += f":{self.hang_sec:g}"
        if self.delay_ms is not None:
            s += f":{self.delay_ms:g}"
        return s


def parse_fault_spec(spec: Optional[str]) -> Optional[FaultSpec]:
    """``"sigkill@10"`` -> FaultSpec; None/empty -> None; junk raises.

    Grammar: ``KIND`` | ``KIND@STEP`` | ``hang@STEP:SECS`` |
    ``sigterm-rank@STEP:RANK`` | ``stall-rank@STEP:RANK[:SECS]``.
    Stepped kinds *require* the step (a fault with no defined firing
    point would not be reproducible) — the ranked kinds additionally
    require the target rank; the save-path kinds refuse one (they fire on
    save events, not steps).
    """
    if not spec:
        return None
    spec = spec.strip()
    kind, _, rest = spec.partition("@")
    if kind not in FAULT_KINDS:
        raise ValueError(
            f"unknown fault kind {kind!r} (expected one of "
            f"{sorted(FAULT_KINDS)})"
        )
    if kind in STEPPED_KINDS:
        if not rest:
            raise ValueError(
                f"fault {kind!r} needs an explicit step: {kind}@N "
                "(a fault without a firing step is not reproducible)"
            )
        step_str, _, suffix = rest.partition(":")
        if suffix and kind not in (
            "hang", "data-stall", "data-slow-reader", *RANKED_KINDS
        ):
            raise ValueError(
                f"only 'hang', 'data-stall', 'data-slow-reader' and the "
                f"ranked kinds ({sorted(RANKED_KINDS)}) take a suffix, "
                f"got {spec!r}"
            )
        if kind in RANKED_KINDS and not suffix:
            raise ValueError(
                f"{kind} needs a target rank: {kind}@N:R (without one the "
                f"fault is rankless — which rank it hits is the whole "
                "point of the spec)"
            )
        if kind == "data-slow-reader" and not suffix:
            raise ValueError(
                f"data-slow-reader needs a per-record latency: "
                f"data-slow-reader@N:MS (without one the degradation it "
                f"injects is undefined), got {spec!r}"
            )
        try:
            step = int(step_str)
        except ValueError:
            raise ValueError(f"fault step must be an integer, got {spec!r}")
        if step < 0:
            raise ValueError(f"fault step must be >= 0, got {spec!r}")
        hang_sec = None
        rank = None
        delay_ms = None
        if suffix and kind == "data-slow-reader":
            try:
                delay_ms = float(suffix)
            except ValueError:
                raise ValueError(
                    f"data-slow-reader latency must be a number of "
                    f"milliseconds, got {spec!r}"
                )
            if delay_ms <= 0:
                raise ValueError(
                    f"data-slow-reader latency must be > 0, got {spec!r}"
                )
            return FaultSpec(kind=kind, step=step, delay_ms=delay_ms)
        if suffix and kind in RANKED_KINDS:
            rank_str, _, secs_str = suffix.partition(":")
            if secs_str and kind != "stall-rank":
                raise ValueError(
                    f"only stall-rank takes a duration suffix, got {spec!r}"
                )
            try:
                rank = int(rank_str)
            except ValueError:
                raise ValueError(
                    f"{kind} target must be an integer rank, got {spec!r}"
                )
            if rank < 0:
                raise ValueError(f"fault rank must be >= 0, got {spec!r}")
            if secs_str:
                try:
                    hang_sec = float(secs_str)
                except ValueError:
                    raise ValueError(
                        f"stall duration must be a number, got {spec!r}"
                    )
                if hang_sec <= 0:
                    raise ValueError(
                        f"stall duration must be > 0, got {spec!r}"
                    )
        elif suffix:
            try:
                hang_sec = float(suffix)
            except ValueError:
                raise ValueError(
                    f"hang duration must be a number, got {spec!r}"
                )
            if hang_sec <= 0:
                raise ValueError(f"hang duration must be > 0, got {spec!r}")
        return FaultSpec(kind=kind, step=step, hang_sec=hang_sec, rank=rank)
    if rest:
        raise ValueError(
            f"fault {kind!r} fires on checkpoint saves and takes no @step "
            f"(got {spec!r})"
        )
    return FaultSpec(kind=kind)


def _tear_newest_file(step_dir: str) -> Optional[str]:
    """Truncate the first (sorted) non-empty file under ``step_dir``.

    Deterministic pick so the torn artifact is the same every run; returns
    the torn path (repo of the chaos trail) or None when nothing tearable.
    """
    candidates = []
    for dirpath, _dirnames, filenames in os.walk(step_dir):
        for fn in sorted(filenames):
            path = os.path.join(dirpath, fn)
            try:
                if os.path.getsize(path) > 0:
                    candidates.append(path)
            except OSError:
                continue
    if not candidates:
        return None
    victim = sorted(candidates)[0]
    size = os.path.getsize(victim)
    with open(victim, "r+b") as f:
        f.truncate(max(size // 2, 1) - 1 if size > 1 else 0)
    return victim


class FaultInjector:
    """Arms one :class:`FaultSpec` against the train loop's boundaries.

    Call sites (all at device-fenced points — the injector never adds a
    sync of its own):

    - :meth:`at_boundary` from ``sync_window`` after the window's
      telemetry, with the window's last completed step;
    - :meth:`corrupt_loss` on each step's freshly dispatched loss;
    - :meth:`maybe_fail_save` just before a checkpoint save;
    - :meth:`after_save` just after a committed checkpoint save.
    """

    def __init__(self, spec: Optional[FaultSpec] = None, recorder=None,
                 is_main: bool = True, rank: int = 0):
        self.spec = spec
        self.recorder = recorder
        self.is_main = is_main
        # This process's rank — the sigterm-rank kind fires only when it
        # matches the spec's target (every worker of a multi-host run is
        # handed the same spec string).
        self.rank = rank
        self.fired = False

    @property
    def armed(self) -> bool:
        return self.spec is not None

    def _announce(self, detail: str) -> None:
        if self.recorder is not None:
            try:
                self.recorder.note(
                    "fault_injected", fault=str(self.spec), detail=detail,
                )
            except Exception:
                pass
        if self.is_main:
            print(f"CHAOS: injecting fault {self.spec} — {detail}",
                  flush=True)

    # -- boundary faults ---------------------------------------------------

    def at_boundary(self, last_step: int) -> None:
        """Fire sigkill/sigterm/hang/stall at the first boundary past N."""
        if (
            self.spec is None or self.fired
            or self.spec.kind not in (
                "sigkill", "sigterm", "sigterm-rank", "hang", "stall-rank"
            )
            or last_step < (self.spec.step or 0)
        ):
            return
        if self.spec.kind == "stall-rank" and self.rank != (self.spec.rank or 0):
            # Not this worker's stall: THIS rank must learn of the hang
            # from the coordination-service broadcast (the watchdog's
            # hang flag), not from its own stopped clock — that asymmetry
            # is what the spec exists to prove. Stay armed (fired False):
            # a healthy rank never fires anything.
            return
        self.fired = True
        if self.spec.kind == "sigkill":
            self._announce(f"SIGKILL at sync boundary, step {last_step}")
            os.kill(os.getpid(), signal.SIGKILL)
        elif self.spec.kind == "sigterm":
            self._announce(f"SIGTERM at sync boundary, step {last_step}")
            os.kill(os.getpid(), signal.SIGTERM)
        elif self.spec.kind == "sigterm-rank":
            if self.rank != (self.spec.rank or 0):
                # Not this worker's fault to fire: the kill lands on rank
                # R only, and THIS rank must learn of the preemption from
                # the cross-host broadcast — that asymmetry is what the
                # spec exists to prove.
                return
            self._announce(
                f"SIGTERM (rank {self.rank}) at sync boundary, step {last_step}"
            )
            os.kill(os.getpid(), signal.SIGTERM)
        elif self.spec.kind == "stall-rank":
            secs = self.spec.hang_sec or HANG_DEFAULT_SEC
            self._announce(
                f"stall (rank {self.rank}, {secs:g}s) at sync boundary, "
                f"step {last_step}"
            )
            time.sleep(secs)
        else:  # hang
            secs = self.spec.hang_sec or HANG_DEFAULT_SEC
            self._announce(
                f"hang ({secs:g}s stall) at sync boundary, step {last_step}"
            )
            time.sleep(secs)

    # -- loss corruption ---------------------------------------------------

    def corrupt_loss(self, step: int, loss):
        """NaN exactly step N's loss for ``nan-loss@N`` (else passthrough)."""
        if (
            self.spec is None or self.fired
            or self.spec.kind != "nan-loss" or step != self.spec.step
        ):
            return loss
        self.fired = True
        self._announce(f"NaN loss injected at step {step}")
        # Multiplying keeps shape/dtype/sharding; no host sync, no
        # device fence — the NaN just rides the normal loss handle.
        return loss * float("nan")

    # -- parameter corruption (numerics-sentinel proofs) -------------------

    def corrupt_params(self, step: int, params):
        """Poison the parameter tree before step N dispatches (else
        passthrough) — the SDC / gradient-explosion injection point.

        ``bitflip@N`` sets one element of one leaf (the LARGEST leaf —
        deterministically the embedding table, whose poison flows into
        every logit rather than being washed out by the next LayerNorm;
        ties break on path) to :data:`BITFLIP_VALUE`; ``grad-explode@N``
        scales that whole leaf by :data:`GRAD_EXPLODE_SCALE`. Pure device
        ops on the fenced pre-dispatch handle: no host sync, no
        shape/dtype/sharding change — the poison just rides the normal
        params into the step, exactly like a real corrupted HBM word
        would.
        """
        if (
            self.spec is None or self.fired
            or self.spec.kind not in ("bitflip", "grad-explode")
            or step != self.spec.step
        ):
            return params
        self.fired = True
        import jax
        import jax.numpy as jnp

        leaves = jax.tree_util.tree_flatten_with_path(params)[0]
        if self.spec.kind == "grad-explode":
            # Prefer the embedding table (weight-tied LM head): scaling it
            # multiplies every logit, so the loss and the backward pass
            # explode THROUGH the normalization layers instead of being
            # washed out by the next LayerNorm. Fall back to the largest
            # leaf on head-less trees.
            named = [e for e in leaves if "wte" in str(e[0])]
            leaves = named or leaves
        victim_path, victim = sorted(
            leaves, key=lambda e: (-getattr(e[1], "size", 0), str(e[0]))
        )[0]
        name = jax.tree_util.keystr(victim_path)
        if self.spec.kind == "bitflip":
            poisoned = victim.at[(0,) * victim.ndim].set(
                jnp.asarray(BITFLIP_VALUE, victim.dtype)
            )
            self._announce(
                f"bitflip: params{name}[0...] = {BITFLIP_VALUE:g} before "
                f"step {step}"
            )
        else:
            poisoned = victim * jnp.asarray(GRAD_EXPLODE_SCALE, victim.dtype)
            self._announce(
                f"grad-explode: params{name} scaled x{GRAD_EXPLODE_SCALE:g} "
                f"before step {step}"
            )

        def swap(path, leaf):
            return poisoned if path == victim_path else leaf

        return jax.tree_util.tree_map_with_path(swap, params)

    def corrupt_opt_state(self, step: int, opt_state):
        """Corrupt the Adam moment buffers before step N dispatches
        (``opt-moments@N``; else passthrough).

        One exponent burst across the optimizer's moment state: every
        leaf under a ``nu`` field (optax's ``ScaleByAdamState.nu`` —
        matched by the exact attribute name in the tree path, so a
        parameter coincidentally containing 'nu' can never be hit)
        collapses by :data:`MOMENT_COLLAPSE_SCALE`, and the paired
        ``mu`` leaves flip up by :data:`MOMENT_BURST_SCALE` (see the
        constants' note for why the mu half is load-bearing: the nu
        refill self-heals within the corrupted step). The corrupted
        step itself computes HEALTHY loss and gradients — the poison
        only enters through the optimizer update — which is what makes
        this the one fault class whose first observable symptom is the
        NEXT step's exploding grad-norm: the sentinel's grad-norm guard
        must trip before the loss/checksum guards ever see anything.
        Pure device ops on the fenced pre-dispatch handle, like
        ``corrupt_params``. The moment buffers are also the state no
        other guard covers at rest — the checkpoint digests protect
        them on disk, but in HBM a flipped moment is invisible until
        the update fires.
        """
        if (
            self.spec is None or self.fired
            or self.spec.kind != "opt-moments" or step != self.spec.step
        ):
            return opt_state
        self.fired = True
        import jax
        import jax.numpy as jnp

        flat = jax.tree_util.tree_flatten_with_path(opt_state)[0]

        def moment_field(path):
            for e in path:
                if getattr(e, "name", None) in ("mu", "nu"):
                    return e.name
            return None

        n_nu = sum(1 for path, leaf in flat
                   if moment_field(path) == "nu" and hasattr(leaf, "dtype"))
        if n_nu == 0:
            # An optimizer layout without Adam moments (e.g. a future
            # SGD arm): the fault has nothing to corrupt — say so
            # loudly rather than silently passing a healthy run off as
            # a survived injection.
            self._announce(
                "opt-moments: no Adam moment (mu/nu) leaves in this "
                "optimizer state — fault inert"
            )
            return opt_state
        self._announce(
            f"opt-moments: collapsing {n_nu} second-moment (nu) leaves "
            f"x{MOMENT_COLLAPSE_SCALE:g} and bursting the paired mu "
            f"leaves x{MOMENT_BURST_SCALE:g} before step {step}"
        )

        def scale(path, leaf):
            field = moment_field(path)
            if field is None or not hasattr(leaf, "dtype"):
                return leaf
            factor = (MOMENT_COLLAPSE_SCALE if field == "nu"
                      else MOMENT_BURST_SCALE)
            return leaf * jnp.asarray(factor, leaf.dtype)

        return jax.tree_util.tree_map_with_path(scale, opt_state)

    # -- save-path faults --------------------------------------------------

    def maybe_fail_save(self) -> None:
        """Raise ENOSPC from the save path for ``enospc-on-save``."""
        if self.spec is None or self.spec.kind != "enospc-on-save":
            return
        self._announce("OSError(ENOSPC) raised from checkpoint save")
        raise OSError(errno.ENOSPC, "No space left on device (injected)")

    def after_save(self, ckpt, step: int) -> None:
        """Tear the newest checkpoint + SIGKILL for ``torn-checkpoint``.

        Waits until a committed *previous* step exists, so the resume has
        a good step to fall back to — the whole point of the fault class.
        """
        if (
            self.spec is None or self.fired
            or self.spec.kind != "torn-checkpoint"
        ):
            return
        steps = ckpt.all_steps()
        if len(steps) < 2:
            return
        self.fired = True
        victim = ckpt.step_dir(max(steps))
        torn = _tear_newest_file(victim)
        self._announce(
            f"tore checkpoint step {max(steps)} ({torn}); SIGKILL"
        )
        os.kill(os.getpid(), signal.SIGKILL)

    # -- data-path faults (consumed by data/stream.py + data/prefetch.py) --

    def data_missing_shard(self) -> Optional[int]:
        """Shard index to withhold from discovery (``data-missing-shard@K``),
        or None. Fires at stream construction — pre-dispatch, so the
        refusal it provokes never wastes device time."""
        if self.spec is None or self.spec.kind != "data-missing-shard":
            return None
        if not self.fired:
            self.fired = True
            self._announce(
                f"shard {self.spec.step} withheld from discovery — the "
                "stream must refuse loudly naming it"
            )
        return self.spec.step

    def data_stall_sec(self, step: int) -> float:
        """Seconds the prefetch producer sleeps before the batch for step
        N (``data-stall@N[:SECS]``); 0.0 otherwise. Runs on the prefetch
        thread — the announce reaches the JSONL before the consumer
        starves, so the trail records what stalled it."""
        if (
            self.spec is None or self.fired
            or self.spec.kind != "data-stall" or step != self.spec.step
        ):
            return 0.0
        self.fired = True
        secs = self.spec.hang_sec or HANG_DEFAULT_SEC
        self._announce(
            f"input source silent for {secs:g}s before the batch for "
            f"step {step}"
        )
        return secs

    def data_corrupt_payload(self, global_index: int, payload: bytes) -> bytes:
        """Flip one byte of global record N's payload as read
        (``data-corrupt-record@N``; passthrough otherwise). Emulates disk
        bit-rot deterministically WITHOUT mutating the shard files — the
        CRC check downstream must catch it."""
        if (
            self.spec is None or self.fired
            or self.spec.kind != "data-corrupt-record"
            or global_index != self.spec.step
        ):
            return payload
        self.fired = True
        self._announce(
            f"flipped one payload byte of global record {global_index} "
            "(CRC must catch it; slot heals by substitution)"
        )
        return bytes([payload[0] ^ 0xFF]) + payload[1:]

    def data_read_delay_sec(self, global_index: int) -> float:
        """Extra per-record read latency from record N on
        (``data-slow-reader@N:MS``); 0.0 otherwise. ``fired`` only gates
        the announce — the degradation persists for the rest of the run,
        which is what makes data_stall_frac measurable."""
        if (
            self.spec is None or self.spec.kind != "data-slow-reader"
            or global_index < (self.spec.step or 0)
        ):
            return 0.0
        if not self.fired:
            self.fired = True
            self._announce(
                f"every record read from global record {self.spec.step} "
                f"on takes +{self.spec.delay_ms:g} ms (degraded mount)"
            )
        return (self.spec.delay_ms or 0.0) / 1000.0
