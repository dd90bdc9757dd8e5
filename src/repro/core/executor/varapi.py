"""Variable surface of the TerraEngine: reads, assigns, out-of-band
rebinds, the iteration-start snapshot's lifetime, RNG and the per-value
fence wait.

Split out of coordinator.py as a mixin for the same reason
python_runner.py is one — the phase machine and the variable API are
independently readable, and the coordinator stays within the executor's
module-size budget.  Everything here operates on the engine's own state
(store, mode, bindings, event stream).  Fenced *device-side* variable
updates (the serving prefill path) live in varops.py.
"""

from __future__ import annotations

import time
import weakref

import jax
import jax.numpy as jnp

from repro.core import ops as ops_mod
from repro.core.events import emit as ev
from repro.core.tensor import TerraTensor, Variable
from repro.core.trace import Aval, Ref, VarAssign, VarRef

SKELETON = "skeleton"


class _SnapshotHold:
    """An iteration-start snapshot kept alive by the placeholders that may
    still replay a closed iteration (``VariableOps.settle``)."""

    __slots__ = ("snap", "__weakref__")

    def __init__(self, snap):
        self.snap = snap


def no_hold():
    return None


class VariableOps:
    """Mixin for TerraEngine: the variable-facing API."""

    _snapshot_hold = staticmethod(no_hold)     # weakref to a _SnapshotHold

    def _ensure_var(self, var: Variable):
        owner = var._owner
        if owner is None:
            var._owner = self           # this store holds the buffer now
        elif owner is not self and var.var_id not in self.store.buffers:
            # another engine holds the buffer: start from its value
            self.store.put(var.var_id, owner.variable_value(var))
        self.store.ensure(var)

    def read_variable(self, var: Variable) -> TerraTensor:
        self._ensure_var(var)
        bound = self._var_binding.get(var.var_id)
        if bound is not None:
            return bound
        if self.mode == SKELETON:
            return TerraTensor(VarRef(var.var_id), var.aval, engine=self,
                               iter_id=self.iter_id)
        # eager modes read the committed store value
        return TerraTensor(VarRef(var.var_id), var.aval,
                           eager=self.store.get(var.var_id, var._value),
                           engine=self, iter_id=self.iter_id)

    def assign_variable(self, var: Variable, value):
        self._ensure_var(var)
        if not isinstance(value, TerraTensor):
            value = ops_mod.identity(value)
        if not isinstance(value.ref, Ref) or value._iter != self.iter_id:
            value = ops_mod.identity(value)
        self.trace.events.append(VarAssign(var.var_id, value.ref))
        self.trace.var_assigns[var.var_id] = value.ref
        self._var_binding[var.var_id] = value

    def _await_fence(self, seq) -> None:
        """Block on one per-value readiness fence (DESIGN.md §4.4) — a
        GraphRunner sequence number — instead of draining the whole queue;
        the FIFO runner guarantees the fenced writer has committed its
        buffer once the sequence completes.  Lazy mode executes the queued
        work on this thread, as drain() used to."""
        if seq is None or self.runner.done(seq):
            return
        t0 = time.perf_counter()
        self.runner.wait_for(seq)
        self.events.add("py_stall_time", time.perf_counter() - t0)

    def variable_value(self, var: Variable):
        self._ensure_var(var)
        if self._iter_open and self.mode == SKELETON:
            # Python saw device state: poison the steady-state plan (§12)
            if not getattr(self, "_steady_poison", False):
                ev.steady_poison(self.events, self.iter_id)
            self._steady_poison = True
        bound = self._var_binding.get(var.var_id)
        if bound is not None and bound._eager is not None:
            return bound._eager
        # block only on this variable's last pending writer (not the queue)
        self._await_fence(self.store.write_fence(var.var_id))
        val = self.store.buffers[var.var_id]
        if self._may_donate(var.var_id):
            # a later dispatch may donate this buffer: hand the caller a
            # private copy (DESIGN.md §4.2)
            val = jnp.array(val)
        return val

    def _may_donate(self, var_id: int) -> bool:
        """Whether a dispatch still to come may donate the variable's
        current buffer: a later segment of the open walker iteration, or
        the live steady plan, which donates what it rewrites (§12)."""
        if self.mode != SKELETON or self.gp is None:
            return False
        if self._iter_open and var_id in self.gp.donatable_var_ids:
            return True
        plan = self.family.steady if self.family is not None else None
        return plan is not None and var_id in plan.sp.don_var_ids

    def variable_read_ref(self, var: Variable):
        return VarRef(var.var_id)

    def reset_variable(self, var: Variable, value):
        """Out-of-band variable (re)binding between iterations — used by
        drivers (e.g. the serving engine rebinding KV-cache variables after
        a prefill) to swap device state without recording a trace event.
        Rebinding to a different shape is legal: the new aval flows into
        the store's shape digest, so the next iteration selects (or traces)
        the matching TraceGraph family (§8) instead of diverging."""
        if self._iter_open and self.mode == SKELETON:
            raise RuntimeError("reset_variable inside an open co-executed "
                               "iteration")
        self._ensure_var(var)
        # wait for the last pending toucher (reader or writer) of this
        # variable only; rebinds between iterations no longer serialize
        # behind the whole previous iteration's queue
        self._await_fence(self.store.use_fence(var.var_id))
        # the store must own a buffer that a later dispatch donates
        value = (jnp.array(value) if self._may_donate(var.var_id)
                 else jnp.asarray(value))
        self.store.put(var.var_id, value)
        new_aval = Aval.of(value)
        if new_aval != var.aval:
            var.aval = new_aval
            self.store.invalidate_avals()

    def release_variable(self, var: Variable) -> None:
        """Drop a variable's buffer from the store (driver-retired state)."""
        self._await_fence(self.store.use_fence(var.var_id))
        self.store.remove(var.var_id)

    # ------------------------------------------------------------------
    # iteration-start snapshot lifetime
    # ------------------------------------------------------------------
    def settle(self):
        """End of a call: stop keeping the previous state alive.  A replay
        of the closed iteration restarts from its start snapshot, and only
        a placeholder with neither a value nor a fetch future can ask for
        one.  Each such placeholder holds the snapshot, and the engine
        keeps weak references to them and to the snapshot, so the
        snapshot's buffers — the whole state before the call when every
        variable was written — are freed once the driver drops them."""
        pending = {k: t for k, t in self._tensors.items()
                   if t._eager is None and t._future is None
                   and t._iter == self.iter_id}
        if pending and self._snapshot_slot is not None:
            hold = _SnapshotHold(self._snapshot_slot)
            for t in pending.values():
                t._hold = hold
            self._snapshot_hold = weakref.ref(hold)
            self._tensors = weakref.WeakValueDictionary(pending)
        else:
            self._tensors = {}
        self._snapshot_slot = None
        self._var_binding = {}

    def cut_snapshot_hold(self):
        """The iteration clock moved on: placeholders of earlier
        iterations are stale and can no longer replay, so the snapshot
        they hold goes."""
        hold = self._snapshot_hold()
        if hold is not None:
            hold.snap = None
        self._snapshot_hold = no_hold

    def rollback_open_iteration(self):
        """Restore the store to the open iteration's start (an aborted
        call).  A steady-state call takes no snapshot: nothing to undo."""
        snap = self._snapshot_slot
        if snap is not None:
            self.store.restore(snap)
            ev.rollback(self.events, self.iter_id, len(snap))

    def start_snapshot(self):
        """The snapshot a replay of the current iteration restores: the
        open iteration's, or the one a placeholder of the closed iteration
        holds."""
        if self._snapshot_slot is not None:
            return self._snapshot_slot
        hold = self._snapshot_hold()
        if hold is None or hold.snap is None:
            raise RuntimeError("replay of a closed iteration whose start "
                               "snapshot was released")
        return hold.snap

    # ------------------------------------------------------------------
    # RNG
    # ------------------------------------------------------------------
    def next_rng_key(self):
        k = jax.random.fold_in(jax.random.fold_in(self._base_key,
                                                  self.iter_id),
                               self._rng_count)
        self._rng_count += 1
        return k
