"""Host-maestro oracle for collective tapes.

``HostMaestro`` runs the SAME compiled comm DAG as the device tape,
but the way the SMPI maestro would: all schedule bookkeeping (pred
counts, ready dates, fault cursor, the clock) lives on the HOST, and
the device is consulted once per advance for the rate fixpoint plus
once for the forced decrement — >= 2 dispatches and >= 2 fetches per
advance, with every activation and fault costing an extra scatter
upload.  That is the baseline the tape path's one-dispatch-per-K
supersteps are measured against (bench.py --stage collective), and
the bit-identity reference of check_determinism --runtime-collective.

Bit-identity is by construction, not by tolerance: the maestro replays
the exact per-advance recurrence of ops.lmm_drain._superstep_program
(has_coll arm) —

* rates from the same ``fixpoint`` program over the same device
  arrays;
* ``dt_plan = min(rem / rate)`` in the solve dtype (elementwise IEEE
  division and min match the device reduction);
* the event peek: ``next_t = min(fault date, min(ready))``, fire iff
  ``next_t <= now + dt_plan`` (ties to the event), dt clamped to land
  exactly on the date;
* remains decremented ON DEVICE via ``_drain_forced_advance`` — the
  ``_rounded_product`` FMA-pinning detour is the one piece of advance
  math that must not be re-derived on host;
* the clock accumulated by the same compensated (Kahan) pair, one
  python-float step per advance — grouping K advances per dispatch
  leaves the recurrence unchanged, which is the whole invariant.

The solve dtype is the one ``lmm/dtype:auto`` resolves, as the tape's:
float64 on the CPU, where all of the above is bit for bit, float32 on
the TPU, where the dates and the clock pair stay float64 and only the
rates, the remains and ``dt`` are narrow (the program's own split) and
the device's divide is not numpy's to the bit.

Event streams come out in the device's order: completions by flow
slot, then the fault entry, then activations by flow slot, all at the
advance's Kahan clock.
"""

from __future__ import annotations

import functools
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from ..ops import opstats
from ..ops.lmm_drain import (_MAX_ROUNDS, _ZERO_BITS,
                             _drain_forced_advance, DrainSim)
from ..ops.lmm_jax import fixpoint
from .tape import DeviceCollective


@functools.partial(jax.jit, static_argnames=("eps", "n_c", "n_v",
                                             "has_bounds"))
def _maestro_solve(e_var, e_cnst, e_w, c_bound, pen, v_bound,
                   eps: float, n_c: int, n_v: int,
                   has_bounds: bool = False):
    """One solve-to-convergence dispatch: the same fixpoint call the
    superstep body makes, minus the surrounding while_loop."""
    dtype = e_w.dtype
    out = fixpoint(e_var, e_cnst, e_w, c_bound, jnp.zeros(n_c, bool),
                   pen, v_bound, jnp.asarray(eps, dtype), n_c, n_v,
                   parallel_rounds=True, carry=None,
                   max_rounds=_MAX_ROUNDS, return_carry=True,
                   has_bounds=has_bounds, has_fatpipe=False)
    carry2 = out[4]
    return carry2[0], out[3], jnp.count_nonzero(carry2[4])


class HostMaestro:
    """Drive a DeviceCollective one advance per dispatch, host-side."""

    def __init__(self, dc: DeviceCollective, tape=None, device=None,
                 eps: float = 1e-5, done_eps: float = 1e-4,
                 dtype="auto"):
        self.dc = dc
        self.n_v = dc.n_v
        self.n_c = dc.n_c
        self.sim = DrainSim(dc.e_var, dc.e_cnst, dc.e_w, dc.c_bound,
                            dc.sizes, dtype=dtype, device=device,
                            eps=eps, done_eps=done_eps,
                            penalty=dc.penalty0,
                            repack_min=1 << 62)
        self.pred = dc.pred0.astype(np.int64).copy()
        self.ready = dc.ready0.astype(np.float64).copy()
        self.exec_cost = dc.exec_cost
        em = dc.edge_dst < dc.n_v          # drop the pad row
        self.edge_src = dc.edge_src[em]
        self.edge_dst = dc.edge_dst[em]
        if tape is not None and len(tape[0]):
            self.tape_t = np.asarray(tape[0], np.float64)
            self.tape_slot = np.asarray(tape[1], np.int32)
            self.tape_val = np.asarray(tape[2], np.float64)
        else:
            self.tape_t = np.zeros(0)
            self.tape_slot = np.zeros(0, np.int32)
            self.tape_val = np.zeros(0)
        self.tpos = 0
        self.t = 0.0
        self.comp = 0.0                    # Kahan compensation term
        self.events: list = []
        self.collective_events: list = []
        self.fault_events: list = []
        self.advances = 0
        self.dispatches = 0
        self.fetches = 0

    # -- one maestro advance ----------------------------------------------

    def _advance(self) -> None:
        s = self.sim
        rates_dev, rounds, n_light = _maestro_solve(
            *s._dev, s._cb, s._pen, s._vb, eps=s.eps, n_c=s.n_c,
            n_v=s.n_v, has_bounds=s.has_bounds)
        self.dispatches += 1
        opstats.bump("dispatches")
        if int(n_light):
            raise RuntimeError("maestro solve did not converge")
        rates = opstats.timed_fetch(rates_dev)
        pen = opstats.timed_fetch(s._pen)
        rem = opstats.timed_fetch(s._rem)
        self.fetches += 3

        live = pen > 0
        rate = np.where(live, rates, 0.0)
        flowing = live & (rate > 0)
        q = rem / np.where(flowing, rate, 1.0)
        dt_plan = float(np.min(np.where(flowing, q, np.inf))) \
            if len(q) else float("inf")

        next_ft = (float(self.tape_t[self.tpos])
                   if self.tpos < len(self.tape_t) else float("inf"))
        next_at = float(np.min(self.ready))
        now = self.t
        next_t = min(next_ft, next_at)
        fire = np.isfinite(next_t) and next_t <= now + dt_plan
        # dt is the solve dtype's, as the remains it decrements
        dt = float(s.dtype.type(max(next_t - now, 0.0))) if fire \
            else dt_plan
        if not np.isfinite(dt):
            raise RuntimeError(
                f"collective schedule deadlocked: "
                f"{len(self.events)}/{self.n_v} flows completed and "
                f"nothing is pending")

        s._pen, s._rem, out = _drain_forced_advance(
            s._pen, s._rem, s._thresh, rates_dev,
            jnp.asarray(dt, s.dtype), _ZERO_BITS)
        self.dispatches += 1
        opstats.bump("dispatches")
        out = opstats.timed_fetch(out)
        self.fetches += 1
        done = out[1:] > 0
        self.advances += 1

        # Kahan clock, one python-float step — the same compensated
        # recurrence the superstep body runs in-dispatch
        y = dt - self.comp
        t_new = self.t + y
        self.comp = (t_new - self.t) - y
        self.t = t_new

        for fid in np.flatnonzero(done):
            self.events.append((t_new, int(fid)))

        if fire and next_ft <= next_at:          # fault entry
            slot = int(self.tape_slot[self.tpos])
            val = float(self.tape_val[self.tpos])
            s.apply_transitions({"c_bound": ([slot], [val])})
            self.dispatches += 1
            self.fault_events.append((t_new, slot))
            self.tpos += 1

        acts = np.zeros(0, np.int64)
        if fire and next_at <= next_ft:          # activations
            acts = np.flatnonzero(self.ready <= next_t)
            for fid in acts:
                self.collective_events.append((t_new, int(fid)))
            self.ready[acts] = np.inf

        # DAG walk: completions decrement successors; flows reaching
        # zero get ready = t_new + exec on a LATER advance
        if done.any():
            m = done[self.edge_src]
            pred_before = self.pred.copy()
            np.add.at(self.pred, self.edge_dst[m], -1)
            newly = (self.pred <= 0) & (pred_before > 0)
            self.ready[newly] = t_new + self.exec_cost[newly]
        if len(acts):
            s.apply_transitions(
                {"v_penalty": (acts, np.ones(len(acts)))})
            self.dispatches += 1

    def run(self, max_advances: int = 10_000_000) -> None:
        budget = max_advances
        while len(self.events) < self.n_v and budget > 0:
            self._advance()
            budget -= 1
        if len(self.events) < self.n_v:
            raise RuntimeError("maestro exceeded its advance budget")

    # oracle hooks ---------------------------------------------------------

    @property
    def clock(self):
        """(t, compensation) — compare bitwise against the tape sim's
        carried coll_clk pair."""
        return (self.t, self.comp)
