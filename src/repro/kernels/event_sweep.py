"""Pallas kernel for the event-level MC sweep (``engine_kind="pallas"``).

The sim engine's fast path (``sim/engine.py::_run_one_event``) is a
``lax.scan`` with one iteration per FAILURE, double-vmapped over
(grid points x trials).  This kernel is the accelerator-native port:

* the failure-gap schedule is laid out capacity-first, ``(F, B, N)``, so
  one gap slab ``gaps_ref[g]`` is a whole ``(bp, bt)`` tile: the dynamic
  index is on the untiled leading axis, never on the 128-lane axis.
* grid = ``(points/bp, trials/bt, F/fb)``.  Each ``(bp, bt)`` tile of
  trajectories keeps its state in VMEM scratch while the capacity axis
  streams through in ``(fb, bp, bt)`` gap blocks (the last grid axis,
  sequential).  The VMEM a tile needs is therefore bounded by ``fb``
  whatever capacity bucket the engine dispatches: ``fb`` is sized so
  the double-buffered gap block stays within :data:`GAP_BLOCK_BYTES`.
* the closed-form between-failure arithmetic is kept TERM-FOR-TERM from
  ``_run_one_event`` (same expressions, same parenthesization, same
  select ordering), so in f64 the kernel is bit-identical to the scan —
  the dyadic-schedule parity tests assert exactly that.
* the gap index needs no per-lane gather: an ACTIVE (not-done) lane at
  loop iteration ``i`` has seen exactly ``i`` failures (any earlier
  completion freezes the lane through the done-select), so
  ``n_fail == i`` and one uniform slab load per iteration serves every
  active lane; done lanes read a stale slab and discard it in the same
  select the scan kernel uses.
* unlike the fixed-length scan, each gap block runs a ``while_loop``
  that exits as soon as every lane in the tile is done (and later
  blocks run no iteration at all).  Post-completion iterations are
  identities under the done-select, so the exit is bit-exact.
* Mosaic constraints: the flags (``used_inf``, ``done``, the two bool
  outputs) are int32 in the kernel, and every loop carry starts from a
  tile loaded out of scratch rather than from a splat constant.

Precision follows the engine's :class:`~repro.sim.precision
.PrecisionPolicy`: under ``f64`` the state updates are the scan
kernel's verbatim; under a compensated policy every running-sum state
(wall, committed, work, io, down) becomes a Neumaier pair ``(s, c)``
(``sim/precision.py::comp_add``), branch contributions are formed as
increments and selected BEFORE accumulation, and the remaining-work
read uses the corrected ``committed + c`` — the parity gates in
tests/test_pallas_engine.py bound the result against the f64 oracle.

On the CPU backend the wrapper runs ``pallas_call(..., interpret=True)``
(traced to plain XLA ops, jit-compatible) so tier-1 parity runs there;
on a TPU it lowers to Mosaic, which has no f64 — the ``f64`` policy is
refused there.  Any other backend is an error, never a silent
interpret-mode fallback.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..sim.precision import comp_add
from .ops import interpret_mode

#: work-completion slack — MUST match sim/engine.py::_EPS term-for-term.
_EPS = 1e-12

#: VMEM the double-buffered gap block of one tile may take (bytes).  At
#: the default ``8 x 128`` tile in f32 this streams 1024 gaps per block;
#: the scoped-VMEM limit Mosaic applies by default is 16 MiB.
GAP_BLOCK_BYTES = 8 << 20


def gap_block(F: int, bp: int, bt: int, itemsize: int) -> int:
    """Gaps per streamed block: the whole capacity when it fits, else the
    largest power of two whose double buffer fits GAP_BLOCK_BYTES."""
    per_gap = 2 * bp * bt * itemsize
    fb = max(1, GAP_BLOCK_BYTES // per_gap)
    if F <= fb:
        return F
    return 1 << (fb.bit_length() - 1)


def _event_kernel(T_ref, C_ref, R_ref, D_ref, O_ref, TB_ref, gaps_ref,
                  wall_ref, work_ref, io_ref, down_ref, nfail_ref,
                  nckpt_ref, trunc_ref, ginf_ref, *state_refs, n_steps: int,
                  n_gaps: int, compensated: bool):
    f = gaps_ref.dtype
    zero = jnp.zeros((), f)
    one = jnp.ones((), f)
    # int32 scalars: a Python int would trace as int64 under the caller's
    # x64 context, which Mosaic cannot lower.
    i32 = lambda v: jnp.asarray(v, jnp.int32)
    fb = gaps_ref.shape[0]
    k_blk = pl.program_id(2)
    last_blk = pl.num_programs(2) - 1

    # state_refs = (wall, committed, work_exec, io_time, down_time,
    #               n_fail, n_ckpt, used_inf, done [, 5 Neumaier c-terms])
    # — VMEM scratch that persists across the capacity grid axis.
    @pl.when(k_blk == 0)
    def _init():
        for r in state_refs:
            r[...] = jnp.zeros(r.shape, r.dtype)

    T = T_ref[...]                       # (bp, 1) — broadcasts over trials
    C = C_ref[...]
    R = R_ref[...]
    D = D_ref[...]
    omega = O_ref[...]
    T_base = TB_ref[...]
    Tc = T - C                           # compute-segment length
    w = T - (one - omega) * C            # work committed per full period
    omega_safe = jnp.where(omega > zero, omega, one)

    # This block serves failures [lo, hi); the last block also runs the
    # past-the-schedule iterations (inf gap) up to the step budget.
    lo = k_blk * fb
    hi = jnp.where(k_blk == last_blk, i32(n_steps),
                   jnp.minimum(lo + fb, i32(n_steps)))

    def cond(carry):
        # "some lane not done" as an int32 min (jnp.all's bool reduction
        # does not lower under x64).
        return (carry[0] < hi) & (jnp.min(carry[9]) == 0)

    def body(carry):
        (i, wall, committed, work_exec, io_time, down_time,
         n_fail, n_ckpt, used_inf, done) = carry[:10]
        if compensated:
            c_wall, c_comm, c_work, c_io, c_down = carry[10:]

        # Uniform slab read: active lanes have n_fail == i (see module
        # docstring), so one dynamic index on the capacity axis serves
        # every lane, as in the scan kernel; past-the-schedule reads are
        # inf == "no more failures", flagging exhaustion.
        in_range = i < n_gaps
        slab = gaps_ref[jnp.clip(i - lo, i32(0), i32(fb - 1))]
        g = jnp.where(in_range, slab, jnp.asarray(jnp.inf, f))

        # ---- closed-form completion time from this segment start ----
        # (verbatim from sim/engine.py::_run_one_event)
        committed_true = committed + c_comm if compensated else committed
        rem = T_base - committed_true
        j = jnp.maximum(jnp.floor((rem - _EPS) / w), zero)
        r = rem - j * w
        rr = r - Tc
        t_in = jnp.where(rr > zero, Tc + rr / omega_safe, r)
        t_fin = j * T + t_in
        complete = t_fin < g

        # ---- branch B geometry: failure at s = g after segment start ----
        s = jnp.where(jnp.isfinite(g), g, zero)
        k = jnp.floor(s / T)
        k = jnp.where((k > zero) & (k * T >= s), k - one, k)
        u = s - k * T
        uc = u - Tc

        def sel(a_val, b_val):
            return jnp.where(complete, a_val, b_val)

        is_done = done != 0
        keep = lambda old, upd: jnp.where(is_done, old, upd)
        flags = (jnp.where(in_range, used_inf, i32(1)),
                 jnp.where(complete, i32(1), done))
        counts = (sel(n_fail, n_fail + 1).astype(jnp.int32),
                  (n_ckpt + sel(j, k).astype(jnp.int32)).astype(jnp.int32))

        if not compensated:
            wall_a = wall + t_fin
            work_a = work_exec + rem
            io_a = io_time + j * C + jnp.maximum(rr, zero) / omega_safe
            work_b = work_exec + k * w + jnp.where(uc > zero,
                                                   Tc + omega * uc, u)
            io_b = io_time + k * C + jnp.maximum(uc, zero) + R
            wall_b = (wall + s) + D + R
            committed_b = jnp.where(k >= one,
                                    committed + (k - one) * w + Tc,
                                    committed)
            new = (sel(wall_a, wall_b),
                   sel(committed, committed_b),
                   sel(work_a, work_b),
                   sel(io_a, io_b),
                   sel(down_time, down_time + D)) + counts + flags
            return (i + 1,) + tuple(
                keep(o, u_) for o, u_ in zip(carry[1:10], new))

        # Compensated policy: form each branch's CONTRIBUTION, select it,
        # then fold it into the Neumaier pair; the done-select freezes
        # both pair members, preserving the s + c invariant lane-by-lane.
        inc_wall = sel(t_fin, s + D + R)
        inc_comm = sel(zero, jnp.where(k >= one, (k - one) * w + Tc, zero))
        inc_work = sel(rem, k * w + jnp.where(uc > zero,
                                              Tc + omega * uc, u))
        inc_io = sel(j * C + jnp.maximum(rr, zero) / omega_safe,
                     k * C + jnp.maximum(uc, zero) + R)
        inc_down = sel(zero, D)
        pairs = [comp_add(s_, c_, x_) for s_, c_, x_ in (
            (wall, c_wall, inc_wall), (committed, c_comm, inc_comm),
            (work_exec, c_work, inc_work), (io_time, c_io, inc_io),
            (down_time, c_down, inc_down))]
        new = tuple(p[0] for p in pairs) + counts + flags
        new_c = tuple(p[1] for p in pairs)
        return ((i + 1,)
                + tuple(keep(o, u_) for o, u_ in zip(carry[1:10], new))
                + tuple(keep(o, u_) for o, u_ in zip(carry[10:], new_c)))

    carry = (lo,) + tuple(r[...] for r in state_refs)
    carry = lax.while_loop(cond, body, carry)
    for r, v in zip(state_refs, carry[1:]):
        r[...] = v

    @pl.when(k_blk == last_blk)
    def _finish():
        (wall, _, work_exec, io_time, down_time,
         n_fail, n_ckpt, used_inf, done) = carry[1:10]
        if compensated:
            c_wall, _, c_work, c_io, c_down = carry[10:]
            wall = wall + c_wall
            work_exec = work_exec + c_work
            io_time = io_time + c_io
            down_time = down_time + c_down
        wall_ref[...] = wall
        work_ref[...] = work_exec
        io_ref[...] = io_time
        down_ref[...] = down_time
        nfail_ref[...] = n_fail
        nckpt_ref[...] = n_ckpt
        trunc_ref[...] = i32(1) - done
        ginf_ref[...] = used_inf


def event_sweep(T, C, R, D, omega, T_base, gaps, *, n_steps: int,
                dtype="float64", compensated: bool = False,
                block_points: int = 8, block_trials: int = 128,
                force_interpret: bool | None = None) -> dict:
    """Run the event kernel over a ``(B,) x (B, N, F)`` workload.

    ``T``/``C``/``R``/``D``/``omega``/``T_base``: per-grid-point scalars,
    shape ``(B,)``; ``gaps``: failure schedules ``(B, N, F)``.  Returns
    the engine's per-trajectory output dict, shape ``(B, N)`` per key
    (floats delivered in f64 like the scan kernels, whatever the compute
    ``dtype``; cast back happens under the caller's x64 context).

    Inputs are padded to block multiples by edge replication — replica
    lanes complete exactly like the originals, so the all-done early
    exit still fires; their outputs are sliced off.
    """
    dt = jnp.dtype(dtype)
    interpret = interpret_mode(force_interpret)
    if not interpret and dt.itemsize > 4:
        raise ValueError(f"the Pallas event kernel cannot compute in {dt} "
                         f"on a TPU (Mosaic has no f64); use the "
                         f"compensated_f32 policy or engine_kind='event'")
    gaps = jnp.asarray(gaps, dt)
    B, N, F = gaps.shape
    bp = min(int(block_points), B)  # reprolint: disable=RPL004 (keyword-only static Python int by contract — block shapes must be concrete to build the pallas grid)
    bt = min(int(block_trials), N)  # reprolint: disable=RPL004 (keyword-only static Python int by contract — block shapes must be concrete to build the pallas grid)
    fb = gap_block(F, bp, bt, dt.itemsize)
    Bp = -(-B // bp) * bp
    Np = -(-N // bt) * bt
    Fp = -(-F // fb) * fb
    col = lambda x: jnp.pad(jnp.asarray(x, dt).reshape(B, 1),
                            ((0, Bp - B), (0, 0)), mode="edge")
    # Capacity-first layout; the capacity padding is never read as a gap
    # (the kernel treats every index >= F as past the schedule).
    gaps = jnp.pad(jnp.transpose(gaps, (2, 0, 1)),
                   ((0, Fp - F), (0, Bp - B), (0, Np - N)), mode="edge")

    kernel = functools.partial(_event_kernel, n_steps=int(n_steps),  # reprolint: disable=RPL004 (static loop bound — the while_loop's worst-case trip count is baked into the kernel)
                               n_gaps=F, compensated=bool(compensated))
    # (An int32 zero: a literal 0 would be int64 under the x64 context.)
    pspec = pl.BlockSpec((bp, 1), lambda i, j, k: (i, jnp.int32(0)))
    ospec = pl.BlockSpec((bp, bt), lambda i, j, k: (i, j))
    oshape = lambda d: jax.ShapeDtypeStruct((Bp, Np), d)
    n_float = 5 + (5 if compensated else 0)
    scratch = ([pltpu.VMEM((bp, bt), dt)] * 5
               + [pltpu.VMEM((bp, bt), jnp.int32)] * 4
               + [pltpu.VMEM((bp, bt), dt)] * (n_float - 5))
    outs = pl.pallas_call(
        kernel,
        grid=(Bp // bp, Np // bt, Fp // fb),
        in_specs=[pspec] * 6 + [pl.BlockSpec((fb, bp, bt),
                                             lambda i, j, k: (k, i, j))],
        out_specs=[ospec] * 8,
        out_shape=[oshape(dt)] * 4 + [oshape(jnp.int32)] * 4,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(col(T), col(C), col(R), col(D), col(omega), col(T_base), gaps)
    wall, work, io, down, n_fail, n_ckpt, trunc, ginf = (
        o[:B, :N] for o in outs)
    as_f64 = lambda x: jnp.asarray(x, jnp.float64)
    return {"wall_time": as_f64(wall), "work_executed": as_f64(work),
            "io_time": as_f64(io), "down_time": as_f64(down),
            "n_failures": n_fail, "n_checkpoints": n_ckpt,
            "truncated": trunc != 0, "gaps_exhausted": ginf != 0}
