"""Vmapped ASAP simulator: the topology-dispatched ASAP recurrence of
``repro.core.simulator`` expressed as a ``lax.scan`` over installment cells,
jitted and ``vmap``-ed over a batch of packed instances.

The recurrence per cell ``t`` (identical to the NumPy reference):

  **chain** communications, upstream to downstream (an inner scan over
  links, because store-and-forward makes ``cs[i, t]`` depend on
  ``ce[i-1, t]``):

      cs[i,t] = max( rel_t                 if i == 0,
                     ce[i-1, t]            if i >= 1,        # (1)
                     ce[i, t-1],                             # (2b)/(3b)
                     ce[i+1, t-1]          if i+1 <= m-2 )   # (2)/(3)
      ce[i,t] = cs[i,t] + dcomm[i,t]

  **star** communications: one serialized send chain on the master's port
  (the scan carry is simply the previous send's end, crossing cell
  boundaries):

      cs[i,t] = max( rel_t, previous send end )              # (1*)
      ce[i,t] = cs[i,t] + dcomm[i,t]

  computations (no intra-cell chain, a pure vector step — identical in both
  topologies because link i-1 feeds P_i in both):

      ps[i,t] = max( tau_i if t == 0 else pe[i, t-1],        # (10), (8)/(9)
                     rel_t if i == 0 else ce[i-1, t] )       # (6)
      pe[i,t] = ps[i,t] + dcomp[i,t]

  result-return phase (when the bucket activates it): chain results flow
  backward with store-and-forward + per-link serialization (a reversed inner
  scan); star results serialize on the master's receive port (a forward scan
  whose carry crosses cells); the makespan additionally covers every return
  arrival.

Everything runs in float64 (under :func:`repro.jaxenv.x64`); the operations
are the same IEEE max/add/mul the NumPy simulator performs, so results match
it to the last ulp in practice (parity-tested at <= 1e-9).

Padded cells/processors/links (see arena.py) carry zero durations — their
latency term, in the forward and return phases alike, is masked by
``cell_valid`` — so they can never push any time past the real makespan.
"""

from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from repro.core.schedule import Schedule
from repro.jaxenv import x64
from repro.obs.trace import span

from .arena import InstanceArena, PackedBucket

__all__ = ["simulate_bucket", "simulate_many", "makespans"]

_NEG = -jnp.inf  # identity for max over absent lower bounds


def _durations(bucket_arrays, gamma, topology, with_ret):
    """dcomm/dret [m-1, T], dcomp [m, T] for one instance (same math as
    schedule.comm/comp/ret_durations, with cell-validity masking)."""
    w_cell, z, latency, vcomm, vcomp, retr, valid = bucket_arrays
    m = gamma.shape[0]
    if m > 1:
        if topology == "star":
            vol = gamma[1:, :]  # link i carries worker i+1's own fraction
        else:
            # suffix[i] = sum_{k >= i} gamma[k] — same reversed-cumsum as NumPy
            vol = jnp.cumsum(gamma[::-1], axis=0)[::-1][1:, :]
        dcomm = (z[:, None] * vcomm[None, :] * vol + latency[:, None]) * valid[None, :]
        dret = (
            (z[:, None] * (retr * vcomm)[None, :] * vol + latency[:, None]) * valid[None, :]
            if with_ret else None
        )
    else:
        dcomm = jnp.zeros((0, gamma.shape[1]))
        dret = jnp.zeros((0, gamma.shape[1])) if with_ret else None
    dcomp = w_cell * vcomp[None, :] * gamma
    return dcomm, dcomp, dret


def _asap_chain(dcomm, dcomp, dret, rel, tau, with_ret):
    """Chain ASAP recurrence for one instance."""
    m = dcomp.shape[0]

    def cell_step(carry, xs):
        if with_ret:
            prev_ce, prev_pe, prev_re = carry  # [m-1], [m], [m-1]
            dcm_t, dcp_t, dr_t, rel_t = xs
        else:
            prev_ce, prev_pe = carry
            dcm_t, dcp_t, rel_t = xs

        if m > 1:
            # lower bounds known before the intra-cell chain:
            #   (2b)/(3b) own-port + (2)/(3) receive-after-forward + release
            ready = prev_ce
            ready = jnp.maximum(ready, jnp.concatenate([prev_ce[1:], jnp.full((1,), _NEG)]))
            ready = ready.at[0].max(rel_t)

            def link_step(up_ce, xs_i):
                ready_i, dcm_i, is_head = xs_i
                lo = jnp.maximum(ready_i, jnp.where(is_head, 0.0, up_ce))  # (1)
                lo = jnp.maximum(lo, 0.0)
                ce_i = lo + dcm_i
                return ce_i, (lo, ce_i)

            is_head = jnp.arange(m - 1) == 0
            _, (cs_t, ce_t) = lax.scan(link_step, _NEG, (ready, dcm_t, is_head))
        else:
            cs_t = jnp.zeros((0,))
            ce_t = jnp.zeros((0,))

        # computations: (8)/(9)+(10) via prev_pe (initialized to tau), (6)/(4r)
        recv = jnp.concatenate([jnp.full((1,), rel_t), ce_t]) if m > 1 else jnp.full((1,), rel_t)
        ps_t = jnp.maximum(prev_pe, recv)
        pe_t = ps_t + dcp_t
        if not with_ret:
            return (ce_t, pe_t), (cs_t, ce_t, ps_t, pe_t)

        # returns: backward store-and-forward (R1) + per-link serial (R2b)
        def ret_step(down_re, xs_i):
            pe_down, pre_i, dr_i = xs_i
            lo = jnp.maximum(pe_down, pre_i)  # (R6), (R2b)
            lo = jnp.maximum(lo, down_re)  # (R1)
            lo = jnp.maximum(lo, 0.0)
            re_i = lo + dr_i
            return re_i, (lo, re_i)

        _, (rs_t, re_t) = lax.scan(
            ret_step, _NEG, (pe_t[1:], prev_re, dr_t), reverse=True
        )
        return (ce_t, pe_t, re_t), (cs_t, ce_t, ps_t, pe_t, rs_t, re_t)

    n_links = max(m - 1, 0)
    dcm = jnp.moveaxis(dcomm, 1, 0)
    dcp = jnp.moveaxis(dcomp, 1, 0)
    if with_ret:
        init = (jnp.zeros(n_links), tau, jnp.zeros(n_links))
        xs = (dcm, dcp, jnp.moveaxis(dret, 1, 0), rel)
        _, (cs, ce, ps, pe, rs, re) = lax.scan(cell_step, init, xs)
        return cs.T, ce.T, ps.T, pe.T, rs.T, re.T
    init = (jnp.zeros(n_links), tau)
    _, (cs, ce, ps, pe) = lax.scan(cell_step, init, (dcm, dcp, rel))
    return cs.T, ce.T, ps.T, pe.T


def _asap_star(dcomm, dcomp, dret, rel, tau, with_ret):
    """Star ASAP recurrence: serialized master send/receive ports."""
    m = dcomp.shape[0]

    def cell_step(carry, xs):
        if with_ret:
            last_send, prev_pe, last_ret = carry  # scalar, [m], scalar
            dcm_t, dcp_t, dr_t, rel_t = xs
        else:
            last_send, prev_pe = carry
            dcm_t, dcp_t, rel_t = xs

        if m > 1:
            def link_step(c, dcm_i):  # (1*) one-port: carry = previous send end
                lo = jnp.maximum(c, rel_t)
                lo = jnp.maximum(lo, 0.0)
                ce_i = lo + dcm_i
                return ce_i, (lo, ce_i)

            last_send, (cs_t, ce_t) = lax.scan(link_step, last_send, dcm_t)
        else:
            cs_t = jnp.zeros((0,))
            ce_t = jnp.zeros((0,))

        recv = jnp.concatenate([jnp.full((1,), rel_t), ce_t]) if m > 1 else jnp.full((1,), rel_t)
        ps_t = jnp.maximum(prev_pe, recv)
        pe_t = ps_t + dcp_t
        if not with_ret:
            return (last_send, pe_t), (cs_t, ce_t, ps_t, pe_t)

        def ret_step(c, xs_i):  # (R1*) receive port: carry = previous return end
            pe_i, dr_i = xs_i
            lo = jnp.maximum(c, pe_i)  # (R6)
            lo = jnp.maximum(lo, 0.0)
            re_i = lo + dr_i
            return re_i, (lo, re_i)

        last_ret, (rs_t, re_t) = lax.scan(ret_step, last_ret, (pe_t[1:], dr_t))
        return (last_send, pe_t, last_ret), (cs_t, ce_t, ps_t, pe_t, rs_t, re_t)

    dcm = jnp.moveaxis(dcomm, 1, 0)
    dcp = jnp.moveaxis(dcomp, 1, 0)
    zero = jnp.float64(0.0)
    if with_ret:
        init = (zero, tau, zero)
        xs = (dcm, dcp, jnp.moveaxis(dret, 1, 0), rel)
        _, (cs, ce, ps, pe, rs, re) = lax.scan(cell_step, init, xs)
        return cs.T, ce.T, ps.T, pe.T, rs.T, re.T
    _, (cs, ce, ps, pe) = lax.scan(cell_step, (zero, tau), (dcm, dcp, rel))
    return cs.T, ce.T, ps.T, pe.T


def _sim_one(w_cell, z, latency, tau, vcomm, vcomp, rel, retr, valid, gamma,
             topology, with_ret):
    dcomm, dcomp, dret = _durations(
        (w_cell, z, latency, vcomm, vcomp, retr, valid), gamma, topology, with_ret
    )
    recur = _asap_star if topology == "star" else _asap_chain
    out = recur(dcomm, dcomp, dret, rel, tau, with_ret)
    if with_ret:
        cs, ce, ps, pe, rs, re = out
        mk = jnp.max(pe[:, -1]) if pe.shape[1] else jnp.float64(0.0)
        if re.size:
            mk = jnp.maximum(mk, jnp.max(re))
        return cs, ce, ps, pe, rs, re, mk
    cs, ce, ps, pe = out
    mk = jnp.max(pe[:, -1]) if pe.shape[1] else jnp.float64(0.0)
    return cs, ce, ps, pe, mk


@partial(jax.jit, static_argnums=(10, 11))
def _sim_batch(w_cell, z, latency, tau, vcomm, vcomp, rel, retr, valid, gamma,
               topology, with_ret):
    return jax.vmap(
        _sim_one, in_axes=(0, 0, 0, 0, 0, 0, 0, 0, None, 0, None, None)
    )(w_cell, z, latency, tau, vcomm, vcomp, rel, retr, valid, gamma,
      topology, with_ret)


def simulate_bucket(bucket: PackedBucket, gamma: np.ndarray):
    """ASAP-replay a [B, m, T] fraction batch.

    Always returns the fixed 7-slot shape ``(cs, ce, ps, pe, rs, re, mk)``;
    ``rs``/``re`` are None unless the bucket activates the result-return
    phase, so consumers never dispatch on tuple arity.

    ``gamma`` must already be padded to the bucket shape (see
    :meth:`PackedBucket.gamma_padded`); returned arrays are bucket-shaped —
    use :meth:`PackedBucket.unpad` to strip padding.
    """
    # numpy args go straight into the jitted call: its argument machinery
    # batches the host->device transfers, where a per-array ``jnp.asarray``
    # here costs ~100us each — the dominant cost of a small-bucket replay
    args = (
        bucket.w_cell, bucket.z, bucket.latency, bucket.tau,
        bucket.vcomm_cell, bucket.vcomp_cell, bucket.rel_cell,
    )
    with_ret = bool(bucket.has_returns) and bucket.m > 1
    with x64():
        retr = bucket.ret_cell
        valid = np.asarray(bucket.cell_valid, dtype=np.float64)
        g = np.asarray(gamma, dtype=np.float64)
        # host spans, as in the simplex: the call, then the fetch that waits
        with span("replay.dispatch", B=g.shape[0]):
            out = _sim_batch(*args, retr, valid, g, bucket.topology, with_ret)
        with span("replay.fetch", B=g.shape[0]):
            out = tuple(np.asarray(o) for o in out)
        if not with_ret:  # the 5 slots of a replay without returns, as 7
            out = out[:4] + (None, None) + out[4:]
        return out


def simulate_many(instances: list, gammas: list, pad_shapes: bool = True) -> list:
    """Batched counterpart of ``[simulate(i, g) for i, g in zip(...)]``.

    Returns a list of :class:`repro.core.schedule.Schedule` in caller order;
    numerically interchangeable with the NumPy simulator (<= 1e-9).
    """
    if len(instances) != len(gammas):
        raise ValueError("need one gamma per instance")
    arena = InstanceArena(instances, pad_shapes=pad_shapes)
    results = []
    for bucket in arena.buckets:
        g = bucket.gamma_padded([gammas[i] for i in bucket.indices])
        cs, ce, ps, pe, rs, re, mk = simulate_bucket(bucket, g)
        if rs is not None:
            rs, re = bucket.unpad(rs), bucket.unpad(re)
        cs, ce = bucket.unpad(cs), bucket.unpad(ce)
        ps, pe = bucket.unpad(ps), bucket.unpad(pe)
        scheds = [
            Schedule(
                instance=bucket.instances[b],
                gamma=np.asarray(gammas[bucket.indices[b]], dtype=np.float64),
                comm_start=cs[b],
                comm_end=ce[b],
                comp_start=ps[b],
                comp_end=pe[b],
                makespan=float(mk[b]),
                ret_start=rs[b] if rs is not None else None,
                ret_end=re[b] if re is not None else None,
            )
            for b in range(bucket.B)
        ]
        results.append(scheds)
    return arena.scatter(results)


def makespans(instances: list, gammas: list, pad_shapes: bool = True) -> np.ndarray:
    """Just the achieved makespans, [len(instances)] — the sweep fast path."""
    arena = InstanceArena(instances, pad_shapes=pad_shapes)
    per_bucket = []
    for bucket in arena.buckets:
        g = bucket.gamma_padded([gammas[i] for i in bucket.indices])
        *_, mk = simulate_bucket(bucket, g)
        per_bucket.append(list(np.asarray(mk)))
    return np.array(arena.scatter(per_bucket), dtype=np.float64)
