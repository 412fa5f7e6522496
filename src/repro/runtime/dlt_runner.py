"""DLT chain runner: execute a planner schedule on a linear device chain with
real JAX collectives (shard_map + ppermute), exactly mirroring the paper's
platform model:

  * all load data starts on stage 0 (the head pod holds the dataset);
  * per cell (load, installment), the chunk hops down the chain stage by
    stage (store-and-forward) via ``jax.lax.ppermute`` — one outstanding
    neighbour send per stage per step (the full one-port model, conservative
    on multi-port ICI; see DESIGN.md);
  * each stage extracts its planned sample range when the chunk arrives and
    accumulates its gradient contribution while later installments are still
    in flight (XLA schedules the ppermute sends asynchronously — the paper's
    comm/compute overlap);
  * gradients are weighted by sample counts and psum'd over the chain (and
    any data axes), then AdamW updates parameters.

The executed loss is bit-identical (up to reduction order) to a single-device
pass over the same samples — property-tested in tests/test_dlt_runner.py.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.config import ArchConfig, ShardingPolicy, TrainConfig
from repro.core.planner import DLTPlan, Planner
from repro.models import loss_fn
from repro.optim import adamw_update, cosine_lr

__all__ = ["stage_batches", "make_dlt_train_step", "ChainReplanner"]


class ChainReplanner:
    """Online replanning for a running platform, through the session front door.

    Owns a :class:`repro.core.planner.Planner` and shares its
    :class:`repro.api.Session` (backend handles + solution cache): every
    replan — straggler drift, stage failure, or a bulk what-if sweep — is
    stated as a (Problem, Policy) pair against the ``backend`` registry
    entry (the batched engine by default), and platform
    states the chain has seen before replay from the session's cache
    instead of re-solving.  The topology rides on the planner
    (``Planner(topology="star")`` replans a one-port master fleet with the
    same session plumbing); the historical name stays.
    """

    def __init__(self, planner: Planner, q: int | list = 2, backend="batched"):
        self.planner = planner
        self.q = q
        self.backend = backend
        # the planner's session owns the solution cache (created lazily on
        # first engine use) — touching it here just pins the sharing intent
        self.session = planner.session

    def stream(self, batches: list, policy=None, warm: bool = True):
        """Open an online :class:`repro.runtime.replan.EventStreamReplanner`
        for this chain's current problem.

        The streaming successor of the offline what-ifs below (``replan`` /
        ``on_failure`` / ``what_if_speeds``): instead of re-stating a
        hypothetical per call, feed typed events (``SpeedObserved``,
        ``ProcessorDown``, ...) to the returned replanner — each re-solve
        warm-starts from the previous exit basis through this replanner's
        session, and subscribers see every plan update.
        """
        from repro.api import Policy
        from repro.runtime.replan import EventStreamReplanner

        if policy is None:
            backend = self.backend if isinstance(self.backend, str) else "auto"
            policy = Policy(installments=self.q, backend=backend)
        return EventStreamReplanner(
            self.session, self.planner.to_problem(batches), policy,
            warm=warm,
            backend=None if isinstance(self.backend, str) else self.backend,
        )

    def replan(self, batches: list) -> DLTPlan:
        """One offline re-solve (see :meth:`stream` for the online path)."""
        return self.planner.plan(batches, q=self.q, backend=self.backend)

    def observe(self, stage: int, achieved_flops_per_sec: float, batches: list):
        """EWMA speed feedback; returns a fresh plan when drift demands one."""
        if self.planner.observe_step_time(stage, achieved_flops_per_sec):
            return self.replan(batches)
        return None

    def on_failure(self, dead: int, batches: list, restore_delay: float = 0.0):
        """Stage loss: fuse links, carry the cache over, batched re-solve."""
        p2, plan = self.planner.replan_without_stage(
            dead, batches, restore_delay=restore_delay, q=self.q, backend=self.backend
        )
        self.planner = p2
        return plan

    def auto_installments(
        self, batches: list, t_max: int = 8, installment_cost: float = 0.0
    ):
        """Cost-aware installment chooser for the running chain: one batched
        sweep (``Planner.plan_auto_T``) through this replanner's backend and
        cache.  Returns the :class:`repro.core.planner.AutoTResult`."""
        return self.planner.plan_auto_T(
            batches,
            t_max=t_max,
            installment_cost=installment_cost,
            backend=self.backend,
        )

    def what_if_speeds(self, batches: list, speed_scales) -> np.ndarray:
        """Straggler sensitivity: predicted makespan per speed scenario.

        ``speed_scales`` is [S, m] multipliers on the stages' effective
        FLOP/s; all S hypothetical problems solve in one session bulk call.
        Returns the S predicted makespans.
        """
        import dataclasses as _dc

        from repro.api import Policy

        problems = []
        m = len(self.planner.stages)
        for scales in np.atleast_2d(np.asarray(speed_scales, dtype=np.float64)):
            if scales.shape != (m,):
                raise ValueError(
                    f"speed_scales rows must have one entry per stage ({m}), "
                    f"got {scales.shape}"
                )
            stages = [
                _dc.replace(s, flops_per_sec=s.flops_per_sec * float(f))
                for s, f in zip(self.planner.stages, scales)
            ]
            p = Planner(stages, self.planner.links, ewma=self.planner.ewma,
                        topology=self.planner.topology, session=self.session)
            problems.append(p.to_problem(batches))
        backend = self.backend if isinstance(self.backend, str) else "auto"
        arts = self.session.solve_bulk(
            problems,
            Policy(installments=self.q, backend=backend),
            backend=None if isinstance(self.backend, str) else self.backend,
        )
        return np.array([a.makespan for a in arts])


def stage_batches(plan: DLTPlan, batches: list, n_stages: int):
    """Stack the per-cell host batches for the runner.

    Returns (tokens [T, cap, S], labels [T, cap, S], counts [T, n_stages]):
    every cell padded to the largest cell size; data logically lives on stage 0
    (the runner scatters it there).
    """
    T = len(plan.cells)
    caps = [int(np.sum(plan.samples[t])) for t in range(T)]
    cap = max(caps)
    tok_list, lab_list = [], []
    consumed = {n: 0 for n in range(len(batches))}
    for t, (n, _) in enumerate(plan.cells):
        k = caps[t]
        start = consumed[n]
        tok = batches[n]["tokens"][start : start + k]
        lab = batches[n]["labels"][start : start + k]
        consumed[n] += k
        pad = cap - k
        if pad:
            tok = np.concatenate([tok, np.zeros((pad,) + tok.shape[1:], tok.dtype)])
            lab = np.concatenate([lab, np.zeros((pad,) + lab.shape[1:], lab.dtype)])
        tok_list.append(tok)
        lab_list.append(lab)
    counts = np.array([[int(c) for c in plan.samples[t]] for t in range(T)], dtype=np.int32)
    return np.stack(tok_list), np.stack(lab_list), counts


def make_dlt_train_step(
    cfg: ArchConfig,
    policy: ShardingPolicy,
    tcfg: TrainConfig,
    mesh,
    n_cells: int,
    stage_axis: str = "stage",
):
    """Build the jitted chain train step for a fixed number of cells.

    Signature: step(state, tokens [T,cap,S], labels [T,cap,S],
                    counts [T,m]) -> (state, metrics).
    ``tokens``/``labels`` are replicated inputs; the chain flow (who holds
    which samples when) happens inside via ppermute — on hardware the inputs
    are fed only to stage 0's hosts and the ppermute hops are the actual
    inter-pod transfers.
    """
    m = mesh.shape[stage_axis]

    def chain_loss(params, tokens, labels, counts):
        """Runs inside shard_map over the stage axis; returns (loss, weight)."""
        idx = jax.lax.axis_index(stage_axis)
        total = jnp.float32(0.0)
        weight = jnp.float32(0.0)
        for t in range(n_cells):
            chunk_tok, chunk_lab = tokens[t], labels[t]
            cnt = counts[t]  # [m]
            offs = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(cnt)[:-1]])
            cap = chunk_tok.shape[0]
            # the chunk hops down the chain; stage i sees valid data after i hops
            buf_t, buf_l = chunk_tok, chunk_lab
            for hop in range(m):
                if hop > 0:
                    perm = [(s, s + 1) for s in range(m - 1)]
                    buf_t = jax.lax.ppermute(buf_t, stage_axis, perm)
                    buf_l = jax.lax.ppermute(buf_l, stage_axis, perm)
                arrived = (idx == hop).astype(jnp.float32)
                sample = jnp.arange(cap)
                mine = (sample >= offs[hop]) & (sample < offs[hop] + cnt[hop])
                w = mine.astype(jnp.float32) * arrived
                n_mine = w.sum()
                batch = {"tokens": buf_t, "labels": buf_l, "mask": w[:, None] * jnp.ones_like(buf_l, jnp.float32)}
                l, _ = loss_fn(params, cfg, policy, batch)
                total = total + l * n_mine
                weight = weight + n_mine
        # aggregate over the chain (and data axes if present)
        total = jax.lax.psum(total, stage_axis)
        weight = jax.lax.psum(weight, stage_axis)
        return total / jnp.maximum(weight, 1.0)

    param_spec = P()  # replicated across the stage axis (DP chain)

    smapped = shard_map(
        chain_loss,
        mesh=mesh,
        in_specs=(param_spec, P(), P(), P()),
        out_specs=P(),
        check_vma=False,
    )

    def step(state, tokens, labels, counts):
        def loss_of(params):
            return smapped(params, tokens, labels, counts)

        loss, grads = jax.value_and_grad(loss_of)(state.params)
        lr = cosine_lr(state.opt.step, tcfg.lr, tcfg.warmup_steps, tcfg.total_steps)
        new_params, new_opt, om = adamw_update(
            grads, state.opt, state.params,
            lr=lr, beta1=tcfg.beta1, beta2=tcfg.beta2, eps=tcfg.eps,
            weight_decay=tcfg.weight_decay, grad_clip=tcfg.grad_clip,
        )
        from .train import TrainState

        return TrainState(new_params, new_opt), {"loss": loss, "lr": lr, **om}

    return jax.jit(step)
