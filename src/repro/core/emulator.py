"""Emulation framework (paper §5.1): real DLRM training with the failure &
overhead characteristics of the production cluster projected onto simulated
time.

Real computation: the DLRM actually trains on the (synthetic) click log and
the final test AUC is actually measured — failures really clear/revert
embedding-table shards, so accuracy degradation is measured, not modeled.
Simulated time: each optimizer step advances the clock by
``T_total / n_steps``; checkpoint saves and failure handling charge the
overhead ledger per the production-projected ``SystemParams``.

Full recovery exploits replay determinism (reverting all state and replaying
the same batches reproduces the pre-failure trajectory exactly) so it only
charges time, which is also the paper's observation that full recovery
matches the no-failure accuracy.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import trackers as trk
from repro.core.failure import FailureInjector
from repro.core.manager import CPRManager
from repro.core.sharded_checkpoint import load_latest_auto
from repro.core.tracing import span, step_span
from repro.metrics.classification import log_loss, roc_auc
from repro.models import dlrm as D
from repro.optim.optimizers import apply_updates, get_optimizer


@dataclass
class EmulationResult:
    auc: float
    logloss: float
    final_loss: float
    report: dict
    n_steps: int

    def summary(self):
        o = self.report["overheads"]
        return (f"{self.report['mode']:>9s} auc={self.auc:.4f} "
                f"pls={self.report['measured_pls']:.4f} "
                f"ovh={100 * o['fraction']:.2f}% "
                f"(save={o['save']:.2f}h load={o['load']:.2f}h "
                f"lost={o['lost']:.2f}h res={o['resched']:.2f}h)")


class Emulator:
    def __init__(self, dlrm_cfg, dataset, manager: CPRManager,
                 injector: FailureInjector, batch_size=512, lr=0.02,
                 seed=0, eval_frac=0.1, use_kernel=False, optimizer=None):
        self.cfg = dlrm_cfg
        self.ds = dataset
        self.mgr = manager
        self.injector = injector
        self.batch_size = batch_size
        self.lr = lr
        self.seed = seed
        self.eval_frac = eval_frac
        self.use_kernel = use_kernel
        if use_kernel:
            from repro.kernels import ops
            ops.require_lowering("embedding_bag")
        # any Optimizer whose state carries row-wise accumulators under
        # state["acc"] (extra top-level entries — step counters, momenta —
        # are preserved across failure restores)
        self.optimizer = optimizer
        self.final_params = None
        self.final_ostate = None

    def _build_step(self):
        cfg, mgr = self.cfg, self.mgr
        opt = self.optimizer or get_optimizer("rowwise_adagrad", self.lr)
        mode = mgr.mode if mgr.is_priority else None
        big = mgr.big_tables if mgr.is_priority else []
        period = mgr.ssu_period

        def loss_fn(p, batch):
            # the scope sits inside the differentiated function, so backward
            # ops carry transpose(jvp(forward)) in their op names
            with jax.named_scope("forward"):
                return D.dlrm_loss(p, batch, cfg, self.use_kernel)

        @jax.jit
        def step(params, ostate, tracker, batch):
            (loss, _), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
            with jax.named_scope("optimizer"):
                updates, ostate = opt.update(grads, ostate, params)
                params = apply_updates(params, updates)
            with jax.named_scope("tracker"):
                def ids(t):
                    return D.table_ids(cfg, batch["sparse"], t)
                if mode == "cpr-mfu":
                    tracker = {t: trk.mfu_update(tracker[t], ids(t))
                               for t in big}
                elif mode == "cpr-ssu":
                    tracker = {t: trk.ssu_update(tracker[t], ids(t), period,
                                                 backend=mgr.tracker_backend)
                               for t in big}
            return params, ostate, tracker, loss

        return step, opt

    def run(self, max_steps: Optional[int] = None,
            resume_from: Optional[str] = None) -> EmulationResult:
        cfg, mgr = self.cfg, self.mgr
        params = D.init_dlrm(cfg, jax.random.PRNGKey(self.seed))
        step_fn, opt = self._build_step()
        ostate = opt.init(params)
        if resume_from:
            # disk-mode full recovery: embedding shards + optimizer rows +
            # the trainer replica (every dense leaf) all come back from the
            # last consistent checkpoint cycle, whichever store layout
            # (flat or per-shard fleet) wrote it.  load_latest_auto resolves
            # the run-versioned CURRENT pointer first, so a prior run that
            # crashed before its first fence is transparently skipped in
            # favor of the newest *stamped* run
            loaded = load_latest_auto(
                resume_from, [np.asarray(t) for t in params["tables"]],
                [np.asarray(a) for a in ostate["acc"]["tables"]], mgr.spec,
                trainer_state=D.trainer_state(params))
            r_t, r_a, trainer = loaded.restore_all()
            params = {**params, "tables": [jnp.asarray(x) for x in r_t]}
            if trainer is not None:
                params = {**params,
                          **jax.tree.map(jnp.asarray, trainer)}
            ostate = {**ostate,
                      "acc": {**ostate["acc"],
                              "tables": [jnp.asarray(x) for x in r_a]}}
        tracker = mgr.tracker_init(params["tables"])
        mgr.attach_store(params["tables"], ostate["acc"]["tables"],
                         D.trainer_state(params))

        (tr0, tr1), (ev0, ev1) = self.ds.eval_split(self.eval_frac)
        n_train = tr1 - tr0
        n_steps = n_train // self.batch_size
        if max_steps:
            n_steps = min(n_steps, max_steps)
        mgr.set_total_samples(n_steps * self.batch_size)
        dt = mgr.p.T_total / n_steps

        t = 0.0
        loss = jnp.zeros(())
        for i, batch in enumerate(self.ds.batches(self.batch_size, tr0, tr1)):
            if i >= n_steps:
                break
            with step_span(i):
                params, ostate, tracker, loss = step_fn(params, ostate,
                                                        tracker, batch)
            mgr.samples_seen += self.batch_size
            t_prev, t = t, t + dt
            for t_ev in mgr.due_saves(t):
                tracker = mgr.run_save(
                    t_ev, params["tables"], ostate["acc"]["tables"], tracker,
                    D.trainer_state(params), step=i)
            for ev in self.injector.between(t_prev, t):
                fid = mgr.n_failures
                with span("cpr.restore", failure=fid,
                          shards=",".join(map(str, ev.shard_ids))):
                    with span("cpr.restore.fetch", failure=fid):
                        old_t = [np.asarray(x) for x in params["tables"]]
                        old_a = [np.asarray(x)
                                 for x in ostate["acc"]["tables"]]
                    mgr.counters["restore_fetch_bytes"] += sum(
                        x.nbytes for x in old_t + old_a)
                    with span("cpr.restore.apply", failure=fid):
                        new_t, new_a, _ = mgr.on_failure(ev, old_t, old_a)
                    with span("cpr.restore.put", failure=fid):
                        params = {**params,
                                  "tables": [jnp.asarray(x) for x in new_t]}
                        # rebuild via {**ostate, ...}: optimizer state beyond
                        # "acc" (momenta, step counters) must survive a
                        # failure restore
                        ostate = {**ostate, "acc": {
                            **ostate["acc"],
                            "tables": [jnp.asarray(x) for x in new_a]}}
                    mgr.counters["restore_put_bytes"] += sum(
                        x.nbytes for x in list(new_t) + list(new_a))
        mgr.close()   # drain + stop the async writer thread (if any)
        self.final_params = params
        self.final_ostate = ostate

        # ---- evaluation ----
        scores, labels = [], []
        fwd = jax.jit(lambda p, b: D.dlrm_forward(p, b, cfg, self.use_kernel))
        for batch in self.ds.batches(4096, ev0, ev1):
            scores.append(np.asarray(jax.nn.sigmoid(fwd(params, batch))))
            labels.append(batch["label"])
        y = np.concatenate(labels)
        s = np.concatenate(scores)
        return EmulationResult(
            auc=roc_auc(y, s), logloss=log_loss(y, s),
            final_loss=float(loss), report=mgr.report(), n_steps=n_steps)
