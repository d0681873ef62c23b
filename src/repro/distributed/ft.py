"""Fault tolerance: heartbeats, failure detection, restart, stragglers.

``ResilientTrainer`` wraps a train step with the full production loop:

* periodic atomic checkpoints (distributed/checkpoint.py);
* a heartbeat registry — hosts that miss ``dead_after`` heartbeats are
  declared failed; the trainer restores the latest checkpoint and resumes
  (optionally on a re-sized mesh via distributed/elastic.py);
* straggler mitigation for the *data* path: if a batch misses its
  deadline, the ODS service substitutes cached unseen samples instead of
  stalling the step (the paper's opportunistic sampling doubles as
  straggler relief — DESIGN.md §3);
* failure injection hooks for tests/examples.

All timing runs on an injected ``Clock`` (default
:class:`~repro.workload.clock.RealClock`), so heartbeat expiry and
batch deadlines are testable under ``VirtualClock`` like the rest of
the stack.  ``HeartbeatRegistry`` is now a thin host-flavoured view of
the generalized :class:`~repro.faults.liveness.LivenessRegistry` shared
with the sharded cache client.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional

import jax

from repro.distributed import checkpoint as ckpt
from repro.faults.liveness import LivenessRegistry


class HeartbeatRegistry(LivenessRegistry):
    """Host-liveness view kept for API compatibility: ``beat(host)`` /
    ``failed_hosts()`` over the generalized registry."""

    def failed_hosts(self, now: Optional[float] = None) -> List[int]:
        return self.failed(now)


@dataclass
class FTConfig:
    ckpt_dir: str = "/tmp/repro_ckpt"
    ckpt_every: int = 50          # <= 0: no checkpoints at all
    keep: int = 3
    dead_after_s: float = 10.0
    batch_deadline_s: Optional[float] = None   # straggler cutoff
    max_restarts: int = 10


class ResilientTrainer:
    """step_fn(params, opt_state, batch) -> (params, opt_state, metrics)."""

    def __init__(self, step_fn: Callable, params, opt_state,
                 cfg: FTConfig,
                 batch_source: Callable[[], Any],
                 straggler_substitute: Optional[Callable[[], Any]] = None,
                 failure_injector: Optional[Callable[[int], bool]] = None,
                 clock: Optional[Any] = None):
        if clock is None:
            from repro.workload.clock import RealClock
            clock = RealClock()
        self.clock = clock
        self.step_fn = step_fn
        self.params = params
        self.opt_state = opt_state
        # keep the initial state so a missing/corrupt checkpoint restarts
        # from step 0 instead of crashing the whole job
        self._init_params = jax.tree_util.tree_map(lambda x: x, params)
        self._init_opt = jax.tree_util.tree_map(lambda x: x, opt_state)
        self.cfg = cfg
        self.batch_source = batch_source
        self.straggler_substitute = straggler_substitute
        self.failure_injector = failure_injector
        self.heartbeats = HeartbeatRegistry(cfg.dead_after_s, clock=clock)
        self.step = 0
        self.restarts = 0
        self.straggler_substitutions = 0
        self.history: List[Dict] = []

    # ------------------------------------------------------------------
    def _checkpoint(self) -> None:
        ckpt.save(self.cfg.ckpt_dir, self.step,
                  {"params": self.params, "opt": self.opt_state},
                  extras={"restarts": self.restarts})
        ckpt.prune(self.cfg.ckpt_dir, self.cfg.keep)

    def _restore(self) -> None:
        """Restore the newest complete checkpoint; with none usable,
        restart from the initial state at step 0 rather than crash."""
        try:
            tree, manifest = ckpt.restore(
                self.cfg.ckpt_dir, {"params": self.params,
                                    "opt": self.opt_state})
        except (FileNotFoundError, ValueError, KeyError, OSError):
            if any(getattr(x, "is_deleted", lambda: False)()
                   for x in jax.tree.leaves(self._init_params)):
                raise RuntimeError(
                    "no usable checkpoint to restore and the initial "
                    "state was donated to the step; checkpoint more "
                    "often (FTConfig.ckpt_every > 0)") from None
            self.params = self._init_params
            self.opt_state = self._init_opt
            self.step = 0
            return
        self.params = tree["params"]
        self.opt_state = tree["opt"]
        self.step = manifest["step"]

    # ------------------------------------------------------------------
    def _get_batch(self):
        if self.cfg.batch_deadline_s is None or \
                self.straggler_substitute is None:
            return self.batch_source()
        t0 = self.clock.now()
        batch = self.batch_source()
        if self.clock.now() - t0 > self.cfg.batch_deadline_s:
            self.straggler_substitutions += 1
            return self.straggler_substitute()
        return batch

    def _restart(self) -> None:
        if self.restarts >= self.cfg.max_restarts:
            raise RuntimeError("restart budget exhausted")
        self.restarts += 1
        self._restore()

    def run(self, n_steps: int) -> List[Dict]:
        saving = self.cfg.ckpt_every > 0
        if saving and ckpt.latest_step(self.cfg.ckpt_dir) is not None:
            self._restore()            # resume an interrupted run
        while self.step < n_steps:
            if self.failure_injector and self.failure_injector(self.step):
                # simulated node failure: lose in-memory state, restart
                self._restart()
                continue
            failed = self.heartbeats.failed_hosts()
            if failed:
                # a host missed its heartbeat window (or was marked dead
                # by a fault injector): restore and bring it back in
                self._restart()
                for h in failed:
                    self.heartbeats.mark_alive(h)
                continue
            batch = self._get_batch()
            self.params, self.opt_state, metrics = self.step_fn(
                self.params, self.opt_state, batch)
            self.step += 1
            self.heartbeats.beat(0)
            rec = {k: float(v) for k, v in metrics.items()}
            rec["step"] = self.step
            self.history.append(rec)
            if saving and self.step % self.cfg.ckpt_every == 0:
                self._checkpoint()
        if saving:
            self._checkpoint()
        return self.history
