"""Fault tolerance: restartable training/completion loops and straggler
handling.

At 1000+ nodes the failure model is: (a) node loss ⇒ job restart from the
last checkpoint (possibly on fewer nodes — see ``runtime.elastic``);
(b) stragglers ⇒ detect via step-time watchdog, mitigate by eviction+restart
or, for the sparse workloads, by construction (equal-capacity shuffled
shards make per-device work identical — DESIGN.md §3).

``RestartableLoop`` drives a jit'd step function with periodic async
checkpoints, resumes from the newest valid manifest (falling back to older
ones if the newest is corrupt), and exposes failure injection for tests.
"""
from __future__ import annotations

import logging
import os
import time
from typing import Any, Callable, Optional

import jax

from repro import obs
from repro.checkpoint.checkpointer import (Checkpointer, StructureMismatch,
                                           restore, _list_steps)

log = logging.getLogger(__name__)


class StepWatchdog:
    """Flags steps slower than ``threshold × median`` (straggler signal).

    On a real cluster this feeds the controller's evict/restart policy; here
    it records events for inspection and tests."""

    def __init__(self, threshold: float = 3.0, warmup: int = 5):
        self.threshold = threshold
        self.warmup = warmup
        self.times = []
        self.events = []

    def observe(self, seconds: float, step: int):
        self.times.append(seconds)
        if len(self.times) > self.warmup:
            hist = sorted(self.times[:-1])
            med = hist[len(hist) // 2]
            if seconds > self.threshold * med:
                self.events.append((step, seconds, med))
                log.warning("straggler step %d: %.3fs vs median %.3fs",
                            step, seconds, med)


class RestartableLoop:
    """Checkpoint/restart driver.

    step_fn: (step_idx, state) -> state   (jit'd by the caller)
    state is any pytree. Checkpoints every ``ckpt_every`` steps (async) and
    at completion. ``fail_at`` raises mid-run after the step executes —
    used by tests to prove restart-resume equivalence."""

    def __init__(self, directory: str, step_fn: Callable[[int, Any], Any],
                 ckpt_every: int = 10, keep_last: int = 3,
                 watchdog: Optional[StepWatchdog] = None,
                 metadata_fn: Optional[Callable[[int], dict]] = None):
        self.ckpt = Checkpointer(directory, keep_last)
        self.step_fn = step_fn
        self.ckpt_every = ckpt_every
        self.watchdog = watchdog or StepWatchdog()
        # metadata_fn(step) -> JSON-able dict stored in the checkpoint
        # manifest (e.g. the experiment harness's per-sweep metric history);
        # on resume the newest manifest's metadata lands in last_metadata
        # BEFORE the first step runs, so callers can rebuild their history
        self.metadata_fn = metadata_fn
        self.last_metadata: dict = {}

    def _resume(self, init_state):
        """Newest-first restore. A corrupt or partially written step falls
        back to the next older one; a step holding another state structure
        (``StructureMismatch``, e.g. another solver's checkpoint in the same
        directory) raises."""
        steps = sorted(_list_steps(self.ckpt.directory), reverse=True)
        for s in steps:
            try:
                state, manifest = restore(self.ckpt.directory, s, init_state)
            except StructureMismatch:
                raise
            except (OSError, ValueError, EOFError) as e:  # corrupt/partial
                log.warning("checkpoint step %d unreadable (%s); falling back",
                            s, e)
                continue
            print(f"resumed from checkpoint step {s} in "
                  f"{self.ckpt.directory}")
            self.last_metadata = manifest.get("metadata", {}) or {}
            return s + 1, state
        return 0, init_state

    def run(self, init_state, num_steps: int, fail_at: Optional[int] = None):
        start, state = self._resume(init_state)
        for step in range(start, num_steps):
            t0 = time.perf_counter()
            # the span tree of everything the step does (planner dispatch,
            # kernels, the caller's own sweep spans) lands in the obs
            # registry and, for the experiment harness, in the per-sweep
            # metric history riding the checkpoint manifest
            with obs.span("loop/step", step=step):
                state = self.step_fn(step, state)
                jax.block_until_ready(jax.tree.leaves(state)[0])
            self.watchdog.observe(time.perf_counter() - t0, step)
            if (step + 1) % self.ckpt_every == 0:
                self.ckpt.save_async(step, state, self._metadata(step))
            if fail_at is not None and step == fail_at:
                self.ckpt.wait()
                raise RuntimeError(f"injected failure at step {step}")
        self.ckpt.wait()
        final = num_steps - 1
        if final >= 0 and start <= final:
            # skip the final re-save when the resume point was already past
            # the end: no step ran, and re-writing would clobber the stored
            # manifest metadata with this process's (empty) metadata_fn view
            from repro.checkpoint.checkpointer import save
            save(self.ckpt.directory, final, state,
                 metadata=self._metadata(final))
        return state

    def _metadata(self, step: int) -> Optional[dict]:
        return None if self.metadata_fn is None else self.metadata_fn(step)
