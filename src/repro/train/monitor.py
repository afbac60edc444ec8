"""Runtime health monitoring: straggler detection, NaN sentinels, heartbeats.

On a real multi-host pod these feed the coordination service; here they are
host-local but fully functional (and unit-tested with a fake clock):

  * ``StepMonitor``     -- per-step wall time + median, taken at each metric
    fetch (a device sync) over the steps it completed; flags fetches slower
    than ``straggler_factor`` x median (straggler mitigation hook: the train
    loop logs and can re-shard/skip input hosts); NaN/Inf loss sentinel with
    configurable tolerance before abort; backend compiles and their seconds
    (one ``jax.monitoring`` listener per process).
  * ``HeartbeatRegistry`` -- worker liveness bookkeeping with stale-detection
    and an escalation edge: ``check(step)`` returns workers that *newly* went
    stale (re-arming when they come back), records the first-stale step per
    worker, and feeds the loop's configurable stale-worker action
    (``RecoveryPolicy.stale_worker_action``: log / rollback / abort).
  * ``CollectiveWatchdog`` -- bounds the wall time of a dispatched train
    step's collectives: ``guard`` arms a timer, blocks until the step's
    outputs are ready, and records a firing if readiness took longer than
    ``timeout_s`` (a hung reduce-scatter on a real fabric never returns;
    here the firing is the restart-decision signal).
  * ``SpectrumLogger`` -- refresh-cadence probe of the update's singular
    spectrum (``core/metrics.update_singular_spectrum`` /
    ``effective_rank``): one probe leaf per refresh group, one host-side
    SVD per refresh step.  Gated by ``TrainConfig.log_spectrum`` (default
    off); its per-group effective-rank reading is the input signal of the
    adaptive rank schedule (DESIGN.md §2.12).
"""
from __future__ import annotations

import math
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
# a jax.monitoring listener stays for the life of the process, so one
# listener counts for every monitor, each from its own baseline
_compiles = [0, 0.0]  # backend compiles of this process and their seconds
_listening = False


def _on_duration(event: str, duration_secs: float, **kwargs) -> None:
    if event == _BACKEND_COMPILE:
        _compiles[0] += 1
        _compiles[1] += duration_secs


def compile_totals() -> Tuple[int, float]:
    """(backend compiles, their seconds) of this process since the first
    call, which registers the one listener."""
    global _listening
    if not _listening:
        import jax

        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _listening = True
    return _compiles[0], _compiles[1]


class StepMonitor:
    def __init__(
        self,
        straggler_factor: float = 3.0,
        window: int = 50,
        max_bad_losses: int = 5,
        clock: Callable[[], float] = time.monotonic,
    ):
        self.straggler_factor = straggler_factor
        self.window = window
        self.max_bad_losses = max_bad_losses
        self._clock = clock
        self._times: List[float] = []
        self._t_start: Optional[float] = None
        self.stragglers: List[int] = []
        self.bad_loss_count = 0
        self.step_count = 0
        # recovery counters (train/recovery.py): maintained by the train
        # loop, surfaced in every history record via ``counters()``
        self.skip_steps = 0  # updates gated out (non-finite grads)
        self.rollbacks = 0  # checkpoint rollbacks performed
        self.save_retries = 0  # checkpoint write attempts retried
        self.save_failures = 0  # saves abandoned after retries
        self._compile_base = compile_totals()

    def start_step(self) -> None:
        """Start the clock: the loop's start, or its resumption after a
        rollback.  Each ``end_step`` restarts it."""
        self._t_start = self._clock()

    def end_step(
        self, step: int, loss: Optional[float] = None, steps: int = 1
    ) -> Dict[str, float]:
        """Close a wall-time window at a device sync (straggler
        bookkeeping): ``steps`` steps, the last ``step``, completed since
        the previous call.  Dispatch is asynchronous, so only a sync ends
        a step: the loop calls this at each metric fetch, and the time is
        per step of that fetch.

        ``loss`` may be omitted when the caller defers the device->host
        metric fetch and feeds the NaN sentinel via ``note_loss``.
        """
        now = self._clock()
        start = now if self._t_start is None else self._t_start
        dt = (now - start) / max(steps, 1)
        self._t_start = now
        self._times.append(dt)
        if len(self._times) > self.window:
            self._times.pop(0)
        self.step_count += steps
        med = sorted(self._times)[len(self._times) // 2]
        is_straggler = (
            len(self._times) >= 5 and dt > self.straggler_factor * med
        )
        if is_straggler:
            self.stragglers.append(step)
        if loss is not None:
            self.note_loss(step, loss)
        return {
            "step_time_s": dt,
            "median_step_time_s": med,
            "straggler": float(is_straggler),
        }

    def note_loss(
        self, step: int, loss: float, raise_on_streak: bool = True
    ) -> bool:
        """NaN/Inf sentinel: consecutive non-finite losses abort the run.

        Counters behave identically whether losses arrive per step or in
        deferred batches (the counter resets on every finite loss either
        way); only the *moment* the abort raises moves to the fetch point.

        ``raise_on_streak=False`` keeps the bookkeeping but returns the
        tripped flag instead of raising -- the recovery-enabled loop owns
        the abort decision (rollback first, abort only past the budget).
        """
        if not math.isfinite(loss):
            self.bad_loss_count += 1
            if self.bad_loss_count > self.max_bad_losses:
                if raise_on_streak:
                    raise FloatingPointError(
                        f"{self.bad_loss_count} non-finite losses; aborting "
                        f"(last at step {step})"
                    )
                return True
        else:
            self.bad_loss_count = 0
        return False

    def counters(self) -> Dict[str, float]:
        """Recovery counters and the backend compiles since the monitor
        was made, merged into every history record."""
        n, secs = compile_totals()
        return {
            "skip_steps": float(self.skip_steps),
            "rollbacks": float(self.rollbacks),
            "save_retries": float(self.save_retries),
            "save_failures": float(self.save_failures),
            "compiles": float(n - self._compile_base[0]),
            "compile_s": secs - self._compile_base[1],
        }


class HeartbeatRegistry:
    def __init__(
        self,
        timeout_s: float = 60.0,
        clock: Callable[[], float] = time.monotonic,
    ):
        self.timeout_s = timeout_s
        self._clock = clock
        self._last: Dict[str, float] = {}
        # escalation bookkeeping: workers currently flagged stale (so a
        # worker only escalates once per stale episode) and the step at
        # which each worker was FIRST seen stale (history/audit record).
        self._flagged: set = set()
        self.first_stale: Dict[str, int] = {}

    def beat(self, worker: str) -> None:
        self._last[worker] = self._clock()
        # A returning heartbeat ends the stale episode: the next timeout
        # re-escalates instead of being swallowed as already-flagged.
        self._flagged.discard(worker)

    def stale(self) -> List[str]:
        now = self._clock()
        return [
            w for w, t in self._last.items() if now - t > self.timeout_s
        ]

    def check(self, step: int) -> List[str]:
        """Per-step staleness edge detection (the escalation input).

        Returns only workers that went stale SINCE the previous check --
        each stale episode escalates exactly once, and the first step a
        worker was seen stale is recorded in ``first_stale`` (kept across
        recoveries for the audit trail).
        """
        newly = [w for w in self.stale() if w not in self._flagged]
        for w in newly:
            self._flagged.add(w)
            self.first_stale.setdefault(w, step)
        return newly

    def healthy(self) -> bool:
        return not self.stale()


class CollectiveWatchdog:
    """Bounds the wall time of a train step's dispatched collectives.

    JAX dispatch is async: a hung per-bucket reduce-scatter (dead peer,
    wedged fabric) shows up as outputs that never become ready.  ``guard``
    arms a (real-time) timer, blocks until ``result`` is ready, and
    cancels; if readiness exceeded ``timeout_s`` the firing is recorded in
    ``fired`` and ``on_timeout(step, elapsed_s)`` is invoked -- from the
    timer thread if the block is genuinely hung, so the signal escapes
    even when ``block_until_ready`` never returns.

    Opt-in: wrapping ``guard`` around the jitted step forces a per-step
    device sync, trading the loop's deferred-fetch overlap for bounded
    detection latency.  ``_block`` is overridable for tests.
    """

    def __init__(
        self,
        timeout_s: float = 60.0,
        on_timeout: Optional[Callable[[int, float], None]] = None,
        clock: Callable[[], float] = time.monotonic,
    ):
        self.timeout_s = timeout_s
        self.on_timeout = on_timeout
        self._clock = clock
        self.fired: List[Tuple[int, float]] = []  # (step, elapsed_s)

    def _block(self, result) -> None:
        import jax

        jax.block_until_ready(result)

    def guard(self, step: int, result):
        """Block until ``result`` is ready, escalating past ``timeout_s``."""
        timed_out = threading.Event()

        def _fire():
            timed_out.set()
            if self.on_timeout is not None:
                self.on_timeout(step, self.timeout_s)

        timer = threading.Timer(self.timeout_s, _fire)
        timer.daemon = True
        timer.start()
        t0 = self._clock()
        try:
            self._block(result)
        finally:
            timer.cancel()
        elapsed = self._clock() - t0
        if elapsed > self.timeout_s and not timed_out.is_set():
            # Slow-but-finished collective (fake clock or near-miss): the
            # timer thread did not escalate, do it synchronously.
            if self.on_timeout is not None:
                self.on_timeout(step, elapsed)
            timed_out.set()
        if timed_out.is_set():
            self.fired.append((step, elapsed))
        return result


class SpectrumLogger:
    """Refresh-cadence singular-spectrum probe for the low-rank update.

    One probe leaf per refresh group (the largest low-rank leaf of the
    group -- the spectrum of the biggest matrix dominates the group's
    memory, so it is the right leaf to size the rank by).  The train loop
    snapshots the probe leaf to host BEFORE the refresh step (the jitted
    step donates its input state, so the pre-step buffer is gone after
    dispatch) and hands the post-step value to ``observe``; the cost is
    one host transfer + one SVD per refresh step, and the whole logger is
    gated off by default (``TrainConfig.log_spectrum``).

    ``effective_rank_for(group)`` exposes the latest reading -- the
    measurement consumed by the ``adaptive`` rank-schedule policy
    (``core/rank_schedule.propose_adaptive_rank``).
    """

    def __init__(self, specs) -> None:
        import jax

        from repro.core.lowrank import LeafSpec

        leaves = jax.tree_util.tree_leaves(
            specs, is_leaf=lambda x: isinstance(x, LeafSpec)
        )
        paths = jax.tree_util.tree_leaves_with_path(
            specs, is_leaf=lambda x: isinstance(x, LeafSpec)
        )
        # Probe leaf per group, keyed by flat leaf index (tree_leaves order
        # matches the params tree's leaf order).  LeafSpec carries no
        # shape, so the clamped per-leaf rank is the footprint proxy: the
        # leaf whose rank survived the min(d, n) clamp at the highest
        # value is the group's largest matrix.
        self.probe: Dict[int, Tuple[int, str]] = {}
        best: Dict[int, int] = {}
        for idx, ((path, spec), _leaf) in enumerate(zip(paths, leaves)):
            if not spec.lowrank:
                continue
            if spec.group not in best or spec.rank > best[spec.group]:
                best[spec.group] = spec.rank
                self.probe[spec.group] = (idx, jax.tree_util.keystr(path))
        self._before: Dict[int, Any] = {}
        self._latest: Dict[int, float] = {}
        self.history: List[Dict[str, float]] = []

    def _leaf(self, params, group: int):
        import jax

        idx, _ = self.probe[group]
        return jax.tree_util.tree_leaves(params)[idx]

    def capture_before(self, params, group: int) -> None:
        """Host-snapshot the probe leaf before a (donating) refresh step."""
        if group not in self.probe:
            return
        import numpy as np

        self._before[group] = np.asarray(self._leaf(params, group))

    def observe(self, params, step: int, group: int) -> Optional[Dict[str, float]]:
        """Spectrum of the refresh step's update on the probe leaf."""
        if group not in self.probe or group not in self._before:
            return None
        import numpy as np

        from repro.core import metrics as metrics_lib

        before = self._before.pop(group)
        after = np.asarray(self._leaf(params, group))
        spectrum = metrics_lib.update_singular_spectrum(before, after)
        eff = float(np.mean(np.asarray(metrics_lib.effective_rank(spectrum))))
        top = float(np.max(np.asarray(spectrum)))
        self._latest[group] = eff
        rec = {
            "event": "spectrum",
            "step": float(step),
            "group": float(group),
            "effective_rank": eff,
            "top_singular_value": top,
            "path": self.probe[group][1],
        }
        self.history.append(rec)
        return rec

    def effective_rank_for(self, group: int) -> Optional[float]:
        return self._latest.get(group)
