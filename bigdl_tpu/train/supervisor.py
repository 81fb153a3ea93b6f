"""Anomaly-guarded training supervisor: the resilience layer between a
jitted train step and a long-lived multi-host job.

The reference runs finetuning as bare MPI k8s jobs (SURVEY §2.3): one
NaN step corrupts the optimizer state for good, a preempted pod loses
everything since the last manual save, and a lost peer hangs every
other host inside a collective forever. Low-bit training makes the
first failure routine — quantized grads overflow/NaN far more readily
("Training Transformers with 4-bit Integers", arxiv 2306.11987). This
module is the training-side counterpart of what PR 6/7 built for
serving and storage:

- **Anomaly guard** — after every step the loss (and, when the step
  exposes it, the global grad-norm) is checked host-side for NaN/inf,
  plus an EMA spike detector (loss > `spike_factor` x EMA after
  warmup). An anomalous step is *skipped*: the freshly computed
  lora/opt_state are discarded and the previous ones — bit-identical,
  never donated — carry forward. The skip/continue verdict AND the
  preemption flag ride one `parallel/health.consensus_any` reduction
  per step, so on a multi-host job every rank takes the same branch
  (a rank-local decision would fork the SPMD program state) and one
  rank's SIGTERM exits the whole job at the same step boundary.
- **Rollback** — `max_consecutive_anomalies` anomalies in a row mean
  the *state* is poisoned, not the batch: the supervisor reloads the
  last good rotating checkpoint (`load_latest_train_state`) and
  resumes from its step. `max_rollbacks` bounds the retry loop.
- **Preemption safety** — SIGTERM/SIGINT set a flag; at the next step
  boundary the supervisor writes an emergency rotating checkpoint and
  exits with the distinct code :data:`EXIT_PREEMPTED` (43). Resume is
  *unconditional* on start: a restarted pod picks up the newest
  loadable checkpoint and continues bit-exactly.
- **Hung-step watchdog** — `train/watchdog.StepWatchdog` beats on every
  *finished* step (the host-side loss fetch synchronizes); a wedged
  DCN collective becomes exit 42 with a diagnostic instead of an idle
  pod bill.
- **Structured events** — every anomaly/skip/rollback/checkpoint/
  preempt/abort appends a crc-suffixed JSONL record under the
  checkpoint dir (`bigdl-tpu train-status` tails it), and process-wide
  counters render on /metrics (`serving/metrics.py`).

Every path is driven on CPU by :class:`TrainFaultInjector` (the same
arm/fire discipline as `serving/faults.FaultInjector`):

==================  ====================================================
point               effect when armed
==================  ====================================================
``nan_loss``        the next step's host-side loss reads as NaN
``nan_grad``        the next step's host-side grad-norm reads as NaN
``loss_spike``      the next step's loss reads as spike_factor x EMA x 4
``hang_step``       the step stalls ``seconds=`` before running (drives
                    the watchdog). payload: ``seconds=float``
``preempt_signal``  as if SIGTERM arrived before the step boundary
``rank_drop``       the heartbeat loses ``rank=`` (default: last rank)
                    — drives the RankDropError abort path
==================  ====================================================

Usage (deploy/multihost_qlora.py is the production caller)::

    sup = TrainSupervisor(
        lambda lora, opt, *b: step_j(params, lora, opt, *b),
        ckpt_dir=ckpt_dir, lora=lora, opt_state=opt_state,
        rng=jax.random.PRNGKey(42),
        config=SupervisorConfig(save_every=100, step_timeout_s=1800),
        is_chief=(jax.process_index() == 0),
    )
    sup.resume()               # unconditional auto-resume
    state = sup.run(batch_fn, total_steps)

The wrapped step fn must NOT donate lora/opt_state at its jit call
site: the skip path keeps the previous buffers alive for exactly one
step (the price of an untouched optimizer state after a NaN).
"""

from __future__ import annotations

import dataclasses
import json
import os
import signal
import sys
import threading
import time
from typing import Any, Callable, Optional

from bigdl_tpu.serving.faults import FaultInjector
from bigdl_tpu.serving.metrics import (
    TRAIN_ANOMALIES,
    TRAIN_EMERGENCY_CHECKPOINTS,
    TRAIN_ROLLBACKS,
    TRAIN_STEP_SECONDS,
    TRAIN_STEPS_SKIPPED,
    TRAIN_WATCHDOG_ABORTS,
)
from bigdl_tpu.train.checkpoint import (
    load_latest_train_state,
    save_train_state_rotating,
)
from bigdl_tpu.train.watchdog import StepWatchdog

POINTS = ("nan_loss", "nan_grad", "loss_spike", "hang_step",
          "preempt_signal", "rank_drop")

#: distinct exit codes the orchestrator's restart policy can tell apart
EXIT_WATCHDOG = StepWatchdog.EXIT_CODE  # 42: hung step, restart+resume
EXIT_PREEMPTED = 43  # emergency checkpoint written, restart+resume


class TrainFaultInjector(FaultInjector):
    """Seedable injector for the training loop — reuses the serving
    harness's class-attr `points` discipline (arm/disarm/fire, seen/
    fired counters, deterministic times/after/prob arming)."""

    points = POINTS


class SupervisorAbort(RuntimeError):
    """Terminal, structured abort: the supervisor refuses to continue
    (rank drop, rollback loop) and says exactly why — never a silent
    hang, never a bare stack trace from deep inside a collective."""

    def __init__(self, kind: str, step: int, detail: str):
        self.kind = kind
        self.step = step
        self.detail = detail
        super().__init__(
            f"training aborted at step {step} [{kind}]: {detail}"
        )


@dataclasses.dataclass
class SupervisorConfig:
    save_every: int = 100        # rotating-checkpoint cadence (chief)
    keep_last: int = 3           # rotation retention
    verify: str = "fast"         # resume/rollback load verification
    spike_factor: float = 10.0   # loss > factor * EMA -> anomaly
    ema_beta: float = 0.9        # EMA smoothing for the spike baseline
    warmup_steps: int = 5        # applied steps before the spike guard arms
    max_consecutive_anomalies: int = 3  # K -> rollback
    max_rollbacks: int = 3       # rollbacks before SupervisorAbort
    step_timeout_s: Optional[float] = None  # watchdog (None = off)
    heartbeat_every: int = 10    # steps between cross-host health checks
    event_log: str = "supervisor_events.jsonl"  # under ckpt_dir (chief)


class EventLog:
    """Append-only JSONL event stream, one `{ts, step, kind, ...}` per
    line in the serving journal's exact tab+crc32 wire discipline
    (serving/journal.crc_line — interior rot in a months-old log is
    detectable, and the two formats cannot drift). Losing events must
    never kill training: every write failure degrades to a
    warning-free no-op.

    An optional `tracer` (obs/tracing.TraceRecorder) mirrors every
    event as an instant on the trainer track — the same recorder and
    trace format the serving engine uses, so a training run and a
    serving run open identically in Perfetto (docs/observability.md)."""

    def __init__(self, path: Optional[str], tracer: Optional[Any] = None,
                 clock: Callable[[], float] = time.time):
        self.path = path
        self.tracer = tracer
        # the log line's ts is OPERATOR time (wall by default, injectable
        # for simulated runs; graftlint WCT001) — the mirrored trace
        # instant below stays in the tracer's own clock domain
        self._clock = clock
        self._f = None
        if path is not None:
            try:
                os.makedirs(os.path.dirname(os.path.abspath(path)),
                            exist_ok=True)
                self._f = open(path, "a", encoding="utf-8")
            except OSError:  # pragma: no cover - read-only ckpt mount
                self._f = None

    def emit(self, kind: str, step: int, **detail: Any) -> None:
        ts = round(self._clock(), 3)
        if self.tracer is not None and self.tracer.enabled:
            # the mirrored instant is stamped in the TRACER's clock
            # domain (the log line keeps wall time for operators): a
            # simulated-clock tracer must not get wall-epoch instants
            # billions of seconds away from its train.step spans
            self.tracer.instant(kind, ts=self.tracer.now(), tid=0,
                                cat="train", step=int(step), **detail)
        if self._f is None:
            return
        from bigdl_tpu.serving.journal import crc_line

        body = json.dumps(
            {"ts": ts, "step": int(step), "kind": kind, **detail},
            separators=(",", ":"),
        )
        try:
            self._f.write(crc_line(body) + "\n")
            self._f.flush()
        except OSError:  # pragma: no cover
            pass

    def close(self) -> None:
        if self._f is not None:
            try:
                self._f.close()
            finally:
                self._f = None

    @staticmethod
    def tail(path: str, n: int = 20) -> list:
        """Last `n` decodable events (crc-mismatched / torn lines are
        skipped — same tolerance as the serving journal's scan, via the
        same split_crc_line codec)."""
        from bigdl_tpu.serving.journal import split_crc_line

        if not os.path.exists(path):
            return []
        out = []
        with open(path, "r", encoding="utf-8") as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                body, ok = split_crc_line(line)
                if ok is False:
                    continue  # interior bit rot: skip, keep tailing
                try:
                    out.append(json.loads(body))
                except json.JSONDecodeError:
                    continue
        return out[-n:]


@dataclasses.dataclass
class StepReport:
    """What one supervised step did (the `on_step` hook's argument)."""

    step: int            # the step index this report is about
    loss: float
    grad_norm: Optional[float]
    skipped: bool        # anomaly: update discarded, state untouched
    reasons: tuple       # () when clean; ("nan_loss", ...) when skipped
    seconds: float       # wall-clock of the step (incl. loss fetch)


class TrainSupervisor:
    """Wraps `step_fn(lora, opt_state, *batch) -> (lora, opt_state,
    loss[, grad_norm])` — the shape every recipe factory in train/
    (qlora / dpo / galore / recipes) produces once the caller closes
    over its frozen params — with the full resilience layer described
    in the module docstring. State (lora, opt_state, rng, step) lives
    ON the supervisor between calls; `run` drives the loop."""

    def __init__(
        self,
        step_fn: Callable,
        *,
        ckpt_dir: str,
        lora: dict,
        opt_state: Any,
        rng: Any,
        config: Optional[SupervisorConfig] = None,
        faults: Optional[TrainFaultInjector] = None,
        is_chief: bool = True,
        process_index: int = 0,
        health=None,  # parallel/health.HealthMonitor (default-built)
        on_watchdog_timeout: Optional[Callable] = None,  # tests
        exit_fn: Optional[Callable] = None,  # tests: replace sys.exit
        tracer=None,  # obs/tracing.TraceRecorder: per-step "train.step"
        # spans + every EventLog event mirrored as trace instants, in
        # the serving engine's exact trace format
        clock: Callable[[], float] = time.monotonic,  # step-duration
        # timing (watchdog beats, TRAIN_STEP_SECONDS); injectable like
        # the serving engine's clock= (graftlint WCT001)
        wall_clock: Callable[[], float] = time.time,  # epoch-domain ts
        # for the EventLog lines (durations and epochs are different
        # clock domains — a simulated run injects both)
        fused_backward: Optional[bool] = None,  # which dx path step_fn
        # was traced with (train/qlora.make_train_step's knob): recorded
        # in the EventLog at run start so loss curves compared across
        # the fused/remat flip carry their provenance. None = the caller
        # didn't say (pre-knob step_fn); nothing is recorded.
    ):
        from bigdl_tpu.parallel.health import HealthMonitor

        self.step_fn = step_fn
        self.ckpt_dir = ckpt_dir
        self.config = config or SupervisorConfig()
        if self.config.save_every < 1:
            raise ValueError(
                f"save_every must be >= 1, got {self.config.save_every}"
            )
        self.lora = lora
        self.opt_state = opt_state
        self.rng = rng
        self.step = 0
        # resume/rollback templates: the INITIAL trees define the pytree
        # structure every checkpoint must unflatten onto
        self._like_lora = lora
        self._like_opt_state = opt_state
        self.is_chief = is_chief
        self.process_index = process_index
        self._faults = faults if faults is not None else _NULL_TRAIN_INJECTOR
        self.health = health if health is not None else HealthMonitor(
            process_index=process_index, faults=self._faults,
        )
        self._exit = exit_fn or sys.exit
        self._clock = clock
        self._on_watchdog_timeout = on_watchdog_timeout
        self._ema: Optional[float] = None
        self._applied_steps = 0       # spike-guard warmup counter
        self._consecutive_anomalies = 0
        self.rollbacks = 0
        self._preempt_flag = threading.Event()
        self._prev_handlers: dict = {}
        # chief writes supervisor_events.jsonl; other ranks get a
        # rank-suffixed sibling so a non-chief abort still leaves a trace
        name = self.config.event_log
        if not is_chief:
            root, ext = os.path.splitext(name)
            name = f"{root}.r{process_index}{ext or '.jsonl'}"
        self.tracer = tracer
        self.fused_backward = fused_backward
        self.events = EventLog(os.path.join(ckpt_dir, name),
                               tracer=tracer, clock=wall_clock)
        self._wd: Optional[StepWatchdog] = None
        if self.config.step_timeout_s is not None:
            self._wd = StepWatchdog(
                self.config.step_timeout_s,
                on_timeout=self._watchdog_fired,
            )

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------

    def resume(self) -> int:
        """Unconditional auto-resume: adopt the newest loadable rotated
        checkpoint (corrupt candidates are skipped by
        `load_latest_train_state` with the verify-failure counter
        bumped). Also seeds a step-0 baseline checkpoint when the dir
        is empty, so an early rollback always has a target. Returns the
        start step (0 when starting fresh)."""
        state = load_latest_train_state(
            self.ckpt_dir,
            like_lora=self._like_lora,
            like_opt_state=self._like_opt_state,
            verify=self.config.verify,
        )
        if state is not None:
            self.lora = state["lora"]
            self.opt_state = state["opt_state"]
            self.rng = state["rng"]
            self.step = int(state["step"])
            self.events.emit("resume", self.step, path=state["path"])
        elif self.is_chief:
            self._save(kind="baseline")
        return self.step

    def install_signal_handlers(self) -> None:
        """SIGTERM/SIGINT -> preempt flag (k8s sends SIGTERM, then
        SIGKILL after terminationGracePeriodSeconds — the emergency
        save must fit that window). Main-thread only; a second signal
        falls through to the previous handler so a stuck save is still
        interruptible."""
        if threading.current_thread() is not threading.main_thread():
            return  # signal.signal would raise; tests run in workers

        def _handler(signum, frame):
            self._preempt_flag.set()
            prev = self._prev_handlers.get(signum, signal.SIG_DFL)
            signal.signal(signum, prev)

        for sig in (signal.SIGTERM, signal.SIGINT):
            self._prev_handlers[sig] = signal.signal(sig, _handler)

    def request_preemption(self) -> None:
        """Programmatic SIGTERM equivalent (thread-safe)."""
        self._preempt_flag.set()

    def close(self) -> None:
        if self._wd is not None:
            self._wd.stop()
        for sig, prev in self._prev_handlers.items():
            try:
                signal.signal(sig, prev)
            except (ValueError, OSError):  # pragma: no cover
                pass
        self._prev_handlers.clear()
        self.events.close()

    # ------------------------------------------------------------------
    # the supervised loop
    # ------------------------------------------------------------------

    def run(
        self,
        batch_fn: Callable[[int], tuple],
        total_steps: int,
        on_step: Optional[Callable[[StepReport], None]] = None,
    ) -> dict:
        """Drive training to `total_steps`. `batch_fn(step)` returns the
        step args after lora/opt_state (a deterministic-by-step fn makes
        skip/rollback replays exact; a stream that ignores `step` is
        fine for stochastic data). Returns the final state dict."""
        if self.fused_backward is not None:
            # one provenance event per run, not per step: `bigdl-tpu
            # train-status` surfaces it so two loss curves can be told
            # apart by backward path after the fact
            self.events.emit(
                "backward", self.step,
                path=("fused_pallas" if self.fused_backward
                      else "xla_remat"),
            )
        try:
            while self.step < total_steps:
                self._check_preempt()
                report = self.train_step(batch_fn(self.step))
                if on_step is not None:
                    on_step(report)
            self._check_preempt()
            if self.is_chief:
                self._save(kind="final")
        finally:
            self.close()
        return {"lora": self.lora, "opt_state": self.opt_state,
                "rng": self.rng, "step": self.step}

    def train_step(self, batch: tuple) -> StepReport:
        """One supervised step at `self.step`: run, guard, adopt-or-skip
        (possibly roll back), checkpoint on cadence. Advances
        `self.step` by one on BOTH applied and skipped steps — a
        skipped step consumes its batch, so a run with skips equals a
        clean run minus exactly the skipped updates."""
        step = self.step
        t0 = self._clock()
        tracing = self.tracer is not None and self.tracer.enabled
        tw0 = self.tracer.now() if tracing else 0.0
        f = self._faults.fire("hang_step")
        if f is not None:
            # a wedged collective never returns; the injected stall is
            # bounded so the test process survives after the watchdog
            # hook fires
            time.sleep(float(f.get("seconds", 1.0)))
        import jax

        self.rng, _sub = jax.random.split(self.rng)
        out = self.step_fn(self.lora, self.opt_state, *batch)
        if len(out) == 4:
            new_lora, new_opt, loss, gnorm = out
        else:
            new_lora, new_opt, loss = out
            gnorm = None
        # the float() fetch blocks until the step really finished on
        # device — the watchdog beat below therefore counts completed
        # work, and the anomaly guard reads settled numbers
        loss_h = float(loss)
        gnorm_h = None if gnorm is None else float(gnorm)
        if self._wd is not None:
            self._wd.beat(step)
        loss_h, gnorm_h = self._inject_anomalies(loss_h, gnorm_h)
        reasons = self._anomaly_reasons(loss_h, gnorm_h)
        dt = self._clock() - t0
        # the span's end in the clock that stamped its start: a length on
        # another clock (dt) next to a simulated tracer's stamps can make
        # one step's span run into the next
        tw1 = self.tracer.now() if tracing else 0.0
        TRAIN_STEP_SECONDS.observe(dt)
        anomaly, preempt = self._consensus(
            bool(reasons), self._preempt_flag.is_set())
        if preempt:
            # one rank's SIGTERM becomes EVERY rank's preempt flag in
            # the same per-step reduction as the anomaly verdict: all
            # ranks reach the next _check_preempt boundary together and
            # exit 43 as a group instead of one rank vanishing and
            # wedging the others' next collective until the watchdog
            self._preempt_flag.set()
        if anomaly:
            self._on_anomaly(step, loss_h, gnorm_h, reasons or
                             ("peer_anomaly",))
            report = StepReport(step, loss_h, gnorm_h, True,
                                tuple(reasons) or ("peer_anomaly",), dt)
        else:
            self.lora, self.opt_state = new_lora, new_opt
            self._consecutive_anomalies = 0
            self._applied_steps += 1
            beta = self.config.ema_beta
            self._ema = (loss_h if self._ema is None
                         else beta * self._ema + (1 - beta) * loss_h)
            self.step = step + 1
            if self.is_chief and self.step % self.config.save_every == 0:
                self._save(kind="periodic")
            report = StepReport(step, loss_h, gnorm_h, False, (), dt)
        if tracing:
            # the same span vocabulary as serving's decode_step: one
            # engine-track complete span per step, anomalies visible as
            # skipped=True plus the EventLog-mirrored "anomaly" instant
            self.tracer.complete(
                "train.step", tw0, tw1 - tw0, tid=0, cat="train", step=step,
                loss=report.loss, skipped=report.skipped,
            )
        if (self.config.heartbeat_every
                and self.step % self.config.heartbeat_every == 0):
            self._heartbeat(self.step)
        return report

    # ------------------------------------------------------------------
    # guards
    # ------------------------------------------------------------------

    def _inject_anomalies(self, loss_h: float, gnorm_h: Optional[float]):
        if self._faults.fire("nan_loss") is not None:
            loss_h = float("nan")
        if self._faults.fire("nan_grad") is not None:
            gnorm_h = float("nan")
        f = self._faults.fire("loss_spike")
        if f is not None:
            base = self._ema if self._ema is not None else 1.0
            loss_h = float(f.get("factor", 4.0)) * \
                self.config.spike_factor * max(abs(base), 1e-6)
        return loss_h, gnorm_h

    def _anomaly_reasons(self, loss_h: float,
                         gnorm_h: Optional[float]) -> list:
        import math

        reasons = []
        if not math.isfinite(loss_h):
            reasons.append("nan_loss")
        if gnorm_h is not None and not math.isfinite(gnorm_h):
            reasons.append("nan_grad")
        if (self._ema is not None
                and self._applied_steps >= self.config.warmup_steps
                and math.isfinite(loss_h)
                and loss_h > self.config.spike_factor * max(self._ema, 1e-12)):
            reasons.append("loss_spike")
        return reasons

    def _consensus(self, anomaly: bool, preempt: bool) -> tuple:
        from bigdl_tpu.parallel.health import consensus_any

        return tuple(consensus_any([anomaly, preempt]))

    def _on_anomaly(self, step: int, loss_h: float,
                    gnorm_h: Optional[float], reasons) -> None:
        TRAIN_ANOMALIES.inc()
        TRAIN_STEPS_SKIPPED.inc()
        self._consecutive_anomalies += 1
        self.events.emit(
            "anomaly", step, reasons=list(reasons), loss=loss_h,
            grad_norm=gnorm_h,
            consecutive=self._consecutive_anomalies,
        )
        if (self._consecutive_anomalies
                < self.config.max_consecutive_anomalies):
            # skip: discard the computed update, consume the batch
            self.step = step + 1
            return
        self._rollback(step)

    def _rollback(self, step: int) -> None:
        if self.rollbacks >= self.config.max_rollbacks:
            detail = (
                f"anomalies persist after {self.rollbacks} rollbacks "
                f"(max_rollbacks={self.config.max_rollbacks}) — data, "
                "learning rate, or hardware is bad"
            )
            self.events.emit("abort", step, abort_kind="rollback_loop",
                             detail=detail)
            raise SupervisorAbort("rollback_loop", step, detail)
        state = load_latest_train_state(
            self.ckpt_dir,
            like_lora=self._like_lora,
            like_opt_state=self._like_opt_state,
            verify=self.config.verify,
        )
        if state is None:
            detail = (
                f"no loadable checkpoint in {self.ckpt_dir} to roll "
                "back to after "
                f"{self._consecutive_anomalies} consecutive anomalies"
            )
            self.events.emit("abort", step, abort_kind="rollback_failed",
                             detail=detail)
            raise SupervisorAbort("rollback_failed", step, detail)
        self.lora = state["lora"]
        self.opt_state = state["opt_state"]
        self.rng = state["rng"]
        self.step = int(state["step"])
        self._consecutive_anomalies = 0
        self._ema = None  # re-warm: the poisoned stretch skewed it
        self._applied_steps = 0
        # counted only after a restore actually happened — the abort
        # paths above must not inflate "rollbacks performed"
        self.rollbacks += 1
        TRAIN_ROLLBACKS.inc()
        self.events.emit(
            "rollback", step, restored_step=self.step,
            path=state["path"], rollbacks=self.rollbacks,
        )

    # ------------------------------------------------------------------
    # preemption / watchdog / heartbeat
    # ------------------------------------------------------------------

    def _check_preempt(self) -> None:
        if self._faults.fire("preempt_signal") is not None:
            self._preempt_flag.set()
        if not self._preempt_flag.is_set():
            return
        path = None
        if self.is_chief:
            path = self._save(kind="emergency")
            # the metric counts checkpoints actually written: non-chief
            # ranks exiting alongside would otherwise overcount N-fold
            TRAIN_EMERGENCY_CHECKPOINTS.inc()
        self.events.emit("preempt", self.step, checkpoint=path,
                         exit_code=EXIT_PREEMPTED)
        self.close()
        self._exit(EXIT_PREEMPTED)

    def _watchdog_fired(self, idle: float) -> None:
        TRAIN_WATCHDOG_ABORTS.inc()
        self.events.emit(
            "watchdog_abort", self.step, idle_s=round(idle, 1),
            timeout_s=self.config.step_timeout_s,
            exit_code=EXIT_WATCHDOG,
        )
        if self._on_watchdog_timeout is not None:  # tests
            self._on_watchdog_timeout(idle)
            return
        self.events.close()  # the hard exit below skips atexit flushes
        print(
            f"[bigdl-tpu supervisor] no step finished for {idle:.0f}s "
            f"(> {self.config.step_timeout_s}s) at step {self.step} on "
            f"process {self.process_index} — likely a lost peer wedging "
            f"a collective; exiting {EXIT_WATCHDOG} for a restart + "
            "auto-resume from the last checkpoint.",
            file=sys.stderr, flush=True,
        )
        os._exit(EXIT_WATCHDOG)  # a blocked collective never returns

    def _heartbeat(self, step: int) -> None:
        from bigdl_tpu.parallel.health import RankDropError

        try:
            self.health.check(step)
        except RankDropError as e:
            self.events.emit(
                "rank_drop", step, missing=e.missing, present=e.present,
            )
            raise SupervisorAbort("rank_drop", step, str(e)) from e

    # ------------------------------------------------------------------

    def _save(self, kind: str) -> str:
        path = save_train_state_rotating(
            self.ckpt_dir, step=self.step,
            keep_last=self.config.keep_last,
            lora=self.lora, opt_state=self.opt_state, rng=self.rng,
        )
        self.events.emit("checkpoint", self.step, ckpt_kind=kind,
                         path=path)
        return path


class _NullTrainInjector(TrainFaultInjector):
    """Module-shared inert default (mirrors faults.NULL_INJECTOR)."""

    def arm(self, *a, **k):  # pragma: no cover - guard rail
        raise RuntimeError(
            "this is the shared no-op injector; construct your own "
            "TrainFaultInjector and pass it via faults="
        )

    def fire(self, point: str) -> Optional[dict]:
        return None


_NULL_TRAIN_INJECTOR = _NullTrainInjector()
