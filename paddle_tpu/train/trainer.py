"""Trainer (ref: PaddleNLP ``Trainer`` / the reference's Fleet training loop).

One fused jitted step (grads+clip+optimizer+schedule), gradient accumulation
via an inner ``lax.scan``-free accumulation (accumulate in fp32 and apply on
the boundary — keeps one compiled program), watchdog/NaN sentinel hooks, MFU
logging, checkpoint/resume.

Host/device overlap (ISSUE 3): with ``pipeline_depth=K > 0``, ``fit``
keeps a K-deep window of dispatched-but-unfetched steps — XLA's async
dispatch queue executes step N while the host is already feeding steps
N+1..N+K — and the host-side work that needs the loss value (the
``float()`` fetch, NaN guard, fault_value override, watchdog poke, loss
gauge) moves to the DRAIN side of the window with correct (≤K-lagged)
step attribution. Log/eval/checkpoint boundaries drain the window first,
so everything they observe (LR, params, step counter) is exact.
``pipeline_depth=0`` (the default) is the unchanged synchronous loop.
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.core.module import Module, combine, partition_trainable, value_and_grad
from paddle_tpu.observability import METRICS, span as _span
from paddle_tpu.observability.compile import instrumented_jit
from paddle_tpu.observability.flight import FLIGHT
from paddle_tpu.observability.flops import record_throughput
from paddle_tpu.train.checkpoint import CheckpointManager
from paddle_tpu.train.step import TrainState, init_state
from paddle_tpu.utils.faults import fault_point, fault_value

# Training telemetry (ISSUE 2). tokens/sec + MFU ride the SHARED gauges
# in observability.flops (record_throughput) — the same choke point
# StepTimer feeds, so there is exactly one FLOPs/MFU model.
_STEPS = METRICS.counter("train_steps_total", "optimizer steps completed")
_STEP_S = METRICS.histogram(
    "train_step_seconds", "wall time per training step (host-observed)")
_NAN_SKIPS = METRICS.counter(
    "train_nan_skips_total", "steps skipped on non-finite loss")
_NAN_BACKOFF = METRICS.counter(
    "train_nan_backoff_total", "backoff sleeps taken during NaN streaks")
_LOSS = METRICS.gauge("train_loss", "most recent host-fetched loss")


@dataclass
class TrainerArgs:
    max_steps: int = 1000
    log_every: int = 10
    ckpt_every: int = 0                   # 0 = disabled
    ckpt_dir: str = "checkpoints"
    grad_accum_steps: int = 1
    flops_per_token: float = 0.0          # for MFU logging
    peak_flops: float = 197e12
    nan_guard: bool = True                # skip update & count on non-finite loss
    max_bad_steps: int = 25               # trip watchdog after this many
    # backoff after a SKIPPED (non-finite) step: sleep nan_backoff_s,
    # doubling per consecutive bad step up to nan_backoff_cap_s — a NaN
    # storm from a sick host/chip slows down instead of spinning the
    # accelerator at full rate on poisoned updates. 0 disables.
    nan_backoff_s: float = 0.0
    nan_backoff_cap_s: float = 30.0
    resume_reskip: bool = False           # fast-forward a FRESH stream on resume
    # (leave False when the caller positions the iterator; ElasticRunner
    # always rebuilds streams from scratch and turns this on)
    # host/device overlap: keep up to this many dispatched steps in
    # flight before fetching their losses. 0 = the synchronous loop,
    # bit-identical to the pre-pipelining trainer.
    pipeline_depth: int = 0
    # background checkpoint writes (CheckpointManager(async_save=True)):
    # save() snapshots to host and returns; the tmp+fsync+rename protocol
    # runs on a writer thread. fit() calls mgr.wait() at exit either way.
    async_ckpt: bool = False
    # device-side double-buffered input: while step N executes on the
    # accelerator, step N+1's microbatches are fetched from the iterator
    # and shipped with jax.device_put, so the next dispatch never waits
    # on a host->device transfer. Composes with any pipeline_depth
    # (including 0); the dispatch sequence is unchanged, so losses stay
    # bit-identical to the synchronous loop.
    device_double_buffer: bool = False


class Trainer:
    def __init__(self, model: Module, optimizer, loss_fn: Callable,
                 args: TrainerArgs = None, mesh=None, hooks=None):
        self.args = args or TrainerArgs()
        self.optimizer = optimizer
        self.loss_fn = loss_fn
        self.mesh = mesh
        self.state = init_state(model, optimizer, mesh)
        self.hooks = hooks or []
        self._step_fn = self._build_step()
        self.history: list[dict] = []
        self._bad_steps = 0
        self.watchdog = None           # StallWatchdog, poked every step
        # robustness accounting — ElasticRunner and tests read these
        self.stats = {"nan_skips": 0, "bad_streak_max": 0}

    def _build_step(self):
        loss_fn = self.loss_fn
        optimizer = self.optimizer
        accum = self.args.grad_accum_steps
        nan_guard = self.args.nan_guard

        def step(state: TrainState, *batches):
            if accum == 1:
                loss, grads = value_and_grad(loss_fn)(state.model, *batches[0])
            else:
                def acc_body(carry, batch):
                    loss_sum, grads_sum = carry
                    loss, grads = value_and_grad(loss_fn)(state.model, *batch)
                    grads_sum = jax.tree_util.tree_map(
                        lambda a, g: a if g is None else a + g.astype(jnp.float32),
                        grads_sum, grads, is_leaf=lambda x: x is None)
                    return (loss_sum + loss, grads_sum), None

                zero = jax.tree_util.tree_map(
                    lambda p: None if p is None else jnp.zeros(p.shape, jnp.float32),
                    partition_trainable(state.model)[0], is_leaf=lambda x: x is None)
                (loss, grads), _ = jax.lax.scan(
                    acc_body, (jnp.zeros((), jnp.float32), zero),
                    jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *batches))
                loss = loss / accum
                grads = jax.tree_util.tree_map(
                    lambda g: None if g is None else g / accum,
                    grads, is_leaf=lambda x: x is None)
            new_model, new_opt = optimizer.step(state.model, grads, state.opt_state)
            if nan_guard:
                ok = jnp.isfinite(loss)
                new_model = jax.tree_util.tree_map(
                    lambda new, old: old if new is None else jnp.where(ok, new, old),
                    new_model, state.model, is_leaf=lambda x: x is None)
                new_opt = jax.tree_util.tree_map(
                    lambda new, old: old if new is None else jnp.where(ok, new, old),
                    new_opt, state.opt_state, is_leaf=lambda x: x is None)
            return state.updated(new_model, new_opt, state.rng), loss

        # compile introspection (ISSUE 4): spans + compile_seconds +
        # cache hit/miss counters, and cost_analysis FLOPs that back the
        # MFU gauges when no analytic flops_per_token was configured
        return instrumented_jit(step, name="train.step", donate_argnums=(0,))

    def resume(self):
        mgr = CheckpointManager(self.args.ckpt_dir)
        restored = mgr.restore(self.state)
        if restored is not None:
            self.state = restored
        return self

    def fit(self, data_iter, eval_fn: Optional[Callable] = None):
        try:
            if self.args.pipeline_depth > 0 or self.args.device_double_buffer:
                return self._fit_pipelined(data_iter, eval_fn)
            return self._fit_sync(data_iter, eval_fn)
        except BaseException as e:
            # last event of a dead run; the dump is a no-op unless a
            # flight dir is configured (PT_FLIGHT_DIR / FLIGHT.dir). No
            # int(state.step) here — syncing a poisoned device state in
            # a crash path can hang; FLIGHT already tracks last_step.
            FLIGHT.record("train.crash",
                          error=f"{type(e).__name__}: {e}")
            FLIGHT.dump(reason=f"train.crash:{type(e).__name__}")
            raise

    def _flops_per_token(self, steps: int, tokens: int) -> float:
        """Analytic FLOPs model when configured, else derived from the
        newest XLA cost_analysis estimate of the instrumented step
        (flops-per-call × steps ÷ tokens over the logging window)."""
        if self.args.flops_per_token:
            return self.args.flops_per_token
        fpc = getattr(self._step_fn, "flops_per_call", 0.0)
        if fpc and steps and tokens:
            return fpc * steps / tokens
        return 0.0

    def _fit_sync(self, data_iter, eval_fn: Optional[Callable] = None):
        args = self.args
        mgr = (CheckpointManager(args.ckpt_dir, async_save=args.async_ckpt)
               if args.ckpt_every else None)
        accum = args.grad_accum_steps
        t_last = time.perf_counter()
        tokens_since = 0
        steps_since = 0
        start_step = int(self.state.step)
        if start_step >= args.max_steps:
            return self.state       # already done — consume nothing
        it = iter(data_iter)
        if start_step and args.resume_reskip:
            # align a FRESH stream with the restored step counter — without
            # this a resumed run re-trains the first batches and never sees
            # the tail. Pass resume_reskip=False if the iterator is already
            # positioned.
            for _ in range(start_step * accum):
                next(it)
        for _ in range(start_step, args.max_steps):
            # chaos hooks: train.step may raise (→ elastic restart) or
            # stall (→ StallWatchdog trip); train.loss overrides the host
            # loss value (NaN-storm injection without poisoning data)
            fault_point("train.step", step=int(self.state.step),
                        trainer=self)
            t_step = time.monotonic()
            with _span("train.loop", step=int(self.state.step)):
                micro = [self._to_batch(next(it)) for _ in range(accum)]
                self.state, loss = self._step_fn(self.state, *micro)
                if self.watchdog is not None:
                    self.watchdog.poke()   # raises WatchdogTrip if stalled
                step_no = int(self.state.step)
                # the float() fetch blocks on the device step, so the
                # histogram sees real step latency, not dispatch latency
                loss_val = fault_value("train.loss", float(loss),
                                       step=step_no)
            _STEP_S.observe(time.monotonic() - t_step)
            _STEPS.inc()
            _LOSS.set(loss_val)
            FLIGHT.record("train.step", step=step_no, loss=loss_val)

            if args.nan_guard:
                if not np.isfinite(loss_val):
                    # the in-graph guard already kept the params/opt state
                    # of the poisoned update; here we count, back off, and
                    # eventually trip into the elastic restart path
                    self._bad_steps += 1
                    self.stats["nan_skips"] += 1
                    _NAN_SKIPS.inc()
                    FLIGHT.record("train.nan_skip", step=step_no,
                                  streak=self._bad_steps)
                    self.stats["bad_streak_max"] = max(
                        self.stats["bad_streak_max"], self._bad_steps)
                    if self._bad_steps >= args.max_bad_steps:
                        from paddle_tpu.utils.watchdog import WatchdogTrip
                        FLIGHT.record("train.giveup", step=step_no,
                                      streak=self._bad_steps)
                        raise WatchdogTrip(
                            f"{self._bad_steps} consecutive non-finite losses")
                    if args.nan_backoff_s > 0:
                        _NAN_BACKOFF.inc()
                        FLIGHT.record("train.nan_backoff", step=step_no,
                                      streak=self._bad_steps)
                        time.sleep(min(
                            args.nan_backoff_s * 2 ** (self._bad_steps - 1),
                            args.nan_backoff_cap_s))
                else:
                    self._bad_steps = 0

            steps_since += 1
            tokens_since += sum(int(np.prod(b[0].shape[:2])) for b in micro
                                if hasattr(b[0], "shape") and b[0].ndim >= 2)
            if args.log_every and step_no % args.log_every == 0:
                now = time.perf_counter()
                dt = now - t_last
                rec = {"step": step_no, "loss": loss_val,
                       "steps_per_sec": args.log_every / dt if dt > 0 else 0.0,
                       "lr": self.optimizer.get_lr(self.state.opt_state)}
                fpt = self._flops_per_token(steps_since, tokens_since)
                if fpt and tokens_since and dt > 0:
                    rec["tokens_per_sec"] = tokens_since / dt
                    # one MFU model for trainer and StepTimer:
                    # the shared gauges in observability.flops
                    rec["mfu"] = record_throughput(
                        tokens_since / dt, fpt, args.peak_flops)
                self.history.append(rec)
                for h in self.hooks:
                    h(rec)
                t_last, tokens_since, steps_since = now, 0, 0
            if mgr and step_no % args.ckpt_every == 0:
                mgr.save(step_no, self.state)
            if eval_fn and args.log_every and step_no % (args.log_every * 10) == 0:
                eval_fn(self.state.model)
        if mgr is not None:
            mgr.wait()     # async mode: "fit returned" implies durable
        return self.state

    # ------------------------------------------------- pipelined fit path
    def _fit_pipelined(self, data_iter, eval_fn: Optional[Callable] = None):
        """The deferred-sync loop. Invariants vs the synchronous path:

        * the DISPATCH sequence (batch order, jitted calls, donation
          chain) is identical, so per-step losses are bit-identical;
        * every host decision that needs a loss value happens at drain
          time, attributed to the step that produced it — a host step
          mirror tracks the in-graph counter (which does NOT advance on
          a non-finite loss when nan_guard holds the update);
        * log/ckpt/eval fire only with the window empty, so they see
          exactly the state the synchronous loop would have seen.
        """
        args = self.args
        depth = args.pipeline_depth
        mgr = (CheckpointManager(args.ckpt_dir, async_save=args.async_ckpt)
               if args.ckpt_every else None)
        accum = args.grad_accum_steps
        start_step = int(self.state.step)
        if start_step >= args.max_steps:
            return self.state
        it = iter(data_iter)
        if start_step and args.resume_reskip:
            for _ in range(start_step * accum):
                next(it)

        window: deque = deque()   # (loss_handle, t_dispatch, n_tokens)
        drained = start_step      # host mirror of the device step counter
        last_loss = float("nan")
        t_last = time.perf_counter()
        tokens_since = 0
        steps_since = 0
        # host input/dispatch seconds that rode in the shadow of in-flight
        # device steps this logging window — the overlap-aware MFU
        # (ROADMAP leftover) subtracts them from the wall-clock window
        hidden_host_s = 0.0
        boundary_done = start_step   # last step boundary actions ran for

        def is_boundary(s: int) -> bool:
            if s <= boundary_done:
                return False
            return ((args.log_every and s % args.log_every == 0)
                    or (mgr and s % args.ckpt_every == 0)
                    or (eval_fn is not None and args.log_every
                        and s % (args.log_every * 10) == 0))

        def drain_one():
            nonlocal drained, last_loss, tokens_since, steps_since
            loss, t_disp, ntok = window.popleft()
            with _span("train.drain", step=drained + 1,
                       inflight=len(window) + 1):
                raw = float(loss)         # blocks until the step executed
            if self.watchdog is not None:
                self.watchdog.poke()      # raises WatchdogTrip if stalled
            # in-graph guard held params/opt/step on a non-finite loss, so
            # the device counter did not move — mirror that on the host
            if (not args.nan_guard) or np.isfinite(raw):
                drained += 1
            step_no = drained
            loss_val = fault_value("train.loss", raw, step=step_no)
            _STEP_S.observe(time.monotonic() - t_disp)
            _STEPS.inc()
            _LOSS.set(loss_val)
            FLIGHT.record("train.step", step=step_no, loss=loss_val)
            last_loss = loss_val
            tokens_since += ntok
            steps_since += 1
            if args.nan_guard:
                if not np.isfinite(loss_val):
                    self._bad_steps += 1
                    self.stats["nan_skips"] += 1
                    _NAN_SKIPS.inc()
                    FLIGHT.record("train.nan_skip", step=step_no,
                                  streak=self._bad_steps)
                    self.stats["bad_streak_max"] = max(
                        self.stats["bad_streak_max"], self._bad_steps)
                    if self._bad_steps >= args.max_bad_steps:
                        from paddle_tpu.utils.watchdog import WatchdogTrip
                        FLIGHT.record("train.giveup", step=step_no,
                                      streak=self._bad_steps)
                        raise WatchdogTrip(
                            f"{self._bad_steps} consecutive non-finite losses")
                    if args.nan_backoff_s > 0:
                        _NAN_BACKOFF.inc()
                        FLIGHT.record("train.nan_backoff", step=step_no,
                                      streak=self._bad_steps)
                        time.sleep(min(
                            args.nan_backoff_s * 2 ** (self._bad_steps - 1),
                            args.nan_backoff_cap_s))
                else:
                    self._bad_steps = 0

        def run_boundaries():
            """Log/ckpt/eval for the (fully drained) current step — same
            order and conditions as the synchronous loop."""
            nonlocal t_last, tokens_since, steps_since, hidden_host_s, \
                boundary_done
            step_no = drained
            if step_no <= boundary_done:
                return
            boundary_done = step_no
            if args.log_every and step_no % args.log_every == 0:
                now = time.perf_counter()
                dt = now - t_last
                rec = {"step": step_no, "loss": last_loss,
                       "steps_per_sec": args.log_every / dt if dt > 0 else 0.0,
                       "lr": self.optimizer.get_lr(self.state.opt_state)}
                fpt = self._flops_per_token(steps_since, tokens_since)
                if fpt and tokens_since and dt > 0:
                    rec["tokens_per_sec"] = tokens_since / dt
                    rec["mfu"] = record_throughput(
                        tokens_since / dt, fpt, args.peak_flops,
                        hidden_host_s=hidden_host_s, window_s=dt)
                self.history.append(rec)
                for h in self.hooks:
                    h(rec)
                t_last, tokens_since, steps_since = now, 0, 0
                hidden_host_s = 0.0
            if mgr and step_no % args.ckpt_every == 0:
                # the window is empty: self.state IS step `step_no`
                mgr.save(step_no, self.state)
            if (eval_fn and args.log_every
                    and step_no % (args.log_every * 10) == 0):
                eval_fn(self.state.model)

        dbuf = args.device_double_buffer
        staged_next = None      # step i+1's microbatches, already on device
        for i in range(start_step, args.max_steps):
            # chaos hook rides the dispatch side (an exception here must
            # reach the elastic restart net immediately); the host step
            # prediction replaces int(state.step), which would sync
            fault_point("train.step", step=drained + len(window),
                        trainer=self)
            in_flight_before = len(window)
            t_disp = time.monotonic()
            with _span("train.loop", step=drained + len(window)):
                if staged_next is not None:
                    micro, staged_next = staged_next, None
                else:
                    micro = [self._to_batch(next(it)) for _ in range(accum)]
                self.state, loss = self._step_fn(self.state, *micro)
            if in_flight_before > 0:
                # host input/dispatch time spent while device steps were
                # already executing — hidden from the critical path
                hidden_host_s += time.monotonic() - t_disp
            ntok = sum(int(np.prod(b[0].shape[:2])) for b in micro
                       if hasattr(b[0], "shape") and b[0].ndim >= 2)
            window.append((loss, t_disp, ntok))
            if dbuf and i + 1 < args.max_steps:
                # the step just dispatched is executing: fetch the NEXT
                # step's batches and start their host->device transfers
                # now so the next dispatch finds them resident. device_put
                # is async — this overlaps transfer with compute.
                t_pf = time.monotonic()
                staged_next = [
                    tuple(jax.device_put(x) for x in self._to_batch(b))
                    for b in [next(it) for _ in range(accum)]]
                hidden_host_s += time.monotonic() - t_pf
            while len(window) > depth:
                drain_one()
            # drain fully when the just-dispatched step lands on a
            # boundary (host prediction — exact unless a NaN is in
            # flight), or when a mid-window drain revealed one
            if is_boundary(drained + len(window)) or is_boundary(drained):
                while window:
                    drain_one()
                run_boundaries()
        while window:
            drain_one()
        run_boundaries()
        if mgr is not None:
            mgr.wait()     # async mode: "fit returned" implies durable
        return self.state

    @staticmethod
    def _to_batch(b):
        if isinstance(b, (tuple, list)):
            return tuple(jnp.asarray(x) for x in b)
        return (jnp.asarray(b),)
