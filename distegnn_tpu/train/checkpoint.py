"""Durable checkpoint save/restore (reference utils/train.py:234-259, main.py:208-220).

Saves {epoch, params, opt_state, losses, config} — the same payload as the
reference's best_model.pth/last_model.pth. Written by process 0 only
(``jax.process_index() == 0``; params are replicated so any host's copy is the
global state — reference does the same with rank 0, SURVEY.md §5.4).

Format: pickle of numpy leaf lists + the pytree re-built from a template at
restore time (so saved files don't depend on optax's internal tree classes
being pickleable across versions). Unlike the reference (whose DDP-wrapped
state_dicts are not portable between world sizes, SURVEY.md §5.4), params here
carry no wrapper prefix — checkpoints are world-size-portable by construction.

Durability layer (docs/ROBUSTNESS.md):
  - every save is tmp-write + fsync + atomic rename, and records a CRC32 +
    size entry in a per-directory ``manifest.json`` (itself written
    atomically), so restore can prove a file intact before unpickling it;
  - truncated/corrupt files surface as a typed :class:`CheckpointCorruptError`
    naming the path, never a bare ``EOFError``/``UnpicklingError``;
  - ``save_checkpoint`` sweeps ``*.tmp`` leftovers of a previously killed
    write out of the directory before writing;
  - step-granular checkpoints (``step_<n>.ckpt``) rotate, keeping the last K
    alongside ``best_model.ckpt``/``last_model.ckpt``/``preempt_model.ckpt``;
  - ``find_resume_checkpoint`` scans a whole log dir, verifies checksums, and
    falls back past corrupt/incompatible files to the newest valid state —
    the ``train.resume: auto`` entry point.
"""

from __future__ import annotations

import glob
import json
import os
import pickle
import re
import zlib
from dataclasses import dataclass, field
from typing import Any, List, Optional

import jax
import numpy as np

from distegnn_tpu import obs

MANIFEST_NAME = "manifest.json"
PREEMPT_MARKER = "PREEMPTED"

# payload keys every intact checkpoint must carry (older checkpoints predate
# step_in_epoch/seed — those stay optional for back-compat)
_REQUIRED_KEYS = ("epoch", "params_leaves", "opt_state_leaves", "step")

# unpickle failure modes of a torn/garbled file — anything else (e.g. a
# genuine OSError opening the file) propagates untouched
_UNPICKLE_ERRORS = (EOFError, pickle.UnpicklingError, AttributeError,
                    ImportError, IndexError, MemoryError, TypeError,
                    ValueError)


class CheckpointCorruptError(RuntimeError):
    """A checkpoint file failed verification (CRC/size mismatch against its
    manifest entry, truncated pickle, or missing payload keys)."""

    def __init__(self, path: str, reason: str):
        super().__init__(f"corrupt checkpoint {path}: {reason}")
        self.path = path
        self.reason = reason
        # every detected corruption lands on the obs fault timeline (no-op
        # when no sink is configured) — raise sites are many, this is one
        obs.event("ckpt/corrupt", path=os.path.basename(path), reason=reason)


@dataclass
class RestoredRun:
    """Everything a resumed run needs to replay the schedule exactly: the
    train state, how many epochs completed, how many steps of the NEXT epoch
    already applied (mid-epoch cadence/preempt saves), and the seed the run
    was started with (PRNG keys derive from (seed, epoch, step), so carrying
    the seed lets resume detect a mismatched --seed override)."""

    state: Any
    epoch: int
    step_in_epoch: int = 0
    losses: dict = field(default_factory=dict)
    seed: Optional[int] = None
    path: Optional[str] = None


def _mesh_of(config) -> Optional[dict]:
    """The (data, graph, tensor) mesh shape recorded in a config, as plain
    ints, or None when the config predates / doesn't carry one. Tolerant of
    both ConfigDict and plain-dict payload configs."""
    if not isinstance(config, dict):
        return None
    mesh = (config.get("parallel") or {}).get("mesh")
    if not isinstance(mesh, dict):
        return None
    try:
        return {k: int(mesh.get(k) or 1) for k in ("data", "graph", "tensor")}
    except (TypeError, ValueError):
        return None


def check_mesh_restore_compat(payload: dict, config=None) -> None:
    """Cross-mesh restore gate: a checkpoint written under mesh A restores
    under mesh B. Params are saved FULL (never tensor-sliced — the TP layers
    slice replicated weights at compute time), so the param tree is invariant
    in the mesh shape and 'resharding' is a plain load. The one real
    constraint is that the RESTORING mesh's tensor degree must still divide
    the saved model's hidden width; violations raise a typed ValueError here
    instead of surfacing as a shape error deep inside shard_map."""
    saved_mesh = payload.get("mesh") or _mesh_of(payload.get("config"))
    target_mesh = _mesh_of(config)
    if target_mesh is None:
        return
    tp = target_mesh["tensor"]
    saved_cfg = payload.get("config") or {}
    model_cfg = saved_cfg.get("model") if isinstance(saved_cfg, dict) else None
    hidden = (model_cfg or {}).get("hidden_nf")
    if tp > 1 and hidden is not None and int(hidden) % tp != 0:
        raise ValueError(
            f"checkpoint incompatible with mesh: saved hidden_nf={hidden} is "
            f"not divisible by restoring parallel.mesh.tensor={tp}")
    if saved_mesh is not None and saved_mesh != target_mesh:
        obs.event("ckpt/reshard", saved=saved_mesh, target=target_mesh)
        obs.log(f"restore: resharding checkpoint saved under mesh {saved_mesh} "
                f"onto mesh {target_mesh} (params are full/replicated — "
                "plain load)")


class ResumeConsensusError(RuntimeError):
    """Multi-host resume diverged: hosts adopted different (epoch,
    step_in_epoch) coordinates from their local filesystem views. Carries
    enough structure for tooling (and the operator) to see WHO is behind:

    - ``coords``: [(epoch, step_in_epoch)] per process index;
    - ``lagging``: process indices whose coordinates trail the newest view
      (the hosts whose checkpoint directory is stale);
    - ``local_path``: the checkpoint THIS process resolved (one concrete
      path to diff against the lagging hosts' directories).
    """

    def __init__(self, coords, lagging, local_path=None):
        self.coords = [tuple(int(v) for v in row) for row in coords]
        self.lagging = sorted(int(i) for i in lagging)
        self.local_path = local_path
        latest = max(self.coords)
        views = ", ".join(
            f"process {i}: epoch={e} step_in_epoch={s}"
            for i, (e, s) in enumerate(self.coords))
        behind = ", ".join(f"process {i}" for i in self.lagging)
        where = (f" (this process resolved {local_path!r})"
                 if local_path else "")
        super().__init__(
            f"resume consensus failure: {behind} lag(s) behind the newest "
            f"view epoch={latest[0]} step_in_epoch={latest[1]} — a "
            f"half-propagated checkpoint directory on the lagging host(s) "
            f"is the usual cause. Views: {views}{where}. Propagate the "
            "same state_dict/ contents to every host, then relaunch.")


def verify_resume_consensus(epoch: int, step_in_epoch: int,
                            allgather=None, path: Optional[str] = None) -> None:
    """Multi-host coordinated-restore barrier (closes the docs/ROBUSTNESS.md
    'Known gap'): each process resolves its resume checkpoint independently
    from its own filesystem view, so a half-propagated checkpoint directory
    (NFS lag, partial rsync) can leave hosts resuming from DIFFERENT steps —
    silently corrupting gradient averaging, since psum assumes every host
    holds the same params. After restore, every process publishes the
    (epoch, step_in_epoch) it adopted; any disagreement fails loudly here,
    before a single step runs.

    ``allgather`` is injectable for single-process tests: a callable taking
    the local ``np.ndarray([epoch, step_in_epoch])`` and returning the
    [n_process, 2] stack. Default uses
    ``jax.experimental.multihost_utils.process_allgather``; single-process
    runs with the default are a no-op. ``path`` is the resume checkpoint
    THIS process resolved — it rides the typed error so the operator has a
    concrete path to diff against the lagging hosts."""
    if allgather is None:
        if jax.process_count() == 1:
            return
        from jax.experimental import multihost_utils

        def allgather(x):
            return np.asarray(multihost_utils.process_allgather(x))

    local = np.asarray([int(epoch), int(step_in_epoch)], dtype=np.int64)
    coords = np.asarray(allgather(local)).reshape(-1, 2)
    uniq = {tuple(int(v) for v in row) for row in coords}
    obs.event("resume/consensus", epoch=int(epoch),
              step_in_epoch=int(step_in_epoch), n_views=len(uniq))
    if len(uniq) > 1:
        latest = max(uniq)
        lagging = [i for i, row in enumerate(coords)
                   if (int(row[0]), int(row[1])) < latest]
        obs.event("resume/consensus_failure", lagging=lagging,
                  latest=list(latest),
                  views=[[int(v) for v in row] for row in coords])
        raise ResumeConsensusError(coords, lagging, local_path=path)


def _to_leaves(tree) -> list:
    return [np.asarray(x) for x in jax.tree.leaves(tree)]


def _from_leaves(template, leaves: list):
    treedef = jax.tree.structure(template)
    tmpl_leaves = jax.tree.leaves(template)
    leaves = [np.asarray(l) for l in leaves]
    if len(leaves) != len(tmpl_leaves):
        raise ValueError(
            f"checkpoint incompatible with model: {len(leaves)} saved arrays vs "
            f"{len(tmpl_leaves)} expected — was the checkpoint written by a "
            "different architecture/config (e.g. hoist_edge_mlp flipped)?")
    for i, (saved, want) in enumerate(zip(leaves, tmpl_leaves)):
        if tuple(saved.shape) != tuple(np.shape(want)):
            raise ValueError(
                f"checkpoint incompatible with model: array {i} has shape "
                f"{tuple(saved.shape)}, model expects {tuple(np.shape(want))} — "
                "was the checkpoint written by a different architecture/config "
                "(e.g. hoist_edge_mlp flipped)?")
    return jax.tree.unflatten(treedef, leaves)


# ---- manifest --------------------------------------------------------------

def _manifest_path(ckpt_dir: str) -> str:
    return os.path.join(ckpt_dir, MANIFEST_NAME)


def read_manifest(ckpt_dir: str) -> dict:
    """{basename: {crc32, size, epoch, step, step_in_epoch, time}} — empty on
    a missing or unparseable manifest (the manifest is an integrity aid, not
    a dependency: restore still works without it)."""
    try:
        with open(_manifest_path(ckpt_dir)) as f:
            m = json.load(f)
        return m if isinstance(m, dict) else {}
    except (OSError, json.JSONDecodeError):
        return {}


def _write_manifest(ckpt_dir: str, manifest: dict) -> None:
    tmp = _manifest_path(ckpt_dir) + ".manifest.tmp"
    with open(tmp, "w") as f:
        json.dump(manifest, f, indent=2, sort_keys=True)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, _manifest_path(ckpt_dir))


def _sweep_stale_tmps(ckpt_dir: str) -> None:
    """Remove ``*.tmp`` leftovers of a previous killed write. Safe by
    construction: a live save holds no .tmp across calls (tmp → rename is one
    call), and process 0 is the only writer."""
    for stale in glob.glob(os.path.join(ckpt_dir, "*.tmp")):
        try:
            os.remove(stale)
            obs.log(f"checkpoint: removed stale partial write {stale}")
        except OSError:
            pass


# ---- save ------------------------------------------------------------------

def save_checkpoint(path: str, state, epoch: int, losses: Optional[dict] = None,
                    config: Optional[dict] = None, seed: Optional[int] = None,
                    step_in_epoch: int = 0) -> None:
    """Atomically write one checkpoint + its CRC manifest entry.

    ``epoch`` counts COMPLETED epochs; ``step_in_epoch`` counts steps of
    epoch ``epoch + 1`` already applied to ``state`` (0 = epoch boundary) —
    a resumed run replays the schedule from exactly there."""
    if jax.process_index() != 0:
        return
    import time as _time

    t0 = _time.perf_counter()
    payload = {
        "epoch": int(epoch),
        "params_leaves": _to_leaves(state.params),
        "opt_state_leaves": _to_leaves(state.opt_state),
        "step": int(state.step),
        "step_in_epoch": int(step_in_epoch),
        "seed": None if seed is None else int(seed),
        "losses": losses or {},
        "config": config,
        # the (data, graph, tensor) shape this run trained under — restore
        # under any other shape is legal (params are full), the metadata
        # feeds the reshard log + compat check (check_mesh_restore_compat)
        "mesh": _mesh_of(config),
    }
    ckpt_dir = os.path.dirname(path) or "."
    os.makedirs(ckpt_dir, exist_ok=True)
    _sweep_stale_tmps(ckpt_dir)
    blob = pickle.dumps(payload)
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        f.write(blob)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)  # atomic: a crash never leaves a torn checkpoint
    manifest = read_manifest(ckpt_dir)
    manifest[os.path.basename(path)] = {
        "crc32": zlib.crc32(blob) & 0xFFFFFFFF,
        "size": len(blob),
        "epoch": int(epoch),
        "step": int(state.step),
        "step_in_epoch": int(step_in_epoch),
        "time": _time.time(),
    }
    # drop entries whose files are gone (rotation, manual cleanup)
    manifest = {k: v for k, v in manifest.items()
                if os.path.exists(os.path.join(ckpt_dir, k))}
    _write_manifest(ckpt_dir, manifest)
    obs.event("ckpt/save", path=os.path.basename(path), epoch=int(epoch),
              bytes=len(blob), dur_s=round(_time.perf_counter() - t0, 6))


_STEP_RE = re.compile(r"^step_(\d+)\.ckpt$")


def step_checkpoint_name(step: int) -> str:
    return f"step_{int(step):010d}.ckpt"


def rotate_checkpoints(ckpt_dir: str, keep: int) -> List[str]:
    """Keep the newest ``keep`` step-granular checkpoints (by step number);
    ``best_model``/``last_model``/``preempt_model`` never rotate. Returns the
    removed paths. Manifest entries for removed files are dropped on the next
    save (see save_checkpoint's existence filter)."""
    if jax.process_index() != 0:
        return []
    steps = []
    for p in glob.glob(os.path.join(ckpt_dir, "step_*.ckpt")):
        m = _STEP_RE.match(os.path.basename(p))
        if m:
            steps.append((int(m.group(1)), p))
    steps.sort()
    removed = []
    for _, p in steps[:max(0, len(steps) - max(int(keep), 1))]:
        try:
            os.remove(p)
            removed.append(p)
        except OSError:
            pass
    if steps:
        # rotation was silent before the promotion conveyor landed; the
        # event makes publish latency attributable in obs_report waterfalls
        # (ckpt/save -> ckpt/rotate -> promote/publish)
        newest_step, newest_path = steps[-1]
        try:
            newest_bytes = os.path.getsize(newest_path)
        except OSError:
            newest_bytes = -1
        obs.event("ckpt/rotate", step=newest_step, bytes=newest_bytes,
                  kept=min(len(steps) - len(removed), max(int(keep), 1)),
                  removed=len(removed))
    return removed


# ---- verify + restore ------------------------------------------------------

def verify_checkpoint(path: str) -> dict:
    """Read + integrity-check one checkpoint file; returns the payload.
    Raises CheckpointCorruptError on CRC/size mismatch vs the directory
    manifest, torn pickle, or missing payload keys."""
    try:
        with open(path, "rb") as f:
            blob = f.read()
    except FileNotFoundError:
        raise CheckpointCorruptError(path, "file missing") from None
    entry = read_manifest(os.path.dirname(path) or ".").get(os.path.basename(path))
    if entry is not None:
        if len(blob) != int(entry.get("size", -1)):
            raise CheckpointCorruptError(
                path, f"size {len(blob)} != manifest {entry.get('size')} "
                      "(truncated or partially-written file)")
        if (zlib.crc32(blob) & 0xFFFFFFFF) != int(entry.get("crc32", -1)):
            raise CheckpointCorruptError(
                path, "CRC32 mismatch vs manifest (bit-rot or torn write)")
    try:
        payload = pickle.loads(blob)
    except _UNPICKLE_ERRORS as e:
        raise CheckpointCorruptError(path, f"unpickle failed: {e!r}") from None
    if not isinstance(payload, dict) or any(k not in payload for k in _REQUIRED_KEYS):
        raise CheckpointCorruptError(path, "payload missing required keys")
    return payload


def _with_config_hint(payload, e: ValueError) -> ValueError:
    saved_cfg = payload.get("config") or {}
    model_cfg = saved_cfg.get("model") if isinstance(saved_cfg, dict) else None
    hint = (f"; the checkpoint was written with model config {model_cfg}"
            if model_cfg else "")
    return ValueError(f"{e}{hint}")


def restore_for_resume(path: str, state, config=None) -> RestoredRun:
    """Verified restore into the structure of ``state`` (a freshly-created
    TrainState), carrying the resume coordinates (epoch, step_in_epoch, seed).
    The optimizer configuration must match the one the checkpoint was written
    with (grad-accumulation wrapping changes the opt-state tree);
    evaluation-only consumers should use :func:`restore_params` instead.
    With ``config`` given, the checkpoint's recorded mesh is checked against
    the restoring mesh (:func:`check_mesh_restore_compat`)."""
    import time as _time

    t0 = _time.perf_counter()
    payload = verify_checkpoint(path)
    if config is not None:
        check_mesh_restore_compat(payload, config)
    from distegnn_tpu.train.step import TrainState

    try:
        restored = TrainState(
            params=_from_leaves(state.params, payload["params_leaves"]),
            opt_state=_from_leaves(state.opt_state, payload["opt_state_leaves"]),
            step=np.int32(payload["step"]),
        )
    except ValueError as e:
        raise _with_config_hint(payload, e) from None
    obs.event("ckpt/restore", path=os.path.basename(path),
              epoch=int(payload["epoch"]),
              bytes=int(os.path.getsize(path)) if os.path.exists(path) else 0,
              dur_s=round(_time.perf_counter() - t0, 6))
    return RestoredRun(
        state=restored,
        epoch=int(payload["epoch"]),
        step_in_epoch=int(payload.get("step_in_epoch", 0) or 0),
        losses=payload.get("losses", {}) or {},
        seed=payload.get("seed"),
        path=path,
    )


def restore_checkpoint(path: str, state, config=None) -> tuple[Any, int, dict]:
    """Back-compat wrapper over :func:`restore_for_resume`: returns
    (state, start_epoch, losses)."""
    r = restore_for_resume(path, state, config=config)
    return r.state, r.epoch, r.losses


def restore_params(path: str, params) -> Any:
    """Params-only restore for evaluation/rollout: ignores the saved
    optimizer state, so a checkpoint written with ANY optimizer wrapping
    (grad accumulation, schedules) loads into a bare model."""
    payload = verify_checkpoint(path)
    try:
        return _from_leaves(params, payload["params_leaves"])
    except ValueError as e:
        raise _with_config_hint(payload, e) from None


# ---- auto-resume scan ------------------------------------------------------

def scan_resume_candidates(log_dir: str) -> List[str]:
    """All checkpoints under ``<log_dir>/<exp>/state_dict/`` (and a bare
    ``<log_dir>/state_dict/``), newest first by mtime — exp dirs are
    timestamped per run, so a preemption's ``preempt_model.ckpt`` (written at
    death) naturally sorts first."""
    pats = [os.path.join(log_dir, "*", "state_dict", "*.ckpt"),
            os.path.join(log_dir, "state_dict", "*.ckpt")]
    hits = [p for pat in pats for p in glob.glob(pat)]
    return sorted(hits, key=lambda p: os.path.getmtime(p), reverse=True)


def peek_resume_seed(log_dir: str):
    """(seed, path) of the newest checksum-valid checkpoint under ``log_dir``,
    or (None, None). Called BEFORE the model/loaders exist — a resumed run
    must adopt the original run's seed before anything derives from it (loader
    permutations, PRNG folds), and the full architecture-checked restore can
    only happen once a template TrainState exists."""
    for path in scan_resume_candidates(log_dir):
        try:
            payload = verify_checkpoint(path)
        except CheckpointCorruptError:
            continue
        return payload.get("seed"), path
    return None, None


def find_resume_checkpoint(log_dir: str, state, config=None) -> Optional[RestoredRun]:
    """``train.resume: auto``: scan the experiment log dir, verify checksums,
    and restore the NEWEST valid checkpoint — falling back past corrupt /
    truncated / architecture-incompatible files with a printed diagnosis.
    Returns None when nothing under ``log_dir`` restores (fresh start)."""
    for path in scan_resume_candidates(log_dir):
        try:
            return restore_for_resume(path, state, config=config)
        except CheckpointCorruptError as e:
            obs.log(f"resume: skipping {path} ({e.reason})")
        except ValueError as e:
            obs.log(f"resume: skipping incompatible {path} ({e})")
    return None


def adopt_resume_seed(config) -> None:
    """With ``train.resume`` set, adopt the seed of the checkpoint we are
    about to resume BEFORE anything derives from ``config.seed`` (loader
    permutations and per-step PRNG keys fold (seed, epoch, step) — replaying
    the schedule exactly requires the original seed, not a drifted default)."""
    resume = config.train.get("resume")
    if not resume:
        return
    if resume == "auto":
        seed, path = peek_resume_seed(config.log.log_dir)
    else:
        try:
            seed, path = verify_checkpoint(resume).get("seed"), resume
        except CheckpointCorruptError:
            return  # resolve_resume raises the loud, typed error
    if seed is not None and int(seed) != int(config.seed):
        obs.log(f"resume: adopting seed {seed} from {path} (config had "
                f"{config.seed}) so the resumed run replays the schedule")
        config.seed = int(seed)


def resolve_resume(config, state) -> Optional[RestoredRun]:
    """The ``train.resume`` entry point (main.py / parallel/launch.py):
    'auto' scans ``log.log_dir`` and falls back past corrupt files; an
    explicit path fails loudly. Returns a RestoredRun or None (fresh start)."""
    resume = config.train.get("resume")
    if not resume:
        return None
    if resume == "auto":
        rr = find_resume_checkpoint(config.log.log_dir, state, config=config)
        if rr is None:
            obs.log("resume: auto found no valid checkpoint under "
                    f"{config.log.log_dir}; starting fresh")
        return rr
    return restore_for_resume(resume, state, config=config)


def write_preempt_marker(ckpt_dir: str, ckpt_name: str, epoch: int,
                         step_in_epoch: int) -> None:
    """Drop the resumable marker a wrapper script can key off: the run
    exited on purpose mid-training and the named checkpoint continues it."""
    if jax.process_index() != 0:
        return
    import time as _time

    tmp = os.path.join(ckpt_dir, PREEMPT_MARKER + ".tmp")
    with open(tmp, "w") as f:
        json.dump({"checkpoint": ckpt_name, "epoch": int(epoch),
                   "step_in_epoch": int(step_in_epoch),
                   "time": _time.time()}, f)
    os.replace(tmp, os.path.join(ckpt_dir, PREEMPT_MARKER))


def clear_preempt_marker(ckpt_dir: str) -> None:
    try:
        os.remove(os.path.join(ckpt_dir, PREEMPT_MARKER))
    except OSError:
        pass
